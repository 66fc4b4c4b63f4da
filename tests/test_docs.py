"""The operator guides stay in lock-step with the code they document.

``docs/serving.md`` must mention every public ``EngineConfig`` and
``WorkloadSpec`` field by its backticked name — adding a knob without
documenting it fails here, as does documenting a knob that no longer
exists (stale backticked ``field (--flag)`` table rows).

``docs/observability.md`` is diffed against the obs catalog in *both*
directions: every declared metric and span must be documented, and every
backticked name in a metric/span namespace must still be declared.
"""
import dataclasses
import pathlib
import re

from repro import obs
from repro.serve.engine import EngineConfig
from repro.serve.request import WorkloadSpec

DOCS = pathlib.Path(__file__).resolve().parents[1] / "docs"
DOC = DOCS / "serving.md"
OBS_DOC = DOCS / "observability.md"

# metric names and span names live in disjoint dotted namespaces (see
# repro/obs/catalog.py) so a backticked token can be classified by prefix;
# tokens with wildcards (`serve.engine.*`) or paths (`a/b`) never match
_METRIC_TOKEN = re.compile(
    r"^(?:ft|statexfer|serve|train|kernels|incidents)\.[a-z0-9_.]+$"
)
_SPAN_TOKEN = re.compile(
    r"^(?:trainer|controller|snapshot|reshard|engine|router|kernel|lowrank)"
    r"\.[a-z0-9_]+$"
)


def _documented_names():
    text = DOC.read_text()
    return set(re.findall(r"`([A-Za-z_][A-Za-z0-9_]*)`", text)), text


def test_every_engine_config_field_is_documented():
    names, _ = _documented_names()
    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    missing = fields - names
    assert not missing, (
        f"EngineConfig fields missing from docs/serving.md: {sorted(missing)}"
    )


def test_every_workload_spec_field_is_documented():
    names, _ = _documented_names()
    fields = {f.name for f in dataclasses.fields(WorkloadSpec)}
    missing = fields - names
    assert not missing, (
        f"WorkloadSpec fields missing from docs/serving.md: {sorted(missing)}"
    )


def test_documented_knob_rows_still_exist():
    """Every `field` at the start of a knob-table row must still be a real
    dataclass field — catches docs rotting after a rename."""
    _, text = _documented_names()
    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    fields |= {f.name for f in dataclasses.fields(WorkloadSpec)}
    knob_sections = text.split("## Priority admission")[0]
    rows = re.findall(r"^\| `([A-Za-z_][A-Za-z0-9_]*)`", knob_sections, re.M)
    assert rows, "knob tables not found — did the doc headings move?"
    stale = [r for r in rows if r not in fields]
    assert not stale, f"stale knob rows in docs/serving.md: {stale}"


def test_doc_mentions_every_serve_event_kind():
    from repro.serve.trace import EVENT_KINDS

    names, _ = _documented_names()
    missing = set(EVENT_KINDS) - names
    assert not missing, (
        f"serve event kinds missing from docs/serving.md: {sorted(missing)}"
    )


# -- docs/observability.md <-> repro.obs.catalog ---------------------------

def _obs_doc_tokens():
    text = OBS_DOC.read_text()
    return set(re.findall(r"`([^`\n]+)`", text))


def test_obs_doc_documents_every_declared_metric():
    tokens = _obs_doc_tokens()
    missing = set(obs.declared_names()) - tokens
    assert not missing, (
        f"metrics missing from docs/observability.md: {sorted(missing)}"
    )


def test_obs_doc_has_no_stale_metric_names():
    documented = {t for t in _obs_doc_tokens() if _METRIC_TOKEN.match(t)}
    stale = documented - set(obs.declared_names())
    assert not stale, (
        f"docs/observability.md names undeclared metrics: {sorted(stale)}"
    )


def test_obs_doc_documents_every_span():
    tokens = _obs_doc_tokens()
    missing = set(obs.SPANS) - tokens
    assert not missing, (
        f"spans missing from docs/observability.md: {sorted(missing)}"
    )


def test_obs_doc_has_no_stale_span_names():
    documented = {t for t in _obs_doc_tokens() if _SPAN_TOKEN.match(t)}
    stale = documented - set(obs.SPANS)
    assert not stale, (
        f"docs/observability.md names undeclared spans: {sorted(stale)}"
    )


# -- incident pipeline: record schema, detectors, paths --------------------

def _obs_doc_section(heading):
    text = OBS_DOC.read_text()
    m = re.search(rf"^###? {re.escape(heading)}$(.*?)(?=^###? |\Z)",
                  text, re.M | re.S)
    assert m, f"docs/observability.md section {heading!r} not found"
    return m.group(1)


def test_incident_record_schema_table_matches_pinned_fields():
    """The schema table's pinned/unpinned split IS the code's split —
    both directions: every PINNED_INCIDENT_FIELDS member must be a `yes`
    row, and no extra field may claim to be pinned."""
    section = _obs_doc_section("Incident record schema")
    rows = re.findall(r"^\| `([a-z_]+)` \| (yes|no) \|", section, re.M)
    assert rows, "incident record schema table not found"
    pinned = {name for name, flag in rows if flag == "yes"}
    assert pinned == set(obs.PINNED_INCIDENT_FIELDS), (
        f"schema table pinned rows != PINNED_INCIDENT_FIELDS: "
        f"{sorted(pinned ^ set(obs.PINNED_INCIDENT_FIELDS))}"
    )
    # every unpinned frame field is documented as such
    tokens = _obs_doc_tokens()
    missing = set(obs.UNPINNED_FRAME_FIELDS) - tokens
    assert not missing, f"unpinned frame fields undocumented: {missing}"


def test_detector_table_matches_declared_detectors():
    """Two-way: the detector-rules table names exactly the detectors the
    code ships (repro.obs.DETECTORS)."""
    section = _obs_doc_section("Anomaly detectors")
    rows = set(re.findall(r"^\| `([a-z_]+)` \|", section, re.M))
    assert rows == set(obs.DETECTORS), (
        f"detector table != DETECTORS: {sorted(rows ^ set(obs.DETECTORS))}"
    )


def test_every_recovery_path_is_documented():
    from repro.obs.incidents import PATHS

    tokens = _obs_doc_tokens()
    missing = set(PATHS) - tokens
    assert not missing, (
        f"recovery paths missing from docs/observability.md: "
        f"{sorted(missing)}"
    )


# -- adaptive recovery policy: decision schema, prior table ----------------

def test_policy_decision_schema_table_matches_record_fields():
    """Two-way: the decision + candidate schema tables name exactly the
    fields the engine emits, and every row is documented as pinned —
    the whole record is replay-verified."""
    from repro.ft.policy import CANDIDATE_FIELDS, DECISION_FIELDS

    section = _obs_doc_section("Adaptive recovery policy")
    rows = re.findall(r"^\| `([a-z_]+)` \| (yes|no) \|", section, re.M)
    assert rows, "policy decision schema tables not found"
    documented = {name for name, _ in rows}
    expected = set(DECISION_FIELDS) | set(CANDIDATE_FIELDS)
    assert documented == expected, (
        f"decision schema rows != DECISION_FIELDS + CANDIDATE_FIELDS: "
        f"{sorted(documented ^ expected)}"
    )
    unpinned = [name for name, flag in rows if flag != "yes"]
    assert not unpinned, (
        f"policy decision fields documented as unpinned: {unpinned}"
    )


def test_policy_prior_table_matches_committed_priors():
    """Two-way, values included: the documented prior table IS the
    committed PRIORS cold-start table."""
    from repro.ft.policy import PRIORS

    section = _obs_doc_section("Adaptive recovery policy")
    num = r"([0-9][0-9e.+]*)"
    rows = re.findall(
        rf"^\| `([a-z_]+)` \| {num} \| {num} \| {num} \|", section, re.M
    )
    assert rows, "policy prior table not found"
    documented = {
        path: {"lost_steps": float(a), "transfer_bytes": float(b),
               "replayed_tokens": float(c)}
        for path, a, b, c in rows
    }
    assert documented == PRIORS, (
        f"prior table != repro.ft.policy.PRIORS: "
        f"{sorted(set(documented) ^ set(PRIORS))} / value drift in "
        f"{[p for p in documented if p in PRIORS and documented[p] != PRIORS[p]]}"
    )


def test_policy_doc_mentions_every_reason_and_mode():
    """The decision vocabulary (reasons, modes, the --ft-policy grammar)
    stays documented."""
    from repro.ft.policy import POLICY_MODES

    tokens = _obs_doc_tokens()
    reasons = {"fixed", "fixed:fallback", "only_valid",
               "adaptive:measured", "adaptive:prior"}
    missing = (reasons | set(POLICY_MODES) | {"--ft-policy"}) - tokens
    assert not missing, (
        f"policy vocabulary missing from docs/observability.md: "
        f"{sorted(missing)}"
    )
