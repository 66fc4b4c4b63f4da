"""Finds a cell's files by the names ``BENCHMARK.json`` gives them.

A cell ``<config>.<traffic>`` joins ``configs/<config>.json`` (the model
configuration as it is run) and ``traffic/<traffic>.json`` (the traffic mix
and the limits of the check).  The traffic's ``kind`` names the driver,
``drivers/<kind>.py``.  A per-layer metric ``<name>`` is read by
``metrics/<name>.py``.  New cells, mixes and metrics are new files.

A configuration file states the program's model field by field: its
``repro_config`` names the program's registry entry, and each entry
``field: file key`` of its ``harness_keys`` sets that field of the
program's ``ModelConfig`` to the file's value.  A dotted field reaches
into a sub-config (``moe.n_experts``, ``moe.top_k``, ``moe.d_ff_expert``,
``moe.every``, ``moe.offset``, ``ssm.d_state``, ``ssm.expand``,
``ssm.head_dim``, ``ssm.chunk``, ...); top-level fields such as ``family``,
``attn_every``, ``attn_offset`` and ``ffn_act`` are set the same way.  The
same map, keyed by field, is the sizes map ``m`` that ``flops.py``, the
metric readers and the reference read.

The file's ``reference`` names the plain reference, ``<reference>.py`` in
this directory (``refmodel`` where the file names none).  A reference
imports nothing of the program and gives:

* ``accept(m)``: raises ``ValueError`` for sizes it does not compute; the
  drivers call it at set-up, so such a file stops before the window;
* ``init_weights(m, seed)``: ``{leaf name: array}`` in the configuration's
  dtype, the leaf names those of ``program.named_leaves`` of the program's
  parameters (``layers/0/ffn/w_up``);
* ``run_training(m, job, seed, batches, keep, weight, quant=None)``: the
  first ``len(batches)`` steps, as ``{"losses": [...], "grad0": {leaf:
  norm}, "delta": {leaf: norm}}``; ``keep`` (layers, batch) or ``None``,
  ``weight`` (batch,);
* ``make_seq_logits(m, quant=None)``: a jitted ``(weights, tokens (1, T),
  start, n) -> (n, vocab)`` of float32 logits.

``quant`` asks for the control, one precision below the configuration's.
A new configuration is then added by files alone: its configuration file,
a traffic file, its reference where ``refmodel`` does not compute it, and
readers for any new metric; its cell is appended to ``workloads`` in
``BENCHMARK.json`` and to the ``workloads`` lists of the metrics it reports.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]   # the cell's end-to-end metric entries
    per_layer: List[dict]    # the cell's per-layer metric entries


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT, bench: Optional[Path] = None
              ) -> Cell:
    bench = bench or BENCH
    spec = load_json(root / "BENCHMARK.json")
    ws = [w for w in spec["workloads"] if w["name"] == name]
    if not ws:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = ws[0]
    cfg = [c for c in spec["configs"] if c["name"] == w["config"]][0]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(root / cfg["file"]),
        traffic=load_json(bench / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[e for e in spec["end_to_end"] if _applies(e, name)],
        per_layer=[e for e in spec["per_layer"] if _applies(e, name)],
    )


def model_sizes(config: dict) -> dict:
    """The sizes the harness and the references use, read from the
    configuration file through its ``harness_keys`` map."""
    keys = config["harness_keys"]
    return {k: config[v] for k, v in keys.items()}


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_for(config: dict):
    """The plain reference the configuration file names, once it has
    accepted the file's sizes."""
    ref = load_module(BENCH / f"{config.get('reference', 'refmodel')}.py")
    ref.accept(model_sizes(config))
    return ref


def driver_for(cell: Cell, bench: Optional[Path] = None):
    return load_module((bench or BENCH) / "drivers"
                       / f"{cell.traffic['kind']}.py")


def metric_reader(name: str, bench: Optional[Path] = None):
    return load_module((bench or BENCH) / "metrics" / f"{name}.py")
