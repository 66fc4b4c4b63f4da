#!/usr/bin/env python3
"""The train step's device time by named scope, and the host's idle gaps
by program span, from one profiler trace.

The program names its train step's device work with ``jax.named_scope``
(``embed``, ``attn``, ``ffn``, ``head``, ``optimizer`` and, inside the
MeCeFO backward rules, ``lowrank_wgrad``) and writes each ``obs`` span into
the trace as a host annotation.  This module reads both:

* each device operation gets its module (the program event around it)
  and its scope path (its HLO ``op_name``, read from the compiled train
  step's HLO text: a TPU trace's op events carry neither);
* an operation's self time is its duration less the operations nested in
  it on the same device, so the ``while`` / ``conditional`` containers of
  the layer scans are not counted twice;
* a scope matches a path component in its forward (``attn``), ``jvp(attn)``,
  ``transpose(jvp(attn))`` or remat (``checkpoint(attn)``) form;
* each device-idle gap is split over time by the innermost annotation open
  over each piece, of any name the program or the harness gives.

Run on the chip, it drives a training cell through its set-up, one SVD
period with the profiler off and one traced around its refresh (no check),
and prints the split beside the harness's own per-layer metrics:

    python bench/scopes.py --workload qwen3-0.6b.train.failover --seed 7

The last line of standard output is one JSON object; ``--out`` writes it,
with the window's step history, to a file as well.
"""
from __future__ import annotations

import bisect
import re
import sys
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench.trace_reduce import ANNOTATION_PREFIX, Event, short_name

STEP_SCOPES = ("embed", "attn", "ffn", "head", "optimizer")
LOWRANK = "lowrank_wgrad"

# metric -> the scopes whose operations it sums (an op counts once)
SCOPE_METRICS = {
    "train_step.attn_ms": ("attn",),
    "train_step.ffn_ms": ("ffn", LOWRANK),
    "train_step.vocab_ms": ("embed", "head"),
    "train_step.optimizer_ms": ("optimizer",),
    "mecefo.lowrank_wgrad_ms": (LOWRANK,),
}

_HLO_LINE = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]*)"')


@dataclass(frozen=True)
class Op:
    """One run of one device operation."""

    plane: str
    module: str   # XLA module, ``jit_step_fn``
    name: str     # HLO instruction, ``fusion.12``
    start: float  # seconds
    dur: float
    op_name: str = ""  # scope path, ``jit(step_fn)/jvp()/while/body/attn/...``

    @property
    def end(self) -> float:
        return self.start + self.dur


# ---------------------------------------------------------------------------
# scope paths
# ---------------------------------------------------------------------------


def component_scope(component: str) -> str:
    """``transpose(jvp(attn))`` -> ``attn``: a path component with its
    transformation wrappers taken off."""
    c = component
    while c.endswith(")") and "(" in c:
        c = c[c.index("(") + 1:-1]
    return c


def scopes_in(op_name: str) -> List[str]:
    """The scope names of a path, outermost first."""
    return [component_scope(c) for c in op_name.split("/")]


def has_scope(op_name: str, scopes: Iterable[str]) -> bool:
    want = set(scopes)
    return any(s in want for s in scopes_in(op_name))


def step_scope(op_name: str) -> Optional[str]:
    """The outermost of ``STEP_SCOPES`` on the path, or ``None``."""
    for s in scopes_in(op_name):
        if s in STEP_SCOPES:
            return s
    return None


def op_names_from_hlo(text: str) -> Dict[str, str]:
    """``{instruction: op_name}`` from a compiled module's HLO text."""
    out = {}
    for line in text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def with_op_names(ops: Sequence[Op], module: str,
                  names: Dict[str, str]) -> List[Op]:
    """The ops of ``module`` with their scope paths from its HLO text."""
    return [Op(o.plane, o.module, o.name, o.start, o.dur,
               names.get(o.name, "")) if o.module == module else o
            for o in ops]


# ---------------------------------------------------------------------------
# self time
# ---------------------------------------------------------------------------


def self_times(ops: Sequence[Op]) -> List[Tuple[Op, float]]:
    """Each op of one device with its duration less the ops nested in it
    (an op nests in the latest-starting op that still covers its start)."""
    order = sorted(ops, key=lambda o: (o.start, -o.dur))
    child = [0.0] * len(order)
    stack: List[int] = []
    for i, o in enumerate(order):
        while stack and order[stack[-1]].end <= o.start:
            stack.pop()
        if stack:
            p = order[stack[-1]]
            child[stack[-1]] += min(o.end, p.end) - o.start
        stack.append(i)
    return [(o, max(o.dur - c, 0.0)) for o, c in zip(order, child)]


def module_self_times(ops_by_device: Dict[str, List[Op]], module: str
                      ) -> List[Tuple[Op, float]]:
    out = []
    for ops in ops_by_device.values():
        out.extend((o, s) for o, s in self_times(ops) if o.module == module)
    return out


def split_by_step_scope(selfs: Sequence[Tuple[Op, float]]) -> Dict[str, float]:
    """Self seconds by the outermost step scope; ``other`` for the rest."""
    out: Dict[str, float] = defaultdict(float)
    for o, s in selfs:
        out[step_scope(o.op_name) or "other"] += s
    return dict(out)


def scope_metrics(selfs: Sequence[Tuple[Op, float]], n_steps: int
                  ) -> Dict[str, float]:
    """The five scope metrics, in ms per train step."""
    if not n_steps or not selfs:
        return {}
    return {k: sum(s for o, s in selfs if has_scope(o.op_name, v))
            * 1e3 / n_steps for k, v in SCOPE_METRICS.items()}


# ---------------------------------------------------------------------------
# host annotations
# ---------------------------------------------------------------------------


def annotations(events: Sequence[Event], names: Iterable[str]) -> List[Event]:
    """Host events named as a program span (``names``) or a harness
    annotation (``bench.*``)."""
    names = set(names)
    return [e for e in events if not e.plane.startswith("/device:")
            and (e.name in names or e.name.startswith(ANNOTATION_PREFIX))]


def attribute_innermost(gap_list: Sequence[Tuple[float, float]],
                        anns: Sequence[Event]) -> Dict[str, float]:
    """Seconds of gaps by the innermost annotation open over each piece:
    every gap is cut at the annotations' edges, and each piece goes to the
    latest-starting annotation that covers it (``host`` where none does)."""
    out: Dict[str, float] = defaultdict(float)
    for s, e in gap_list:
        inside = [a for a in anns if a.start < e and a.end > s]
        cuts = sorted({s, e} | {t for a in inside for t in (a.start, a.end)
                                if s < t < e})
        for lo, hi in zip(cuts, cuts[1:]):
            mid = 0.5 * (lo + hi)
            cover = [a for a in inside if a.start <= mid <= a.end]
            best = max(cover, key=lambda a: (a.start, -a.dur), default=None)
            out[best.name if best is not None else "host"] += hi - lo
    return dict(out)


# ---------------------------------------------------------------------------
# loading a trace
# ---------------------------------------------------------------------------


def load(path: str) -> Tuple[List[Event], Dict[str, List[Op]]]:
    """Every event (as ``trace_reduce.load_events`` gives them) and each
    TPU device's ``XLA Ops``, with the module that ran each (the ``XLA
    Modules`` event around it: the op events name neither their module
    nor their scope path)."""
    from jax.profiler import ProfileData

    events: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                events.append(Event(plane.name, line.name, short_name(ev.name),
                                    ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                                    str(stats.get("hlo_module", ""))))
    return events, device_ops(events)


def device_ops(events: Sequence[Event]) -> Dict[str, List[Op]]:
    """``{plane: ops}``: each TPU device's ``XLA Ops``, each in the module
    of the ``XLA Modules`` event that covers its start (``""`` where none
    does), ``jit_step_fn(123)`` read as ``jit_step_fn``."""
    out: Dict[str, List[Op]] = {}
    for plane in sorted({e.plane for e in events
                         if e.plane.startswith("/device:TPU:")}):
        mods = sorted((e for e in events if e.plane == plane
                       and e.line == "XLA Modules"), key=lambda e: e.start)
        starts = [m.start for m in mods]
        ops = []
        for e in events:
            if e.plane != plane or e.line != "XLA Ops":
                continue
            i = bisect.bisect_right(starts, e.start) - 1
            module = (mods[i].name.split("(")[0]
                      if i >= 0 and e.start < mods[i].end else "")
            ops.append(Op(plane, module, e.name, e.start, e.dur))
        out[plane] = ops
    return out


# ---------------------------------------------------------------------------
# on the chip
# ---------------------------------------------------------------------------


def report(st, res, first: int, events: Sequence[Event],
           ops: Dict[str, List[Op]], log=print) -> dict:
    """The traced stretch of a training window, split: the train step's
    device time by scope, the new metrics beside the harness's own, the
    idle gaps between plain steps by the innermost program span, and the
    step time before, inside and after the traced stretch."""
    from statistics import median

    from bench import cell as cells, readers, trace_reduce as tr
    from repro.obs.catalog import SPANS

    win = [e for e in tr.host_annotations(events) if e.name == "bench.window"]
    lo, hi = win[0].start, win[0].end
    red = tr.reduce_events(events, lo, hi, tr.device_events(events, "XLA Ops"),
                           tr.device_events(events, "XLA Modules"))

    class Run:
        pass

    run = Run()
    run.cell, run.model, run.peaks = st.ctx.cell, st.ctx.model, st.ctx.peaks
    run.trace, run.spans, run.info = red, res["spans"], res["info"]
    step_mod = readers.train_step_program(run)
    steps = readers.train_steps(run)
    hlo = train_step_hlo(st)
    # the ops that start inside the stretch, whole
    ops = {p: with_op_names([o for o in v if lo <= o.start <= hi], step_mod,
                            hlo) for p, v in ops.items()}
    selfs = module_self_times(ops, step_mod)
    n = len(steps)
    unnamed = sum(s for o, s in selfs if not o.op_name)
    out = {"train_step_program": step_mod, "traced_steps": n,
           "hlo_instructions_named": len(hlo),
           "self_ms_per_step": sum(s for _, s in selfs) * 1e3 / n,
           "unnamed_self_ms_per_step": unnamed * 1e3 / n,
           "split_ms_per_step": {k: v * 1e3 / n for k, v in
                                 split_by_step_scope(selfs).items()}}
    out.update(scope_metrics(selfs, n))
    for e in st.ctx.cell.per_layer:
        out[e["name"]] = cells.metric_reader(e["name"]).read(run)

    # idle gaps between plain step pairs, by the innermost program span
    _, plain = readers.step_pairs(run)
    gl = [(max(s, a.end), min(e, b.start)) for a, b in plain
          for s, e in red.gap_list if s < b.start and e > a.end]
    by_span = attribute_innermost(gl, annotations(events, SPANS))
    idle = sum(by_span.values())
    below = sum(v for k, v in by_span.items()
                if k in SPANS and k != "trainer.step")
    out["plain_gap_ms_per_step_by_span"] = {
        k: v * 1e3 / len(plain) for k, v in by_span.items()} if plain else {}
    out["plain_gap_share_below_trainer_step"] = below / idle if idle else None
    out["gap_s_by_innermost_span"] = attribute_innermost(
        red.gap_list, annotations(events, SPANS))
    out["spans_ms_per_step"] = {p: s * 1e3 / n
                                for p, (c, s) in res["spans"].items()}

    # step times (host clock, loss read to loss read) inside and outside
    # the traced stretch, the steps next to a refresh left out; the lead
    # before the stretch is the driver's
    hist = st.trainer.history[first:]
    period, stretch = st.job["svd_period"], res["info"]["traced_steps"]
    lead = min(max((period - first) % period - stretch // 2, 0),
               len(hist) - stretch)
    plain_steps = [(j, h["seconds"]) for j, h in enumerate(hist)
                   if h["step"] % period not in (0, 1)]
    for name, keep in (("before", lambda j: j < lead),
                       ("traced", lambda j: lead <= j < lead + stretch),
                       ("after", lambda j: j >= lead + stretch)):
        xs = [s for j, s in plain_steps if keep(j)]
        out[f"step_s_{name}"] = median(xs) if xs else None
    # what no step scope owns: the scope path, or the instruction where
    # the HLO text gives the op no op_name
    other: Dict[str, float] = defaultdict(float)
    for o, s in selfs:
        if step_scope(o.op_name) is None:
            other[o.op_name or "no op_name: " + o.name.split(".")[0]] += s
    out["other_ms_per_step"] = {
        k: v * 1e3 / n for k, v in sorted(other.items(),
                                          key=lambda kv: -kv[1])[:15]}
    for k, v in sorted(out["split_ms_per_step"].items(), key=lambda kv: -kv[1]):
        log(f"scope {k}: {v:.3f} ms per step")
    for k, v in sorted(out["plain_gap_ms_per_step_by_span"].items(),
                       key=lambda kv: -kv[1]):
        log(f"gap {k}: {v:.4f} ms per step")
    return out


def train_step_hlo(st) -> Dict[str, str]:
    """``{instruction: op_name}`` of the trainer's compiled train steps."""
    from repro.core.ndb import plan_to_masks
    from repro.data.pipeline import make_batch

    out: Dict[str, str] = {}
    trainer = st.trainer
    for key, jitted in trainer._step_cache.items():
        args = (trainer.state, make_batch(trainer.cfg, trainer.shape, 0,
                                          source=trainer.source,
                                          seed=trainer.seed))
        if key[0] == "dynamic":
            keep, w = plan_to_masks(trainer._mask_plan(), trainer.cfg,
                                    trainer.shape.global_batch)
            args += ({"keep": keep, "example_weight": w},)
        with trainer.mesh:
            out.update(op_names_from_hlo(
                jitted.lower(*args).compile().as_text()))
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import shutil
    import time
    from statistics import median

    t_start = time.perf_counter()
    root = Path(__file__).resolve().parents[1]
    # the harness's compile cache: a run of bench/run.py after this one
    # in the same checkout loads what this one compiled
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(root), str(root / "src")]

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    trace_dir = str(root / ".scope_trace")

    import jax

    from bench import cell as cells, program, trace_reduce as tr
    from bench.compile_clock import CompileClock
    from bench.peaks import peaks_for

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    cell = cells.load_cell(args.workload)
    devices = jax.devices()
    if cell.traffic["kind"] != "train" or devices[0].platform != "tpu":
        log("scopes: a training cell on the TPU only")
        return 2

    class Ctx:
        """What the driver is handed (``bench/run.py``'s context), with
        the trace kept for this module's reading."""

        def __init__(self):
            self.cell, self.seed, self.trace = cell, args.seed, True
            self.devices = devices[:cell.chips]
            self.peaks = peaks_for(devices[0].device_kind)
            self.clock = CompileClock()
            self.model = cells.model_sizes(cell.config)
            self.log, self.timers = log, {}

        def start_trace(self):
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)

        def stop_trace(self):
            jax.profiler.stop_trace()

    driver = cells.driver_for(cell)
    st = driver.setup(Ctx())
    log(f"scopes: set-up {time.perf_counter() - t_start:.3f} s")
    # one SVD period with the profiler off, then one traced around its
    # refresh: the spans and step times of the first are the second's
    # untraced counterparts
    first = len(st.trainer.history)
    before = program.span_totals()
    driver.window(st, 1.0, False)
    untraced = program.span_delta(program.span_totals(), before)
    hist = st.trainer.history[first:]
    steps = [h["seconds"] for h in hist if h["step"] % st.job["svd_period"]
             not in (0, 1)]
    quarters = [steps[i * len(steps) // 4:(i + 1) * len(steps) // 4]
                for i in range(4)]
    untraced_out = {
        "spans_ms_per_step": {p: v * 1e3 / len(hist)
                              for p, (c, v) in untraced.items()},
        "step_s_by_quarter": [median(q) if q else None for q in quarters]}
    first = len(st.trainer.history)
    res = driver.window(st, 1.0, True)
    events, ops = load(tr.find_xplane(trace_dir))
    out = report(st, res, first, events, ops, log)
    out["untraced_window"] = untraced_out
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(dict(
            out, history=st.trainer.history)))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
