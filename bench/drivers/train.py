"""Training cells: the MeCeFO trainer driven through ``Trainer.run``.

Set-up builds one ``Trainer`` (state made on the chip from the seed), feeds
it the benchmark's own bigram batches, and drives it through its first three
steps: step 0 compiles the step and the SVD refresh that follows it.  The
readings that the check compares are taken there: the loss of each step,
the first step's clipped gradient as the optimizer holds it (``m / (1 -
b1)`` after one step), and the weights' change over the three steps.  The
same trainer then runs the window in whole refresh periods, so every window
holds as many SVD refreshes as it has periods.

The traffic file's ``failures`` lists (rank, stage) devices failed from
step 0 to the end of the run; ``job`` overrides the configuration's
``train_job``.
"""
from __future__ import annotations

import math
import time
from statistics import median
from typing import List, Optional, Sequence, Tuple

import numpy as np

from bench import cell as cells
from bench import program
from bench.traffic import BigramSource

MAX_STEPS = 10**9


def job_of(config: dict, traffic: dict) -> dict:
    return {**config["train_job"], **traffic.get("job", {})}


# ---------------------------------------------------------------------------
# the neighbour-do-both placement, written out for the reference
# ---------------------------------------------------------------------------


def stage_of_layer(layer: int, n_layers: int, n_stages: int) -> int:
    per = -(-n_layers // n_stages)
    return min(layer // per, n_stages - 1)


def keep_masks(failures: Sequence[Tuple[int, int]], n_dp: int, n_stages: int,
               n_layers: int, batch: int) -> Tuple[np.ndarray, np.ndarray]:
    """``keep`` (layers, batch): 0 where an example's rank runs the layer in
    degraded mode; ``weight`` (batch,): every example of a rank with a live
    stage counts.  A failed stage's work goes to the nearest live stage
    below it (cyclically); both run degraded."""
    failed = {tuple(f) for f in failures}
    keep = np.ones((n_layers, batch), np.float32)
    weight = np.ones((batch,), np.float32)
    per = batch // n_dp
    stage = np.array([stage_of_layer(i, n_layers, n_stages)
                      for i in range(n_layers)])
    for r in range(n_dp):
        rows = slice(r * per, (r + 1) * per)
        if all((r, s) in failed for s in range(n_stages)):
            weight[rows] = 0.0
            keep[:, rows] = 0.0
            continue
        deg = set()
        for (fr, s) in failed:
            if fr != r:
                continue
            for delta in range(1, n_stages):
                nb = (s - delta) % n_stages
                if (r, nb) not in failed:
                    deg.update((s, nb))
                    break
        keep[np.isin(stage, sorted(deg)), rows] = 0.0
    return keep, weight


# ---------------------------------------------------------------------------
# set-up, window, check
# ---------------------------------------------------------------------------


class State:
    pass


def build_trainer(ctx, job: dict):
    from repro.configs.base import (MeCeFOConfig, ParallelConfig,
                                    ShapeConfig, TrainConfig)
    from repro.ft.events import FAIL, FailureEvent
    from repro.ft.injectors import ScheduledInjector
    from repro.launch.train import Trainer

    cfg = program.model_config(ctx.cell.config, ctx.model)
    train = TrainConfig(
        steps=job["total_steps"], learning_rate=job["learning_rate"],
        warmup_frac=job["warmup_frac"], weight_decay=job["weight_decay"],
        b1=job["b1"], b2=job["b2"], eps=job["eps"],
        grad_clip=job["grad_clip"], optimizer="adamw", seed=ctx.seed)
    events = [FailureEvent(step=0, kind=FAIL, device=(int(r), int(s)),
                           duration_steps=MAX_STEPS, source="scripted")
              for r, s in ctx.cell.traffic.get("failures", [])]
    trainer = Trainer(
        cfg, ShapeConfig("bench", job["seq_len"], job["global_batch"], "train"),
        train,
        parallel=ParallelConfig(fsdp=False, remat=job["remat"],
                                scan_layers=True),
        mecefo=MeCeFOConfig(mode=job["mecefo_mode"], rank=job["rank"],
                            svd_period=job["svd_period"]),
        injectors=[ScheduledInjector(events)], n_dp=job["n_dp"],
        n_stages=job["n_stages"], seed=ctx.seed,
    )
    trainer.source = BigramSource(cfg.vocab_size, ctx.seed)
    return trainer


def first_steps(trainer, b1: float) -> dict:
    """Steps 0-2 through ``Trainer.run``, with the readings of the check."""
    import jax

    p0 = jax.device_get(trainer.state.params)
    trainer.run(steps=1, log_every=0)
    grad0 = {k: v / (1 - b1)
             for k, v in program.leaf_norms(trainer.state.opt.m).items()}
    trainer.run(steps=2, log_every=0)
    p3 = jax.device_get(trainer.state.params)
    return {"p0": p0, "p3": p3, "grad0": grad0,
            "losses": [h["loss"] for h in trainer.history[:3]]}


def setup(ctx) -> State:
    import jax

    st = State()
    st.ctx = ctx
    st.job = job_of(ctx.cell.config, ctx.cell.traffic)
    # a reference that does not compute the file's sizes stops the run here
    cells.reference_for(ctx.cell.config)
    t0 = time.perf_counter()
    st.trainer = build_trainer(ctx, st.job)
    jax.block_until_ready(st.trainer.state)
    ctx.timers["state_init_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    st.readings = first_steps(st.trainer, st.job["b1"])
    ctx.timers["warmup_s"] = time.perf_counter() - t0
    st.step_s = median(h["seconds"] for h in st.trainer.history[1:3])
    ctx.log(f"train: steps 1-2 took {st.step_s:.6f} s each (median)")
    return st


def window(st: State, seconds: float, trace: bool) -> dict:
    import jax

    tr = st.trainer
    job = st.job
    period = job["svd_period"]
    n_steps = period * max(1, round(seconds / (period * st.step_s)))
    first = len(tr.history)

    def run(n):
        with jax.profiler.TraceAnnotation("bench.trainer.run"):
            tr.run(steps=n, log_every=0)

    spans = {}
    stretch = 0
    t0 = time.perf_counter()
    if trace:
        # a stretch of ten steps around the first refresh of the window
        stretch = min(10, n_steps)
        lead = max((period - first) % period - stretch // 2, 0)
        lead = min(lead, n_steps - stretch)
        if lead:
            run(lead)
        before = program.span_totals()
        st.ctx.start_trace()
        with jax.profiler.TraceAnnotation("bench.window"):
            run(stretch)
        st.ctx.stop_trace()
        spans = program.span_delta(program.span_totals(), before)
        rest = n_steps - lead - stretch
        if rest:
            run(rest)
    else:
        run(n_steps)
    elapsed = time.perf_counter() - t0
    hist = tr.history[first:]
    B, S, n_dp = job["global_batch"], job["seq_len"], job["n_dp"]
    tokens = sum(B * S * h["dp_size"] / n_dp for h in hist)
    failed = sum(1 for h in hist if not math.isfinite(h["loss"]))
    st.ctx.log(f"train: window {len(hist)} steps in {elapsed:.6f} s, "
               f"{tokens:.0f} tokens, {failed} non-finite losses")
    info = {"tokens_per_step": B * S * hist[-1]["dp_size"] / n_dp,
            "seq_len": S, "traced_steps": stretch}
    return {"metrics": {"train_tokens_per_s": tokens / elapsed},
            "attempted": len(hist), "failed": failed, "info": info,
            "spans": spans}


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------


def leaf_gap(prog: dict, ref: dict, names: Optional[List[str]] = None) -> float:
    """Worst leaf: the gap between the two norms over the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    names = names if names is not None else sorted(ref)
    med = float(np.median([ref[k] for k in names]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in names)


def moved_leaves(grad_ref: dict) -> List[str]:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's; the rest move by round-off alone."""
    med = float(np.median(list(grad_ref.values())))
    return sorted(k for k, v in grad_ref.items() if v >= 1e-3 * med)


def delta_norms(p0: dict, p3: dict) -> dict:
    a, b = program.named_leaves(p0), program.named_leaves(p3)
    return {k: float(np.linalg.norm(np.asarray(b[k], np.float32)
                                    - np.asarray(a[k], np.float32)))
            for k in a}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers the check compares; ``prog`` and ``ref`` each hold
    ``losses``, ``grad0`` and ``delta`` (per-leaf norms)."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                   ref["losses"]))
    moved = moved_leaves(ref["grad0"])
    return {"loss_gap": loss,
            "grad_gap": leaf_gap(prog["grad0"], ref["grad0"]),
            "delta_gap": leaf_gap(prog["delta"], ref["delta"], moved)}


def reference_inputs(ctx, job: dict):
    m = ctx.model
    src = BigramSource(m["vocab_size"], ctx.seed)
    batches = [src.batch(t, job["global_batch"], job["seq_len"])
               for t in range(3)]
    failures = ctx.cell.traffic.get("failures", [])
    if job["mecefo_mode"] == "off" or not failures:
        keep = None
        weight = np.ones((job["global_batch"],), np.float32)
    else:
        keep, weight = keep_masks(failures, job["n_dp"], job["n_stages"],
                                  m["n_layers"], job["global_batch"])
    return batches, keep, weight


def check(st: State) -> Tuple[bool, dict]:
    import gc

    r = st.readings
    prog = {"losses": r["losses"], "grad0": r["grad0"],
            "delta": delta_norms(r["p0"], r["p3"])}
    del st.trainer, st.readings
    gc.collect()
    ref = reference(st.ctx, st.job)
    gaps = compare(prog, ref)
    limits = st.ctx.cell.traffic["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in gaps.items()}
    # a limit not yet set from the chip's readings judges nothing
    return all(limits[k] is not None and v <= limits[k]
               for k, v in gaps.items()), checks


def reference(ctx, job: dict, quant=None, drop_half: bool = False) -> dict:
    """The reference's readings; ``quant`` and ``drop_half`` make the
    control and a planted fault."""
    batches, keep, weight = reference_inputs(ctx, job)
    if drop_half:
        weight = weight.copy()
        weight[len(weight) // 2:] = 0.0
    ref = cells.reference_for(ctx.cell.config)
    return ref.run_training(ctx.model, job, ctx.seed, batches, keep, weight,
                            quant=quant)
