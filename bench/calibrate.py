#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from, and the knee of
a serving cell.  Not part of a benchmark run.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 [--seconds 12]
    python bench/calibrate.py --workload <cell> --seeds 1 --sweep 2,3,4 --seconds 30

For each seed, in one process: the program's readings against the
reference, the control's (the reference in fp8) and each planted fault's
(training: half of the batch left out, the mean over the rest; serving: a
served token altered where it is produced).  ``--kv-int8`` serves with the
program's own int8 KV pages, the program's path one precision below.  A step that returns its state
unchanged reads 1 on ``delta_gap`` by construction and needs no run.  One
JSON line per seed.  ``--sweep`` runs the serving window at each rate and
prints the tails and the backlog: the knee is the highest rate whose
backlog does not grow through the window.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import cell as cells  # noqa: E402
from bench.run import Context, check_devices, log  # noqa: E402


def train_seed(ctx, drv) -> dict:
    job = drv.job_of(ctx.cell.config, ctx.cell.traffic)
    tr = drv.build_trainer(ctx, job)
    r = drv.first_steps(tr, job["b1"])
    prog = {"losses": r["losses"], "grad0": r["grad0"],
            "delta": drv.delta_norms(r["p0"], r["p3"])}
    del tr, r
    gc.collect()
    ref = drv.reference(ctx, job)
    ctl = drv.reference(ctx, job, quant="fp8")
    half = drv.reference(ctx, job, drop_half=True)
    return {"program": drv.compare(prog, ref), "control": drv.compare(ctl, ref),
            "fault_half_batch": drv.compare(half, ref),
            "losses": {"program": prog["losses"], "reference": ref["losses"]},
            "grad0_ref": ref["grad0"], "grad0_program": prog["grad0"],
            "delta_ref": ref["delta"], "delta_program": prog["delta"]}


def serve_seed(ctx, drv, st, seconds) -> dict:
    st.rset, st.params = drv.build(ctx, ctx.seed)
    res = drv.window(st, seconds, False)
    pairs = drv.sample(st)
    del st.rset, st.params
    gc.collect()
    out = drv.reference_gaps(ctx, ctx.seed, pairs, quant="fp8")
    alt = [(p, [(s[0] + 1) % ctx.model["vocab_size"]] + s[1:])
           for p, s in pairs[:1]] + pairs[1:]
    out["fault_token"] = drv.reference_gaps(ctx, ctx.seed, alt)["token_gap"]
    out["metrics"] = res["metrics"]
    out["served_tokens"] = sum(len(s) for _, s in pairs)
    return out


def sweep(ctx, drv, st, rates, seconds):
    for rate in rates:
        ctx.cell.traffic["rate_per_s"] = rate
        res = drv.window(st, seconds, False)
        q = st.queue
        mid = [n for t, n in q if t <= seconds / 2][-1:] or [0]
        end = [n for t, n in q if t <= seconds][-1:] or [0]
        print(json.dumps({"rate_per_s": rate, **res["metrics"],
                          "attempted": res["attempted"],
                          "unfinished": res["failed"],
                          "queue_mid": mid[0], "queue_end": end[0]}),
              flush=True)
        # drain what the tail left behind before the next rate
        while not drv.idle(st.rset):
            st.rset.step(st.t, [])
            st.t += 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--kv-int8", action="store_true",
                    help="serve with the program's own int8 KV pages: the "
                         "program's lower-precision path, the serving control")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    cell = cells.load_cell(args.workload)
    if args.kv_int8:
        cell.traffic["engine"]["kv_dtype"] = "int8"
    if args.sweep:
        cell.traffic["tail_s"] = 10.0
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices, peaks = check_devices(cell.chips)
    from bench.compile_clock import CompileClock

    clock = CompileClock()
    drv = cells.driver_for(cell)
    ctx = Context(cell, seeds[0], args.seconds, False, devices, peaks, clock)
    st = None
    if cell.traffic["kind"] == "serve":
        st = drv.setup(ctx)
        if args.sweep:
            sweep(ctx, drv, st, [float(r) for r in args.sweep.split(",")],
                  args.seconds)
            return 0
        del st.rset, st.params
        gc.collect()
    for seed in seeds:
        t0 = time.perf_counter()
        ctx.seed = seed
        if cell.traffic["kind"] == "train":
            out = train_seed(ctx, drv)
        else:
            out = serve_seed(ctx, drv, st, args.seconds)
        out.update(seed=seed, seconds=time.perf_counter() - t0)
        print(json.dumps(out), flush=True)
        log(f"seed {seed} done in {out['seconds']:.1f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
