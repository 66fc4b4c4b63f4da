#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights and state made on the chip from the seed, every shape the
cell's traffic uses warmed up) is timed from process start to the opening
of the measured window.  With ``--trace 0`` the window yields the cell's
end-to-end metrics; with ``--trace 1`` a profiler trace of a short steady
stretch of the window yields its per-layer metrics.  After the window the
outputs of the timed path are compared with a plain reference.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), then
``checks``: each number compared, with its limit.  The same numbers end
standard error.

The run refuses, with a non-zero exit and no result, where JAX finds no TPU
or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# JAX's persistent cache at the checkout's fixed path (the default of
# repro.launch.compile_cache), whatever the environment held
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import cell as cells  # noqa: E402
from bench.peaks import UnknownDevice, peaks_for  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Context:
    """What a driver is handed: the cell, the run's arguments, the chip."""

    def __init__(self, cell, seed, seconds, trace, devices, peaks, clock):
        self.cell, self.seed, self.seconds, self.trace = cell, seed, seconds, trace
        self.devices, self.peaks, self.clock = devices, peaks, clock
        self.model = cells.model_sizes(cell.config)
        self.log = log
        self.timers = {}  # set-up split by the driver: init_s, warmup_s

    def start_trace(self):
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))

    def stop_trace(self):
        import jax

        jax.profiler.stop_trace()


def check_devices(chips: int):
    """The chip's devices, or SystemExit where there is no TPU or too few."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        log(f"no accelerator: {e}")
        raise SystemExit(2)
    d = devices[0]
    log(f"device: platform {d.platform} kind {d.device_kind} "
        f"count {len(devices)}")
    if d.platform != "tpu":
        log("no TPU found: the benchmark runs on the chip only")
        raise SystemExit(2)
    if len(devices) < chips:
        log(f"the cell asks for {chips} chips, JAX finds {len(devices)}")
        raise SystemExit(2)
    try:
        peaks = peaks_for(d.device_kind)
    except UnknownDevice as e:
        log(str(e))
        raise SystemExit(2)
    return devices[:chips], peaks


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def per_layer_metrics(cell, run) -> dict:
    out = {}
    for entry in cell.per_layer:
        value = cells.metric_reader(entry["name"]).read(run)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


class TracedRun:
    """What a per-layer metric reader is handed."""

    def __init__(self, cell, model, peaks, red, spans, info):
        self.cell, self.model, self.peaks = cell, model, peaks
        self.trace, self.spans, self.info = red, spans, info


def reduce_traced(ctx, info):
    from bench import trace_reduce as tr

    events = tr.load_events(tr.find_xplane(str(TRACE_DIR)))
    win = [e for e in tr.host_annotations(events) if e.name == "bench.window"]
    if not win:
        raise RuntimeError("the trace holds no bench.window annotation")
    lo, hi = win[0].start, win[0].end
    red = tr.reduce_events(events, lo, hi, tr.device_events(events, "XLA Ops"),
                           tr.device_events(events, "XLA Modules"))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    top = sorted(red.programs.items(), key=lambda kv: -kv[1][1])[:12]
    for name, (n, sec) in top:
        log(f"trace: program {name} x{n} {sec:.6f} s")
    return red


def drive(ctx, driver, window_seconds: float) -> dict:
    """Set-up, window and check of one run; returns the result line."""
    import jax

    state = driver.setup(ctx)
    setup_s = time.perf_counter() - T_START
    c = ctx.clock.snapshot()
    log(f"setup: {setup_s:.3f} s = compile {c['compile_s']:.3f} s "
        f"({c['compiled_n']} programs) + cache load {c['cache_load_s']:.3f} s "
        f"({c['cache_hits']} programs) + "
        + " + ".join(f"{k} {v:.3f} s" for k, v in ctx.timers.items())
        + " (the rest is imports and host work)")
    for sec, name in ctx.clock.slowest(5):
        log(f"setup: slowest program {name} {sec:.3f} s")
    before = ctx.clock.backend_n
    res = driver.window(state, window_seconds, ctx.trace)
    in_window = ctx.clock.backend_n - before
    log(f"window: {in_window} programs compiled or loaded inside the window")
    mem = memory_peak(ctx.devices)
    device = {"platform": ctx.devices[0].platform,
              "kind": ctx.devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    out = {"attempted": res["attempted"], "failed": res["failed"]}
    if ctx.trace:
        red = reduce_traced(ctx, res["info"])
        run = TracedRun(ctx.cell, ctx.model, ctx.peaks, red,
                        res.get("spans", {}), res["info"])
        metrics = per_layer_metrics(ctx.cell, run)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        breakdown = red.breakdown()
    else:
        values = dict(res["metrics"], setup_s=setup_s)
        metrics = {e["name"]: {"value": values[e["name"]], "unit": e["unit"]}
                   for e in ctx.cell.end_to_end}
        breakdown = None
    gc.collect()
    t0 = time.perf_counter()
    correct, checks = driver.check(state)
    log(f"check: {time.perf_counter() - t0:.3f} s")
    line = {"correct": bool(correct), **out, "metrics": metrics,
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["window_compiles"] = in_window
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = cells.load_cell(args.workload)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # no size cap: the SVD programs alone are over 100 MB each
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices, peaks = check_devices(cell.chips)
    from bench.compile_clock import CompileClock

    clock = CompileClock()
    ctx = Context(cell, args.seed, args.seconds, bool(args.trace), devices,
                  peaks, clock)
    line = drive(ctx, cells.driver_for(cell), args.seconds)
    for name, c in line["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    log(f"correct: {line['correct']}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
