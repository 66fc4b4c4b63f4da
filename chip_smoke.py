#!/usr/bin/env python3
"""Bring-up check on the TPU: MeCeFO training and the serving engine at
published widths, through the entry points a user calls.

    python chip_smoke.py               # one chip: training, then serving
    python chip_smoke.py --four-chips  # data-parallel training on 4 chips

One chip runs two phases in one process:

* training — ``launch.train.Trainer`` on ``llama-350m`` (bf16, batch 16 x
  seq 256, dynamic NDB).  Healthy steps first, then a scripted failure of
  one (rank, stage) switches the step to the MeCeFO-degraded program
  (skip-MHA backward, FFN recompute, low-rank Wgrad).  Step 0 refreshes
  the SVD projections.  Every loss and grad norm must be finite.
* serving — ``serve.run.run_from_header`` on ``qwen3-0.6b`` (bf16) under
  pod-kill chaos, recorded and replayed in the same process; the replay
  must be bit-exact.  Once on the dense path and once with the compiled
  Pallas paged-decode kernel, which is also checked against the XLA page
  walk on random pages.

``--four-chips`` runs only the training path on a ``data=4`` mesh and
compares it with the same steps on one device.

The script exits non-zero, printing no result, when JAX finds no TPU.
Its last line on standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times and memory are printed on earlier lines, each labelled with the
device kind it was measured on.  Recorded serve traces go to
``chiprun_out/``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import (  # noqa: E402
    MeCeFOConfig,
    ShapeConfig,
    TrainConfig,
    get_config,
    reduced,
)
from repro.ft.events import FAIL, FailureEvent  # noqa: E402
from repro.ft.injectors import ScheduledInjector  # noqa: E402
from repro.kernels import ops as kernel_ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.train import Trainer  # noqa: E402
from repro.serve.engine import EngineConfig, resolve_kernel_impl  # noqa: E402
from repro.serve.request import WorkloadSpec  # noqa: E402
from repro.serve.run import replay_serve_trace, run_from_header  # noqa: E402
from repro.serve.trace import ServeTraceHeader  # noqa: E402

OUT = ROOT / "chiprun_out"
TRAIN_ARCH = "llama-350m"
SERVE_ARCH = "qwen3-0.6b"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# step-0 agreement of the 4-chip run with the one-device run: both compute
# the same bf16 model; only reduction order across the data shards differs
STEP0_RTOL = 2e-2
# paged Pallas kernel vs XLA page walk on the same bf16 pages: both
# accumulate in f32, the output is rounded to bf16 (ulp 2**-7 at 1.0)
PAGED_ATOL = 2.0 ** -6


class CompileClock:
    """Sums XLA backend-compile time reported through ``jax.monitoring``."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.programs = []  # (seconds, program name)
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, fun_name="", **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration
            self.count += 1
            self.programs.append((duration, fun_name))

    def slowest(self, n=5):
        return sorted(self.programs, reverse=True)[:n]


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes(device):
    stats = device.memory_stats()  # None where the backend keeps none
    return "not reported" if stats is None else stats["peak_bytes_in_use"]


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def train_cfg(full: bool):
    cfg = get_config(TRAIN_ARCH)
    return cfg if full else reduced(cfg, dtype="bfloat16")


def run_training(cfg, shape, *, mesh=None, n_healthy=4, n_failed=4,
                 fail=(1, 3), seed=0, clock=None, tag="train"):
    """Healthy steps, then a scripted (rank, stage) failure that holds to
    the end of the run.  Returns the trainer and its per-step records."""
    steps = n_healthy + n_failed
    failure = FailureEvent(step=n_healthy, kind=FAIL, device=fail,
                           duration_steps=10 * steps, source="scripted")
    trainer = Trainer(
        cfg, shape, TrainConfig(steps=steps, seed=seed),
        mecefo=MeCeFOConfig(mode="dynamic", rank=16, svd_period=20),
        mesh=mesh, injectors=[ScheduledInjector([failure])], seed=seed,
    )
    proj0 = [np.asarray(x) for x in jax.tree.leaves(trainer.state.proj)]
    for label, n in (("healthy", n_healthy), ("degraded", n_failed)):
        c0 = clock.seconds if clock else 0.0
        trainer.run(steps=n, log_every=0)
        key = trainer._step_key()
        if label == "healthy":
            assert key == ("off",), f"healthy steps ran program {key}"
        else:
            assert key == ("dynamic",), f"degraded steps ran program {key}"
        if clock:
            log(f"{tag}: {label} program XLA compile "
                f"{clock.seconds - c0:.3f} s")
    hist = trainer.history
    for rec in hist:
        assert math.isfinite(rec["loss"]), rec
        assert math.isfinite(rec["grad_norm"]), rec
    assert all(r["failed"] == 0 for r in hist[:n_healthy]), hist
    assert all(r["failed"] > 0 and r["degraded_frac"] > 0
               for r in hist[n_healthy:]), hist
    # step 0 refreshed V1 (the projections start at zero)
    refreshed = [np.asarray(x) for x in jax.tree.leaves(trainer.state.proj)]
    assert refreshed and all(not np.array_equal(a, b)
                             for a, b in zip(proj0, refreshed)), (
        "the SVD projection refresh did not run")
    return trainer, hist


def train_phase(full: bool, device, clock) -> None:
    cfg = train_cfg(full)
    shape = ShapeConfig("smoke", 256, 16, "train")
    kind = device.device_kind
    log(f"train: {cfg.name} d_model={cfg.d_model} layers={cfg.n_layers} "
        f"vocab={cfg.vocab_size} {cfg.dtype} batch {shape.global_batch} x "
        f"seq {shape.seq_len}")
    _, hist = run_training(cfg, shape, clock=clock)
    for rec in hist:
        log(f"train: step {rec['step']} loss {rec['loss']:.6f} "
            f"grad_norm {rec['grad_norm']:.6f} failed {rec['failed']} "
            f"degraded_frac {rec['degraded_frac']:.4f} "
            f"wall {rec['seconds']:.6f} s on {kind}")
    log(f"train: peak_bytes_in_use {peak_bytes(device)} on {kind}")


def four_chip_phase(full: bool, devices, clock) -> None:
    from jax.sharding import NamedSharding

    from repro.launch.steps import make_train_step

    cfg = train_cfg(full)
    shape = ShapeConfig("smoke", 256, 16, "train")
    kind = devices[0].device_kind
    ref_mesh = make_host_mesh()
    assert list(ref_mesh.devices.flat) == [devices[0]], ref_mesh
    ref, ref_hist = run_training(cfg, shape, mesh=ref_mesh, clock=clock,
                                 tag="train[1 device]")
    del ref
    mesh = make_host_mesh(data=4, model=1)
    dp, dp_hist = run_training(cfg, shape, mesh=mesh, clock=clock,
                               tag="train[data=4]")

    state_devs = {d for leaf in jax.tree.leaves(dp.state)
                  for d in leaf.sharding.device_set}
    assert len(state_devs) == 4, f"state spans {len(state_devs)} devices"
    _, _, bshard, _ = make_train_step(
        cfg, dp.train_cfg, dp.parallel, dp.mecefo, mesh, shape,
        ndb_mode="dynamic", donate=False,
    )
    for name, sh in bshard.items():
        assert isinstance(sh, NamedSharding) and len(sh.device_set) == 4, (
            name, sh)
        assert sh.spec and sh.spec[0] in ("data", ("data",)), (name, sh.spec)

    for r, d in zip(ref_hist, dp_hist):
        log(f"train[data=4]: step {d['step']} loss {d['loss']:.6f} "
            f"(1 device {r['loss']:.6f}) grad_norm {d['grad_norm']:.6f} "
            f"(1 device {r['grad_norm']:.6f}) wall {d['seconds']:.6f} s "
            f"on 4 x {kind}")
    for key in ("loss", "grad_norm"):
        a, b = dp_hist[0][key], ref_hist[0][key]
        assert abs(a - b) <= STEP0_RTOL * abs(b), (
            f"step-0 {key} {a} vs one-device {b} beyond rtol {STEP0_RTOL}")
    log(f"train[data=4]: step-0 loss and grad_norm within rtol {STEP0_RTOL} "
        f"of the one-device run")
    for i, d in enumerate(devices[:4]):
        log(f"train[data=4]: device {i} peak_bytes_in_use {peak_bytes(d)} "
            f"on {kind}")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def serve_header(full: bool, paged: bool) -> ServeTraceHeader:
    cfg = get_config(SERVE_ARCH)
    vocab = cfg.vocab_size if full else reduced(cfg).vocab_size
    ecfg = EngineConfig(max_slots=4, page_size=16, pages_per_slot=8,
                        use_paged_kernel=paged)
    spec = WorkloadSpec(n_requests=8, vocab_size=vocab, seed=0,
                        mean_interarrival_steps=2.0, prompt_len=(16, 64),
                        new_tokens=(16, 32))
    return ServeTraceHeader(
        config=SERVE_ARCH, reduced=not full,
        dtype="bfloat16" if full else "float32", seed=0, n_replicas=3,
        ranks_per_pod=1, snapshot_cadence=2, layout_seed=0,
        engine=dataclasses.asdict(ecfg), workload=spec.to_json(),
        chaos={"kind": "pod", "fail_every_steps": 8.0, "heal_steps": 4.0,
               "ranks_per_pod": 1, "transfer_steps": 1},
    )


def check_paged_kernel(impl: str) -> float:
    """Paged kernel ``impl`` vs the XLA page walk at qwen3-0.6b widths on
    random bf16 pages; returns the max abs difference."""
    cfg = get_config(SERVE_ARCH)
    B, H, KV, hd, ps, P = 4, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, 16, 8
    n_pages = 1 + B * P
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, 1, H, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(n_pages, ps, KV, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(n_pages, ps, KV, hd)), jnp.bfloat16)
    tables = jnp.asarray(1 + rng.permutation(B * P).reshape(B, P), jnp.int32)
    lens = jnp.asarray([1, 37, 100, P * ps], jnp.int32)
    out = kernel_ops.paged_flash_decode(q, k, v, tables, lens, impl=impl)
    ref = kernel_ops.paged_flash_decode(q, k, v, tables, lens, impl="xla")
    out = np.asarray(out, np.float32)
    assert np.all(np.isfinite(out)), "paged kernel produced non-finite values"
    return float(np.max(np.abs(out - np.asarray(ref, np.float32))))


def serve_phase(full: bool, paged: bool, device, clock, out_dir=OUT) -> None:
    tag = "serve[paged]" if paged else "serve[dense]"
    kind = device.device_kind
    header = serve_header(full, paged)
    impl = resolve_kernel_impl(EngineConfig(**header.engine))
    if paged:
        if full:
            assert impl == "pallas", f"paged decode resolved to {impl!r}"
        err = check_paged_kernel(impl)
        assert err <= PAGED_ATOL, (
            f"{impl} paged kernel vs XLA walk: max |diff| {err} > "
            f"{PAGED_ATOL}")
        log(f"{tag}: {impl} kernel vs XLA page walk max |diff| {err:.6g} "
            f"(atol {PAGED_ATOL:.6g}) on {kind}")
    out_dir.mkdir(exist_ok=True)
    path = out_dir / f"smoke_{'paged' if paged else 'dense'}.jsonl"
    c0 = clock.seconds
    t0 = time.perf_counter()
    result, _ = run_from_header(header, record_path=str(path))
    run_s = time.perf_counter() - t0
    c1 = clock.seconds
    t0 = time.perf_counter()
    problems = replay_serve_trace(str(path))
    replay_s = time.perf_counter() - t0
    assert problems == [], "serve replay diverged:\n" + "\n".join(problems)

    acct = result.accounting
    vocab = WorkloadSpec.from_json(header.workload).vocab_size
    assert all(rs.done for rs in result.states.values()), "requests left"
    streams = result.streams()
    assert all(0 <= t < vocab for s in streams.values() for t in s)
    assert acct["n_kills"] >= 1 and acct["n_migrations"] >= 1, acct
    log(f"{tag}: impl {impl or 'dense'} requests {len(streams)} tokens "
        f"{acct['n_tokens']} steps {result.n_steps} kills {acct['n_kills']} "
        f"migrations {acct['n_migrations']}; replay bit-exact")
    log(f"{tag}: run {run_s:.6f} s (XLA compile {c1 - c0:.3f} s), replay "
        f"{replay_s:.6f} s (XLA compile {clock.seconds - c1:.3f} s) on {kind}")
    log(f"{tag}: peak_bytes_in_use {peak_bytes(device)} on {kind}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only data-parallel training on a data=4 mesh "
                         "against the same steps on one device")
    args = ap.parse_args(argv)

    cache = Path(enable_compile_cache())
    warm = len(list(cache.iterdir())) if cache.is_dir() else 0
    log(f"compile cache: {cache} ({warm} entries at start)")
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform {dev.platform} kind {dev.device_kind} "
        f"count {len(devices)}")
    if dev.platform != "tpu":
        log("no TPU found: this check runs on the chip only")
        return 1
    if args.four_chips and len(devices) < 4:
        log(f"--four-chips needs 4 devices, found {len(devices)}")
        return 1
    assert kernel_ops.resolve_interpret() is False
    assert kernel_ops.resolve_paged_impl() == "pallas"

    clock = CompileClock()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(True, devices, clock)
    else:
        train_phase(True, dev, clock)
        serve_phase(True, False, dev, clock)
        serve_phase(True, True, dev, clock)
    log(f"total wall {time.perf_counter() - t0:.3f} s, XLA compile "
        f"{clock.seconds:.3f} s over {clock.count} programs on "
        f"{dev.device_kind}")
    for sec, name in clock.slowest():
        log(f"slowest compile: {name} {sec:.3f} s on {dev.device_kind}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
