"""End-to-end training driver with MeCeFO fault tolerance.

Wires every substrate together: data pipeline → jitted train step (with NDB
masks) → failure process → failover controller (plan updates, compile cache,
recovery accounting) → SVD projection refresh every τ → async checkpoints.

CLI (CPU-scale by default — reduced configs):
  PYTHONPATH=src python -m repro.launch.train --arch llama-350m --steps 200 \
      --mecefo dynamic --scenario high --reduced
"""
from __future__ import annotations

import argparse
import dataclasses
import logging
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from repro import obs
from repro.checkpoint.ckpt import CheckpointManager
from repro.configs.base import (
    MeCeFOConfig,
    ModelConfig,
    ParallelConfig,
    ShapeConfig,
    TrainConfig,
    get_config,
    reduced,
)
from repro.core.lowrank import refresh_projections
from repro.core.ndb import NDBPlan, plan_to_masks
from repro.data.pipeline import SyntheticLM, make_batch
from repro.ft.controller import FTController
from repro.ft.failures import (
    SCENARIOS,
    ChaosEngine,
    FailureScenario,
    engine_for_scenario,
)
from repro.ft.injectors import CHAOS_PRESETS, Injector, chaos_preset
from repro.ft.trace import (
    Trace,
    TraceRecorder,
    load_trace,
    replay_engine,
    verify_replay,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.state import init_state, state_specs, to_shardings
from repro.launch.steps import build_rules, make_train_step

_log = logging.getLogger("repro.train")


class Trainer:
    """Fault-tolerant trainer (single-host mesh; same code scales by mesh)."""

    def __init__(
        self,
        cfg: ModelConfig,
        shape: ShapeConfig,
        train: TrainConfig = TrainConfig(),
        parallel: Optional[ParallelConfig] = None,
        mecefo: MeCeFOConfig = MeCeFOConfig(),
        mesh=None,
        scenario: FailureScenario = SCENARIOS["none"],
        n_dp: int = 4,
        n_stages: int = 8,
        step_time_s: float = 1.0,
        seed: int = 0,
        injectors: Optional[List[Injector]] = None,
        trace_record: Optional[str] = None,
        trace_replay: Optional[str] = None,
        elastic: Optional[bool] = None,
        statexfer: bool = False,
        snapshot_every: int = 1,
        ft_policy: Optional[str] = None,
    ):
        self.cfg, self.shape, self.train_cfg = cfg, shape, train
        self.parallel = parallel or ParallelConfig(
            fsdp=False, remat="ffn", scan_layers=True
        )
        self.mecefo = mecefo
        self.mesh = mesh or make_host_mesh()
        self.source = SyntheticLM(cfg.vocab_size)
        self.seed = seed

        key = jax.random.PRNGKey(seed)
        # the state starts on the step's shardings: an uncommitted initial
        # state would compile the step a second time on step 1
        rules = build_rules(cfg, self.mesh, self.parallel)
        self.state = jax.device_put(
            init_state(cfg, train, mecefo, key),
            to_shardings(self.mesh, state_specs(cfg, train, mecefo, rules)),
        )

        # -- chaos engine: replayed trace > explicit injectors > scenario ---
        self.replay_trace = None
        recorder = TraceRecorder(trace_record) if trace_record else None
        if trace_replay is not None:
            # accept a path or an already-loaded Trace (avoids re-parsing
            # when the caller needed the header/footer anyway)
            self.replay_trace = (
                trace_replay if isinstance(trace_replay, Trace)
                else load_trace(trace_replay)
            )
            h = self.replay_trace.header
            n_dp, n_stages, step_time_s = h.n_dp, h.n_stages, h.step_time_s
            # the header's policy wins on replay: decisions must re-derive
            # from the same engine the recording ran
            ft_policy = h.policy or None
        self.policy_spec = ft_policy or ""
        if recorder is not None:
            recorder.policy = self.policy_spec
        self.controller = FTController(
            cfg=cfg, mecefo=mecefo, n_dp=n_dp, n_stages=min(n_stages, cfg.n_layers),
            global_batch=shape.global_batch,
            params_replicated=not self.parallel.fsdp,
        )
        from repro.ft.policy import make_policy

        self.controller.policy = make_policy(
            ft_policy,
            cost=(self.controller.incidents.mgr.cost
                  if self.controller.incidents is not None else None),
        )
        if self.replay_trace is not None:
            if self.replay_trace.header.n_stages != self.controller.n_stages:
                raise ValueError(
                    f"trace recorded for n_stages={self.replay_trace.header.n_stages}"
                    f" but this model clamps to {self.controller.n_stages}"
                )
            self.process = replay_engine(self.replay_trace, recorder=recorder)
        elif injectors is not None:
            self.process = ChaosEngine(
                n_dp, self.controller.n_stages, step_time_s,
                injectors=injectors, seed=seed + 1, recorder=recorder,
                elastic=elastic,
            )
        else:
            self.process = engine_for_scenario(
                scenario, n_dp, self.controller.n_stages, step_time_s,
                seed=seed + 1, recorder=recorder, elastic=elastic,
            )
        self.ckpt = (
            CheckpointManager(train.checkpoint_dir)
            if train.checkpoint_every
            else None
        )
        self._step_cache: Dict = {}
        self.history: List[Dict] = []
        self._obs_step_wall = obs.histogram("train.step.wall_s")
        self._obs_steps = obs.counter("train.steps_total")
        self._refresh_proj = None
        self._logged_reshard = None

        # -- live state transfer: replicated snapshots + real reshards ------
        self.xfer = None
        self._pending_rejoin: set = set()
        self._executed_reshard = None
        if statexfer:
            from repro.statexfer import StateTransferRegistry, tree_nbytes

            self.xfer = StateTransferRegistry(
                n_dp=self.controller.n_dp, cadence=snapshot_every,
                replicated=self.controller.params_replicated,
            )
            # accounting basis becomes the measured state size
            self.controller.state_nbytes = tree_nbytes(self.state)
            if self.controller.incidents is not None:
                # rejoin incidents now close on the measured receipt,
                # not on the planned-bytes attribution
                self.controller.incidents.expect_receipts = True

    # ------------------------------------------------------------------
    def _mask_plan(self) -> NDBPlan:
        """The plan the batch masks are built from: the controller's plan
        with rejoined-but-still-transferring ranks re-detached — masks only
        flip once a rank's state transfer has actually completed.  If EVERY
        active rank is mid-transfer, gating them all would zero-weight the
        whole batch (a silent wasted step), so the plan is left ungated and
        the pending ranks serve with the state they have."""
        plan = self.controller.plan
        active = set(plan.active_ranks())
        pending = self._pending_rejoin & active
        if not pending or pending == active:
            return plan
        return plan.detach(*sorted(pending))

    def _get_step(self, key):
        if key in self._step_cache:
            return self._step_cache[key]
        mode = key[0]
        kwargs = {}
        if mode == "static":
            keep, weight = plan_to_masks(
                self._mask_plan(), self.cfg, self.shape.global_batch
            )
            kwargs["static_ndb"] = (keep, weight)
        jitted, *_ = make_train_step(
            self.cfg, self.train_cfg, self.parallel, self.mecefo, self.mesh,
            self.shape, ndb_mode=mode, total_steps=max(self.train_cfg.steps, 1),
            donate=False, **kwargs,
        )
        self._step_cache[key] = jitted
        return jitted

    def _step_key(self):
        if self.mecefo.mode == "off" or self.controller.plan.is_healthy():
            return ("off",)
        if self.mecefo.mode == "dynamic":
            return ("dynamic",)
        # static mode bakes the masks: pending transfers are part of the key
        return (
            ("static",) + self.controller.compile_key()
            + tuple(sorted(self._pending_rejoin))
        )

    def _run_state_transfers(self, step_idx: int) -> None:
        """Execute any new ReshardPlan on real arrays and retry gated ranks."""
        ckpt_dir = self.train_cfg.checkpoint_dir if self.ckpt else None
        rp = self.controller.last_reshard
        if rp is not None and rp is not self._executed_reshard:
            self._executed_reshard = rp
            out = self.xfer.on_reshard(
                rp, self.state, step_idx,
                ckpt_like=self.state, ckpt_dir=ckpt_dir,
            )
            for receipt in out.receipts:
                self.controller.record_transfer(receipt)
        if self.xfer.pending:
            for receipt in self.xfer.retry_pending(
                step_idx, ckpt_like=self.state, ckpt_dir=ckpt_dir
            ):
                self.controller.record_transfer(receipt)
        self._pending_rejoin = set(self.xfer.pending)

    def _record_step(self, i, step_idx, dt, loss, grad_norm, outcome, slow,
                     log_every):
        """A finished step's telemetry, flight frame, history row and logs."""
        self._obs_step_wall.observe(dt)
        self._obs_steps.inc()
        self.controller.observe_step_time(dt)
        if self.controller.incidents is not None:
            # one flight-recorder frame per step (wall_s/snap_blocked_s are
            # unpinned; the rest replay bit-exactly)
            self.controller.incidents.record_frame(
                step_idx,
                wall_s=dt,
                goodput=self.controller.plan.dp_size(),
                dp_size=self.controller.plan.dp_size(),
                failed=len(self.controller.plan.failed),
                pending=len(self._pending_rejoin),
                snap_blocked_s=(
                    self.xfer.telemetry()["snapshot_blocked_s"]
                    if self.xfer is not None else None
                ),
            )
        rec = {
            "step": step_idx,
            "loss": loss,
            "grad_norm": grad_norm,
            "seconds": dt,
            "failed": len(self.controller.plan.failed),
            "stragglers": len(slow),
            "net_inflation": outcome.net_inflation,
            "degraded_frac": self.controller.degraded_layer_fraction(),
            "dp_size": self.controller.plan.dp_size(),
            "pending_rejoin": len(self._pending_rejoin),
        }
        self.history.append(rec)
        rp = self.controller.last_reshard
        if log_every and rp is not None and rp is not self._logged_reshard:
            self._logged_reshard = rp  # each resize produces a fresh plan
            measured = ""
            if self.xfer is not None:
                acc = self.controller.accounting
                measured = (
                    f" measured={acc.measured_transfer_bytes/1e6:.1f}MB"
                    f" pending={sorted(self._pending_rejoin)}"
                )
            _log.info(
                "step %5d elastic resize: dp %d->%d dropped=%s "
                "rejoined=%s transfer=%.1fMB (%s)%s",
                step_idx, len(rp.old_active), rp.dp_size,
                list(rp.dropped), list(rp.rejoined),
                rp.transfer_bytes / 1e6, rp.source, measured,
            )
        if log_every and i % log_every == 0:
            _log.info(
                "step %5d loss %.4f gnorm %.3f %.0fms failed=%d "
                "slow=%d deg=%.2f dp=%d",
                rec["step"], rec["loss"], rec["grad_norm"], dt * 1e3,
                rec["failed"], rec["stragglers"], rec["degraded_frac"],
                rec["dp_size"],
            )

    # ------------------------------------------------------------------
    def run(self, steps: Optional[int] = None, log_every: int = 10):
        steps = steps or self.train_cfg.steps
        for i in range(steps):
            with obs.span("trainer.step"):
                t0 = time.time()
                step_idx = int(self.state.step)
                outcome = self.process.step(step_idx)
                _, slow = self.controller.apply_chaos(outcome)
                if (self.controller.policy is not None
                        and self.process.recorder is not None):
                    # pin this step's committed decisions right after its
                    # events — replay re-derives and verifies them
                    for dec in self.controller.policy.drain():
                        self.process.recorder.record_decision(dec)
                if self.xfer is not None:
                    with obs.span("trainer.state_transfers"):
                        self._run_state_transfers(step_idx)

                with obs.span("trainer.feed"):
                    batch = make_batch(
                        self.cfg, self.shape, step_idx, source=self.source,
                        seed=self.seed,
                    )
                key = self._step_key()
                args = (self.state, batch)
                if key[0] == "dynamic":
                    with obs.span("trainer.masks"):
                        keep, weight = plan_to_masks(
                            self._mask_plan(), self.cfg, self.shape.global_batch
                        )
                    args += ({"keep": keep, "example_weight": weight},)
                with obs.span("trainer.dispatch"), self.mesh:
                    self.state, metrics = self._get_step(key)(*args)

                # technique III: refresh V1 every tau steps (Alg. 3)
                if (
                    self.mecefo.mode != "off"
                    and self.mecefo.lowrank_wgrad
                    and step_idx % self.mecefo.svd_period == 0
                ):
                    with obs.span("lowrank.refresh"), self.mesh:
                        self.state = self.state._replace(
                            proj=refresh_projections(
                                self.state.params, self.cfg, self.mecefo.rank
                            )
                        )

                if self.xfer is not None:
                    # hot-spare snapshot of the post-step state (async, double-
                    # buffered: only the thread launch blocks this loop)
                    self.xfer.on_step(self.state, step_idx, self.controller.plan)

                if self.ckpt and step_idx and step_idx % self.train_cfg.checkpoint_every == 0:
                    self.ckpt.save_async(self.state, step_idx)

                # the host read ends the step: its wall time covers the
                # device work, not only the dispatch
                with obs.span("trainer.read"):
                    loss = float(metrics["loss"])
                    grad_norm = float(metrics["grad_norm"])
                dt = time.time() - t0
                with obs.span("trainer.record"):
                    self._record_step(i, step_idx, dt, loss, grad_norm,
                                      outcome, slow, log_every)
        if self.ckpt:
            self.ckpt.wait()
        if self.xfer is not None:
            self.xfer.wait()
        if self.process.recorder is not None:
            self.process.recorder.close(
                total_steps=len(self.history),
                accounting=self.controller.accounting.as_dict(),
            )
        if self.controller.incidents is not None:
            # recovery that never completed in-trace -> unclosed: true
            self.controller.incidents.finalize(len(self.history))
        return self.history

    def verify_replay(self) -> List[str]:
        """After a replay run: mismatches vs the recorded trace (empty = OK)."""
        assert self.replay_trace is not None, "trainer not in replay mode"
        return verify_replay(
            self.replay_trace, self.process,
            accounting=self.controller.accounting.as_dict(),
            decisions=(self.controller.policy.decisions
                       if self.controller.policy is not None else None),
        )

    def resume_from_checkpoint(self) -> bool:
        if not self.ckpt:
            return False
        out = self.ckpt.restore_latest(self.state)
        if out is None:
            return False
        self.state, _step = out
        return True


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-350m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mecefo", default="off", choices=["off", "static", "dynamic"])
    ap.add_argument("--scenario", default="none", choices=list(SCENARIOS))
    ap.add_argument(
        "--chaos", default=None, choices=list(CHAOS_PRESETS),
        help="chaos preset (injector bundle) layered on --scenario's rates",
    )
    ap.add_argument(
        "--trace", nargs=2, metavar=("MODE", "PATH"), default=None,
        help="'record PATH' writes a chaos trace; 'replay PATH' reproduces "
             "one bit-exactly and verifies events + accounting against it",
    )
    ap.add_argument(
        "--replay-record", metavar="PATH", default=None,
        help="while replaying, also record the replayed event stream to PATH "
             "(CI uploads it as the divergence artifact when a replay fails)",
    )
    ap.add_argument("--n-dp", type=int, default=4)
    ap.add_argument("--n-stages", type=int, default=8)
    ap.add_argument(
        "--statexfer", action="store_true",
        help="enable the live state-transfer subsystem: in-memory replicated "
             "snapshots, real ReshardPlan execution on rejoin, measured "
             "transfer accounting",
    )
    ap.add_argument(
        "--snapshot-every", type=int, default=1, metavar="N",
        help="statexfer snapshot cadence in steps (default 1)",
    )
    ap.add_argument(
        "--ft-policy", metavar="SPEC", default=None,
        help="recovery-policy selection: 'adaptive' (pick the cheapest "
             "path per event from CostModel estimates, priors until "
             "confident) or 'fixed:<path>' (e.g. fixed:peer_restore); "
             "default: the legacy static dispatch",
    )
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "sgdm"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--obs-out", metavar="PATH", default=None,
        help="write run telemetry (metrics + span timeline) as JSONL to "
             "PATH, the Prometheus exposition to PATH.prom, and render the "
             "run report (see docs/observability.md)",
    )
    ap.add_argument(
        "--incidents-out", metavar="PATH", default=None,
        help="write the incident log (flight-recorder windows + attributed "
             "recovery costs) as JSONL to PATH; render with "
             "'python -m repro.obs incidents PATH'",
    )
    args = ap.parse_args(argv)
    obs.logging_setup()
    enable_compile_cache()

    trace_mode, trace_path = args.trace or (None, None)
    if trace_mode not in (None, "record", "replay"):
        ap.error(f"--trace mode must be 'record' or 'replay', got {trace_mode!r}")
    if args.replay_record and trace_mode != "replay":
        ap.error("--replay-record requires --trace replay PATH")
    if args.ft_policy is not None:
        from repro.ft.policy import parse_policy

        try:
            parse_policy(args.ft_policy)
        except ValueError as e:
            ap.error(str(e))

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg, dtype="float32")
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    steps = args.steps
    replay_trace = None
    if trace_mode == "replay":
        replay_trace = load_trace(trace_path)
        if replay_trace.footer is not None:
            # replay the exact recorded run length
            steps = replay_trace.footer.total_steps
    train = TrainConfig(
        steps=steps, optimizer=args.optimizer, learning_rate=args.lr,
        checkpoint_every=args.checkpoint_every, seed=args.seed,
    )
    mecefo = MeCeFOConfig(mode=args.mecefo, rank=16, svd_period=20)
    scenario = SCENARIOS[args.scenario]
    injectors = (
        chaos_preset(args.chaos, scenario) if args.chaos is not None else None
    )
    trainer = Trainer(
        cfg, shape, train, mecefo=mecefo,
        scenario=scenario,
        n_dp=args.n_dp, n_stages=args.n_stages,
        step_time_s=3600.0 if (args.scenario != "none" or args.chaos) else 1.0,
        seed=args.seed,
        injectors=injectors,
        trace_record=(
            trace_path if trace_mode == "record" else args.replay_record
        ),
        trace_replay=replay_trace,
        statexfer=args.statexfer,
        snapshot_every=args.snapshot_every,
        ft_policy=args.ft_policy,
    )
    run_meta = {
        "run": "train", "arch": args.arch,
        "mecefo": args.mecefo, "scenario": args.scenario,
        "chaos": args.chaos, "statexfer": args.statexfer,
        "ft_policy": trainer.policy_spec or None,
    }
    disarm = None
    if args.obs_out or args.incidents_out:
        # flush-on-death: a crashed/killed run still emits partial dumps
        disarm = obs.install_crash_flush(
            obs_path=args.obs_out, incidents_path=args.incidents_out,
            incidents=trainer.controller.incidents, meta=run_meta,
        )
    hist = trainer.run()
    if disarm is not None:
        disarm()
    acc = trainer.controller.accounting
    _log.info(
        "final loss %.4f  failovers=%d recoveries=%d rank_drops=%d "
        "rejoins=%d dp=%d/%d peer_fetch=%.1fMB",
        hist[-1]["loss"], acc.n_failovers, acc.n_recoveries,
        acc.n_rank_drops, acc.n_rejoins,
        trainer.controller.plan.dp_size(), trainer.controller.n_dp,
        acc.peer_fetch_bytes / 1e6,
    )
    if trainer.xfer is not None:
        tele = trainer.xfer.telemetry()
        _log.info(
            "statexfer: %.0f snapshot cycles (%.1fMB replicated, %.1fms "
            "blocked) restores peer=%.0f ckpt=%.0f measured=%.1fMB in %.1fms",
            tele["snapshot_cycles"], tele["snapshot_bytes"] / 1e6,
            tele["snapshot_blocked_s"] * 1e3, tele["n_peer_restores"],
            tele["n_ckpt_restores"], tele["measured_transfer_bytes"] / 1e6,
            tele["transfer_s"] * 1e3,
        )
    if args.obs_out:
        import sys

        dump_path = obs.dump(args.obs_out, meta={**run_meta, "steps": len(hist)})
        _log.info("obs telemetry written to %s (+ .prom)", dump_path)
        sys.stdout.write(obs.render_report_file(dump_path))
    if args.incidents_out and trainer.controller.incidents is not None:
        inc_path = obs.write_incident_log(
            args.incidents_out, trainer.controller.incidents.mgr,
            meta={**run_meta, "steps": len(hist)},
        )
        _log.info("incident log written to %s (%d incidents)", inc_path,
                  len(trainer.controller.incidents.mgr.incidents))
    if trace_mode == "record":
        _log.info("chaos trace recorded to %s (%d events)",
                  trace_path, len(trainer.process.events))
    if trace_mode == "replay":
        problems = trainer.verify_replay()
        if problems:
            _log.error("REPLAY MISMATCH vs %s:", trace_path)
            for p in problems:
                _log.error("  %s", p)
            return 1
        _log.info("REPLAY OK: %d events and accounting totals match %s",
                  len(trainer.process.events), trace_path)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
