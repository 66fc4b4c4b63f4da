"""Fault-tolerance runtime: failure process, controller, elastic, straggler."""
import numpy as np
import pytest

from repro.configs.base import MeCeFOConfig
from repro.core.ndb import NDBPlan
from repro.ft.controller import FTController
from repro.ft.failures import SCENARIOS, FailureProcess, FailureScenario
from tests.conftest import TINY_DENSE


def test_failure_rate_matches_scenario():
    sc = FailureScenario("t", fail_interval_s=100.0, recover_time_s=1e9)
    proc = FailureProcess(sc, n_dp=4, n_stages=8, step_time_s=1.0, seed=0)
    for step in range(2000):
        proc.step(step)
    fails = [e for e in proc.events if e.kind == "fail"]
    # expected ~ 2000 steps * (1 failure / 100 s) = 20 (one step = 1 s)
    assert 8 <= len(fails) <= 40


def test_recovery_timing():
    sc = FailureScenario("t", fail_interval_s=1e9, recover_time_s=5.0)
    proc = FailureProcess(sc, 2, 2, step_time_s=1.0, seed=0)
    proc.inject(0, (0, 1), down_steps=5)
    assert (0, 1) in proc.step(1).failed
    assert (0, 1) in proc.step(4).failed
    assert (0, 1) not in proc.step(5).failed
    kinds = [e.kind for e in proc.events]
    assert kinds == ["fail", "recover"]


def test_persistent_subset_asymmetric():
    """Appendix C.2: failures restricted to a fixed subset of devices."""
    sc = FailureScenario("t", fail_interval_s=10.0, recover_time_s=20.0)
    allowed = {(0, 0), (1, 1)}
    proc = FailureProcess(sc, 2, 2, 1.0, seed=1, persistent_subset=allowed)
    for step in range(500):
        proc.step(step)
    failed_devs = {e.device for e in proc.events if e.kind == "fail"}
    assert failed_devs and failed_devs <= allowed


def test_controller_accounting_and_compile_key():
    ctl = FTController(
        cfg=TINY_DENSE, mecefo=MeCeFOConfig(mode="static"),
        n_dp=2, n_stages=2, global_batch=4,
    )
    assert ctl.compile_key() == ("healthy",)
    plan = NDBPlan(2, 2, frozenset({(0, 1)}))
    assert ctl.update_plan(plan)
    assert ctl.accounting.n_failovers == 1
    assert ctl.accounting.peer_fetch_bytes > 0
    key = ctl.compile_key()
    assert key == (2, 2, ((0, 1),), ())
    # recovery refetches from the neighbor
    assert ctl.update_plan(NDBPlan(2, 2, frozenset()))
    assert ctl.accounting.n_recoveries == 1


def test_controller_checkpoint_recovery_under_fsdp():
    ctl = FTController(
        cfg=TINY_DENSE, mecefo=MeCeFOConfig(mode="static"),
        n_dp=2, n_stages=2, global_batch=4, params_replicated=False,
    )
    ctl.update_plan(NDBPlan(2, 2, frozenset({(1, 0)})))
    assert ctl.accounting.ckpt_restore_bytes > 0
    assert ctl.accounting.peer_fetch_bytes == 0


def test_elastic_rank_drop():
    ctl = FTController(
        cfg=TINY_DENSE, mecefo=MeCeFOConfig(mode="dynamic"),
        n_dp=2, n_stages=2, global_batch=4,
    )
    whole_rank = frozenset({(0, 0), (0, 1)})
    ctl.update_plan(NDBPlan(2, 2, whole_rank))
    assert ctl.accounting.n_rank_drops == 1
    ctx = ctl.context()
    assert ctx.example_weight is not None
    np.testing.assert_array_equal(
        np.asarray(ctx.example_weight), [0, 0, 1, 1]
    )


def test_elastic_detached_rank_rebalances_batch():
    """A *detached* rank (formal resize) redistributes its batch share to
    the survivors instead of zero-weighting it."""
    ctl = FTController(
        cfg=TINY_DENSE, mecefo=MeCeFOConfig(mode="dynamic"),
        n_dp=2, n_stages=2, global_batch=4,
    )
    plan = NDBPlan(2, 2, frozenset({(0, 0), (0, 1)})).detach(0)
    ctl.update_plan(plan)
    assert ctl.plan.dp_size() == 1
    assert ctl.batch_shares() == {1: 4}
    ctx = ctl.context()
    np.testing.assert_array_equal(np.asarray(ctx.example_weight), [1, 1, 1, 1])
    rp = ctl.last_reshard
    assert rp is not None and rp.dropped == (0,) and rp.shares == {1: 4}
    # rejoin: membership restored, full-state transfer accounted
    before = ctl.accounting.peer_fetch_bytes
    ctl.update_plan(ctl.plan.rejoin(0))
    assert ctl.plan.is_healthy() and ctl.plan.dp_size() == 2
    assert ctl.accounting.n_rejoins == 1
    assert ctl.accounting.peer_fetch_bytes - before == 2 * ctl.stage_param_bytes()
    assert ctl.last_reshard.rejoined == (0,)
    assert ctl.batch_shares() == {0: 2, 1: 2}


def test_rejoin_under_fsdp_restores_from_checkpoint():
    ctl = FTController(
        cfg=TINY_DENSE, mecefo=MeCeFOConfig(mode="dynamic"),
        n_dp=2, n_stages=2, global_batch=4, params_replicated=False,
    )
    ctl.update_plan(NDBPlan(2, 2, frozenset({(1, 0), (1, 1)})).detach(1))
    ctl.update_plan(ctl.plan.rejoin(1))
    assert ctl.accounting.n_rejoins == 1
    assert ctl.accounting.ckpt_restore_bytes > 0
    assert ctl.accounting.peer_fetch_bytes == 0
    assert ctl.last_reshard.source == "ckpt"


def test_straggler_detection_reuses_ndb():
    ctl = FTController(
        cfg=TINY_DENSE, mecefo=MeCeFOConfig(mode="dynamic"),
        n_dp=2, n_stages=2, global_batch=4,
    )
    times = {(r, s): 1.0 for r in range(2) for s in range(2)}
    assert ctl.detect_straggler(times) is None
    times[(1, 0)] = 10.0
    plan = ctl.detect_straggler(times)
    assert plan is not None and (1, 0) in plan.failed


def test_degraded_fraction():
    ctl = FTController(
        cfg=TINY_DENSE, mecefo=MeCeFOConfig(mode="dynamic"),
        n_dp=4, n_stages=2, global_batch=8,
    )
    assert ctl.degraded_layer_fraction() == 0.0
    ctl.update_plan(NDBPlan(4, 2, frozenset({(0, 0)})))
    # rank 0: both stages degraded (failed + neighbor) -> 1/4 of cells
    assert ctl.degraded_layer_fraction() == pytest.approx(0.25)


def test_table1_scenarios_registered():
    for name in ("low", "mid", "high", "higher", "none"):
        assert name in SCENARIOS
    assert SCENARIOS["high"].fail_interval_s == 1800.0
    assert SCENARIOS["high"].recover_time_s == 7200.0


def test_grad_compression_psum():
    """int8-compressed psum ~ exact psum (shard_map path)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core.grad_sync import compress_psum

    devs = np.array(jax.devices()[:1])
    mesh = Mesh(devs, ("data",))
    g = {"w": jnp.linspace(-3, 3, 8192).reshape(64, 128)}

    def sync(g):
        return compress_psum(g, "data", method="int8")

    out = shard_map(
        sync, mesh=mesh, in_specs=({"w": P()},), out_specs={"w": P()}
    )(g)
    err = float(jnp.max(jnp.abs(out["w"] - g["w"])))
    assert err <= float(jnp.max(jnp.abs(g["w"]))) / 127 + 1e-6
