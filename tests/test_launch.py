"""Launch-layer units: input specs, batch-axis policy, roofline model,
accumulation policy, trainer compile-cache, straggler integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import (
    SHAPES,
    MeCeFOConfig,
    ParallelConfig,
    ShapeConfig,
    TrainConfig,
    get_config,
    reduced,
)
from repro.launch.specs import batch_axes_for, input_specs, ndb_specs
from repro.parallel.sharding import ShardingRules


RULES = ShardingRules()
MSD = {"pod": 2, "data": 16, "model": 16}


def test_batch_axes_divisibility():
    assert batch_axes_for(256, RULES, MSD) == ("pod", "data")
    assert batch_axes_for(32, RULES, MSD) == ("pod", "data")
    assert batch_axes_for(1, RULES, MSD) is None
    assert batch_axes_for(2, RULES, MSD) == ("pod",)


@pytest.mark.parametrize("arch", ["glm4-9b", "mamba2-2.7b", "phi-3-vision-4.2b",
                                  "musicgen-medium"])
@pytest.mark.parametrize("shape_name", ["train_4k", "prefill_32k", "decode_32k"])
def test_input_specs_shapes(arch, shape_name):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    structs, specs = input_specs(cfg, shape, RULES, MSD)
    assert set(structs) == set(specs)
    if shape.kind == "train":
        assert "labels" in structs
    if shape.kind == "decode":
        assert structs["token"].shape == (shape.global_batch,)
        assert "caches" in structs
        # every cache leaf has a matching spec leaf
        cs = jax.tree.leaves(structs["caches"])
        sp = jax.tree.leaves(specs["caches"], is_leaf=lambda x: isinstance(x, P))
        assert len(cs) == len(sp)
        for leaf, spec in zip(cs, sp):
            assert len(spec) <= len(leaf.shape)
    if cfg.frontend == "vision" and shape.kind != "decode":
        assert structs["patch_embeds"].shape[1] == cfg.n_patches


def test_ndb_specs_match_masks():
    cfg = get_config("glm4-9b")
    structs, specs = ndb_specs(cfg, 256, ("pod", "data"))
    assert structs["keep"].shape == (cfg.n_layers, 256)
    assert specs["example_weight"] == P(("pod", "data"))


def test_model_flops_scaling():
    from repro.launch.roofline import model_flops

    cfg = get_config("glm4-9b")
    train = model_flops(cfg, SHAPES["train_4k"])
    prefill = model_flops(cfg, SHAPES["prefill_32k"])
    decode = model_flops(cfg, SHAPES["decode_32k"])
    # train ~ 3x a forward at the same token count; decode is tiny
    assert train > prefill > decode > 0
    # 6ND lower bound sanity: within 3x of the classic estimate
    import math

    n = cfg.param_count()
    d_tokens = 256 * 4096
    assert 0.5 * 6 * n * d_tokens < train < 3 * 6 * n * d_tokens


def test_moe_active_flops_counted():
    from repro.launch.roofline import model_flops

    moe = get_config("qwen3-moe-235b-a22b")
    dense_equiv = model_flops(moe, SHAPES["train_4k"])
    # active params 22B -> far less than a 235B-dense train step would be
    assert dense_equiv < 6 * moe.param_count() * 256 * 4096 * 0.5


def test_default_accum_reasonable():
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import default_accum

    mesh = make_host_mesh()
    cfg = reduced(get_config("glm4-9b"))
    assert default_accum(cfg, SHAPES["train_4k"], mesh) >= 1
    assert default_accum(cfg, SHAPES["decode_32k"], mesh) == 1


def test_host_mesh_refuses_more_devices_than_exist():
    from repro.launch.mesh import make_host_mesh

    n = len(jax.devices())
    assert make_host_mesh(data=n).devices.size == n
    with pytest.raises(ValueError, match="devices"):
        make_host_mesh(data=n + 1)


def test_compile_cache_dir_env_wins_else_checkout(monkeypatch, tmp_path):
    from pathlib import Path

    from repro.launch import compile_cache

    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv(compile_cache.ENV)
    repo = Path(__file__).resolve().parents[1]
    assert compile_cache.compile_cache_dir() == str(repo / ".jax_cache")


def test_trainer_starts_on_step_shardings_and_compiles_once():
    """The initial state is committed to the step's shardings, so step 1
    reuses step 0's executable instead of compiling it again."""
    from repro.ft.failures import SCENARIOS
    from repro.launch.train import Trainer
    from tests.conftest import TINY_DENSE

    compiles = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kw.get("fun_name"))

    tr = Trainer(
        TINY_DENSE, ShapeConfig("t", 16, 4, "train"), TrainConfig(steps=3),
        scenario=SCENARIOS["none"], n_dp=2, n_stages=2,
    )
    tr.run(1, log_every=0)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        tr.run(2, log_every=0)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []


def test_trainer_spans_cover_every_host_phase():
    """Each host phase of a training iteration is a named span under
    ``trainer.step``; the existing span paths keep their counts, and the
    flight-recorder frames no longer carry the tracer's ``span_s``."""
    from repro import obs
    from repro.ft.failures import SCENARIOS
    from repro.launch.train import Trainer
    from tests.conftest import TINY_DENSE

    tr = Trainer(
        TINY_DENSE, ShapeConfig("t", 16, 4, "train"), TrainConfig(steps=3),
        mecefo=MeCeFOConfig(mode="dynamic", rank=8, svd_period=2),
        scenario=SCENARIOS["none"], n_dp=2, n_stages=2,
    )
    tr.process.inject(0, (0, 1), down_steps=10)

    def totals():
        return {p: c for p, c, _ in obs.get_tracer().timeline()}

    before = totals()
    tr.run(3, log_every=0)
    after = totals()
    got = {p: after[p] - before.get(p, 0) for p in after
           if after[p] - before.get(p, 0)}
    assert got == {
        "trainer.step": 3,
        "trainer.step/controller.apply_chaos": 3,
        "trainer.step/trainer.feed": 3,
        "trainer.step/trainer.masks": 3,
        "trainer.step/trainer.dispatch": 3,
        # the step's one trace resolves the attention path
        "trainer.step/trainer.dispatch/kernel.select": 1,
        "trainer.step/lowrank.refresh": 2,   # steps 0 and 2
        "trainer.step/trainer.read": 3,
        "trainer.step/trainer.record": 3,
    }
    assert [h["step"] for h in tr.history] == [0, 1, 2]
    assert all(h["seconds"] > 0 for h in tr.history)
    frames = tr.controller.incidents.mgr.flight.frames()
    assert len(frames) == 3
    assert all("span_s" not in f and "wall_s" in f for f in frames)


def test_trainer_static_mode_compile_cache():
    """Static mode compiles one executable per distinct NDB plan."""
    from repro.ft.failures import SCENARIOS
    from repro.launch.train import Trainer
    from tests.conftest import TINY_DENSE

    shape = ShapeConfig("t", 16, 4, "train")
    tc = TrainConfig(steps=8, learning_rate=1e-3)
    tr = Trainer(
        TINY_DENSE, shape, tc, mecefo=MeCeFOConfig(mode="static", rank=8),
        scenario=SCENARIOS["none"], n_dp=2, n_stages=2,
    )
    tr.process.inject(2, (0, 1), down_steps=3)
    tr.run(log_every=0)
    keys = set(tr._step_cache)
    assert ("off",) in keys  # healthy executable
    assert any(k[0] == "static" for k in keys)  # plan-specialized executable
    assert len(keys) == 2


def test_trainer_straggler_plan_flows_into_context():
    from repro.ft.controller import FTController
    from tests.conftest import TINY_DENSE

    ctl = FTController(
        cfg=TINY_DENSE, mecefo=MeCeFOConfig(mode="dynamic"),
        n_dp=2, n_stages=2, global_batch=4,
    )
    plan = ctl.detect_straggler({(0, 0): 1.0, (0, 1): 1.0, (1, 0): 9.0, (1, 1): 1.0})
    ctl.update_plan(plan)
    ctx = ctl.context()
    keep = np.asarray(ctx.keep)
    # rank 1 degraded on all layers (straggler + its neighbor stage)
    assert keep[:, 2:].sum() == 0 and keep[:, :2].min() == 1


def test_sharding_rules_dedupe_conflicting_axes():
    import dataclasses

    r = dataclasses.replace(ShardingRules(), seq="model")
    # seq and mlp both want 'model': the later dim must yield
    assert r.spec("batch", "seq", "mlp") == P(("pod", "data"), "model", None)


def test_hlo_cost_ar_vs_rs_accounting():
    from repro.launch.hlo_cost import analyze

    # a psum whose result is used whole must be charged as 2x (all-reduce)
    txt = """
HloModule m

ENTRY %main (p: f32[1024,1024]) -> f32[1024,1024] {
  %p = f32[1024,1024] parameter(0)
  %ar = f32[1024,1024] all-reduce(%p), to_apply=%add
  ROOT %r = f32[1024,1024] add(%ar, %ar)
}
"""
    cost = analyze(txt)
    assert cost.collective_bytes == pytest.approx(2 * 1024 * 1024 * 4)
