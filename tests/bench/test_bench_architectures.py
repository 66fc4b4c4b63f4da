"""Configuration files for every architecture the program runs: the
program's model built field by field from ``harness_keys``, the reference a
file names, and the training driver over tiny expert and hybrid models on
the CPU, with no harness file edited."""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import pytest

import benchtiny as bt
from bench import cell as cells
from bench import program

QWEN3 = bt.ROOT / "bench/configs/qwen3-0.6b.json"
LIMITS = {"loss_gap": 2e-4, "grad_gap": 6e-3, "delta_gap": 3.5e-3}


def train_driver():
    return cells.load_module(bt.ROOT / "bench/drivers/train.py")


def test_the_dense_file_gives_the_model_it_gave_before():
    from repro.configs.base import get_config

    config = cells.load_json(QWEN3)
    cfg = program.model_config(config, cells.model_sizes(config))
    assert cfg == dataclasses.replace(
        get_config("qwen3-0.6b"), n_layers=28, d_model=1024, n_heads=16,
        n_kv_heads=8, head_dim=128, d_ff=3072, vocab_size=151936,
        norm_eps=1e-6, rope_theta=1000000, qk_norm=True, tie_embeddings=True,
        dtype="bfloat16")


def test_the_dense_file_names_the_dense_reference():
    ref = cells.reference_for(cells.load_json(QWEN3))
    assert ref.__file__ == str(bt.ROOT / "bench/refmodel.py")


@pytest.mark.parametrize("arch", sorted(bt.TINY_ARCHS))
def test_a_non_dense_file_gives_the_reduced_registry_entry(arch):
    from repro.configs.base import get_config, reduced

    repro, sizes = bt.TINY_ARCHS[arch]
    base = get_config(repro)
    # reduced() also shrinks the vision patches, which a text model keeps;
    # Jamba states its head size (d / H) and is cut to one period
    over = {"n_patches": base.n_patches}
    if arch == "hybrid":
        over.update(n_layers=8, head_dim=32)
    config = bt.arch_config(arch)
    assert program.model_config(config, cells.model_sizes(config)) == reduced(
        base, **over)


def test_a_sub_config_the_entry_lacks_is_made_from_its_defaults():
    from repro.configs.base import MoEConfig

    sizes = {"moe.n_experts": 4, "moe.top_k": 1, "moe.d_ff_expert": 32}
    config = bt.tiny_config(**sizes, harness_keys=dict(
        bt.TINY_KEYS, **{k: k for k in sizes}))
    cfg = program.model_config(config, cells.model_sizes(config))
    assert cfg.moe == MoEConfig(n_experts=4, top_k=1, d_ff_expert=32)
    assert cfg.ssm is None


@pytest.mark.parametrize("key, says", [
    ("no_such_field", "'no_such_field' is no field"),
    ("moe.no_such_field", "'moe.no_such_field' is no field"),
    ("ssm.no_such_field", "'ssm.no_such_field' is no field"),
    ("n_layers.depth", "'n_layers' is no sub-config")])
def test_a_harness_key_that_is_no_field_raises(key, says):
    config = bt.arch_config("hybrid", **{key: 1})
    config["harness_keys"] = dict(config["harness_keys"], **{key: key})
    with pytest.raises(ValueError, match=says):
        program.model_config(config, cells.model_sizes(config))


def test_a_file_that_leaves_its_layer_pattern_to_the_registry_raises():
    config = bt.arch_config("hybrid")
    del config["harness_keys"]["family"]
    with pytest.raises(ValueError, match="attn_every"):
        program.model_config(config, cells.model_sizes(config))


@pytest.mark.parametrize("key", ["moe.top_k", "ssm.chunk"])
def test_a_file_that_leaves_a_counted_size_to_the_defaults_raises(key):
    """The program would run the sub-config's default; the FLOP count has
    none to read."""
    config = bt.arch_config("hybrid")
    del config["harness_keys"][key]
    with pytest.raises(ValueError, match=f"reads \\['{key}'\\]"):
        program.model_config(config, cells.model_sizes(config))


@pytest.mark.parametrize("named", [False, True])
@pytest.mark.parametrize("arch", sorted(bt.TINY_ARCHS))
def test_the_dense_reference_stops_a_non_dense_file_at_set_up(arch, named):
    config = bt.arch_config(arch)
    if named:
        config["reference"] = "refmodel"
    drv = train_driver()

    def never(*a, **k):
        raise AssertionError("the trainer was built")

    drv.build_trainer = never
    ctx = bt.Ctx(config, bt.train_traffic(limits=dict(LIMITS)))
    with pytest.raises(ValueError, match="covers dense SwiGLU only"):
        drv.setup(ctx)


@pytest.mark.parametrize("arch", sorted(bt.TINY_ARCHS))
def test_the_driver_runs_a_non_dense_file_with_the_reference_it_names(
        arch, monkeypatch):
    from repro.models.params import init_params

    config = bt.arch_config(arch, reference="recording_reference")
    monkeypatch.setattr(cells, "BENCH", bt.ROOT / "tests/bench")
    ctx = bt.Ctx(config, bt.train_traffic(limits=dict(LIMITS)))
    cfg = program.model_config(config, ctx.model)
    shapes = jax.eval_shape(partial(init_params, cfg), jax.random.PRNGKey(0))
    monkeypatch.setattr(bt, "REFERENCE_LEAVES",
                        sorted(program.named_leaves(shapes)))
    monkeypatch.setattr(bt, "REFERENCE_CALLS", [])

    res, correct, checks = bt.run_cell(ctx)
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert res["metrics"]["train_tokens_per_s"] > 0
    assert [c[0] for c in bt.REFERENCE_CALLS] == [
        "accept", "accept", "run_training"]
    _, m, seed, n_batches, keep_shape, quant = bt.REFERENCE_CALLS[-1]
    assert m == ctx.model and seed == ctx.seed and n_batches == 3
    assert keep_shape == (cfg.n_layers, 4) and quant is None
    # the stand-in's unit readings are no model's: compared, and failed
    assert set(checks) == set(LIMITS) and not correct
    assert all(math.isfinite(c["value"]) for c in checks.values())
