"""The benchmark's check on a tiny cell on the CPU: a sound run comes out
correct, the control (the reference one precision below, fp8) and each
fault a cell can have come out not correct.

The limits here are for the tiny size (bf16, 4 layers of width 64), set
between the tiny program's readings and the tiny control's the way the
chip cells' limits are set (PERF.md gives those readings).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import benchtiny as bt
from bench import cell as cells

TRAIN_LIMITS = {"loss_gap": 2e-4, "grad_gap": 6e-3, "delta_gap": 3.5e-3}
SERVE_LIMITS = {"token_gap": 0.01}


def train_driver():
    return cells.load_module(bt.ROOT / "bench/drivers/train.py")


def serve_driver():
    return cells.load_module(bt.ROOT / "bench/drivers/serve.py")


def train_ctx(traffic="train.failover"):
    return bt.Ctx(bt.tiny_config(),
                  bt.train_traffic(traffic, limits=dict(TRAIN_LIMITS)))


def serve_ctx():
    return bt.Ctx(bt.tiny_config(), bt.serve_traffic(limits=SERVE_LIMITS))


@pytest.fixture
def fresh_jit_caches():
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("traffic", ["train.failover", "train.healthy"])
def test_a_sound_training_run_is_correct(traffic):
    res, correct, checks = bt.run_cell(train_ctx(traffic))
    assert correct, checks
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert res["metrics"]["train_tokens_per_s"] > 0


def test_the_training_control_is_not_correct():
    ctx = train_ctx()
    drv = train_driver()
    job = drv.job_of(ctx.cell.config, ctx.cell.traffic)
    ref = drv.reference(ctx, job)
    gaps = drv.compare(drv.reference(ctx, job, quant="fp8"), ref)
    assert any(gaps[k] > TRAIN_LIMITS[k] for k in gaps), gaps


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch, fresh_jit_caches):
    import repro.launch.steps as steps

    monkeypatch.setattr(steps, "apply_update",
                        lambda params, grads, opt, *a, **k: (params, opt))
    _, correct, checks = bt.run_cell(train_ctx())
    assert not correct
    assert checks["delta_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch,
                                                   fresh_jit_caches):
    import repro.launch.train as train

    real = train.plan_to_masks

    def half(plan, cfg, batch):
        keep, weight = real(plan, cfg, batch)
        weight = weight.copy()
        weight[batch // 2:] = 0.0
        return keep, weight

    monkeypatch.setattr(train, "plan_to_masks", half)
    _, correct, checks = bt.run_cell(train_ctx())
    assert not correct, checks


def test_a_sound_serving_run_is_correct():
    res, correct, checks = bt.run_cell(serve_ctx(), seconds=1.5)
    assert correct, checks
    assert res["attempted"] > 5 and res["failed"] == 0
    assert res["metrics"]["serve_tokens_per_s"] > 0


def test_the_serving_control_is_not_correct():
    ctx = serve_ctx()
    drv = serve_driver()
    st = drv.setup(ctx)
    drv.window(st, 1.5, False)
    gaps = drv.reference_gaps(ctx, ctx.seed, drv.sample(st), quant="fp8")
    assert gaps["token_gap"] <= SERVE_LIMITS["token_gap"]
    assert gaps["control_gap"] > SERVE_LIMITS["token_gap"], gaps


def test_a_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch, fresh_jit_caches):
    import repro.serve.engine as engine

    real = engine.greedy_token

    def altered(logits, cfg):
        tok = real(logits, cfg)
        return jnp.where(jnp.arange(tok.shape[0]) == 0,
                         (tok + 1) % cfg.vocab_size, tok)

    monkeypatch.setattr(engine, "greedy_token", altered)
    _, correct, checks = bt.run_cell(serve_ctx(), seconds=1.5)
    assert not correct, checks
