"""Training attention's Pallas flash kernel (forward and backward, interpret
mode here) against the jnp path of ``causal_attention``, and the dispatch
that picks between them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.models.layers import attention_block, causal_attention

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}


def _qkvd(key, B, S, H, KV, hd, dt):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, S, H, hd), dt)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dt)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dt)
    do = jax.random.normal(ks[3], (B, S, H, hd), dt)
    return q, k, v, do


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("S", [128, 256])
@pytest.mark.parametrize("G", [1, 2])
def test_flash_forward_and_vjp_match_jnp(G, S, dt):
    """o, dq, dk and dv of the kernel (blocks smaller than S, unequal) match
    the jnp path's under one cotangent."""
    KV, hd = 2, 128
    q, k, v, do = _qkvd(jax.random.PRNGKey(G * 1000 + S), 2, S, G * KV, KV,
                        hd, dt)
    o, vjp = jax.vjp(
        lambda q, k, v: fa.flash_attention(
            q, k, v, block_q=S // 2, block_k=S // 4, interpret=True),
        q, k, v)
    r, vjp_r = jax.vjp(lambda q, k, v: causal_attention(q, k, v, chunk=S),
                       q, k, v)
    assert o.dtype == r.dtype == dt
    assert _rel(o, r) < TOL[dt]
    for name, got, want in zip(("dq", "dk", "dv"), vjp(do), vjp_r(do)):
        assert got.shape == want.shape and got.dtype == want.dtype, name
        assert _rel(got, want) < TOL[dt], name


def test_degraded_examples_get_no_gradient_through_flash(monkeypatch):
    """keep = 0 for one example: its gradient into the block's input is
    the residual's alone, and no weight gradient comes from it."""
    from repro.configs.base import ModelConfig
    from repro.models.params import init_params
    from repro.parallel.sharding import ShardingRules

    monkeypatch.setattr(ops, "resolve_attention_impl",
                        lambda *a, **kw: "flash")
    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=256,
                      n_heads=2, n_kv_heads=1, head_dim=128, d_ff=256,
                      vocab_size=64, dtype="float32")
    p = init_params(cfg, jax.random.PRNGKey(0))["layers"][0]["mixer"]
    p = jax.tree.map(lambda a: a[0], p)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 256))
    dy = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    keep = jnp.array([1.0, 0.0])

    def f(p, x):
        y, _ = attention_block(p, x, cfg, ShardingRules(), keep,
                               jnp.arange(128))
        return jnp.sum(y * dy)

    gp, gx = jax.grad(f, argnums=(0, 1))(p, x)
    np.testing.assert_array_equal(gx[1], dy[1])
    assert float(jnp.abs(gx[0] - dy[0]).max()) > 0
    # the weight gradients come from example 0 alone
    gp0, _ = jax.grad(f, argnums=(0, 1))(
        p, x.at[1].set(jax.random.normal(jax.random.PRNGKey(3), x[1].shape)))
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gp0)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


QWEN3_06B = (4, 1024, 16, 128)  # the benchmark cell's (B, S, H, hd), KV 8


@pytest.mark.parametrize("shape,kv,kw,want", [
    (QWEN3_06B, 8, dict(backend="tpu", n_devices=1), "flash"),
    (QWEN3_06B, 8, dict(backend="cpu", n_devices=1), "jnp"),
    ((4, 1000, 16, 128), 8, dict(backend="tpu", n_devices=1), "jnp"),
    (QWEN3_06B, 8, dict(backend="tpu", n_devices=1, cached=True), "jnp"),
    (QWEN3_06B, 8, dict(backend="tpu", n_devices=4), "jnp"),
    ((4, 1024, 16, 64), 8, dict(backend="tpu", n_devices=1), "jnp"),
    ((1, 256, 8, 128), 1, dict(backend="tpu", n_devices=1), "flash"),
], ids=["cell-tpu", "cpu", "S1000", "cache", "4-devices", "hd64", "short"])
def test_resolve_attention_impl(shape, kv, kw, want):
    assert ops.resolve_attention_impl(shape, kv, **kw) == want


def test_qwen3_trace_counts_28_flash_sites(monkeypatch):
    """A trace of the qwen3-0.6b train loss at the cell's shape, resolved
    as on one TPU chip, puts all 28 scanned layers on the kernel."""
    from jax.sharding import Mesh

    from repro.configs.base import (MeCeFOConfig, ParallelConfig,
                                    ShapeConfig, TrainConfig, get_config)
    from repro.core.ndb import NDBContext
    from repro.launch.specs import input_specs
    from repro.launch.state import state_structs
    from repro.launch.steps import build_flags, build_rules
    from repro.models.model import forward_loss

    real = ops.resolve_attention_impl
    monkeypatch.setattr(
        ops, "resolve_attention_impl",
        lambda shape, kv, **kw: real(shape, kv, **kw, backend="tpu",
                                     n_devices=1))
    cfg = get_config("qwen3-0.6b")
    shape = ShapeConfig("cell", 1024, 4, "train")
    parallel = ParallelConfig(fsdp=False, remat="ffn", scan_layers=True)
    mecefo = MeCeFOConfig(mode="off")
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    rules = build_rules(cfg, mesh, parallel)
    flags = build_flags(cfg, parallel, mesh, shape)
    params = state_structs(cfg, TrainConfig(), mecefo).params
    batch, _ = input_specs(cfg, shape, rules, {"data": 1, "model": 1})
    ctx = NDBContext(mode="off", mecefo=mecefo)

    def loss(p, b):
        return forward_loss(p, None, b, cfg, rules, ctx, flags)[0]

    before = obs.get_registry().snapshot()
    jax.eval_shape(jax.grad(loss), params, batch)
    after = obs.get_registry().snapshot()
    key = "kernels.attention_sites{impl=flash}"
    assert after.get(key, 0) - before.get(key, 0) == cfg.n_layers == 28
    assert (after.get("kernels.attention_sites{impl=jnp}", 0)
            == before.get("kernels.attention_sites{impl=jnp}", 0))
