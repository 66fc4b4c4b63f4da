"""Backend-gated kernel implementation selection + cross-impl bitwise pins.

Separate from tests/test_kernels.py on purpose: that module needs the
optional ``hypothesis`` extra and skips entirely without it, while the
compiled-vs-interpret and XLA-vs-Pallas bitwise contracts here are part
of the serving engine's correctness story and must run everywhere.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops


def _random_paged_layout(rng, B, P, n_pages):
    """Distinct random live pages per slot (null page 0 never handed out)."""
    perm = rng.permutation(np.arange(1, n_pages))
    return np.asarray(perm[: B * P].reshape(B, P), np.int32)


def _bitwise_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return (
        a.dtype == b.dtype and a.shape == b.shape
        and np.array_equal(a.view(np.uint8), b.view(np.uint8))
    )


def _compiled_or_skip(fn, *args, **kwargs):
    """Run a wrapper with its compiled lowering; skip where none exists
    (the pltpu kernels only compile on TPU — CPU raises at lowering)."""
    try:
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
        return out
    except Exception as e:  # lowering errors surface as ValueError etc.
        pytest.skip(
            f"no compiled lowering on {jax.default_backend()}: {e}"
        )


# ---------------------------------------------------------------------------
# backend-gated implementation selection
# ---------------------------------------------------------------------------


def test_resolve_paged_impl_table():
    assert ops.resolve_paged_impl(True, "cpu") == "pallas-interpret"
    assert ops.resolve_paged_impl(True, "tpu") == "pallas-interpret"
    assert ops.resolve_paged_impl(None, "tpu") == "pallas"
    assert ops.resolve_paged_impl(False, "tpu") == "pallas"
    assert ops.resolve_paged_impl(None, "cpu") == "xla"
    assert ops.resolve_paged_impl(False, "cpu") == "xla"
    assert ops.resolve_paged_impl(None, "gpu") == "xla"


def test_default_interpret_backend_derived():
    assert ops.default_interpret("tpu") is False
    assert ops.default_interpret("cpu") is True
    assert ops.default_interpret("gpu") is True


def test_kernel_tuning_validates_paged_impl():
    with pytest.raises(ValueError, match="paged_impl"):
        ops.KernelTuning(paged_impl="nope")


def test_configure_overrides_tuning():
    try:
        ops.configure(ops.KernelTuning(decode_block_k=64, paged_impl="xla"))
        assert ops.get_tuning().decode_block_k == 64
        assert ops.resolve_paged_impl(None, "cpu") == "xla"
    finally:
        ops.configure(None)
    assert ops.get_tuning("cpu").decode_block_k == 512


def test_tuning_pallas_off_tpu_falls_back():
    """A tuning table asking for compiled Pallas is only honored on TPU —
    elsewhere the walk must fall back to the XLA lowering."""
    try:
        ops.configure(ops.KernelTuning(paged_impl="pallas"))
        assert ops.resolve_paged_impl(None, "tpu") == "pallas"
        assert ops.resolve_paged_impl(None, "cpu") == "xla"
        assert ops.resolve_paged_impl(True, "cpu") == "pallas-interpret"
    finally:
        ops.configure(None)


# ---------------------------------------------------------------------------
# compiled-vs-interpret bitwise pins (skipped where no compiled lowering)
# ---------------------------------------------------------------------------


def test_rmsnorm_compiled_matches_interpret():
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
    s = jax.random.normal(jax.random.PRNGKey(1), (64,))
    compiled = _compiled_or_skip(ops.rmsnorm, x, s, interpret=False)
    assert _bitwise_equal(compiled, ops.rmsnorm(x, s, interpret=True))


def test_swiglu_compiled_matches_interpret():
    g = jax.random.normal(jax.random.PRNGKey(2), (32, 128))
    u = jax.random.normal(jax.random.PRNGKey(3), (32, 128))
    compiled = _compiled_or_skip(ops.swiglu, g, u, interpret=False)
    assert _bitwise_equal(compiled, ops.swiglu(g, u, interpret=True))


def test_flash_attention_compiled_matches_interpret():
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))
    compiled = _compiled_or_skip(ops.flash_attention, q, k, v,
                                 interpret=False)
    assert _bitwise_equal(compiled, ops.flash_attention(q, k, v,
                                                        interpret=True))


@pytest.mark.parametrize("G", [1, 2])
def test_flash_attention_vjp_compiled_matches_interpret(G):
    """Training attention's forward and backward kernels, compiled, against
    the same kernels in interpret mode (bf16, as in training)."""
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q = jax.random.normal(ks[0], (2, 256, 2 * G, 128), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, 256, 2, 128), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, 256, 2, 128), jnp.bfloat16)
    do = jax.random.normal(ks[3], q.shape, jnp.bfloat16)

    def run(interpret):
        o, vjp = jax.vjp(
            lambda q, k, v: ops.flash_attention(
                q, k, v, block_q=128, block_k=128, interpret=interpret),
            q, k, v)
        return (o, *vjp(do))

    compiled = _compiled_or_skip(run, False)
    for got, want in zip(compiled, run(True)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)


def test_flash_decode_compiled_matches_interpret():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (2, 1, 4, 32))
    k = jax.random.normal(ks[1], (2, 128, 2, 32))
    v = jax.random.normal(ks[2], (2, 128, 2, 32))
    lens = jnp.asarray([37, 128], jnp.int32)
    compiled = _compiled_or_skip(ops.flash_decode, q, k, v, lens,
                                 interpret=False)
    assert _bitwise_equal(compiled, ops.flash_decode(q, k, v, lens,
                                                     interpret=True))


def test_lowrank_wgrad_compiled_matches_interpret():
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    x = jax.random.normal(ks[0], (256, 64))
    dy = jax.random.normal(ks[1], (256, 256))
    v1 = jax.random.normal(ks[2], (64, 16))
    compiled = _compiled_or_skip(ops.lowrank_wgrad, x, dy, v1,
                                 interpret=False)
    assert _bitwise_equal(compiled, ops.lowrank_wgrad(x, dy, v1,
                                                      interpret=True))


def test_paged_decode_compiled_pallas_matches_interpret():
    rng = np.random.default_rng(8)
    B, H, KV, hd, ps, P = 3, 4, 2, 32, 8, 6
    n_pages = 1 + 2 * B * P
    q = jnp.asarray(rng.normal(size=(B, 1, H, hd)), jnp.float32)
    k_pages = jnp.asarray(rng.normal(size=(n_pages, ps, KV, hd)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(n_pages, ps, KV, hd)), jnp.float32)
    tables = jnp.asarray(_random_paged_layout(rng, B, P, n_pages))
    lens = jnp.asarray(rng.integers(0, P * ps + 1, size=B), jnp.int32)
    compiled = _compiled_or_skip(
        ops.paged_flash_decode, q, k_pages, v_pages, tables, lens,
        impl="pallas",
    )
    interp = ops.paged_flash_decode(
        q, k_pages, v_pages, tables, lens, impl="pallas-interpret"
    )
    assert _bitwise_equal(compiled, interp)


# ---------------------------------------------------------------------------
# cross-implementation bitwise contract: the XLA page walk (the compiled
# CPU/GPU serving path) vs the interpret-mode Pallas kernel vs the dense
# gather — this trio runs on every backend
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("seed", [0, 42])
def test_paged_decode_xla_interpret_dense_all_bitwise(seed, dt):
    rng = np.random.default_rng(seed)
    B, H, KV, hd, ps, P = 3, 4, 2, 32, 8, 6
    n_pages = 1 + 2 * B * P
    q = jnp.asarray(rng.normal(size=(B, 1, H, hd)), dt)
    k_pages = jnp.asarray(rng.normal(size=(n_pages, ps, KV, hd)), dt)
    v_pages = jnp.asarray(rng.normal(size=(n_pages, ps, KV, hd)), dt)
    tables = _random_paged_layout(rng, B, P, n_pages)
    tables[0] = 0  # one null lane rides along
    lens = np.asarray(rng.integers(0, P * ps + 1, size=B), np.int32)
    lens[0] = 0
    lens = jnp.asarray(lens)
    tj = jnp.asarray(tables)

    o_xla = ops.paged_flash_decode(q, k_pages, v_pages, tj, lens, impl="xla")
    o_int = ops.paged_flash_decode(
        q, k_pages, v_pages, tj, lens, impl="pallas-interpret"
    )
    kd = k_pages[tables].reshape(B, P * ps, KV, hd)
    vd = v_pages[tables].reshape(B, P * ps, KV, hd)
    o_dense = ops.flash_decode(q, kd, vd, lens, block_k=ps, interpret=True)
    assert _bitwise_equal(o_xla, o_int), "xla walk != pallas interpret"
    assert _bitwise_equal(o_xla, o_dense), "xla walk != dense gather"
