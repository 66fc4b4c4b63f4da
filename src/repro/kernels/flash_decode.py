"""Flash decode — single-token GQA attention against a long KV cache.

The decode_32k / long_500k serving hot spot: one query row per sequence
attends to a (Smax, KV, hd) cache.  Online softmax over KV blocks with the
(H × hd) accumulator in VMEM; the cache is streamed block-by-block, the
length mask handles cur_len < Smax.

``cur_len`` may be a scalar (every sequence at the same position — the
lock-step path) or a ``(B,)`` vector of per-sequence lengths — the ragged
layout the continuous-batching serve engine produces, where every slot of
the decode batch sits at a different position in its own cache.

Grid: (batch, Smax/Bk) — KV-block axis innermost (sequential on TPU),
scratch carries (m, l, acc) across blocks.  One grid step covers every
head of a block: the K/V block is ``(1, bk, KV, hd)`` and the query block
``(1, 1, H, hd)``, so the last two block dims always equal the array's
(the TPU tiling rule).  GQA grouping happens in the body: the wrapper
orders the query heads group-major (:func:`group_major`), so rows
``r*KV:(r+1)*KV`` hold the ``r``-th query head of every KV head and the
whole ``(bk, KV, hd)`` block multiplies against them without a slice.

The per-block update, :func:`online_update`, is shared with the paged
kernel and the compiled XLA page walk, so all three compute the same
algebra by construction.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def group_major(q: jnp.ndarray, kv: int) -> jnp.ndarray:
    """(..., H, hd) heads ``j*g + r`` -> reordered so index ``r*KV + j``
    holds query head ``r`` of KV group ``j``."""
    *lead, H, hd = q.shape
    x = q.reshape(*lead, kv, H // kv, hd)
    return jnp.swapaxes(x, -3, -2).reshape(*lead, H, hd)


def head_major(o: jnp.ndarray, kv: int) -> jnp.ndarray:
    """Inverse of :func:`group_major`."""
    *lead, H, hd = o.shape
    x = o.reshape(*lead, H // kv, kv, hd)
    return jnp.swapaxes(x, -3, -2).reshape(*lead, H, hd)


def online_update(q, k, v, m, l, acc, pos, cur_len, *, scale, pin):
    """Fold one KV block into the online-softmax carry.

    ``q``: (g, KV, hd) f32 group-major queries; ``k``/``v``: (bk, KV, hd)
    f32; ``m``/``l``: (g, KV, 1); ``acc``: (g, KV, hd); ``pos``: (bk, 1, 1,
    1) absolute positions of the block's rows.  Returns the new (m, l, acc).

    ``pin`` materializes every product before it is summed.  XLA's CPU
    backend contracts a multiply feeding an add into an FMA in some fusion
    contexts and not in others, which moves results by an ulp between the
    interpret-mode kernel and the XLA walk; the barrier rounds each product
    once in both.  Mosaic has no lowering for it and needs none.
    """
    def rounded(x):
        return jax.lax.optimization_barrier(x) if pin else x

    s = jnp.sum(rounded(k[:, None] * q[None]), axis=-1, keepdims=True)
    s = s * scale                                        # (bk, g, KV, 1)
    s = jnp.where(pos < cur_len, s, NEG_INF)
    m_cur = jnp.maximum(m, jnp.max(s, axis=0))
    alpha = jnp.exp(m - m_cur)
    p = jnp.exp(s - m_cur[None])
    l_new = rounded(l * alpha) + jnp.sum(p, axis=0)
    pv = jnp.sum(rounded(p * v[:, None]), axis=0)         # (g, KV, hd)
    acc_new = rounded(acc * alpha) + pv
    return m_cur, l_new, acc_new


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            bk, scale, pin):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    g, KV, hd = acc_ref.shape

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    cur_len = len_ref[b]
    k_start = ki * bk

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32).reshape(g, KV, hd)
        pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bk, 1, 1, 1), 0)
        m, l, acc = online_update(
            q, k_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
            m_ref[...], l_ref[...], acc_ref[...], pos, cur_len,
            scale=scale, pin=pin,
        )
        m_ref[...] = m
        l_ref[...] = l
        acc_ref[...] = acc

    # skip cache blocks entirely past the valid length
    pl.when(k_start < cur_len)(_compute)

    @pl.when(ki == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).reshape(g * KV, hd).astype(
            o_ref.dtype
        )


def scratch_shapes(H: int, KV: int, hd: int):
    g = H // KV
    return [
        pltpu.VMEM((g, KV, hd), jnp.float32),
        pltpu.VMEM((g, KV, 1), jnp.float32),
        pltpu.VMEM((g, KV, 1), jnp.float32),
    ]


def flash_decode(
    q: jnp.ndarray,        # (B, 1, H, hd)
    k_cache: jnp.ndarray,  # (B, Smax, KV, hd)
    v_cache: jnp.ndarray,
    cur_len,               # scalar or (B,) int32 — valid cache positions
    *,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    B, _, H, hd = q.shape
    _, Smax, KV, _ = k_cache.shape
    assert H % KV == 0
    bk = min(block_k, Smax)
    assert Smax % bk == 0, (Smax, bk)
    scale = 1.0 / math.sqrt(hd)
    lens = jnp.broadcast_to(
        jnp.asarray(cur_len, jnp.int32).reshape(-1), (B,)
    )

    kernel = functools.partial(_kernel, bk=bk, scale=scale, pin=interpret)
    out = pl.pallas_call(
        kernel,
        grid=(B, Smax // bk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, H, hd), lambda b, ki: (b, 0, 0, 0)),
            pl.BlockSpec((1, bk, KV, hd), lambda b, ki: (b, ki, 0, 0)),
            pl.BlockSpec((1, bk, KV, hd), lambda b, ki: (b, ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, H, hd), lambda b, ki: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, H, hd), q.dtype),
        scratch_shapes=scratch_shapes(H, KV, hd),
        interpret=interpret,
    )(lens, group_major(q, KV), k_cache, v_cache)
    return head_major(out, KV)
