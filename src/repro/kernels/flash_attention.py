"""Causal GQA flash attention: Pallas TPU kernels for the forward and the
backward, joined by a ``jax.custom_vjp``.

Training attention takes this path on TPU (``models/layers.causal_attention``
asks ``ops.resolve_attention_impl``).  Score, probability, dP and dS tiles
live in VMEM only: what reaches HBM is q, k, v, o, their gradients and the
per-row logsumexp.

Layout.  q and o are viewed as (B, S, H*hd), k and v as (B, S, KV*hd)
(free reshapes), so one block holds the G = H // KV query heads of one KV
head side by side on lanes and K/V are fetched once per KV head.  The
logsumexp and the backward's delta = rowsum(dO * O) are f32 rows of ``t``
positions, (B, KV, S/t, G, t).

A grid step holds ``block_q`` rows: queries in the forward, grid (B, KV,
S/block_q), the whole K/V sequence of the KV head in VMEM; keys in the
backward, grid (B, KV, S/block_q), the whole q/dO sequence in VMEM and dQ
accumulated there in f32 across the steps.  Inside a step the work walks
square ``t x t`` tiles (``t = block_k``) in a loop: under ``causal`` only
the tiles on or below the diagonal, and only the diagonal tile is masked.
q/k/v enter the MXU in their own dtype (bf16 in training) with f32
accumulation; the softmax statistics and the accumulators are f32, and p
and dS are cast to the operands' dtype for their matmuls, as the jnp path
does.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))  # contract the last dims: a @ b.T


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    return jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)


def _tiles(lo, hi, body):
    """``body(i)`` for tile indices lo <= i < hi (bounds may be traced)."""

    def step(i, carry):
        body(i)
        return carry

    jax.lax.fori_loop(lo, hi, step, 0)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, g, hd, t, scale, causal):
    nt_step = q_ref.shape[1] // t
    first = pl.program_id(2) * nt_step  # tile index of the step's first row
    diag = (jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
            <= jax.lax.broadcasted_iota(jnp.int32, (t, t), 0))
    for a in range(nt_step):
        rows = slice(a * t, (a + 1) * t)
        for h in range(g):
            cols = slice(h * hd, (h + 1) * hd)
            q = q_ref[0, rows, cols]  # (t, hd)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

            def tile(c, masked, q=q):
                kv = pl.ds(pl.multiple_of(c * t, t), t)
                s = _dot_nt(q, k_ref[0, kv, :]) * scale  # (t, t)
                if masked:
                    s = jnp.where(diag, s, NEG_INF)
                m_prev = m_ref[...]  # (t, LANES), lanes equal
                m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_next)
                p = jnp.exp(s - m_next[:, :1])
                l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1,
                                                          keepdims=True)
                m_ref[...] = m_next
                v = v_ref[0, kv, :]
                acc_ref[...] = acc_ref[...] * alpha[:, :1] + _dot(
                    p.astype(v.dtype), v)

            if causal:
                _tiles(0, first + a, lambda c: tile(c, False))
                tile(first + a, True)
            else:
                _tiles(0, k_ref.shape[1] // t, lambda c: tile(c, False))
            l = l_ref[...]
            o_ref[0, rows, cols] = (acc_ref[...] / l[:, :1]).astype(o_ref.dtype)
            lse = m_ref[...] + jnp.log(l)  # (t, LANES)
            lse_ref[0, 0, a, h:h + 1, :] = lse.T[0:1, :]


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                *, g, hd, t, scale, causal):
    kj = pl.program_id(2)
    nt_step = k_ref.shape[1] // t
    nq = q_ref.shape[1] // t
    first = kj * nt_step  # tile index of the step's first key
    diag = (jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
            <= jax.lax.broadcasted_iota(jnp.int32, (t, t), 1))

    @pl.when(kj == 0)
    def _init_dq():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    for c in range(nt_step):
        keys = slice(c * t, (c + 1) * t)
        k = k_ref[0, keys, :]  # (t, hd)
        v = v_ref[0, keys, :]
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

        def tile(i, masked, k=k, v=v):
            rows = pl.ds(pl.multiple_of(i * t, t), t)
            for h in range(g):
                cols = slice(h * hd, (h + 1) * hd)
                q = q_ref[0, rows, cols]  # (t, hd)
                do = do_ref[0, rows, cols]
                st = _dot_nt(k, q) * scale  # (t keys, t queries)
                if masked:
                    st = jnp.where(diag, st, NEG_INF)
                pt = jnp.exp(st - lse_ref[0, 0, i, h:h + 1, :])
                dv_acc[...] += _dot(pt.astype(do.dtype), do)
                dst = pt * (_dot_nt(v, do) - dl_ref[0, 0, i, h:h + 1, :])
                dk_acc[...] += _dot(dst.astype(q.dtype), q)
                dq_acc[rows, cols] += _dot(dst.T.astype(k.dtype), k)

        if causal:
            tile(first + c, True)
            _tiles(first + c + 1, nq, lambda i: tile(i, False))
        else:
            _tiles(0, nq, lambda i: tile(i, False))
        dk_ref[0, keys, :] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, keys, :] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _write_dq():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _compiler_params(sem):
    return pltpu.CompilerParams(dimension_semantics=sem,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _fwd(q, k, v, causal, bq, t, interpret):
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    g = H // KV
    kernel = functools.partial(_fwd_kernel, g=g, hd=hd, t=t,
                               scale=1.0 / math.sqrt(hd), causal=causal)
    q_spec = pl.BlockSpec((1, bq, g * hd), lambda b, h, i: (b, i, h))
    kv_spec = pl.BlockSpec((1, Sk, hd), lambda b, h, i: (b, 0, h))
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, KV, Sq // bq),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[
            q_spec,
            pl.BlockSpec((1, 1, bq // t, g, t),
                         lambda b, h, i: (b, h, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sq, H * hd), q.dtype),
            jax.ShapeDtypeStruct((B, KV, Sq // t, g, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((t, LANES), jnp.float32),
            pltpu.VMEM((t, LANES), jnp.float32),
            pltpu.VMEM((t, hd), jnp.float32),
        ],
        compiler_params=_compiler_params(("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q.reshape(B, Sq, H * hd), k.reshape(B, Sk, KV * hd),
      v.reshape(B, Sk, KV * hd))
    return o.reshape(B, Sq, H, hd), lse


def _bwd(q, k, v, do, lse, delta, causal, bk, t, interpret):
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    g = H // KV
    kernel = functools.partial(_bwd_kernel, g=g, hd=hd, t=t,
                               scale=1.0 / math.sqrt(hd), causal=causal)
    q_spec = pl.BlockSpec((1, Sq, g * hd), lambda b, h, j: (b, 0, h))
    kv_spec = pl.BlockSpec((1, bk, hd), lambda b, h, j: (b, j, h))
    row_spec = pl.BlockSpec((1, 1, Sq // t, g, t),
                            lambda b, h, j: (b, h, 0, 0, 0))
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(B, KV, Sk // bk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[q_spec, kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sq, H * hd), q.dtype),
            jax.ShapeDtypeStruct((B, Sk, KV * hd), k.dtype),
            jax.ShapeDtypeStruct((B, Sk, KV * hd), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((Sq, g * hd), jnp.float32),
            pltpu.VMEM((t, hd), jnp.float32),
            pltpu.VMEM((t, hd), jnp.float32),
        ],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd",
    )(q.reshape(B, Sq, H * hd), k.reshape(B, Sk, KV * hd),
      v.reshape(B, Sk, KV * hd), do.reshape(B, Sq, H * hd), lse, delta)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, bq, t, interpret):
    return _fwd(q, k, v, causal, bq, t, interpret)[0]


def _flash_fwd(q, k, v, causal, bq, t, interpret):
    o, lse = _fwd(q, k, v, causal, bq, t, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, bq, t, interpret, res, do):
    q, k, v, o, lse = res
    B, Sq, H, _ = q.shape
    _, KV, nt, g, _ = lse.shape
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    delta = delta.reshape(B, nt, t, KV, g).transpose(0, 3, 1, 4, 2)
    return _bwd(q, k, v, do, lse, delta, causal, min(bq, k.shape[1]), t,
                interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,  # (B, Sq, H, hd)
    k: jnp.ndarray,  # (B, Sk, KV, hd)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Attention of q over k/v, (B, Sq, H, hd), differentiable in all three.

    ``block_q`` is the rows a grid step holds (shrunk to the sequence),
    ``block_k`` the tile it walks in (shrunk to ``block_q``); the sequence
    lengths are multiples of ``block_q`` and ``block_q`` of ``block_k``.
    Causal attention needs Sq == Sk.  Compiled on TPU, the tile and
    head_dim are multiples of 128 (``ops.resolve_attention_impl`` checks).
    """
    _, Sq, H, _ = q.shape
    _, Sk, KV, _ = k.shape
    assert H % KV == 0, (H, KV)
    assert Sq == Sk or not causal, (Sq, Sk)
    bq = min(block_q, Sq)
    t = min(block_k, bq)
    assert Sq % bq == 0 and Sk % min(bq, Sk) == 0 and bq % t == 0, (
        Sq, Sk, bq, t)
    return _flash(q, k, v, causal, bq, t, interpret)
