"""Paged flash decode — page-table-walking GQA attention for serving.

The zero-copy decode hot path: instead of materializing a slot-major dense
copy of every KV page (``serve/kvpool.py:gather_pages`` — O(B * P * page_size)
HBM rows per layer per token), the kernel's grid walks each slot's page
table directly.  Block ``ki`` of slot ``b`` is page ``tables[b, ki]`` of the
physical pool; the ``(B, P)`` table and the per-slot lengths ride in as
scalar-prefetch operands so the K/V block index maps can chase the table
before the block is fetched.

Traffic model: block indices for positions past ``cur_len`` are clamped to
the last valid page, and the TPU pipeline skips the copy when consecutive
grid steps ask for the same block — so the per-step KV traffic is the pages
each slot actually covers, not ``B * pages_per_slot``.

Bitwise contract (pinned by tests/test_kernels.py): identical to
``flash_decode(q, gather(k_pages, tables), gather(v_pages, tables), lens,
block_k=page_size)`` — same online-softmax accumulator, same block order,
same length mask, so swapping the dense gather for the page walk can never
change logits.

Grid: (batch, pages_per_slot) — page axis innermost (sequential), scratch
carries (m, l, acc) across a slot's pages.  Each grid step reads one whole
page ``(1, page_size, KV, hd)`` and the slot's ``(1, 1, H, hd)`` queries:
both blocks end in the array's own last two dims, as the TPU requires.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_decode import (
    _kernel as _dense_kernel,
    group_major,
    head_major,
    scratch_shapes,
)


def _kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
            l_ref, *, ps, scale, pin):
    # the accumulator body IS flash_decode's kernel with block_k ==
    # page_size — only the scalar-prefetch ref (unused in the body) and the
    # K/V index maps differ, so the bitwise-equality contract holds by
    # construction, not by keeping two copies in lockstep.  Pages entirely
    # past the valid length are skipped by the body's own length gate, and
    # their block index is clamped in ``kv_index`` so no fresh fetch
    # happens either.
    _dense_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                  l_ref, bk=ps, scale=scale, pin=pin)


def paged_flash_decode(
    q: jnp.ndarray,        # (B, 1, H, hd)
    k_pages: jnp.ndarray,  # (n_pages, page_size, KV, hd) physical pool
    v_pages: jnp.ndarray,
    tables: jnp.ndarray,   # (B, P) int32 page tables (0 = null page)
    cur_len,               # (B,) or scalar int32 — valid positions per slot
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    B, _, H, hd = q.shape
    _, ps, KV, _ = k_pages.shape
    assert H % KV == 0
    P = tables.shape[1]
    scale = 1.0 / math.sqrt(hd)
    lens = jnp.broadcast_to(
        jnp.asarray(cur_len, jnp.int32).reshape(-1), (B,)
    )
    tables = jnp.asarray(tables, jnp.int32)

    def kv_index(b, ki, tbl, lens):
        # walk the page table; clamp blocks past the covered length to the
        # last valid page so the pipeline re-uses the previous fetch
        last = jnp.maximum(lens[b] - 1, 0) // ps
        return (tbl[b, jnp.minimum(ki, last)], 0, 0, 0)

    def q_index(b, ki, tbl, lens):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, 1, H, hd), q_index),
            pl.BlockSpec((1, ps, KV, hd), kv_index),
            pl.BlockSpec((1, ps, KV, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, H, hd), q_index),
        scratch_shapes=scratch_shapes(H, KV, hd),
    )
    kernel = functools.partial(_kernel, ps=ps, scale=scale, pin=interpret)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, H, hd), q.dtype),
        interpret=interpret,
    )(tables, lens, group_major(q, KV), k_pages, v_pages)
    return head_major(out, KV)
