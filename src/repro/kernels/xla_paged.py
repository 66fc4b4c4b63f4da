"""Compiled paged flash decode — a pure-XLA page-table walk.

The Pallas paged kernel (``kernels/paged_decode.py``) only *executes* on a
real TPU; on the CPU backend it runs under ``interpret=True``, which
re-enters Python for every grid step and turns the flagship zero-copy
decode path into a multiple-× slowdown.  This module is the compiled
fallback: the same page-table-walking online-softmax decode expressed in
plain ``jax.numpy`` so it lowers natively on every backend.

Structure: a ``lax.fori_loop`` over the page-table columns plays the role
of the kernel's sequential innermost grid axis.  Each step fetches the
``B`` physical pages named by ``tables[:, ki]`` (one dynamic-index gather
per step — never a dense ``(B, P * page_size)`` copy of the whole window),
scores them against the query, and folds them into the ``(m, l, acc)``
carry through ``flash_decode.online_update`` — the very function the
Pallas kernel body calls, vmapped over the batch — so the f32 casts,
the score, the ``NEG_INF`` length mask and the ``exp``/rescale order are
the kernel's by construction.  The kernel's ``pl.when(k_start <
cur_len)`` skip gate becomes a ``where`` select on the carry (the gate
matters: a fully-masked page would otherwise contribute
``exp(NEG_INF - NEG_INF) == 1`` to ``l``).

The loop's trip count is data-dependent: it stops after
``ceil(max(cur_len) / page_size)`` columns, because any page at or past
every lane's length is fully masked and leaves the carry bit-for-bit
untouched (that is precisely what the skip gate guarantees), so walking
it would be a no-op.  This is the paged path's structural advantage over
the dense round — the dense ``gather_pages + flash_decode`` always pays
for all ``P * page_size`` allocated positions, while the walk's cost
scales with the *live* context.  Truncation is bitwise-free by
construction, and the contract with both the interpret-mode Pallas
kernel and the dense ``gather_pages + flash_decode(block_k=page_size)``
path is pinned by tests/test_kernels.py.

Optionally the pool may hold int8-quantized pages with per-page f32
scales (``k_scale``/``v_scale`` of shape ``(n_pages,)``): pages are
dequantized on fetch, after which the accumulator math is unchanged.
That path trades bitwise equality for a quantization tolerance and is
only reachable through the explicit ``EngineConfig.kv_dtype`` opt-in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.flash_decode import (
    NEG_INF,
    group_major,
    head_major,
    online_update,
)


def paged_flash_decode_xla(
    q: jnp.ndarray,        # (B, 1, H, hd)
    k_pages: jnp.ndarray,  # (n_pages, page_size, KV, hd) physical pool
    v_pages: jnp.ndarray,
    tables: jnp.ndarray,   # (B, P) int32 page tables (0 = null page)
    cur_len,               # (B,) or scalar int32 — valid positions per slot
    *,
    k_scale: jnp.ndarray | None = None,  # (n_pages,) f32 per-page scales
    v_scale: jnp.ndarray | None = None,
) -> jnp.ndarray:
    B, _, H, hd = q.shape
    _, ps, KV, _ = k_pages.shape
    assert H % KV == 0
    g = H // KV
    P = tables.shape[1]
    scale = 1.0 / math.sqrt(hd)
    lens = jnp.broadcast_to(
        jnp.asarray(cur_len, jnp.int32).reshape(-1), (B,)
    )
    tables = jnp.asarray(tables, jnp.int32)
    qg = group_major(q[:, 0].astype(jnp.float32), KV).reshape(B, g, KV, hd)
    update = jax.vmap(
        functools.partial(online_update, scale=scale, pin=True),
        in_axes=(0, 0, 0, 0, 0, 0, None, 0),
    )

    def step(ki, carry):
        m, l, acc = carry
        pids = tables[:, ki]                               # (B,)
        k = k_pages[pids].astype(jnp.float32)              # (B, ps, KV, hd)
        v = v_pages[pids].astype(jnp.float32)
        if k_scale is not None:
            k = k * k_scale[pids][:, None, None, None]
            v = v * v_scale[pids][:, None, None, None]
        pos = ki * ps + jax.lax.broadcasted_iota(jnp.int32, (ps, 1, 1, 1), 0)
        m_new, l_new, acc_new = update(qg, k, v, m, l, acc, pos, lens)
        live = (ki * ps < lens)[:, None, None, None]       # (B, 1, 1, 1)
        m = jnp.where(live, m_new, m)
        l = jnp.where(live, l_new, l)
        acc = jnp.where(live, acc_new, acc)
        return (m, l, acc)

    init = (
        jnp.full((B, g, KV, 1), NEG_INF, jnp.float32),
        jnp.zeros((B, g, KV, 1), jnp.float32),
        jnp.zeros((B, g, KV, hd), jnp.float32),
    )
    # stop at the last page any lane still covers — everything past it is
    # fully masked and would leave the carry bit-for-bit unchanged
    n_live = jnp.minimum((jnp.max(lens) + ps - 1) // ps, P).astype(jnp.int32)
    m, l, acc = jax.lax.fori_loop(0, n_live, step, init)
    o = (acc / jnp.maximum(l, 1e-30)).reshape(B, H, hd)
    return head_major(o, KV).astype(q.dtype)[:, None]
