"""Core NN layers: RMSNorm, RoPE, GQA attention, FFN, chunked cross-entropy.

Pure functions over explicit param pytrees.  The MeCeFO hooks surface as:
  * ``grad_gate`` wrapping the attention branch (technique I),
  * ``lowrank_linear`` for FFN matmuls (technique III),
  * ``ffn_recompute`` checkpointing (technique II).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.lowrank import lowrank_linear
from repro.core.recompute import ffn_recompute, maybe_remat
from repro.core.skipconn import cast_grad, grad_gate
from repro.kernels import kvquant
from repro.kernels import ops as kernel_ops
from repro.parallel.sharding import ShardingRules, constrain


# ---------------------------------------------------------------------------
# Norms / RoPE
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def rope(x, positions, theta: float):
    """Rotary embedding. x: (..., S, H, hd); positions: (S,) or (B, S).

    x is upcast *first* so the f32 region is closed by an explicit cast —
    otherwise the backward cotangent stays f32 all the way into the QKV
    dx matmuls and doubles the TP all-reduce bytes.
    """
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    pos = jnp.asarray(positions, jnp.float32)
    angles = pos[..., None] * freqs  # (..., S, half)
    # broadcast to (..., S, 1, half) over head dim
    angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def causal_attention(q, k, v, *, chunk: int = 1024, causal_slice: bool = False,
                     cached: bool = False, sites: int = 1):
    """Causal GQA attention. q: (B, S, H, hd); k, v: (B, S, KV, hd).
    Returns (B, S, H, hd).

    Where ``kernel_ops.resolve_attention_impl`` says ``"flash"`` (on TPU,
    on one device, not a prefill into a KV cache (``cached``), head_dim a
    multiple of 128 and S a multiple of the tuned blocks) this is the
    Pallas flash kernel with its backward (kernels/flash_attention.py):
    nothing S²-shaped reaches HBM, and the forward's residuals (o and the
    row logsumexp) replace recomputing it.  Otherwise it is the chunked
    jnp path below, each chunk's (Qc, S) probabilities recomputed in the
    backward; ``chunk`` and ``causal_slice`` apply to it alone.
    ``causal_slice=True`` unrolls the query-chunk loop in Python and slices
    K/V to the causal prefix per chunk — halves attention FLOPs at the cost
    of per-chunk specialization.  The choice is counted at trace time in
    ``kernels.attention_sites{impl}``, ``sites`` layers per call.
    """
    impl = kernel_ops.resolve_attention_impl(q.shape, k.shape[2],
                                             cached=cached)
    kernel_ops.count_attention_sites(impl, sites)
    if impl == "flash":
        return kernel_ops.flash_attention(q, k, v)
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = hd ** -0.5
    qg = q.reshape(B, S, KV, G, hd)
    chunk = min(chunk, S)
    while S % chunk:  # fall back to the largest divisor (correctness path)
        chunk -= 1
    nc = S // chunk

    def attend(qc, offset, k_ctx, v_ctx, ctx_len):
        # qc: (B, Qc, KV, G, hd); k_ctx/v_ctx: (B, L, KV, hd)
        # the named scope marks this region as "replaced by the Pallas flash
        # kernel on TPU" for the roofline's kernel-substitution accounting
        with jax.named_scope("flashsubst"):
            s = jnp.einsum("bqkgh,bskh->bkgqs", qc, k_ctx).astype(jnp.float32)
            s = s * scale
            q_pos = offset + jnp.arange(chunk)
            k_pos = jnp.arange(ctx_len)
            mask = k_pos[None, :] <= q_pos[:, None]
            s = jnp.where(mask[None, None, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(v_ctx.dtype)
            return jnp.einsum("bkgqs,bskh->bqkgh", p, v_ctx)

    # never keep a chunk's (Qc, S) probabilities for backward — recompute
    # (the flash path recomputes its score tiles in VMEM instead)
    attend = jax.checkpoint(
        attend,
        policy=jax.checkpoint_policies.nothing_saveable,
        static_argnums=(4,),  # ctx_len is a python int
    )

    if causal_slice:
        outs = []
        for i in range(nc):
            qc = jax.lax.dynamic_slice_in_dim(qg, i * chunk, chunk, axis=1)
            ctx = (i + 1) * chunk
            outs.append(
                attend(qc, i * chunk, k[:, :ctx], v[:, :ctx], ctx)
            )
        out = jnp.concatenate(outs, axis=1)
    else:
        qcs = qg.reshape(B, nc, chunk, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
        offsets = jnp.arange(nc) * chunk

        def body(_, xs):
            qc, off = xs
            return None, attend(qc, off, k, v, S)

        _, out = jax.lax.scan(body, None, (qcs, offsets))
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, KV, G, hd)
        return out.reshape(B, S, H, hd)
    return out.reshape(B, S, H, hd)


def decode_attention(q, k_cache, v_cache, cur_len):
    """Single-token attention against a (B, Smax, KV, hd) cache.

    q: (B, 1, H, hd). ``cur_len``: number of valid cache positions (after the
    current token's K/V were written) — a scalar, or a (B,) vector for the
    ragged continuous-batching layout where every slot sits at its own
    position.  fp32 softmax; GQA grouped einsum.
    """
    B, _, H, hd = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    scale = hd ** -0.5
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache).astype(jnp.float32) * scale
    lens = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32).reshape(-1), (B,))
    valid = jnp.arange(k_cache.shape[1])[None, :] < lens[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgs,bskh->bkgh", p, v_cache)
    return out.reshape(B, 1, H, hd)


def history_attention(q, k_cache, v_cache, off):
    """Chunk-prefill attention: C queries starting at position ``off``
    attend to the cache prefix plus themselves (their K/V were written at
    ``off..off+C-1`` before the call).

    q: (B, C, H, hd); k_cache, v_cache: (B, Smax, KV, hd); ``off`` a scalar.
    Query ``i`` attends to cache positions ``<= off + i``; pad queries past
    the true chunk length produce garbage rows that are never read (their
    cache writes sit at or past the slot's ``cur_len``).
    """
    B, C, H, hd = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    scale = hd ** -0.5
    qg = q.reshape(B, C, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k_cache).astype(jnp.float32)
    s = s * scale
    k_pos = jnp.arange(k_cache.shape[1])[None, :]
    q_pos = off + jnp.arange(C)[:, None]
    mask = k_pos <= q_pos  # (C, Smax)
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p, v_cache)
    return out.reshape(B, C, H, hd)


# The backward recomputes the head merge (a reshape) instead of keeping its
# output, so it holds one copy of o, which the flash kernel's backward
# shares as its residual.
_out_proj = jax.checkpoint(lambda o, wo: o.reshape(*o.shape[:2], -1) @ wo)


def attention_block(
    p,
    x,
    cfg: ModelConfig,
    rules: ShardingRules,
    keep,
    positions,
    *,
    cache: Optional[dict] = None,
    cur_len=None,
    attn_chunk: int = 1024,
    causal_slice: bool = False,
    history: bool = False,
    page_tables=None,
    page_size: Optional[int] = None,
    kernel_impl: Optional[str] = None,
    sites: int = 1,
):
    """Pre-norm MHA sublayer with residual; returns (y, new_cache).

    ``keep`` is the technique-I mask ((B,) array, scalar, or python float).
    The whole MHA branch (incl. its norm) sits behind ``grad_gate`` so
    degraded examples propagate gradients via the residual only.
    ``sites`` is the number of layers this call stands for (a scanned
    stack's length), for the attention-site count.
    """
    xn = rmsnorm(x, p["ln"], cfg.norm_eps)
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = (xn @ p["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = (xn @ p["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v = (xn @ p["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None and page_tables is not None:
        # paged decode: the pool (n_pages, ps, KV, hd) is the native layout —
        # the new K/V row lands in its page in place and the flash-decode
        # kernel walks the page table, so no slot-major dense copy exists
        if cur_len is None or page_size is None:
            raise ValueError("paged decode requires cur_len and page_size")
        pids = jnp.take_along_axis(
            page_tables, (cur_len // page_size)[:, None], axis=1
        )[:, 0]
        offs = cur_len % page_size
        if "k_scale" in cache:
            # int8 pool: dequantize only the B touched pages, insert the
            # exact new row, requantize with fresh per-page scales; decode
            # reads the quantized pages through the compiled XLA walk
            k_pages, k_scale = kvquant.insert_row_q8(
                cache["k"], cache["k_scale"], pids, offs, k[:, 0]
            )
            v_pages, v_scale = kvquant.insert_row_q8(
                cache["v"], cache["v_scale"], pids, offs, v[:, 0]
            )
            new_cache = {"k": k_pages, "v": v_pages,
                         "k_scale": k_scale, "v_scale": v_scale}
            o = kernel_ops.paged_dispatch(
                q, k_pages, v_pages, page_tables, cur_len + 1,
                impl=kernel_impl, k_scale=k_scale, v_scale=v_scale,
            )
        else:
            k_pages = cache["k"].at[pids, offs].set(
                k[:, 0].astype(cache["k"].dtype)
            )
            v_pages = cache["v"].at[pids, offs].set(
                v[:, 0].astype(cache["v"].dtype)
            )
            new_cache = {"k": k_pages, "v": v_pages}
            o = kernel_ops.paged_dispatch(
                q, k_pages, v_pages, page_tables, cur_len + 1,
                impl=kernel_impl,
            )
    elif cache is not None:
        if cur_len is None:
            raise ValueError("decode/prefill cache requires cur_len")
        if history:  # chunk prefill: write the chunk, attend to prefix+self
            new_cache = {
                "k": jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), cur_len, axis=1
                ),
                "v": jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), cur_len, axis=1
                ),
            }
            o = history_attention(q, new_cache["k"], new_cache["v"], cur_len)
        elif q.shape[1] == 1:  # decode: write one position, attend to cache
            if jnp.ndim(cur_len):  # ragged: per-slot write positions
                upd = jax.vmap(
                    lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(
                        c, u, i, axis=0
                    )
                )
                k_cache = upd(cache["k"], k.astype(cache["k"].dtype), cur_len)
                v_cache = upd(cache["v"], v.astype(cache["v"].dtype), cur_len)
            else:
                k_cache = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), cur_len, axis=1
                )
                v_cache = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), cur_len, axis=1
                )
            new_cache = {"k": k_cache, "v": v_cache}
            o = decode_attention(q, k_cache, v_cache, cur_len + 1)
        else:  # prefill: attend within the prompt, write K/V into the cache
            new_cache = {
                "k": jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), cur_len, axis=1
                ),
                "v": jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), cur_len, axis=1
                ),
            }
            o = causal_attention(
                q, k, v, chunk=attn_chunk, causal_slice=causal_slice,
                cached=True, sites=sites,
            )
    else:
        o = causal_attention(q, k, v, chunk=attn_chunk,
                             causal_slice=causal_slice, sites=sites)

    y = _out_proj(o, p["wo"])
    # technique I: skip MHA in backward for degraded examples. A static 0
    # becomes stop_gradient so XLA provably DCEs the whole MHA backward
    # (Wgrad + Dgrad + saved residuals) — the paper's memory/compute claim.
    if isinstance(keep, (int, float)) and keep == 0.0:
        y = jax.lax.stop_gradient(y)
    else:
        y = grad_gate(y, keep)
    y = constrain(y, rules, "batch", "seq", None)
    return cast_grad(x + y), new_cache


# ---------------------------------------------------------------------------
# FFN (dense)
# ---------------------------------------------------------------------------


def ffn_block(
    p,
    x,
    cfg: ModelConfig,
    rules: ShardingRules,
    *,
    proj=None,
    keep=1.0,
    lowrank_mode: str = "exact",
    recompute: bool = False,
):
    """Pre-norm FFN sublayer with residual. SwiGLU or squared-ReLU."""

    def body(p, x, proj, keep):
        xn = rmsnorm(x, p["ln"], cfg.norm_eps)
        if cfg.ffn_act == "swiglu":
            g = _lin(xn, p["w_gate"], _p(proj, "w_gate"), keep, lowrank_mode)
            u = _lin(xn, p["w_up"], _p(proj, "w_up"), keep, lowrank_mode)
            h = jax.nn.silu(g.astype(jnp.float32)).astype(u.dtype) * u
        else:  # non-gated: relu2 (Nemotron-4) or gelu (granite / musicgen)
            u = _lin(xn, p["w_up"], _p(proj, "w_up"), keep, lowrank_mode)
            h = nonlin(u, cfg.ffn_act)
        h = constrain(h, rules, "batch", "seq", "mlp")
        y = _lin(h, p["w_down"], _p(proj, "w_down"), keep, lowrank_mode)
        return constrain(y, rules, "batch", "seq", None)

    if recompute:  # technique II: keep only the FFN input
        body = ffn_recompute(body)
    keep_arr = jnp.asarray(keep, x.dtype) if not isinstance(keep, jnp.ndarray) else keep
    return cast_grad(x + body(p, x, proj, keep_arr))


def nonlin(u, act: str):
    if act == "relu2":
        r = jax.nn.relu(u)
        return (r * r).astype(u.dtype)
    if act == "gelu":
        return jax.nn.gelu(u.astype(jnp.float32)).astype(u.dtype)
    raise ValueError(act)


def _p(proj, name):
    if proj is None:
        return None
    return proj.get(name)


def _lin(x, w, v1, keep, mode):
    if mode == "exact" or v1 is None:
        return x @ w
    return lowrank_linear(x, w, v1, keep, mode)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def chunked_cross_entropy(
    h,
    unembed,
    labels,
    token_weight,
    rules: ShardingRules,
    *,
    chunk: int = 512,
    vocab_size: Optional[int] = None,
):
    """CE over vocab-sharded logits without materializing (B, S, V).

    h: (B, S, d); unembed: (d, V); labels: (B, S) int32; token_weight: (B, S).
    Scans over sequence chunks, remats the per-chunk logits.  Logit columns
    >= vocab_size (TP padding) are masked out of the softmax.
    """
    B, S, d = h.shape
    V = unembed.shape[-1]
    pad_mask = None
    if vocab_size is not None and vocab_size < V:
        pad_mask = jnp.where(jnp.arange(V) < vocab_size, 0.0, -1e30).astype(jnp.float32)
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    nc = S // chunk

    def chunk_loss(hc, yc, wc):
        logits = (hc @ unembed).astype(jnp.float32)
        if pad_mask is not None:
            logits = logits + pad_mask
        logits = constrain(logits, rules, "batch", None, "vocab")
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(yc, V, dtype=logits.dtype)
        gold = jnp.sum(logits * onehot, axis=-1)
        nll = (lse - gold) * wc
        return jnp.sum(nll), jnp.sum(wc)

    chunk_loss = jax.checkpoint(chunk_loss)

    hcs = h.reshape(B, nc, chunk, d).transpose(1, 0, 2, 3)
    ycs = labels.reshape(B, nc, chunk).transpose(1, 0, 2)
    wcs = token_weight.reshape(B, nc, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        tot, cnt = carry
        l, c = chunk_loss(*xs)
        return (tot + l, cnt + c), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)), (hcs, ycs, wcs))
    return tot / jnp.maximum(cnt, 1.0)


def logits_for_position(h_last, unembed, vocab_size: Optional[int] = None):
    """(B, d) @ (d, V) -> (B, V) fp32 logits (serving head)."""
    logits = (h_last @ unembed).astype(jnp.float32)
    V = logits.shape[-1]
    if vocab_size is not None and vocab_size < V:
        logits = logits + jnp.where(
            jnp.arange(V) < vocab_size, 0.0, -1e30
        ).astype(jnp.float32)
    return logits
