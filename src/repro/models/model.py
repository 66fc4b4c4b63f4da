"""Top-level model: trunk executor (scan-over-layers) + train/serve heads.

Pure functions; every parallelism/fault-tolerance policy arrives as explicit
arguments (rules, ExecFlags, NDBContext) so the same code path serves smoke
tests (1 CPU device), the 512-device dry-run, and a real TPU deployment.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.ndb import NDBContext
from repro.models import frontends
from repro.models.layers import (
    attention_block,
    chunked_cross_entropy,
    ffn_block,
    logits_for_position,
    rmsnorm,
)
from repro.models.moe import moe_block
from repro.models.params import block_layout
from repro.models.ssm import ssm_block
from repro.parallel.sharding import ShardingRules, constrain

Tree = Any


@dataclass(frozen=True)
class ExecFlags:
    """Execution policy knobs (hillclimb levers)."""

    scan_layers: bool = True
    remat: str = "ffn"  # "none" | "ffn" | "full"
    attn_chunk: int = 1024
    causal_slice: bool = False  # triangular-sliced attention (halves FLOPs)
    ce_chunk: int = 512
    n_dp_shards: int = 1


# ---------------------------------------------------------------------------
# Trunk
# ---------------------------------------------------------------------------


def _apply_block(
    pos_kind,
    bp,
    pj,
    h,
    keep_l,
    cache_l,
    cfg,
    rules,
    ctx: NDBContext,
    flags: ExecFlags,
    positions,
    cur_len,
    prefill_history: bool = False,
    page_tables=None,
    page_size=None,
    kernel_impl: Optional[str] = None,
    sites: int = 1,
):
    kind, is_moe = pos_kind
    lowrank_mode = ctx.lowrank_mode()
    recompute = ctx.recompute_ffn() or flags.remat == "ffn"
    aux = jnp.float32(0)
    if kind == "attn":
        keep_attn = keep_l if ctx.mecefo.skip_mha_backward else 1.0
        with jax.named_scope("attn"):
            h, new_cache = attention_block(
                bp["mixer"], h, cfg, rules, keep_attn, positions,
                cache=cache_l, cur_len=cur_len,
                attn_chunk=flags.attn_chunk, causal_slice=flags.causal_slice,
                history=prefill_history, page_tables=page_tables,
                page_size=page_size, kernel_impl=kernel_impl, sites=sites,
            )
    else:
        h, new_cache = ssm_block(
            bp["mixer"], h, cfg, rules,
            proj=None if pj is None else pj.get("mixer"),
            keep=keep_l, lowrank_mode=lowrank_mode,
            recompute=ctx.recompute_ffn(), cache=cache_l,
        )
    with jax.named_scope("ffn"):
        if is_moe:
            h, aux = moe_block(
                bp["ffn"], h, cfg, rules, n_dp_shards=flags.n_dp_shards,
                proj=None if pj is None else pj.get("ffn"),
                keep=keep_l, lowrank_mode=lowrank_mode, recompute=recompute,
            )
        else:
            h = ffn_block(
                bp["ffn"], h, cfg, rules,
                proj=None if pj is None else pj.get("ffn"),
                keep=keep_l, lowrank_mode=lowrank_mode, recompute=recompute,
            )
    return h, new_cache, aux


def run_trunk(
    params: Tree,
    proj: Optional[Tree],
    h: jnp.ndarray,
    cfg: ModelConfig,
    rules: ShardingRules,
    ctx: NDBContext,
    flags: ExecFlags,
    *,
    positions,
    caches: Optional[Tree] = None,
    cur_len=None,
    prefill_history: bool = False,
    page_tables=None,
    page_size=None,
    kernel_impl: Optional[str] = None,
):
    """Runs all layers. Returns (h, new_caches, aux_loss_sum).

    ``page_tables`` switches the decode cache handling to the paged layout:
    ``caches`` leaves are physical page pools (n_periods, n_pages, page_size,
    KV, hd) and attention walks each slot's page table in place.
    ``prefill_history`` marks a chunk prefill (queries at ``cur_len..``
    attending to the cache prefix plus themselves).
    """
    layout = block_layout(cfg)
    period = cfg.block_period
    n_periods = cfg.n_layers // period
    B = h.shape[0]

    keep = None
    if ctx.mode in ("dynamic", "static"):
        keep = ctx.keep.reshape(n_periods, period, B)

    layer_params = params["layers"]
    layer_proj = proj["layers"] if proj is not None else None
    scanned = flags.scan_layers and n_periods > 1

    def super_block(h, xs):
        bps, pjs, keeps, cls = xs
        new_cls = [] if cls is not None else None
        aux_tot = jnp.float32(0)
        for p in range(period):
            keep_l = (
                keeps[p]
                if keeps is not None
                else (0.0 if ctx.mode == "degraded" else 1.0)
            )
            h, nc, aux = _apply_block(
                layout[p],
                bps[p],
                None if pjs is None else pjs[p],
                h,
                keep_l,
                None if cls is None else cls[p],
                cfg, rules, ctx, flags, positions, cur_len,
                prefill_history=prefill_history, page_tables=page_tables,
                page_size=page_size, kernel_impl=kernel_impl,
                sites=n_periods if scanned else 1,
            )
            aux_tot = aux_tot + aux
            if new_cls is not None:
                new_cls.append(nc)
        return h, (tuple(new_cls) if new_cls is not None else None, aux_tot)

    xs = (layer_params, layer_proj, keep, caches)

    if scanned:
        body = super_block
        if flags.remat == "full":
            body = jax.checkpoint(
                super_block, policy=jax.checkpoint_policies.nothing_saveable
            )
        elif flags.remat == "dots":
            # save matmul outputs: backward skips the forward recompute at
            # the cost of keeping per-layer dot results (needs accum=1-scale
            # per-device batches)
            body = jax.checkpoint(
                super_block,
                policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            )

        def scan_body(carry, xs):
            h = carry
            h, (ncs, aux) = body(h, xs)
            return h, (ncs, aux)

        h, (new_caches, auxs) = jax.lax.scan(scan_body, h, xs)
        aux_total = jnp.sum(auxs)
    else:
        new_caches = [] if caches is not None else None
        aux_total = jnp.float32(0)
        for i in range(n_periods):
            xs_i = jax.tree.map(lambda a: a[i], xs)
            body = super_block
            if flags.remat == "full":
                body = jax.checkpoint(
                    super_block, policy=jax.checkpoint_policies.nothing_saveable
                )
            h, (ncs, aux) = body(h, xs_i)
            aux_total = aux_total + aux
            if new_caches is not None:
                new_caches.append(ncs)
        if new_caches is not None:
            new_caches = jax.tree.map(lambda *a: jnp.stack(a), *new_caches)
    return h, new_caches, aux_total


# ---------------------------------------------------------------------------
# Heads
# ---------------------------------------------------------------------------


def _unembed(params):
    if "unembed" in params:
        return params["unembed"]
    return params["embed"].T


def forward_loss(
    params: Tree,
    proj: Optional[Tree],
    batch: Tree,
    cfg: ModelConfig,
    rules: ShardingRules,
    ctx: NDBContext,
    flags: ExecFlags,
):
    """Training loss (+ metrics dict).

    The step's device work carries named scopes (HLO ``op_name`` metadata,
    so a profile can split the step): ``embed``, ``attn`` and ``ffn`` (per
    layer, see ``_apply_block``), ``head``.
    """
    with jax.named_scope("embed"):
        h, token_w = frontends.embed_inputs(params, batch, cfg)
    h = constrain(h, rules, "batch", "seq", None)
    labels = frontends.full_labels(batch, cfg)
    S = h.shape[1]
    positions = jnp.arange(S)

    if ctx.example_weight is not None:
        token_w = token_w * ctx.example_weight[:, None]

    h, _, aux = run_trunk(
        params, proj, h, cfg, rules, ctx, flags, positions=positions
    )
    with jax.named_scope("head"):
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        ce = chunked_cross_entropy(
            h, _unembed(params), labels, token_w, rules, chunk=flags.ce_chunk,
            vocab_size=cfg.vocab_size,
        )
    loss = ce + aux
    return loss, {"loss": loss, "ce": ce, "aux": aux}


def forward_prefill(
    params: Tree,
    batch: Tree,
    cfg: ModelConfig,
    rules: ShardingRules,
    flags: ExecFlags,
    cache_structs_tree: Tree,
    logit_pos=None,
):
    """Prompt prefill: returns (filled caches, last-position logits).

    ``logit_pos`` selects which position's logits to return (default: the
    last) — a scalar, or a ``(B,)`` vector of per-row last-prompt positions
    for the batched-prefill path.  The serve engine pads prompts up to a
    page multiple to bound the number of compiled prefill shapes, and reads
    the logits at the true last prompt position — pad positions beyond it
    are never attended to later (the decode length mask stops at
    ``cur_len``).
    """
    ctx = NDBContext(mode="off")
    h, _ = frontends.embed_inputs(params, batch, cfg)
    h = constrain(h, rules, "batch", "seq", None)
    S = h.shape[1]
    positions = jnp.arange(S)
    caches = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_structs_tree)
    h, new_caches, _ = run_trunk(
        params, None, h, cfg, rules, ctx, flags,
        positions=positions, caches=caches, cur_len=jnp.int32(0),
    )
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    if logit_pos is None:
        h_last = h[:, -1]
    elif jnp.ndim(logit_pos):  # per-row positions (batched prefill)
        h_last = jnp.take_along_axis(
            h, jnp.asarray(logit_pos)[:, None, None], axis=1
        )[:, 0]
    else:
        h_last = jnp.take(h, logit_pos, axis=1)
    logits = logits_for_position(h_last, _unembed(params), cfg.vocab_size)
    return new_caches, logits


def forward_prefill_chunk(
    params: Tree,
    caches: Tree,
    batch: Tree,
    off,
    cfg: ModelConfig,
    rules: ShardingRules,
    flags: ExecFlags,
    logit_idx,
):
    """One page-aligned prompt chunk: tokens at positions ``off..off+C-1``
    attend to the cache prefix (``[0, off)`` — earlier chunks or a forked
    shared prefix) plus themselves, and write their K/V rows into the dense
    cache view at ``off``.  Returns (new caches, logits at chunk-local
    position ``logit_idx``).  Pad tokens past the true chunk length write
    garbage rows at or past the slot's ``cur_len`` — never read.
    """
    ctx = NDBContext(mode="off")
    h, _ = frontends.embed_inputs(params, batch, cfg)
    h = constrain(h, rules, "batch", "seq", None)
    C = h.shape[1]
    positions = off + jnp.arange(C)
    h, new_caches, _ = run_trunk(
        params, None, h, cfg, rules, ctx, flags,
        positions=positions, caches=caches, cur_len=jnp.asarray(off, jnp.int32),
        prefill_history=True,
    )
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    h_last = jnp.take(h, logit_idx, axis=1)
    logits = logits_for_position(h_last, _unembed(params), cfg.vocab_size)
    return new_caches, logits


def forward_decode(
    params: Tree,
    caches: Tree,
    token: jnp.ndarray,  # (B,) int32
    cur_len,  # scalar int32, or (B,) for ragged per-slot positions
    cfg: ModelConfig,
    rules: ShardingRules,
    flags: ExecFlags,
    *,
    page_tables=None,  # (B, P) int32: caches are physical page pools
    page_size: Optional[int] = None,
    kernel_impl: Optional[str] = None,
):
    """One decode step: returns (new caches, (B, V) logits).

    With ``page_tables`` the caches are the paged KV pool itself
    ((n_periods, n_pages, page_size, KV, hd) leaves): each slot's new K/V
    row is written to its page in place and attention walks the page table
    via the Pallas flash-decode kernel — no slot-major dense copy.
    """
    ctx = NDBContext(mode="off")
    if cfg.frontend == "audio":
        # stub frontend: decode consumes a token id like any LM
        h = params["embed"][token][:, None, :]
    else:
        h = params["embed"][token][:, None, :]
    h = constrain(h, rules, "batch", None, None)
    cur_len = jnp.asarray(cur_len, jnp.int32)
    # scalar: one shared position; (B,): per-slot rope positions (B, 1)
    positions = cur_len[None] if jnp.ndim(cur_len) == 0 else cur_len[:, None]
    if page_tables is not None and jnp.ndim(cur_len) == 0:
        cur_len = jnp.broadcast_to(cur_len, (h.shape[0],))
    h, new_caches, _ = run_trunk(
        params, None, h, cfg, rules, ctx, flags,
        positions=positions, caches=caches, cur_len=cur_len,
        page_tables=page_tables, page_size=page_size,
        kernel_impl=kernel_impl,
    )
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = logits_for_position(h[:, -1], _unembed(params), cfg.vocab_size)
    return new_caches, logits
