"""Step builders: jitted train / prefill / decode steps with full shardings.

These are the exact programs the dry-run lowers and a real deployment runs.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (
    MeCeFOConfig,
    ModelConfig,
    ParallelConfig,
    ShapeConfig,
    TrainConfig,
)
from repro.core.grad_sync import rescale_skipped_grads
from repro.core.ndb import NDBContext
from repro.models.model import ExecFlags, forward_decode, forward_loss, forward_prefill
from repro.models.kvcache import cache_structs
from repro.optim.optimizers import apply_update, clip_by_global_norm, lr_schedule
from repro.parallel.sharding import default_rules, spec_tree
from repro.launch.mesh import mesh_shape_dict, n_dp_shards
from repro.launch.specs import batch_axes_for, input_specs, ndb_specs
from repro.launch.state import TrainState, state_specs, to_shardings

Tree = Any


def build_rules(cfg: ModelConfig, mesh, parallel: ParallelConfig):
    rules = default_rules(
        mesh,
        fsdp=parallel.fsdp,
        sequence_parallel=parallel.sequence_parallel,
        n_kv_heads=cfg.n_kv_heads if cfg.family != "ssm" else 0,
    )
    msd = mesh_shape_dict(mesh)
    model_n = msd.get("model", 1)
    hd = cfg.resolved_head_dim
    if (cfg.n_heads * hd) % model_n != 0:
        rules = replace(rules, heads=None)
    if (cfg.n_kv_heads * hd) % model_n != 0:
        rules = replace(rules, kv_heads=None)
    # Fused head-dim storage (models/params.py) keeps the TP dims divisible
    # even for non-divisible head counts (musicgen 24H on 16) — the per-head
    # attention math pads internally (GSPMD), ~33% attn waste vs the 16x
    # waste of replication. See EXPERIMENTS.md §Perf.
    if parallel.sharding_mode == "fsdp":
        # pure 2D FSDP: the batch shards over EVERY axis (model included —
        # otherwise the model axis holds storage but no compute); weights
        # shard over both axes via the embed dim; vocab stays model-sharded
        # for the chunked CE
        both = tuple(a for a in ("data", "model") if a in msd)
        batch = tuple(a for a in ("pod", "data", "model") if a in msd)
        rules = replace(
            rules,
            batch=batch,
            dispatch=tuple(a for a in ("pod", "data") if a in msd),
            heads=None, kv_heads=None, kv_cache=None, mlp=None,
            ssm_inner=None, vocab=None,
            embed=both if parallel.fsdp else None,
        )
    return rules


def build_flags(cfg: ModelConfig, parallel: ParallelConfig, mesh, shape=None) -> ExecFlags:
    attn_chunk = 1024
    if shape is not None and shape.kind != "decode":
        attn_chunk = min(1024, shape.seq_len)
    msd = mesh_shape_dict(mesh)
    nds = n_dp_shards(mesh)
    if parallel.sharding_mode == "fsdp":
        nds *= msd.get("model", 1)  # batch shards over the model axis too
    return ExecFlags(
        scan_layers=parallel.scan_layers,
        remat=parallel.remat,
        attn_chunk=attn_chunk,
        ce_chunk=512,
        n_dp_shards=nds,
    )


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


def make_train_step(
    cfg: ModelConfig,
    train: TrainConfig,
    parallel: ParallelConfig,
    mecefo: MeCeFOConfig,
    mesh,
    shape: ShapeConfig,
    *,
    ndb_mode: str = "off",  # "off" | "dynamic" | "degraded" | "static"
    static_ndb=None,        # (keep, weight) arrays baked in for "static"
    total_steps: int = 1000,
    flags: Optional[ExecFlags] = None,
    donate: bool = True,
):
    """Returns (jitted_step, state_shardings, batch_shardings, ndb_shardings).

    Signatures:
      off/degraded/static:  step(state, batch)       -> (state, metrics)
      dynamic:              step(state, batch, ndb)  -> (state, metrics)

    "static" bakes the plan's masks in as compile-time constants (one
    specialized executable per NDB plan — the compile-cache failover mode).
    """
    rules = build_rules(cfg, mesh, parallel)
    flags = flags or build_flags(cfg, parallel, mesh, shape)
    schedule = lr_schedule(train, total_steps)
    msd = mesh_shape_dict(mesh)
    bax = batch_axes_for(shape.global_batch, rules, msd)
    pspec_tree = state_specs(cfg, train, mecefo, rules).params
    nds = n_dp_shards(mesh)
    if parallel.sharding_mode == "fsdp":
        nds *= msd.get("model", 1)
    accum = max(parallel.accum, 1)
    B = shape.global_batch
    if B % (nds * accum) != 0:
        accum = 1

    def _split_micro(x):
        """(B, ...) -> (accum, B/accum, ...) without crossing batch shards.

        dim 0 is sharded contiguously over `nds` shards; interleave so every
        microbatch keeps the same per-shard row block (no resharding).
        """
        b_loc = B // nds
        rest = x.shape[1:]
        x = x.reshape(nds, accum, b_loc // accum, *rest)
        x = jnp.swapaxes(x, 0, 1).reshape(accum, B // accum, *rest)
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.PartitionSpec(None, bax, *([None] * len(rest)))
        )

    if ndb_mode == "static":
        assert static_ndb is not None
        _static_keep = jnp.asarray(static_ndb[0])
        _static_w = jnp.asarray(static_ndb[1])

    def _make_ctx(ndb, mb=None):
        if ndb_mode == "off":
            return NDBContext(mode="off", mecefo=mecefo)
        if ndb_mode == "degraded":
            return NDBContext(mode="degraded", mecefo=mecefo)
        if ndb_mode == "static":
            keep, w = _static_keep, _static_w
            if mb is not None:
                keep, w = mb
            return NDBContext(
                mode="static", keep=keep, example_weight=w, mecefo=mecefo
            )
        keep, w = ndb["keep"], ndb["example_weight"]
        if mb is not None:
            keep, w = mb
        return NDBContext(mode="dynamic", keep=keep, example_weight=w, mecefo=mecefo)

    def step_fn(state: TrainState, batch: Dict, ndb: Optional[Dict] = None):
        proj = state.proj if mecefo.mode != "off" else None

        def loss_fn(params, mbatch, mb_ctx):
            ctx = _make_ctx(ndb, mb_ctx)
            return forward_loss(params, proj, mbatch, cfg, rules, ctx, flags)

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        if accum == 1:
            (loss, metrics), grads = grad_fn(state.params, batch, None)
        else:
            mbatches = jax.tree.map(_split_micro, batch)
            mb_ctx = None
            if ndb_mode in ("dynamic", "static"):
                keep_full = ndb["keep"] if ndb_mode == "dynamic" else _static_keep
                w_full = (
                    ndb["example_weight"] if ndb_mode == "dynamic" else _static_w
                )
                keep_mb = _split_micro(jnp.swapaxes(keep_full, 0, 1))
                keep_mb = jnp.swapaxes(keep_mb, 1, 2)  # (accum, L, b)
                w_mb = _split_micro(w_full)
                mb_ctx = (keep_mb, w_mb)

            def micro(carry, xs):
                g_acc, l_acc = carry
                mbatch = xs[0]
                mctx = (xs[1], xs[2]) if ndb_mode in ("dynamic", "static") else None
                (l, m), g = grad_fn(state.params, mbatch, mctx)
                if parallel.grad_compression == "bf16":
                    # industry-standard: cross-device gradient reduction in
                    # bf16 (half the wire), fp32 accumulation locally
                    g = jax.tree.map(lambda a: a.astype(jnp.bfloat16), g)
                # constrain the per-microbatch gradient itself: turns the
                # per-µb cross-data reduction into a reduce-scatter (half the
                # wire bytes of the all-reduce GSPMD otherwise picks)
                g = jax.tree.map(
                    lambda a, sp: jax.lax.with_sharding_constraint(a, sp),
                    g, pspec_tree,
                    is_leaf=lambda x: isinstance(x, jnp.ndarray),
                )
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g
                )
                # keep the carry on the param sharding: the per-microbatch
                # partial dW is reduce-scattered (ZeRO-style), not all-reduced
                g_acc = jax.tree.map(
                    lambda a, sp: jax.lax.with_sharding_constraint(a, sp),
                    g_acc, pspec_tree,
                    is_leaf=lambda x: isinstance(x, jnp.ndarray),
                )
                return (g_acc, l_acc + l), m

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            xs = (
                (mbatches, mb_ctx[0], mb_ctx[1])
                if mb_ctx is not None
                else (mbatches, (), ())
            )
            (grads, loss_sum), ms = jax.lax.scan(micro, (g0, jnp.float32(0)), xs)
            grads = jax.tree.map(lambda g: g / accum, grads)
            loss = loss_sum / accum
            metrics = jax.tree.map(lambda x: x[-1], ms)
            metrics["loss"] = loss

        with jax.named_scope("optimizer"):
            if mecefo.skip_mha_backward and ndb_mode in ("dynamic", "static"):
                # eq. (1), with |N_l|/n measured over live examples only:
                # under an elastic resize the repartitioned batch keeps every
                # weight at 1, while a transient whole-rank failure
                # zero-weights its slice and must not deflate the per-layer
                # active fraction.
                keep_full = ndb["keep"] if ndb_mode == "dynamic" else _static_keep
                w_full = (ndb["example_weight"] if ndb_mode == "dynamic"
                          else _static_w)
                grads = rescale_skipped_grads(grads, keep_full, cfg, w_full)
            grads, gnorm = clip_by_global_norm(grads, train.grad_clip)
            lr = schedule(state.step)
            new_params, new_opt = apply_update(
                state.params, grads, state.opt, lr, state.step, train
            )
        new_state = TrainState(
            step=state.step + 1, params=new_params, opt=new_opt, proj=state.proj
        )
        metrics = dict(metrics, grad_norm=gnorm, lr=lr)
        return new_state, metrics

    sspecs = state_specs(cfg, train, mecefo, rules)
    sshard = to_shardings(mesh, sspecs)
    _, bspecs = input_specs(cfg, shape, rules, msd)
    bshard = to_shardings(mesh, bspecs)
    mshard = NamedSharding(mesh, P())

    if ndb_mode == "dynamic":
        _, nspecs = ndb_specs(cfg, shape.global_batch, bax)
        nshard = to_shardings(mesh, nspecs)
        jitted = jax.jit(
            step_fn,
            in_shardings=(sshard, bshard, nshard),
            out_shardings=(sshard, mshard),
            donate_argnums=(0,) if donate else (),
        )
        return jitted, sshard, bshard, nshard
    jitted = jax.jit(
        lambda state, batch: step_fn(state, batch, None),
        in_shardings=(sshard, bshard),
        out_shardings=(sshard, mshard),
        donate_argnums=(0,) if donate else (),
    )
    return jitted, sshard, bshard, None


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------


def make_prefill_step(
    cfg: ModelConfig,
    parallel: ParallelConfig,
    mesh,
    shape: ShapeConfig,
    *,
    flags: Optional[ExecFlags] = None,
    max_len: Optional[int] = None,
):
    """step(params, batch) -> (caches, logits)."""
    rules = build_rules(cfg, mesh, parallel)
    flags = flags or build_flags(cfg, parallel, mesh, shape)
    flags = replace(flags, remat="none")
    msd = mesh_shape_dict(mesh)
    B, S = shape.global_batch, shape.seq_len
    bax = batch_axes_for(B, rules, msd)
    cstructs = cache_structs(cfg, B, max_len or S)

    def step_fn(params, batch):
        return forward_prefill(params, batch, cfg, rules, flags, cstructs)

    from repro.models.params import param_annotations

    pspec = spec_tree(rules, param_annotations(cfg))
    pshard = to_shardings(mesh, pspec)
    _, bspecs = input_specs(cfg, shape, rules, msd)
    bshard = to_shardings(mesh, bspecs)
    dshape = ShapeConfig("tmp", max_len or S, B, "decode")
    dstructs, dspecs = input_specs(cfg, dshape, rules, msd)
    cshard = to_shardings(mesh, dspecs["caches"])
    lshard = NamedSharding(mesh, P(bax, rules.vocab))
    jitted = jax.jit(
        step_fn, in_shardings=(pshard, bshard), out_shardings=(cshard, lshard)
    )
    return jitted, pshard, bshard


def make_decode_step(
    cfg: ModelConfig,
    parallel: ParallelConfig,
    mesh,
    shape: ShapeConfig,
    *,
    flags: Optional[ExecFlags] = None,
):
    """step(params, caches, token, cur_len) -> (caches, logits)."""
    rules = build_rules(cfg, mesh, parallel)
    flags = flags or build_flags(cfg, parallel, mesh, shape)
    flags = replace(flags, remat="none")
    msd = mesh_shape_dict(mesh)
    B = shape.global_batch
    bax = batch_axes_for(B, rules, msd)

    def step_fn(params, caches, token, cur_len):
        return forward_decode(params, caches, token, cur_len, cfg, rules, flags)

    from repro.models.params import param_annotations

    pspec = spec_tree(rules, param_annotations(cfg))
    pshard = to_shardings(mesh, pspec)
    dstructs, dspecs = input_specs(cfg, shape, rules, msd)
    cshard = to_shardings(mesh, dspecs["caches"])
    tshard = to_shardings(mesh, dspecs["token"])
    clshard = NamedSharding(mesh, P())
    lshard = NamedSharding(mesh, P(bax, rules.vocab))
    jitted = jax.jit(
        step_fn,
        in_shardings=(pshard, cshard, tshard, clshard),
        out_shardings=(cshard, lshard),
        donate_argnums=(1,),
    )
    return jitted, pshard, dspecs


def default_accum(cfg: ModelConfig, shape: ShapeConfig, mesh,
                  parallel: ParallelConfig = None) -> int:
    """Pick grad-accumulation so per-device layer-input checkpoints stay
    within ~2.5 GB (the stacked remat carries are the activation floor)."""
    if shape.kind != "train":
        return 1
    nds = n_dp_shards(mesh)
    if parallel is not None and parallel.sharding_mode == "fsdp":
        nds *= mesh_shape_dict(mesh).get("model", 1)
    n_dev = mesh.devices.size
    B = shape.global_batch
    b_loc = max(B // nds, 1)
    tokens_dev = b_loc * shape.seq_len
    ckpt_bytes = tokens_dev * cfg.d_model * 2 * cfg.n_layers
    from repro.models.params import count_params

    state_bytes = count_params(cfg) * 14 // n_dev  # bf16 p + f32 g,m,v
    # halve the nominal budget: transient (non-checkpoint) buffers in the
    # layer backward roughly match the checkpoint footprint
    budget = max(int((16e9 - state_bytes - 6e9) // 2), int(1_200_000_000))
    need = max(1, -(-ckpt_bytes // budget))
    accum = 1
    for cand in range(1, b_loc + 1):
        if b_loc % cand == 0:
            accum = cand
            if cand >= need:
                break
    return accum
