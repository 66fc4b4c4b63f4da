"""Technique III — low-rank FFN weight-gradient approximation (paper eq. (2)).

For a linear ``y = x @ W`` with ``W ∈ R^{n×m}`` (input dim n), the exact
weight gradient is ``dW = x^T dy`` (2·b·m·n FLOPs, b = tokens).  MeCeFO
approximates it by projecting onto the top-r input-space singular subspace of
W (``V1 ∈ R^{n×r}``, refreshed every τ steps):

    dW ≈ V1 @ ((x @ V1)^T dy)        # 2brn + 2brm + 2rmn FLOPs

Three backward modes:
  * ``exact``     — standard dW (healthy layers).
  * ``degraded``  — pure low-rank path in the FLOP-efficient order above
                    (static NDB: the whole segment is degraded).
  * ``mixed``     — per-example: masked examples contribute the projected
                    gradient, unmasked ones the exact gradient (dynamic NDB).

``dx`` is always exact — the paper only approximates Wgrad, not Dgrad.

The storage convention here is transposed vs. the paper (W: m×n, right
singular vectors): our ``V1`` are the top *left* singular vectors of the
stored (n×m) matrix, which span the same input space.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

Tree = Any

# ---------------------------------------------------------------------------
# SVD projections
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnums=1)
def svd_projection(w: jnp.ndarray, rank: int) -> jnp.ndarray:
    """Top-`rank` input-space singular vectors of a stacked weight.

    Accepts (..., n, m); returns (..., n, r). Computed in fp32, cast back.
    One program per weight shape, named ``jit_svd_projection`` in a
    profile.
    """
    rank = min(rank, w.shape[-2], w.shape[-1])
    u, _s, _vh = jnp.linalg.svd(w.astype(jnp.float32), full_matrices=False)
    return u[..., :, :rank].astype(w.dtype)


_LOWRANK_FFN = ("w_gate", "w_up", "w_down")
_LOWRANK_SSM = ("in_proj", "out_proj")


def _lowrank_leaf_names(kind: str, part: str):
    if part == "ffn":
        return _LOWRANK_FFN
    if part == "mixer" and kind == "ssm":
        return _LOWRANK_SSM
    return ()


def refresh_projections(params: Tree, cfg: ModelConfig, rank: int) -> Tree:
    """(Re)compute the V1 tree from current params (Alg. 3, every τ steps)."""
    from repro.models.params import block_layout

    layers = []
    for pos, (kind, _is_moe) in enumerate(block_layout(cfg)):
        block = params["layers"][pos]
        out = {"mixer": {}, "ffn": {}}
        for part in ("mixer", "ffn"):
            for name in _lowrank_leaf_names(kind, part):
                if name in block[part]:
                    out[part][name] = svd_projection(block[part][name], rank)
        layers.append(out)
    return {"layers": tuple(layers)}


def init_projections(params: Tree, cfg: ModelConfig, rank: int) -> Tree:
    """Zero-initialized V1 tree (valid before the first τ-refresh)."""
    proj = refresh_projections_structs_like(params, cfg, rank)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), proj)


def refresh_projections_structs_like(params: Tree, cfg: ModelConfig, rank: int) -> Tree:
    from repro.models.params import block_layout

    layers = []
    for pos, (kind, _is_moe) in enumerate(block_layout(cfg)):
        block = params["layers"][pos]
        out = {"mixer": {}, "ffn": {}}
        for part in ("mixer", "ffn"):
            for name in _lowrank_leaf_names(kind, part):
                if name in block[part]:
                    w = block[part][name]
                    r = min(rank, w.shape[-2], w.shape[-1])
                    shape = (*w.shape[:-1], r)
                    out[part][name] = jax.ShapeDtypeStruct(shape, w.dtype)
        layers.append(out)
    return {"layers": tuple(layers)}


def projection_structs(cfg: ModelConfig, rank: int, dtype=None) -> Tree:
    """ShapeDtypeStruct V1 tree for the dry-run (no allocation)."""
    from repro.models.params import param_structs

    structs = param_structs(cfg, dtype)
    return refresh_projections_structs_like(structs, cfg, rank)


def projection_annotations(cfg: ModelConfig) -> Tree:
    """Logical sharding annotations for the V1 tree (input dim follows W)."""
    from repro.models.params import param_annotations, block_layout

    anns = param_annotations(cfg)
    layers = []
    for pos, (kind, _is_moe) in enumerate(block_layout(cfg)):
        block = anns["layers"][pos]
        out = {"mixer": {}, "ffn": {}}
        for part in ("mixer", "ffn"):
            for name in _lowrank_leaf_names(kind, part):
                if name in block[part]:
                    ann = block[part][name]
                    out[part][name] = (*ann[:-1], None)  # rank dim replicated
        layers.append(out)
    return {"layers": tuple(layers)}


# ---------------------------------------------------------------------------
# Low-rank linear (dense)
# ---------------------------------------------------------------------------


def _replicate(a):
    """Force replication (→ all-reduce of the factored gradient) when a mesh
    context is active; no-op otherwise (single-device tests)."""
    try:
        from jax.sharding import PartitionSpec as P

        return jax.lax.with_sharding_constraint(a, P())
    except (ValueError, RuntimeError, TypeError):
        return a


# the named scope of the low-rank Wgrad inside the custom backward rules:
# a profile's ops under it are the degraded path's extra device work
LOWRANK_SCOPE = "lowrank_wgrad"


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def lowrank_linear(x, w, v1, keep, mode: str = "exact"):
    """``y = x @ w`` with a MeCeFO backward for dW.

    Args:
      x:    (..., n) activations.
      w:    (n, m) weight.
      v1:   (n, r) projection (ignored in ``exact`` mode; pass zeros).
      keep: (B,) per-example keep mask (1 = exact) — used by ``mixed`` only.
      mode: "exact" | "degraded" | "mixed" (static — selects the compiled bwd).
    """
    return x @ w


def _ll_fwd(x, w, v1, keep, mode):
    return x @ w, (x, w, v1, keep)


def _ll_bwd(mode, res, dy):
    x, w, v1, keep = res
    dx = dy @ w.T
    xf = x.reshape(-1, x.shape[-1])
    dyf = dy.reshape(-1, dy.shape[-1])
    if mode == "exact":
        dw = xf.T @ dyf
    elif mode in ("degraded", "degraded_sync"):
        # FLOP-efficient order: never materialize the full x^T dy.
        with jax.named_scope(LOWRANK_SCOPE):
            p = xf @ v1                 # (b, r)
            a = p.T @ dyf               # (r, m)
            if mode == "degraded_sync":
                # Beyond-paper: force the DP all-reduce onto the factored
                # (r, m) gradient instead of the (n, m) product — cuts
                # collective bytes by r/n for degraded layers (DESIGN.md §3).
                a = _replicate(a)
            dw = v1 @ a                 # (n, m)
    elif mode == "mixed":
        k = keep.astype(dy.dtype)
        k = k.reshape(k.shape + (1,) * (dy.ndim - 1))
        dy_keep = (dy * k).reshape(-1, dy.shape[-1])
        dw_exact = xf.T @ dy_keep
        with jax.named_scope(LOWRANK_SCOPE):
            dy_skip = (dy * (1 - k)).reshape(-1, dy.shape[-1])
            p = xf @ v1
            a = p.T @ dy_skip
            dw_low = v1 @ a
        dw = dw_exact + dw_low
    else:
        raise ValueError(mode)
    return dx, dw.astype(w.dtype), jnp.zeros_like(v1), jnp.zeros_like(keep)


lowrank_linear.defvjp(_ll_fwd, _ll_bwd)


# ---------------------------------------------------------------------------
# Low-rank linear (grouped — MoE experts)
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def lowrank_linear_grouped(x, w, v1, keep, mode: str = "exact"):
    """Grouped ``y[e] = x[e] @ w[e]`` with MeCeFO backward per expert.

    x: (E, C, n), w: (E, n, m), v1: (E, n, r).  ``keep`` is a (E, C) slot mask
    for mixed mode (slots from degraded examples).
    """
    return jnp.einsum("ecn,enm->ecm", x, w)


def _llg_fwd(x, w, v1, keep, mode):
    return jnp.einsum("ecn,enm->ecm", x, w), (x, w, v1, keep)


def _llg_bwd(mode, res, dy):
    x, w, v1, keep = res
    dx = jnp.einsum("ecm,enm->ecn", dy, w)
    if mode == "exact":
        dw = jnp.einsum("ecn,ecm->enm", x, dy)
    elif mode in ("degraded", "degraded_sync"):
        with jax.named_scope(LOWRANK_SCOPE):
            p = jnp.einsum("ecn,enr->ecr", x, v1)
            a = jnp.einsum("ecr,ecm->erm", p, dy)
            if mode == "degraded_sync":
                a = _replicate(a)
            dw = jnp.einsum("enr,erm->enm", v1, a)
    elif mode == "mixed":
        k = keep.astype(dy.dtype)[..., None]
        dw = jnp.einsum("ecn,ecm->enm", x, dy * k)
        with jax.named_scope(LOWRANK_SCOPE):
            p = jnp.einsum("ecn,enr->ecr", x, v1)
            a = jnp.einsum("ecr,ecm->erm", p, dy * (1 - k))
            dw_low = jnp.einsum("enr,erm->enm", v1, a)
        dw = dw + dw_low
    else:
        raise ValueError(mode)
    return dx, dw.astype(w.dtype), jnp.zeros_like(v1), jnp.zeros_like(keep)


lowrank_linear_grouped.defvjp(_llg_fwd, _llg_bwd)


def wgrad_flops(b: int, n: int, m: int, r: Optional[int]) -> int:
    """Napkin-math helper: Wgrad FLOPs exact vs low-rank (paper §3.4)."""
    if r is None:
        return 2 * b * m * n
    return 2 * b * r * n + 2 * b * r * m + 2 * r * m * n
