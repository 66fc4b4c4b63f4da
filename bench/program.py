"""The program under test, as the benchmark reaches it: its model
configuration object built from a configuration file, and span and tree
helpers.  Nothing here is used by the references."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def model_config(config: dict, m: dict):
    """The program's ``ModelConfig`` for a configuration file: the registry
    entry the file names, with every size set from the file."""
    from repro.configs.base import get_config

    base = get_config(config["repro_config"])
    cfg = dataclasses.replace(
        base, n_layers=m["n_layers"], d_model=m["d_model"],
        n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
        head_dim=m["head_dim"], d_ff=m["d_ff"], vocab_size=m["vocab_size"],
        norm_eps=m["norm_eps"], rope_theta=m["rope_theta"],
        qk_norm=m["qk_norm"], tie_embeddings=m["tie_embeddings"],
        dtype=m["dtype"])
    if cfg.family != "dense" or cfg.ffn_act != "swiglu" or cfg.moe or cfg.ssm:
        raise ValueError(f"{cfg.name}: the reference covers dense SwiGLU only")
    return cfg


def named_leaves(tree) -> dict:
    """``{"layers/0/ffn/w_up": leaf, ...}``: the names the references use."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p): v
            for p, v in flat}


@jax.jit
def _norms(tree):
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def leaf_norms(tree) -> dict:
    return {k: float(v) for k, v in named_leaves(_norms(tree)).items()}


def span_totals() -> dict:
    """``{span path: [count, seconds]}`` of the program's span tracer."""
    from repro import obs

    return {p: [c, s] for p, c, s in obs.get_tracer().timeline()}


def span_delta(after: dict, before: dict) -> dict:
    out = {}
    for p, (c, s) in after.items():
        c0, s0 = before.get(p, [0, 0.0])
        if c - c0:
            out[p] = [c - c0, s - s0]
    return out
