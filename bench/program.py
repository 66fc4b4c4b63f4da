"""The program under test, as the benchmark reaches it: its model
configuration object built from a configuration file, and span and tree
helpers.  Nothing here is used by the references."""
from __future__ import annotations

import dataclasses
import typing

import jax
import jax.numpy as jnp

from bench import flops


def model_config(config: dict, m: dict):
    """The program's ``ModelConfig`` for a configuration file: the registry
    entry the file names, with every entry of ``m`` (the file's
    ``harness_keys``, as ``bench.cell.model_sizes`` reads them) set on it.

    A dotted name (``moe.n_experts``, ``ssm.d_state``) sets a field of that
    sub-config, which is made from its defaults where the entry has none.  A
    name that is no field of the program's config raises.  The layer pattern
    that ``bench.flops`` reads from ``m`` has to be the one the program
    runs, so that a file cannot leave its family or experts to the registry
    and have them counted as dense, and the map states every size that
    count reads."""
    from repro.configs.base import get_config

    base = get_config(config["repro_config"])
    top, subs = {}, {}
    for key, value in m.items():
        group, _, name = key.partition(".")
        if name:
            subs.setdefault(group, {})[name] = value
        else:
            top[key] = value
    for group, fields in subs.items():
        top[group] = _with_fields(_sub_config(base, group), fields, group + ".")
    cfg = _with_fields(base, top, "")
    runs = [(cfg.layer_kind(i), cfg.layer_is_moe(i))
            for i in range(cfg.n_layers)]
    described = flops.layer_pattern(m)
    if described != runs:
        raise ValueError(
            f"{config['name']}: the program runs the layers {runs}, the "
            f"harness_keys describe {described}; state family, attn_every, "
            "attn_offset and the moe.* sizes in the file")
    missing = flops.missing_sizes(m)
    if missing:
        raise ValueError(f"{config['name']}: the FLOP count reads {missing}, "
                         "which the harness_keys leave to the defaults")
    return cfg


def _sub_config(cfg, group: str):
    """The sub-config in field ``group``, or its defaults where it is unset."""
    hint = typing.get_type_hints(type(cfg)).get(group)
    kinds = [t for t in typing.get_args(hint) if dataclasses.is_dataclass(t)]
    if not kinds:
        raise ValueError(f"harness_keys: {group!r} is no sub-config of the "
                         f"program's {type(cfg).__name__}")
    return getattr(cfg, group) or kinds[0]()


def _with_fields(obj, fields: dict, prefix: str):
    names = {f.name for f in dataclasses.fields(obj)}
    for key in fields:
        if key not in names:
            raise ValueError(f"harness_keys: {prefix + key!r} is no field of "
                             f"the program's {type(obj).__name__}")
    return dataclasses.replace(obj, **fields)


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
