"""Mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
must set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax


def _mk(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (data, model) per pod; 2 pods add a leading pure-DP 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """(data, model) mesh over the local devices (CPU training, one chip,
    or one four-chip host).  Asking for more devices than exist is an
    error: a mesh that silently shrinks would put every shard on one
    device."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(
            f"mesh data={data} x model={model} needs {data * model} devices,"
            f" {n} available"
        )
    return _mk((data, model), ("data", "model"))


def mesh_shape_dict(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def n_dp_shards(mesh) -> int:
    d = mesh_shape_dict(mesh)
    return d.get("pod", 1) * d.get("data", 1)
