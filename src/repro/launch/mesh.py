"""Production mesh construction (multi-pod dry-run brief, step 1).

A function — not a module-level constant — so importing this module never
touches jax device state (smoke tests must keep seeing 1 CPU device).
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "mesh_shape_dict"]


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the step functions place values with
    # with_sharding_constraint, which refuses make_mesh's default Explicit
    # axes
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(shape)
    )


def mesh_shape_dict(mesh: jax.sharding.Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
