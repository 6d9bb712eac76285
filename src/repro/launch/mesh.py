"""Production meshes.

Single pod: (data=16, model=16) — 256 v5e chips.
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the `pod` axis is the
geo-distribution axis (DCI links), priced accordingly by the cost model
(repro.core.devices.fleet_from_tpu_mesh).

A FUNCTION, not a module constant: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before any device init).
"""

from __future__ import annotations

import jax

__all__ = ["make_mesh", "use_mesh", "named_shardings", "make_production_mesh",
           "mesh_chips", "data_axes", "MODEL_AXIS"]

MODEL_AXIS = "model"


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis of type Auto."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def use_mesh(mesh: jax.sharding.Mesh):
    """Context manager installing ``mesh`` as the ambient mesh that
    ``repro.models.sharding._active_mesh`` reads back."""
    return jax.set_mesh(mesh)


def named_shardings(mesh: jax.sharding.Mesh, spec_tree):
    """PartitionSpec tree → NamedSharding tree."""
    P = jax.sharding.PartitionSpec
    return jax.tree.map(lambda s: jax.sharding.NamedSharding(mesh, s),
                        spec_tree, is_leaf=lambda x: isinstance(x, P))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_chips(mesh: jax.sharding.Mesh) -> int:
    n = 1
    for _, s in mesh.shape.items():
        n *= s
    return n


def data_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
