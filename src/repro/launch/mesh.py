"""Production mesh construction (assignment-mandated shapes).

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh", "batch_axes", "model_axis"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis of type Auto."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod adds the 2-pod leading axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def batch_axes(mesh) -> tuple[str, ...]:
    """Mesh axes that shard the batch (data parallel, incl. pods)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis(mesh) -> str:
    return "model"
