"""Production mesh construction (assignment-mandated shapes).

A FUNCTION, not a module-level constant: importing this module never
touches jax device state.
"""
from __future__ import annotations

from jax import make_mesh
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "data_axes"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def data_axes(mesh) -> tuple:
    """The data-parallel axes of a production mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
