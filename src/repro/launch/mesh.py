"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then calls it.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh_compat(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over however many devices the host actually has (CPU
    tests / the runnable examples)."""
    return make_mesh_compat((data, model), ("data", "model"))
