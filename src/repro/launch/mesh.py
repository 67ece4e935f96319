"""Device meshes for the tests and the runnable examples.

Functions, not module-level constants, so importing this module never
touches jax device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh_compat(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over however many devices the host actually has (CPU
    tests / the runnable examples)."""
    return make_mesh_compat((data, model), ("data", "model"))
