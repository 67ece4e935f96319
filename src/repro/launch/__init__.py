"""Mesh construction."""

from .mesh import make_local_mesh

__all__ = ["make_local_mesh"]
