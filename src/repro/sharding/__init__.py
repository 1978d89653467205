"""Sharding substrate: mesh construction and the mesh-axis vocabulary."""

from .compat import MESH_AXIS_NAMES, compat_make_mesh

__all__ = ["MESH_AXIS_NAMES", "compat_make_mesh"]
