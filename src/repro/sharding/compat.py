"""Mesh construction shared by every layer (depends only on jax)."""

from __future__ import annotations

import jax

# The mesh-axis vocabulary: the single source of truth every collective
# axis-name literal in the tree must be drawn from (edgelint EDG005).
MESH_AXIS_NAMES = ("pod", "data", "model")


def compat_make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which the
    shard_map'd edge programs would have to spell out every output
    sharding; ``Auto`` leaves it to the compiler.
    """
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
