"""Production meshes.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model); the pod axis
is pure data parallelism (gradient all-reduce over DCN), FSDP stays inside
a pod (ICI), TP stays on the model axis — the standard multi-slice layout.

Functions, not module constants: importing this module never touches jax
device state (required so tests/benches see 1 device).
"""

from __future__ import annotations


from ..sharding.compat import compat_make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat_make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    """Small mesh for CI-scale sharding tests (8 host devices)."""
    return compat_make_mesh(shape, axes)


def data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def num_chips(mesh) -> int:
    n = 1
    for s in mesh.shape.values():
        n *= s
    return n
