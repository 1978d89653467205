"""Ahead-of-time compiles of the served-path kernels for a TPU v5e.

Interpret mode runs a kernel's arithmetic but not the chip's compiler, so
it misses what only Mosaic refuses: blocks that break the (8, 128) tiling
rule or mismatch XLA's layout, and kernels that need more fast memory than
the chip has.  These tests compile each kernel for one chip of a
*described* (not attached) ``v5e:2x2`` topology at a paper-scale pane
(N = 262,144 tuples, Geohash-6 slots over the Shenzhen bounding box) and
check that the compiled program holds the Mosaic kernel.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and pytest-xdist workers all import
this file.  Everything stays in one file so one worker owns the library.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.edge_megakernel.edge_megakernel import edge_megakernel_pallas
from repro.kernels.edge_reduce.edge_reduce import edge_reduce_pallas
from repro.kernels.geohash.geohash import encode_pallas
from repro.kernels.sample_mask.sample_mask import sample_mask_pallas

N = 262_144  # tuples per pane
SLOTS = 6_558  # Geohash-6 strata over SHENZHEN_BBOX + the overflow slot
PRECISION = 6


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def sd(one_chip):
    """``sd(shape, dtype)``: an argument shape placed on the described chip."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *shapes):
    """Compile ``fn`` for the described chip; returns the compiled text."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize(
    "mode,members,cols",
    [
        ("sidx", 1, 4),  # the single-query program, every stat row kind
        ("sidx", 3, 2),  # the refined multi-member SRS program
        ("latlon", 1, 2),  # Bernoulli: in-kernel geohash + table match
        ("latlon", 2, 2),  # the refined multi-member Bernoulli program
    ],
)
def test_megakernel_compiles_for_v5e(sd, mode, members, cols):
    idx = tuple(range(cols))  # every column gets extrema and sketch rows
    per_member = [sd((members, N), jnp.float32), sd((members, N), jnp.float32)]
    args = [sd((cols, N), jnp.float32), *per_member, sd((members, SLOTS), jnp.float32)]
    if mode == "sidx":

        def fn(vals, ok, scores, thr, sidx):
            return edge_megakernel_pallas(
                vals, ok, scores, thr, SLOTS, sidx=sidx, ext_idx=idx, sk_idx=idx
            )

        args.append(sd((members, N), jnp.int32))
    else:

        def fn(vals, ok, scores, thr, lat, lon, codes):
            return edge_megakernel_pallas(
                vals, ok, scores, thr, SLOTS, lat=lat, lon=lon, codes=codes,
                precision=PRECISION, ext_idx=idx, sk_idx=idx,
            )

        args += [sd((N,), jnp.float32), sd((N,), jnp.float32), sd((SLOTS - 1,), jnp.uint32)]
    assert "tpu_custom_call" in _compile(fn, *args)


def test_edge_reduce_compiles_for_v5e(sd):
    text = _compile(
        lambda sidx, vals, mask: edge_reduce_pallas(sidx, vals, mask, SLOTS),
        sd((N,), jnp.int32), sd((2, N), jnp.float32), sd((N,), jnp.bool_),
    )
    assert "tpu_custom_call" in text


def test_sample_mask_compiles_for_v5e(sd):
    text = _compile(
        sample_mask_pallas,
        sd((N,), jnp.int32), sd((N,), jnp.float32), sd((SLOTS,), jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_geohash_compiles_for_v5e(sd):
    text = _compile(
        lambda lat, lon: encode_pallas(lat, lon, PRECISION),
        sd((N,), jnp.float32), sd((N,), jnp.float32),
    )
    assert "tpu_custom_call" in text
