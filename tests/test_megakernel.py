"""Property tests for the single-traversal edge megakernel triad.

The interpreted Pallas kernel and the portable segment lowering are both
checked against the jax-free numpy oracle (``ref.py``) across both
membership modes:

* ``sidx`` mode — precomputed stratum indices, every slot (overflow
  included) covered exactly;
* ``latlon`` mode — geohash encode + sorted-code-table membership resolve
  *inside* the kernel; tuples whose cell is absent from the table land in
  no slot (their stat rows stay zero — the wrapper layer reconstructs
  overflow counts as residuals).

Sweeps cover non-block-multiple N, the overflow stratum, all-masked
windows, multi-member thresholds, ext/sketch column subsets, and bf16
value staging (f32 accumulation; parity against the pre-rounded oracle).
"""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from hypothesis import given, settings, strategies as st

from repro.core.estimators import SKETCH_NUM_BINS
from repro.kernels.edge_megakernel import edge_megakernel
from repro.kernels.edge_megakernel.edge_megakernel import edge_megakernel_pallas
from repro.kernels.edge_megakernel.ops import _edge_megakernel_segment
from repro.kernels.geohash.ref import encode_ref

FIELDS = ("pop", "keep", "s1", "s2", "mins", "maxs", "bins")


def _assert_matches(got, ref, label, rtol=2e-6, atol=1e-3):
    for g, r, name in zip(tuple(got), ref, FIELDS):
        g = np.asarray(g)
        assert g.shape == np.asarray(r).shape, f"{label}:{name}"
        np.testing.assert_allclose(
            g, r, rtol=rtol, atol=atol, err_msg=f"{label}:{name}"
        )


def _sidx_case(n, m, c, s, seed, ok_mode):
    rng = np.random.default_rng(seed)
    sidx = rng.integers(0, s, (m, n)).astype(np.int32)
    if s > 1 and n > 1:
        sidx[:, 0] = s - 1  # always hit the overflow slot when possible
    vals = rng.normal(25, 8, (c, n)).astype(np.float32)
    if ok_mode == "all":
        ok = np.ones((m, n), np.float32)
    elif ok_mode == "none":
        ok = np.zeros((m, n), np.float32)  # all-masked window
    else:
        ok = (rng.random((m, n)) < 0.7).astype(np.float32)
    scores = rng.random((m, n)).astype(np.float32)
    thr = rng.uniform(0.0, 1.0, (m, s)).astype(np.float32)
    return sidx, vals, ok, scores, thr


@given(
    n=st.integers(1, 700),  # straddles the 512-point block boundary
    m=st.integers(1, 3),
    c=st.integers(1, 4),
    s=st.integers(1, 40),
    seed=st.integers(0, 2**30),
    ok_mode=st.sampled_from(["random", "all", "none"]),
)
@settings(max_examples=10, deadline=None)
def test_megakernel_sidx_parity(n, m, c, s, seed, ok_mode):
    """Interpreted kernel == numpy oracle in sidx mode across member
    counts, non-block-multiple N, the overflow stratum, and all-masked
    windows, with extrema+sketch rows on a column subset."""
    sidx, vals, ok, scores, thr = _sidx_case(n, m, c, s, seed, ok_mode)
    ext_idx = (0,) if c >= 1 else ()
    sk_idx = (c - 1,) if c >= 1 else ()
    got = edge_megakernel_pallas(
        jnp.asarray(vals), jnp.asarray(ok), jnp.asarray(scores), jnp.asarray(thr),
        s, sidx=jnp.asarray(sidx), ext_idx=ext_idx, sk_idx=sk_idx, interpret=True,
    )
    from repro.kernels.edge_megakernel.ref import edge_megakernel_ref

    ref = edge_megakernel_ref(
        vals, ok, scores, thr, s, sidx=sidx, ext_idx=ext_idx, sk_idx=sk_idx
    )
    _assert_matches(got, ref, f"sidx[{n},{m},{c},{s},{ok_mode}]")
    if ok_mode == "none":
        assert not np.asarray(got.keep).any()
        assert np.all(np.asarray(got.mins) == np.inf)
        assert np.all(np.asarray(got.maxs) == -np.inf)


def _latlon_case(n, m, seed, *, drop_every_other=True):
    rng = np.random.default_rng(seed)
    lat = rng.uniform(0.0, 1.0, n).astype(np.float32)
    lon = rng.uniform(0.0, 1.0, n).astype(np.float32)
    codes = np.unique(np.asarray(encode_ref(lat, lon, 4)))
    if drop_every_other and codes.shape[0] > 1:
        codes = codes[::2]  # absent cells exercise the match-nothing path
    s = int(codes.shape[0])
    vals = rng.normal(5, 3, (2, n)).astype(np.float32)
    ok = (rng.random((m, n)) < 0.8).astype(np.float32)
    scores = rng.random((m, n)).astype(np.float32)
    thr = np.broadcast_to(
        rng.uniform(0.2, 0.9, (m, 1)).astype(np.float32), (m, s)
    ).copy()
    return lat, lon, codes, s, vals, ok, scores, thr


@given(n=st.integers(1, 600), m=st.integers(1, 2), seed=st.integers(0, 2**30))
@settings(max_examples=8, deadline=None)
def test_megakernel_latlon_parity(n, m, seed):
    """Interpreted kernel == numpy oracle in latlon mode: in-kernel geohash
    encode + code-table membership, absent cells matching no slot."""
    lat, lon, codes, s, vals, ok, scores, thr = _latlon_case(n, m, seed)
    got = edge_megakernel_pallas(
        jnp.asarray(vals), jnp.asarray(ok), jnp.asarray(scores), jnp.asarray(thr),
        s, lat=jnp.asarray(lat), lon=jnp.asarray(lon), codes=jnp.asarray(codes),
        precision=4, ext_idx=(0,), sk_idx=(1,), interpret=True,
    )
    from repro.kernels.edge_megakernel.ref import edge_megakernel_ref

    ref = edge_megakernel_ref(
        vals, ok, scores, thr, s, lat=lat, lon=lon, codes=codes,
        precision=4, ext_idx=(0,), sk_idx=(1,),
    )
    _assert_matches(got, ref, f"latlon[{n},{m}]")


@given(seed=st.integers(0, 2**30))
@settings(max_examples=6, deadline=None)
def test_megakernel_segment_lowering_parity(seed):
    """The portable jnp lowering (what backend='fused' runs off-TPU)
    matches the oracle in both membership modes."""
    from repro.kernels.edge_megakernel.ref import edge_megakernel_ref

    sidx, vals, ok, scores, thr = _sidx_case(900, 2, 3, 25, seed, "random")
    got = _edge_megakernel_segment(
        jnp.asarray(vals), jnp.asarray(ok), jnp.asarray(scores), jnp.asarray(thr),
        25, sidx=jnp.asarray(sidx), ext_idx=(1,), sk_idx=(0, 2),
    )
    ref = edge_megakernel_ref(
        vals, ok, scores, thr, 25, sidx=sidx, ext_idx=(1,), sk_idx=(0, 2)
    )
    _assert_matches(got, ref, "segment/sidx")

    lat, lon, codes, s, vals, ok, scores, thr = _latlon_case(800, 2, seed)
    got = _edge_megakernel_segment(
        jnp.asarray(vals), jnp.asarray(ok), jnp.asarray(scores), jnp.asarray(thr),
        s, lat=jnp.asarray(lat), lon=jnp.asarray(lon), codes=jnp.asarray(codes),
        precision=4, ext_idx=(0,), sk_idx=(1,),
    )
    ref = edge_megakernel_ref(
        vals, ok, scores, thr, s, lat=lat, lon=lon, codes=codes,
        precision=4, ext_idx=(0,), sk_idx=(1,),
    )
    _assert_matches(got, ref, "segment/latlon")


def test_megakernel_bf16_staging_parity():
    """bf16-staged values accumulate in f32: the kernel matches the oracle
    fed the *pre-rounded* values exactly (staging only rounds inputs), and
    the sampling lanes (ok/scores/thresholds) are untouched by staging."""
    sidx, vals, ok, scores, thr = _sidx_case(640, 1, 3, 20, 7, "random")
    vals16 = jnp.asarray(vals).astype(jnp.bfloat16)
    got = edge_megakernel_pallas(
        vals16, jnp.asarray(ok), jnp.asarray(scores), jnp.asarray(thr),
        20, sidx=jnp.asarray(sidx), ext_idx=(0,), sk_idx=(1,), interpret=True,
    )
    from repro.kernels.edge_megakernel.ref import edge_megakernel_ref

    ref = edge_megakernel_ref(
        np.asarray(vals16.astype(jnp.float32)), ok, scores, thr, 20,
        sidx=sidx, ext_idx=(0,), sk_idx=(1,),
    )
    _assert_matches(got, ref, "bf16", rtol=1e-6, atol=1e-4)
    # keep decisions identical to the f32-staged run: staging never
    # touches the sampling compare
    got32 = edge_megakernel_pallas(
        jnp.asarray(vals), jnp.asarray(ok), jnp.asarray(scores), jnp.asarray(thr),
        20, sidx=jnp.asarray(sidx), ext_idx=(0,), sk_idx=(1,), interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got.keep), np.asarray(got32.keep))


def test_megakernel_sketch_rows_shape():
    """Sketch rows carry the full (S, NUM_BINS) log-histogram per sketch
    column — the in-kernel binning contract behind
    ``QuantileSketchAccumulator.from_kernel_rows``."""
    sidx, vals, ok, scores, thr = _sidx_case(100, 1, 2, 5, 1, "random")
    res = edge_megakernel(
        jnp.asarray(vals), jnp.asarray(ok), jnp.asarray(scores), jnp.asarray(thr),
        5, sidx=jnp.asarray(sidx), sk_idx=(0, 1), interpret=True,
    )
    assert res.bins.shape == (1, 2, 5, SKETCH_NUM_BINS)
    # every kept tuple lands in exactly one bin
    np.testing.assert_allclose(
        np.asarray(res.bins).sum(axis=(1, 3)) / 2.0, np.asarray(res.keep), atol=1e-5
    )


@pytest.mark.xdist_group("tiling-overrides")
def test_megakernel_block_override_hook():
    """kernels/tiling.py overrides reshape the grid without changing
    results (the TPU block-tuning knob); pinned to one xdist worker — the
    override table is process-global state."""
    from repro.kernels import tiling

    sidx, vals, ok, scores, thr = _sidx_case(700, 1, 2, 30, 3, "random")
    args = (
        jnp.asarray(vals), jnp.asarray(ok), jnp.asarray(scores), jnp.asarray(thr)
    )
    base = edge_megakernel_pallas(
        *args, 30, sidx=jnp.asarray(sidx), ext_idx=(0,), sk_idx=(1,), interpret=True
    )
    try:
        tiling.set_block_override("edge_megakernel", n_block=256, s_block=256)
        small = edge_megakernel_pallas(
            *args, 30, sidx=jnp.asarray(sidx), ext_idx=(0,), sk_idx=(1,),
            n_block=256, s_block=256, interpret=True,
        )
    finally:
        tiling.clear_block_overrides()
    for a, b in zip(tuple(base), tuple(small)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-4)


@pytest.mark.skipif(
    "NIGHTLY_MEGA_N" not in os.environ,
    reason="nightly-only: set NIGHTLY_MEGA_N (e.g. 65536) to run",
)
def test_megakernel_sidx_parity_nightly_large_n():
    """Interpret-mode parity at nightly scale: N from ``NIGHTLY_MEGA_N``
    (far past the PR sweep's 700-point ceiling, many block boundaries),
    wide stratum count, mixed masking.  The nightly workflow runs this at
    N=65536; PR runs skip it."""
    n = int(os.environ["NIGHTLY_MEGA_N"])
    sidx, vals, ok, scores, thr = _sidx_case(n, 2, 3, 96, 7, "random")
    got = edge_megakernel_pallas(
        jnp.asarray(vals), jnp.asarray(ok), jnp.asarray(scores), jnp.asarray(thr),
        96, sidx=jnp.asarray(sidx), ext_idx=(0,), sk_idx=(2,), interpret=True,
    )
    from repro.kernels.edge_megakernel.ref import edge_megakernel_ref

    ref = edge_megakernel_ref(
        vals, ok, scores, thr, 96, sidx=sidx, ext_idx=(0,), sk_idx=(2,)
    )
    _assert_matches(got, ref, f"nightly-sidx[{n}]")
