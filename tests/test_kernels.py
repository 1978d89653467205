"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.kernels.edge_reduce.edge_reduce import edge_reduce_pallas
from repro.kernels.edge_reduce.ops import edge_reduce_percol
from repro.kernels.edge_reduce.ref import edge_reduce_ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.geohash import geohash_encode
from repro.kernels.geohash.ref import encode_ref
from repro.kernels.sample_mask import sample_mask
from repro.kernels.sample_mask.ref import sample_mask_ref
from repro.kernels.stratified_stats import stratified_stats
from repro.kernels.stratified_stats.ref import stratified_stats_ref


@pytest.mark.parametrize("n", [17, 2048, 5000])
@pytest.mark.parametrize("precision", [4, 5, 6])
def test_geohash_kernel(rng, n, precision):
    lat = jnp.asarray(rng.uniform(-89, 89, n), jnp.float32)
    lon = jnp.asarray(rng.uniform(-179, 179, n), jnp.float32)
    got = geohash_encode(lat, lon, precision)
    ref = encode_ref(lat, lon, precision)
    assert got.dtype == jnp.uint32
    assert bool(jnp.all(got == ref))


@pytest.mark.parametrize("n,s", [(100, 7), (4096, 512), (20000, 1300)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stratified_stats_kernel(rng, n, s, dtype):
    sidx = jnp.asarray(rng.integers(0, s, n), jnp.int32)
    vals = jnp.asarray(rng.normal(10, 3, n), dtype)
    mask = jnp.asarray(rng.random(n) < 0.7)
    got = stratified_stats(sidx, vals, mask, s)
    ref = stratified_stats_ref(sidx, vals, mask, s)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-3, atol=0.3)


@pytest.mark.parametrize("n,s", [(100, 9), (10000, 600), (30000, 1024)])
def test_sample_mask_kernel(rng, n, s):
    sidx = jnp.asarray(rng.integers(0, s, n), jnp.int32)
    frac = jnp.asarray(rng.uniform(0.05, 1.0, s), jnp.float32)
    u = jnp.asarray(rng.random(n), jnp.float32)
    gm, gw = sample_mask(sidx, u, frac)
    rm, rw = sample_mask_ref(sidx, u, frac)
    assert bool(jnp.all(gm == rm))
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), rtol=1e-5)


def _edge_reduce_case(n, c, s, seed, mask_mode):
    rng = np.random.default_rng(seed)
    # always hit the overflow stratum s-1 when there is room for it
    sidx = jnp.asarray(rng.integers(0, s, n), jnp.int32)
    if s > 1 and n > 1:
        sidx = sidx.at[0].set(s - 1)
    vals = jnp.asarray(rng.normal(25, 8, (c, n)), jnp.float32)
    if mask_mode == "all":
        mask = jnp.ones(n, bool)
    elif mask_mode == "none":
        mask = jnp.zeros(n, bool)  # all-masked window: every output zero
    else:
        mask = jnp.asarray(rng.random(n) < 0.6)
    return sidx, vals, mask


@given(
    n=st.integers(1, 1300),  # straddles the 512-point block boundary
    c=st.integers(1, 5),
    s=st.integers(1, 40),
    seed=st.integers(0, 2**30),
    mask_mode=st.sampled_from(["random", "all", "none"]),
)
@settings(max_examples=20, deadline=None)
def test_edge_reduce_kernel_parity(n, c, s, seed, mask_mode):
    """Fused multi-column kernel (interpret mode) == the single-pass
    segment oracle, across non-block-multiple N, the overflow stratum, and
    all-masked windows."""
    sidx, vals, mask = _edge_reduce_case(n, c, s, seed, mask_mode)
    got = edge_reduce_pallas(sidx, vals, mask, s, interpret=True)
    ref = edge_reduce_ref(sidx, vals, mask, s)
    for g, r, name in zip(got, ref, ("count", "s1", "s2")):
        assert g.shape == r.shape, name
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=2e-6, atol=1e-3, err_msg=name
        )
    if mask_mode == "none":
        for g in got:
            assert not np.asarray(g).any()


def test_edge_reduce_multi_block_strata(rng):
    """S > S_BLOCK exercises the strata grid dimension of the kernel."""
    n, c, s = 5_000, 3, 1_300
    sidx = jnp.asarray(rng.integers(0, s, n), jnp.int32)
    vals = jnp.asarray(rng.normal(0, 50, (c, n)), jnp.float32)
    mask = jnp.asarray(rng.random(n) < 0.5)
    got = edge_reduce_pallas(sidx, vals, mask, s, interpret=True)
    ref = edge_reduce_ref(sidx, vals, mask, s)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-6, atol=5e-2)


def test_edge_reduce_ref_equals_percol(rng):
    """The stacked single-pass oracle reproduces the per-column segment
    path — the fused backend changes the schedule, not the sums."""
    sidx = jnp.asarray(rng.integers(0, 37, 8_000), jnp.int32)
    vals = jnp.asarray(rng.normal(10, 3, (4, 8_000)), jnp.float32)
    mask = jnp.asarray(rng.random(8_000) < 0.7)
    a = edge_reduce_ref(sidx, vals, mask, 37)
    b = edge_reduce_percol(sidx, vals, mask, 37)
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6, atol=1e-4)


def test_edge_reduce_generalizes_stratified_stats(rng):
    """C=1 edge_reduce == the original single-column stratified_stats."""
    sidx = jnp.asarray(rng.integers(0, 50, 4_096), jnp.int32)
    vals = jnp.asarray(rng.normal(5, 2, 4_096), jnp.float32)
    mask = jnp.asarray(rng.random(4_096) < 0.8)
    cnt, s1, s2 = edge_reduce_pallas(sidx, vals[None, :], mask, 50, interpret=True)
    r_cnt, r_s1, r_s2 = stratified_stats_ref(sidx, vals, mask, 50)
    np.testing.assert_allclose(np.asarray(cnt), np.asarray(r_cnt), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s1[0]), np.asarray(r_s1), rtol=2e-6, atol=1e-3)
    np.testing.assert_allclose(np.asarray(s2[0]), np.asarray(r_s2), rtol=2e-6, atol=1e-2)


@pytest.mark.parametrize(
    "B,S,H,K,dh",
    [
        (1, 256, 4, 4, 64),  # MHA
        (2, 512, 8, 2, 64),  # GQA
        (1, 512, 8, 1, 128),  # MQA
        (1, 256, 4, 4, 112),  # zamba head_dim (padded to 128 internally)
        (1, 300, 4, 2, 64),  # ragged seq (padded internally)
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(rng, B, S, H, K, dh, dtype):
    q = jnp.asarray(rng.normal(0, 1, (B, S, H, dh)), dtype)
    k = jnp.asarray(rng.normal(0, 1, (B, S, K, dh)), dtype)
    v = jnp.asarray(rng.normal(0, 1, (B, S, K, dh)), dtype)
    got = flash_attention(q, k, v).astype(jnp.float32)
    ref = flash_attention_ref(q, k, v).astype(jnp.float32)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=tol, rtol=tol)


def test_flash_attention_matches_model_layer(rng):
    """Kernel agrees with the model's chunked-causal attention path."""
    from repro.models.layers import chunked_causal_attention

    q = jnp.asarray(rng.normal(0, 1, (2, 512, 8, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (2, 512, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (2, 512, 2, 64)), jnp.float32)
    a = flash_attention(q, k, v)
    b = chunked_causal_attention(q, k, v, q_chunk=128)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5)
