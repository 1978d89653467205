"""Geohash encode/decode: reference strings, roundtrip, prefix nesting."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import geohash as G

# Known geohash reference values (from public geohash tools)
KNOWN = [
    (42.605, -5.603, 5, "ezs42"),
    (57.64911, 10.40744, 6, "u4pruy"),
    (39.92324, 116.3906, 6, "wx4g0e"),
    (-25.382708, -49.265506, 6, "6gkzwg"),
]


@pytest.mark.parametrize("lat,lon,p,expected", KNOWN)
def test_known_strings(lat, lon, p, expected):
    got = G.to_strings(np.asarray(G.encode(lat, lon, p)).reshape(1), p)[0]
    assert got == expected


@pytest.mark.parametrize("p", [1, 2, 3, 4, 5, 6])
def test_matches_bisection_reference(rng, p):
    lat = rng.uniform(-85, 85, 200)
    lon = rng.uniform(-175, 175, 200)
    got = G.to_strings(np.asarray(G.encode(jnp.asarray(lat, jnp.float32), jnp.asarray(lon, jnp.float32), p)), p)
    # bisection reference on float32-rounded inputs (same quantization grid)
    ref = [G.encode_host(float(np.float32(a)), float(np.float32(o)), p) for a, o in zip(lat, lon)]
    mismatch = sum(g != r for g, r in zip(got, ref))
    # ulp-boundary cells may differ; must be rare and adjacent
    assert mismatch <= 2


@given(
    lat=st.floats(-89.875, 89.875, allow_nan=False, width=32),
    lon=st.floats(-179.875, 179.875, allow_nan=False, width=32),
    p=st.integers(2, 6),
)
@settings(max_examples=200, deadline=None)
def test_decode_roundtrip_within_cell(lat, lon, p):
    code = G.encode(lat, lon, p)
    dlat, dlon = G.decode(code, p)
    cell_lat, cell_lon = G.cell_size_deg(p)
    assert abs(float(dlat) - lat) <= cell_lat * 0.51
    assert abs(float(dlon) - lon) <= cell_lon * 0.51


@given(
    lat=st.floats(-89.875, 89.875, allow_nan=False, width=32),
    lon=st.floats(-179.875, 179.875, allow_nan=False, width=32),
    p=st.integers(2, 6),
    pp=st.integers(1, 6),
)
@settings(max_examples=200, deadline=None)
def test_prefix_nesting(lat, lon, p, pp):
    """parent(code) equals encoding directly at the coarser precision, and
    string prefixes nest (the geohash hierarchy property)."""
    if pp > p:
        pp, p = p, pp
    code_fine = G.encode(lat, lon, p)
    code_coarse = G.encode(lat, lon, pp)
    assert int(G.parent(code_fine, p, pp)) == int(code_coarse)
    s_fine = G.to_strings(np.asarray(code_fine).reshape(1), p)[0]
    s_coarse = G.to_strings(np.asarray(code_coarse).reshape(1), pp)[0]
    assert s_fine.startswith(s_coarse)


def test_string_roundtrip(rng):
    lat = jnp.asarray(rng.uniform(-85, 85, 50), jnp.float32)
    lon = jnp.asarray(rng.uniform(-175, 175, 50), jnp.float32)
    codes = np.asarray(G.encode(lat, lon, 6))
    strings = G.to_strings(codes, 6)
    back = G.from_strings(strings)
    assert (back == codes.astype(np.uint64)).all()
