"""Stratum lookup: the dense-grid inverted map against the sorted-code search.

Every table whose bounding box fits ``GRID_BUDGET`` looks codes up with one
gather into a grid over the box; a wider table keeps ``searchsorted``.  Both
must give the stratum index a NumPy search of the sorted codes gives, for
every uint32 code.
"""

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import CHICAGO_BBOX, SHENZHEN_BBOX, edge_sample, make_table, make_table_from_codes
from repro.core import geohash as G
from repro.core.stratify import GRID_BUDGET

PANE = 200_000


def _chicago_sparse():
    # a sensor network's occupied cells: 120 of the box's Geohash-6 cells,
    # so the grid has holes between strata
    full = np.asarray(make_table(*CHICAGO_BBOX, precision=6).codes)
    pick = np.random.default_rng(7).choice(len(full), 120, replace=False)
    return make_table_from_codes(full[pick], precision=6)


def _over_budget():
    # two cells at opposite corners of the world: a 2^15 x 2^15 box
    return make_table_from_codes([0, (1 << 30) - 1], precision=6)


TABLES = {
    "shenzhen_gh5": lambda: make_table(*SHENZHEN_BBOX, precision=5),
    "shenzhen_gh6": lambda: make_table(*SHENZHEN_BBOX, precision=6),
    "chicago_sparse_gh6": _chicago_sparse,
    "one_cell": lambda: make_table_from_codes([int(G.encode(22.55, 114.05, 6))], precision=6),
    "over_budget": _over_budget,
}


def _reference(table_codes: np.ndarray, codes: np.ndarray) -> np.ndarray:
    s = len(table_codes)
    pos = np.clip(np.searchsorted(table_codes, codes), 0, s - 1)
    return np.where(table_codes[pos] == codes, pos, s).astype(np.int32)


def _cells(table_codes: np.ndarray, precision: int):
    lon, lat = G.deinterleave(jnp.asarray(table_codes), precision)
    return np.asarray(lon, np.int64), np.asarray(lat, np.int64)


def _encode_cells(lon: np.ndarray, lat: np.ndarray, precision: int) -> np.ndarray:
    lon_bits, lat_bits = G.split_bits(precision)
    keep = (lon >= 0) & (lon < 1 << lon_bits) & (lat >= 0) & (lat < 1 << lat_bits)
    lon, lat = jnp.asarray(lon[keep], jnp.uint32), jnp.asarray(lat[keep], jnp.uint32)
    return np.asarray(G.interleave(lon, lat, precision))


def _probe_codes(table, rng) -> np.ndarray:
    """Every table code and box cell, each side's outer ring, extremes, random."""
    p = table.precision
    table_codes = np.asarray(table.codes)
    lon, lat = _cells(table_codes, p)
    lat0, lat1, lon0, lon1 = lat.min(), lat.max(), lon.min(), lon.max()
    parts = [table_codes]
    if (lat1 - lat0 + 1) * (lon1 - lon0 + 1) <= GRID_BUDGET:
        box_lon, box_lat = np.meshgrid(np.arange(lon0, lon1 + 1), np.arange(lat0, lat1 + 1))
        parts.append(_encode_cells(box_lon.ravel(), box_lat.ravel(), p))
        rows, cols = np.arange(lat0 - 1, lat1 + 2), np.arange(lon0 - 1, lon1 + 2)
        for r in (lat0 - 1, lat1 + 1):  # one cell below and above the box
            parts.append(_encode_cells(cols, np.full_like(cols, r), p))
        for c in (lon0 - 1, lon1 + 1):  # one cell left and right of it
            parts.append(_encode_cells(np.full_like(rows, c), rows, p))
    for dlon, dlat in ((-1, 0), (1, 0), (0, -1), (0, 1)):  # each code's neighbours
        parts.append(_encode_cells(lon + dlon, lat + dlat, p))
    parts.append(np.array([0, (1 << 5 * p) - 1, 1 << 5 * p, 0xFFFFFFFF], np.uint32))
    parts.append(rng.integers(0, 1 << 5 * p, 50_000, dtype=np.uint32))
    parts.append(rng.integers(0, 1 << 32, 10_000, dtype=np.uint64).astype(np.uint32))
    return np.concatenate([np.asarray(a, np.uint32) for a in parts])


@pytest.mark.parametrize("name", list(TABLES))
def test_grid_lookup_matches_sorted_search(name):
    table = TABLES[name]()
    rng = np.random.default_rng(11)
    table_codes = np.asarray(table.codes)
    assert (table.grid is None) == (name == "over_budget")
    search = dataclasses.replace(table, grid=None, grid_box=None)

    codes = _probe_codes(table, rng)
    ref = _reference(table_codes, codes)
    got = np.asarray(jax.jit(table.lookup)(jnp.asarray(codes)))
    np.testing.assert_array_equal(got, ref)
    np.testing.assert_array_equal(np.asarray(jax.jit(search.lookup)(jnp.asarray(codes))), ref)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got[: len(table_codes)], np.arange(len(table_codes)))

    # a padded pane through the edge program: in-table cell centres and
    # points around them, the last quarter padding (-> overflow)
    n = 4096
    centres = table_codes[rng.integers(0, len(table_codes), n)]
    lat_c, lon_c = (np.asarray(a) for a in G.decode(jnp.asarray(centres), table.precision))
    cell_lat, cell_lon = G.cell_size_deg(table.precision)
    lat = (lat_c + rng.uniform(-3, 3, n) * cell_lat).astype(np.float32)
    lon = (lon_c + rng.uniform(-3, 3, n) * cell_lon).astype(np.float32)
    valid = np.arange(n) < 3 * n // 4
    sample = jax.jit(edge_sample, static_argnames=("method",))
    sidx, _ = sample(jax.random.key(0), table, jnp.asarray(lat), jnp.asarray(lon),
                     jnp.asarray(valid), 0.5, method="srs")
    pane_codes = np.asarray(G.encode(jnp.asarray(lat), jnp.asarray(lon), table.precision))
    want = np.where(valid, _reference(table_codes, pane_codes), table.num_strata)
    np.testing.assert_array_equal(np.asarray(sidx), want)
    assert (want[valid] < table.num_strata).any()


def _op_counts(table) -> tuple[int, int]:
    lat = jax.ShapeDtypeStruct((PANE,), jnp.float32)
    text = jax.jit(table.assign).lower(lat, lat).as_text()
    return len(re.findall(r'"stablehlo\.gather"', text)), len(re.findall(r"stablehlo\.while", text))


@pytest.mark.parametrize("name", ["shenzhen_gh5", "shenzhen_gh6", "over_budget"])
def test_assign_lowers_to_one_gather_unless_over_budget(name):
    gathers, whiles = _op_counts(TABLES[name]())
    if name == "over_budget":
        assert whiles >= 1  # the sorted-code search stays reachable
    else:
        assert (gathers, whiles) == (1, 0)
