"""Stratum tables: the spatial model of the paper.

The area of interest is a regular grid of geohash cells ("strata").  The
paper's edge binary maps each tuple's geohash to a stratum and to a coarser
"neighborhood" via a precomputed inverted hashmap (O(1) FxHash lookup).

TPU adaptation: hash maps don't vectorize, so the inverted map is laid out
as a dense grid.  At build time (host) the table's sorted cell codes are
deinterleaved into per-axis cell indices; their bounding box of ``H x W``
cells becomes an int32 array holding each cell's rank in the sorted code
table, or ``S`` for a cell in the box that is not a stratum.  On device a
lookup deinterleaves the code (elementwise bit ops), tests the box with one
unsigned compare per axis and does a single gather.  A code and its cell
indices determine each other at a fixed precision, so the grid returns the
same stratum index as a search of the sorted codes would.

The grid is built only when ``H * W`` fits ``GRID_BUDGET`` entries; a table
whose codes are scattered more widely keeps the ``searchsorted`` lookup over
the sorted codes (a ``while`` loop of ceil(log2(S + 1)) gathers over the
whole batch on TPU).  Both paths give identical indices; which one a table
takes depends only on its box size.  Neighborhood lookup is a dense O(1)
gather from a precomputed ``stratum -> neighborhood`` int array.

Out-of-region tuples map to a dedicated overflow stratum (index ``S``), so
every downstream segment op uses the static size ``S + 1``.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from . import geohash


# Largest grid (in cells of the table's bounding box) that ``lookup`` lays
# out densely: 16 MiB of int32.  Wider tables keep the sorted-code search.
GRID_BUDGET = 1 << 22


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StratumTable:
    """Static table of geohash strata covering a region of interest.

    Attributes:
      codes: (S,) uint32, sorted geohash codes of the in-region cells.
      neighborhood: (S + 1,) int32, neighborhood id per stratum; the final
        entry is the overflow stratum's neighborhood (``num_neighborhoods``,
        i.e. its own catch-all).
      grid: (H * W + 1,) int32 inverted map over the codes' bounding box,
        row-major by (lat, lon) cell: a cell's stratum index, or ``S`` for a
        cell that is not a stratum; the final entry is ``S`` for codes
        outside the box.  ``None`` when ``H * W`` exceeds ``GRID_BUDGET``.
      precision: geohash precision of the strata (static).
      neighborhood_precision: coarser precision defining neighborhoods.
      num_neighborhoods: static count of distinct in-region neighborhoods.
      grid_box: static ``(lat0, lon0, H, W)``, the box's origin cell and
        shape (``None`` with ``grid``).
    """

    codes: jnp.ndarray
    neighborhood: jnp.ndarray
    grid: jnp.ndarray | None
    precision: int = dataclasses.field(metadata=dict(static=True))
    neighborhood_precision: int = dataclasses.field(metadata=dict(static=True))
    num_neighborhoods: int = dataclasses.field(metadata=dict(static=True))
    grid_box: tuple[int, int, int, int] | None = dataclasses.field(metadata=dict(static=True))

    @property
    def num_strata(self) -> int:
        return int(self.codes.shape[0])

    @property
    def num_slots(self) -> int:
        """Strata + 1 overflow slot; the static segment count downstream."""
        return self.num_strata + 1

    def lookup(self, codes: jnp.ndarray) -> jnp.ndarray:
        """Map geohash codes -> stratum index in [0, S]; S = out-of-region."""
        if self.grid is None:
            idx = jnp.searchsorted(self.codes, codes)
            idx = jnp.clip(idx, 0, self.num_strata - 1)
            hit = self.codes[idx] == codes
            return jnp.where(hit, idx, self.num_strata).astype(jnp.int32)
        lat0, lon0, h, w = self.grid_box
        lon_i, lat_i = geohash.deinterleave(codes, self.precision)
        # unsigned: a cell below the origin wraps past the box's far side
        row = lat_i - jnp.uint32(lat0)
        col = lon_i - jnp.uint32(lon0)
        inside = (row < jnp.uint32(h)) & (col < jnp.uint32(w))
        cell = jnp.where(inside, row * jnp.uint32(w) + col, jnp.uint32(h * w))
        return self.grid.at[cell].get(mode="promise_in_bounds")

    def assign(
        self, lat: jnp.ndarray, lon: jnp.ndarray, backend: str = "segment"
    ) -> jnp.ndarray:
        """Coordinates -> stratum index (encode + table lookup).

        ``backend="pallas"`` routes the geohash encode through the fused
        quantize+Morton Pallas kernel on TPU (bit-identical to the jnp
        encoder, which remains the path everywhere else).
        """
        from ..kernels.platform import on_tpu

        with jax.named_scope("stratify"):
            if backend == "pallas" and on_tpu():
                from ..kernels.geohash import geohash_encode

                codes = geohash_encode(lat, lon, self.precision)
            else:
                codes = geohash.encode(lat, lon, self.precision)
            return self.lookup(codes)

    def neighborhood_of(self, stratum_idx: jnp.ndarray) -> jnp.ndarray:
        """O(1) gather: stratum index -> neighborhood id."""
        return self.neighborhood[stratum_idx]


def _build_grid(codes: np.ndarray, precision: int):
    """Inverted map of sorted ``codes`` over their bounding box (host side).

    Returns ``(grid, (lat0, lon0, H, W))``, or ``(None, None)`` when the box
    holds more than ``GRID_BUDGET`` cells.
    """
    s = len(codes)
    lon_i, lat_i = geohash.deinterleave(jnp.asarray(codes), precision)
    lon_i, lat_i = np.asarray(lon_i, np.int64), np.asarray(lat_i, np.int64)
    lat0, lon0 = int(lat_i.min()), int(lon_i.min())
    h, w = int(lat_i.max()) - lat0 + 1, int(lon_i.max()) - lon0 + 1
    if h * w > GRID_BUDGET:
        return None, None
    grid = np.full(h * w + 1, s, np.int32)
    grid[(lat_i - lat0) * w + (lon_i - lon0)] = np.arange(s, dtype=np.int32)
    return grid, (lat0, lon0, h, w)


def _build_table(codes: np.ndarray, precision: int, neighborhood_precision: int) -> StratumTable:
    """Table over sorted unique uint32 ``codes``: neighborhoods and grid."""
    parents = np.asarray(geohash.parent(jnp.asarray(codes), precision, neighborhood_precision))
    uniq, inv = np.unique(parents, return_inverse=True)
    neighborhood = np.concatenate([inv.astype(np.int32), np.array([len(uniq)], dtype=np.int32)])
    grid, box = _build_grid(codes, precision)
    return StratumTable(
        codes=jnp.asarray(codes),
        neighborhood=jnp.asarray(neighborhood),
        grid=None if grid is None else jnp.asarray(grid),
        precision=precision,
        neighborhood_precision=neighborhood_precision,
        num_neighborhoods=int(len(uniq)),
        grid_box=box,
    )


def make_table(
    lat_range: tuple[float, float],
    lon_range: tuple[float, float],
    precision: int,
    neighborhood_precision: int | None = None,
) -> StratumTable:
    """Enumerate the geohash cells covering a bounding box (host side).

    This is the paper's "area of interest divided into a regular grid of
    fixed-sized adjacent non-overlapping cells".  Built once at launch, then
    used read-only on device.
    """
    if neighborhood_precision is None:
        neighborhood_precision = max(1, precision - 2)
    if neighborhood_precision > precision:
        raise ValueError("neighborhood_precision must be <= precision")
    lat_lo, lat_hi = lat_range
    lon_lo, lon_hi = lon_range
    lon_bits, lat_bits = geohash.split_bits(precision)
    lat_cell = (geohash.LAT_MAX - geohash.LAT_MIN) / (1 << lat_bits)
    lon_cell = (geohash.LON_MAX - geohash.LON_MIN) / (1 << lon_bits)
    lat_i0 = int(np.floor((lat_lo - geohash.LAT_MIN) / lat_cell))
    lat_i1 = int(np.floor((lat_hi - geohash.LAT_MIN) / lat_cell - 1e-12))
    lon_i0 = int(np.floor((lon_lo - geohash.LON_MIN) / lon_cell))
    lon_i1 = int(np.floor((lon_hi - geohash.LON_MIN) / lon_cell - 1e-12))
    lat_idx = np.arange(lat_i0, lat_i1 + 1, dtype=np.uint32)
    lon_idx = np.arange(lon_i0, lon_i1 + 1, dtype=np.uint32)
    lon_grid, lat_grid = np.meshgrid(lon_idx, lat_idx)
    codes = np.asarray(
        geohash.interleave(jnp.asarray(lon_grid.reshape(-1)), jnp.asarray(lat_grid.reshape(-1)), precision)
    )
    codes = np.sort(codes.astype(np.uint32))
    return _build_table(codes, precision, neighborhood_precision)


def make_table_from_codes(
    codes: Sequence[int] | np.ndarray,
    precision: int,
    neighborhood_precision: int | None = None,
) -> StratumTable:
    """Build a table from an explicit set of geohash codes (e.g. observed)."""
    if neighborhood_precision is None:
        neighborhood_precision = max(1, precision - 2)
    codes = np.unique(np.asarray(codes, dtype=np.uint32))
    return _build_table(codes, precision, neighborhood_precision)


# Bounding boxes used across examples/benchmarks (approximate city extents).
SHENZHEN_BBOX = ((22.44, 22.87), (113.75, 114.65))
CHICAGO_BBOX = ((41.62, 42.05), (-87.95, -87.50))
