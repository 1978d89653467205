"""The plain reference: the same stratification, allocation and reductions
in NumPy and float64, over the same tuples, with nothing taken from the
program (its own geohash encoder and its own table of cells).

What is compared, per emitted query result (see :func:`check_result`):

* ``count_mismatches``: per-slot population ``total`` (and, at fraction
  1.0, the sample size ``n``) against the reference's tuple counts; exact.
* ``nk_mismatches``: per-stratum SRS sample sizes against the paper's
  allocation ``n_k = round(f * N_k)`` in float32, summed over edge nodes
  (each node allocates over its own share of the pane); Bernoulli sizes
  may not exceed ``N_k``.  Exact.
* ``extrema_mismatches``: per-slot min/max at fraction 1.0, and the
  grouped min/max estimates; exact.
* ``sum_err_x_f32_bound``: sums and means (per slot and per group) at
  fraction 1.0, as a share of the float32 recursive-summation bound
  ``(n + 2) * eps32 * sum|y|``; float32 accumulation stays within 1, a
  bfloat16 input does not.
* ``sample_sum_x_f32_bound``: at a fraction below 1, how far a stratum's
  sampled sum lies outside the range that any sample of its size ``n_k``
  can give (the sum of its ``n_k`` smallest to its ``n_k`` largest
  values), as a share of the same bound.
* ``truncated``: tuples a raw-mode buffer dropped; exact (0).
"""

from __future__ import annotations

import numpy as np

EPS32 = float(np.finfo(np.float32).eps)
LAT_MIN, LAT_MAX = -90.0, 90.0
LON_MIN, LON_MAX = -180.0, 180.0


# -- geohash and the table of strata ------------------------------------------


def _split_bits(precision: int) -> tuple[int, int]:
    """(lon_bits, lat_bits): longitude takes the extra bit at odd width."""
    total = 5 * precision
    return (total + 1) // 2, total // 2


def _part1by1(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32) & np.uint32(0x0000FFFF)
    x = (x | (x << np.uint32(8))) & np.uint32(0x00FF00FF)
    x = (x | (x << np.uint32(4))) & np.uint32(0x0F0F0F0F)
    x = (x | (x << np.uint32(2))) & np.uint32(0x33333333)
    x = (x | (x << np.uint32(1))) & np.uint32(0x55555555)
    return x


def _interleave(lon_i: np.ndarray, lat_i: np.ndarray, precision: int) -> np.ndarray:
    if (5 * precision) % 2 == 0:
        return (_part1by1(lon_i) << np.uint32(1)) | _part1by1(lat_i)
    return _part1by1(lon_i) | (_part1by1(lat_i) << np.uint32(1))


def encode(lat, lon, precision: int) -> np.ndarray:
    """Morton geohash codes (uint32), quantized in float32 by one multiply."""
    lat = np.asarray(lat, np.float32)
    lon = np.asarray(lon, np.float32)
    lon_bits, lat_bits = _split_bits(precision)
    lat_scale = np.float32((1 << lat_bits) / (LAT_MAX - LAT_MIN))
    lon_scale = np.float32((1 << lon_bits) / (LON_MAX - LON_MIN))
    lat_i = np.clip(((lat - np.float32(LAT_MIN)) * lat_scale).astype(np.int32), 0, (1 << lat_bits) - 1)
    lon_i = np.clip(((lon - np.float32(LON_MIN)) * lon_scale).astype(np.int32), 0, (1 << lon_bits) - 1)
    return _interleave(lon_i.astype(np.uint32), lat_i.astype(np.uint32), precision)


class Table:
    """Sorted codes of the cells covering a bounding box, and the coarser
    neighborhood of each; slot ``S`` holds every tuple outside them."""

    def __init__(self, bbox, precision: int, neighborhood_precision: int):
        (lat_lo, lat_hi), (lon_lo, lon_hi) = bbox
        lon_bits, lat_bits = _split_bits(precision)
        lat_cell = (LAT_MAX - LAT_MIN) / (1 << lat_bits)
        lon_cell = (LON_MAX - LON_MIN) / (1 << lon_bits)
        lat_idx = np.arange(
            int(np.floor((lat_lo - LAT_MIN) / lat_cell)),
            int(np.floor((lat_hi - LAT_MIN) / lat_cell - 1e-12)) + 1,
        )
        lon_idx = np.arange(
            int(np.floor((lon_lo - LON_MIN) / lon_cell)),
            int(np.floor((lon_hi - LON_MIN) / lon_cell - 1e-12)) + 1,
        )
        lon_grid, lat_grid = np.meshgrid(lon_idx, lat_idx)
        self.codes = np.sort(_interleave(lon_grid.ravel(), lat_grid.ravel(), precision))
        parents = self.codes >> np.uint32(5 * (precision - neighborhood_precision))
        uniq, self.group = np.unique(parents, return_inverse=True)
        self.num_groups = len(uniq)
        self.precision = precision

    @property
    def num_strata(self) -> int:
        return len(self.codes)

    def strata(self, lat, lon) -> np.ndarray:
        """Slot of each tuple: its cell's index, or ``S`` outside the table."""
        code = encode(lat, lon, self.precision)
        pos = np.minimum(np.searchsorted(self.codes, code), self.num_strata - 1)
        return np.where(self.codes[pos] == code, pos, self.num_strata)


def in_roi(roi, lat, lon) -> np.ndarray:
    if roi is None:
        return np.ones(len(lat), bool)
    (lat_lo, lat_hi), (lon_lo, lon_hi) = roi
    return (lat >= lat_lo) & (lat <= lat_hi) & (lon >= lon_lo) & (lon <= lon_hi)


def allocate(counts: np.ndarray, fraction: float) -> np.ndarray:
    """The paper's SRS allocation ``round(f * N_k)`` in float32 arithmetic."""
    target = np.round(counts.astype(np.float32) * np.float32(fraction))
    return np.clip(target.astype(np.int64), 0, counts)


# -- one query's window ----------------------------------------------------------


class WindowRef:
    """What a query's window holds, slot by slot, over the panes it covers.

    ``panes`` are ``(lat, lon, columns)`` tuples; ``edge_nodes`` cuts each
    pane into that many contiguous shares, each allocating on its own."""

    def __init__(self, table: Table, query: dict, panes: list, edge_nodes: int):
        slots = table.num_strata + 1
        self.table, self.query, self.slots = table, query, slots
        self.count = np.zeros(slots)
        self.n_alloc = np.zeros(slots, np.int64)
        sidx_all, cols_all = [], {c: [] for c in query_columns(query)}
        for lat, lon, cols in panes:
            # tuples outside the query's region count nowhere (index ``slots``)
            sidx = np.where(in_roi(query.get("roi"), lat, lon), table.strata(lat, lon), slots)
            sidx_all.append(sidx)
            for c in cols_all:
                cols_all[c].append(np.asarray(cols[c], np.float64))
            self.count += np.bincount(sidx, minlength=slots + 1)[:slots]
            for share in np.array_split(sidx, edge_nodes):
                counts = np.bincount(share, minlength=slots + 1)[:slots]
                self.n_alloc += allocate(counts, query["fraction"])
        self.sidx = np.concatenate(sidx_all)
        self.cols = {c: np.concatenate(v) for c, v in cols_all.items()}
        self._columns: dict = {}
        self._sorted: dict = {}

    def column(self, c: str) -> dict:
        if c not in self._columns:
            self._columns[c] = self._column(c)
        return self._columns[c]

    def _column(self, c: str) -> dict:
        y, sidx, slots = self.cols[c], self.sidx, self.slots
        lo = np.full(slots + 1, np.inf)
        hi = np.full(slots + 1, -np.inf)
        np.minimum.at(lo, sidx, y)
        np.maximum.at(hi, sidx, y)
        return {
            "sum": np.bincount(sidx, weights=y, minlength=slots + 1)[:slots],
            "abssum": np.bincount(sidx, weights=np.abs(y), minlength=slots + 1)[:slots],
            "min": lo[:slots],
            "max": hi[:slots],
        }

    def sample_range(self, c: str, n: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Per stratum, the least and the greatest sum a sample of ``n[k]``
        of its tuples can have."""
        if c not in self._sorted:
            y = self.cols[c]
            self._sorted[c] = np.concatenate([[0.0], np.cumsum(y[np.lexsort((y, self.sidx))])])
        cum = self._sorted[c]
        count = self.count.astype(np.int64)
        start = np.cumsum(count) - count
        n = np.clip(n.astype(np.int64), 0, count)
        lo = cum[start + n] - cum[start]
        hi = cum[start + count] - cum[start + count - n]
        return lo, hi


def query_columns(query: dict) -> list:
    return sorted({c for _, c in query["aggs"]})


# -- the comparison ---------------------------------------------------------------

CHECKS = (
    "count_mismatches",
    "nk_mismatches",
    "extrema_mismatches",
    "sum_err_x_f32_bound",
    "sample_sum_x_f32_bound",
    "truncated",
)


def empty_checks() -> dict:
    return {name: 0.0 for name in CHECKS}


def merge_checks(a: dict, b: dict) -> dict:
    """Counts add; shares of a bound take the worst."""
    out = dict(a)
    for k, v in b.items():
        out[k] = max(out[k], v) if k.endswith("_bound") else out[k] + v
    return out


def check_result(res, ref: WindowRef) -> dict:
    """Compare one emitted result (its arrays already on the host) with the
    reference of its window.  ``res`` has ``stats[col]['moments'|'extrema']``,
    ``estimates[key].value`` and ``n_truncated``, as ``QueryResult`` does."""
    q, table = ref.query, ref.table
    out = empty_checks()
    s = table.num_strata
    count = ref.count
    exact = q["fraction"] >= 1.0

    def share(got, want, tol):
        err = np.abs(np.asarray(got, np.float64) - want)
        return float(np.max(err / tol)) if err.size else 0.0

    def differ(got, want) -> int:
        return int(np.sum(np.asarray(got, np.float64) != want))

    out["truncated"] = float(np.asarray(res.n_truncated))
    for c in query_columns(q):
        r = ref.column(c)
        m = res.stats[c]["moments"]
        n = np.asarray(m.n, np.float64)
        out["count_mismatches"] += differ(m.total, count)
        tol = (np.maximum(n, count) + 2.0) * EPS32 * r["abssum"] + 1e-30
        if exact:
            out["count_mismatches"] += differ(n, count)
            out["sum_err_x_f32_bound"] = max(
                out["sum_err_x_f32_bound"],
                share(m.wsum, r["sum"], tol),
                share(np.asarray(m.mean)[count > 0], (r["sum"] / np.maximum(count, 1))[count > 0],
                      (tol / np.maximum(count, 1))[count > 0]),
            )
            if "extrema" in res.stats[c]:
                e = res.stats[c]["extrema"]
                out["extrema_mismatches"] += differ(e.min, r["min"]) + differ(e.max, r["max"])
        else:
            if q.get("method", "srs") == "srs":
                out["nk_mismatches"] += differ(n[:s], ref.n_alloc[:s])
            else:
                out["nk_mismatches"] += int(np.sum(n[:s] > count[:s]))
            lo, hi = ref.sample_range(c, n)
            over = np.maximum(lo - np.asarray(m.wsum, np.float64), 0.0)
            over = np.maximum(over, np.asarray(m.wsum, np.float64) - hi)
            out["sample_sum_x_f32_bound"] = max(out["sample_sum_x_f32_bound"], float(np.max(over[:s] / tol[:s])))
    if exact and q.get("group_by") == "neighborhood":
        out = merge_checks(out, _check_groups(res, ref))
    return out


def _check_groups(res, ref: WindowRef) -> dict:
    """Per-neighborhood count/sum/mean/min/max estimates at fraction 1.0
    (the overflow slot excluded)."""
    table, out = ref.table, empty_checks()
    s, groups = table.num_strata, table.num_groups

    def by_group(x):
        return np.bincount(table.group, weights=x[:s], minlength=groups)

    n_g = by_group(ref.count)
    on = n_g > 0
    for kind, c in ref.query["aggs"]:
        est = res.estimates.get(f"{kind}_{c}")
        if est is None or kind not in ("count", "sum", "mean", "min", "max"):
            continue
        got = np.asarray(est.value, np.float64)
        r = ref.column(c)
        tol_g = 2.0 * by_group((ref.count + 4.0) * EPS32 * r["abssum"]) + 1e-30
        if kind == "count":
            out["count_mismatches"] += int(np.sum(got != n_g))
        elif kind == "sum":
            err = float(np.max(np.abs(got - by_group(r["sum"])) / tol_g))
            out["sum_err_x_f32_bound"] = max(out["sum_err_x_f32_bound"], err)
        elif kind == "mean":
            want = by_group(r["sum"]) / np.maximum(n_g, 1)
            err = float(np.max((np.abs(got - want) / (tol_g / np.maximum(n_g, 1)))[on]))
            out["sum_err_x_f32_bound"] = max(out["sum_err_x_f32_bound"], err)
        else:
            want = np.full(groups, np.inf if kind == "min" else -np.inf)
            (np.minimum if kind == "min" else np.maximum).at(want, table.group, r[kind][:s])
            out["extrema_mismatches"] += int(np.sum(got[on] != want[on]))
    return out


# each compared number's limit: exact counts allow nothing; a sum may stray
# as far as float32 accumulation can (the configuration's stated precision)
LIMITS = {
    "count_mismatches": 0.0,
    "nk_mismatches": 0.0,
    "extrema_mismatches": 0.0,
    "sum_err_x_f32_bound": 1.0,
    "sample_sum_x_f32_bound": 1.0,
    "truncated": 0.0,
    "results_missing": 0.0,
}
AT_LEAST = {"steps_checked": 1.0}


def verdicts(checks: dict) -> dict:
    """``name -> (value, (kind of limit, limit), within it)``."""
    out = {}
    for name, value in checks.items():
        if name in AT_LEAST:
            out[name] = (value, ("at_least", AT_LEAST[name]), value >= AT_LEAST[name])
        else:
            out[name] = (value, ("at_most", LIMITS[name]), value <= LIMITS[name])
    return out
