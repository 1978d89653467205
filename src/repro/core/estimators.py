"""Stratified estimators with rigorous error bounds (paper §3.5–3.6).

Implements equations (1)–(10): per-stratum sample statistics, the
stratified SUM/MEAN estimators, the variance of those estimators with
finite-population correction, and normal-approximation confidence
intervals / margin of error / relative error.

This module also hosts the **accumulator registry** — the pluggable layer
the query engine reduces windows into.  An :class:`Accumulator` is a named
kind of mergeable per-stratum summary (``accumulate / merge / merge_panes /
psum / zero_overflow / interval`` — the last derives sampling-error CIs
from the merged state, see :mod:`.bounds`); the built-in citizens are

  * ``moments``  — the eq 4 sample moments (:class:`StratumStats`), exact
    Chan-et-al. merges; backs sum/mean/count/var,
  * ``extrema``  — per-stratum min/max lattices; backs min/max,
  * ``sketch``   — a mergeable fixed-size log-domain quantile histogram
    (DDSketch-style); backs the ``p50``/``p99`` quantile aggregates.

Each column a query references carries a *dict of accumulator states*
(``{"moments": ..., "extrema": ...}``) chosen by plan lowering; the dict is
a plain pytree, so it jits, shard_maps, stacks into pane rings, and crosses
collectives untouched.  New aggregate families plug in by registering an
accumulator kind — no pipeline/session/collective code changes.

Two aggregation modes mirror the paper's two edge->cloud transmission modes:

  * raw mode — the "cloud" groups raw sampled tuples by stratum and applies
    the formulas on the full (masked) arrays;
  * pre-aggregated mode — each edge shard reduces its window to per-stratum
    moments ``(n_k, sum_k, M2_k)`` and only those are combined across shards
    (``psum`` over the data axes).  This is the bandwidth-saving mode; the
    combination rule is exact (parallel-variance / Chan et al. decomposition),
    so both modes return identical estimates — a property we test.

Numerics: per-stratum second moments are computed *centered* inside a
shard (Chan-et-al. merges along each stratum's run of a stratum-sorted
pass, see :func:`accumulate_sorted`), and the cross-shard merge uses the
mean-shift decomposition, avoiding the catastrophic cancellation of naive
sum-of-squares in f32.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.scipy.special import ndtri


class StratumStats(NamedTuple):
    """Mergeable per-stratum sample moments; shapes all (S+1,).

    n: realized sample size n_k (float for psum-friendliness)
    total: population size N_k of the window(s)
    wsum:  Σ y over sampled tuples of stratum k
    m2:    Σ (y - ȳ_k)^2 over sampled tuples (centered second moment)
    mean:  ȳ_k (carried so merges can re-center without re-reading data)
    """

    n: jnp.ndarray
    total: jnp.ndarray
    wsum: jnp.ndarray
    m2: jnp.ndarray
    mean: jnp.ndarray


class ColumnStats(NamedTuple):
    """Generalized mergeable per-stratum accumulator for one value column.

    Extends :class:`StratumStats` (whose five moments cover sum/mean/count/var
    via the Chan-et-al. parallel merge) with per-stratum sample extrema so
    ``min``/``max`` aggregates also merge *exactly* across shards.  Empty
    strata carry ``+inf``/``-inf`` sentinels, the identities of min/max, so
    every field is a segment-reduction with an exact associative combine:
    additive (n/total/wsum), mean-shift (m2), or lattice (min/max).

    This is the edge-side payload of the query layer's pre-aggregated
    transmission mode: one ColumnStats per referenced column per shard.
    """

    n: jnp.ndarray
    total: jnp.ndarray
    wsum: jnp.ndarray
    m2: jnp.ndarray
    mean: jnp.ndarray
    min: jnp.ndarray
    max: jnp.ndarray

    @property
    def base(self) -> "StratumStats":
        """The moment-only view (drop extrema) for the eq 5-10 estimators."""
        return StratumStats(n=self.n, total=self.total, wsum=self.wsum, m2=self.m2, mean=self.mean)


class Estimate(NamedTuple):
    """Global stratified estimate with uncertainty (eqs 5–10)."""

    sum: jnp.ndarray
    mean: jnp.ndarray
    var_sum: jnp.ndarray
    var_mean: jnp.ndarray
    moe: jnp.ndarray
    relative_error: jnp.ndarray
    ci_low: jnp.ndarray
    ci_high: jnp.ndarray
    n_total: jnp.ndarray
    population: jnp.ndarray


def sample_stats(
    values: jnp.ndarray,
    stratum_idx: jnp.ndarray,
    mask: jnp.ndarray,
    num_slots: int,
    counts: jnp.ndarray | None = None,
) -> StratumStats:
    """Per-stratum moments of the *sampled* tuples (eq 4), centered.

    One stratum-sorted pass (:func:`accumulate_sorted`).  ``counts`` are the
    population sizes N_k; when None they are the tuples of each stratum in
    ``stratum_idx`` (all tuples of the window, sampled or not).
    """
    return MOMENTS.accumulate(values, stratum_idx, mask, num_slots, counts=counts)


def merge_stats(a: StratumStats, b: StratumStats) -> StratumStats:
    """Exact pairwise merge (Chan et al. parallel-variance update)."""
    n = a.n + b.n
    total = a.total + b.total
    wsum = a.wsum + b.wsum
    mean = jnp.where(n > 0, wsum / jnp.maximum(n, 1.0), 0.0)
    delta = b.mean - a.mean
    m2 = a.m2 + b.m2 + delta * delta * jnp.where(n > 0, a.n * b.n / jnp.maximum(n, 1.0), 0.0)
    return StratumStats(n=n, total=total, wsum=wsum, m2=m2, mean=mean)


def merge_all(stats: Sequence[StratumStats]) -> StratumStats:
    out = stats[0]
    for s in stats[1:]:
        out = merge_stats(out, s)
    return out


def psum_stats(stats: StratumStats, axis_names) -> StratumStats:
    """Cross-shard combine with a single additive collective.

    Uses the mean-shift decomposition
        M2_g = Σ_s M2_s + Σ_s n_s ȳ_s² − n_g ȳ_g²
    so one ``psum`` of 4 (S+1)-vectors suffices — this is the paper's
    "pre-aggregated statistics transmission" mapped onto the interconnect:
    collective bytes are O(S), independent of window size.
    """
    n = jax.lax.psum(stats.n, axis_names)
    total = jax.lax.psum(stats.total, axis_names)
    wsum = jax.lax.psum(stats.wsum, axis_names)
    raw2 = jax.lax.psum(stats.m2 + stats.n * stats.mean * stats.mean, axis_names)
    mean = jnp.where(n > 0, wsum / jnp.maximum(n, 1.0), 0.0)
    m2 = jnp.maximum(raw2 - n * mean * mean, 0.0)
    return StratumStats(n=n, total=total, wsum=wsum, m2=m2, mean=mean)


def merge_stats_panes(stacked: StratumStats) -> StratumStats:
    """Vectorized multi-way moment merge over a leading pane axis.

    Input fields are (P, S+1): P pane accumulators of the same stratum
    table.  One mean-shift pass merges all panes at once —
        M2 = Σ_p (M2_p + n_p ȳ_p²) − n ȳ²
    (the :func:`psum_stats` decomposition applied on a local axis) — instead
    of P−1 sequential :func:`merge_stats` folds.
    """
    n = jnp.sum(stacked.n, axis=0)
    total = jnp.sum(stacked.total, axis=0)
    wsum = jnp.sum(stacked.wsum, axis=0)
    raw2 = jnp.sum(stacked.m2 + stacked.n * stacked.mean * stacked.mean, axis=0)
    mean = jnp.where(n > 0, wsum / jnp.maximum(n, 1.0), 0.0)
    m2 = jnp.maximum(raw2 - n * mean * mean, 0.0)
    return StratumStats(n=n, total=total, wsum=wsum, m2=m2, mean=mean)


def stats_from_raw_moments(
    count: jnp.ndarray, s1: jnp.ndarray, s2: jnp.ndarray, counts: jnp.ndarray
) -> StratumStats:
    """Raw per-stratum sums {n, Σy, Σy²} -> the centered StratumStats form.

    This is the adapter between the fused edge-reduce kernel (which emits
    raw power sums — the matmul-friendly form) and the mean-shift moment
    representation the estimators consume.  The centering ``m2 = Σy² − nȳ²``
    is the one fp32-cancellation step of the kernel path; the segment-ops
    backend centers two-pass and is the parity oracle (documented tolerance
    in the backend parity suite).
    """
    n = count.astype(jnp.float32)
    mean = jnp.where(n > 0, s1 / jnp.maximum(n, 1.0), 0.0)
    m2 = jnp.maximum(s2 - n * mean * mean, 0.0)
    return StratumStats(n=n, total=counts.astype(jnp.float32), wsum=s1, m2=m2, mean=mean)


def zero_overflow_stats(stats: StratumStats) -> StratumStats:
    """Neutralize the overflow slot (additive fields -> 0) so it drops out
    of estimation; the canonical implementation shared by pipeline shims
    and the query layer."""
    keep = jnp.arange(stats.n.shape[0]) < (stats.n.shape[0] - 1)

    def z(x):
        return jnp.where(keep, x, 0.0)

    return StratumStats(n=z(stats.n), total=z(stats.total), wsum=z(stats.wsum), m2=z(stats.m2), mean=z(stats.mean))


def column_stats(
    values: jnp.ndarray,
    stratum_idx: jnp.ndarray,
    mask: jnp.ndarray,
    num_slots: int,
    counts: jnp.ndarray | None = None,
    extrema: bool = True,
) -> ColumnStats:
    """Per-stratum generalized accumulator of the sampled tuples of one column.

    Moments are :func:`sample_stats`'; extrema are the masked per-stratum
    min/max with ``±inf`` identities on empty strata, from the same sorted
    pass.  Pass ``extrema=False`` when no aggregate reads min/max — the
    fields are then filled with their identities without reducing them.
    """
    if extrema:
        base, ext = accumulate_sorted(
            ((MOMENTS, values, mask), (EXTREMA, values, mask)),
            stratum_idx, num_slots, counts,
        )
    else:
        base = sample_stats(values, stratum_idx, mask, num_slots, counts=counts)
        ext = EXTREMA.identity(num_slots)
    return ColumnStats(
        n=base.n, total=base.total, wsum=base.wsum, m2=base.m2, mean=base.mean,
        min=ext.min, max=ext.max,
    )


def merge_column_stats(a: ColumnStats, b: ColumnStats) -> ColumnStats:
    """Exact pairwise merge: Chan et al. for moments, lattice for extrema."""
    base = merge_stats(a.base, b.base)
    return ColumnStats(
        n=base.n, total=base.total, wsum=base.wsum, m2=base.m2, mean=base.mean,
        min=jnp.minimum(a.min, b.min), max=jnp.maximum(a.max, b.max),
    )


def merge_all_columns(stats: Sequence[ColumnStats]) -> ColumnStats:
    out = stats[0]
    for s in stats[1:]:
        out = merge_column_stats(out, s)
    return out


def stack_column_stats(stats: Sequence[ColumnStats]) -> ColumnStats:
    """Stack accumulators along a new leading pane axis: (P, S+1) fields."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *stats)


def merge_column_stats_panes(stacked: ColumnStats) -> ColumnStats:
    """Vectorized multi-way merge over a leading pane axis.

    Input fields are (P, S+1): P pane accumulators of the same stratum
    table, merged in one mean-shift pass (:func:`merge_stats_panes`) instead
    of P−1 sequential :func:`merge_column_stats` folds.  This is the
    cloud-side pane merge of sliding/hopping windows: a window's answer is
    assembled from its panes' accumulators without re-touching raw tuples.
    """
    base = merge_stats_panes(stacked.base)
    return ColumnStats(
        n=base.n, total=base.total, wsum=base.wsum, m2=base.m2, mean=base.mean,
        min=jnp.min(stacked.min, axis=0), max=jnp.max(stacked.max, axis=0),
    )


def psum_column_stats(
    stats: ColumnStats, axis_names, shared: ColumnStats | None = None,
    extrema: bool = True,
) -> ColumnStats:
    """Cross-shard combine: psum of the moment vectors (mean-shift
    decomposition, see :func:`psum_stats`) plus a pmin/pmax pair for the
    extrema — O(S) collective bytes per column.

    Columns accumulated from the same sample share identical ``n``/``total``
    vectors; pass an already-combined column as ``shared`` to reuse them and
    skip their redundant psums (2 fewer collective vectors per extra column).
    ``extrema=False`` skips the pmin/pmax collectives for columns no min/max
    aggregate reads (the identity-filled fields pass through unchanged).
    """
    base = MOMENTS.psum(stats.base, axis_names, shared=shared.base if shared is not None else None)
    return ColumnStats(
        n=base.n, total=base.total, wsum=base.wsum, m2=base.m2, mean=base.mean,
        min=jax.lax.pmin(stats.min, axis_names) if extrema else stats.min,
        max=jax.lax.pmax(stats.max, axis_names) if extrema else stats.max,
    )


def z_value(confidence: float) -> jnp.ndarray:
    """Upper alpha/2 normal quantile, e.g. 1.96 for 95%."""
    alpha = 1.0 - confidence
    return ndtri(1.0 - alpha / 2.0).astype(jnp.float32)


def guarded_s2(
    n: jnp.ndarray,
    total: jnp.ndarray,
    m2: jnp.ndarray,
    grp: jnp.ndarray | None = None,
    num_groups: int = 1,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-stratum sample variance with the lonely-singleton guard.

    A stratum sampled at ``n_k == 1`` while under-sampled (``n_k < N_k``)
    has an *unidentified* variance; plugging in its recorded ``m2 == 0``
    silently reports zero sampling error (false certainty that collapses
    the SLO feedback loop).  Following the survey-statistics lonely-PSU
    "average" adjustment, such strata borrow the mean ``s²`` of the
    identified (``n_k >= 2``) strata of their group.  Returns
    ``(s2_eff, unidentified)`` where ``unidentified`` flags groups whose
    variance *no* stratum identifies — their half-width must be reported
    as infinite, which the feedback controller treats as "hold the
    fraction" (graceful degradation instead of a poisoned update).
    """
    s2 = jnp.where(n > 1, m2 / jnp.maximum(n - 1.0, 1.0), 0.0)
    active = (n > 0) & (total > 0)
    known = active & (n > 1)
    lonely = active & (n < 2) & (n < total)

    def reduce(x):
        if grp is None:
            return jnp.sum(x)
        return jax.ops.segment_sum(x, grp, num_segments=num_groups + 1)[:num_groups]

    cnt = reduce(known.astype(jnp.float32))
    s2_bar = reduce(jnp.where(known, s2, 0.0)) / jnp.maximum(cnt, 1.0)
    s2_bar_k = s2_bar if grp is None else s2_bar_at(s2_bar, grp)
    s2_eff = jnp.where(lonely, s2_bar_k, s2)
    unidentified = (reduce(lonely.astype(jnp.float32)) > 0) & (cnt == 0)
    return s2_eff, unidentified


def s2_bar_at(s2_bar_g: jnp.ndarray, grp: jnp.ndarray) -> jnp.ndarray:
    """Gather per-group imputed s² back to strata (overflow slot -> 0)."""
    padded = jnp.concatenate([s2_bar_g, jnp.zeros((1,), s2_bar_g.dtype)])
    return padded[jnp.clip(grp, 0, s2_bar_g.shape[0])]


def estimate(stats: StratumStats, confidence: float = 0.95) -> Estimate:
    """Equations (5)–(10) from merged per-stratum statistics.

    The MEAN is normalized by the *covered* population Σ_{k: n_k>0} N_k
    (a ratio estimator): strata whose allocation rounded to zero samples
    (tiny N_k at low fractions — the paper's "neighborhoods with too few
    data points" caveat) would otherwise bias the mean toward zero.  Under
    full coverage this equals the textbook eq 5 exactly.

    Under-sampled singleton strata (``n_k == 1 < N_k``) carry the
    :func:`guarded_s2` lonely-PSU adjustment: they borrow the average s²
    of identified strata instead of contributing false-zero variance; if
    *no* stratum identifies a variance the half-width is infinite.
    """
    n = stats.n
    N = stats.total
    active = (n > 0) & (N > 0)
    mean_k = stats.mean
    # s_k^2 (eq 4) with the singleton guard; full-population strata stay
    # exact via the fpc term regardless.
    s2_k, unidentified = guarded_s2(n, N, stats.m2)
    sum_hat = jnp.sum(jnp.where(active, N * mean_k, 0.0))  # eq 5
    population = jnp.sum(N)
    covered = jnp.sum(jnp.where(active, N, 0.0))
    mean_hat = sum_hat / jnp.maximum(covered, 1.0)  # eq 5 (ratio form)
    fpc = jnp.where(N > 0, 1.0 - n / jnp.maximum(N, 1.0), 0.0)
    var_sum = jnp.sum(jnp.where(active, N * N * fpc * s2_k / jnp.maximum(n, 1.0), 0.0))  # eq 6
    var_sum = jnp.where(unidentified, jnp.inf, var_sum)
    var_mean = var_sum / jnp.maximum(covered, 1.0) ** 2  # eq 7
    z = z_value(confidence)
    moe = z * jnp.sqrt(jnp.maximum(var_mean, 0.0))  # eq 9
    rel = jnp.where(jnp.abs(mean_hat) > 0, moe / jnp.maximum(jnp.abs(mean_hat), 1e-30), jnp.inf)  # eq 10
    return Estimate(
        sum=sum_hat,
        mean=mean_hat,
        var_sum=var_sum,
        var_mean=var_mean,
        moe=moe,
        relative_error=rel,
        ci_low=mean_hat - moe,
        ci_high=mean_hat + moe,
        n_total=jnp.sum(n),
        population=population,
    )


def substream_sums(stats_per_substream: Sequence[StratumStats]) -> jnp.ndarray:
    """Equations (1)–(2): per-substream estimated sums t̂_s and their total.

    Each element is one edge node's local stats; t̂_s = Σ_k N_{s,k} ȳ_{s,k}.
    Returns the vector of t̂_s (the global SUM is their sum, eq 2 — equal to
    ``estimate(merge_all(...)).sum`` when strata don't overlap; when they do,
    the weighted form of eq 3 is what merge_all computes).
    """
    return jnp.stack([jnp.sum(s.total * s.mean) for s in stats_per_substream])


def per_stratum_means(stats: StratumStats, confidence: float = 0.95):
    """Per-stratum mean and CI half-width (for heatmaps / per-cell queries).

    A stratum is its own group here, so no lonely-singleton imputation is
    possible: under-sampled strata with ``n_k < 2`` report an *infinite*
    half-width instead of the false-zero a singleton's ``m2 == 0`` would
    plug in (fully sampled strata stay exact: fpc == 0)."""
    s2_k = jnp.where(stats.n > 1, stats.m2 / jnp.maximum(stats.n - 1.0, 1.0), 0.0)
    fpc = jnp.where(stats.total > 0, 1.0 - stats.n / jnp.maximum(stats.total, 1.0), 0.0)
    var_k = fpc * s2_k / jnp.maximum(stats.n, 1.0)
    identified = (stats.n > 1) | ((stats.n > 0) & (stats.n >= stats.total))
    var_k = jnp.where(identified, var_k, jnp.inf)
    moe_k = z_value(confidence) * jnp.sqrt(jnp.maximum(var_k, 0.0))
    return stats.mean, moe_k


def weighted_estimate(
    values: jnp.ndarray, weight: jnp.ndarray, population: jnp.ndarray
) -> jnp.ndarray:
    """Horvitz-Thompson mean from (value, weight) pairs — one-liner used by
    the LM training integration (weights from SampleResult)."""
    return jnp.sum(values * weight) / jnp.maximum(population, 1.0)


# ---------------------------------------------------------------------------
# Accumulator registry: pluggable mergeable per-stratum summary kinds
# ---------------------------------------------------------------------------


class Extrema(NamedTuple):
    """Per-stratum sample extrema lattice; shapes (S+1,), ±inf identities."""

    min: jnp.ndarray
    max: jnp.ndarray


class QuantileSketch(NamedTuple):
    """Mergeable fixed-size per-stratum quantile histogram.

    ``bins`` is (S+1, SKETCH_NUM_BINS) f32: per-stratum counts of *sampled*
    tuples over a fixed log-domain bin layout (DDSketch-style, see
    :func:`sketch_bin_index`).  Because the layout is a global constant, the
    merge is plain addition — bins psum across shards, sum across panes, and
    compose associatively/commutatively by construction.  Counts are
    unweighted on the edge; finalize expands stratum k's row by the
    Horvitz-Thompson factor N_k/n_k (constant within a stratum for SRS,
    Bernoulli, and Neyman draws), which is exactly per-tuple HT weighting.
    """

    bins: jnp.ndarray


# Sketch bin layout (global constants — the mergeability precondition).
# Geometric bins over magnitude: relative accuracy alpha = tanh(LOG_GAMMA/2)
# ~ 4%, covering magnitudes MIN_MAG .. MIN_MAG*e^(B*LOG_GAMMA) (~8.9 decades:
# 1e-4 .. ~8e4); magnitudes outside clamp to the edge bins.  Layout, in
# ascending value order: B negative-magnitude bins (reversed), one zero bin,
# B positive-magnitude bins.
SKETCH_BINS_PER_SIDE = 256
SKETCH_LOG_GAMMA = 0.08
SKETCH_MIN_MAG = 1e-4
SKETCH_NUM_BINS = 2 * SKETCH_BINS_PER_SIDE + 1


def sketch_bin_index(values: jnp.ndarray) -> jnp.ndarray:
    """Value -> bin index in [0, SKETCH_NUM_BINS): the fixed log layout."""
    v = values.astype(jnp.float32)
    mag = jnp.abs(v)
    k = jnp.floor(jnp.log(jnp.maximum(mag, SKETCH_MIN_MAG) / SKETCH_MIN_MAG) / SKETCH_LOG_GAMMA)
    k = jnp.clip(k, 0, SKETCH_BINS_PER_SIDE - 1).astype(jnp.int32)
    zero = SKETCH_BINS_PER_SIDE  # index of the |v| <= MIN_MAG bin
    idx = jnp.where(v > SKETCH_MIN_MAG, zero + 1 + k, jnp.where(v < -SKETCH_MIN_MAG, zero - 1 - k, zero))
    return idx.astype(jnp.int32)


def sketch_bin_values() -> jnp.ndarray:
    """(SKETCH_NUM_BINS,) representative value per bin (geometric mid)."""
    k = jnp.arange(SKETCH_BINS_PER_SIDE, dtype=jnp.float32)
    rep = SKETCH_MIN_MAG * jnp.exp((k + 0.5) * SKETCH_LOG_GAMMA)
    return jnp.concatenate([-rep[::-1], jnp.zeros((1,), jnp.float32), rep])


def sketch_bin_edges() -> jnp.ndarray:
    """(SKETCH_NUM_BINS + 1,) ascending bin boundaries of the fixed layout.

    Bin ``i`` covers ``[edges[i], edges[i+1]]``; the zero bin spans
    ``[-MIN_MAG, MIN_MAG]`` and the outermost edges clamp the layout range.
    """
    k = jnp.arange(SKETCH_BINS_PER_SIDE + 1, dtype=jnp.float32)
    pos = SKETCH_MIN_MAG * jnp.exp(k * SKETCH_LOG_GAMMA)
    return jnp.concatenate([-pos[::-1], pos])


def sketch_quantile(weighted_bins: jnp.ndarray, q: float) -> jnp.ndarray:
    """Invert a (..., SKETCH_NUM_BINS) weighted histogram at quantile ``q``.

    Continuous inversion: finds the first bin whose cumulative mass reaches
    ``q`` of the total and interpolates linearly between that bin's edges by
    the within-bin mass fraction; NaN where the histogram is empty (an
    empty group has no ``p50`` — a confident-looking 0 there masquerades as
    data, and the query layer's ``n == 0`` guard turns the NaN into the
    standard no-evidence report of ``relative_error = inf``).  The
    continuity matters beyond accuracy — it is what lets the bootstrap in
    :mod:`.bounds` resolve sampling error *finer than one bin* (a
    representative-value inversion would quantize replicate quantiles to the
    bin grid and collapse narrow CIs to zero width).  Works batched over
    leading group/replicate dimensions.
    """
    total = jnp.sum(weighted_bins, axis=-1, keepdims=True)
    cdf = jnp.cumsum(weighted_bins, axis=-1)
    target = jnp.maximum(jnp.asarray(q, jnp.float32) * total, 1e-30)
    idx = jnp.argmax(cdf >= target, axis=-1)
    c_cur = jnp.take_along_axis(cdf, idx[..., None], axis=-1)[..., 0]
    c_prev = jnp.where(
        idx > 0,
        jnp.take_along_axis(cdf, jnp.maximum(idx - 1, 0)[..., None], axis=-1)[..., 0],
        0.0,
    )
    frac = jnp.clip(
        (target[..., 0] - c_prev) / jnp.maximum(c_cur - c_prev, 1e-30), 0.0, 1.0
    )
    edges = sketch_bin_edges()
    lo_e = edges[idx]
    hi_e = edges[idx + 1]
    val = lo_e + frac * (hi_e - lo_e)
    return jnp.where(total[..., 0] > 0, val, jnp.nan)


class Accumulator:
    """Protocol of one registry citizen: a named mergeable summary kind.

    State is any pytree of (S+1,)-leading arrays.  Laws the engine relies on
    (property-tested): ``merge`` is associative + commutative with
    ``accumulate`` on an empty window as identity; ``merge_panes`` equals a
    sequential merge fold; ``psum`` equals merging all shards' states;
    ``zero_overflow`` removes the out-of-region slot from estimation.
    """

    kind: str = "?"

    #: True where the kind implements the three sorted-pass hooks below:
    #: the engine then reduces it inside the pass's one stratum-sorted scan
    #: (:func:`accumulate_sorted`) instead of calling :meth:`accumulate`
    sorted_pass: bool = False

    def accumulate(self, values, stratum_idx, mask, num_slots, counts=None):
        """Reduce one window's sampled tuples of a column to a state."""
        raise NotImplementedError

    def sorted_leaves(self, values, mask):
        """Sorted-pass hook: the per-tuple summary pytree, ``(N,)`` leaves,
        of one column's values and kept flags in stratum-sorted order."""
        raise NotImplementedError

    def sorted_combine(self, a, b):
        """Sorted-pass hook: the summary of two adjacent runs of one stratum
        (``a`` before ``b``); must be associative."""
        raise NotImplementedError

    def sorted_state(self, runs, present, counts):
        """Sorted-pass hook: the state from each slot's summary over its
        kept tuples (``(S+1,)`` leaves).  ``present`` is False where the
        slot kept no tuple: its summary is then undefined and the state
        takes the merge identity there.  ``counts`` are the population
        sizes N_k."""
        raise NotImplementedError

    def merge(self, a, b):
        """Exact pairwise combine of two states."""
        raise NotImplementedError

    def merge_panes(self, stacked):
        """Vectorized multi-way merge over a leading pane axis."""
        raise NotImplementedError

    def psum(self, state, axis_names, shared=None):
        """Cross-shard combine via collectives (``shared`` is an optional
        already-combined moments state for n/total reuse)."""
        raise NotImplementedError

    def zero_overflow(self, state):
        """Neutralize the overflow slot (merge identities there)."""
        raise NotImplementedError

    def payload_vectors(self) -> int:
        """(S+1)-float vectors this kind adds to one column's preagg uplink
        payload (excluding the n/total pair, shipped once per pass)."""
        raise NotImplementedError

    def payload_flatten(self, state):
        """The wire-format view of a state: ordered ``(name, array,
        quantize_ok, identity)`` rows, each array ``(S+1,)`` or
        ``(S+1, K)`` with the stratum axis leading.

        ``quantize_ok`` marks value rows a lossy codec may quantize;
        count/population rows must declare ``False`` — they drive fpc and
        error bounds and stay exact on the wire.  ``identity`` is the
        scalar a codec may skip (the row's merge identity: 0 for additive
        rows, ±inf for extrema lattices), so empty strata compress to a
        bitmap bit.  Contract: ``payload_unflatten`` over these rows must
        rebuild the state bit-exactly (see :mod:`.codec`)."""
        raise NotImplementedError

    def payload_unflatten(self, rows):
        """Rebuild a state from a ``{name: array}`` mapping of decoded
        :meth:`payload_flatten` rows.  Must be the bit-exact inverse on
        untouched rows; derived leaves (e.g. the moments ``mean``) are
        recomputed rather than shipped."""
        raise NotImplementedError

    def template(self):
        """Structure-only state (for shard_map out_specs trees)."""
        raise NotImplementedError

    def interval(
        self,
        state,
        agg_kind: str,
        moments: "StratumStats",
        *,
        q: float | None = None,
        confidence: float = 0.95,
        key=None,
        replicates: int = 0,
        grp=None,
        num_groups: int = 1,
        **aux,
    ):
        """Sampling-error CI ``(lo, hi)`` for aggregate ``agg_kind``
        finalized from this state, or ``None`` when the kind carries no
        bound logic (the engine falls back to a zero-width interval).

        ``moments`` is the column's merged moment state — the
        ``(n_k, N_k)`` expansion factors every bound needs (and the
        mean/s² rows the bootstrap resamples).  ``key`` seeds the
        bootstrap deterministically; ``replicates == 0`` disables
        resampling-based bounds.  ``aux`` carries kind-specific extras the
        engine forwards uniformly (e.g. ``sketch``/``center`` for the
        moments kind) — implementations must tolerate and ignore extras
        they don't use, so ``finalize`` can call any registered kind
        through one signature.  Registered kinds own their bound logic
        (see :mod:`.bounds`), and new kinds inherit the contract by
        overriding this hook.
        """
        return None


class MomentsAccumulator(Accumulator):
    """Eq 4 sample moments (:class:`StratumStats`), exact Chan merges."""

    kind = "moments"
    sorted_pass = True

    def accumulate(self, values, stratum_idx, mask, num_slots, counts=None):
        return accumulate_sorted(((self, values, mask),), stratum_idx, num_slots, counts)[0]

    def sorted_leaves(self, values, mask):
        v = jnp.where(mask, values.astype(jnp.float32), 0.0)
        return _MomentRun(n=mask.astype(jnp.float32), wsum=v, mean=v, m2=jnp.zeros_like(v))

    def sorted_combine(self, a, b):
        # Chan et al.: the runs' means and centred second moments merge
        # without cancellation; wsum stays a plain sum of the run's values
        n = a.n + b.n
        w = jnp.where(n > 0, b.n / jnp.maximum(n, 1.0), 0.0)
        d = b.mean - a.mean
        return _MomentRun(
            n=n, wsum=a.wsum + b.wsum, mean=a.mean + d * w, m2=a.m2 + b.m2 + d * d * a.n * w
        )

    def sorted_state(self, runs, present, counts):
        n = jnp.where(present, runs.n, 0.0)
        wsum = jnp.where(present, runs.wsum, 0.0)
        mean = jnp.where(n > 0, wsum / jnp.maximum(n, 1.0), 0.0)
        return StratumStats(
            n=n, total=counts.astype(jnp.float32), wsum=wsum,
            m2=jnp.where(present, runs.m2, 0.0), mean=mean,
        )

    def from_kernel_rows(self, count, s1, s2, counts):
        """Optional kernel hook: adapt fused-kernel raw power-sum rows
        (kept count, Σy, Σy²; population ``counts``) to the state this
        accumulator's merges/finalize consume.  Not part of the registry
        protocol — only kinds a kernel emits rows for implement it."""
        return stats_from_raw_moments(count, s1, s2, counts)

    def merge(self, a, b):
        return merge_stats(a, b)

    def merge_panes(self, stacked):
        return merge_stats_panes(stacked)

    def psum(self, state, axis_names, shared=None):
        if shared is None:
            return psum_stats(state, axis_names)
        # columns accumulated from the same sample share n/total: reuse the
        # combined vectors and psum only this column's wsum/raw2 pair
        n, total = shared.n, shared.total
        wsum = jax.lax.psum(state.wsum, axis_names)
        raw2 = jax.lax.psum(state.m2 + state.n * state.mean * state.mean, axis_names)
        mean = jnp.where(n > 0, wsum / jnp.maximum(n, 1.0), 0.0)
        m2 = jnp.maximum(raw2 - n * mean * mean, 0.0)
        return StratumStats(n=n, total=total, wsum=wsum, m2=m2, mean=mean)

    def zero_overflow(self, state):
        return zero_overflow_stats(state)

    def payload_vectors(self) -> int:
        return 2  # wsum + raw second moment (mean/m2 derived cloud-side)

    def payload_flatten(self, state):
        # n/total are count rows (exact on the wire — fpc and every bound
        # reads them); wsum/m2 are the value moments.  m2 ships *directly*
        # rather than as the psum-style raw2 = m2 + n·mean²: recovering m2
        # from raw2 cancels catastrophically when n·mean² >> m2, so the
        # raw2 form could not honor the bit-exact unflatten contract.
        return (
            ("n", state.n, False, 0.0),
            ("total", state.total, False, 0.0),
            ("wsum", state.wsum, True, 0.0),
            ("m2", state.m2, True, 0.0),
        )

    def payload_unflatten(self, rows):
        n, total, wsum, m2 = rows["n"], rows["total"], rows["wsum"], rows["m2"]
        # mean is derived exactly as every producer derives it, so a
        # lossless round-trip reproduces it bitwise
        mean = jnp.where(n > 0, wsum / jnp.maximum(n, 1.0), 0.0)
        return StratumStats(n=n, total=total, wsum=wsum, m2=m2, mean=mean)

    def template(self):
        return StratumStats(*(0,) * 5)

    def interval(self, state, agg_kind, moments, *, q=None, confidence=0.95,
                 key=None, replicates=0, grp=None, num_groups=1, sketch=None,
                 center=None, **aux):
        """``var``: stratified parametric bootstrap over the moment rows
        (singleton-guarded s², see :func:`guarded_s2`).

        When the column *already ships* a quantile sketch (``sketch`` is
        its state and ``center`` the plug-in point estimate), two free
        sharpenings kick in with zero extra uplink: the sketch's
        per-stratum kurtosis widens the s² spread beyond normal theory,
        and a second, fully nonparametric CI is bootstrapped from the
        collapsed bin replicates — the reported interval is the
        conservative union of both channels.  Without a sketch the
        normal-theory moment bootstrap stands alone (documented to
        under-cover extremely heavy-tailed columns).
        """
        if agg_kind != "var" or key is None or replicates <= 0:
            return None
        from . import bounds  # deferred: bounds builds on this module

        s2_eff, unidentified = guarded_s2(
            state.n, state.total, state.m2, grp=grp, num_groups=num_groups
        )
        kurtosis = None
        if sketch is not None:
            kurtosis = bounds.sketch_kurtosis(sketch.bins, state.n)
        k_mom, k_sk = jax.random.split(key)
        lo, hi = bounds.var_interval(
            k_mom, state.n, state.total, state.mean, s2_eff, confidence,
            replicates, grp=grp, num_groups=num_groups, unidentified=unidentified,
            kurtosis=kurtosis,
        )
        if sketch is not None and center is not None:
            lo_s, hi_s = bounds.var_sketch_interval(
                k_sk, sketch.bins, state.n, state.total, confidence, replicates,
                center, grp=grp, num_groups=num_groups,
            )
            lo = jnp.minimum(lo, lo_s)
            hi = jnp.maximum(hi, hi_s)
        return lo, hi


class ExtremaAccumulator(Accumulator):
    """Per-stratum min/max lattices with ±inf identities."""

    kind = "extrema"
    sorted_pass = True

    def accumulate(self, values, stratum_idx, mask, num_slots, counts=None):
        return accumulate_sorted(((self, values, mask),), stratum_idx, num_slots, counts)[0]

    def sorted_leaves(self, values, mask):
        v = values.astype(jnp.float32)
        return Extrema(min=jnp.where(mask, v, jnp.inf), max=jnp.where(mask, v, -jnp.inf))

    def sorted_combine(self, a, b):
        return self.merge(a, b)

    def sorted_state(self, runs, present, counts):
        return Extrema(
            min=jnp.where(present, runs.min, jnp.inf), max=jnp.where(present, runs.max, -jnp.inf)
        )

    def from_kernel_rows(self, mins, maxs) -> Extrema:
        """Optional kernel hook: wrap fused-kernel extrema rows (±inf
        identities where a stratum kept nothing)."""
        return Extrema(min=mins, max=maxs)

    def identity(self, num_slots: int) -> Extrema:
        return Extrema(
            min=jnp.full((num_slots,), jnp.inf, jnp.float32),
            max=jnp.full((num_slots,), -jnp.inf, jnp.float32),
        )

    def merge(self, a, b):
        return Extrema(min=jnp.minimum(a.min, b.min), max=jnp.maximum(a.max, b.max))

    def merge_panes(self, stacked):
        return Extrema(min=jnp.min(stacked.min, axis=0), max=jnp.max(stacked.max, axis=0))

    def psum(self, state, axis_names, shared=None):
        return Extrema(
            min=jax.lax.pmin(state.min, axis_names), max=jax.lax.pmax(state.max, axis_names)
        )

    def zero_overflow(self, state):
        keep = jnp.arange(state.min.shape[0]) < (state.min.shape[0] - 1)
        return Extrema(
            min=jnp.where(keep, state.min, jnp.inf), max=jnp.where(keep, state.max, -jnp.inf)
        )

    def payload_vectors(self) -> int:
        return 2  # min + max

    def payload_flatten(self, state):
        # identities are the lattice units: a stratum that kept nothing
        # holds (+inf, -inf) and costs one bitmap bit on the wire
        return (
            ("min", state.min, True, float("inf")),
            ("max", state.max, True, float("-inf")),
        )

    def payload_unflatten(self, rows):
        return Extrema(min=rows["min"], max=rows["max"])

    def template(self):
        return Extrema(*(0,) * 2)

    def interval(self, state, agg_kind, moments, *, q=None, confidence=0.95,
                 key=None, replicates=0, grp=None, num_groups=1, **aux):
        """``min``/``max``: closed-form order-statistic + Cantelli bounds
        from the rank slack of per-stratum sampling fractions (no
        resampling; deterministic)."""
        if agg_kind not in ("min", "max"):
            return None
        from . import bounds  # deferred: bounds builds on this module

        s2 = jnp.where(
            moments.n > 1, moments.m2 / jnp.maximum(moments.n - 1.0, 1.0), 0.0
        )
        ext = state.max if agg_kind == "max" else state.min
        return bounds.extrema_interval(
            agg_kind, ext, moments.n, moments.total, moments.mean, s2,
            confidence, grp=grp, num_groups=num_groups,
        )


class QuantileSketchAccumulator(Accumulator):
    """DDSketch-style mergeable log-histogram (see :class:`QuantileSketch`)."""

    kind = "sketch"

    def accumulate(self, values, stratum_idx, mask, num_slots, counts=None):
        b = sketch_bin_index(values)
        flat = stratum_idx.astype(jnp.int32) * SKETCH_NUM_BINS + b
        bins = jax.ops.segment_sum(
            mask.astype(jnp.float32), flat, num_segments=num_slots * SKETCH_NUM_BINS
        )
        return QuantileSketch(bins=bins.reshape(num_slots, SKETCH_NUM_BINS))

    def from_kernel_rows(self, bins) -> QuantileSketch:
        """Optional kernel hook: wrap fused-kernel (S, NUM_BINS) sketch
        rows — the binning already happened inside the kernel (the fused
        backend's single-traversal contract), so this is shape adoption,
        not re-binning."""
        return QuantileSketch(bins=bins)

    def merge(self, a, b):
        return QuantileSketch(bins=a.bins + b.bins)

    def merge_panes(self, stacked):
        return QuantileSketch(bins=jnp.sum(stacked.bins, axis=0))

    def psum(self, state, axis_names, shared=None):
        return QuantileSketch(bins=jax.lax.psum(state.bins, axis_names))

    def zero_overflow(self, state):
        keep = jnp.arange(state.bins.shape[0]) < (state.bins.shape[0] - 1)
        return QuantileSketch(bins=jnp.where(keep[:, None], state.bins, 0.0))

    def payload_vectors(self) -> int:
        return SKETCH_NUM_BINS

    def payload_flatten(self, state):
        # bin rows are integer-valued counts: HT expansion and quantile
        # inversion read them as masses, so they never quantize (top-k +
        # residual is the sanctioned lossy path — it preserves totals)
        return (("bins", state.bins, False, 0.0),)

    def payload_unflatten(self, rows):
        return QuantileSketch(bins=rows["bins"])

    def template(self):
        return QuantileSketch(bins=0)

    def interval(self, state, agg_kind, moments, *, q=None, confidence=0.95,
                 key=None, replicates=0, grp=None, num_groups=1, **aux):
        """``p<q>``: stratified multinomial bootstrap over the sketch bin
        rows (Poissonized + CLT-collapsed, see :mod:`.bounds`)."""
        if q is None or key is None or replicates <= 0:
            return None
        from . import bounds  # deferred: bounds builds on this module

        return bounds.quantile_interval(
            key, state.bins, moments.n, moments.total, q, confidence,
            replicates, grp=grp, num_groups=num_groups,
        )


ACCUMULATORS: dict[str, Accumulator] = {}


def register_accumulator(acc: Accumulator) -> Accumulator:
    """Add (or replace) a registry citizen; returns it for chaining."""
    ACCUMULATORS[acc.kind] = acc
    return acc


MOMENTS = register_accumulator(MomentsAccumulator())
EXTREMA = register_accumulator(ExtremaAccumulator())
SKETCH = register_accumulator(QuantileSketchAccumulator())


def accumulator(kind: str) -> Accumulator:
    acc = ACCUMULATORS.get(kind)
    if acc is None:
        raise KeyError(
            f"unknown accumulator kind {kind!r}; registered: {sorted(ACCUMULATORS)}"
        )
    return acc


# -- the stratum-sorted pass --------------------------------------------------
#
# A scatter of N tuples into S+1 slots is the slowest op a pass runs on the
# TPU: XLA must assume that indices collide.  The sorted pass reduces the
# sorted-pass states of one sample mask from ONE sort keyed on (stratum
# slot, kept first, position), with the columns carried as sort operands
# (nothing is gathered into sorted order), then ONE segmented scan over the
# sorted runs, read out at each slot's last kept tuple.  Its cost does not
# depend on S.  Each slot's kept tuples thus form one contiguous run in
# their original order, and the scan is translation invariant, so a slot's
# state depends only on the sequence of its kept values: a pass over the
# whole pane and one over the raw mode's compacted buffer agree bit for bit.


class _MomentRun(NamedTuple):
    """The moments kind's per-run summary in the sorted pass."""

    n: jnp.ndarray
    wsum: jnp.ndarray
    mean: jnp.ndarray
    m2: jnp.ndarray


class SortedSegments(NamedTuple):
    """Where each stratum slot's tuples lie in a stratum-sorted pass.

    start: (N,) bool — the tuple at this sorted position opens a run (a
           slot's kept tuples, or its other tuples);
    last:  (S+1,) int32 — sorted position of each slot's last kept tuple
           (0 where the slot kept none);
    kept:  (S+1,) int32 — kept tuples of each slot;
    size:  (S+1,) int32 — tuples of each slot, kept or not.
    """

    start: jnp.ndarray
    last: jnp.ndarray
    kept: jnp.ndarray
    size: jnp.ndarray


def sort_by_stratum(stratum_idx: jnp.ndarray, mask: jnp.ndarray, num_slots: int, operands):
    """One sort keyed on ``(slot, not mask, position)`` that carries
    ``operands`` (``(N,)`` arrays) along; returns ``(SortedSegments, sorted
    mask, sorted operands)``.

    The position makes every key unique, so the order is the stable one at
    the price of an unstable sort (which the TPU compiles several times
    faster), packed into one uint32 key where the three fit in 32 bits.
    Run boundaries come from one binary search of the sorted runs for every
    ``(slot, kept)`` key (``log2 N`` gathers of ``2 * (S+1)``).  Slot ids outside
    ``[0, S+1)`` share one run that no slot reads, as ``jax.ops.segment_*``
    drops them."""
    n = stratum_idx.shape[0]
    s = stratum_idx.astype(jnp.int32)
    run = jnp.where(
        (s >= 0) & (s < num_slots), 2 * s + (~mask).astype(jnp.int32), 2 * num_slots
    )
    pos_bits = max((n - 1).bit_length(), 1)
    if pos_bits + (2 * num_slots).bit_length() <= 32:
        key = (run.astype(jnp.uint32) << pos_bits) | jax.lax.iota(jnp.uint32, n)
        key, *ops = jax.lax.sort((key, *operands), num_keys=1, is_stable=False)
        run = (key >> pos_bits).astype(jnp.int32)
    else:
        run, _, *ops = jax.lax.sort(
            (run, jax.lax.iota(jnp.int32, n), *operands), num_keys=2, is_stable=False
        )
    start = jnp.concatenate([jnp.ones((1,), bool), run[1:] != run[:-1]])
    # first position of run q, for q = 2k (slot k's kept tuples), 2k + 1 (its
    # other tuples) and 2(S+1) (the slot ids outside the table)
    first = jnp.searchsorted(
        run, jnp.arange(2 * num_slots + 1, dtype=jnp.int32), side="left",
        method="scan_unrolled",
    )
    begin, kept_end, end = first[0:-1:2], first[1::2], first[2::2]
    seg = SortedSegments(
        start=start, last=jnp.maximum(kept_end - 1, 0), kept=kept_end - begin,
        size=end - begin,
    )
    return seg, (run & 1) == 0, tuple(ops)


def _segmented_scan(start, runs, combine):
    """Inclusive segmented scan (Hillis-Steele) along axis 0: afterwards
    position ``p`` holds the combine of its run's elements up to ``p``.
    Step ``i`` combines every position with the one ``2**i`` before it,
    elementwise: no scatter, no gather; the steps run as a loop, so the
    program holds one.  The combine tree at ``p`` depends only on ``p``'s
    distance from its run's start, so the same run anywhere gives the same
    bits."""
    n = start.shape[0]

    def shifted(x, by, fill):
        return jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([jnp.full_like(x, fill), x]), n - by, n
        )

    def step(i, carry):
        start, runs = carry
        by = jnp.left_shift(1, i)
        merged = combine(jax.tree.map(lambda x: shifted(x, by, 0), runs), runs)
        # ``start`` marks positions whose window already reaches back to
        # their run's start; position 0 always opens a run
        runs = jax.tree.map(lambda x, m: jnp.where(start, x, m), runs, merged)
        return start | shifted(start, by, False), runs

    return jax.lax.fori_loop(0, (n - 1).bit_length(), step, (start, runs))[1]


def accumulate_sorted(
    requests: Sequence,
    stratum_idx: jnp.ndarray,
    num_slots: int,
    counts: jnp.ndarray | None = None,
) -> list:
    """The states of ``requests`` — ``(accumulator, values, mask)`` triples
    of sorted-pass kinds — from one stratum-sorted pass per distinct mask.

    The requests of one mask share its sort (each distinct values array
    rides it once as an operand) and one segmented scan.  ``counts`` are the
    population sizes N_k (the moments' ``total``); when None, each slot's
    tuples.  Empty and fully masked slots take the identities: 0 for sums,
    ``±inf`` for extrema."""
    by_mask: dict = {}
    for i, (_, _, mask) in enumerate(requests):
        by_mask.setdefault(id(mask), []).append(i)
    out: list = [None] * len(requests)
    for idx in by_mask.values():
        mask = requests[idx[0]][2]
        columns = {id(requests[i][1]): requests[i][1] for i in idx}
        seg, kept, ops = sort_by_stratum(stratum_idx, mask, num_slots, tuple(columns.values()))
        at = dict(zip(columns, ops))
        accs = [requests[i][0] for i in idx]
        leaves = [acc.sorted_leaves(at[id(requests[i][1])], kept) for acc, i in zip(accs, idx)]

        def combine(a, b, accs=accs):
            return [acc.sorted_combine(x, y) for acc, x, y in zip(accs, a, b)]

        scanned = _segmented_scan(seg.start, leaves, combine)
        totals = jax.tree.map(lambda x: x[seg.last], scanned)
        present = seg.kept > 0
        pop = seg.size if counts is None else counts
        for acc, i, t in zip(accs, idx, totals):
            out[i] = acc.sorted_state(t, present, pop)
    return out


# -- column-level operations over {kind: state} dicts ------------------------


def accumulate_column(
    kinds: Sequence[str],
    values: jnp.ndarray,
    stratum_idx: jnp.ndarray,
    mask: jnp.ndarray,
    num_slots: int,
    counts: jnp.ndarray | None = None,
) -> dict:
    """One column's registry states for the requested accumulator kinds:
    the sorted-pass kinds from one :func:`accumulate_sorted`, the others
    through their own ``accumulate``."""
    accs = [accumulator(k) for k in kinds]
    in_sort = iter(accumulate_sorted(
        [(a, values, mask) for a in accs if a.sorted_pass], stratum_idx, num_slots, counts
    ))
    return {
        a.kind: next(in_sort) if a.sorted_pass
        else a.accumulate(values, stratum_idx, mask, num_slots, counts=counts)
        for a in accs
    }


def merge_accs(a: dict, b: dict) -> dict:
    return {k: accumulator(k).merge(a[k], b[k]) for k in a}


def merge_accs_panes(stacked: dict) -> dict:
    """Vectorized pane merge of one column's stacked states (leading P axis)."""
    with jax.named_scope("merge_panes"):
        return {k: accumulator(k).merge_panes(s) for k, s in stacked.items()}


def psum_accs(accs: dict, axis_names, shared: StratumStats | None = None) -> dict:
    """Cross-shard combine of one column's states; pass an already-combined
    moments state as ``shared`` to skip the redundant n/total psums."""
    return {
        k: accumulator(k).psum(s, axis_names, shared=shared if k == "moments" else None)
        for k, s in accs.items()
    }


def zero_overflow_accs(accs: dict) -> dict:
    return {k: accumulator(k).zero_overflow(s) for k, s in accs.items()}


def accs_template(kinds: Sequence[str]) -> dict:
    return {k: accumulator(k).template() for k in kinds}
