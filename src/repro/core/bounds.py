"""Error-bounds subsystem: CIs for every accumulator kind, cloud-side only.

The paper's headline claim is *error-bounded* approximation, yet classic
stratified theory (eqs 5-10 in :mod:`.estimators`) only covers linear
statistics (SUM/MEAN).  This module closes the gap for the remaining
aggregate families — ``var``, quantiles (``p<q>``), and ``min``/``max`` —
by deriving sampling-error intervals **from the mergeable sufficient
statistics already shipped to the cloud**: per-stratum moment rows
``(n_k, N_k, ȳ_k, s²_k)`` and per-stratum sketch bin counts.  No extra
uplink bytes; everything here runs after the collective.

Three bound families, one per accumulator kind:

``var``     — **stratified parametric bootstrap over moment rows.**  Within
              stratum k the CLT gives ``ȳ*_k ~ N(ȳ_k, (1-f_k) s²_k / n_k)``
              and ``s²*_k`` resamples log-normally with relative variance
              ``(κ_k-1)(1-f_k)/(n_k-1)`` (κ from :func:`sketch_kurtosis`
              when the column already ships a sketch, normal-theory κ = 3
              otherwise); each replicate re-evaluates the plug-in
              population variance from the resampled rows.  Shapes are
              ``(R, S+1)`` — broadcast over replicates and strata,
              jit-friendly, microseconds on CPU.  When a sketch is shipped
              the reported interval is the conservative union with the
              fully nonparametric :func:`var_sketch_interval` channel.

``p<q>``    — **stratified multinomial bootstrap over sketch bins,
              Poissonized and collapsed across strata.**  Resampling the
              ``n_k`` sampled tuples of stratum k over its bin row is
              multinomial; Poissonizing makes bins independent
              (``c*_kb ~ Poisson(c_kb)``), and because finalize only reads
              the *weighted sum across strata*, the CLT collapses the
              whole stratum axis exactly:

                  Σ_k w_k Pois(c_kb)  ≈  N( Σ_k w_k c_kb,
                                            Σ_k w_k² (1-f_k) c_kb )

              with ``w_k = N_k/n_k`` the Horvitz-Thompson expansion and
              ``(1-f_k)`` the per-stratum finite-population correction.
              Each replicate perturbs the weighted histogram with one
              ``(R, ..., B)`` draw — third-moment-matched via the
              Wilson-Hilferty transform and pseudo-count-smoothed (see
              :func:`collapsed_replicates`) so sparse heavy-tail bins keep
              nominal coverage — and re-inverts the CDF.  The collapse is
              what makes 200-replicate bootstraps affordable per pane
              (a direct per-bin Poisson sampler is ~2000× slower on CPU).

``min/max`` — **order-statistic rank bounds + Cantelli.**  Under
              per-stratum SRS at fraction f_k, the probability that the
              ``m`` most extreme population values all evade the sample is
              ``≤ (1-f_k)^m``; hence with confidence c at most
              ``m_k = ⌈ln(1-c)/ln(1-f_k)⌉`` unsampled values of stratum k
              exceed the sample max (and symmetrically for min), clipped
              to the ``N_k - n_k`` unsampled tuples.  Cantelli's one-sided
              inequality converts the rank slack into a value bound: at
              most ``N_k s²/(s² + d²)`` values lie above ``ȳ_k + d``, so
              ``d_k = s_k·√(N_k/m_k − 1)`` bounds the overshoot.  Fully
              sampled strata (m_k = 0) get zero-width bounds; strata too
              thin to estimate spread (n_k < 2, under-sampled) are
              honestly unbounded (±inf).

All three shrink to zero width at fraction 1 (the fpc/rank terms vanish),
are deterministic in the PRNG key, and are continuous in the merged
statistics — so preagg/raw modes and fused sessions produce matching
bounds for the same sample (property-tested).

Every family reads the sampling fraction *only* through the realized
per-stratum ``(n_k, N_k)`` rows, never through a nominal fraction knob —
so when a fused session refines a member's shared sample down to its own
fraction (nested HT subsampling, see :mod:`.session`), the member's
intervals automatically reflect its **effective** fraction: a 10%-fraction
member fused with an 80% one reports honest 10% widths, which widen
monotonically as the refined fraction shrinks (property-tested in
``tests/test_subsampling.py``).

Grouped queries reuse the same code paths: every function takes an
optional ``grp`` stratum→group index (overflow slot mapping to a discarded
trailing group) and a static ``num_groups``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .estimators import sketch_bin_values, sketch_quantile

DEFAULT_REPLICATES = 200


def _gsum(x: jnp.ndarray, grp: jnp.ndarray, num_groups: int) -> jnp.ndarray:
    """Segment-sum strata into groups along the last axis (overflow group
    dropped); works batched over arbitrary leading axes."""
    moved = jnp.moveaxis(x, -1, 0)
    out = jax.ops.segment_sum(moved, grp, num_segments=num_groups + 1)[:num_groups]
    return jnp.moveaxis(out, 0, -1)


def _reduce(x: jnp.ndarray, grp: jnp.ndarray | None, num_groups: int) -> jnp.ndarray:
    return jnp.sum(x, axis=-1) if grp is None else _gsum(x, grp, num_groups)


def percentile_interval(
    reps: jnp.ndarray, confidence: float
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(lo, hi) percentile-bootstrap interval over the leading replicate axis."""
    alpha = (1.0 - confidence) / 2.0
    qs = jnp.asarray([alpha, 1.0 - alpha], jnp.float32)
    lo_hi = jnp.quantile(reps, qs, axis=0)
    return lo_hi[0], lo_hi[1]


def sketch_kurtosis(bins: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """Per-stratum kurtosis ``κ̂_k = m4/m2²`` estimated from sketch bin rows.

    The sampling variance of a stratum's s² is ``≈ (κ-1) σ⁴ / n`` — the
    normal-theory ``κ = 3`` badly under-covers heavy-tailed streams, and
    the moment rows themselves carry no fourth-moment information.  When
    the column *already ships* a quantile sketch, its binned distribution
    estimates κ for free (the ~4% bin resolution is negligible against
    κ's dynamic range); strata too thin to estimate (n < 8) fall back to
    the normal value.  Clipped to [1.5, 1e4] for numeric sanity.
    """
    vals = sketch_bin_values()
    cnt = jnp.sum(bins, axis=-1)
    mean = jnp.sum(bins * vals, axis=-1) / jnp.maximum(cnt, 1.0)
    d = vals - mean[..., None]
    m2 = jnp.sum(bins * d * d, axis=-1) / jnp.maximum(cnt, 1.0)
    m4 = jnp.sum(bins * d * d * d * d, axis=-1) / jnp.maximum(cnt, 1.0)
    kappa = m4 / jnp.maximum(m2 * m2, 1e-30)
    return jnp.where((n >= 8) & (m2 > 0), jnp.clip(kappa, 1.5, 1e4), 3.0)


def moment_replicates(
    key,
    n: jnp.ndarray,
    total: jnp.ndarray,
    mean: jnp.ndarray,
    s2: jnp.ndarray,
    replicates: int,
    kurtosis: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(R, S+1) parametric-bootstrap draws of per-stratum (mean, s²) rows.

    Strata with ``n_k == 0`` draw no mean spread; strata with ``n_k < 2``
    draw no s² spread (callers decide how to guard their contribution —
    see :func:`~.estimators.guarded_s2`).  Both spreads carry the
    finite-population correction ``(1 - f_k)`` so fully sampled strata are
    reproduced exactly.  ``kurtosis`` sets the s² spread
    ``Var(s²) ≈ (κ-1) s⁴ / n`` per stratum; ``None`` assumes normal tails
    (κ = 3) — pass :func:`sketch_kurtosis` when the column ships a sketch.
    """
    f = jnp.where(total > 0, n / jnp.maximum(total, 1.0), 1.0)
    fpc = jnp.maximum(1.0 - f, 0.0)
    kappa = jnp.asarray(3.0, jnp.float32) if kurtosis is None else kurtosis
    k1, k2 = jax.random.split(key)
    shape = (replicates,) + mean.shape
    e1 = jax.random.normal(k1, shape)
    e2 = jax.random.normal(k2, shape)
    se_mean = jnp.where(n > 0, jnp.sqrt(fpc * s2 / jnp.maximum(n, 1.0)), 0.0)
    mean_r = mean + se_mean * e1
    # s² resamples log-normally (moment-matched): the sampling distribution
    # of a variance is right-skewed — a symmetric normal clips its upper
    # tail and under-covers; the multiplicative form also keeps s²* >= 0
    # and degenerates to exactly s² at full fraction.
    rel_sd = jnp.where(
        n > 1,
        jnp.sqrt(jnp.maximum(kappa - 1.0, 0.0) * fpc / jnp.maximum(n - 1.0, 1.0)),
        0.0,
    )
    sig = jnp.sqrt(jnp.log1p(rel_sd * rel_sd))
    s2_r = s2 * jnp.exp(sig * e2 - 0.5 * sig * sig)
    return mean_r, s2_r


def var_interval(
    key,
    n: jnp.ndarray,
    total: jnp.ndarray,
    mean: jnp.ndarray,
    s2: jnp.ndarray,
    confidence: float,
    replicates: int,
    grp: jnp.ndarray | None = None,
    num_groups: int = 1,
    unidentified: jnp.ndarray | None = None,
    kurtosis: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Bootstrap CI for the plug-in population variance, per group.

    ``s2`` should already be singleton-guarded (imputed) so lonely strata
    contribute borrowed spread instead of false-zero; ``unidentified``
    marks groups whose variance no stratum identifies — their interval is
    ``[0, inf)``.  ``kurtosis`` (see :func:`sketch_kurtosis`) sharpens the
    s² resampling spread beyond normal theory for heavy-tailed columns.
    """
    with jax.named_scope("bounds"):
        mean_r, s2_r = moment_replicates(
            key, n, total, mean, s2, replicates, kurtosis=kurtosis
        )
        active = (n > 0) & (total > 0)
        w = jnp.where(active, total, 0.0)
        covered = jnp.maximum(_reduce(w, grp, num_groups), 1.0)
        sum_r = _reduce(w * mean_r, grp, num_groups)
        ey2_r = _reduce(w * (s2_r + mean_r * mean_r), grp, num_groups)
        mean_g_r = sum_r / covered
        var_r = jnp.maximum(ey2_r / covered - mean_g_r * mean_g_r, 0.0)
        lo, hi = percentile_interval(var_r, confidence)
        lo = jnp.maximum(lo, 0.0)
        if unidentified is not None:
            lo = jnp.where(unidentified, 0.0, lo)
            hi = jnp.where(unidentified, jnp.inf, hi)
        return lo, hi


# Poisson-rate smoothing of occupied bins: a sparse bin's observed count c
# systematically understates the uncertainty its true rate λ contributes to
# the resample (the tail the sample barely saw is exactly where λ̂ = c is
# least trustworthy).  Resampling at the Gamma posterior-mean rate c+1
# (exponential prior on occupied bins) is the standard smoothing fix; it
# restores heavy-tail coverage and vanishes under the fpc at full fraction.
SKETCH_PSEUDO_COUNT = 1.0


def _skewed_unit(eps: jnp.ndarray, skew: jnp.ndarray) -> jnp.ndarray:
    """Zero-mean unit-variance draws with target skewness (Wilson-Hilferty).

    Maps standard normals through the WH cube approximation of a gamma with
    shape ``α = 4/γ²`` and standardizes — smooth, vectorized, and exactly
    normal in the γ → 0 limit.  Matching the third moment matters: tail
    bins hold few, heavily-HT-weighted counts, and a symmetric perturbation
    clips their upper reach, under-covering right-skewed columns.
    """
    alpha = jnp.where(skew > 1e-6, 4.0 / jnp.maximum(skew * skew, 1e-12), 1e12)
    g = alpha * (1.0 - 1.0 / (9.0 * alpha) + eps / (3.0 * jnp.sqrt(alpha))) ** 3
    return (g - alpha) / jnp.sqrt(alpha)


def collapsed_replicates(
    key,
    bins: jnp.ndarray,
    n: jnp.ndarray,
    total: jnp.ndarray,
    replicates: int,
    grp: jnp.ndarray | None = None,
    num_groups: int = 1,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The collapsed stratified bootstrap engine over sketch bin rows.

    Returns ``(wb, wb_r)``: the per-group HT-weighted histogram
    ``(..., B)`` and ``replicates`` perturbed copies ``(R, ..., B)`` whose
    per-bin mean/variance/skew match the Poissonized multinomial resample
    collapsed across strata (variance ``Σ_k w_k²(1-f_k)(c_kb + 1)``, third
    moment ``Σ_k w_k³(1-f_k)(c_kb + 1)``, pseudo-count on occupied bins).
    """
    w = jnp.where(n > 0, total / jnp.maximum(n, 1.0), 0.0)
    fpc = jnp.where(total > 0, jnp.maximum(1.0 - n / jnp.maximum(total, 1.0), 0.0), 0.0)
    cb = bins + SKETCH_PSEUDO_COUNT * (bins > 0)
    wb = _reduce((w[:, None] * bins).swapaxes(-1, -2), grp, num_groups)
    v = _reduce(((w * w * fpc)[:, None] * cb).swapaxes(-1, -2), grp, num_groups)
    m3 = _reduce(((w * w * w * fpc)[:, None] * cb).swapaxes(-1, -2), grp, num_groups)
    # _reduce consumed the stratum axis; bins axis is now leading — restore
    wb = jnp.moveaxis(wb, 0, -1)  # (B,) or (B, G) -> (..., B)
    v = jnp.moveaxis(v, 0, -1)
    m3 = jnp.moveaxis(m3, 0, -1)
    skew = m3 / jnp.maximum(v, 1e-30) ** 1.5
    eps = jax.random.normal(key, (replicates,) + wb.shape)
    wb_r = jnp.maximum(wb + jnp.sqrt(v) * _skewed_unit(eps, skew), 0.0)
    return wb, wb_r


def quantile_interval(
    key,
    bins: jnp.ndarray,
    n: jnp.ndarray,
    total: jnp.ndarray,
    q: float,
    confidence: float,
    replicates: int,
    grp: jnp.ndarray | None = None,
    num_groups: int = 1,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Bootstrap CI for the HT-expanded sketch quantile, per group.

    ``bins`` is the merged (S+1, B) sampled-count histogram; each collapsed
    replicate (see :func:`collapsed_replicates`) re-inverts its CDF.
    """
    with jax.named_scope("bounds"):
        _, wb_r = collapsed_replicates(
            key, bins, n, total, replicates, grp=grp, num_groups=num_groups
        )
        q_r = sketch_quantile(wb_r, q)
        return percentile_interval(q_r, confidence)


def _hist_var(wb: jnp.ndarray) -> jnp.ndarray:
    """Population variance of a (..., B) weighted histogram."""
    vals = sketch_bin_values()
    tot = jnp.maximum(jnp.sum(wb, axis=-1), 1e-30)
    m1 = jnp.sum(wb * vals, axis=-1) / tot
    m2 = jnp.sum(wb * vals * vals, axis=-1) / tot
    return jnp.maximum(m2 - m1 * m1, 0.0)


def var_sketch_interval(
    key,
    bins: jnp.ndarray,
    n: jnp.ndarray,
    total: jnp.ndarray,
    confidence: float,
    replicates: int,
    center: jnp.ndarray,
    grp: jnp.ndarray | None = None,
    num_groups: int = 1,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Nonparametric var CI from an already-shipped sketch, per group.

    Each collapsed replicate re-evaluates the population variance of its
    weighted histogram — a full bootstrap of the plug-in functional at bin
    resolution, so third/fourth-moment sampling error is captured without
    distributional assumptions.  The interval is re-centered on ``center``
    (the exact moment-based plug-in estimate), which cancels the constant
    ~bin-resolution bias between the binned and exact statistics.
    """
    with jax.named_scope("bounds"):
        wb, wb_r = collapsed_replicates(
            key, bins, n, total, replicates, grp=grp, num_groups=num_groups
        )
        var_0 = _hist_var(wb)
        lo, hi = percentile_interval(_hist_var(wb_r), confidence)
        return jnp.maximum(center + (lo - var_0), 0.0), center + (hi - var_0)


def _rank_slack(n: jnp.ndarray, total: jnp.ndarray, confidence: float) -> jnp.ndarray:
    """m_k: with prob >= confidence at most this many unsampled tuples of
    stratum k lie beyond the sample extreme (0 when fully sampled)."""
    f = jnp.where(total > 0, n / jnp.maximum(total, 1.0), 1.0)
    log_miss = jnp.log(jnp.maximum(1.0 - f, 1e-30))
    m = jnp.ceil(jnp.log(1.0 - confidence) / jnp.minimum(log_miss, -1e-30))
    return jnp.clip(m, 0.0, jnp.maximum(total - n, 0.0))


def extrema_interval(
    side: str,
    ext_value: jnp.ndarray,
    n: jnp.ndarray,
    total: jnp.ndarray,
    mean: jnp.ndarray,
    s2: jnp.ndarray,
    confidence: float,
    grp: jnp.ndarray | None = None,
    num_groups: int = 1,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Order-statistic + Cantelli bound for ``min``/``max``, per group.

    Returns (lo, hi): for ``max`` the population extreme lies in
    ``[sample_max, hi]``; for ``min`` in ``[lo, sample_min]``.  The open
    side is +/-inf for strata whose spread is unobservable (n_k < 2 while
    under-sampled, including sampled-empty populated strata).
    """
    with jax.named_scope("bounds"):
        sign = 1.0 if side == "max" else -1.0
        m = _rank_slack(n, total, confidence)
        d = jnp.sqrt(s2 * jnp.maximum(total / jnp.maximum(m, 1.0) - 1.0, 0.0))
        # work in signed space (negate for min) so both sides are maxima
        witnessed = jnp.where(total > 0, sign * ext_value, -jnp.inf)
        bound = jnp.where(m > 0, sign * mean + d, witnessed)
        # spread unobservable: an under-sampled stratum with n_k < 2 admits no
        # Cantelli bound — its population extreme is honestly unbounded
        bound = jnp.where((m > 0) & (n < 2), jnp.inf, bound)
        # the bound can never undercut the witnessed sample extreme, and empty
        # populations contribute the lattice identity
        bound = jnp.where(total > 0, jnp.maximum(bound, witnessed), -jnp.inf)
        if grp is None:
            far = jnp.max(bound)
            near = jnp.max(witnessed)
        else:
            far = jax.ops.segment_max(bound, grp, num_segments=num_groups + 1)[:num_groups]
            near = jax.ops.segment_max(witnessed, grp, num_segments=num_groups + 1)[:num_groups]
        if side == "max":
            return near, far
        return -far, -near
