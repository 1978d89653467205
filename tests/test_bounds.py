"""Error-bounds subsystem: every aggregate kind reports a (lo, hi, rel)
sampling-error interval from the shipped sufficient statistics — bootstrap
coverage (property-tested), determinism, preagg/raw and session parity,
zero width at full fraction, and graceful SLO degradation."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st

from repro.core import (
    SHENZHEN_BBOX,
    AggSpec,
    EdgeCloudPipeline,
    PipelineConfig,
    Query,
    SLO,
    StreamSession,
    WindowSpec,
    estimators,
    feedback,
    make_table,
    sampling,
    windows,
)
from repro.data.streams import shenzhen_taxi_stream

ALL_KINDS = ("mean", "sum", "count", "var", "min", "max", "p50", "p99")
ALL_AGGS = tuple(AggSpec(k, "value") for k in ALL_KINDS)


@pytest.fixture(scope="module")
def table():
    return make_table(*SHENZHEN_BBOX, precision=5)


@pytest.fixture(scope="module")
def pipe(table):
    return EdgeCloudPipeline(table, PipelineConfig(raw_capacity=20_000))


@pytest.fixture(scope="module")
def window():
    stream = shenzhen_taxi_stream(num_chunks=2, seed=0)
    return next(windows.count_windows(stream, 20_000))


def _check_interval(est, key):
    lo = np.asarray(est.ci_low)
    hi = np.asarray(est.ci_high)
    val = np.asarray(est.value)
    rel = np.asarray(est.relative_error)
    moe = np.asarray(est.moe)
    assert not np.isnan(lo).any(), f"{key}: NaN ci_low"
    assert not np.isnan(hi).any(), f"{key}: NaN ci_high"
    assert not np.isnan(rel).any(), f"{key}: NaN relative_error"
    assert not np.isnan(moe).any(), f"{key}: NaN moe"
    # a NaN value is the explicit no-evidence marker (empty quantile
    # histogram): its interval is pinned to (-inf, inf) with rel = inf,
    # so containment only applies where there is a point estimate
    nan_val = np.isnan(val)
    assert np.isinf(np.asarray(rel)[nan_val]).all(), f"{key}: NaN value w/ finite rel"
    assert np.all((lo <= val + 1e-6) | nan_val), key
    assert np.all((val <= hi + 1e-6) | nan_val), key


# -- every kind, both execution paths -----------------------------------------


def test_every_kind_bounded_through_execute(pipe, window):
    """All eight aggregate kinds return a finite or explicitly-infinite
    (lo, hi, rel) triple through one-shot execute; the error-bounded
    families are finite at a healthy fraction."""
    q = Query(aggs=ALL_AGGS)
    r = pipe.execute(q, jax.random.key(3), window, fraction=0.6)
    for k in ALL_KINDS:
        _check_interval(r.estimates[f"{k}_value"], k)
    for k in ("mean", "sum", "var", "p50", "p99"):
        rel = float(r.estimates[f"{k}_value"].relative_error)
        assert np.isfinite(rel) and rel > 0, k
    assert float(r.estimates["count_value"].moe) == 0.0


def test_every_kind_bounded_through_session_panes(pipe, window):
    """The same triples flow through fused session pane emission — including
    a multi-pane sliding window (the pane-merge finalize path)."""
    sess = StreamSession(pipe, initial_fraction=0.6)
    reg1 = sess.register(Query(aggs=ALL_AGGS))
    reg2 = sess.register(
        Query(aggs=(AggSpec("var", "value"), AggSpec("p99", "value"))),
        window=WindowSpec("sliding", size=2),
    )
    steps = sess.run([window, window], key=jax.random.key(4))
    for k in ALL_KINDS:
        _check_interval(steps[-1].results[reg1.qid].estimates[f"{k}_value"], k)
    two_pane = steps[-1].results[reg2.qid]
    for key in ("var_value", "p99_value"):
        _check_interval(two_pane.estimates[key], key)
        assert np.isfinite(float(two_pane.estimates[key].relative_error)), key


def test_grouped_bounds_shapes_and_sanity(pipe, window, table):
    """Grouped queries report per-group intervals; empty groups degrade to
    explicit infinite intervals (quantiles surface a NaN *value* as the
    no-evidence marker, never a silent 0), and bound arithmetic never
    yields NaN lo/hi/rel/moe."""
    q = Query(aggs=(AggSpec("var", "value"), AggSpec("p50", "value"),
                    AggSpec("max", "value")), group_by="neighborhood")
    r = pipe.execute(q, jax.random.key(5), window, fraction=0.5)
    for key in ("var_value", "p50_value", "max_value"):
        est = r.estimates[key]
        assert np.asarray(est.value).shape == (table.num_neighborhoods,)
        _check_interval(est, key)


def test_full_fraction_zero_width(pipe, window):
    """At fraction 1 every bound collapses: the fpc/rank-slack terms vanish
    (no sampling error left to bound)."""
    q = Query(aggs=ALL_AGGS)
    r = pipe.execute(q, jax.random.key(0), window, fraction=1.0)
    for k in ALL_KINDS:
        assert float(r.estimates[f"{k}_value"].moe) == 0.0, k


def test_bounds_shrink_with_fraction(pipe, window):
    """var and quantile CI widths shrink as the fraction grows."""
    q = Query(aggs=(AggSpec("var", "value"), AggSpec("p50", "value")))
    widths = {k: [] for k in ("var_value", "p50_value")}
    for f in (0.2, 0.5, 0.9):
        r = pipe.execute(q, jax.random.key(11), window, fraction=f)
        for k in widths:
            widths[k].append(float(r.estimates[k].moe))
    for k, ws in widths.items():
        assert ws[0] > ws[1] > ws[2] > 0, (k, ws)


def test_extrema_bounds_are_one_sided_and_contain_truth(pipe, window, table):
    """min/max: the sample extreme is one endpoint, the order-statistic +
    Cantelli bound the other; the full-population extreme lies inside
    whenever the bound is finite."""
    q = Query(aggs=(AggSpec("min", "value"), AggSpec("max", "value")))
    r = pipe.execute(q, jax.random.key(6), window, fraction=0.8)
    sidx = np.asarray(table.assign(jnp.asarray(window.lat), jnp.asarray(window.lon)))
    v = window.value[sidx < table.num_strata]
    mx = r.estimates["max_value"]
    assert float(mx.ci_low) == pytest.approx(float(mx.value))
    assert float(mx.ci_high) >= v.max() - 1e-5
    mn = r.estimates["min_value"]
    assert float(mn.ci_high) == pytest.approx(float(mn.value))
    assert float(mn.ci_low) <= v.min() + 1e-5


def test_replicates_zero_disables_bootstrap(pipe, window):
    """bootstrap_replicates=0 falls back to zero-width var/quantile
    intervals (the pre-bounds behavior) without touching the values."""
    q_on = Query(aggs=(AggSpec("var", "value"), AggSpec("p50", "value")))
    q_off = Query(
        aggs=(AggSpec("var", "value"), AggSpec("p50", "value")),
        bootstrap_replicates=0,
    )
    r_on = pipe.execute(q_on, jax.random.key(2), window, fraction=0.5)
    r_off = pipe.execute(q_off, jax.random.key(2), window, fraction=0.5)
    for k in ("var_value", "p50_value"):
        assert float(r_off.estimates[k].moe) == 0.0
        assert float(r_on.estimates[k].moe) > 0.0
        assert float(r_off.estimates[k].value) == pytest.approx(
            float(r_on.estimates[k].value), rel=1e-6
        )
    with pytest.raises(ValueError, match="bootstrap_replicates"):
        Query(aggs=(AggSpec("var", "value"),), bootstrap_replicates=-1)


# -- determinism ---------------------------------------------------------------


def test_bounds_deterministic_in_key(table, window):
    """Same PRNG key => bit-identical bounds, across pipeline instances;
    a different key moves the bootstrap intervals."""
    q = Query(aggs=(AggSpec("var", "value"), AggSpec("p99", "value")))
    r1 = EdgeCloudPipeline(table).execute(q, jax.random.key(9), window, 0.5)
    r2 = EdgeCloudPipeline(table).execute(q, jax.random.key(9), window, 0.5)
    r3 = EdgeCloudPipeline(table).execute(q, jax.random.key(10), window, 0.5)
    moved = False
    for k in ("var_value", "p99_value"):
        for field in ("ci_low", "ci_high", "moe", "relative_error"):
            a = np.asarray(getattr(r1.estimates[k], field))
            b = np.asarray(getattr(r2.estimates[k], field))
            np.testing.assert_array_equal(a, b, err_msg=f"{k}.{field}")
        moved |= float(r1.estimates[k].ci_low) != float(r3.estimates[k].ci_low)
    assert moved  # the key actually seeds the bootstrap


# -- transmission-mode / session parity ---------------------------------------


def test_preagg_raw_bounds_parity_through_session(pipe, window):
    """One session, the same aggregates registered in preagg and raw modes
    (two fusion groups, same step key => identical samples): the bounds
    agree — exactly for sketch quantiles (bin counts merge exactly), to fp
    tolerance for the moment-derived families."""
    aggs = (AggSpec("var", "value"), AggSpec("p50", "value"),
            AggSpec("max", "value"), AggSpec("mean", "value"))
    sess = StreamSession(pipe, initial_fraction=0.6)
    r_pre = sess.register(Query(aggs=aggs))
    r_raw = sess.register(Query(aggs=aggs, mode="raw"))
    step = sess.step(jax.random.key(21), window)
    pre = step.results[r_pre.qid].estimates
    raw = step.results[r_raw.qid].estimates
    for spec in aggs:
        for field in ("value", "ci_low", "ci_high", "relative_error"):
            a = np.asarray(getattr(pre[spec.key], field))
            b = np.asarray(getattr(raw[spec.key], field))
            np.testing.assert_allclose(
                a, b, rtol=1e-4, atol=1e-5, err_msg=f"{spec.key}.{field}"
            )
    np.testing.assert_array_equal(
        np.asarray(pre["p50_value"].ci_low), np.asarray(raw["p50_value"].ci_low)
    )
    # and the session path reproduces one-shot execute bit-for-bit
    ind = pipe.execute(Query(aggs=aggs), jax.random.key(21), window, 0.6)
    for spec in aggs:
        for field in ("ci_low", "ci_high"):
            np.testing.assert_array_equal(
                np.asarray(getattr(pre[spec.key], field)),
                np.asarray(getattr(ind.estimates[spec.key], field)),
                err_msg=f"{spec.key}.{field}",
            )


# -- bootstrap coverage (the property the subsystem exists for) ----------------


def _skewed_population(seed, n=3_000, s=4):
    """A skewed (lognormal-mixture) stream over a few strata."""
    rng = np.random.default_rng(seed)
    sidx = rng.integers(0, s, n)
    scale = 1.0 + 0.8 * sidx
    v = rng.lognormal(mean=1.0, sigma=0.6, size=n) * scale + 0.5
    return jnp.asarray(sidx, jnp.int32), jnp.asarray(v, jnp.float32), s


@settings(deadline=None, max_examples=2)
@given(seed=st.integers(0, 10_000))
def test_bootstrap_coverage_var_and_p50(seed):
    """Empirical coverage of the 95% bootstrap CIs stays within ±5pp of
    nominal for var and p50 on skewed synthetic streams.  Truth is the
    full-population plug-in variance / sketch quantile (the estimators'
    own fraction-1 values), so only *sampling* error is scored."""
    sidx, v, s = _skewed_population(seed)
    slots = s + 1
    full = jnp.ones(v.shape, bool)
    counts = jax.ops.segment_sum(jnp.ones_like(sidx), sidx, num_segments=slots)
    mom_full = estimators.sample_stats(v, sidx, full, slots, counts=counts)
    n_f, N_f = mom_full.n, mom_full.total
    s2_f = jnp.where(n_f > 1, mom_full.m2 / jnp.maximum(n_f - 1.0, 1.0), 0.0)
    active = (n_f > 0) & (N_f > 0)
    covered = jnp.sum(jnp.where(active, N_f, 0.0))
    ey2 = jnp.sum(jnp.where(active, N_f * (s2_f + mom_full.mean**2), 0.0))
    mean_full = jnp.sum(jnp.where(active, N_f * mom_full.mean, 0.0)) / covered
    var_true = float(ey2 / covered - mean_full**2)
    bins_full = estimators.SKETCH.accumulate(v, sidx, full, slots)
    p50_true = float(estimators.sketch_quantile(jnp.sum(bins_full.bins, axis=0), 0.5))

    fraction = 0.4
    replicates = 300

    @jax.jit
    def trial(key):
        # the finalize path for a var+p50 query: moments + sketch states,
        # union'd var channels, both interval hooks
        k_samp, k_var, k_q = jax.random.split(key, 3)
        res = sampling.edgesos(k_samp, sidx, slots, fraction)
        mom = estimators.sample_stats(v, sidx, res.mask, slots, counts=res.counts)
        sk = estimators.SKETCH.accumulate(v, sidx, res.mask, slots)
        s2 = jnp.where(mom.n > 1, mom.m2 / jnp.maximum(mom.n - 1.0, 1.0), 0.0)
        act = (mom.n > 0) & (mom.total > 0)
        cov = jnp.maximum(jnp.sum(jnp.where(act, mom.total, 0.0)), 1.0)
        ey2_t = jnp.sum(jnp.where(act, mom.total * (s2 + mom.mean**2), 0.0))
        m_t = jnp.sum(jnp.where(act, mom.total * mom.mean, 0.0)) / cov
        vhat = jnp.maximum(ey2_t / cov - m_t * m_t, 0.0)  # finalize's plug-in
        vlo, vhi = estimators.MOMENTS.interval(
            mom, "var", mom, confidence=0.95, key=k_var, replicates=replicates,
            sketch=sk, center=vhat,
        )
        qlo, qhi = estimators.SKETCH.interval(
            sk, "p50", mom, q=0.5, confidence=0.95, key=k_q, replicates=replicates
        )
        return vlo, vhi, qlo, qhi

    trials = 250
    keys = jax.random.split(jax.random.key(seed), trials)
    cover_var = cover_q = 0
    for t in range(trials):
        vlo, vhi, qlo, qhi = (float(x) for x in trial(keys[t]))
        cover_var += vlo <= var_true <= vhi
        cover_q += qlo <= p50_true <= qhi
    assert 0.90 <= cover_var / trials <= 1.0, f"var coverage {cover_var / trials}"
    assert 0.90 <= cover_q / trials <= 1.0, f"p50 coverage {cover_q / trials}"


# -- singleton guard + graceful SLO degradation --------------------------------


def test_singleton_stratum_reports_infinite_not_false_zero():
    """A window whose only sampled evidence is singletons must report an
    infinite relative error (previously: moe 0 / rel 0 — false certainty
    that collapses the QoS fraction to its floor)."""
    # two strata, one sampled tuple each, populations of 5
    sidx = jnp.asarray([0, 0, 0, 0, 0, 1, 1, 1, 1, 1], jnp.int32)
    v = jnp.asarray([1.0, 2, 3, 4, 5, 10, 20, 30, 40, 50], jnp.float32)
    mask = jnp.asarray([True] + [False] * 4 + [True] + [False] * 4)
    stats = estimators.sample_stats(v, sidx, mask, 3)
    est = estimators.estimate(stats)
    assert np.isinf(float(est.moe)) and np.isinf(float(est.relative_error))
    assert not np.isnan(float(est.moe))
    # the controller holds the fraction on the non-finite observation
    state = feedback.update(
        feedback.init_state(0.5), est.relative_error, jnp.int32(10), SLO()
    )
    assert np.isfinite(float(state.fraction)) and float(state.fraction) > 0.05
    vec = feedback.update_vector(
        feedback.init_vector_state([0.5]),
        jnp.asarray([float(est.relative_error)], jnp.float32),
        jnp.asarray([10.0], jnp.float32),
        feedback.stack_slos([SLO()]),
    )
    assert np.isfinite(float(vec.fraction[0]))


def test_lonely_stratum_borrows_spread_keeps_global_finite():
    """With identified strata present, a lonely singleton borrows their
    average s² instead of zero (moe grows, stays finite) — the survey
    lonely-PSU 'average' adjustment."""
    rng = np.random.default_rng(0)
    sidx = jnp.asarray(np.concatenate([np.zeros(100), np.ones(100), [2] * 10]), jnp.int32)
    v = jnp.asarray(rng.normal(50, 10, 210), jnp.float32)
    mask = np.ones(210, bool)
    mask[100:] = rng.random(110) < 0.5
    mask[200:] = False
    mask[200] = True  # stratum 2: singleton of population 10
    stats = estimators.sample_stats(v, sidx, jnp.asarray(mask), 4)
    assert float(stats.n[2]) == 1.0
    est = estimators.estimate(stats)
    assert np.isfinite(float(est.moe)) and float(est.moe) > 0
    # removing the singleton's population lowers the variance: the guard
    # added real (borrowed) spread for stratum 2 rather than zero
    no_lonely = estimators.sample_stats(
        v[:200], sidx[:200], jnp.asarray(mask[:200]), 4
    )
    assert float(est.moe) > float(estimators.estimate(no_lonely).moe)


def test_per_stratum_means_singleton_infinite():
    """per_stratum_means: an under-sampled singleton stratum reports an
    infinite half-width; fully-sampled and n>=2 strata stay finite."""
    sidx = jnp.asarray([0, 0, 1, 2], jnp.int32)
    v = jnp.asarray([1.0, 3.0, 7.0, 9.0], jnp.float32)
    mask = jnp.asarray([True, True, True, True])
    counts = jnp.asarray([2, 5, 1, 0])  # stratum 1 under-sampled singleton
    stats = estimators.sample_stats(v, sidx, mask, 4, counts=counts)
    _, moe_k = estimators.per_stratum_means(stats)
    moe = np.asarray(moe_k)
    assert np.isfinite(moe[0])  # n=2
    assert np.isinf(moe[1])  # n=1 < N=5: unidentified, was false-zero
    assert moe[2] == 0.0  # n=1 == N=1: exact (fpc)
    assert np.isinf(moe[3])  # unsampled
    assert not np.isnan(moe).any()


def test_empty_window_var_quantile_report_infinite_rel(pipe, table):
    """A window with no sampled evidence must report RE = inf for var and
    quantiles (like mean), not a false-perfect 0 that would collapse the
    newly var/quantile-driven QoS fraction when the stream goes quiet."""
    n = 512
    win = {
        "lat": jnp.zeros(n, jnp.float32),
        "lon": jnp.zeros(n, jnp.float32),
        "valid": jnp.zeros(n, bool),  # all invalid
        "value": jnp.ones(n, jnp.float32),
    }
    q = Query(aggs=(AggSpec("mean", "value"), AggSpec("var", "value"),
                    AggSpec("p99", "value")))
    r = pipe.execute(q, jax.random.key(0), win, fraction=0.5)
    for k in ("mean_value", "var_value", "p99_value"):
        assert np.isinf(float(r.estimates[k].relative_error)), k
    # the controller holds the fraction on the non-finite observation
    sess = StreamSession(pipe, initial_fraction=0.5)
    reg = sess.register(Query(aggs=(AggSpec("p99", "value"),)),
                        slo=SLO(target_relative_error=0.05, min_fraction=0.02))
    steps = sess.run([win, win], key=jax.random.key(1))
    assert [s.fractions[reg.qid] for s in steps] == pytest.approx([0.5, 0.5])


def test_replicates_zero_query_cannot_drive_qos(pipe, window):
    """bootstrap_replicates=0 disables var/quantile bounds, so such a query
    must not drive the controller (its zero-width RE=0 would collapse the
    fraction to the floor)."""
    q = Query(aggs=(AggSpec("var", "value"),), bootstrap_replicates=0)
    sess = StreamSession(pipe, initial_fraction=0.4)
    reg = sess.register(q, slo=SLO(target_relative_error=0.01, min_fraction=0.02))
    steps = sess.run([window, window], key=jax.random.key(3))
    assert [s.fractions[reg.qid] for s in steps] == [0.4, 0.4]
    assert reg.steps == 0


def test_session_var_query_drives_qos(pipe):
    """A var-only continuous query now carries an observed RE, so its SLO
    can adapt the fraction (previously var was treated as unbounded and the
    fraction froze)."""
    stream = shenzhen_taxi_stream(num_chunks=3, seed=9)
    panes = list(windows.count_windows(stream, 8_000))[:4]
    sess = StreamSession(pipe, initial_fraction=0.9)
    reg = sess.register(
        Query(aggs=(AggSpec("var", "value"),)),
        slo=SLO(target_relative_error=0.5, min_fraction=0.02),
    )
    sess.run(panes, key=jax.random.key(1))
    assert reg.steps == len(panes)
    assert reg.fraction < 0.9  # loose SLO released the fraction
    # and a quantile query advances its controller too
    sess2 = StreamSession(pipe, initial_fraction=0.7)
    reg2 = sess2.register(
        Query(aggs=(AggSpec("p50", "value"),)),
        slo=SLO(target_relative_error=0.2, min_fraction=0.02),
    )
    sess2.run(panes, key=jax.random.key(2))
    assert reg2.steps == len(panes)
    assert reg2.fraction < 0.7
