"""Continuous-query sessions: fusion exactness (property-tested), pane-based
sliding/hopping windows, vectorized per-query QoS, and drop accounting."""

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
    fuse,
    fusion_key,
    make_table,
    windows,
)
from repro.data.streams import shenzhen_taxi_stream

WINDOW = 16_000
PANE = 8_000


@pytest.fixture(scope="module")
def table():
    return make_table(*SHENZHEN_BBOX, precision=5)


@pytest.fixture(scope="module")
def pipe(table):
    return EdgeCloudPipeline(table, PipelineConfig(raw_capacity=WINDOW))


@pytest.fixture(scope="module")
def window():
    stream = shenzhen_taxi_stream(num_chunks=1, seed=0)
    return next(windows.count_windows(stream, WINDOW))


@pytest.fixture(scope="module")
def panes():
    stream = shenzhen_taxi_stream(num_chunks=3, seed=1)
    return list(windows.count_windows(stream, PANE))[:6]


# A workload of concurrent queries: indices 0-3 and 6 share the default
# sampling signature (one fusion group); 4 (raw mode) and 5 (bernoulli) each
# get their own group.  Distinct aggs/group-by/confidence fuse freely — the
# quantile query (6) rides the same pass, adding only its sketch states.
POOL = (
    Query(aggs=(AggSpec("mean", "value"), AggSpec("max", "value"))),
    Query(aggs=(AggSpec("sum", "value"), AggSpec("var", "value")), confidence=0.9),
    Query(
        aggs=(AggSpec("mean", "occupancy"), AggSpec("count", "value")),
        group_by="neighborhood",
    ),
    Query(aggs=(AggSpec("min", "occupancy"),), group_by="stratum"),
    Query(aggs=(AggSpec("mean", "value"),), mode="raw"),
    Query(aggs=(AggSpec("mean", "value"), AggSpec("count", "value")), method="bernoulli"),
    Query(aggs=(AggSpec("p99", "value"), AggSpec("p50", "occupancy"))),
)


# -- fusion correctness -------------------------------------------------------


@settings(deadline=None, max_examples=10)
@given(mask=st.integers(min_value=1, max_value=2 ** len(POOL) - 1))
def test_fusion_matches_independent_execute(pipe, window, mask):
    """For any registered QuerySet, session estimates are elementwise-
    identical (same PRNG key) to executing each query independently — in
    preagg and raw modes, grouped and global, across sampling methods."""
    queries = [q for i, q in enumerate(POOL) if mask >> i & 1]
    sess = StreamSession(pipe, initial_fraction=0.6)
    regs = [sess.register(q) for q in queries]
    key = jax.random.key(11)
    step = sess.step(key, window)
    for q, reg in zip(queries, regs):
        ind = pipe.execute(q, key, window, 0.6)
        got = step.results[reg.qid]
        for spec in q.aggs:
            for field in ("value", "moe", "ci_low", "ci_high", "n", "population"):
                a = np.asarray(getattr(ind.estimates[spec.key], field))
                b = np.asarray(getattr(got.estimates[spec.key], field))
                np.testing.assert_array_equal(a, b, err_msg=f"{spec.key}.{field}")
        assert int(got.n_sampled) == int(ind.n_sampled)
        assert int(got.n_valid) == int(ind.n_valid)
        assert int(got.n_overflow) == int(ind.n_overflow)


def test_fusion_shares_one_pass_and_uplink(pipe, window):
    """Signature-compatible queries form ONE fusion group: a single pass
    whose uplink payload is far below the N independent payloads."""
    queries = POOL[:4]
    sess = StreamSession(pipe, initial_fraction=0.6)
    for q in queries:
        sess.register(q)
    assert len(sess._groups()) == 1
    key = jax.random.key(0)
    step = sess.step(key, window)
    independent = sum(
        int(pipe.execute(q, key, window, 0.6).comm_bytes) for q in queries
    )
    assert step.comm_bytes < independent
    # the full pool spans three sampling signatures -> three groups
    sess_all = StreamSession(pipe, initial_fraction=0.6)
    for q in POOL:
        sess_all.register(q)
    assert len(sess_all._groups()) == 3


def test_fuse_unions_and_rejects_mismatch(pipe, table):
    plans = [pipe.plan(q) for q in POOL[:4]]
    fused = fuse(plans)
    assert fused.columns == ("value", "occupancy")
    assert set(fused.extrema_columns) == {"value", "occupancy"}
    assert fused.shared.query.mode == "preagg"
    # accumulator-field union covers every member's finalize inputs
    acc = dict(fused.shared.accumulators)
    for p in plans:
        for k, fields in p.accumulators:
            assert set(fields) <= set(acc[k])
    with pytest.raises(ValueError, match="sampling signatures"):
        fuse([pipe.plan(POOL[0]), pipe.plan(POOL[5])])
    assert fusion_key(pipe.plan(POOL[0])) == fusion_key(pipe.plan(POOL[1]))
    assert fusion_key(pipe.plan(POOL[0])) != fusion_key(pipe.plan(POOL[4]))


def test_register_unregister_lifecycle(pipe, window):
    sess = StreamSession(pipe, initial_fraction=0.5)
    r1 = sess.register(POOL[0])
    r2 = sess.register(POOL[2])
    step = sess.step(jax.random.key(0), window)
    assert set(step.results) == {r1.qid, r2.qid}
    sess.unregister(r1)
    step = sess.step(jax.random.key(1), window)
    assert set(step.results) == {r2.qid}
    sess.unregister(r2)
    with pytest.raises(ValueError, match="no registered queries"):
        sess.step(jax.random.key(2), window)


# -- pane-based sliding / hopping windows -------------------------------------


def _concat(panes):
    cat = {
        f: np.concatenate([getattr(p, f) for p in panes])
        for f in ("sensor_id", "timestamp", "lat", "lon", "value", "valid")
    }
    extra = {k: np.concatenate([p.extra[k] for p in panes]) for k in panes[0].extra}
    return windows.WindowBatch(**cat, extra=extra)


def test_sliding_window_equals_tumbling_span(pipe, panes):
    """Pane-merge exactness: at full fraction a sliding window's estimate
    equals the tumbling estimate over the same tuple span."""
    q = Query(
        aggs=(AggSpec("mean", "value"), AggSpec("max", "value"), AggSpec("count", "value"))
    )
    sess = StreamSession(pipe, initial_fraction=1.0)
    reg = sess.register(q, window=WindowSpec("sliding", size=3))
    history = sess.run(panes[:3], key=jax.random.key(0))
    assert all(reg.qid in s.results for s in history)  # sliding emits every pane
    res = history[-1].results[reg.qid]
    ind = pipe.execute(q, jax.random.key(9), _concat(panes[:3]), 1.0)
    for spec in q.aggs:
        a = float(np.asarray(ind.estimates[spec.key].value))
        b = float(np.asarray(res.estimates[spec.key].value))
        assert b == pytest.approx(a, rel=1e-5), spec.key
    assert int(res.n_valid) == int(ind.n_valid)
    # partial windows at the start cover only the panes seen so far
    assert int(history[0].results[reg.qid].n_valid) == PANE


def test_sliding_quantile_equals_tumbling_span(pipe, panes):
    """Quantile panes merge exactly: summed sketch bins across a sliding
    window's panes equal one accumulation over the concatenated span, so the
    sliding p50/p99 match the one-shot execute bit-for-bit at full fraction."""
    q = Query(aggs=(AggSpec("p50", "value"), AggSpec("p99", "value")))
    sess = StreamSession(pipe, initial_fraction=1.0)
    reg = sess.register(q, window=WindowSpec("sliding", size=3))
    history = sess.run(panes[:3], key=jax.random.key(0))
    res = history[-1].results[reg.qid]
    ind = pipe.execute(q, jax.random.key(9), _concat(panes[:3]), 1.0)
    for key in ("p50_value", "p99_value"):
        a = float(np.asarray(ind.estimates[key].value))
        b = float(np.asarray(res.estimates[key].value))
        assert b == pytest.approx(a, rel=1e-6), key


def test_vectorized_pane_merge_matches_sequential(rng):
    """merge_column_stats_panes == folding merge_column_stats, exactly for
    count/extrema and to fp tolerance for the moments."""
    parts = []
    for _ in range(4):
        sidx = jnp.asarray(rng.integers(0, 12, 3_000), jnp.int32)
        vals = jnp.asarray(rng.normal(30, 9, 3_000), jnp.float32)
        mask = jnp.asarray(rng.random(3_000) < 0.5)
        parts.append(estimators.column_stats(vals, sidx, mask, 13))
    seq = estimators.merge_all_columns(parts)
    vec = estimators.merge_column_stats_panes(estimators.stack_column_stats(parts))
    np.testing.assert_array_equal(np.asarray(vec.n), np.asarray(seq.n))
    np.testing.assert_array_equal(np.asarray(vec.total), np.asarray(seq.total))
    np.testing.assert_array_equal(np.asarray(vec.min), np.asarray(seq.min))
    np.testing.assert_array_equal(np.asarray(vec.max), np.asarray(seq.max))
    np.testing.assert_allclose(np.asarray(vec.wsum), np.asarray(seq.wsum), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(vec.mean), np.asarray(seq.mean), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(vec.m2), np.asarray(seq.m2), rtol=2e-4, atol=2e-2)


def test_hopping_emission_cadence(pipe, panes):
    """size=3 stride=2: emit on panes 2,4,6; each window spans the last
    min(3, seen) panes."""
    q = Query(aggs=(AggSpec("mean", "value"),))
    sess = StreamSession(pipe, initial_fraction=0.5)
    reg = sess.register(q, window=WindowSpec("hopping", size=3, stride=2))
    history = sess.run(panes, key=jax.random.key(4))
    assert [reg.qid in s.results for s in history] == [False, True] * 3
    spans = [2, 3, 3]  # panes covered at emits 2, 4, 6
    emitted = [s.results[reg.qid] for s in history if reg.qid in s.results]
    for res, span in zip(emitted, spans):
        assert int(res.n_valid) == span * PANE


def test_tumbling_multi_pane(pipe, panes):
    q = Query(aggs=(AggSpec("mean", "value"),))
    sess = StreamSession(pipe, initial_fraction=0.5)
    reg = sess.register(q, window=WindowSpec("tumbling", size=2))
    history = sess.run(panes[:4], key=jax.random.key(5))
    assert [reg.qid in s.results for s in history] == [False, True, False, True]
    for s in history:
        if reg.qid in s.results:
            assert int(s.results[reg.qid].n_valid) == 2 * PANE


def test_window_spec_validation():
    assert WindowSpec().stride == 1  # tumbling 1-pane default
    assert WindowSpec("tumbling", size=3).stride == 3
    assert WindowSpec("sliding", size=4).stride == 1
    with pytest.raises(ValueError, match="kind"):
        WindowSpec("session", size=2)
    with pytest.raises(ValueError, match="size"):
        WindowSpec(size=0)
    with pytest.raises(ValueError, match="stride"):
        WindowSpec("hopping", size=4)  # hopping needs explicit stride
    with pytest.raises(ValueError, match="stride == size"):
        WindowSpec("tumbling", size=3, stride=1)
    with pytest.raises(ValueError, match="stride == 1"):
        WindowSpec("sliding", size=3, stride=2)
    with pytest.raises(ValueError, match="skip panes"):
        WindowSpec("hopping", size=2, stride=5)


def test_query_method_validation():
    """Unknown Query.method fails at construction with the allowed set, not
    deep inside sampling.edgesos at trace time."""
    with pytest.raises(ValueError, match="srs|bernoulli|neyman"):
        Query(aggs=(AggSpec("mean", "value"),), method="reservoir")


# -- vectorized per-query QoS -------------------------------------------------


def test_per_query_fractions_diverge_and_refine_to_own_fraction(pipe, panes):
    """One fraction per registered query: a tight-SLO query's fraction stays
    above a loose-SLO query's, and once the fractions diverge the shared
    pass *refines* each member to its own fraction (nested subsampling) —
    the loose query's realized sample shrinks to what its controller asked
    for instead of free-riding the group max."""
    q_loose = Query(aggs=(AggSpec("mean", "value"),))
    q_tight = Query(aggs=(AggSpec("mean", "value", name="tight_mean"),))
    sess = StreamSession(pipe, initial_fraction=0.6)
    r_loose = sess.register(q_loose, slo=SLO(target_relative_error=0.5, min_fraction=0.02))
    r_tight = sess.register(q_tight, slo=SLO(target_relative_error=0.001))
    history = sess.run(panes[:4], key=jax.random.key(6))
    assert r_loose.fraction < 0.6  # loose SLO released its fraction
    assert r_tight.fraction > r_loose.fraction
    last = history[-1]
    n_loose = int(last.results[r_loose.qid].n_sampled)
    n_tight = int(last.results[r_tight.qid].n_sampled)
    n_valid = int(last.results[r_tight.qid].n_valid)
    # still ONE fusion group (one pass per pane), but per-member samples
    assert len(sess._groups()) == 1
    assert n_loose < n_tight
    # each member's realized sample tracks its own controller fraction (the
    # fractions recorded in the step are post-update; compare against a
    # loose proportional band)
    assert n_loose <= 0.5 * n_tight
    assert n_tight == pytest.approx(n_valid * max(r.fraction for r in (r_loose, r_tight)), rel=0.1)
    # nested: the loose member's downstream volume shrank accordingly
    assert r_loose.downstream_tuples < r_tight.downstream_tuples


def test_latency_budget_caps_session_fraction(pipe, panes):
    """SLO.max_downstream_tuples caps f·N through the vectorized controller:
    even an impossible error target cannot push the fraction past cap/N."""
    q = Query(aggs=(AggSpec("mean", "value"),))
    sess = StreamSession(pipe, initial_fraction=0.9)
    reg = sess.register(
        q, slo=SLO(target_relative_error=1e-5, max_downstream_tuples=1_000, min_fraction=0.01)
    )
    sess.run(panes[:2], key=jax.random.key(7))
    assert reg.fraction <= 1_000 / PANE + 1e-6


def test_update_vector_matches_scalar_and_masks_inactive():
    """The vectorized controller is elementwise the scalar controller; the
    latency-budget cap applies per entry and inactive entries are frozen."""
    slos = [
        SLO(target_relative_error=0.1),
        SLO(target_relative_error=0.01, max_downstream_tuples=2_000),
        SLO(target_relative_error=0.05),
    ]
    state = feedback.init_vector_state([0.5, 0.5, 0.5])
    re = jnp.asarray([0.02, 0.2, 0.05], jnp.float32)
    n = jnp.asarray([10_000.0, 20_000.0, 10_000.0], jnp.float32)
    new = feedback.update_vector(
        state, re, n, feedback.stack_slos(slos), jnp.asarray([True, True, False])
    )
    # entry 0 == scalar controller on the same observation
    s0 = feedback.update(
        feedback.init_state(0.5), jnp.float32(0.02), jnp.int32(10_000), slos[0]
    )
    assert float(new.fraction[0]) == pytest.approx(float(s0.fraction), abs=1e-7)
    # entry 1: analytic raise capped by the downstream budget 2000/20000
    assert float(new.fraction[1]) == pytest.approx(0.1, abs=1e-6)
    # entry 2 inactive: untouched
    assert float(new.fraction[2]) == 0.5
    assert int(new.steps[2]) == 0 and int(new.steps[0]) == 1


def test_session_no_error_bounded_agg_holds_fraction(pipe, panes):
    """A registered query with only point-estimate aggregates cannot drive
    QoS even with an SLO attached — its fraction must stay fixed."""
    q = Query(aggs=(AggSpec("count", "value"), AggSpec("max", "value")))
    sess = StreamSession(pipe, initial_fraction=0.4)
    reg = sess.register(q, slo=SLO(target_relative_error=0.01))
    history = sess.run(panes[:3], key=jax.random.key(8))
    assert [s.fractions[reg.qid] for s in history] == [0.4] * 3
    assert reg.steps == 0


def test_session_all_groups_empty_roi_holds_fraction(pipe, panes):
    """Grouped query whose ROI covers no data: every group's RE is inf and
    the controller holds the fraction (the all-infinite branch)."""
    q = Query(
        aggs=(AggSpec("mean", "value"),),
        group_by="neighborhood",
        roi=((0.0, 1.0), (0.0, 1.0)),  # far outside the city
    )
    sess = StreamSession(pipe, initial_fraction=0.5)
    reg = sess.register(q, slo=SLO(target_relative_error=0.1))
    history = sess.run(panes[:2], key=jax.random.key(9))
    assert [s.fractions[reg.qid] for s in history] == pytest.approx([0.5, 0.5])


# -- drop accounting ----------------------------------------------------------


def test_time_pane_drop_accounting(pipe):
    """Bounded-capacity time panes surface their shed-tuple count, and the
    session accumulates it into its diagnostics."""
    stream = shenzhen_taxi_stream(num_chunks=3, chunk_size=5_000, seed=3)
    panes = list(windows.pane_windows(stream, pane_seconds=60.0, capacity=2_000))
    assert panes and all(p.capacity == 2_000 for p in panes)
    assert sum(p.n_dropped for p in panes) > 0  # 60s of stream >> 2000 tuples
    sess = StreamSession(pipe, initial_fraction=0.5)
    sess.register(Query(aggs=(AggSpec("mean", "value"),)))
    history = sess.run(panes, key=jax.random.key(1))
    assert [s.n_dropped for s in history] == [p.n_dropped for p in panes]
    assert sess.total_dropped == sum(p.n_dropped for p in panes)


def test_count_windows_never_drop():
    stream = shenzhen_taxi_stream(num_chunks=1, chunk_size=6_000, seed=0)
    for w in windows.count_windows(stream, 3_000):
        assert w.n_dropped == 0


def test_pane_windows_validation():
    with pytest.raises(ValueError, match="exactly one"):
        windows.pane_windows(iter(()), pane_tuples=10, pane_seconds=1.0)
    with pytest.raises(ValueError, match="capacity"):
        windows.pane_windows(iter(()), pane_seconds=1.0)
