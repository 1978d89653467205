"""Multi-tenant serving: incremental fusion planning vs full replanning
(property-tested), batched signature-vmapped finalize parity, the
zero-recompile churn contract, the planner audit trail, and the
``emit_all`` serving read."""

import numpy as np
import pytest

import jax

from hypothesis import given, settings, strategies as st

from repro.core import (
    SHENZHEN_BBOX,
    SLO,
    AggSpec,
    EdgeCloudPipeline,
    PipelineConfig,
    Query,
    StreamSession,
    WindowSpec,
    make_table,
    windows,
)
from repro.core.runtime import StreamRuntime
from repro.data.streams import shenzhen_taxi_stream

PANE = 4_000

ROI_SOUTH = ((22.45, 22.66), (113.76, 114.64))
ROI_NORTH = ((22.64, 22.86), (113.76, 114.64))

# tenants spanning several sampling signatures (srs x 2 ROIs, bernoulli,
# raw) while many share *finalize* signatures (same aggs/confidence/columns,
# differing only in ROI/method — exactly what the batched emit exploits)
POOL = (
    Query(aggs=(AggSpec("mean", "value"),), roi=ROI_SOUTH, bootstrap_replicates=0),
    Query(aggs=(AggSpec("mean", "value"),), roi=ROI_NORTH, bootstrap_replicates=0),
    Query(aggs=(AggSpec("mean", "value"),), method="bernoulli", bootstrap_replicates=0),
    Query(aggs=(AggSpec("mean", "occupancy"),), roi=ROI_SOUTH, bootstrap_replicates=0),
    Query(aggs=(AggSpec("sum", "value"), AggSpec("var", "value")), confidence=0.9),
    Query(aggs=(AggSpec("mean", "value"), AggSpec("p50", "value"))),
)


@pytest.fixture(scope="module")
def table():
    return make_table(*SHENZHEN_BBOX, precision=4)


@pytest.fixture(scope="module")
def pipe(table):
    return EdgeCloudPipeline(table, PipelineConfig())


@pytest.fixture(scope="module")
def panes():
    stream = shenzhen_taxi_stream(num_chunks=1, seed=3)
    return list(windows.count_windows(stream, PANE))[:3]


def _partition(sess):
    """fusion_key -> ordered member queries, plus the fused carrier plans."""
    groups = {g.key: [m.query for m in g.members] for g in sess._fusion_groups.values()}
    fused = {g.key: g.fused_plan() for g in sess._fusion_groups.values()}
    return groups, fused


def _estimates_np(res):
    return {
        k: {
            f: np.asarray(getattr(est, f))
            for f in ("value", "moe", "ci_low", "ci_high", "n", "population")
        }
        for k, est in res.estimates.items()
    }


# -- incremental planning == full replanning ---------------------------------


@settings(deadline=None, max_examples=10)
@given(
    seed=st.integers(min_value=0, max_value=10_000),
    n_ops=st.integers(min_value=1, max_value=24),
)
def test_incremental_refusion_matches_full_replanning(pipe, panes, seed, n_ops):
    """After ANY register/unregister sequence, the incrementally maintained
    fusion partition equals a fresh session's full replanning over the
    survivors (same groups, same member order, equal fused plans), and
    subsequent stepped estimates are bit-identical."""
    rng = np.random.default_rng(seed)
    inc = StreamSession(pipe, initial_fraction=0.7)
    live = []
    for _ in range(n_ops):
        if live and rng.random() < 0.4:
            inc.unregister(live.pop(int(rng.integers(len(live)))))
        else:
            live.append(inc.register(POOL[int(rng.integers(len(POOL)))]))
    fresh = StreamSession(pipe, initial_fraction=0.7)
    mirror = [fresh.register(reg.query) for reg in inc.registrations]

    inc_groups, inc_fused = _partition(inc)
    fresh_groups, fresh_fused = _partition(fresh)
    assert inc_groups == fresh_groups
    assert inc_fused == fresh_fused
    assert len(inc.plan_log) == n_ops

    if not live:
        return
    key = jax.random.key(seed)
    for pane in panes[:2]:
        key, sub = jax.random.split(key)
        step_inc = inc.step(sub, pane)
        step_fresh = fresh.step(sub, pane)
        for reg, ref in zip(inc.registrations, mirror):
            a = _estimates_np(step_inc.results[reg.qid])
            b = _estimates_np(step_fresh.results[ref.qid])
            assert a.keys() == b.keys()
            for k in a:
                for f in a[k]:
                    np.testing.assert_array_equal(
                        a[k][f], b[k][f], err_msg=f"{k}.{f} seed={seed}"
                    )


# -- batched finalize parity --------------------------------------------------


def test_batched_finalize_matches_per_query_loop(pipe, panes):
    """The signature-vmapped batched emit returns the same estimates,
    fractions, and controller state as the per-query finalize loop — across
    tumbling and sliding windows, grouped/quantile aggs, and QoS."""
    workload = [
        (POOL[0], WindowSpec()),
        (POOL[1], WindowSpec()),
        (POOL[3], WindowSpec()),
        (Query(aggs=(AggSpec("mean", "value"),), roi=ROI_NORTH, bootstrap_replicates=0),
         WindowSpec("sliding", size=2)),
        (Query(aggs=(AggSpec("mean", "occupancy"),), roi=ROI_NORTH, bootstrap_replicates=0),
         WindowSpec("sliding", size=2)),
        (Query(aggs=(AggSpec("mean", "value"), AggSpec("p99", "value"))), WindowSpec()),
        (Query(aggs=(AggSpec("mean", "value"), AggSpec("p99", "value")),
               group_by="neighborhood"), WindowSpec()),
    ]
    sessions = (
        StreamSession(pipe, initial_fraction=0.7, batched_finalize=True),
        StreamSession(pipe, initial_fraction=0.7, batched_finalize=False),
    )
    regs = []
    for sess in sessions:
        regs.append(
            [sess.register(q, window=w, slo=SLO(target_relative_error=0.05))
             for q, w in workload]
        )
    key = jax.random.key(5)
    for pane in panes:
        key, sub = jax.random.split(key)
        steps = [sess.step(sub, pane) for sess in sessions]
        assert set(steps[0].results) == {
            regs[0][i].qid for i, r in enumerate(regs[1]) if regs[1][i].qid in steps[1].results
        }
        for r_a, r_b in zip(regs[0], regs[1]):
            if r_a.qid not in steps[0].results:
                continue
            a = _estimates_np(steps[0].results[r_a.qid])
            b = _estimates_np(steps[1].results[r_b.qid])
            for k in b:
                for f in b[k]:
                    np.testing.assert_allclose(
                        a[k][f], b[k][f], rtol=1e-5, atol=1e-6,
                        err_msg=f"batched vs loop: {k}.{f}",
                    )
    # one vectorized controller update per pane must agree with the
    # singleton-fed update: fractions and EMAs track identically
    for r_a, r_b in zip(regs[0], regs[1]):
        assert np.isclose(r_a.fraction, r_b.fraction, rtol=1e-5)
        assert np.isclose(r_a.re_ema, r_b.re_ema, rtol=1e-5)
        assert r_a.steps == r_b.steps


def test_emit_all_is_batched_and_lazy(pipe, panes):
    """``emit_all`` serves every registration's current window through the
    batched path without advancing panes, and materializes per-tenant
    views only on access."""
    sess = StreamSession(pipe, initial_fraction=0.7)
    regs = [sess.register(POOL[i % 4]) for i in range(8)]
    key = jax.random.key(9)
    step = sess.step(key, panes[0])
    before = sess.pane_index
    out = sess.emit_all(key)
    assert sess.pane_index == before
    assert out._batches, "8 tenants over shared signatures must batch"
    assert set(out) == {r.qid for r in regs}
    # same window, same key -> the serving read reproduces the step's emit
    for reg in regs:
        a = _estimates_np(out[reg.qid])
        b = _estimates_np(step.results[reg.qid])
        for k in a:
            np.testing.assert_allclose(a[k]["value"], b[k]["value"], rtol=1e-6)


# -- compiled-program cache / churn ------------------------------------------


def test_register_churn_performs_zero_recompiles(pipe, panes):
    """A register/unregister storm over structurally-seen queries hits every
    pipeline cache family: compile_count stays flat, hits grow."""
    sess = StreamSession(pipe, initial_fraction=0.7)
    for q in POOL[:4]:
        sess.register(q)
    key = jax.random.key(1)
    sess.step(key, panes[0])  # warm every family for this workload
    sess.emit_all(key)
    before = pipe.cache_snapshot()
    for _ in range(5):
        reg = sess.register(POOL[0])
        sess.unregister(reg)
        sess.register(POOL[2])
        sess.unregister(sess.registrations[-1])
        sess.step(key, panes[0])
        sess.emit_all(key)
    after = pipe.cache_snapshot()
    assert after["compile_count"] == before["compile_count"]
    for family in ("plan", "pass", "finalize"):
        assert after["families"][family]["misses"] == before["families"][family]["misses"]
        assert after["families"][family]["hits"] > before["families"][family]["hits"]


def test_runtime_stats_expose_compile_cache(pipe):
    """RuntimeStats carries the pipeline cache counters (the churn gate's
    observability surface)."""
    sess = StreamSession(pipe, initial_fraction=0.7)
    sess.register(POOL[0])
    stats = StreamRuntime(sess, key=jax.random.key(0)).stats()
    assert stats.compile_cache["compile_count"] == pipe.compile_count
    assert set(stats.compile_cache["families"]) == {
        "plan", "exec", "pass", "refined_pass", "finalize"
    }


# -- planner audit trail ------------------------------------------------------


def test_plan_log_records_admission_decisions(pipe):
    sess = StreamSession(pipe)
    a = sess.register(POOL[0])  # new srs/ROI_SOUTH group
    b = sess.register(POOL[3])  # same sampling signature -> joins
    c = sess.register(POOL[2])  # bernoulli -> new group
    sess.unregister(b)
    sess.unregister(c)
    outcomes = [(d.action, d.outcome, d.group_size) for d in sess.plan_log]
    assert outcomes == [
        ("register", "new-group", 1),
        ("register", "joined", 2),
        ("register", "new-group", 1),
        ("unregister", "left", 1),
        ("unregister", "dissolved", 0),
    ]
    assert [d.seq for d in sess.plan_log] == list(range(5))
    assert sess.plan_log[0].qid == a.qid
    assert sess.plan_log[1].group_key == sess.plan_log[0].group_key
    assert sess.plan_log[2].group_key != sess.plan_log[0].group_key
