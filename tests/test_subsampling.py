"""Per-query fraction refinement + cross-signature Bernoulli fusion.

The session layer's nested Horvitz-Thompson subsampling contract,
property-tested:

  * a refined member of a fused preagg group is **elementwise-identical**
    to running its query through ``pipeline.execute`` independently at its
    *own* fraction (the strongest form of "unbiased vs. independent
    execute": the nested subsample IS the independent draw);
  * nested masks are genuine subsets (a lower-fraction member's sample is
    contained in a higher-fraction member's);
  * refined estimates are unbiased against the full-population truth;
  * reported confidence intervals widen monotonically as the refined
    fraction shrinks (the ``bounds.py`` intervals see the *effective*
    fraction through the realized per-stratum ``n_k``);
  * differing-ROI Bernoulli queries fuse into ONE preagg pass
    (cross-signature fusion), while raw mode keeps them separate.
"""

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
    StreamSession,
    make_table,
    query as aqp,
    sampling,
    windows,
)
from repro.data.streams import shenzhen_taxi_stream

WINDOW = 8_000

ROI_SOUTH = ((22.45, 22.65), (113.76, 114.64))
ROI_NORTH = ((22.60, 22.86), (113.76, 114.64))  # overlaps ROI_SOUTH

EXACT_FIELDS = ("value", "moe", "ci_low", "ci_high", "relative_error", "n", "population")


def _f32(x: float) -> float:
    """Nearest float32: hypothesis needs ``width=32`` bounds it can represent."""
    return float(np.float32(x))


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


def _assert_estimates_equal(ind, got, aggs):
    for spec in aggs:
        for field in EXACT_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(ind.estimates[spec.key], field)),
                np.asarray(getattr(got.estimates[spec.key], field)),
                err_msg=f"{spec.key}.{field}",
            )


# -- refined members == independent execute at their own fraction -------------


@settings(deadline=None, max_examples=8)
@given(
    f_lo=st.floats(min_value=_f32(0.1), max_value=0.5, width=32),
    f_hi=st.floats(min_value=_f32(0.55), max_value=1.0, width=32),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_srs_refined_members_match_independent_execute(pipe, window, f_lo, f_hi, seed):
    """A divergent-fraction SRS fusion group refines each member to its own
    fraction, and the refined estimates (every field, including the bounds)
    are bit-identical to independent ``execute`` at that fraction — nested
    subsampling via shared ranks draws *the same sample* the member's own
    pass would."""
    q_lo = Query(aggs=(AggSpec("mean", "value"), AggSpec("var", "value")))
    q_hi = Query(
        aggs=(AggSpec("mean", "occupancy", name="occ"), AggSpec("p50", "value", name="med"))
    )
    sess = StreamSession(pipe)
    r_lo = sess.register(q_lo, initial_fraction=f_lo)
    r_hi = sess.register(q_hi, initial_fraction=f_hi)
    assert len(sess._groups()) == 1
    key = jax.random.key(seed)
    step = sess.step(key, window)
    for q, reg, f in ((q_lo, r_lo, f_lo), (q_hi, r_hi, f_hi)):
        ind = pipe.execute(q, key, window, f)
        got = step.results[reg.qid]
        _assert_estimates_equal(ind, got, q.aggs)
        assert int(got.n_sampled) == int(ind.n_sampled)
        assert int(got.n_valid) == int(ind.n_valid)
        assert int(got.n_overflow) == int(ind.n_overflow)


@settings(deadline=None, max_examples=8)
@given(
    f_a=st.floats(min_value=_f32(0.1), max_value=_f32(0.9), width=32),
    f_b=st.floats(min_value=_f32(0.1), max_value=_f32(0.9), width=32),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_bernoulli_cross_roi_members_match_independent_execute(pipe, window, f_a, f_b, seed):
    """Differing-ROI Bernoulli queries share ONE preagg pass; each member's
    per-query accumulation mask reproduces its independent ROI-filtered
    draw bit-for-bit (uniforms are stratum- and ROI-oblivious), at each
    member's own fraction."""
    q_a = Query(aggs=(AggSpec("mean", "value"), AggSpec("count", "value")),
                method="bernoulli", roi=ROI_SOUTH)
    q_b = Query(aggs=(AggSpec("sum", "occupancy", name="s_occ"),),
                method="bernoulli", roi=ROI_NORTH)
    sess = StreamSession(pipe)
    r_a = sess.register(q_a, initial_fraction=f_a)
    r_b = sess.register(q_b, initial_fraction=f_b)
    assert len(sess._groups()) == 1  # cross-signature fusion: one group
    key = jax.random.key(seed)
    step = sess.step(key, window)
    assert sess.total_passes == 1  # ... and one edge pass for both ROIs
    for q, reg, f in ((q_a, r_a, f_a), (q_b, r_b, f_b)):
        ind = pipe.execute(q, key, window, f)
        got = step.results[reg.qid]
        _assert_estimates_equal(ind, got, q.aggs)
        assert int(got.n_sampled) == int(ind.n_sampled)
        assert int(got.n_overflow) == int(ind.n_overflow)


def test_neyman_groups_never_refine(pipe):
    """Neyman members must stay on the shared group-max pass: refined
    thinning would silently swap the variance-optimal allocation for a
    proportional one (the refined program refuses the method outright)."""
    from repro.core import pipeline as pipeline_mod

    q1 = Query(aggs=(AggSpec("mean", "value"),), method="neyman")
    q2 = Query(aggs=(AggSpec("mean", "value", name="b"),), method="neyman")
    fused = aqp.fuse([pipe.plan(q1), pipe.plan(q2)])
    assert not StreamSession._refines(fused, [0.2, 0.8])
    with pytest.raises(NotImplementedError, match="neyman"):
        pipeline_mod._fused_edge_program(
            fused, pipe.table, pipe.config, jax.random.key(0),
            None, None, {}, None, None,
        )


def test_bernoulli_raw_mode_keeps_separate_groups(pipe):
    """Raw mode ships one ROI-filtered compact buffer, so differing-ROI
    Bernoulli queries must NOT fuse there (the ROI stays in the raw fusion
    key)."""
    q_a = Query(aggs=(AggSpec("mean", "value"),), method="bernoulli", roi=ROI_SOUTH, mode="raw")
    q_b = Query(aggs=(AggSpec("mean", "value"),), method="bernoulli", roi=ROI_NORTH, mode="raw")
    sess = StreamSession(pipe)
    sess.register(q_a)
    sess.register(q_b)
    assert len(sess._groups()) == 2
    # ... while the preagg twins fuse
    p_a = pipe.plan(Query(aggs=(AggSpec("mean", "value"),), method="bernoulli", roi=ROI_SOUTH))
    p_b = pipe.plan(Query(aggs=(AggSpec("mean", "value"),), method="bernoulli", roi=ROI_NORTH))
    assert aqp.fusion_key(p_a) == aqp.fusion_key(p_b)
    fused = aqp.fuse([p_a, p_b])
    assert fused.cross_roi and fused.shared.query.roi is None


# -- nesting ------------------------------------------------------------------


@settings(deadline=None, max_examples=8)
@given(
    f_lo=st.floats(min_value=_f32(0.05), max_value=_f32(0.95), width=32),
    f_hi=st.floats(min_value=_f32(0.05), max_value=_f32(0.95), width=32),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_nested_masks_are_subsets(f_lo, f_hi, seed):
    """The shared-randomness masks are nested in the fraction: the
    lower-fraction sample is contained in the higher-fraction one, for both
    SRS ranks and Bernoulli uniforms — the property that lets one edge pass
    serve every member fraction."""
    f_lo, f_hi = sorted((f_lo, f_hi))
    rng = np.random.default_rng(seed)
    sidx = jnp.asarray(rng.integers(0, 12, 4_000), jnp.int32)
    key = jax.random.key(seed)
    ranks, counts = sampling.srs_ranks(key, sidx, 13)
    masks = []
    for f in (f_lo, f_hi):
        n_k = sampling.allocate_proportional(counts, f)
        masks.append(np.asarray(ranks < n_k[sidx]))
    assert not np.any(masks[0] & ~masks[1])  # lo ⊆ hi
    # and each mask is exactly the srs_sample draw at that fraction
    for f, m in zip((f_lo, f_hi), masks):
        n_k = sampling.allocate_proportional(counts, f)
        ref = sampling.srs_sample(key, sidx, 13, n_k, counts)
        np.testing.assert_array_equal(m, np.asarray(ref.mask))
    u = jax.random.uniform(key, sidx.shape)
    assert not np.any(np.asarray((u < f_lo) & ~(u < f_hi)))


def test_session_refined_samples_are_nested(pipe, window):
    """End-to-end nesting: the refined low-fraction member's per-stratum
    sample sizes never exceed the high-fraction member's."""
    q_lo = Query(aggs=(AggSpec("mean", "value"),))
    q_hi = Query(aggs=(AggSpec("mean", "value", name="hi"),))
    sess = StreamSession(pipe)
    r_lo = sess.register(q_lo, initial_fraction=0.15)
    r_hi = sess.register(q_hi, initial_fraction=0.85)
    sess.step(jax.random.key(2), window)
    n_lo = np.asarray(r_lo.ring[-1].stats["value"]["moments"].n)
    n_hi = np.asarray(r_hi.ring[-1].stats["value"]["moments"].n)
    assert np.all(n_lo <= n_hi)
    assert n_lo.sum() < n_hi.sum()
    # downstream accounting follows the refined samples, not the group max
    assert r_lo.downstream_bytes < r_hi.downstream_bytes


# -- unbiasedness -------------------------------------------------------------


def test_refined_estimates_unbiased_against_truth(pipe):
    """Across independent windows/keys, the refined 25%-fraction member's
    mean estimate is unbiased for the full-population window mean (bias
    well inside the Monte-Carlo standard error band)."""
    q_lo = Query(aggs=(AggSpec("mean", "value"),))
    q_hi = Query(aggs=(AggSpec("mean", "value", name="hi"),))
    stream = shenzhen_taxi_stream(num_chunks=8, seed=11)
    errs = []
    for i, w in enumerate(windows.count_windows(stream, WINDOW)):
        sess = StreamSession(pipe)
        r_lo = sess.register(q_lo, initial_fraction=0.25)
        sess.register(q_hi, initial_fraction=0.9)
        step = sess.step(jax.random.key(100 + i), w)
        truth = float(np.mean(np.asarray(w.value)[np.asarray(w.valid)]))
        est = float(np.asarray(step.results[r_lo.qid].estimates["mean_value"].value))
        errs.append(est - truth)
    errs = np.asarray(errs)
    se = errs.std(ddof=1) / np.sqrt(len(errs))
    assert abs(errs.mean()) < 4.0 * se + 1e-3, (errs.mean(), se)


# -- CI width monotone in the refined fraction --------------------------------


@settings(deadline=None, max_examples=8)
@given(
    f_lo=st.floats(min_value=_f32(0.1), max_value=_f32(0.45), width=32),
    f_hi=st.floats(min_value=_f32(0.65), max_value=_f32(0.98), width=32),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_ci_widens_as_refined_fraction_shrinks(pipe, window, f_lo, f_hi, seed):
    """Identical queries fused at divergent fractions: the refined
    low-fraction member reports strictly wider mean intervals — its bounds
    see the effective (thinned) per-stratum sample, not the group max."""
    q_lo = Query(aggs=(AggSpec("mean", "value"),))
    q_mid = Query(aggs=(AggSpec("mean", "value", name="mid"),))
    q_hi = Query(aggs=(AggSpec("mean", "value", name="hi"),))
    f_mid = (f_lo + f_hi) / 2.0
    sess = StreamSession(pipe)
    regs = [
        sess.register(q, initial_fraction=f)
        for q, f in ((q_lo, f_lo), (q_mid, f_mid), (q_hi, f_hi))
    ]
    step = sess.step(jax.random.key(seed), window)
    moes = [
        float(np.asarray(next(iter(step.results[r.qid].estimates.values())).moe))
        for r in regs
    ]
    assert moes[0] > moes[1] > moes[2], (moes, (f_lo, f_mid, f_hi))


# -- determinism & cost accounting --------------------------------------------


def test_refined_step_deterministic_in_key(pipe, window):
    """Two fresh sessions over the same pane and key produce bit-identical
    refined results (the thinning randomness is keyed on the step key)."""
    q_lo = Query(aggs=(AggSpec("mean", "value"), AggSpec("p99", "value")))
    q_hi = Query(aggs=(AggSpec("var", "occupancy", name="v"),))

    def run(key):
        sess = StreamSession(pipe)
        r_lo = sess.register(q_lo, initial_fraction=0.3)
        r_hi = sess.register(q_hi, initial_fraction=0.8)
        step = sess.step(key, window)
        return step.results[r_lo.qid], step.results[r_hi.qid]

    a = run(jax.random.key(5))
    b = run(jax.random.key(5))
    for res_a, res_b in zip(a, b):
        for k in res_a.estimates:
            for field in EXACT_FIELDS:
                np.testing.assert_array_equal(
                    np.asarray(getattr(res_a.estimates[k], field)),
                    np.asarray(getattr(res_b.estimates[k], field)),
                )
    c = run(jax.random.key(6))
    assert int(c[0].n_sampled) != 0  # different key still samples


def test_uniform_fraction_group_keeps_shared_pass_cost(pipe, window, table):
    """Equal member fractions keep the PR2 shared pass: one union
    accumulation whose uplink is the shared plan's payload, strictly below
    the refined per-member payload the divergent case ships."""
    q1 = Query(aggs=(AggSpec("mean", "value"),))
    q2 = Query(aggs=(AggSpec("mean", "occupancy", name="o"),))
    fused = aqp.fuse([pipe.plan(q1), pipe.plan(q2)])
    shared_bytes = aqp.preagg_bytes(fused.shared, table.num_slots)
    refined_bytes = aqp.refined_preagg_bytes(fused, table.num_slots)
    assert shared_bytes < refined_bytes

    sess_eq = StreamSession(pipe, initial_fraction=0.6)
    for q in (q1, q2):
        sess_eq.register(q)
    assert sess_eq.step(jax.random.key(0), window).comm_bytes == shared_bytes

    sess_div = StreamSession(pipe)
    sess_div.register(q1, initial_fraction=0.2)
    sess_div.register(q2, initial_fraction=0.8)
    assert sess_div.step(jax.random.key(0), window).comm_bytes == refined_bytes
