"""Stratified estimators (eqs 1-10): exactness, unbiasedness, CI coverage,
merge associativity, raw == pre-aggregated equivalence."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import estimators, sampling


def _make(rng, n=20_000, s=25, mean=40.0, sd=8.0):
    sidx = jnp.asarray(rng.integers(0, s, n), jnp.int32)
    offsets = rng.normal(0, 10, s + 1)
    vals = jnp.asarray(mean + offsets[np.asarray(sidx)] + rng.normal(0, sd, n), jnp.float32)
    return sidx, vals, s + 1


def test_full_sample_is_exact(rng):
    sidx, vals, ns = _make(rng)
    stats = estimators.sample_stats(vals, sidx, jnp.ones_like(sidx, bool), ns)
    est = estimators.estimate(stats)
    assert float(est.mean) == pytest.approx(float(vals.mean()), rel=1e-5)
    assert float(est.sum) == pytest.approx(float(vals.sum()), rel=1e-5)
    assert float(est.var_mean) == pytest.approx(0.0, abs=1e-10)  # fpc = 0


def test_unbiased_over_repeats(rng):
    sidx, vals, ns = _make(rng)
    true = float(vals.mean())
    means = []
    for t in range(40):
        res = sampling.edgesos(jax.random.key(t), sidx, ns, 0.3)
        stats = estimators.sample_stats(vals, sidx, res.mask, ns, counts=res.counts)
        means.append(float(estimators.estimate(stats).mean))
    assert np.mean(means) == pytest.approx(true, rel=2e-3)


def test_ci_coverage(rng):
    """95% CIs cover the true mean ~95% of the time."""
    sidx, vals, ns = _make(rng, n=8_000)
    true = float(vals.mean())
    cover = 0
    trials = 120
    for t in range(trials):
        res = sampling.edgesos(jax.random.key(t + 1000), sidx, ns, 0.25)
        stats = estimators.sample_stats(vals, sidx, res.mask, ns, counts=res.counts)
        est = estimators.estimate(stats, confidence=0.95)
        if float(est.ci_low) <= true <= float(est.ci_high):
            cover += 1
    rate = cover / trials
    assert 0.88 <= rate <= 1.0, f"coverage {rate}"


def test_variance_formula_against_numpy_oracle(rng):
    """Eq 6 evaluated directly in numpy matches the jitted implementation."""
    sidx, vals, ns = _make(rng, n=5_000, s=8)
    res = sampling.edgesos(jax.random.key(5), sidx, ns, 0.5)
    stats = estimators.sample_stats(vals, sidx, res.mask, ns, counts=res.counts)
    est = estimators.estimate(stats)
    sid = np.asarray(sidx)
    m = np.asarray(res.mask)
    v = np.asarray(vals)
    var_sum = 0.0
    for k in range(ns):
        Nk = (sid == k).sum()
        sel = v[(sid == k) & m]
        nk = len(sel)
        if nk > 1 and Nk > 0:
            s2 = sel.var(ddof=1)
            var_sum += Nk**2 * (1 - nk / Nk) * s2 / nk
    assert float(est.var_sum) == pytest.approx(var_sum, rel=1e-3)


def test_merge_equals_global(rng):
    """Pre-aggregated mode: merging per-edge stats == stats of the union
    (the paper's two transmission modes agree)."""
    sidx, vals, ns = _make(rng, n=12_000)
    mask = jnp.asarray(rng.random(12_000) < 0.6)
    chunks = np.array_split(np.arange(12_000), 5)
    parts = [
        estimators.sample_stats(vals[jnp.asarray(c)], sidx[jnp.asarray(c)], mask[jnp.asarray(c)], ns)
        for c in chunks
    ]
    merged = estimators.merge_all(parts)
    glob = estimators.sample_stats(vals, sidx, mask, ns)
    for a, b in zip(merged, glob):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-2)
    em, eg = estimators.estimate(merged), estimators.estimate(glob)
    assert float(em.mean) == pytest.approx(float(eg.mean), rel=1e-5)
    assert float(em.var_mean) == pytest.approx(float(eg.var_mean), rel=1e-3, abs=1e-10)


@given(perm_seed=st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_permutation_invariance(perm_seed):
    """Estimates don't depend on tuple order."""
    rng = np.random.default_rng(42)
    sidx, vals, ns = _make(rng, n=3_000, s=6)
    mask = jnp.asarray(rng.random(3_000) < 0.5)
    perm = np.random.default_rng(perm_seed).permutation(3_000)
    pj = jnp.asarray(perm)
    a = estimators.estimate(estimators.sample_stats(vals, sidx, mask, ns))
    b = estimators.estimate(estimators.sample_stats(vals[pj], sidx[pj], mask[pj], ns))
    assert float(a.mean) == pytest.approx(float(b.mean), rel=1e-5)
    assert float(a.var_mean) == pytest.approx(float(b.var_mean), rel=1e-4, abs=1e-12)


def test_substream_sums_eq_1_2(rng):
    """Eqs (1)-(2): per-substream estimated sums add up to the global sum
    estimate when substreams cover disjoint strata."""
    s = 12
    sidx_a = jnp.asarray(rng.integers(0, 6, 4_000), jnp.int32)
    sidx_b = jnp.asarray(rng.integers(6, 12, 4_000), jnp.int32)
    vals_a = jnp.asarray(rng.normal(20, 3, 4_000), jnp.float32)
    vals_b = jnp.asarray(rng.normal(60, 3, 4_000), jnp.float32)
    ra = sampling.edgesos(jax.random.key(0), sidx_a, s + 1, 0.5)
    rb = sampling.edgesos(jax.random.key(1), sidx_b, s + 1, 0.5)
    sa = estimators.sample_stats(vals_a, sidx_a, ra.mask, s + 1, counts=ra.counts)
    sb = estimators.sample_stats(vals_b, sidx_b, rb.mask, s + 1, counts=rb.counts)
    t_hats = estimators.substream_sums([sa, sb])
    merged = estimators.merge_stats(sa, sb)
    est = estimators.estimate(merged)
    assert float(jnp.sum(t_hats)) == pytest.approx(float(est.sum), rel=1e-5)


def test_paper_toy_example():
    """Paper §3.5 toy: A samples (10,7,8) of 6 tuples, B samples (6,11) of 4;
    sums 25 and 17, grand total 42... with the HT expansion the paper
    describes: N_k * ȳ_k per node. Node A: 6 * mean(10,7,8)=50? The paper's
    arithmetic treats the *sample sums* directly (25+17=42, mean 8.4 over 5
    sampled tuples); our estimator reproduces that when N_k == n_k."""
    sidx = jnp.asarray([0, 0, 0, 1, 1], jnp.int32)
    vals = jnp.asarray([10.0, 7.0, 8.0, 6.0, 11.0], jnp.float32)
    stats = estimators.sample_stats(vals, sidx, jnp.ones(5, bool), 3)
    est = estimators.estimate(stats)
    assert float(est.sum) == pytest.approx(42.0)
    assert float(est.mean) == pytest.approx(8.4)
