"""EdgeSOS sampler: exact SRS sizes, uniformity, weights, compaction."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import sampling


def _random_strata(rng, n, s):
    return jnp.asarray(rng.integers(0, s, n), jnp.int32)


def test_exact_per_stratum_sizes(rng):
    sidx = _random_strata(rng, 20_000, 50)
    res = sampling.edgesos(jax.random.key(0), sidx, 51, 0.37)
    expected = np.round(0.37 * np.asarray(res.counts)).clip(0, np.asarray(res.counts))
    assert (np.asarray(res.n_k) == expected).all()
    # realized mask matches n_k per stratum
    realized = np.zeros(51, np.int64)
    np.add.at(realized, np.asarray(sidx)[np.asarray(res.mask)], 1)
    assert (realized == np.asarray(res.n_k)).all()


@given(frac=st.floats(0.05, 1.0), s=st.integers(1, 40), seed=st.integers(0, 2**30))
@settings(max_examples=50, deadline=None)
def test_fraction_one_keeps_everything(frac, s, seed):
    rng = np.random.default_rng(seed)
    sidx = _random_strata(rng, 2_000, s)
    res = sampling.edgesos(jax.random.key(seed), sidx, s + 1, 1.0)
    assert bool(jnp.all(res.mask))
    assert bool(jnp.allclose(res.weight, 1.0))
    res_f = sampling.edgesos(jax.random.key(seed), sidx, s + 1, frac)
    kept = int(jnp.sum(res_f.mask))
    assert abs(kept - frac * 2000) <= s + 1  # rounding per stratum


def test_srs_uniformity_within_stratum(rng):
    """Every tuple of a stratum has inclusion probability n_k/N_k."""
    n = 4_000
    sidx = jnp.zeros(n, jnp.int32)
    counts = np.zeros(n)
    trials = 200
    for t in range(trials):
        res = sampling.edgesos(jax.random.key(t), sidx, 1 + 1, 0.3)
        counts += np.asarray(res.mask)
    p = counts / trials
    # inclusion prob should be 0.3 for every position; binomial CI
    se = np.sqrt(0.3 * 0.7 / trials)
    assert abs(p.mean() - 0.3) < 3 * se / np.sqrt(n) + 1e-3
    assert (np.abs(p - 0.3) < 6 * se).all()


def test_ht_weights_unbiased_sum(rng):
    """Horvitz-Thompson weighted sum is unbiased for the population sum."""
    n, s = 30_000, 30
    sidx = _random_strata(rng, n, s)
    vals = jnp.asarray(rng.normal(50, 12, n), jnp.float32)
    true_sum = float(jnp.sum(vals))
    ests = []
    for t in range(30):
        res = sampling.edgesos(jax.random.key(t), sidx, s + 1, 0.4)
        ests.append(float(jnp.sum(vals * res.weight)))
    rel = abs(np.mean(ests) - true_sum) / abs(true_sum)
    assert rel < 0.01


def test_bernoulli_mode(rng):
    sidx = _random_strata(rng, 50_000, 20)
    res = sampling.edgesos(jax.random.key(1), sidx, 21, 0.25, method="bernoulli")
    kept = int(jnp.sum(res.mask))
    assert abs(kept - 12_500) < 600  # ~4 sigma
    w = np.asarray(res.weight)
    assert np.allclose(w[np.asarray(res.mask)], 4.0)


def test_neyman_allocates_more_to_high_variance(rng):
    n = 20_000
    sidx = jnp.asarray((np.arange(n) % 2), jnp.int32)
    stddev = jnp.asarray([1.0, 10.0, 0.0], jnp.float32)
    res = sampling.edgesos(jax.random.key(0), sidx, 3, 0.3, method="neyman", stddev=stddev)
    nk = np.asarray(res.n_k)
    assert nk[1] > 3 * nk[0]
    assert nk[0] + nk[1] == pytest.approx(0.3 * n, rel=0.05)


def test_compact(rng):
    sidx = _random_strata(rng, 1_000, 10)
    vals = jnp.asarray(rng.normal(0, 1, 1_000), jnp.float32)
    res = sampling.edgesos(jax.random.key(0), sidx, 11, 0.5)
    kept = int(jnp.sum(res.mask))
    valid, s_c, v_c = sampling.compact(res.mask, 600, sidx, vals)
    assert int(valid.sum()) == min(kept, 600)
    # the kept values appear in order
    ref = np.asarray(vals)[np.asarray(res.mask)][:600]
    assert np.allclose(np.asarray(v_c)[np.asarray(valid)], ref)
    # capacity larger than input is fine
    valid2, v2 = sampling.compact(res.mask, 1_500, vals)
    assert int(valid2.sum()) == kept


def test_decentralized_equals_shard_independent(rng):
    """Sampling a shard's window is independent of other shards: the same
    per-shard key gives the same sample whether or not other shards exist
    (the paper's synchronization-free property)."""
    n = 4_000
    sidx = _random_strata(rng, n, 16)
    local = sampling.edgesos(jax.random.fold_in(jax.random.key(7), 3), sidx, 17, 0.5)
    again = sampling.edgesos(jax.random.fold_in(jax.random.key(7), 3), sidx, 17, 0.5)
    assert bool(jnp.all(local.mask == again.mask))
