"""Accumulator registry laws: merge associativity/commutativity per kind,
vectorized pane merges vs sequential folds, overflow neutralization, the
quantile sketch against a sorted-sample oracle, and pluggability."""

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core.estimators import (
    ACCUMULATORS,
    SKETCH_NUM_BINS,
    Accumulator,
    accumulate_column,
    accumulator,
    merge_accs,
    merge_accs_panes,
    register_accumulator,
    sketch_bin_values,
    sketch_quantile,
    zero_overflow_accs,
)

ALL_KINDS = ("moments", "extrema", "sketch")


def _parts(rng, n=6_000, s=12, shards=3, kinds=ALL_KINDS):
    """Shard-split registry states plus the global single-pass state."""
    sidx = jnp.asarray(rng.integers(0, s, n), jnp.int32)
    vals = jnp.asarray(rng.normal(40, 12, n), jnp.float32)
    mask = jnp.asarray(rng.random(n) < 0.6)
    parts = []
    for c in np.array_split(np.arange(n), shards):
        c = jnp.asarray(c)
        parts.append(accumulate_column(kinds, vals[c], sidx[c], mask[c], s + 1))
    glob = accumulate_column(kinds, vals, sidx, mask, s + 1)
    return parts, glob


def _assert_state_close(kind, a, b, msg=""):
    exact = kind in ("extrema", "sketch")  # lattice / integer-count merges
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        if exact:
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb), err_msg=msg)
        else:
            np.testing.assert_allclose(
                np.asarray(la), np.asarray(lb), rtol=2e-4, atol=2e-2, err_msg=msg
            )


@pytest.mark.parametrize("kind", ALL_KINDS)
def test_merge_equals_global_per_kind(rng, kind):
    """Folding shard states reproduces the single-pass global state."""
    parts, glob = _parts(rng, kinds=(kind,))
    merged = parts[0]
    for p in parts[1:]:
        merged = merge_accs(merged, p)
    _assert_state_close(kind, merged[kind], glob[kind])


@pytest.mark.parametrize("kind", ALL_KINDS)
def test_merge_associative_commutative(rng, kind):
    parts, _ = _parts(rng, kinds=(kind,))
    a, b, c = parts
    acc = accumulator(kind)
    left = acc.merge(acc.merge(a[kind], b[kind]), c[kind])
    right = acc.merge(a[kind], acc.merge(b[kind], c[kind]))
    flipped = acc.merge(b[kind], a[kind])
    _assert_state_close(kind, left, right, msg="associativity")
    _assert_state_close(kind, acc.merge(a[kind], b[kind]), flipped, msg="commutativity")


@pytest.mark.parametrize("kind", ALL_KINDS)
def test_pane_merge_matches_sequential_fold(rng, kind):
    """merge_panes over a stacked (P, ...) state == P-1 sequential merges."""
    parts, _ = _parts(rng, shards=4, kinds=(kind,))
    acc = accumulator(kind)
    seq = parts[0][kind]
    for p in parts[1:]:
        seq = acc.merge(seq, p[kind])
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *[p[kind] for p in parts])
    vec = merge_accs_panes({kind: stacked})[kind]
    _assert_state_close(kind, vec, seq)


@pytest.mark.parametrize("kind", ALL_KINDS)
def test_zero_overflow_neutralizes(rng, kind):
    """After zero_overflow the overflow slot carries merge identities, so it
    contributes nothing when merged into another state."""
    parts, _ = _parts(rng, kinds=(kind,))
    acc = accumulator(kind)
    z = zero_overflow_accs(parts[0])[kind]
    merged = acc.merge(z, parts[1][kind])
    # overflow slot of the merge == partner's overflow slot untouched
    for lm, lp in zip(jax.tree.leaves(merged), jax.tree.leaves(parts[1][kind])):
        np.testing.assert_allclose(
            np.asarray(lm)[-1], np.asarray(lp)[-1], rtol=1e-6, atol=1e-6
        )


# -- quantile sketch vs sorted-sample oracle ----------------------------------


@given(seed=st.integers(0, 2**30), q=st.floats(0.05, 0.99), scale=st.floats(0.1, 300.0))
@settings(max_examples=30, deadline=None)
def test_sketch_quantile_within_relative_accuracy(seed, q, scale):
    """A sketch inverted at q lands within its documented ~4-5% relative
    value accuracy of the exact sorted-sample quantile."""
    rng = np.random.default_rng(seed)
    v = rng.normal(0, scale, 4_000).astype(np.float32)
    sk = accumulator("sketch").accumulate(
        jnp.asarray(v), jnp.zeros(len(v), jnp.int32), jnp.ones(len(v), bool), 1
    )
    got = float(sketch_quantile(sk.bins[0], q))
    true = float(np.quantile(v, q))
    assert got == pytest.approx(true, rel=0.05, abs=2e-4)


@given(seed=st.integers(0, 2**30), splits=st.integers(2, 6))
@settings(max_examples=25, deadline=None)
def test_sketch_merge_associativity_vs_sorted_oracle(seed, splits):
    """Property: any shard split + any merge order yields the *identical*
    sketch (bin counts are exact f32 integers), and its quantiles agree with
    the sorted oracle of the concatenated sample."""
    rng = np.random.default_rng(seed)
    v = rng.lognormal(1.0, 1.2, 3_000).astype(np.float32)
    acc = accumulator("sketch")
    chunks = np.array_split(v, splits)
    states = [
        acc.accumulate(jnp.asarray(c), jnp.zeros(len(c), jnp.int32), jnp.ones(len(c), bool), 1)
        for c in chunks
    ]
    fold_lr = states[0]
    for s in states[1:]:
        fold_lr = acc.merge(fold_lr, s)
    fold_rl = states[-1]
    for s in states[-2::-1]:
        fold_rl = acc.merge(s, fold_rl)
    np.testing.assert_array_equal(np.asarray(fold_lr.bins), np.asarray(fold_rl.bins))
    whole = acc.accumulate(
        jnp.asarray(v), jnp.zeros(len(v), jnp.int32), jnp.ones(len(v), bool), 1
    )
    np.testing.assert_array_equal(np.asarray(fold_lr.bins), np.asarray(whole.bins))
    for q in (0.5, 0.9, 0.99):
        got = float(sketch_quantile(fold_lr.bins[0], q))
        assert got == pytest.approx(float(np.quantile(v, q)), rel=0.05, abs=2e-4)


def test_sketch_ht_expansion_matches_weighted_oracle(rng):
    """Two strata sampled at different rates: the N_k/n_k row expansion must
    equal the quantile of the explicitly HT-weighted (repeated) sample."""
    lo = rng.normal(10, 1, 2_000).astype(np.float32)
    hi = rng.normal(100, 5, 2_000).astype(np.float32)
    keep_lo = rng.random(2_000) < 1.0  # stratum 0 fully sampled
    keep_hi = rng.random(2_000) < 0.25  # stratum 1 at a quarter
    v = np.concatenate([lo, hi])
    sidx = jnp.asarray(np.repeat([0, 1], 2_000), jnp.int32)
    mask = jnp.asarray(np.concatenate([keep_lo, keep_hi]))
    sk = accumulator("sketch").accumulate(jnp.asarray(v), sidx, mask, 2)
    n_k = np.array([keep_lo.sum(), keep_hi.sum()], np.float64)
    w_k = 2_000.0 / n_k
    weighted = jnp.asarray((w_k[:, None] * np.asarray(sk.bins)).sum(axis=0), jnp.float32)
    # q=0.25 sits inside the lo cluster, q=0.75 inside the hi cluster; the
    # unweighted sketch would give the under-sampled hi cluster only ~20% of
    # the mass and miss p75 badly — HT expansion restores the 50/50 split
    for q in (0.25, 0.75):
        got = float(sketch_quantile(weighted, q))
        true = float(np.quantile(v, q))
        assert got == pytest.approx(true, rel=0.08), q
    # and the weighted histogram total equals the HT-estimated population
    assert float(jnp.sum(weighted)) == pytest.approx(4_000.0, rel=1e-5)


def test_sketch_payload_and_shape(rng):
    sk = accumulator("sketch").accumulate(
        jnp.asarray(rng.normal(0, 1, 100), jnp.float32),
        jnp.zeros(100, jnp.int32),
        jnp.ones(100, bool),
        3,
    )
    assert sk.bins.shape == (3, SKETCH_NUM_BINS)
    assert accumulator("sketch").payload_vectors() == SKETCH_NUM_BINS
    assert float(jnp.sum(sk.bins)) == 100.0
    assert sketch_bin_values().shape == (SKETCH_NUM_BINS,)
    # bin representatives are strictly ordered (CDF inversion precondition)
    assert bool(jnp.all(jnp.diff(sketch_bin_values()) >= 0))


# -- registry pluggability -----------------------------------------------------


def test_register_custom_accumulator_end_to_end(rng):
    """A new kind plugs into accumulate/merge/pane-merge/zero_overflow with
    no engine changes — the tentpole's extensibility contract."""

    class AbsSum(Accumulator):
        kind = "_test_abssum"

        def accumulate(self, values, stratum_idx, mask, num_slots, counts=None):
            return jax.ops.segment_sum(
                mask.astype(jnp.float32) * jnp.abs(values), stratum_idx, num_segments=num_slots
            )

        def merge(self, a, b):
            return a + b

        def merge_panes(self, stacked):
            return jnp.sum(stacked, axis=0)

        def psum(self, state, axis_names, shared=None):
            return jax.lax.psum(state, axis_names)

        def zero_overflow(self, state):
            keep = jnp.arange(state.shape[0]) < (state.shape[0] - 1)
            return jnp.where(keep, state, 0.0)

        def payload_vectors(self):
            return 1

        def payload_flatten(self, state):
            return (("abs", state, True, 0.0),)

        def payload_unflatten(self, rows):
            return rows["abs"]

        def template(self):
            return 0

    register_accumulator(AbsSum())
    try:
        sidx = jnp.asarray(rng.integers(0, 4, 500), jnp.int32)
        vals = jnp.asarray(rng.normal(0, 5, 500), jnp.float32)
        mask = jnp.asarray(rng.random(500) < 0.5)
        halves = [
            accumulate_column(("_test_abssum",), vals[s], sidx[s], mask[s], 5)
            for s in (slice(0, 250), slice(250, 500))
        ]
        merged = merge_accs(halves[0], halves[1])
        whole = accumulate_column(("_test_abssum",), vals, sidx, mask, 5)
        np.testing.assert_allclose(
            np.asarray(merged["_test_abssum"]), np.asarray(whole["_test_abssum"]), rtol=1e-5
        )
        z = zero_overflow_accs(whole)
        assert float(np.asarray(z["_test_abssum"])[-1]) == 0.0
    finally:
        del ACCUMULATORS["_test_abssum"]
    with pytest.raises(KeyError, match="unknown accumulator kind"):
        accumulator("_test_abssum")
