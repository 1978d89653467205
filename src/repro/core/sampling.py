"""EdgeSOS: decentralized, geohash-stratified online sampling (Algorithm 1).

Each edge node (here: each mesh shard) independently partitions its local
window into geohash strata, computes per-stratum target sizes, and draws a
Simple Random Sample within every stratum — no cross-node synchronization.

TPU adaptation.  The paper's Rust implementation groups tuples into per-
stratum Vecs (rayon-parallel hashmap grouping) and then subsamples each Vec.
Dynamic per-stratum buffers don't exist on TPU, so EdgeSOS is re-derived in
fixed-shape form:

  * exact SRS: draw one uniform per tuple, group tuples by stratum with a
    stable sort, rank tuples inside their stratum, keep ``rank < n_k``.
    This is *exactly* an SRS of size ``n_k`` within each stratum (every
    subset of size ``n_k`` equally likely) and costs one O(N log N) device
    sort — the analogue of rayon's parallel grouping, executed by the TPU's
    sort unit instead of a thread pool.
  * bernoulli: keep tuples iid with per-stratum probability ``f_k``; cheaper
    (no sort), sample sizes are random.  Horvitz-Thompson weights keep the
    estimators unbiased in both modes.

The sample is a fixed-shape (mask, weight) pair: downstream consumers either
use the mask directly (weighted reductions — zero extra memory traffic) or
``compact`` kept tuples to a padded buffer (the "raw transmission" mode).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class SampleResult(NamedTuple):
    """Fixed-shape stratified sample.

    mask: (N,) bool — tuple kept?
    weight: (N,) f32 — Horvitz-Thompson weight (N_k/n_k or 1/f_k); 0 if dropped.
    n_k: (S+1,) i32 — realized per-stratum sample sizes.
    counts: (S+1,) i32 — per-stratum population sizes N_k of this window.
    """

    mask: jnp.ndarray
    weight: jnp.ndarray
    n_k: jnp.ndarray
    counts: jnp.ndarray


def stratum_counts(stratum_idx: jnp.ndarray, num_slots: int) -> jnp.ndarray:
    """Per-stratum population counts N_k (including overflow slot)."""
    return jax.ops.segment_sum(
        jnp.ones_like(stratum_idx, dtype=jnp.int32), stratum_idx, num_segments=num_slots
    )


def allocate_proportional(counts: jnp.ndarray, fraction) -> jnp.ndarray:
    """Paper's allocation: n_k = round(f * N_k), clipped to [0, N_k].

    ``fraction`` may be a scalar or a per-stratum vector (adaptive mode).
    """
    target = jnp.round(counts.astype(jnp.float32) * fraction)
    return jnp.clip(target.astype(jnp.int32), 0, counts)


def allocate_neyman(
    counts: jnp.ndarray, stddev: jnp.ndarray, fraction, min_per_stratum: int = 1
) -> jnp.ndarray:
    """Neyman (variance-optimal) allocation — beyond-paper option.

    n_k proportional to N_k * s_k at the same total budget f * N.  Falls back
    to proportional where variance info is degenerate.
    """
    counts_f = counts.astype(jnp.float32)
    total_budget = jnp.sum(counts_f) * fraction
    score = counts_f * jnp.maximum(stddev, 0.0)
    denom = jnp.sum(score)
    prop = jnp.where(denom > 0, score / jnp.maximum(denom, 1e-30), counts_f / jnp.maximum(jnp.sum(counts_f), 1.0))
    target = jnp.round(total_budget * prop).astype(jnp.int32)
    target = jnp.maximum(target, jnp.minimum(counts, min_per_stratum))
    return jnp.clip(target, 0, counts)


def _rank_within_stratum(key, stratum_idx: jnp.ndarray, num_slots: int):
    """Random rank of each tuple within its stratum.

    Returns (ranks, counts).  ranks[i] is uniform over {0..N_k-1} within
    stratum k — the order statistic that turns thresholding into exact SRS.
    """
    n = stratum_idx.shape[0]
    u = jax.random.uniform(key, (n,))
    # Stable sort by stratum after a random shuffle => random order inside
    # each stratum, strata contiguous.
    shuffle = jnp.argsort(u)
    s_shuffled = stratum_idx[shuffle]
    order = jnp.argsort(s_shuffled, stable=True)
    perm = shuffle[order]  # original indices, grouped by stratum
    s_sorted = stratum_idx[perm]
    counts = stratum_counts(stratum_idx, num_slots)
    starts = jnp.cumsum(counts) - counts
    ranks_sorted = jnp.arange(n, dtype=jnp.int32) - starts[s_sorted]
    ranks = jnp.zeros((n,), dtype=jnp.int32).at[perm].set(ranks_sorted)
    return ranks, counts


def srs_ranks(key, stratum_idx: jnp.ndarray, num_slots: int):
    """The shared randomness of nested SRS: ``(ranks, counts)``.

    ``ranks`` depends only on ``(key, stratum_idx)`` — never on the
    fraction — so ``ranks < allocate_proportional(counts, f)[stratum_idx]``
    is *exactly* the sample :func:`srs_sample` draws at fraction ``f`` for
    the same key, and the keep-sets are nested in ``f`` (``n_k`` is
    monotone in the fraction).  One rank vector therefore serves every
    member of a fused pass at its *own* fraction: thinning the shared
    sample to a lower-fraction member's target is Horvitz-Thompson
    subsampling (the nested SRS of per-query fraction refinement), and the
    refined sample is bit-identical to the member's independent draw.
    """
    with jax.named_scope("sample"):
        return _rank_within_stratum(key, stratum_idx, num_slots)


def srs_sample(
    key, stratum_idx: jnp.ndarray, num_slots: int, n_k: jnp.ndarray, counts: jnp.ndarray
) -> SampleResult:
    """Exact within-stratum SRS with target sizes n_k (fixed shapes)."""
    ranks, _ = _rank_within_stratum(key, stratum_idx, num_slots)
    mask = ranks < n_k[stratum_idx]
    w_k = jnp.where(n_k > 0, counts.astype(jnp.float32) / jnp.maximum(n_k, 1).astype(jnp.float32), 0.0)
    weight = jnp.where(mask, w_k[stratum_idx], 0.0)
    return SampleResult(mask=mask, weight=weight, n_k=n_k, counts=counts)


def bernoulli_sample(
    key, stratum_idx: jnp.ndarray, num_slots: int, fraction, backend: str = "segment"
) -> SampleResult:
    """Per-stratum Bernoulli(f_k) sampling (no sort; random n_k).

    The per-tuple uniforms depend only on ``(key, N)`` — not on stratum
    membership or the fraction — so one draw nests every fraction
    (``u < f'`` is a subset of ``u < f`` for ``f' <= f``) and is oblivious
    to ROI-induced stratum reassignment: the properties behind per-query
    fraction refinement and cross-signature Bernoulli fusion in the
    session layer.

    ``backend="pallas"`` routes the fused gather+threshold+weight step
    through the ``kernels/sample_mask`` one-hot MXU kernel on TPU (same
    uniforms, so the sampling decisions are bit-identical); elsewhere it
    falls back to this segment implementation.
    """
    counts = stratum_counts(stratum_idx, num_slots)
    frac_k = jnp.broadcast_to(jnp.asarray(fraction, jnp.float32), (num_slots,))
    u = jax.random.uniform(key, stratum_idx.shape)
    from ..kernels.platform import on_tpu

    if backend == "pallas" and on_tpu():
        from ..kernels.sample_mask import sample_mask as _kernel

        mask, weight = _kernel(stratum_idx, u, frac_k)
    else:
        mask = u < frac_k[stratum_idx]
        weight = jnp.where(mask, 1.0 / jnp.maximum(frac_k[stratum_idx], 1e-9), 0.0)
    n_k = jax.ops.segment_sum(mask.astype(jnp.int32), stratum_idx, num_segments=num_slots)
    return SampleResult(mask=mask, weight=weight, n_k=n_k, counts=counts)


def edgesos(
    key,
    stratum_idx: jnp.ndarray,
    num_slots: int,
    fraction,
    *,
    method: str = "srs",
    stddev: jnp.ndarray | None = None,
    min_per_stratum: int = 1,
    backend: str = "segment",
) -> SampleResult:
    """Algorithm 1 (EdgeSOS): stratified sample of one window.

    Args:
      key: PRNG key (per edge node / per window — never shared across nodes).
      stratum_idx: (N,) int32 stratum of each tuple (from StratumTable.assign).
      num_slots: static S+1.
      fraction: scalar or per-stratum sampling fraction in (0, 1].
      method: 'srs' (paper-faithful exact SRS) | 'bernoulli' | 'neyman'.
      stddev: per-stratum std estimates (required for 'neyman').
      backend: 'segment' | 'pallas' (fused Bernoulli selection kernel on TPU).
    """
    with jax.named_scope("sample"):
        if method == "bernoulli":
            return bernoulli_sample(key, stratum_idx, num_slots, fraction, backend=backend)
        counts = stratum_counts(stratum_idx, num_slots)
        if method == "srs":
            n_k = allocate_proportional(counts, fraction)
        elif method == "neyman":
            if stddev is None:
                raise ValueError("neyman allocation requires per-stratum stddev")
            n_k = allocate_neyman(counts, stddev, fraction, min_per_stratum)
        else:
            raise ValueError(f"unknown method {method!r}")
        return srs_sample(key, stratum_idx, num_slots, n_k, counts)


def compact(mask: jnp.ndarray, max_out: int, *arrays: jnp.ndarray):
    """Gather kept tuples to the front of a padded (max_out, ...) buffer.

    Implements the paper's "raw sampled data transmission" mode with static
    shapes: kept tuples first (original relative order), padding after.
    Returns (valid, gathered...) where valid is a (max_out,) bool mask.
    """
    n = mask.shape[0]
    take = min(max_out, n)
    order = jnp.argsort(~mask, stable=True)  # kept tuples first
    kept = jnp.sum(mask.astype(jnp.int32))
    idx = order[:take]
    valid = jnp.arange(max_out, dtype=jnp.int32) < jnp.minimum(kept, take)

    def gather(a):
        g = a[idx]
        if max_out > n:  # buffer larger than window: pad the tail
            g = jnp.concatenate(
                [g, jnp.zeros((max_out - n,) + a.shape[1:], a.dtype)], axis=0
            )
        return jnp.where(valid.reshape((max_out,) + (1,) * (a.ndim - 1)), g, jnp.zeros_like(g))

    return (valid,) + tuple(gather(a) for a in arrays)
