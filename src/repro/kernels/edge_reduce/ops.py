"""Public wrapper: Pallas on TPU, one-pass stacked segment reduce elsewhere.

Off-TPU the Pallas interpreter is a correctness tool, not a perf path, so
the auto mode (``interpret=None``) lowers to the fused single-pass
``segment_sum`` path instead — the pipeline's ``backend="pallas"`` stays
portable (and still beats the per-column segment path by running one
sort/scatter for the whole fusion group).  Pass ``interpret=True`` to force
the interpreted kernel (parity tests).

Both jnp implementations live here (not in ``ref.py``): references are
jax-free numpy oracles (edgelint EDG006), so anything jitted or used as a
device fast path belongs on the ops side.  ``edge_reduce_percol`` is the
per-column baseline the fused kernel is benchmarked against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..platform import on_tpu
from .edge_reduce import _moment_rows, edge_reduce_pallas


def edge_reduce(stratum_idx, values, mask, num_slots: int, interpret: bool | None = None):
    """-> (count (S,), s1 (C, S), s2 (C, S)) raw per-stratum power sums."""
    if interpret is None:
        if not on_tpu():
            return _edge_reduce_segment(stratum_idx, values, mask, num_slots)
        interpret = False
    return edge_reduce_pallas(stratum_idx, values, mask, num_slots, interpret=interpret)


def _edge_reduce_segment(stratum_idx, values, mask, num_slots: int):
    """Single-pass stacked fast path: one (N, R) segment_sum for all columns."""
    c = values.shape[0]
    rows = _moment_rows(values, mask)  # (1+2C, N)
    out = jax.ops.segment_sum(rows.T, stratum_idx, num_segments=num_slots)  # (S, R)
    return out[:, 0], out[:, 1 : 1 + c].T, out[:, 1 + c : 1 + 2 * c].T


def edge_reduce_percol(stratum_idx, values, mask, num_slots: int):
    """The per-column segment path (3 reductions per column) — the baseline
    the fused kernel is benchmarked against."""
    m = mask.astype(jnp.float32)
    count = jax.ops.segment_sum(m, stratum_idx, num_segments=num_slots)
    s1, s2 = [], []
    for col in values:
        y = col.astype(jnp.float32)
        s1.append(jax.ops.segment_sum(m * y, stratum_idx, num_segments=num_slots))
        s2.append(jax.ops.segment_sum(m * y * y, stratum_idx, num_segments=num_slots))
    return count, jnp.stack(s1), jnp.stack(s2)
