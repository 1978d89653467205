"""Public wrapper: fused Pallas megakernel on TPU, stacked segment elsewhere.

Off-TPU the auto mode (``interpret=None``) lowers to a jnp lowering with
the same contract — one ``segment_sum`` of the stacked stat rows plus
segment min/max and a flat-binned sketch scatter — so
``PipelineConfig(backend="fused")`` stays portable.  Pass
``interpret=True`` to force the interpreted Pallas kernel (parity tests,
``kernel_bench --dry``).

Both jnp implementations live here (not in ``ref.py``): refs are
jax-free numpy oracles (edgelint EDG006).

Contract notes shared by all three implementations (kernel / segment
lowering / numpy ref):

* sampling is the unified threshold compare ``keep = ok & (score <
  thr[slot])`` (Bernoulli: uniforms vs fractions; SRS: ranks vs ``n_k``;
  raw: zeros vs ones);
* ``latlon`` mode resolves membership against the sorted-unique code
  table; tuples whose code is absent (the overflow stratum) land in NO
  slot — overflow stat rows stay zero (+inf/-inf for extrema) and the
  caller reconstructs overflow *counts* as residuals.  Sound because the
  query layer zeroes overflow stats before estimating;
* ``sidx`` mode covers every slot, overflow included, exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.estimators import SKETCH_NUM_BINS, sketch_bin_index
from ...core.geohash import encode
from ..platform import on_tpu
from .edge_megakernel import MegaResult, edge_megakernel_pallas


def edge_megakernel(
    vals,
    ok,
    scores,
    thresholds,
    num_slots: int,
    *,
    sidx=None,
    lat=None,
    lon=None,
    codes=None,
    precision: int | None = None,
    ext_idx: tuple = (),
    sk_idx: tuple = (),
    interpret: bool | None = None,
    n_block: int | None = None,
    s_block: int | None = None,
) -> MegaResult:
    """Single-traversal fused edge pass -> :class:`MegaResult`.

    ``vals`` (C, N) value columns (any float dtype; f32 accumulation),
    ``ok`` (M, N) per-member validity & ROI, ``scores`` (M, N) non-negative
    sampling scores, ``thresholds`` (M, num_slots) per-slot keep
    thresholds.  Membership comes from ``sidx`` (M, N) or from
    ``lat``/``lon`` + ``codes``/``precision`` (see module docstring).
    ``ext_idx``/``sk_idx`` select the value columns that also get extrema
    / sketch stat rows.
    """
    with jax.named_scope("reduce"):
        ext_idx, sk_idx = tuple(ext_idx), tuple(sk_idx)
        if interpret is None:
            if not on_tpu():
                return _edge_megakernel_segment(
                    vals, ok, scores, thresholds, num_slots,
                    sidx=sidx, lat=lat, lon=lon, codes=codes, precision=precision,
                    ext_idx=ext_idx, sk_idx=sk_idx,
                )
            interpret = False
        return edge_megakernel_pallas(
            vals, ok, scores, thresholds, num_slots,
            sidx=sidx, lat=lat, lon=lon, codes=codes, precision=precision,
            ext_idx=ext_idx, sk_idx=sk_idx,
            n_block=n_block, s_block=s_block, interpret=interpret,
        )


@functools.partial(
    jax.jit, static_argnames=("num_slots", "precision", "ext_idx", "sk_idx")
)
def _edge_megakernel_segment(
    vals, ok, scores, thresholds, num_slots: int,
    *, sidx=None, lat=None, lon=None, codes=None, precision=None,
    ext_idx: tuple = (), sk_idx: tuple = (),
):
    """jnp lowering: stacked segment reduce with a trailing dump slot.

    Slot ``num_slots`` collects latlon-mode tuples outside the code table
    (and nothing in sidx mode) and is sliced off, matching the kernel's
    match-nothing behaviour.
    """
    c = vals.shape[0]
    vals = vals.astype(jnp.float32)
    if sidx is None:
        if lat is None or lon is None or codes is None or precision is None:
            raise ValueError("latlon mode needs lat, lon, codes and precision")
        code = encode(lat.astype(jnp.float32), lon.astype(jnp.float32), precision)
        pos = jnp.searchsorted(codes, code)
        pos_c = jnp.clip(pos, 0, codes.shape[0] - 1)
        found = codes[pos_c] == code
        sidx_ext = jnp.where(found, pos_c.astype(jnp.int32), num_slots)
        sidx_ext = jnp.broadcast_to(sidx_ext[None, :], ok.shape)
    else:
        sidx_ext = jnp.clip(sidx.astype(jnp.int32), 0, num_slots)
    okv = ok.astype(jnp.float32)
    thr_ext = jnp.pad(thresholds.astype(jnp.float32), ((0, 0), (0, 1)))
    t = jnp.take_along_axis(thr_ext, sidx_ext, axis=1)  # (M, N)
    keepv = okv * (scores.astype(jnp.float32) < t).astype(jnp.float32)

    def per_member(sidx_m, okv_m, keepv_m):
        kv = keepv_m[None, :] * vals  # (C, N)
        rows = jnp.concatenate([okv_m[None, :], keepv_m[None, :], kv, kv * vals], axis=0)
        out = jax.ops.segment_sum(rows.T, sidx_m, num_segments=num_slots + 1)  # (S+1, R)
        out = out[:num_slots]
        kept = keepv_m > 0.0
        # route non-kept tuples to the dump slot so empty strata keep the
        # +inf/-inf identities without a where over segments
        sidx_kept = jnp.where(kept, sidx_m, num_slots)
        mins = jnp.stack(
            [
                jax.ops.segment_min(vals[e], sidx_kept, num_segments=num_slots + 1)[:num_slots]
                for e in ext_idx
            ]
        ) if ext_idx else jnp.zeros((0, num_slots), jnp.float32)
        maxs = jnp.stack(
            [
                jax.ops.segment_max(vals[e], sidx_kept, num_segments=num_slots + 1)[:num_slots]
                for e in ext_idx
            ]
        ) if ext_idx else jnp.zeros((0, num_slots), jnp.float32)
        bins_l = []
        for k in sk_idx:
            b = sketch_bin_index(vals[k])
            flat = sidx_m * SKETCH_NUM_BINS + b
            bins_l.append(
                jax.ops.segment_sum(
                    keepv_m, flat, num_segments=(num_slots + 1) * SKETCH_NUM_BINS
                ).reshape(num_slots + 1, SKETCH_NUM_BINS)[:num_slots]
            )
        bins = (
            jnp.stack(bins_l)
            if sk_idx
            else jnp.zeros((0, num_slots, SKETCH_NUM_BINS), jnp.float32)
        )
        return (
            out[:, 0], out[:, 1],
            out[:, 2 : 2 + c].T, out[:, 2 + c : 2 + 2 * c].T,
            mins, maxs, bins,
        )

    pop, keep, s1, s2, mins, maxs, bins = jax.vmap(per_member)(sidx_ext, okv, keepv)
    # segment_min/max identities are finite dtype extremes; the kernel and
    # the accumulator protocol use +/-inf for empty strata
    if ext_idx:
        empty = keep[:, None, :] == 0.0
        mins = jnp.where(empty, jnp.inf, mins)
        maxs = jnp.where(empty, -jnp.inf, maxs)
    return MegaResult(pop=pop, keep=keep, s1=s1, s2=s2, mins=mins, maxs=maxs, bins=bins)
