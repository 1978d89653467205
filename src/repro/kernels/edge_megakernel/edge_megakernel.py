"""Pallas kernel: single-traversal fused edge pass.

The edge program used to walk a pane through four kernels — ``geohash``
encode, stratify ``assign``, ``sample_mask``, ``edge_reduce`` — with the
quantile-sketch binning done outside any kernel, re-touching HBM between
every stage.  This kernel fuses the whole per-tuple pipeline into ONE
pass: raw tuples go in, per-stratum sufficient-stat rows come out, and
the intermediate ``code``/``sidx``/``mask``/one-hot arrays never exist
outside VMEM.

Per (member × strata-block × points-block) grid cell:

    code    = morton(lat, lon)                      (latlon mode, in-VMEM)
    member  = code[:, None] == codes_tile[None, :]  -- or sidx == iota
    t_i     = Σ_s member · thr_tile                 (per-tuple threshold)
    keep_i  = ok_i · (score_i < t_i)
    rows    = [ok; keep; keep·y_c; keep·y_c²]       (2+2C, N_blk)
    out    += rows @ member                          (MXU, f32 accumulate)
    mins/maxs over where(member·keep, y, ±inf)       (extrema columns)
    bins   += (member·keep)ᵀ @ binhot                (sketch columns)

Sampling is a unified threshold compare: Bernoulli passes uniform scores
and per-stratum fraction thresholds; SRS passes within-stratum ranks and
allotted counts ``n_k`` (exact in f32 below 2²⁴); raw keep-all passes
zeros against ones.  Scores are non-negative, so the zero threshold a
tuple gathers in every strata block it is *not* a member of can never
produce a spurious keep.

Two membership modes:

* ``latlon`` — full fusion: the Morton encode of :mod:`...core.geohash`
  runs inside the kernel and membership is an equality test against the
  (sorted, unique) stratum code table tile.  Codes absent from the table
  (the overflow stratum) match nothing; the wrapper in ``ops.py``
  reconstructs overflow counts as residuals and leaves overflow *stat*
  rows zero — sound because the query layer zeroes overflow stats before
  estimating.
* ``sidx`` — a precomputed stratum index per tuple (SRS needs the sort
  for ranks anyway); all ``num_slots`` slots, overflow included, are
  covered exactly.

Inputs may arrive in a reduced-precision staging dtype (the pipeline
stages bf16 when configured); the kernel immediately casts value blocks
to f32 — every accumulator, dot and compare is f32.  This file never
names a reduced dtype: staging is the caller's choice, accumulation is
not (EDG004).

BlockSpec tiling: N_BLOCK×S_BLOCK from kernels/tiling.py (default
512×512).  VMEM per cell ≈ member + keep-weighted member (2 MiB) +
per-sketch-column binhot/out tiles (~2.6 MiB each); for many sketch
columns shrink S_BLOCK via ``tiling.set_block_override``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.estimators import SKETCH_NUM_BINS, sketch_bin_index
from ...core.geohash import encode
from ..tiling import ROW_ALIGN, kernel_blocks

# Sketch bin axis padded to the TPU lane width for the (S_blk, B_PAD)
# MXU output tile; the zero pad bins are sliced off host-side.
BINS_PAD = 128 * (-(-SKETCH_NUM_BINS // 128))

# Code-table pad sentinel: real geohash Morton codes fit in 30 bits
# (precision <= 6), so an all-ones uint32 can never match an encode.
CODE_SENTINEL = 0xFFFFFFFF


class MegaResult(NamedTuple):
    """Per-member per-stratum sufficient stats from one fused traversal.

    ``pop``/``keep`` are ok-tuple and kept-tuple counts per slot; ``s1``/
    ``s2`` are kept-tuple power sums per value column; ``mins``/``maxs``
    cover the extrema columns (identity ±inf where no tuple was kept);
    ``bins`` the sketch columns' kept-count log-histograms.
    """

    pop: jnp.ndarray  # (M, S) f32
    keep: jnp.ndarray  # (M, S) f32
    s1: jnp.ndarray  # (M, C, S) f32
    s2: jnp.ndarray  # (M, C, S) f32
    mins: jnp.ndarray  # (M, E, S) f32
    maxs: jnp.ndarray  # (M, E, S) f32
    bins: jnp.ndarray  # (M, K, S, SKETCH_NUM_BINS) f32


def _fused_body(
    n_step,
    member,
    vals,
    okv,
    keepv,
    out_refs,
    *,
    num_ext: int,
    num_sk: int,
    ext_idx: tuple,
    sk_idx: tuple,
    r_pad: int,
):
    """Shared stat emission given the (N_blk, S_blk) membership tile."""
    c = vals.shape[0]
    kv = keepv[None, :] * vals  # (C, N_blk)
    rows = jnp.concatenate([okv[None, :], keepv[None, :], kv, kv * vals], axis=0)
    r = rows.shape[0]
    if r_pad > r:
        rows = jnp.concatenate(
            [rows, jnp.zeros((r_pad - r, rows.shape[1]), jnp.float32)], axis=0
        )
    # value rows must not round to bf16 on the MXU: contract at full f32
    part = jax.lax.dot_general(
        rows, member, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )  # (r_pad, S_blk)

    rows_ref = out_refs[0]
    nxt = 1
    mk = member * keepv[:, None]  # (N_blk, S_blk) kept membership
    if num_ext:
        mins_ref, maxs_ref = out_refs[1:3]
        nxt = 3
        kept = mk > 0.0
        mins_part = jnp.stack(
            [jnp.min(jnp.where(kept, vals[e][:, None], jnp.inf), axis=0) for e in ext_idx]
        )
        maxs_part = jnp.stack(
            [jnp.max(jnp.where(kept, vals[e][:, None], -jnp.inf), axis=0) for e in ext_idx]
        )
    bins_parts = []
    for k in sk_idx:
        b = sketch_bin_index(vals[k])  # (N_blk,) int32
        iota_b = jax.lax.broadcasted_iota(jnp.int32, (b.shape[0], BINS_PAD), 1)
        binhot = (b[:, None] == iota_b).astype(jnp.float32)
        bins_parts.append(
            # 0/1 operands are exact in one bf16 pass: default precision
            jax.lax.dot_general(
                mk, binhot, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )  # (S_blk, BINS_PAD)
        )

    @pl.when(n_step == 0)
    def _init():
        rows_ref[...] = part[None]
        if num_ext:
            mins_ref[...] = mins_part[None]
            maxs_ref[...] = maxs_part[None]
        for i in range(num_sk):
            out_refs[nxt + i][...] = bins_parts[i][None]

    @pl.when(n_step != 0)
    def _acc():
        rows_ref[...] += part[None]
        if num_ext:
            mins_ref[...] = jnp.minimum(mins_ref[...], mins_part[None])
            maxs_ref[...] = jnp.maximum(maxs_ref[...], maxs_part[None])
        for i in range(num_sk):
            out_refs[nxt + i][...] += bins_parts[i][None]


def _threshold_keep(member, okv, scores, thr_tile):
    """Per-tuple gathered threshold -> keep weights (N_blk,) f32."""
    t = jnp.sum(member * thr_tile[None, :], axis=1)  # 0 off-membership
    return okv * (scores < t).astype(jnp.float32)


def _mega_kernel_latlon(
    lat_ref, lon_ref, codes_ref, vals_ref, ok_ref, scores_ref, thr_ref, *out_refs, spec
):
    n_step = pl.program_id(2)
    code = encode(
        lat_ref[...][0].astype(jnp.float32), lon_ref[...][0].astype(jnp.float32), spec["precision"]
    )
    member = (code[:, None] == codes_ref[...]).astype(jnp.float32)
    vals = vals_ref[...].astype(jnp.float32)
    okv = ok_ref[...][0].astype(jnp.float32)
    keepv = _threshold_keep(member, okv, scores_ref[...][0].astype(jnp.float32), thr_ref[...][0])
    _fused_body(
        n_step, member, vals, okv, keepv, out_refs,
        num_ext=spec["num_ext"], num_sk=spec["num_sk"],
        ext_idx=spec["ext_idx"], sk_idx=spec["sk_idx"], r_pad=spec["r_pad"],
    )


def _mega_kernel_sidx(
    sidx_ref, vals_ref, ok_ref, scores_ref, thr_ref, *out_refs, spec
):
    n_step = pl.program_id(2)
    sidx = sidx_ref[...][0]  # (N_blk,) int32
    s_base = pl.program_id(1) * spec["s_block"]
    cols = s_base + jax.lax.broadcasted_iota(jnp.int32, (sidx.shape[0], spec["s_block"]), 1)
    member = (sidx[:, None] == cols).astype(jnp.float32)
    vals = vals_ref[...].astype(jnp.float32)
    okv = ok_ref[...][0].astype(jnp.float32)
    keepv = _threshold_keep(member, okv, scores_ref[...][0].astype(jnp.float32), thr_ref[...][0])
    _fused_body(
        n_step, member, vals, okv, keepv, out_refs,
        num_ext=spec["num_ext"], num_sk=spec["num_sk"],
        ext_idx=spec["ext_idx"], sk_idx=spec["sk_idx"], r_pad=spec["r_pad"],
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_slots", "precision", "ext_idx", "sk_idx", "n_block", "s_block", "interpret",
    ),
)
def edge_megakernel_pallas(
    vals: jnp.ndarray,  # (C, N) any float dtype (bf16 staging allowed)
    ok: jnp.ndarray,  # (M, N) validity & ROI, 0/1
    scores: jnp.ndarray,  # (M, N) f32, >= 0
    thresholds: jnp.ndarray,  # (M, num_slots) f32 per-slot thresholds
    num_slots: int,
    *,
    sidx: jnp.ndarray | None = None,  # (M, N) int32 ("sidx" mode)
    lat: jnp.ndarray | None = None,  # (N,) ("latlon" mode)
    lon: jnp.ndarray | None = None,
    codes: jnp.ndarray | None = None,  # (num_strata,) sorted uint32 table
    precision: int | None = None,
    ext_idx: tuple = (),
    sk_idx: tuple = (),
    n_block: int | None = None,
    s_block: int | None = None,
    interpret: bool = False,
) -> MegaResult:
    """One fused traversal -> :class:`MegaResult` (see module docstring).

    In latlon mode the code table covers ``codes.shape[0]`` strata; slots
    ``>= codes.shape[0]`` of the output (the overflow slot among them)
    stay zero / ±inf and the caller owns the residual overflow counts.
    """
    if n_block is None or s_block is None:
        dn, ds = kernel_blocks("edge_megakernel")
        n_block = n_block or dn
        s_block = s_block or ds
    c, n = vals.shape
    m = ok.shape[0]
    r = 2 + 2 * c
    r_pad = ((r + ROW_ALIGN - 1) // ROW_ALIGN) * ROW_ALIGN
    num_ext, num_sk = len(ext_idx), len(sk_idx)

    pad_n = (-n) % n_block
    s_pad = ((num_slots + s_block - 1) // s_block) * s_block
    n_tot = n + pad_n
    grid = (m, s_pad // s_block, n_tot // n_block)

    # Per-member rows travel as (M, 1, N) with the member axis squeezed out
    # of the block, and per-tuple / per-slot vectors as (1, N) rows: every
    # block's last two dims are then (1 == full extent, multiple of 128),
    # which is the TPU tiling rule for any M and matches XLA's layout.
    def member_rows(x, width, fill=0):
        x = jnp.pad(x, ((0, 0), (0, width - x.shape[1])), constant_values=fill)
        return x.reshape(m, 1, width)

    member_n = pl.BlockSpec((None, 1, n_block), lambda m_, s, i: (m_, 0, i))
    vals_p = jnp.pad(vals, ((0, 0), (0, pad_n)))
    ok_p = member_rows(ok.astype(jnp.float32), n_tot)
    scores_p = member_rows(scores.astype(jnp.float32), n_tot)
    thr_p = member_rows(thresholds.astype(jnp.float32), s_pad)
    shared_specs = [
        pl.BlockSpec((c, n_block), lambda m_, s, i: (0, i)),
        member_n,
        member_n,
        pl.BlockSpec((None, 1, s_block), lambda m_, s, i: (m_, 0, s)),
    ]

    spec = dict(
        precision=precision, num_ext=num_ext, num_sk=num_sk,
        ext_idx=tuple(ext_idx), sk_idx=tuple(sk_idx), r_pad=r_pad, s_block=s_block,
    )
    if sidx is not None:
        kern = functools.partial(_mega_kernel_sidx, spec=spec)
        ins = [member_rows(sidx.astype(jnp.int32), n_tot, fill=-1), vals_p, ok_p, scores_p, thr_p]
        in_specs = [member_n] + shared_specs
    else:
        if lat is None or lon is None or codes is None or precision is None:
            raise ValueError("latlon mode needs lat, lon, codes and precision")
        kern = functools.partial(_mega_kernel_latlon, spec=spec)
        codes_p = jnp.pad(
            codes.astype(jnp.uint32), (0, s_pad - codes.shape[0]),
            constant_values=jnp.asarray(CODE_SENTINEL, jnp.uint32),
        )
        ins = [
            jnp.pad(lat.astype(jnp.float32), (0, pad_n)).reshape(1, n_tot),
            jnp.pad(lon.astype(jnp.float32), (0, pad_n)).reshape(1, n_tot),
            codes_p.reshape(1, s_pad), vals_p, ok_p, scores_p, thr_p,
        ]
        row_n = pl.BlockSpec((1, n_block), lambda m_, s, i: (0, i))
        in_specs = [row_n, row_n, pl.BlockSpec((1, s_block), lambda m_, s, i: (0, s))]
        in_specs += shared_specs

    out_shape = [jax.ShapeDtypeStruct((m, r_pad, s_pad), jnp.float32)]
    out_specs = [pl.BlockSpec((1, r_pad, s_block), lambda m_, s, i: (m_, 0, s))]
    if num_ext:
        for _ in range(2):
            out_shape.append(jax.ShapeDtypeStruct((m, num_ext, s_pad), jnp.float32))
            out_specs.append(pl.BlockSpec((1, num_ext, s_block), lambda m_, s, i: (m_, 0, s)))
    for _ in range(num_sk):
        out_shape.append(jax.ShapeDtypeStruct((m, s_pad, BINS_PAD), jnp.float32))
        out_specs.append(pl.BlockSpec((1, s_block, BINS_PAD), lambda m_, s, i: (m_, s, 0)))

    outs = pl.pallas_call(
        kern,
        out_shape=tuple(out_shape),
        grid=grid,
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        interpret=interpret,
    )(*ins)

    rows = outs[0]
    nxt = 1
    if num_ext:
        mins = outs[1][:, :, :num_slots]
        maxs = outs[2][:, :, :num_slots]
        nxt = 3
    else:
        mins = jnp.zeros((m, 0, num_slots), jnp.float32)
        maxs = jnp.zeros((m, 0, num_slots), jnp.float32)
    if num_sk:
        bins = jnp.stack(
            [outs[nxt + i][:, :num_slots, :SKETCH_NUM_BINS] for i in range(num_sk)],
            axis=1,
        )
    else:
        bins = jnp.zeros((m, 0, num_slots, SKETCH_NUM_BINS), jnp.float32)
    return MegaResult(
        pop=rows[:, 0, :num_slots],
        keep=rows[:, 1, :num_slots],
        s1=rows[:, 2 : 2 + c, :num_slots],
        s2=rows[:, 2 + c : 2 + 2 * c, :num_slots],
        mins=mins,
        maxs=maxs,
        bins=bins,
    )
