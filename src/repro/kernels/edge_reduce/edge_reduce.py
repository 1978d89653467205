"""Pallas kernel: fused multi-column per-stratum moment reduction.

Generalizes ``stratified_stats`` (one column, 3 moment rows) to an entire
fusion group: every fused query column's moment rows are stacked into one

    rows = [ m ; m·y₁ ; m·y₁² ; m·y₂ ; m·y₂² ; … ]        (R, N),  R = 1+2C

matrix and contracted against the one-hot stratum membership tile in a
single MXU pass per (strata-block × points-block) grid cell:

    out[R, S_blk] += rows (R, N_blk) @ onehot (N_blk, S_blk)

so ONE window traversal produces the raw power sums {n, Σy_c, Σy_c²} of
every column at once — the per-column ``jax.ops.segment_sum`` path touches
the window 3·C times.  The count row is shared across columns (it depends
only on the mask), which is where the fused win comes from.

The grid's N dimension revisits the same output block sequentially, so VMEM
holds one (R_pad, S_blk) accumulator plus the one-hot tile.  R is padded to
the f32 sublane multiple (8) so the accumulator tile is layout-aligned; the
zero padding rows contract to zeros and are sliced off host-side.

BlockSpec tiling: N_BLOCK=512 points × S_BLOCK=512 strata -> one-hot tile
512×512 f32 = 1 MiB in VMEM, MXU-aligned (multiples of 128).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Sourced from the shared tiling table (kernels/tiling.py); re-exported
# here so existing `from ...edge_reduce import N_BLOCK` imports keep
# working.  ROW_ALIGN: f32 sublane multiple for the (R, S_blk) tile.
from ..tiling import ROW_ALIGN, kernel_blocks

N_BLOCK, S_BLOCK = kernel_blocks("edge_reduce")


def _moment_rows(values: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Stack [m, m·y_c, m·y_c²] rows for a (C, N) column block -> (1+2C, N).

    The single definition of the row layout shared by the Pallas kernel and
    the segment fast path in ``ops.py`` — the host-side slice offsets (rows
    1..C are Σy, rows C+1..2C are Σy²) depend on this ordering.  The numpy
    oracle in ``ref.py`` mirrors it independently (refs are jax-free).
    """
    m = mask.astype(jnp.float32)
    v = values.astype(jnp.float32)
    my = m[None, :] * v
    return jnp.concatenate([m[None, :], my, my * v], axis=0)


def _reduce_kernel(sidx_ref, rows_ref, out_ref):
    n_step = pl.program_id(1)
    sidx = sidx_ref[...][0]  # (N_blk,)
    s_base = pl.program_id(0) * S_BLOCK
    cols = s_base + jax.lax.broadcasted_iota(jnp.int32, (sidx.shape[0], S_BLOCK), 1)
    onehot = (sidx[:, None] == cols).astype(jnp.float32)
    # value rows must not round to bf16 on the MXU: contract at full f32
    part = jax.lax.dot_general(
        rows_ref[...], onehot, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )  # (R_pad, S_blk)

    @pl.when(n_step == 0)
    def _init():
        out_ref[...] = part

    @pl.when(n_step != 0)
    def _acc():
        out_ref[...] += part


@functools.partial(jax.jit, static_argnames=("num_slots", "interpret"))
def edge_reduce_pallas(
    stratum_idx: jnp.ndarray,
    values: jnp.ndarray,
    mask: jnp.ndarray,
    num_slots: int,
    interpret: bool = False,
):
    """(sidx (N,), values (C, N), mask (N,)) -> (count (S,), s1 (C, S), s2 (C, S)).

    Raw per-stratum power sums of the masked tuples for every column in one
    pass; masked-out points contribute nothing (their rows are zeroed), so
    sampling masks compose directly.  ``S = num_slots`` includes the
    overflow stratum.
    """
    c, n = values.shape
    rows = _moment_rows(values, mask)  # (1+2C, N)
    r = rows.shape[0]
    pad_n = (-n) % N_BLOCK
    pad_r = (-r) % ROW_ALIGN
    s_slots = ((num_slots + S_BLOCK - 1) // S_BLOCK) * S_BLOCK
    # sidx travels as one (1, N) row: a (1, N_BLOCK) block is tiling-legal
    # on the TPU, where a 1-D (N_BLOCK,) block mismatches XLA's layout
    sidx = jnp.pad(stratum_idx.astype(jnp.int32), (0, pad_n), constant_values=-1)[None]
    rows = jnp.pad(rows, ((0, pad_r), (0, pad_n)))
    r_pad = rows.shape[0]
    grid = (s_slots // S_BLOCK, sidx.shape[1] // N_BLOCK)
    out = pl.pallas_call(
        _reduce_kernel,
        out_shape=jax.ShapeDtypeStruct((r_pad, s_slots), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, N_BLOCK), lambda s, i: (0, i)),
            pl.BlockSpec((r_pad, N_BLOCK), lambda s, i: (0, i)),
        ],
        out_specs=pl.BlockSpec((r_pad, S_BLOCK), lambda s, i: (0, s)),
        interpret=interpret,
    )(sidx, rows)
    return out[0, :num_slots], out[1 : 1 + c, :num_slots], out[1 + c : 1 + 2 * c, :num_slots]
