"""Pallas kernel: fused EdgeSOS Bernoulli selection.

Fuses the per-tuple hot loop of Algorithm 1 (bernoulli mode): gather each
tuple's per-stratum fraction f_k, draw keep = (u < f_k), emit the
Horvitz-Thompson weight 1/f_k.  The gather is expressed as a one-hot MXU
contraction (frac[sidx] = onehot(sidx) @ frac) — dynamic VMEM gathers
don't vectorize on the TPU, one-hot matmuls do.

Grid: (N blocks x S blocks); the fraction gather accumulates over the
strata dimension into the (N_blk,) gather row, and the final strata step
applies the threshold + weight.  Uniforms are drawn outside the kernel
(jax.random, counter-based) so the kernel stays deterministic per input.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Sourced from the shared tiling table (kernels/tiling.py); re-exported
# so existing imports of these constants keep working.
from ..tiling import kernel_blocks

N_BLOCK, S_BLOCK = kernel_blocks("sample_mask")


def _select_kernel(sidx_ref, u_ref, frac_ref, mask_ref, w_ref, acc_ref, *, s_steps: int):
    s_step = pl.program_id(1)
    sidx = sidx_ref[...]  # (1, N_blk)
    s_base = s_step * S_BLOCK
    rows = s_base + jax.lax.broadcasted_iota(jnp.int32, (S_BLOCK, sidx.shape[1]), 0)
    onehot = (rows == sidx).astype(jnp.float32)  # (S_blk, N_blk), lane-dense
    # fractions must not round to bf16 on the MXU (0.8 would become
    # 0.80078125 and flip keep decisions): contract at full f32
    part = jax.lax.dot_general(
        frac_ref[...], onehot, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )  # (1, N_blk) gathered fractions from this strata block

    @pl.when(s_step == 0)
    def _init():
        acc_ref[...] = part

    @pl.when(s_step != 0)
    def _acc():
        acc_ref[...] += part

    @pl.when(s_step == s_steps - 1)
    def _emit():
        f = acc_ref[...]
        keep = u_ref[...] < f
        mask_ref[...] = keep.astype(jnp.int32)
        w_ref[...] = jnp.where(keep, 1.0 / jnp.maximum(f, 1e-9), 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sample_mask_pallas(
    stratum_idx: jnp.ndarray,
    uniforms: jnp.ndarray,
    fractions: jnp.ndarray,
    interpret: bool = False,
):
    """(sidx (N,), u (N,), f_k (S,)) -> (mask (N,) bool, weight (N,) f32).

    Every vector travels as one (1, len) row so each block is
    ``(1, multiple of 128)``: tiling-legal on the TPU, where 1-D blocks
    mismatch XLA's layout.
    """
    n = stratum_idx.shape[0]
    s = fractions.shape[0]
    pad_n = (-n) % N_BLOCK
    pad_s = (-s) % S_BLOCK
    sidx = jnp.pad(stratum_idx.astype(jnp.int32), (0, pad_n), constant_values=-1)[None]
    u = jnp.pad(uniforms.astype(jnp.float32), (0, pad_n), constant_values=2.0)[None]
    frac = jnp.pad(fractions.astype(jnp.float32), (0, pad_s))[None]
    s_steps = frac.shape[1] // S_BLOCK
    grid = (sidx.shape[1] // N_BLOCK, s_steps)
    row_n = pl.BlockSpec((1, N_BLOCK), lambda i, s_: (0, i))
    mask, w = pl.pallas_call(
        functools.partial(_select_kernel, s_steps=s_steps),
        out_shape=(
            jax.ShapeDtypeStruct(sidx.shape, jnp.int32),
            jax.ShapeDtypeStruct(sidx.shape, jnp.float32),
        ),
        grid=grid,
        in_specs=[row_n, row_n, pl.BlockSpec((1, S_BLOCK), lambda i, s_: (0, s_))],
        out_specs=(row_n, row_n),
        scratch_shapes=[pltpu.VMEM((1, N_BLOCK), jnp.float32)],
        interpret=interpret,
    )(sidx, u, frac)
    return mask[0, :n].astype(jnp.bool_), w[0, :n]
