"""Jit'd public wrapper with off-TPU interpret fallback."""

from __future__ import annotations

from ..platform import on_tpu
from .sample_mask import sample_mask_pallas


def sample_mask(stratum_idx, uniforms, fractions):
    return sample_mask_pallas(stratum_idx, uniforms, fractions, interpret=not on_tpu())
