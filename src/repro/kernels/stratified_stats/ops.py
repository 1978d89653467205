"""Jit'd public wrapper with off-TPU interpret fallback."""

from __future__ import annotations

from ..platform import on_tpu
from .stratified_stats import stratified_stats_pallas


def stratified_stats(stratum_idx, values, mask, num_slots: int):
    interpret = not on_tpu()
    return stratified_stats_pallas(stratum_idx, values, mask, num_slots, interpret=interpret)
