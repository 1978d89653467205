"""Jit'd public wrapper for the geohash kernel.

Falls back to interpret mode automatically off-TPU so the same call site
works everywhere; the stratum lookup stays outside the kernel
(``StratumTable.lookup``: one XLA gather into the table's dense cell grid —
dynamic VMEM gathers are not TPU-friendly).
"""

from __future__ import annotations

from ..platform import on_tpu
from .geohash import encode_pallas


def geohash_encode(lat, lon, precision: int, block: int = 2048):
    return encode_pallas(lat, lon, precision, block=block, interpret=not on_tpu())
