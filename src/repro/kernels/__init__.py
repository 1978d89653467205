"""Pallas TPU kernels for the compute hot-spots the paper optimizes.

The paper's edge binary optimizes (a) geohash computation + neighborhood
lookup and (b) parallel per-stratum grouping/sampling — its rayon/FxHash
hot loop.  On TPU those become:

  geohash/          fused quantize + Morton interleave (VPU integer)
  stratified_stats/ per-stratum {count, Σy, Σy²} as blocked one-hot
                    matmuls on the MXU (hash-aggregation replacement)
  edge_reduce/      the multi-column generalization of stratified_stats:
                    one (1+2C, N_blk) @ onehot MXU pass yields every fused
                    query column's moments — the preagg hot path behind
                    ``PipelineConfig(backend="pallas")``
  sample_mask/      fused per-stratum threshold gather (one-hot MXU) +
                    Bernoulli keep mask + Horvitz-Thompson weights
  edge_megakernel/  the single-traversal fusion of the whole per-tuple
                    pipeline: in-kernel geohash + stratify + threshold
                    sampling + moments/extrema/sketch stat rows in ONE
                    pass — the hot path behind
                    ``PipelineConfig(backend="fused")``

Every kernel has ops.py (jit'd wrapper with an interpret switch) and
ref.py (pure-jnp oracle); tests sweep shapes/dtypes in interpret mode and
assert allclose against the oracle.  Block tilings live in tiling.py
(single source, override hook for TPU tuning).
"""

from . import (
    edge_megakernel,
    edge_reduce,
    geohash,
    sample_mask,
    stratified_stats,
    tiling,
)

__all__ = [
    "edge_megakernel",
    "edge_reduce",
    "geohash",
    "sample_mask",
    "stratified_stats",
    "tiling",
]
