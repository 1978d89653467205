"""Public wrapper: layout/GQA handling + padding + interpret fallback."""

from __future__ import annotations

import jax.numpy as jnp

from ..platform import on_tpu
from .flash_attention import KV_BLOCK, Q_BLOCK, flash_attention_pallas


def flash_attention(q, k, v):
    """q: (B, S, H, dh); k/v: (B, S, K, dh); causal. Returns (B, S, H, dh).

    Pads head_dim to a 128 multiple and seq to the block size; GQA is
    resolved inside the kernel's BlockSpec index maps.
    """
    B, S, H, dh = q.shape
    K = k.shape[2]
    G = H // K
    scale = 1.0 / (dh**0.5)
    dh_p = ((dh + 127) // 128) * 128
    s_p = ((S + max(Q_BLOCK, KV_BLOCK) - 1) // max(Q_BLOCK, KV_BLOCK)) * max(Q_BLOCK, KV_BLOCK)

    def prep(x, heads):
        x = jnp.pad(x, ((0, 0), (0, s_p - S), (0, 0), (0, dh_p - dh)))
        return x.transpose(0, 2, 1, 3).reshape(B * heads, s_p, dh_p)

    qf = prep(q, H)
    kf = prep(k, K)
    vf = prep(v, K)
    interpret = not on_tpu()
    o = flash_attention_pallas(qf, kf, vf, groups=G, scale=scale, interpret=interpret)
    o = o.reshape(B, H, s_p, dh_p).transpose(0, 2, 1, 3)
    return o[:, :S, :, :dh]
