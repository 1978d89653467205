"""Shared neural layers: norms, rotary embeddings, attention, MLP, losses.

Attention is implemented in a chunked-causal form (static unroll over query
chunks, each attending to its exact causal prefix) so that:
  * peak memory is one (q_chunk x prefix) score block, never (S x S);
  * HLO FLOPs match the causal optimum (no masked-away wasted half), which
    keeps the roofline "useful compute" ratio honest;
  * a sliding-window variant falls out by bounding the prefix slice.
The same entry point later swaps in the Pallas flash kernel on TPU.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


from .base import ModelConfig, ParamSpec


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_spec(d: int) -> ParamSpec:
    return ParamSpec((d,), jnp.float32, (None,), init="ones")


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * scale
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (RoPE + M-RoPE)
# ---------------------------------------------------------------------------


def _rope_angles(positions: jnp.ndarray, dh: int, theta: float) -> jnp.ndarray:
    """positions (..., S) -> angles (..., S, dh//2), f32."""
    half = dh // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    return positions.astype(jnp.float32)[..., None] * inv_freq


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, dh); positions: (B, S). Half-split (LLaMA) convention."""
    dh = x.shape[-1]
    ang = _rope_angles(positions, dh, theta)  # (B, S, dh/2)
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def apply_mrope(
    x: jnp.ndarray, positions: jnp.ndarray, sections: tuple[int, ...], theta: float
) -> jnp.ndarray:
    """Qwen2-VL multimodal RoPE.

    positions: (3, B, S) — temporal / height / width position streams.
    sections: per-stream share of the rotary half-dim (sum == dh//2).
    """
    dh = x.shape[-1]
    half = dh // 2
    assert sum(sections) == half, (sections, half)
    sec_id = jnp.repeat(
        jnp.arange(len(sections)), jnp.array(sections), total_repeat_length=half
    )  # (half,) which stream drives each rotary dim
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    pos_sel = jnp.take(positions, sec_id, axis=0)  # (half, B, S)
    ang = jnp.moveaxis(pos_sel, 0, -1).astype(jnp.float32) * inv_freq  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _sdpa_block(q, k, v, *, causal_offset: int | None, scale: float):
    """One (q_block x kv_prefix) attention block, f32 softmax.

    q: (B, Q, H, dh); k/v: (B, T, K, dh) with H = K * G (GQA).
    causal_offset: absolute position of q[0] minus position of k[0];
      None -> no causal mask (full prefix is visible).
    """
    B, Q, H, dh = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, Q, K, G, dh)
    # bf16 operands, f32 accumulate (MXU-native; also prevents XLA:CPU from
    # materializing f32 copies of the operands)
    scores = jnp.einsum("bqkgd,btkd->bkgqt", qg, k, preferred_element_type=jnp.float32) * scale
    if causal_offset is not None:
        qpos = jnp.arange(Q)[:, None] + causal_offset
        kpos = jnp.arange(T)[None, :]
        mask = qpos >= kpos
        scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqt,btkd->bqkgd", w, v)
    return out.reshape(B, Q, H, dh)


def chunked_causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    q_chunk: int = 1024,
    window: int = 0,
) -> jnp.ndarray:
    """Causal (optionally sliding-window) attention, chunked over queries.

    Static unroll: chunk i attends to the exact prefix slice it can see, so
    compiled FLOPs equal the causal optimum and peak memory is one block.
    """
    B, S, H, dh = q.shape
    scale = 1.0 / (dh**0.5)
    if S <= q_chunk:
        return _sdpa_block(q, k, v, causal_offset=0, scale=scale)
    assert S % q_chunk == 0, (S, q_chunk)
    outs = []
    for i in range(S // q_chunk):
        q_start = i * q_chunk
        kv_end = q_start + q_chunk
        kv_start = 0 if window <= 0 else max(0, kv_end - window - q_chunk)
        qi = jax.lax.slice_in_dim(q, q_start, q_start + q_chunk, axis=1)
        ki = jax.lax.slice_in_dim(k, kv_start, kv_end, axis=1)
        vi = jax.lax.slice_in_dim(v, kv_start, kv_end, axis=1)
        outs.append(_sdpa_block(qi, ki, vi, causal_offset=q_start - kv_start, scale=scale))
    return jnp.concatenate(outs, axis=1)


def chunked_full_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *, q_chunk: int = 1024
) -> jnp.ndarray:
    """Bidirectional attention chunked over queries (encoder / cross-attn)."""
    B, S, H, dh = q.shape
    scale = 1.0 / (dh**0.5)
    if S <= q_chunk:
        return _sdpa_block(q, k, v, causal_offset=None, scale=scale)
    assert S % q_chunk == 0, (S, q_chunk)
    outs = []
    for i in range(S // q_chunk):
        qi = jax.lax.slice_in_dim(q, i * q_chunk, (i + 1) * q_chunk, axis=1)
        outs.append(_sdpa_block(qi, k, v, causal_offset=None, scale=scale))
    return jnp.concatenate(outs, axis=1)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray, length) -> jnp.ndarray:
    """Single-token attention against a cache.

    q: (B, 1, H, dh); caches: (B, T, K, dh); length: (B,) or scalar valid len.
    """
    B, _, H, dh = q.shape
    T, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = 1.0 / (dh**0.5)
    qg = q.reshape(B, K, G, dh)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg, k_cache, preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(T)[None, :] < jnp.reshape(jnp.asarray(length), (-1, 1))
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgt,btkd->bkgd", w, v_cache)
    return out.reshape(B, 1, H, dh)


def sharded_decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    length,
    k_new: jnp.ndarray | None = None,
    v_new: jnp.ndarray | None = None,
    write_at=None,
):
    """Flash-decode over a *sequence-sharded* cache (GQA kv < model axis).

    Each model shard computes attention over its local cache chunk plus a
    local log-sum-exp; partials combine with one psum (max-shifted), so the
    cache is never all-gathered.  The naive GSPMD lowering gathers
    B_local x T x K x dh per layer — see EXPERIMENTS.md §Perf iteration 6.

    When (k_new, v_new, write_at) are given, the cache update also happens
    *inside* the shard_map: only the shard owning the write position
    touches its chunk, and the updated cache is returned seq-sharded —
    GSPMD's dynamic-update-slice on a sharded dim would otherwise gather/
    re-scatter the whole cache (§Perf iteration 8).  Returns
    (out, k_cache', v_cache') in that case, else just out.

    q heads are model-sharded (from the head-sharded projections); every
    shard holds all K kv heads for its sequence chunk, so head-group
    lookups stay local.
    """
    from ..sharding.logical import active_rules

    rules = active_rules()
    mesh = rules.mesh if rules is not None else None
    fused_update = k_new is not None
    if mesh is None or mesh.shape.get("model", 1) <= 1:
        if fused_update:
            k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new.astype(k_cache.dtype), write_at, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new.astype(v_cache.dtype), write_at, axis=1)
            return decode_attention(q, k_cache, v_cache, length), k_cache, v_cache
        return decode_attention(q, k_cache, v_cache, length)
    tp = mesh.shape["model"]
    B, _, H, dh = q.shape
    T, K = k_cache.shape[1], k_cache.shape[2]
    if T % tp != 0:
        if fused_update:
            k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new.astype(k_cache.dtype), write_at, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new.astype(v_cache.dtype), write_at, axis=1)
            return decode_attention(q, k_cache, v_cache, length), k_cache, v_cache
        return decode_attention(q, k_cache, v_cache, length)
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    if B % max(1, _prod(mesh.shape[a] for a in dp)) != 0:
        bspec = None
    G = H // K
    scale = 1.0 / (dh**0.5)

    def local(qh, kc, vc, kn, vn, ln, wa):
        # qh: (B, 1, H_loc, dh); kc/vc: (B, T_loc, K, dh); kn/vn: (B,1,K,dh)
        t_loc = kc.shape[1]
        off = jax.lax.axis_index("model") * t_loc
        if kn is not None:
            # write lands in exactly one shard's chunk
            local_wa = jnp.clip(wa - off, 0, t_loc - 1)
            mine = (wa >= off) & (wa < off + t_loc)
            kc = jnp.where(
                mine,
                jax.lax.dynamic_update_slice_in_dim(kc, kn.astype(kc.dtype), local_wa, axis=1),
                kc,
            )
            vc = jnp.where(
                mine,
                jax.lax.dynamic_update_slice_in_dim(vc, vn.astype(vc.dtype), local_wa, axis=1),
                vc,
            )
        # q is replicated across the model axis (it's one token — tiny);
        # every shard computes ALL heads over ITS sequence chunk, so the
        # LSE-combine psum below is exact.  Sharding heads too would leave
        # each shard a diagonal (heads_i x chunk_i) block — wrong.
        kv_of_head = jnp.arange(qh.shape[2]) // G  # (H,)
        ksel = jnp.take(kc, kv_of_head, axis=2)  # (B, T_loc, h_loc, dh)
        vsel = jnp.take(vc, kv_of_head, axis=2)
        s = jnp.einsum("bhd,bthd->bht", qh[:, 0], ksel, preferred_element_type=jnp.float32) * scale
        pos = off + jnp.arange(t_loc)[None, None, :]
        s = jnp.where(pos < jnp.reshape(jnp.asarray(ln), (-1, 1, 1)), s, -1e30)
        m_loc = jnp.max(s, axis=-1)  # (B, h_loc)
        m_glob = jax.lax.pmax(m_loc, "model")
        p = jnp.exp(s - m_glob[..., None])
        l_loc = jnp.sum(p, axis=-1)
        o_loc = jnp.einsum("bht,bthd->bhd", p.astype(vsel.dtype), vsel)
        l_glob = jax.lax.psum(l_loc, "model")
        o_glob = jax.lax.psum(o_loc.astype(jnp.float32), "model")
        out = o_glob / jnp.maximum(l_glob, 1e-30)[..., None]
        out = out.astype(vc.dtype)[:, None]  # (B, 1, h_loc, dh)
        if kn is not None:
            return out, kc, vc
        return out

    qspec = P(bspec, None, None, None)  # replicated over model (see local)
    cspec = P(bspec, "model", None, None)
    if fused_update:
        mapped = jax.shard_map(
            lambda qh, kc, vc, kn, vn, ln, wa: local(qh, kc, vc, kn, vn, ln, wa),
            mesh=mesh,
            in_specs=(qspec, cspec, cspec, P(bspec, None, None, None), P(bspec, None, None, None), P(), P()),
            out_specs=(qspec, cspec, cspec),
            check_vma=False,
        )
        return mapped(q, k_cache, v_cache, k_new, v_new, jnp.asarray(length), jnp.asarray(write_at))
    mapped = jax.shard_map(
        lambda qh, kc, vc, ln: local(qh, kc, vc, None, None, ln, None),
        mesh=mesh,
        in_specs=(qspec, cspec, cspec, P()),
        out_specs=qspec,
        check_vma=False,
    )
    return mapped(q, k_cache, v_cache, jnp.asarray(length))


def _prod(it):
    n = 1
    for x in it:
        n *= x
    return n


class KVCache(NamedTuple):
    k: jnp.ndarray  # (B, T, K, dh)
    v: jnp.ndarray  # (B, T, K, dh)
    pos: jnp.ndarray  # scalar int32 — tokens already in cache


def cache_update(cache: KVCache, k_new: jnp.ndarray, v_new: jnp.ndarray) -> KVCache:
    """Append k/v (B, n, K, dh) at cache.pos (same pos across batch)."""
    k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new.astype(cache.k.dtype), cache.pos, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new.astype(cache.v.dtype), cache.pos, axis=1)
    return KVCache(k=k, v=v, pos=cache.pos + k_new.shape[1])


# ---------------------------------------------------------------------------
# Attention layer (projections + rope + attention)
# ---------------------------------------------------------------------------


def attention_specs(cfg: ModelConfig, d_model: int | None = None) -> dict:
    d = d_model or cfg.d_model
    dh, H, K = cfg.dh, cfg.num_heads, cfg.num_kv_heads
    spec = {
        "wq": ParamSpec((d, H, dh), cfg.param_dtype, ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, K, dh), cfg.param_dtype, ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, K, dh), cfg.param_dtype, ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((H, dh, d), cfg.param_dtype, ("heads", "head_dim", "embed"), init="scaled"),
    }
    if cfg.qkv_bias:
        spec["bq"] = ParamSpec((H, dh), cfg.param_dtype, ("heads", "head_dim"), init="zeros")
        spec["bk"] = ParamSpec((K, dh), cfg.param_dtype, ("kv_heads", "head_dim"), init="zeros")
        spec["bv"] = ParamSpec((K, dh), cfg.param_dtype, ("kv_heads", "head_dim"), init="zeros")
    return spec


def attention_qkv(p: dict, x: jnp.ndarray, cfg: ModelConfig):
    from ..sharding.logical import constrain

    dt = cfg.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    # Megatron TP: inside the block, heads are model-sharded and seq is
    # gathered — without this, SP seq-sharding propagates into the matmuls
    # and GSPMD replicates the weights instead (measured: f32 full-weight
    # all-gathers; §Perf iteration 3).  Only when heads divide the model
    # axis: an explicit constraint whose dim doesn't divide would PIN
    # replication, which regressed granite (24 heads on 16) to 205 GiB.
    from ..sharding.logical import mesh_axis_size

    if cfg.num_heads % max(mesh_axis_size("model"), 1) == 0:
        q = constrain(q, ("batch", None, "act_heads", None))
        k = constrain(k, ("batch", None, "act_heads", None))
        v = constrain(v, ("batch", None, "act_heads", None))
    return q, k, v


def attention_out(p: dict, o: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(cfg.dtype))


def self_attention(
    p: dict,
    x: jnp.ndarray,
    cfg: ModelConfig,
    positions: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
) -> jnp.ndarray:
    """Full-sequence self-attention (train / prefill)."""
    q, k, v = attention_qkv(p, x, cfg)
    if cfg.mrope_sections:
        q = apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta)
    else:
        pos2d = positions if positions.ndim == 2 else positions[0]
        q = apply_rope(q, pos2d, cfg.rope_theta)
        k = apply_rope(k, pos2d, cfg.rope_theta)
    if causal:
        o = chunked_causal_attention(q, k, v, q_chunk=min(cfg.chunk_size * 4, q.shape[1]), window=window)
    else:
        o = chunked_full_attention(q, k, v, q_chunk=min(cfg.chunk_size * 4, q.shape[1]))
    return attention_out(p, o, cfg)


def cross_attention_specs(cfg: ModelConfig) -> dict:
    return attention_specs(cfg)


def cross_attention(p: dict, x: jnp.ndarray, memory: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    dt = cfg.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", memory, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", memory, p["wv"].astype(dt))
    o = chunked_full_attention(q, k, v, q_chunk=1024)
    return attention_out(p, o, cfg)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_specs(cfg: ModelConfig, d_ff: int | None = None, gated: bool = True) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    spec = {
        "w_up": ParamSpec((d, f), cfg.param_dtype, ("embed", "mlp")),
        "w_down": ParamSpec((f, d), cfg.param_dtype, ("mlp", "embed"), init="scaled"),
    }
    if gated:
        spec["w_gate"] = ParamSpec((d, f), cfg.param_dtype, ("embed", "mlp"))
    return spec


def mlp(p: dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    from ..sharding.logical import constrain, mesh_axis_size

    dt = cfg.dtype
    d_ff = p["w_up"].shape[-1]
    tp_ok = d_ff % max(mesh_axis_size("model"), 1) == 0
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
    if tp_ok:
        up = constrain(up, ("batch", None, "act_mlp"))
    if "w_gate" in p:
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
        if tp_ok:
            gate = constrain(gate, ("batch", None, "act_mlp"))
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up)
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt))


# ---------------------------------------------------------------------------
# Embedding / unembedding / loss
# ---------------------------------------------------------------------------


def embedding_specs(cfg: ModelConfig) -> dict:
    spec = {"tok": ParamSpec((cfg.padded_vocab, cfg.d_model), cfg.param_dtype, ("vocab", "embed"), init="embed")}
    if not cfg.tie_embeddings:
        spec["unembed"] = ParamSpec((cfg.d_model, cfg.padded_vocab), cfg.param_dtype, ("embed", "vocab"))
    return spec


def embed_tokens(p: dict, tokens: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    return jnp.take(p["tok"].astype(cfg.dtype), tokens, axis=0)


def logits_fn(p: dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        w = p["tok"].astype(cfg.dtype).T
    else:
        w = p["unembed"].astype(cfg.dtype)
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    if cfg.padded_vocab != cfg.vocab_size:
        pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad, -1e30, logits)
    return logits


def weighted_ce(
    logits: jnp.ndarray,
    targets: jnp.ndarray,
    seq_weight: jnp.ndarray | None = None,
    token_mask: jnp.ndarray | None = None,
):
    """Cross-entropy with EdgeSOS Horvitz-Thompson sequence weights.

    logits (B, S, V) / targets (B, S) / seq_weight (B,) / token_mask (B, S).
    Returns (loss, per_seq_ce) where loss is the HT-weighted mean so the
    estimate is unbiased for the *unsampled* stream (paper eq 3 applied to
    the training loss), and per_seq_ce feeds the stratified telemetry.
    """
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ce = lse - tgt  # (B, S)
    if token_mask is None:
        token_mask = jnp.ones_like(ce, dtype=jnp.float32)
    else:
        token_mask = token_mask.astype(jnp.float32)
    per_seq = jnp.sum(ce * token_mask, axis=-1) / jnp.maximum(jnp.sum(token_mask, axis=-1), 1.0)
    if seq_weight is None:
        seq_weight = jnp.ones(ce.shape[0], jnp.float32)
    denom = jnp.maximum(jnp.sum(seq_weight * jnp.sum(token_mask, -1)), 1.0)
    loss = jnp.sum(seq_weight[:, None] * ce * token_mask) / denom
    return loss, per_seq
