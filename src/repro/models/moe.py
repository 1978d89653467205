"""Mixture-of-Experts FFN with shuffle-free expert parallelism.

Dispatch is the same rank-in-group primitive EdgeSOS uses for within-stratum
sampling (one stable sort + segment offsets): "experts" are strata and
capacity clipping is per-stratum allocation.

Distribution (the paper's routing idea applied to EP): activations are
data-sharded and *replicated over the model axis*, so each model shard can
gather the assignments of its own experts locally — no token all-to-all at
all.  Each shard computes its experts' contributions to all local tokens and
a single psum over the model axis combines them.  Under shard_map this is
explicit and GSPMD cannot de-optimize it into gathers (the naive jit
lowering of scatter-based dispatch replicated the (E*C, d) buffer and blew
past HBM — see EXPERIMENTS.md §Perf for the before/after).

Two sharding modes, picked by divisibility:
  * E %% tp == 0  -> experts sharded over "model" (true EP; olmoe 64/16);
  * otherwise     -> experts replicated, per-expert FFN dim sharded over
                     "model" (granite: 40 experts, d_ff 512 -> 32/shard);
                     the down-projection contraction makes the same psum
                     combine partial results.

Compiled FLOPs are ~ k * cf * (dense cost): proportional to *active*
experts, keeping MODEL_FLOPS/HLO_FLOPs honest.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


from ..sharding.logical import active_rules
from .base import ModelConfig, ParamSpec


def moe_specs(cfg: ModelConfig) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    return {
        "router": ParamSpec((d, E), jnp.float32, ("embed", None)),
        "w_gate": ParamSpec((E, d, f), cfg.param_dtype, ("experts", "embed", "expert_mlp")),
        "w_up": ParamSpec((E, d, f), cfg.param_dtype, ("experts", "embed", "expert_mlp")),
        "w_down": ParamSpec((E, f, d), cfg.param_dtype, ("experts", "expert_mlp", "embed"), init="scaled"),
    }


def _capacity(num_tokens: int, num_experts: int, cfg: ModelConfig) -> int:
    k, cf = cfg.num_experts_per_tok, cfg.moe_capacity_factor
    c = int((num_tokens * k * cf) / num_experts) + 1
    return max(8, ((c + 7) // 8) * 8)  # pad for lane alignment


def _route(xf: jnp.ndarray, router: jnp.ndarray, k: int):
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    return probs, top_p, top_e


def _dispatch_compute(xf, top_e, top_p, wg, wu, wd, num_slots: int, C: int, dtype):
    """Sort-based capacity dispatch over ``num_slots`` (local) experts.

    top_e holds *local* expert ids in [0, num_slots); ids == num_slots are
    foreign (another shard's expert) and fall into the drop slot.
    """
    T, d = xf.shape
    k = top_e.shape[-1]
    a_expert = top_e.reshape(-1)
    a_token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    a_w = top_p.reshape(-1).astype(dtype)
    order = jnp.argsort(a_expert, stable=True)
    e_sorted = a_expert[order]
    counts = jax.ops.segment_sum(jnp.ones((T * k,), jnp.int32), a_expert, num_segments=num_slots + 1)
    starts = jnp.cumsum(counts) - counts
    rank_sorted = jnp.arange(T * k, dtype=jnp.int32) - starts[e_sorted]
    keep = (rank_sorted < C) & (e_sorted < num_slots)
    slot = jnp.where(keep, e_sorted * C + jnp.minimum(rank_sorted, C - 1), num_slots * C)
    tok_sorted = a_token[order]
    xb = jnp.zeros((num_slots * C + 1, d), dtype).at[slot].set(xf[tok_sorted].astype(dtype), mode="drop")
    xe = xb[: num_slots * C].reshape(num_slots, C, d)
    gate = jnp.einsum("ecd,edf->ecf", xe, wg.astype(dtype))
    up = jnp.einsum("ecd,edf->ecf", xe, wu.astype(dtype))
    h = jax.nn.silu(gate) * up
    ye = jnp.einsum("ecf,efd->ecd", h, wd.astype(dtype))
    yb = ye.reshape(num_slots * C, d)
    y_sorted = jnp.where(keep[:, None], yb[jnp.minimum(slot, num_slots * C - 1)], 0.0)
    contrib = y_sorted * a_w[order][:, None]
    out = jnp.zeros((T, d), dtype).at[tok_sorted].add(contrib)
    dropped = jnp.sum(jnp.maximum(counts[:num_slots] - C, 0))
    return out, dropped


def _moe_local(p: dict, x: jnp.ndarray, cfg: ModelConfig):
    """Single-shard path (no mesh): dispatch over all experts."""
    B, S, d = x.shape
    T = B * S
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    C = _capacity(T, E, cfg)
    xf = x.reshape(T, d)
    probs, top_p, top_e = _route(xf, p["router"], k)
    out, dropped = _dispatch_compute(
        xf, top_e, top_p, p["w_gate"], p["w_up"], p["w_down"], E, C, cfg.dtype
    )
    me = jnp.mean(probs, axis=0)
    ce = jax.ops.segment_sum(jnp.ones((T * k,), jnp.float32), top_e.reshape(-1), num_segments=E)
    ce = ce / jnp.maximum(jnp.sum(ce), 1.0)
    aux_loss = E * jnp.sum(me * ce)
    return out.reshape(B, S, d), {
        "moe_aux_loss": aux_loss,
        "moe_drop_rate": dropped / jnp.maximum(T * k, 1),
    }


def _moe_sharded(p: dict, x: jnp.ndarray, cfg: ModelConfig, rules):
    mesh = rules.mesh
    tp = mesh.shape.get("model", 1)
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    all_axes = dp_axes + (("model",) if tp > 1 else ())
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    ep = tp > 1 and E % tp == 0
    bspec = P(dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None), None, None)

    def local_fn(router, wg, wu, wd, xl):
        B, S, d = xl.shape
        T = B * S
        xf = xl.reshape(T, d)
        probs, top_p, top_e = _route(xf, router, k)
        if ep:
            e_loc = E // tp
            idx = jax.lax.axis_index("model")
            lo = idx * e_loc
            mine = (top_e >= lo) & (top_e < lo + e_loc)
            local_ids = jnp.where(mine, top_e - lo, e_loc)
            C = _capacity(T, E, cfg)
            out, dropped = _dispatch_compute(xf, local_ids, top_p, wg, wu, wd, e_loc, C, cfg.dtype)
        else:
            C = _capacity(T, E, cfg)
            out, dropped = _dispatch_compute(xf, top_e, top_p, wg, wu, wd, E, C, cfg.dtype)
        if tp > 1:
            out = jax.lax.psum(out, "model")
            dropped = jax.lax.psum(dropped, "model") if ep else dropped
        me = jnp.mean(probs, axis=0)
        ce = jax.ops.segment_sum(
            jnp.ones((T * k,), jnp.float32), top_e.reshape(-1), num_segments=E
        )
        ce = ce / jnp.maximum(jnp.sum(ce), 1.0)
        aux_loss = E * jnp.sum(me * ce)
        if all_axes:
            aux_loss = jax.lax.pmean(aux_loss, all_axes)
            drop_rate = jax.lax.pmean(dropped / jnp.maximum(T * k, 1), all_axes)
        else:
            drop_rate = dropped / jnp.maximum(T * k, 1)
        return out.reshape(B, S, d), aux_loss, drop_rate

    if ep:
        wspec_g = P("model", None, None)
        wspec_d = P("model", None, None)
    else:
        wspec_g = P(None, None, "model")
        wspec_d = P(None, "model", None)
    mapped = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(None, None), wspec_g, wspec_g, wspec_d, bspec),
        out_specs=(bspec, P(), P()),
        check_vma=False,
    )
    out, aux_loss, drop_rate = mapped(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)
    return out, {"moe_aux_loss": aux_loss, "moe_drop_rate": drop_rate}


def moe_ffn(p: dict, x: jnp.ndarray, cfg: ModelConfig):
    """x: (B, S, d) -> (out (B, S, d), aux metrics dict)."""
    rules = active_rules()
    if rules is None or rules.mesh is None:
        return _moe_local(p, x, cfg)
    return _moe_sharded(p, x, cfg, rules)
