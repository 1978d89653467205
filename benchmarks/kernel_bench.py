"""Kernel microbenchmarks: Pallas (interpret off-TPU) vs jnp oracle.

Off-TPU the interpret-mode timing is not meaningful as TPU perf; the bench
records correctness deltas + oracle timing so regressions are visible, and
runs the real kernels when a TPU backend is present.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.stratify import make_table
from repro.kernels.edge_megakernel import edge_megakernel
from repro.kernels.edge_megakernel.ref import edge_megakernel_ref
from repro.kernels.edge_reduce import edge_reduce
from repro.kernels.edge_reduce.ops import edge_reduce_percol
from repro.kernels.edge_reduce.ref import edge_reduce_ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.geohash import geohash_encode
from repro.kernels.geohash.ref import encode_ref
from repro.kernels.sample_mask import sample_mask
from repro.kernels.sample_mask.ref import sample_mask_ref
from repro.kernels.stratified_stats import stratified_stats
from repro.kernels.stratified_stats.ref import stratified_stats_ref

from .common import REPEATS, csv_line, median_of_k, time_call


def run():
    rng = np.random.default_rng(0)
    lines = []
    n = 50_000
    lat = jnp.asarray(rng.uniform(-89, 89, n), jnp.float32)
    lon = jnp.asarray(rng.uniform(-179, 179, n), jnp.float32)
    ref_us = time_call(lambda a, b: encode_ref(a, b, 6), lat, lon)
    got = geohash_encode(lat, lon, 6)
    exact = bool(jnp.all(got == encode_ref(lat, lon, 6)))
    lines.append(csv_line("kernel_geohash_ref", ref_us, f"n={n};kernel_exact={exact}"))

    sidx = jnp.asarray(rng.integers(0, 1000, n), jnp.int32)
    vals = jnp.asarray(rng.normal(10, 3, n), jnp.float32)
    mask = jnp.asarray(rng.random(n) < 0.8)
    ref_us = time_call(lambda s, v, m: stratified_stats_ref(s, v, m, 1000), sidx, vals, mask)
    g = stratified_stats(sidx, vals, mask, 1000)
    r = stratified_stats_ref(sidx, vals, mask, 1000)
    ok = all(bool(jnp.allclose(a, b, rtol=1e-5, atol=1e-2)) for a, b in zip(g, r))
    lines.append(csv_line("kernel_stratified_stats_ref", ref_us, f"n={n};allclose={ok}"))

    frac = jnp.asarray(rng.uniform(0.1, 1.0, 1000), jnp.float32)
    u = jnp.asarray(rng.random(n), jnp.float32)
    ref_us = time_call(sample_mask_ref, sidx, u, frac)
    gm, gw = sample_mask(sidx, u, frac)
    rm, rw = sample_mask_ref(sidx, u, frac)
    ok = bool(jnp.all(gm == rm)) and bool(jnp.allclose(gw, rw, rtol=1e-5))
    lines.append(csv_line("kernel_sample_mask_ref", ref_us, f"n={n};match={ok}"))

    # fused multi-column edge reduce: one pass for a whole fusion group's
    # moment rows vs the per-column segment baseline (3·C reductions)
    for c in (4, 8):
        cols = jnp.asarray(rng.normal(10, 3, (c, n)), jnp.float32)
        fused = jax.jit(lambda s, v, m: edge_reduce(s, v, m, 1000))
        percol = jax.jit(lambda s, v, m: edge_reduce_percol(s, v, m, 1000))
        fused_us = time_call(fused, sidx, cols, mask)
        percol_us = time_call(percol, sidx, cols, mask)
        g = edge_reduce(sidx, cols, mask, 1000)
        r = edge_reduce_ref(sidx, cols, mask, 1000)
        ok = all(bool(jnp.allclose(a, b, rtol=1e-5, atol=1e-2)) for a, b in zip(g, r))
        lines.append(csv_line(
            f"kernel_edge_reduce_fused_c{c}", fused_us,
            f"n={n};strata=1000;cols={c};allclose={ok};backend={jax.default_backend()}"))
        lines.append(csv_line(
            f"kernel_edge_reduce_percol_c{c}", percol_us,
            f"n={n};strata=1000;cols={c};fused_speedup={percol_us / max(fused_us, 1e-9):.2f}x"))

    mk = megakernel_metrics(n=n)
    lines.append(csv_line(
        "kernel_edge_megakernel", mk["megakernel_us"],
        f"n={n};chain_us={mk['megakernel_chain_us']:.1f};"
        f"speedup={mk['megakernel_speedup']:.2f}x;"
        f"traversal_ratio={mk['megakernel_traversal_ratio']:.2f}x;"
        f"parity={mk['megakernel_parity']};backend={jax.default_backend()}"))

    B, S, H, K, dh = 1, 512, 8, 2, 64
    q = jnp.asarray(rng.normal(0, 1, (B, S, H, dh)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(0, 1, (B, S, K, dh)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(0, 1, (B, S, K, dh)), jnp.bfloat16)
    ref_us = time_call(flash_attention_ref, q, k, v)
    o = flash_attention(q, k, v)
    r = flash_attention_ref(q, k, v)
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - r.astype(jnp.float32))))
    lines.append(csv_line("kernel_flash_attention_ref", ref_us,
                          f"S={S};H={H};K={K};max_err={err:.4f};backend={jax.default_backend()}"))
    return lines


def _megakernel_bytes_model(c: int, e: int, k: int, staging_bytes: int = 4):
    """Analytic HBM bytes-touched per tuple: chained stages vs megakernel.

    The model counts only (N,)-sized reads/writes — per-slot outputs are
    O(S) and negligible at bench shapes.  f32/int32 = 4 B, bool mask = 1 B.
    Each *chain stage* is a separate dispatch, so its inputs re-read and
    its per-tuple products (``sidx``, ``mask``) round-trip through HBM:

      assign   r(lat, lon) + w(sidx)            = 12
      sample   r(sidx, u, ok) + w(mask)         = 10
      moments  r(sidx, mask) + r(4·C cols)      = 5 + 4C
      extrema  r(sidx, mask) + r(4·E cols)      = 5 + 4E   (if E)
      sketch   r(sidx, mask) + r(4·K cols)      = 5 + 4K   (if K)

    The megakernel reads each input exactly once and materializes nothing
    per-tuple: r(lat, lon, u, ok) + staging_bytes·C = 13 + b·C (b = 4 for
    f32 staging, 2 for bf16).  Returns (chain_bytes, fused_bytes) per
    tuple; their ratio is the ``megakernel_traversal_ratio`` gate —
    machine-independent by construction.
    """
    chain = 12 + 10 + (5 + 4 * c)
    if e:
        chain += 5 + 4 * e
    if k:
        chain += 5 + 4 * k
    fused = 4 + 4 + 4 + 1 + staging_bytes * c
    return chain, fused


def megakernel_metrics(n: int = 20_000, precision: int = 5, c: int = 4) -> dict:
    """Single-traversal megakernel vs the separately-dispatched kernel
    chain (assign -> sample -> per-column moments -> extrema -> sketch) on
    one Bernoulli pane: wall-time speedup, parity, and the analytic
    bytes-touched advantage.  Off-TPU both sides run their portable
    lowerings, so the speedup is a same-machine A/B of one fused dispatch
    vs five chained ones over identical math."""
    rng = np.random.default_rng(0)
    table = make_table((0.0, 1.0), (0.0, 1.0), precision=precision)  # 529 cells at p=5
    slots = table.num_slots
    ext_idx, sk_idx = (0,), (1,)
    lat = jnp.asarray(rng.uniform(-0.05, 1.05, n), jnp.float32)  # ~9% overflow
    lon = jnp.asarray(rng.uniform(-0.05, 1.05, n), jnp.float32)
    u = jnp.asarray(rng.random(n), jnp.float32)
    ok = jnp.asarray(rng.random(n) < 0.9)
    cols = jnp.asarray(rng.normal(10, 3, (c, n)), jnp.float32)
    thr = jnp.full((1, slots), 0.5, jnp.float32)

    # -- the chain: five independently jitted stages, per-tuple
    # intermediates (sidx, mask) crossing HBM between dispatches
    stage_assign = jax.jit(lambda la, lo: table.assign(la, lo))
    stage_sample = jax.jit(lambda s, uu, o: o & (uu < 0.5))
    stage_moments = jax.jit(lambda s, v, m: edge_reduce_percol(s, v, m, slots))
    stage_extrema = jax.jit(
        lambda s, v, m: tuple(
            (jax.ops.segment_min(jnp.where(m, v[e], jnp.inf), s, num_segments=slots),
             jax.ops.segment_max(jnp.where(m, v[e], -jnp.inf), s, num_segments=slots))
            for e in ext_idx
        )
    )

    def _sketch(s, v, m):
        from repro.core.estimators import SKETCH_NUM_BINS, sketch_bin_index

        out = []
        for kk in sk_idx:
            flat = s * SKETCH_NUM_BINS + sketch_bin_index(v[kk])
            out.append(
                jax.ops.segment_sum(
                    m.astype(jnp.float32), flat, num_segments=slots * SKETCH_NUM_BINS
                ).reshape(slots, SKETCH_NUM_BINS)
            )
        return tuple(out)

    stage_sketch = jax.jit(_sketch)

    def chain(la, lo, uu, o, v):
        s = stage_assign(la, lo)
        m = stage_sample(s, uu, o)
        return (
            stage_moments(s, v, m),
            stage_extrema(s, v, m),
            stage_sketch(s, v, m),
        )

    def mega(la, lo, uu, o, v):
        return edge_megakernel(
            v, o.astype(jnp.float32)[None], uu[None], thr, slots,
            lat=la, lon=lo, codes=table.codes, precision=table.precision,
            ext_idx=ext_idx, sk_idx=sk_idx,
        )

    # gated speedup: median of REPEATS paired (chain, mega) re-measurements
    chain_walls: list[float] = []
    mega_walls: list[float] = []

    def paired_speedup() -> float:
        cw = time_call(chain, lat, lon, u, ok, cols)
        mw = time_call(mega, lat, lon, u, ok, cols)
        chain_walls.append(cw)
        mega_walls.append(mw)
        return cw / max(mw, 1e-9)

    speedup = median_of_k(paired_speedup, REPEATS)
    chain_us = float(np.median(chain_walls))
    mega_us = float(np.median(mega_walls))
    mega_bf16_us = time_call(mega, lat, lon, u, ok, cols.astype(jnp.bfloat16))

    # parity over real strata (the chain's overflow slot collects tuples
    # the latlon-mode kernel deliberately drops; its stat rows stay zero
    # and the pipeline reconstructs overflow *counts* as residuals)
    s_real = table.num_strata
    res = mega(lat, lon, u, ok, cols)
    (cnt, s1, s2), ext, sk = chain(lat, lon, u, ok, cols)
    parity = (
        bool(jnp.allclose(res.keep[0][:s_real], cnt[:s_real]))
        and all(
            bool(jnp.allclose(a[0][:, :s_real], b[:, :s_real], rtol=1e-5, atol=1e-2))
            for a, b in zip((res.s1, res.s2), (s1, s2))
        )
        and bool(jnp.allclose(res.mins[0, 0][:s_real], ext[0][0][:s_real]))
        and bool(jnp.allclose(res.maxs[0, 0][:s_real], ext[0][1][:s_real]))
        and bool(jnp.allclose(res.bins[0, 0][:s_real], sk[0][:s_real]))
    )

    chain_b, fused_b = _megakernel_bytes_model(c, len(ext_idx), len(sk_idx))
    _, fused_b16 = _megakernel_bytes_model(c, len(ext_idx), len(sk_idx), staging_bytes=2)
    return {
        "megakernel_us": mega_us,
        "megakernel_bf16_us": mega_bf16_us,
        "megakernel_chain_us": chain_us,
        "megakernel_speedup": speedup,
        "megakernel_chain_bytes_per_tuple": chain_b,
        "megakernel_fused_bytes_per_tuple": fused_b,
        "megakernel_traversal_ratio": chain_b / fused_b,
        "megakernel_traversal_ratio_bf16": chain_b / fused_b16,
        "megakernel_parity": parity,
    }


def small_metrics(n: int = 20_000, strata: int = 500) -> dict:
    """Fixed small-configuration kernel metrics for CI regression tracking:
    fused multi-column edge-reduce vs the per-column segment baseline
    (wall us + speedup at 4 and 8 columns, with parity checks)."""
    rng = np.random.default_rng(0)
    sidx = jnp.asarray(rng.integers(0, strata, n), jnp.int32)
    mask = jnp.asarray(rng.random(n) < 0.8)
    out: dict = {
        "config": {"n": n, "strata": strata, "backend": jax.default_backend()},
        "repeats": REPEATS,
    }
    for c in (4, 8):
        cols = jnp.asarray(rng.normal(10, 3, (c, n)), jnp.float32)
        fused = jax.jit(lambda s, v, m: edge_reduce(s, v, m, strata))
        percol = jax.jit(lambda s, v, m: edge_reduce_percol(s, v, m, strata))
        fused_walls: list[float] = []
        percol_walls: list[float] = []

        def paired_speedup() -> float:
            f = time_call(fused, sidx, cols, mask)
            p = time_call(percol, sidx, cols, mask)
            fused_walls.append(f)
            percol_walls.append(p)
            return p / max(f, 1e-9)

        speedup = median_of_k(paired_speedup, REPEATS)
        g = edge_reduce(sidx, cols, mask, strata)
        r = edge_reduce_ref(sidx, cols, mask, strata)
        out[f"edge_reduce_fused_c{c}_us"] = float(np.median(fused_walls))
        out[f"edge_reduce_percol_c{c}_us"] = float(np.median(percol_walls))
        out[f"edge_reduce_fused_speedup_c{c}"] = speedup
        out[f"edge_reduce_parity_c{c}"] = all(
            bool(jnp.allclose(a, b, rtol=1e-5, atol=1e-2)) for a, b in zip(g, r)
        )
    out.update(megakernel_metrics(n=n))
    return out


def main() -> None:
    """Standalone entry (CI smoke): ``python -m benchmarks.kernel_bench
    [--dry] [--json PATH]``.

    ``--dry`` runs every kernel once on tiny shapes (interpret-mode parity
    included off-TPU) without the timing loops.  ``--json PATH`` runs the
    fixed small CI configuration and writes the edge-reduce metrics dict
    to PATH (see ``benchmarks.regression`` for the gate).
    """
    import sys

    from .common import json_flag_path, write_metrics_json

    path = json_flag_path(sys.argv[1:])
    if path is not None:
        metrics = small_metrics()
        write_metrics_json(path, metrics, "kernel_bench")
        bad = [
            k for k, v in metrics.items()
            if (k.startswith("edge_reduce_parity") or k == "megakernel_parity")
            and v is False
        ]
        if bad:
            raise SystemExit(f"kernel parity failed in bench config: {bad}")
        return
    print("name,us_per_call,derived")
    if "--dry" in sys.argv[1:]:
        rng = np.random.default_rng(0)
        n, s, c = 300, 20, 3
        sidx = jnp.asarray(rng.integers(0, s, n), jnp.int32)
        vals = jnp.asarray(rng.normal(0, 1, (c, n)), jnp.float32)
        mask = jnp.asarray(rng.random(n) < 0.5)
        checks = {
            "geohash": bool(jnp.all(
                geohash_encode(vals[0, :64], vals[1, :64], 5)
                == encode_ref(vals[0, :64], vals[1, :64], 5))),
            "stratified_stats": all(bool(jnp.allclose(a, b, rtol=1e-4, atol=1e-2)) for a, b in zip(
                stratified_stats(sidx, vals[0], mask, s),
                stratified_stats_ref(sidx, vals[0], mask, s))),
            # interpret=True forces the Pallas kernel (auto mode would lower
            # to the oracle itself off-TPU, making the check tautological)
            "edge_reduce": all(bool(jnp.allclose(a, b, rtol=1e-4, atol=1e-2)) for a, b in zip(
                edge_reduce(sidx, vals, mask, s, interpret=True),
                edge_reduce_ref(sidx, vals, mask, s))),
            "sample_mask": bool(jnp.all(
                sample_mask(sidx, jnp.abs(vals[1]) % 1.0, jnp.full((s,), 0.5))[0]
                == sample_mask_ref(sidx, jnp.abs(vals[1]) % 1.0, jnp.full((s,), 0.5))[0])),
        }
        # megakernel: interpreted Pallas (latlon mode, in-kernel geohash +
        # threshold sampling + all stat families) vs the numpy oracle
        la = jnp.asarray(rng.uniform(0.0, 1.0, n), jnp.float32)
        lo = jnp.asarray(rng.uniform(0.0, 1.0, n), jnp.float32)
        codes = jnp.asarray(
            np.unique(np.asarray(encode_ref(la, lo, 4)))[::2]  # every other cell -> overflow exercised
        )
        mg_slots = int(codes.shape[0])
        u = jnp.asarray(rng.random(n), jnp.float32)
        okf = jnp.asarray(rng.random(n) < 0.8, jnp.float32)[None]
        thr = jnp.full((1, mg_slots), 0.5, jnp.float32)
        got_mg = edge_megakernel(
            vals, okf, u[None], thr, mg_slots,
            lat=la, lon=lo, codes=codes, precision=4,
            ext_idx=(0,), sk_idx=(1,), interpret=True,
        )
        ref_mg = edge_megakernel_ref(
            np.asarray(vals), np.asarray(okf), np.asarray(u)[None],
            np.asarray(thr), mg_slots,
            lat=np.asarray(la), lon=np.asarray(lo), codes=np.asarray(codes),
            precision=4, ext_idx=(0,), sk_idx=(1,),
        )
        checks["edge_megakernel"] = all(
            bool(jnp.allclose(jnp.asarray(a), jnp.asarray(b), rtol=1e-4, atol=1e-2))
            for a, b in zip(tuple(got_mg), ref_mg)
        )
        bad = [k for k, ok in checks.items() if not ok]
        for k, ok in checks.items():
            print(f"kernel_bench/{k},0,{'DRY-OK' if ok else 'DRY-MISMATCH'}")
        if bad:
            raise SystemExit(f"kernel dry-run parity failed: {bad}")
        return
    for line in run():
        print(line, flush=True)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
