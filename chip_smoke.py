#!/usr/bin/env python3
"""Bring-up check of the served path on a TPU: ``python3 chip_smoke.py``.

Drives the normal entry points, ``EdgeCloudPipeline`` -> ``StreamSession``
-> ``StreamRuntime`` (``block`` queue policy), over the paper's full
Shenzhen e-taxi stream: 664 vehicles, 60 x 20,000 = 1.2M tuples, generated
from ``--seed``, cut into panes of 200,000 tuples.  Each Geohash-5 and
Geohash-6 table over ``SHENZHEN_BBOX`` runs under every edge backend
(``segment``, ``pallas``, ``fused``) with one session that registers:

  * a neighborhood-grouped count/sum/mean/min/max/var/p99 query on speed
    and mean/max/var/p99 on occupancy, over a sliding window of 4 panes,
    at fraction 1.0;
  * an SRS query (over a region of interest) and a Bernoulli query, at 0.8;
  * two queries sharing one sampling signature at 0.8 and 0.3, which
    forces the refined multi-member edge program.

Checks, for every table and backend:

  (a) at fraction 1.0 the grouped query's per-stratum count/sum/mean/
      min/max, and its per-neighborhood count/sum/mean/min/max estimates,
      equal NumPy over the same tuples: counts and extrema exactly, sums
      and means within the f32 recursive-summation bound
      ``(n + 2) * eps32 * sum|y|`` (a bf16 pass breaks it);
  (b) at 0.8 and 0.3 the per-stratum sample sizes n_k are identical
      across the three backends (same ranks, same uniforms);
  (c) every pane offered is processed;
  (d) under ``pallas`` and ``fused`` every lowered pane program holds the
      Mosaic kernel call (``tpu_custom_call``).

``--chips 4`` runs only the sharded session on a mesh of all devices
(psum uplink in preagg mode, all_gather in raw mode), checked against
NumPy at fraction 1.0 and preagg against raw at 0.8.

Without a TPU, or outside a checkout of the repository, the script exits
non-zero and prints no result.  A passing run's last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

PANE = 200_000
WINDOW_PANES = 4
PRECISIONS = (5, 6)
BACKENDS = ("segment", "pallas", "fused")
COLUMNS = ("value", "occupancy")  # speed, occupancy
ROI_SOUTH = ((22.44, 22.66), (113.75, 114.65))
ROI_NORTH = ((22.62, 22.87), (113.75, 114.65))
EPS32 = float(np.finfo(np.float32).eps)
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


class CompileClock:
    """Seconds JAX spent tracing, lowering, compiling or loading programs,
    and the programs it loaded from the persistent compilation cache."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# -- the plain NumPy reference -------------------------------------------------


def pane_reference(pane, table) -> dict:
    """Per-slot count and per-column sum/|sum|/min/max of one pane, in
    float64 over the pane's own tuples; strata from the NumPy geohash
    encoder against the table's sorted codes (the last slot is overflow)."""
    from repro.kernels.geohash.ref import encode_ref

    codes = np.asarray(table.codes)
    code = encode_ref(pane.lat, pane.lon, table.precision)
    pos = np.minimum(np.searchsorted(codes, code), len(codes) - 1)
    sidx = np.where(codes[pos] == code, pos, len(codes))
    slots = table.num_slots
    ref = {"count": np.bincount(sidx, minlength=slots).astype(np.float64)}
    for col in COLUMNS:
        y = np.asarray(pane.columns[col], np.float64)
        lo = np.full(slots, np.inf)
        hi = np.full(slots, -np.inf)
        np.minimum.at(lo, sidx, y)
        np.maximum.at(hi, sidx, y)
        ref[col] = {
            "sum": np.bincount(sidx, weights=y, minlength=slots),
            "abssum": np.bincount(sidx, weights=np.abs(y), minlength=slots),
            "min": lo,
            "max": hi,
        }
    return ref


def window_reference(refs: list) -> dict:
    """Merge pane references into one window reference."""
    out = {"count": sum(r["count"] for r in refs)}
    for col in COLUMNS:
        out[col] = {
            "sum": sum(r[col]["sum"] for r in refs),
            "abssum": sum(r[col]["abssum"] for r in refs),
            "min": np.minimum.reduce([r[col]["min"] for r in refs]),
            "max": np.maximum.reduce([r[col]["max"] for r in refs]),
        }
    return out


def check_exact(res, ref: dict, table, columns=COLUMNS) -> tuple[list, float]:
    """Check (a): a fraction-1.0 result against the NumPy reference.

    Returns ``(failures, worst)`` where ``worst`` is the largest sum/mean
    error as a share of its tolerance (<= 1 passes)."""
    fails, worst = [], 0.0
    n = ref["count"]
    has = n > 0
    s = table.num_strata
    grp = np.asarray(table.neighborhood)[:s]
    groups = table.num_neighborhoods

    def within(name, got, want, tol):
        nonlocal worst
        err = np.abs(np.asarray(got, np.float64) - want)
        share = float(np.max(err / tol)) if err.size else 0.0
        worst = max(worst, share)
        if not share <= 1.0:
            fails.append(f"{name}: error {share:.3g}x the f32 bound")

    def exact(name, got, want):
        if not np.array_equal(np.asarray(got, np.float64), want):
            bad = int(np.sum(np.asarray(got, np.float64) != want))
            fails.append(f"{name}: {bad} entries differ")

    def by_group(x):
        return np.bincount(grp, weights=x[:s], minlength=groups)

    for col in columns:
        m, e, r = res.stats[col]["moments"], res.stats[col]["extrema"], ref[col]
        tol = (n + 2.0) * EPS32 * r["abssum"] + 1e-30
        exact(f"{col} n", m.n, n)
        exact(f"{col} total", m.total, n)
        within(f"{col} sum", m.wsum, r["sum"], tol)
        within(f"{col} mean", np.asarray(m.mean)[has], (r["sum"] / np.maximum(n, 1))[has],
               (tol / np.maximum(n, 1))[has])
        exact(f"{col} min", e.min, r["min"])
        exact(f"{col} max", e.max, r["max"])
    # per-neighborhood estimates (overflow excluded)
    est = res.estimates
    n_g = by_group(n)
    on = n_g > 0
    exact("count_value estimate", est["count_value"].value, n_g)
    r = ref["value"]
    tol_g = 2.0 * by_group((n + 4.0) * EPS32 * r["abssum"]) + 1e-30
    within("sum_value estimate", est["sum_value"].value, by_group(r["sum"]), tol_g)
    within("mean_value estimate", np.asarray(est["mean_value"].value)[on],
           (by_group(r["sum"]) / np.maximum(n_g, 1))[on], (tol_g / np.maximum(n_g, 1))[on])
    for kind, red in (("min", np.minimum), ("max", np.maximum)):
        want = np.full(groups, np.inf if kind == "min" else -np.inf)
        red.at(want, grp, r[kind][:s])
        exact(f"{kind}_value estimate", np.asarray(est[f"{kind}_value"].value)[on], want[on])
    return fails, worst


# -- the session ---------------------------------------------------------------


def session_queries():
    """``name -> (query, window, fraction)`` of the one-chip session."""
    from repro.core import AggSpec, Query, WindowSpec

    speed = tuple(AggSpec(k, "value") for k in ("count", "sum", "mean", "min", "max", "var", "p99"))
    occ = tuple(AggSpec(k, "occupancy") for k in ("mean", "max", "var", "p99"))
    sliding = WindowSpec("sliding", size=WINDOW_PANES)
    return {
        "grouped": (Query(aggs=speed + occ, group_by="neighborhood"), sliding, 1.0),
        "srs": (
            Query(aggs=(AggSpec("mean", "value"), AggSpec("sum", "occupancy")), roi=ROI_SOUTH),
            None, 0.8,
        ),
        "bernoulli": (
            Query(aggs=(AggSpec("mean", "value"), AggSpec("max", "value")), method="bernoulli"),
            None, 0.8,
        ),
        "pair_0.8": (Query(aggs=(AggSpec("mean", "value"),), roi=ROI_NORTH), None, 0.8),
        "pair_0.3": (
            Query(aggs=(AggSpec("mean", "value"), AggSpec("p50", "value")), roi=ROI_NORTH),
            None, 0.3,
        ),
    }


def lower_pane_programs(sess, key, pane) -> list:
    """Each fusion group's pane program, lowered exactly as
    ``StreamSession.step`` dispatches it for ``pane``."""
    import jax.numpy as jnp

    pipe, lowered = sess.pipe, []
    for grp in sess._fusion_groups.values():
        fused = grp.fused_plan()
        fractions = [r.fraction for r in grp.members]
        lat, lon, cols, valid = pipe._window_arrays(pane, fused.shared)
        if sess._refines(fused, fractions):
            fn = pipe._refined_pass_fn(fused, sess.sharded)
            frac = jnp.asarray(fractions, jnp.float32)
        else:
            fn = pipe._pass_fn(fused.shared, sess.sharded)
            frac = jnp.float32(max(fractions))
        lowered.append(fn.lower(key, lat, lon, cols, valid, frac))
    return lowered


def warm_up(sessions: dict, key, pane) -> dict:
    """Compile every session's pane programs at once, on a thread pool.

    The TPU compiler spends tens of seconds on each program that sorts
    (every SRS pass ranks tuples within strata), and compiles run in
    parallel across host cores.  jit caches the lowering, so the sessions'
    own first dispatches reuse these executables in process; the
    persistent compilation cache keeps them for later processes.  Returns
    ``name -> Mosaic kernel calls per lowered pane program`` for check
    (d)."""
    import os
    from concurrent.futures import ThreadPoolExecutor

    lowered = {name: lower_pane_programs(sess, key, pane) for name, sess in sessions.items()}
    jobs = [low for lows in lowered.values() for low in lows]
    with ThreadPoolExecutor(max_workers=min(len(jobs), os.cpu_count() or 1)) as pool:
        list(pool.map(lambda low: low.compile(), jobs))
    return {
        name: [low.as_text().count("tpu_custom_call") for low in lows]
        for name, lows in lowered.items()
    }


def check_runtime(rt, panes) -> list:
    """Check (c): every offered pane (and tuple) went through the session."""
    st = rt.stats()
    fails = []
    if st.panes_enqueued != len(panes) or st.panes_processed != len(panes):
        fails.append(f"panes: {st.panes_enqueued} enqueued, {st.panes_processed} processed")
    if st.tuples_processed != sum(p.size for p in panes) or st.dropped_tuples:
        fails.append(f"tuples: {st.tuples_processed} processed, {st.dropped_tuples} dropped")
    return fails


def build_session(table, backend):
    """A session over a fresh pipeline with every query registered."""
    from repro.core import EdgeCloudPipeline, PipelineConfig, StreamSession

    sess = StreamSession(EdgeCloudPipeline(table, PipelineConfig(backend=backend)))
    regs = {
        name: sess.register(q, window=w, initial_fraction=f)
        for name, (q, w, f) in session_queries().items()
    }
    return sess, regs


def run_backend(sess, regs, calls, panes, refs, seed, clock) -> tuple[list, dict]:
    """Drive one session over the stream; returns the failed checks and
    the per-query per-pane sample sizes n_k for check (b)."""
    import jax

    from repro.core import RuntimeConfig, StreamRuntime

    table, backend = sess.pipe.table, sess.pipe.config.backend
    rt = StreamRuntime(sess, key=jax.random.key(seed), config=RuntimeConfig(policy="block"))
    c0, h0, t0 = clock.seconds, clock.cache_hits, time.perf_counter()
    history = rt.run(panes)
    jax.block_until_ready([s.results[r.qid].stats for s in history for r in regs.values()])
    wall, compile_s = time.perf_counter() - t0, clock.seconds - c0

    fails = check_runtime(rt, panes)
    worst = 0.0
    for i, step in enumerate(history):
        res = jax.device_get(step.results[regs["grouped"].qid])
        lo = max(0, i - WINDOW_PANES + 1)
        f, w = check_exact(res, window_reference(refs[lo : i + 1]), table)
        fails += [f"pane {i} grouped {x}" for x in f]
        worst = max(worst, w)
    sizes = {
        name: np.stack([
            np.asarray(step.results[reg.qid].stats["value"]["moments"].n) for step in history
        ])
        for name, reg in regs.items() if name != "grouped"
    }
    if backend != "segment" and not all(calls):
        fails.append(f"pane programs without a Mosaic kernel call: {calls}")
    print(
        f"geohash-{table.precision} slots={table.num_slots} backend={backend}: "
        f"compile_s={compile_s:.1f} cache_hits={clock.cache_hits - h0} run_s={wall:.1f} "
        f"panes={len(history)}/{len(panes)} "
        f"tuples={rt.stats().tuples_processed} (a) worst_sum_mean_error={worst:.3g}x_f32_bound "
        f"(d) mosaic_calls_per_pane_program={calls} -> {'FAIL' if fails else 'ok'}",
        flush=True,
    )
    for f in fails[:20]:
        print(f"  FAIL {f}", flush=True)
    return fails, sizes


def check_sizes_agree(sizes_by_backend: dict, precision: int) -> list:
    """Check (b): identical per-stratum n_k under every backend."""
    fails = []
    base = sizes_by_backend[BACKENDS[0]]
    for backend in BACKENDS[1:]:
        for name, n in sizes_by_backend[backend].items():
            if not np.array_equal(n, base[name]):
                bad = int(np.sum(n != base[name]))
                fails.append(f"{name}: {bad} per-stratum n_k differ from {BACKENDS[0]}")
    kept = {name: int(n.sum()) for name, n in base.items()}
    print(
        f"geohash-{precision} (b) n_k identical across {'/'.join(BACKENDS)}: "
        f"{'no' if fails else 'yes'}; kept tuples per query {kept}",
        flush=True,
    )
    for f in fails:
        print(f"  FAIL {f}", flush=True)
    return fails


def run_one_chip(panes, seed, clock) -> list:
    import jax

    from repro.core import SHENZHEN_BBOX, make_table

    tables = {p: make_table(*SHENZHEN_BBOX, precision=p) for p in PRECISIONS}
    sessions = {(p, b): build_session(tables[p], b) for p in PRECISIONS for b in BACKENDS}
    t0 = time.perf_counter()
    calls = warm_up(
        {name: sess for name, (sess, _) in sessions.items()},
        jax.random.fold_in(jax.random.key(seed), 0), panes[0],
    )
    print(
        f"warm-up: {sum(map(len, calls.values()))} pane programs compiled in "
        f"{time.perf_counter() - t0:.1f} s",
        flush=True,
    )
    fails = []
    for p in PRECISIONS:
        refs = [pane_reference(pane, tables[p]) for pane in panes]
        sizes = {}
        for b in BACKENDS:
            sess, regs = sessions.pop((p, b))
            f, sizes[b] = run_backend(sess, regs, calls[(p, b)], panes, refs, seed, clock)
            fails += f
        fails += check_sizes_agree(sizes, p)
    return fails


# -- four chips: the sharded session -------------------------------------------


def run_sharded(panes, seed, clock) -> list:
    """The sharded session on a mesh of every device: one edge node per
    chip, psum (preagg) or all_gather (raw) as the uplink."""
    import jax

    from repro.core import (
        SHENZHEN_BBOX, AggSpec, EdgeCloudPipeline, PipelineConfig, Query,
        RuntimeConfig, StreamRuntime, StreamSession, make_table,
    )
    from repro.sharding.compat import compat_make_mesh

    shards = len(jax.devices())
    mesh = compat_make_mesh((shards,), ("data",))
    table = make_table(*SHENZHEN_BBOX, precision=6)
    refs = [pane_reference(p, table) for p in panes]
    # a raw buffer as large as a shard's share of the pane: nothing truncates
    pipe = EdgeCloudPipeline(table, PipelineConfig(raw_capacity=PANE // shards), mesh=mesh)
    sess = StreamSession(pipe, sharded=True)
    exact = tuple(AggSpec(k, "value") for k in ("count", "sum", "mean", "min", "max"))
    sampled = (AggSpec("mean", "value"), AggSpec("max", "value"))
    regs = {}
    for mode in ("preagg", "raw"):
        q_exact = Query(aggs=exact, group_by="neighborhood", mode=mode)
        q_sampled = Query(aggs=sampled, roi=ROI_SOUTH, mode=mode)
        regs[f"exact_{mode}"] = sess.register(q_exact, initial_fraction=1.0)
        regs[f"sampled_{mode}"] = sess.register(q_sampled, initial_fraction=0.8)
    t0 = time.perf_counter()
    warm_up({"sharded": sess}, jax.random.fold_in(jax.random.key(seed), 0), panes[0])
    warm_s = time.perf_counter() - t0
    rt = StreamRuntime(sess, key=jax.random.key(seed), config=RuntimeConfig(policy="block"))
    c0, h0, t0 = clock.seconds, clock.cache_hits, time.perf_counter()
    history = rt.run(panes)
    jax.block_until_ready([s.results[r.qid].stats for s in history for r in regs.values()])
    wall, compile_s = time.perf_counter() - t0, clock.seconds - c0

    fails, worst = check_runtime(rt, panes), 0.0
    for i, step in enumerate(history):
        got = {name: jax.device_get(step.results[reg.qid]) for name, reg in regs.items()}
        for mode in ("preagg", "raw"):
            f, w = check_exact(got[f"exact_{mode}"], refs[i], table, columns=("value",))
            fails += [f"pane {i} exact_{mode} {x}" for x in f]
            worst = max(worst, w)
            if int(got[f"exact_{mode}"].n_truncated) or int(got[f"sampled_{mode}"].n_truncated):
                fails.append(f"pane {i} {mode}: raw buffer truncated tuples")
        pre, raw = got["sampled_preagg"], got["sampled_raw"]
        m_pre, m_raw = pre.stats["value"]["moments"], raw.stats["value"]["moments"]
        if not np.array_equal(m_pre.n, m_raw.n):
            fails.append(f"pane {i} sampled: preagg and raw n_k differ")
        tol = (np.asarray(m_pre.n, np.float64) + 2.0) * EPS32 * refs[i]["value"]["abssum"] + 1e-30
        share = float(np.max(np.abs(np.asarray(m_pre.wsum, np.float64) - m_raw.wsum) / tol))
        worst = max(worst, share)
        if share > 1.0:
            fails.append(f"pane {i} sampled: preagg/raw sums differ by {share:.3g}x the f32 bound")
        for k in ("mean_value", "max_value"):
            a, b = float(pre.estimates[k].value), float(raw.estimates[k].value)
            if abs(a - b) > 4 * EPS32 * abs(a):
                fails.append(f"pane {i} sampled: {k} preagg {a!r} != raw {b!r}")
    kept = sum(int(step.results[regs["sampled_preagg"].qid].n_sampled) for step in history)
    print(
        f"sharded geohash-6 on {shards} devices (preagg psum, raw all_gather): "
        f"warm-up_s={warm_s:.1f} compile_s={compile_s:.1f} cache_hits={clock.cache_hits - h0} "
        f"run_s={wall:.1f} panes={len(history)}/{len(panes)} "
        f"tuples={rt.stats().tuples_processed} fraction-1.0 vs NumPy and 0.8 preagg vs raw: "
        f"worst_sum_mean_error={worst:.3g}x_f32_bound kept_at_0.8={kept} "
        f"-> {'FAIL' if fails else 'ok'}",
        flush=True,
    )
    for f in fails[:20]:
        print(f"  FAIL {f}", flush=True)
    return fails


# -- entry point -----------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="seed of the generated stream")
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the sharded session on a mesh of all devices",
    )
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro" / "core").is_dir():
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(devices)}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    from repro.core import windows
    from repro.data.streams import shenzhen_taxi_stream

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    panes = list(windows.count_windows(shenzhen_taxi_stream(seed=args.seed), PANE))
    print(
        f"stream: shenzhen_taxi_stream seed={args.seed}: {sum(p.size for p in panes)} tuples in "
        f"{len(panes)} panes of {PANE} ({time.perf_counter() - t0:.1f} s); "
        f"device {devices[0].device_kind} x{len(devices)}",
        flush=True,
    )
    run = run_sharded if args.chips == 4 else run_one_chip
    fails = run(panes, args.seed, clock)
    if fails:
        print(f"chip_smoke: {len(fails)} check(s) failed", file=sys.stderr)
        return 1
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
