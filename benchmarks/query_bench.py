"""Query-layer latency: fused sessions, grouped queries, legacy estimate.

Measures per-window device latency of (a) the legacy `process_window`
single SUM/MEAN path, (b) a 3-aggregate neighborhood-grouped declarative
query, (c) the same query ungrouped — the cost of the API redesign's
generality on the hot path — (d) the headline of the session redesign:
a fused `StreamSession` answering N registered queries with ONE
stratify+EdgeSOS pass vs N independent `execute` calls, for
N ∈ {1, 4, 16}, in wall time and edge->cloud collective bytes — and
(e) the edge-reduce backend on a wide fusion group: the single-pass
multi-column reduction (`backend="pallas"`) vs the per-column segment
path, for 4- and 8-column groups, plus the quantile-sketch query cost and
the bootstrap error-bounds finalize overhead — and (f) the session
refinements: a mixed-fraction fusion group's downstream-bytes reduction
(the low-fraction member pays its own nested subsample, not the group
max) and the one-pass speedup of cross-signature Bernoulli fusion over
the one-pass-per-ROI-group behavior it replaces.

``--json PATH`` runs a fixed small configuration and writes the metrics
CI's regression gate consumes (``benchmarks/regression.py``).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import (
    SHENZHEN_BBOX,
    AggSpec,
    EdgeCloudPipeline,
    PipelineConfig,
    Query,
    StreamSession,
    make_table,
    windows,
)
from repro.data.streams import shenzhen_taxi_stream

from .common import REPEATS, csv_line, median_of_k, time_call

WINDOW = 50_000
FRACTION = 0.8


def _query_set(n: int) -> list[Query]:
    """n distinct single-aggregate queries sharing one sampling signature
    (so the whole set is one fusion group)."""
    kinds = ("mean", "sum", "var", "count", "min", "max")
    cols = ("value", "occupancy")
    return [
        Query(
            aggs=(AggSpec(kinds[i % len(kinds)], cols[i % len(cols)], name=f"a{i}"),),
            confidence=0.95 if i % 2 == 0 else 0.99,
        )
        for i in range(n)
    ]


def run():
    table = make_table(*SHENZHEN_BBOX, precision=5)
    pipe = EdgeCloudPipeline(table, PipelineConfig(raw_capacity=WINDOW))
    w = next(windows.count_windows(shenzhen_taxi_stream(num_chunks=3, seed=0), WINDOW))
    lat = jnp.asarray(w.lat, jnp.float32)
    lon = jnp.asarray(w.lon, jnp.float32)
    val = jnp.asarray(w.value, jnp.float32)
    occ = jnp.asarray(w.extra["occupancy"], jnp.float32)
    valid = jnp.asarray(w.valid)
    key = jax.random.key(0)
    frac = jnp.float32(FRACTION)

    us = time_call(pipe.process_window, key, lat, lon, val, valid, frac)
    yield csv_line("query_bench/legacy_single_estimate", us, f"window={WINDOW}")

    aggs3 = (AggSpec("mean", "value"), AggSpec("max", "value"), AggSpec("mean", "occupancy"))
    win = {"lat": lat, "lon": lon, "valid": valid, "value": val, "occupancy": occ}
    for name, query in (
        ("query3_global", Query(aggs=aggs3)),
        ("query3_grouped_neighborhood", Query(aggs=aggs3, group_by="neighborhood")),
        ("query3_grouped_raw_mode", Query(aggs=aggs3, group_by="neighborhood", mode="raw")),
    ):
        us_q = time_call(pipe.execute, query, key, win, FRACTION)
        yield csv_line(
            f"query_bench/{name}", us_q,
            f"window={WINDOW};aggs={len(aggs3)};vs_legacy={us_q / max(us, 1e-9):.2f}x",
        )

    # fused session vs N independent executes (the multi-query fusion win);
    # both arms consume the same device-resident column mapping
    for n in (1, 4, 16):
        queries = _query_set(n)
        sess = StreamSession(pipe, initial_fraction=FRACTION)
        for q in queries:
            sess.register(q)

        def fused_step():
            step = sess.step(key, win)
            return [r.estimates for r in step.results.values()]

        def independent():
            return [pipe.execute(q, key, win, FRACTION).estimates for q in queries]

        us_fused = time_call(fused_step)
        us_indep = time_call(independent)
        fused_bytes = sess.step(key, win).comm_bytes
        indep_bytes = sum(
            int(pipe.execute(q, key, win, FRACTION).comm_bytes) for q in queries
        )
        yield csv_line(
            f"query_bench/session_fused_n{n}", us_fused,
            f"window={WINDOW};queries={n};bytes={fused_bytes}",
        )
        yield csv_line(
            f"query_bench/independent_n{n}", us_indep,
            f"window={WINDOW};queries={n};bytes={indep_bytes};"
            f"fused_speedup={us_indep / max(us_fused, 1e-9):.2f}x;"
            f"bytes_ratio={indep_bytes / max(fused_bytes, 1):.2f}x",
        )

    # wide fusion groups: single-pass multi-column edge reduction vs the
    # per-column segment path (same plan, same sample, different backend)
    rng = np.random.default_rng(1)
    extras = ("speed", "heading", "accel", "altitude", "battery", "signal")
    wide = dict(win)
    for extra in extras:
        wide[extra] = jnp.asarray(rng.normal(30, 10, WINDOW), jnp.float32)
    for ncols in (4, 8):
        cols = (["value", "occupancy"] + list(extras))[:ncols]
        q_wide = Query(aggs=tuple(AggSpec("mean", c) for c in cols))
        backends = {}
        for backend in ("segment", "pallas"):
            p = EdgeCloudPipeline(table, PipelineConfig(backend=backend))
            backends[backend] = time_call(p.execute, q_wide, key, wide, FRACTION)
        yield csv_line(
            f"query_bench/edge_reduce_fused_c{ncols}", backends["pallas"],
            f"window={WINDOW};cols={ncols};"
            f"vs_percol={backends['segment'] / max(backends['pallas'], 1e-9):.2f}x",
        )
        yield csv_line(
            f"query_bench/edge_reduce_percol_c{ncols}", backends["segment"],
            f"window={WINDOW};cols={ncols}",
        )

    # per-query fraction refinement: a mixed-fraction fusion group refines
    # each member to its own fraction — the low-fraction member's downstream
    # volume shrinks by ~f_hi/f_lo instead of paying the group max
    for name, (f_lo, f_hi) in (("mixed_10_80", (0.1, 0.8)), ("shared_80_80", (0.8, 0.8))):
        sess_mix = StreamSession(pipe)
        r_lo = sess_mix.register(
            Query(aggs=(AggSpec("mean", "value"),)), initial_fraction=f_lo
        )
        r_hi = sess_mix.register(
            Query(aggs=(AggSpec("mean", "occupancy", name="occ"),)), initial_fraction=f_hi
        )
        us_mix = time_call(sess_mix.step, key, win)
        lo_b, hi_b = r_lo.downstream_bytes, r_hi.downstream_bytes
        yield csv_line(
            f"query_bench/refined_{name}", us_mix,
            f"window={WINDOW};fractions={f_lo}/{f_hi};"
            f"downstream_lo={lo_b};downstream_hi={hi_b};"
            f"lo_reduction={hi_b / max(lo_b, 1):.2f}x",
        )

    # cross-signature Bernoulli fusion: two differing-ROI Bernoulli queries
    # share ONE edge pass vs the PR4 behavior of one pass per ROI group
    roi_s = ((22.45, 22.66), (113.76, 114.64))
    roi_n = ((22.64, 22.86), (113.76, 114.64))
    qb = [
        Query(aggs=(AggSpec("mean", "value", name=f"b{i}"),), method="bernoulli", roi=roi)
        for i, roi in enumerate((roi_s, roi_n))
    ]
    sess_x = StreamSession(pipe, initial_fraction=FRACTION)
    for q in qb:
        sess_x.register(q)
    separate = [StreamSession(pipe, initial_fraction=FRACTION) for _ in qb]
    for s, q in zip(separate, qb):
        s.register(q)

    def one_pass():
        return sess_x.step(key, win)

    def two_passes():
        return [s.step(key, win) for s in separate]

    us_one = time_call(one_pass)
    us_two = time_call(two_passes)
    yield csv_line(
        "query_bench/bernoulli_cross_roi_fused", us_one,
        f"window={WINDOW};rois=2;passes={len(sess_x._groups())};"
        f"vs_separate_groups={us_two / max(us_one, 1e-9):.2f}x",
    )

    # quantile aggregates: the sketch's accumulate+finalize cost on top of
    # the same pass (p50/p99 over one column)
    q_quant = Query(aggs=(AggSpec("mean", "value"), AggSpec("p50", "value"), AggSpec("p99", "value")))
    us_quant = time_call(pipe.execute, q_quant, key, win, FRACTION)
    yield csv_line(
        "query_bench/quantile_p50_p99", us_quant,
        f"window={WINDOW};vs_query3={us_quant / max(us, 1e-9):.2f}x",
    )

    # error-bounds finalize cost: the bootstrap (var + p99 CIs, default 200
    # replicates) against the same query with bounds disabled
    aggs_b = (AggSpec("var", "value"), AggSpec("p99", "value"))
    us_bounds = time_call(pipe.execute, Query(aggs=aggs_b), key, win, FRACTION)
    us_nobounds = time_call(
        pipe.execute, Query(aggs=aggs_b, bootstrap_replicates=0), key, win, FRACTION
    )
    yield csv_line(
        "query_bench/bounds_var_p99", us_bounds,
        f"window={WINDOW};replicates=200;"
        f"vs_disabled={us_bounds / max(us_nobounds, 1e-9):.2f}x",
    )


def small_metrics(window: int = 20_000, n_queries: int = 4, fraction: float = FRACTION) -> dict:
    """Fixed small-configuration metrics for CI regression tracking.

    Wall microseconds, uplink bytes, and the fused-vs-independent speedup of
    an ``n_queries`` fusion group — the numbers ``benchmarks/baselines.json``
    gates (see ``benchmarks.regression``).
    """
    table = make_table(*SHENZHEN_BBOX, precision=5)
    pipe = EdgeCloudPipeline(table, PipelineConfig(raw_capacity=window))
    w = next(windows.count_windows(shenzhen_taxi_stream(num_chunks=2, seed=0), window))
    win = {
        "lat": jnp.asarray(w.lat, jnp.float32),
        "lon": jnp.asarray(w.lon, jnp.float32),
        "valid": jnp.asarray(w.valid),
        "value": jnp.asarray(w.value, jnp.float32),
        "occupancy": jnp.asarray(w.extra["occupancy"], jnp.float32),
    }
    key = jax.random.key(0)
    queries = _query_set(n_queries)
    sess = StreamSession(pipe, initial_fraction=fraction)
    for q in queries:
        sess.register(q)

    def fused_step():
        step = sess.step(key, win)
        return [r.estimates for r in step.results.values()]

    def independent():
        return [pipe.execute(q, key, win, fraction).estimates for q in queries]

    # the gated speedup is the median of REPEATS paired re-measurements
    # (both arms per repeat), not a single-shot wall — see common.median_of_k
    fused_walls: list[float] = []
    indep_walls: list[float] = []

    def paired_speedup() -> float:
        f = time_call(fused_step)
        i = time_call(independent)
        fused_walls.append(f)
        indep_walls.append(i)
        return i / max(f, 1e-9)

    fused_speedup = median_of_k(paired_speedup, REPEATS)
    us_fused = float(np.median(fused_walls))
    us_indep = float(np.median(indep_walls))
    fused_bytes = int(sess.step(key, win).comm_bytes)
    indep_bytes = sum(
        int(pipe.execute(q, key, win, fraction).comm_bytes) for q in queries
    )
    q_bounds = Query(aggs=(AggSpec("var", "value"), AggSpec("p99", "value")))
    us_bounds = time_call(pipe.execute, q_bounds, key, win, fraction)

    # per-query fraction refinement: the low-fraction member of a 0.1/0.8
    # group pays ~1/8 the downstream volume of the max member (PR4 charged
    # both the max) — a near-deterministic ratio, gated in baselines.json
    sess_mix = StreamSession(pipe)
    r_lo = sess_mix.register(
        Query(aggs=(AggSpec("mean", "value"),)), initial_fraction=0.1
    )
    r_hi = sess_mix.register(
        Query(aggs=(AggSpec("mean", "occupancy", name="occ"),)), initial_fraction=0.8
    )
    sess_mix.step(key, win)
    refined_ratio = r_hi.downstream_bytes / max(r_lo.downstream_bytes, 1)

    # cross-signature Bernoulli fusion: one pass for two differing ROIs vs
    # the PR4 one-pass-per-ROI-group behavior (same-machine A/B speedup)
    roi_s = ((22.45, 22.66), (113.76, 114.64))
    roi_n = ((22.64, 22.86), (113.76, 114.64))
    qb = [
        Query(aggs=(AggSpec("mean", "value", name=f"b{i}"),), method="bernoulli", roi=roi)
        for i, roi in enumerate((roi_s, roi_n))
    ]
    sess_x = StreamSession(pipe, initial_fraction=fraction)
    for q in qb:
        sess_x.register(q)
    separate = [StreamSession(pipe, initial_fraction=fraction) for _ in qb]
    for s, q in zip(separate, qb):
        s.register(q)
    us_one = time_call(lambda: sess_x.step(key, win))
    us_two = time_call(lambda: [s.step(key, win) for s in separate])

    return {
        "config": {
            "window": window,
            "queries": n_queries,
            "fraction": fraction,
            "precision": 5,
        },
        "repeats": REPEATS,
        f"session_fused_n{n_queries}_us": us_fused,
        f"independent_n{n_queries}_us": us_indep,
        f"fused_speedup_n{n_queries}": fused_speedup,
        f"fused_uplink_bytes_n{n_queries}": fused_bytes,
        f"independent_uplink_bytes_n{n_queries}": indep_bytes,
        f"uplink_ratio_n{n_queries}": indep_bytes / max(fused_bytes, 1),
        "bounds_var_p99_us": us_bounds,
        "refined_downstream_ratio": refined_ratio,
        "refined_downstream_bytes_lo": r_lo.downstream_bytes,
        "refined_downstream_bytes_hi": r_hi.downstream_bytes,
        "bernoulli_cross_roi_fused_us": us_one,
        "bernoulli_cross_roi_separate_us": us_two,
        "bernoulli_cross_roi_speedup": us_two / max(us_one, 1e-9),
    }


def main() -> None:
    """Standalone entry: ``python -m benchmarks.query_bench [--json PATH]``.

    ``--json PATH`` runs the fixed small CI configuration and writes the
    metrics dict (wall us, uplink bytes, fused speedup) to PATH; without it
    the full CSV benchmark suite streams to stdout.
    """
    import sys

    from .common import json_flag_path, write_metrics_json

    path = json_flag_path(sys.argv[1:])
    if path is not None:
        write_metrics_json(path, small_metrics(), "query_bench")
        return
    print("name,us_per_call,derived")
    for line in run():
        print(line, flush=True)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
