"""Multi-tenant serving scale: Q registered queries on one StreamSession.

The paper's deployment story is many independent consumers — dashboards,
alerts, per-district monitors — each registering a slice of the same
geospatial stream.  This bench drives Q ∈ {16, 256, 1024} registered
queries (tenants split across bbox ROIs, confidences, and value columns,
so the session holds several fusion groups and several *finalize
signatures*) through paned streams and measures the three serving-layer
contracts of the multi-tenant session:

  * **per-pane finalize wall** — the batched signature-vmapped emit
    (``emit_all`` / due-window emit) vs the per-query Python finalize loop
    (``batched_finalize=False``), same session, same rings.  Gated as
    ``multitenant_finalize_speedup`` (median of paired repeats) at Q=256.
  * **register-churn latency** — median microseconds for one
    register+unregister round trip against a full tenant population; the
    incremental planner touches exactly one fusion group.
  * **compile counts** — a churn storm over structurally-seen queries must
    perform **zero** recompiles: every pipeline jit family (exec, pass,
    refined pass, finalize) is value-keyed and caches hit.  Gated
    absolute as ``churn_compile_count`` with ``{"max": 0}``.

``--q N`` restricts the CSV run to one population size (the nightly soak
runs ``--q 1024``).  ``--json PATH`` runs the fixed small CI configuration
and writes the metrics ``benchmarks/regression.py`` gates.
"""

from __future__ import annotations

import time

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

WINDOW = 20_000
FRACTION = 0.8
# Shenzhen split into south/north halves: two sampling signatures (fusion
# groups) per method, while finalize signatures ignore ROI entirely — the
# batched emit spans groups
ROI_SOUTH = ((22.45, 22.66), (113.76, 114.64))
ROI_NORTH = ((22.64, 22.86), (113.76, 114.64))


def _tenants(q: int) -> list[Query]:
    """Q tenant queries: mean-over-column dashboards fanned across 2 ROIs,
    2 confidences, and 2 columns.

    That yields up to 4 fusion groups (method x ROI... here srs x 2 ROIs,
    with the ROI inside the sampling signature) but only up to 4 *finalize*
    signatures (confidence x column — ROI drops out), so Q tenants emit
    through <= 4 vmapped finalize dispatches.  Analytic eq-10 error bounds
    (no bootstrap) keep the QoS controller fed without per-tenant replicate
    work — the dashboard-fleet configuration.
    """
    cols = ("value", "occupancy")
    rois = (ROI_SOUTH, ROI_NORTH)
    confs = (0.95, 0.99)
    return [
        Query(
            aggs=(AggSpec("mean", cols[i % 2]),),
            confidence=confs[(i // 2) % 2],
            roi=rois[(i // 4) % 2],
            bootstrap_replicates=0,
        )
        for i in range(q)
    ]


def _pane(window: int = WINDOW, chunks: int = 2) -> dict:
    w = next(windows.count_windows(shenzhen_taxi_stream(num_chunks=chunks, seed=0), window))
    return {
        "lat": jnp.asarray(w.lat, jnp.float32),
        "lon": jnp.asarray(w.lon, jnp.float32),
        "valid": jnp.asarray(w.valid),
        "value": jnp.asarray(w.value, jnp.float32),
        "occupancy": jnp.asarray(w.extra["occupancy"], jnp.float32),
    }


def _serving_session(pipe, q: int, win, key) -> StreamSession:
    """A warmed Q-tenant session: registered, one pane stepped (rings
    filled), both emit paths compiled."""
    sess = StreamSession(pipe, initial_fraction=FRACTION)
    for query in _tenants(q):
        sess.register(query)
    sess.step(key, win)
    return sess


def _emit_walls(sess, key) -> tuple[float, float]:
    """(batched_us, loop_us) for one full-population serving read, same
    session and rings for both arms."""

    def batched():
        out = sess.emit_all(key)
        # time the dispatches, not per-row materialization: a serving read
        # returns the stacked estimates; per-tenant views slice lazily
        return [b.estimates for b in out._batches] or [
            r.estimates for r in out.values()
        ]

    def loop():
        sess.batched_finalize = False
        try:
            return [r.estimates for r in sess.emit_all(key).values()]
        finally:
            sess.batched_finalize = True

    return time_call(batched), time_call(loop)


def _churn(sess, probe: Query, rounds: int = 50) -> float:
    """Median microseconds for one register+unregister round trip (the
    incremental planner touches exactly one fusion group)."""
    samples = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        reg = sess.register(probe)
        sess.unregister(reg)
        samples.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(samples))


def run(only_q: int | None = None):
    table = make_table(*SHENZHEN_BBOX, precision=4)
    pipe = EdgeCloudPipeline(table, PipelineConfig())
    win = _pane(chunks=3)
    key = jax.random.key(0)
    for q in (16, 256, 1024):
        if only_q is not None and q != only_q:
            continue
        sess = _serving_session(pipe, q, win, key)
        batched_us, loop_us = _emit_walls(sess, key)
        base = pipe.compile_count
        churn_us = _churn(sess, _tenants(1)[0])
        sess.step(key, win)
        jax.block_until_ready([b.estimates for b in sess.emit_all(key)._batches])
        compiles = pipe.compile_count - base
        yield csv_line(
            f"multitenant_bench/finalize_batched_q{q}", batched_us,
            f"window={WINDOW};tenants={q};groups={len(sess._groups())};"
            f"speedup={loop_us / max(batched_us, 1e-9):.2f}x",
        )
        yield csv_line(
            f"multitenant_bench/finalize_loop_q{q}", loop_us,
            f"window={WINDOW};tenants={q}",
        )
        yield csv_line(
            f"multitenant_bench/register_churn_q{q}", churn_us,
            f"tenants={q};churn_compiles={compiles};"
            f"plan_decisions={len(sess.plan_log)}",
        )


def small_metrics(q: int = 256, window: int = WINDOW, fraction: float = FRACTION) -> dict:
    """Fixed small-configuration metrics for CI regression tracking.

    The two acceptance gates of the multi-tenant serving layer
    (``benchmarks/baselines.json``): batched-finalize speedup over the
    per-query loop at Q=256 (median of paired repeats), and a zero
    compile count under register/unregister churn at steady state.
    """
    table = make_table(*SHENZHEN_BBOX, precision=4)
    pipe = EdgeCloudPipeline(table, PipelineConfig())
    win = _pane(window)
    key = jax.random.key(0)
    sess = _serving_session(pipe, q, win, key)

    # parity first: the batched emit must agree with the per-query loop
    batched = {qid: r.estimates for qid, r in sess.emit_all(key).items()}
    sess.batched_finalize = False
    looped = {qid: r.estimates for qid, r in sess.emit_all(key).items()}
    sess.batched_finalize = True
    for qid, est in looped.items():
        for k, ref in est.items():
            np.testing.assert_allclose(
                np.asarray(batched[qid][k].value), np.asarray(ref.value),
                rtol=1e-5, err_msg=f"batched finalize diverged: qid={qid} {k}",
            )

    walls: list[tuple[float, float]] = []

    def paired_speedup() -> float:
        b, lo = _emit_walls(sess, key)
        walls.append((b, lo))
        return lo / max(b, 1e-9)

    speedup = median_of_k(paired_speedup, REPEATS)
    batched_us = float(np.median([b for b, _ in walls]))
    loop_us = float(np.median([lo for _, lo in walls]))

    base = pipe.compile_count
    churn_us = _churn(sess, _tenants(1)[0])
    sess.step(key, win)
    jax.block_until_ready([b.estimates for b in sess.emit_all(key)._batches])

    return {
        "config": {
            "window": window,
            "tenants": q,
            "fraction": fraction,
            "precision": 4,
            "fusion_groups": len(sess._groups()),
        },
        "repeats": REPEATS,
        "multitenant_finalize_batched_us": batched_us,
        "multitenant_finalize_loop_us": loop_us,
        "multitenant_finalize_speedup": speedup,
        "register_unregister_us": churn_us,
        "churn_compile_count": pipe.compile_count - base,
        "plan_decisions": len(sess.plan_log),
    }


def main() -> None:
    """Standalone entry: ``python -m benchmarks.multitenant_bench
    [--q N] [--json PATH]``."""
    import sys

    from .common import json_flag_path, write_metrics_json

    path = json_flag_path(sys.argv[1:])
    if path is not None:
        write_metrics_json(path, small_metrics(), "multitenant_bench")
        return
    only_q = None
    if "--q" in sys.argv:
        only_q = int(sys.argv[sys.argv.index("--q") + 1])
    print("name,us_per_call,derived")
    for line in run(only_q):
        print(line, flush=True)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
