"""Paper Fig 8 + §5.2: ingestion throughput and pipelined-runtime overlap.

Two benchmarks share this module:

* :func:`run` (CSV, ``python -m benchmarks.run ingest_throughput``) — the
  original Fig 8 sweep: jitted assign+route+count throughput vs batch size,
  showing the fixed-overhead knee (~20K msgs/batch in the paper).

* :func:`small_metrics` (``--json PATH``) — the streaming-runtime A/B the
  CI regression gate consumes: the same paced pane source driven through a
  synchronous ``session.step`` loop (ingest then compute, serially) vs
  :class:`~repro.core.runtime.StreamRuntime` (producer thread + bounded
  queue + double-buffered staging).  With pane arrival time ≈ per-pane
  compute time the pipelined driver should approach 2× the synchronous
  wall; ``runtime_speedup`` is floor-gated (≥ 1.3× after tolerance) and
  ``p99_pane_latency_ms`` is ceiling-gated in ``benchmarks/baselines.json``
  so a host sync sneaking into the pane loop fails CI, not a reviewer.

Both drivers consume identical panes with identical ``fold_in`` key
discipline, so the A/B is also a parity check: ``parity_ok`` in the JSON
asserts the final estimates agree bit-for-bit.
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
    RuntimeConfig,
    StreamRuntime,
    StreamSession,
    contiguous_plan,
    make_table,
    windows,
)
from repro.data.sources import PacedSource
from repro.data.streams import shenzhen_taxi_stream

from .common import REPEATS, csv_line, time_call


def run(sizes=(2_000, 5_000, 10_000, 20_000, 50_000)):
    table = make_table(*SHENZHEN_BBOX, precision=6, neighborhood_precision=4)
    plan = contiguous_plan(table, num_shards=8)
    rng = np.random.default_rng(0)

    @jax.jit
    def ingest(lat, lon):
        sidx = table.assign(lat, lon)
        dest = plan.route_stratum(sidx)
        counts = jax.ops.segment_sum(
            jnp.ones_like(dest, dtype=jnp.int32), dest, num_segments=plan.num_shards
        )
        return sidx, dest, counts

    lines = []
    best = (0.0, 0)
    for n in sizes:
        lat = jnp.asarray(rng.uniform(22.45, 22.86, n), jnp.float32)
        lon = jnp.asarray(rng.uniform(113.76, 114.64, n), jnp.float32)
        us = time_call(ingest, lat, lon)
        rate = n / (us / 1e6)
        if rate > best[0]:
            best = (rate, n)
        lines.append(csv_line(f"ingest_route_n{n}", us, f"msgs_per_s={rate:.0f}"))
    lines.append(csv_line("ingest_best_batch", 0.0, f"best_batch={best[1]};rate={best[0]:.0f}"))
    return lines


# ---------------------------------------------------------------------------
# Streaming-runtime A/B (CI ``--json`` mode)
# ---------------------------------------------------------------------------


def _query_set():
    return [
        Query(aggs=(AggSpec("mean", "value"), AggSpec("var", "value"))),
        Query(aggs=(AggSpec("mean", "occupancy", name="occ"),)),
    ]


def _fresh_session(pipe, fraction):
    sess = StreamSession(pipe, initial_fraction=fraction)
    for q in _query_set():
        sess.register(q)
    return sess


def _last_estimates(history):
    """Flattened numpy copy of the final step's per-query estimates."""
    out = {}
    for qid, res in history[-1].results.items():
        out[qid] = {k: np.asarray(v) for k, v in res.estimates.items()}
    return out


def small_metrics(
    n_panes: int = 24, pane_tuples: int = 8_000, fraction: float = 0.8,
    backend: str = "segment",
) -> dict:
    """Fixed small-configuration sync-vs-runtime metrics for CI gating.

    ``backend`` selects the edge reduction implementation
    (``segment | pallas | fused`` — see :class:`PipelineConfig`); the
    CI-gated configuration stays on the ``segment`` default, ``--backend
    fused`` A/Bs the single-traversal megakernel path under the same
    paced-pane driver."""
    table = make_table(*SHENZHEN_BBOX, precision=5)
    pipe = EdgeCloudPipeline(
        table, PipelineConfig(raw_capacity=pane_tuples, backend=backend)
    )
    stream = shenzhen_taxi_stream(chunk_size=pane_tuples, num_chunks=n_panes, seed=0)
    panes = list(windows.count_windows(stream, pane_tuples))[:n_panes]
    root = jax.random.key(7)

    # Warm every jit cache through a throwaway session sharing the pipe's
    # compiled-pass cache, so neither timed driver pays compilation.
    warm = _fresh_session(pipe, fraction)

    def warm_step():
        step = warm.step(jax.random.fold_in(root, warm.pane_index), panes[0])
        return [r.estimates for r in step.results.values()]

    step_us = time_call(warm_step)
    # pace arrivals at ~1.5x the per-pane compute time: comfortably inside
    # the regime where the pipelined driver hides the whole arrival delay
    # (runtime wall ~= pacing, sync wall ~= pacing + compute), and bounded
    # so CI stays fast on any machine
    delay_s = min(max(1.5 * step_us / 1e6, 0.004), 0.060)

    def one_trial():
        """One paired sync-vs-runtime A/B over the same panes and keys."""
        # A: synchronous loop — ingest (paced source) then compute, serially
        sess_sync = _fresh_session(pipe, fraction)
        sync_steps = []
        t0 = time.perf_counter()
        for i, pane in enumerate(PacedSource(panes, delay_s)):
            step = sess_sync.step(jax.random.fold_in(root, i), pane)
            jax.block_until_ready([r.estimates for r in step.results.values()])
            sync_steps.append(step)
        sync_wall = time.perf_counter() - t0

        # B: pipelined runtime — producer thread + double-buffered staging.
        # "block" policy: lossless, so the A/B is also a bit-parity check.
        sess_rt = _fresh_session(pipe, fraction)
        rt = StreamRuntime(
            sess_rt, key=root, config=RuntimeConfig(queue_capacity=8, policy="block")
        )
        t0 = time.perf_counter()
        rt.run(PacedSource(panes, delay_s))
        rt_wall = time.perf_counter() - t0

        st = rt.stats()
        a, b = _last_estimates(sync_steps), _last_estimates(rt.history)
        parity_ok = all(
            np.array_equal(a[q][k], b[q][k]) for q in a for k in a[q]
        ) and a.keys() == b.keys()
        return sync_wall, rt_wall, st, parity_ok

    # gated metrics are medians over REPEATS paired trials (a noisy-runner
    # burst skews one trial, not the gate); detail keys come from the last
    trials = [one_trial() for _ in range(REPEATS)]
    sync_wall, rt_wall, st, _ = trials[-1]
    parity_ok = all(t[3] for t in trials)

    return {
        "config": {
            "panes": n_panes,
            "pane_tuples": pane_tuples,
            "fraction": fraction,
            "pacing_ms": delay_s * 1e3,
            "precision": 5,
            "backend": backend,
        },
        "repeats": REPEATS,
        "sync_wall_s": sync_wall,
        "runtime_wall_s": rt_wall,
        "runtime_speedup": float(
            np.median([s / max(r, 1e-9) for s, r, _, _ in trials])
        ),
        "overlap_efficiency": float(
            np.median([t[2].overlap_efficiency for t in trials])
        ),
        "p99_pane_latency_ms": float(
            np.median([t[2].pane_latency["p99_ms"] for t in trials])
        ),
        "p50_pane_latency_ms": st.pane_latency["p50_ms"],
        "queue_depth_high_water": st.queue_depth_high_water,
        "panes_processed": st.panes_processed,
        "tuples_processed": st.tuples_processed,
        "dropped_tuples": st.dropped_tuples,
        "runtime_msgs_per_s": st.tuples_processed / max(rt_wall, 1e-9),
        "parity_ok": bool(parity_ok),
    }


def main() -> None:
    """Standalone entry: ``python -m benchmarks.ingest_throughput [--json
    PATH] [--backend segment|pallas|fused]``.

    ``--json PATH`` runs the fixed sync-vs-runtime configuration and writes
    the gated metrics to PATH; without it the Fig 8 CSV sweep streams to
    stdout.  ``--backend`` selects the pipeline's edge reduction backend
    for the JSON configuration (default ``segment``, the gated baseline;
    ``fused`` drives every pane through the single-traversal megakernel).
    """
    import sys

    from repro.core.pipeline import BACKENDS

    from .common import json_flag_path, write_metrics_json

    argv = sys.argv[1:]
    backend = "segment"
    if "--backend" in argv:
        i = argv.index("--backend") + 1
        if i >= len(argv) or argv[i] not in BACKENDS:
            raise SystemExit(f"usage: --backend {{{'|'.join(BACKENDS)}}}")
        backend = argv[i]
    path = json_flag_path(argv)
    if path is not None:
        metrics = small_metrics(backend=backend)
        if not metrics["parity_ok"]:
            raise SystemExit("runtime/sync estimate parity failed")
        write_metrics_json(path, metrics, "ingest_throughput")
        return
    print("name,us_per_call,derived")
    for line in run():
        print(line, flush=True)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
