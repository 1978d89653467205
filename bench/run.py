#!/usr/bin/env python3
"""Chip benchmark of the served path, one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` names an entry of ``workloads`` in ``BENCHMARK.json``: a
deployment (``bench/configs/``) under a traffic mix (``bench/traffic/``).
The run builds the stream from ``--seed``, sets up and warms the session,
measures one window of ``--seconds`` through ``StreamRuntime.run``, then
compares a sample of the window's results, drawn from the seed, with the
NumPy reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones, each computed by
``bench/metrics/<name>.py``), ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``, each compared number with its limit.

Without a TPU, or with fewer chips than the cell asks for, or outside a
checkout that holds the program (``src/repro``), it exits non-zero and
prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def metric_entries(spec: dict, workload: str, trace: bool) -> list:
    """The metrics this cell reports in this kind of run."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def result_line(ctx: dict, window, checks: dict, trace: bool, devices) -> dict:
    from bench import cell, reference

    workload = ctx["cell"]["name"]
    metrics = {}
    for m in metric_entries(ctx["spec"], workload, trace):
        value = cell.load_module(ctx["dir"] / "metrics" / f"{m['name']}.py").read(window)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    verdicts = reference.verdicts(checks)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": window.memory_peak_bytes}
    out = {
        "correct": all(ok for _, _, ok in verdicts.values()),
        "attempted": window.offered,
        "failed": window.offered - len([r for r in window.records if r.stamps]),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = window.trace.busy_s
        device["window_s"] = window.trace.window_s
        out["breakdown"] = window.trace.breakdown()
    out["checks"] = {name: {"value": v, limit_key: lim}
                     for name, (v, (limit_key, lim), _) in verdicts.items()}
    return out


def prepare(workload: str):
    """Put the checkout's program on the path, point JAX's compilation cache
    into the checkout, read the cell and look for its chips.  Returns
    ``(ctx, devices)``, or None (with the reason on stderr) where the run
    cannot be made."""
    if not (ROOT / "src" / "repro" / "core").is_dir():
        print(f"bench: no repro package under {ROOT / 'src'}", file=sys.stderr)
        return None
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    # one fixed cache directory inside the checkout: only a cell's first run
    # here compiles
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from bench import cell

    ctx = cell.load_cell(ROOT, workload)
    import jax

    devices = jax.devices()
    chips = ctx["cell"]["chips"]
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU; JAX found {devices[0].platform}", file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"bench: {workload} needs {chips} chips; JAX found {len(devices)}", file=sys.stderr)
        return None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    return ctx, devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    ready = prepare(args.workload)
    if ready is None:
        return 2
    ctx, devices = ready
    from bench import cell

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    window, checks = cell.run_window(ctx, args.seed, args.seconds, t_process=T_PROCESS,
                                     trace_dir=trace_dir)
    out = result_line(ctx, window, checks, bool(args.trace), devices)
    for name, c in out["checks"].items():
        print(f"check {name}: {c}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
