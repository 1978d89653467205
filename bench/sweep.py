#!/usr/bin/env python3
"""Find the highest tuple rate a paced cell sustains, once, on the chip.

    python3 bench/sweep.py --workload <paced cell> --seed 1 --seconds 5 \
        --shares 0.6,0.8,0.9,1.0,1.1

First the cell's panes are offered as fast as a lossless queue admits them
(its capacity); then, for each share of that capacity, one window on the
cell's own open-loop schedule and queue.  Prints per rate the panes dropped,
the queue's high-water mark and the latency median and 95th percentile.
The highest rate that drops nothing and keeps the queue from filling is the
knee; the cell's traffic file takes four fifths of it, as a number.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

from run import prepare  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--shares", default="0.6,0.8,0.9,1.0,1.1")
    args = ap.parse_args(argv)
    ready = prepare(args.workload)
    if ready is None:
        return 2
    ctx, _ = ready
    from bench import cell

    backlog = copy.deepcopy(ctx)
    backlog["traffic"].update(arrival="backlog", policy="block")
    w, _ = cell.run_window(backlog, args.seed, args.seconds, t_process=T_PROCESS)
    tuples, seconds = w.completed_tuples()
    capacity = tuples / seconds
    print(json.dumps({"capacity_tuples_per_s": capacity}), flush=True)
    for share in (float(s) for s in args.shares.split(",")):
        paced = copy.deepcopy(ctx)
        paced["traffic"]["tuples_per_s"] = share * capacity
        w, _ = cell.run_window(paced, args.seed, args.seconds, t_process=time.perf_counter())
        lat = w.latencies_ms()
        print(json.dumps({
            "share": share, "tuples_per_s": share * capacity, "offered": w.offered,
            "dropped_panes": w.dropped_panes,
            "queue_high_water": w.runtime_stats.queue_depth_high_water,
            "latency_p50_ms": float(np.percentile(lat, 50)) if lat.size else None,
            "latency_p95_ms": float(np.percentile(lat, 95)) if lat.size else None,
            "gen_lag_p95_ms": float(np.percentile(w.lags, 95)) * 1e3 if w.lags else None,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
