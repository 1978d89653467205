"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.

  Fig 8      -> ingest_throughput
  Fig 9-11   -> edgesos_latency
  Fig 15-16  -> accuracy (fraction sweep, MAPE gate)
  Fig 17-18  -> accuracy (geohash-5 vs -6)
  Fig 19     -> cloud_batch
  Fig 20-21  -> edge_vs_cloud (SpatialSSJP baseline implemented)
  kernels    -> kernel_bench
  query API  -> query_bench (grouped 3-aggregate query vs legacy path)
  serving    -> multitenant_bench (Q-tenant batched finalize + churn)
"""

from __future__ import annotations

import sys
import traceback


def main() -> None:
    from . import (
        accuracy,
        cloud_batch,
        edge_vs_cloud,
        edgesos_latency,
        ingest_throughput,
        kernel_bench,
        multitenant_bench,
        query_bench,
        uplink_codec_bench,
    )

    modules = [
        ("ingest_throughput", ingest_throughput),
        ("edgesos_latency", edgesos_latency),
        ("accuracy", accuracy),
        ("cloud_batch", cloud_batch),
        ("edge_vs_cloud", edge_vs_cloud),
        ("kernel_bench", kernel_bench),
        ("query_bench", query_bench),
        ("multitenant_bench", multitenant_bench),
        ("uplink_codec_bench", uplink_codec_bench),
    ]
    args = sys.argv[1:]
    dry = "--dry" in args
    only = next((a for a in args if not a.startswith("-")), None)
    print("name,us_per_call,derived")
    failures = 0
    if dry:
        # smoke mode (CI): importing the modules above already exercises
        # their top-level code; just verify each still exposes a runner.
        for name, mod in modules:
            if only and name != only:
                continue
            if callable(getattr(mod, "run", None)):
                print(f"{name},0,DRY-OK")
            else:
                failures += 1
                print(f"{name},0,ERROR:no run() callable")
        if failures:
            raise SystemExit(f"{failures} benchmark modules failed the dry check")
        return
    for name, mod in modules:
        if only and name != only:
            continue
        try:
            for line in mod.run():
                print(line, flush=True)
        except Exception as e:
            failures += 1
            print(f"{name},0,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benchmark modules failed")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
