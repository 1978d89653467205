#!/usr/bin/env python3
"""The control of ``correct``: the program with its own bfloat16 staging path
switched on (``PipelineConfig(backend="fused", staging_dtype="bfloat16")``,
the step below the float32 the configurations state), run through the same
window and comparison as a cell, on the chip, at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3

Prints, for each seed, the compared numbers with their limits and whether
the run came out correct; the control has to come out not correct.  The
benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from run import prepare

CONTROL = {"backend": "fused", "staging_dtype": "bfloat16"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    ready = prepare(args.workload)
    if ready is None:
        return 2
    ctx, _ = ready
    from bench import cell, reference

    for seed in (int(s) for s in args.seeds.split(",")):
        _, checks = cell.run_window(ctx, seed, args.seconds, t_process=time.perf_counter(),
                                    pipeline_overrides=CONTROL)
        v = reference.verdicts(checks)
        print(json.dumps({"control": CONTROL, "workload": args.workload, "seed": seed,
                          "correct": all(ok for _, _, ok in v.values()),
                          "checks": {k: x for k, (x, _, _) in v.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
