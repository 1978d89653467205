"""The four-edge-node cell on four virtual CPU devices: a sound run comes out
correct, and one whose uplink exchange is left out does not."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

SCRIPT = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1] + "/src", sys.argv[1]]
from bench import cell, reference
from bench.tests.conftest import cell_of
from repro.core import estimators

out = {}
for name in ("sound", "no_exchange"):
    if name == "no_exchange":
        # each edge node keeps its own per-stratum states: no psum
        estimators.psum_accs = lambda stats, axes, shared=None: stats
    ctx = cell_of("taxi_gh6_edge4", "edge4_backlog")
    _, checks = cell.run_window(ctx, 4000000041, 1.0, t_process=time.perf_counter(),
                                log=lambda *a, **k: None)
    out[name] = {k: [v, ok] for k, (v, _, ok) in reference.verdicts(checks).items()}
print(json.dumps(out))
"""


def test_edge_nodes_need_their_exchange():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert all(ok for _, ok in out["sound"].values()), out["sound"]
    assert out["sound"]["steps_checked"][0] >= 1
    assert not all(ok for _, ok in out["no_exchange"].values()), out["no_exchange"]
