"""The four-edge-node cell's uplink: on four virtual CPU devices a sound run
comes out correct and ships bytes, and one that leaves out either uplink
exchange (the preagg psum or the raw buffer's all_gather) does not; and the
readers of its two uplink metrics."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

from bench import trace

ROOT = Path(__file__).resolve().parents[2]

SCRIPT = r"""
import json, sys, time
from pathlib import Path
sys.path[:0] = [sys.argv[1] + "/src", sys.argv[1]]
import jax
from bench import cell, reference
from bench.tests.conftest import cell_of
from repro.core import estimators

uplink_mb = cell.load_module(Path(sys.argv[1]) / "bench/metrics/uplink_mb.edge4.py").read
psum_accs, all_gather = estimators.psum_accs, jax.lax.all_gather
out = {}
for name in ("sound", "no_psum", "no_gather"):
    # each edge node keeps its own per-stratum states: no psum
    estimators.psum_accs = ((lambda stats, axes, shared=None: stats) if name == "no_psum"
                            else psum_accs)
    # each edge node consolidates only its own sampled tuples: no all_gather
    jax.lax.all_gather = (lambda x, axes, **kw: x) if name == "no_gather" else all_gather
    ctx = cell_of("taxi_gh6_edge4", "edge4_backlog")
    window, checks = cell.run_window(ctx, 4000000043, 1.0, t_process=time.perf_counter(),
                                     log=lambda *a, **k: None)
    out[name] = {"checks": {k: [v, ok] for k, (v, _, ok) in reference.verdicts(checks).items()},
                 "uplink_mb": uplink_mb(window)}
print(json.dumps(out))
"""


def _reader(name):
    from bench import cell

    return cell.load_module(ROOT / "bench" / "metrics" / f"{name}.py").read


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT)], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", ["no_psum", "no_gather"])
def test_edge_nodes_need_each_uplink_exchange(runs, fault):
    sound = runs["sound"]["checks"]
    assert all(ok for _, ok in sound.values()), sound
    assert sound["steps_checked"][0] >= 1
    faulty = runs[fault]["checks"]
    assert not all(ok for _, ok in faulty.values()), faulty


def test_sound_edge_nodes_report_their_uplink(runs):
    assert runs["sound"]["uplink_mb"] > 0.0


def test_uplink_reader_reads_the_uplink_counters():
    """``uplink_mb.edge4``: both uplink counters over the window's panes, in
    10^6 B; nothing where the program keeps neither counter."""
    read = _reader("uplink_mb.edge4")
    stats = types.SimpleNamespace(panes_processed=4, counters={
        "uplink.preagg_bytes": 1_259_136, "uplink.raw_bytes": 14_400_000,
        "runtime.sharded_stages": 4})
    assert read(types.SimpleNamespace(runtime_stats=stats)) == (1_259_136 + 14_400_000) / 4e6
    preagg_only = types.SimpleNamespace(panes_processed=2,
                                        counters={"uplink.preagg_bytes": 1_000_000})
    assert read(types.SimpleNamespace(runtime_stats=preagg_only)) == 0.5
    bare = types.SimpleNamespace(runtime_stats=types.SimpleNamespace(panes_processed=3))
    assert read(bare) is None


def test_collective_reader_counts_each_synchronous_collective_once():
    """``collective_ms.edge4`` on the op names the TPU-compiled edge programs
    hold: synchronous ``all-reduce.N`` ops (the raw buffer's all_gather is
    lowered to one, and a program's psums are combined into one), each taken
    once; every other op is left out, and a window with no collective or no
    trace reads nothing."""
    read = _reader("collective_ms.edge4")
    r = trace.Reduced(window_s=1.0, busy_s=0.5, panes=2, gaps={}, op_s={
        "all-reduce.15 u32[1564,1,128]": 0.004,  # the raw buffer's all_gather, lowered
        "all-reduce.16 (s32[800000], s32[6558])": 0.002,  # combined psums
        "all-gather.3 f32[800000]": 0.001,
        "sort.65 (s32[800000], s32[800000], f32[800000])": 0.25,
        "fusion.7 f32[6558]": 0.5,
    })
    assert read(types.SimpleNamespace(trace=r)) == pytest.approx((0.004 + 0.002 + 0.001) * 1e3 / 2)
    quiet = trace.Reduced(window_s=1.0, busy_s=0.5, panes=2, gaps={}, op_s={"sort.1 s32[8]": 0.1})
    assert read(types.SimpleNamespace(trace=quiet)) is None
    assert read(types.SimpleNamespace(trace=None)) is None
