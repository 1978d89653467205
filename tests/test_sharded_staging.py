"""How ``StreamRuntime`` stages a pane for a sharded session (subprocess, 4
host devices): each edge node's contiguous share goes to its own device,
the results are bit-identical to staging the whole pane on one device, and
the uplink counters hold the plans' per-shard bytes."""

import json
import os
import subprocess
import sys

import pytest

# each test spawns a multi-device jax subprocess; serialize them onto one
# xdist worker so parallel shards don't oversubscribe the CPU
pytestmark = pytest.mark.xdist_group("subprocess-heavy")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, json
import numpy as np, jax
from repro.core import (SHENZHEN_BBOX, AggSpec, EdgeCloudPipeline, PipelineConfig, Query,
                        RuntimeConfig, StreamRuntime, StreamSession, make_table, windows)
from repro.core import query as aqp
from repro.data.streams import shenzhen_taxi_stream
from repro.sharding.compat import compat_make_mesh

PANE, NODES, CAP = 1024, 4, 256
SOUTH = ((22.44, 22.66), (113.75, 114.65))
QUERIES = (
    (Query(aggs=(AggSpec("sum", "value"), AggSpec("max", "value")), group_by="neighborhood"), 1.0),
    (Query(aggs=(AggSpec("mean", "value"),), roi=SOUTH, mode="raw"), 0.8),
)
table = make_table(*SHENZHEN_BBOX, precision=5)
mesh = compat_make_mesh((NODES,), ("data",))
panes = list(windows.pane_windows(shenzhen_taxi_stream(chunk_size=PANE, num_chunks=3, seed=0),
                                  pane_tuples=PANE))


class Watched:
    # delegates to the session; notes where each pane's columns live
    def __init__(self, sess, input_sharding):
        self._sess, self.input_sharding, self.placed = sess, input_sharding, []

    def step(self, key, pane):
        for name, col in [("lat", pane.lat), ("lon", pane.lon), ("valid", pane.valid),
                          *pane.columns.items()]:
            shares = sorted(col.addressable_shards, key=lambda s: s.index[0].start or 0)
            self.placed.append((name, str(getattr(col.sharding, "spec", None)), col.committed,
                                [(s.device.id, s.index[0].start, s.index[0].stop)
                                 for s in shares]))
        return self._sess.step(key, pane)

    def __getattr__(self, name):
        return getattr(self._sess, name)


def run(sharded_staging):
    pipe = EdgeCloudPipeline(table, PipelineConfig(raw_capacity=CAP), mesh=mesh)
    sess = StreamSession(pipe, sharded=True)
    for q, f in QUERIES:
        sess.register(q, initial_fraction=f)
    watched = Watched(sess, sess.input_sharding if sharded_staging else None)
    rt = StreamRuntime(watched, key=jax.random.key(7), config=RuntimeConfig(policy="block"))
    hist = rt.run(panes)
    out = [jax.device_get({q: (r.estimates, r.stats) for q, r in s.results.items()})
           for s in hist]
    return sess, rt.stats().counters, watched.placed, out


sess, counters, placed, new = run(True)
_, old_counters, old_placed, old = run(False)
same = jax.tree.all(jax.tree.map(lambda a, b: bool(np.array_equal(a, b)), new, old))
plans = [g.fused_plan().shared for g in sess._fusion_groups.values()]
expect = {
    "uplink.preagg_bytes": len(panes) * sum(aqp.preagg_bytes(p, table.num_slots)
                                            for p in plans if p.query.mode == "preagg"),
    "uplink.raw_bytes": len(panes) * sum(aqp.raw_bytes(p, CAP)
                                         for p in plans if p.query.mode == "raw"),
}
p0 = panes[0]
cut = {k: getattr(p0, k)[:PANE - 2] for k in ("sensor_id", "timestamp", "lat", "lon", "value",
                                                "valid")}
try:  # a pane that four nodes cannot share evenly
    StreamRuntime(sess, key=jax.random.key(0)).run(
        [dataclasses.replace(p0, **cut, extra={k: v[:PANE - 2] for k, v in p0.extra.items()})])
    uneven = "no error"
except ValueError as e:
    uneven = str(e)
print(json.dumps({"placed": placed, "old_placed": old_placed, "same": same,
                  "counters": counters, "old_counters": old_counters, "expect": expect,
                  "panes": len(panes), "uneven": uneven,
                  "mesh": [d.id for d in mesh.devices.flat]}))
"""


@pytest.fixture(scope="module")
def staged():
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"},
    )
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_each_node_share_is_staged_on_its_own_device(staged):
    quarter, mesh = 1024 // 4, staged["mesh"]
    assert staged["placed"]
    for name, spec, committed, shares in staged["placed"]:
        assert spec == "PartitionSpec('data',)", (name, spec)
        assert committed, name
        assert shares == [[mesh[d], d * quarter, (d + 1) * quarter] for d in range(4)], (name, shares)
    # staged the old way, the whole pane lands on one device
    for name, _, committed, shares in staged["old_placed"]:
        assert not committed and len(shares) == 1 and shares[0][1:] == [None, None], name


def test_sharded_staging_is_bit_identical_to_unsharded(staged):
    assert staged["same"]


def test_sharded_run_counts_its_stages_and_uplink_bytes(staged):
    c, n = staged["counters"], staged["panes"]
    assert c["runtime.sharded_stages"] == n
    assert "runtime.sharded_stages" not in staged["old_counters"]
    for name, expected in staged["expect"].items():
        assert expected > 0 and c[name] == staged["old_counters"][name] == expected, name


def test_uneven_pane_fails_at_staging(staged):
    assert "divisible by 4" in staged["uneven"], staged["uneven"]
