"""Carving batched finalize results: each member's view is its row of every
stacked leaf, taken by one compiled program per batch shape (the row is
traced), bit-identical to slicing each leaf; on four devices the view keeps
the replicated sharding; only members that are read get carved, once each;
and carving another row of a warmed batch shape compiles nothing."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.core import (
    SHENZHEN_BBOX,
    AggSpec,
    EdgeCloudPipeline,
    PipelineConfig,
    Query,
    StreamSession,
    WindowSpec,
    make_table,
    spans,
    windows,
)
from repro.data.streams import shenzhen_taxi_stream

ROOT = Path(__file__).resolve().parents[1]
PANE = 4_000
SOUTH = ((22.45, 22.66), (113.76, 114.64))
NORTH = ((22.64, 22.86), (113.76, 114.64))

# tenants that share finalize signatures pairwise or more (they differ only
# in ROI / method / mode), so every due window emits through a batch
TENANTS = (
    Query(aggs=(AggSpec("mean", "value"), AggSpec("max", "value")), roi=SOUTH),
    Query(aggs=(AggSpec("mean", "value"), AggSpec("max", "value")), roi=NORTH),
    Query(aggs=(AggSpec("mean", "value"), AggSpec("max", "value")), method="bernoulli"),
    Query(aggs=(AggSpec("count", "value"), AggSpec("sum", "value"), AggSpec("min", "value")),
          group_by="neighborhood"),
    Query(aggs=(AggSpec("count", "value"), AggSpec("sum", "value"), AggSpec("min", "value")),
          group_by="neighborhood", roi=SOUTH),
    Query(aggs=(AggSpec("count", "value"), AggSpec("sum", "value"), AggSpec("min", "value")),
          group_by="neighborhood", mode="raw"),
)


@pytest.fixture(scope="module")
def pipe():
    return EdgeCloudPipeline(make_table(*SHENZHEN_BBOX, precision=4), PipelineConfig())


@pytest.fixture(scope="module")
def panes():
    return list(windows.count_windows(shenzhen_taxi_stream(num_chunks=1, seed=11), PANE))[:3]


def _session(pipe, window=None, tenants=TENANTS):
    sess = StreamSession(pipe, initial_fraction=0.7)
    for q in tenants:
        sess.register(q, window=window)
    return sess


def _same_bits(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and a.tobytes() == b.tobytes()


def _check_views(results):
    """Every batched member of ``results``: its carved view against a slice
    of each stacked leaf.  Returns the members checked."""
    sources = dict(results._sources)
    for qid, (batch, row) in sources.items():
        res = results[qid]
        ref = jax.tree.map(lambda x: x[row], (batch.estimates, batch.stats))
        got = (res.estimates, res.stats)
        assert jax.tree.structure(got) == jax.tree.structure(ref), qid
        for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            assert _same_bits(g, r), qid
        assert (res.n_sampled, res.n_valid, res.n_overflow, res.n_truncated,
                res.comm_bytes, res.n_dropped) == batch.counters[row]
    return len(sources)


@pytest.mark.parametrize("window", [None, WindowSpec("sliding", size=2)],
                         ids=["tumbling", "sliding2"])
def test_carved_views_are_bit_identical_to_leaf_slices(pipe, panes, window):
    sess = _session(pipe, window)
    key, checked = jax.random.key(3), 0
    for pane in panes:
        key, sub = jax.random.split(key)
        checked += _check_views(sess.step(sub, pane).results)
    checked += _check_views(sess.emit_all(key))
    # six tenants in two finalize signatures: every emit is batched
    assert checked == (len(panes) + 1) * len(TENANTS)


def test_only_read_members_are_carved_once(pipe, panes):
    sess = _session(pipe)
    step = sess.step(jax.random.key(4), panes[0])
    rec = spans.Recorder(lambda: 0.0)
    qids = [r.qid for r in sess.registrations]
    with rec.active():
        assert all(q in step.results for q in qids)
        assert len(step.results) == len(qids)
        assert sorted(step.results.keys()) == qids
        assert rec.counters().get("session.carved_results", 0) == 0
        step.results[qids[1]]
        assert rec.counters()["session.carved_results"] == 1
        step.results[qids[1]]
        step.results.get(qids[1])
        assert rec.counters()["session.carved_results"] == 1
        step.results.values()
    assert rec.counters()["session.carved_results"] == len(qids)
    assert rec.spans()["session.carve"]["count"] == len(qids)


def test_another_row_of_a_warm_batch_shape_compiles_nothing(pipe, panes):
    # a batch shape no other test here carves: three tenants, padded to four
    tenants = tuple(
        Query(aggs=(AggSpec("min", "value"), AggSpec("max", "value")), group_by="stratum",
              roi=roi, bootstrap_replicates=0)
        for roi in (SOUTH, NORTH, None)
    )
    sess = _session(pipe, tenants=tenants)
    rec = spans.Recorder(lambda: 0.0)
    qids = [r.qid for r in sess.registrations]
    with rec.active():
        first = sess.step(jax.random.key(5), panes[0]).results
        first[qids[0]]
        warm = rec.counters()
        assert warm.get("compiles.session.carve", 0) >= 1, warm
        first[qids[1]]
        sess.step(jax.random.key(6), panes[1]).results[qids[2]]
        after = rec.counters()
    assert after.get("compiles.session.carve", 0) == warm["compiles.session.carve"], after
    assert after["session.carved_results"] == 3


SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
import numpy as np, jax
from repro.core import AggSpec, EdgeCloudPipeline, PipelineConfig, Query, StreamSession, make_table, windows
from repro.data.streams import shenzhen_taxi_stream
from repro.sharding.compat import compat_make_mesh

root = sys.argv[1]
config = json.load(open(f"{root}/bench/configs/taxi_gh6_edge4.json"))
traffic = json.load(open(f"{root}/bench/traffic/edge4_backlog.json"))
NODES, PANE = config["edge_nodes"], 8000
table = make_table(*config["bbox"], precision=config["precision"],
                   neighborhood_precision=config["neighborhood_precision"])
mesh = compat_make_mesh((NODES,), ("data",))
pipe = EdgeCloudPipeline(table, PipelineConfig(raw_capacity=PANE // NODES), mesh=mesh)
sess = StreamSession(pipe, sharded=True)
for q in traffic["queries"]:
    roi = q.get("roi")
    sess.register(Query(aggs=tuple(AggSpec(k, c) for k, c in q["aggs"]),
                        group_by=q.get("group_by"), mode=q["mode"],
                        roi=tuple(map(tuple, roi)) if roi is not None else None),
                  initial_fraction=q["fraction"])
stream = shenzhen_taxi_stream(num_vehicles=config["stream"]["num_vehicles"], chunk_size=PANE,
                              num_chunks=2, seed=0)
key, out = jax.random.key(8), {"members": 0, "leaves": 0, "same": True, "specs": [],
                               "replicated": True, "devices": []}
for pane in windows.pane_windows(stream, pane_tuples=PANE):
    key, sub = jax.random.split(key)
    results = sess.step(sub, pane).results
    for qid, (batch, row) in dict(results._sources).items():
        res = results[qid]
        got = jax.tree.leaves((res.estimates, res.stats))
        ref = jax.tree.leaves(jax.tree.map(lambda x: x[row], (batch.estimates, batch.stats)))
        out["members"] += 1
        out["leaves"] += len(got)
        for g, r in zip(got, ref):
            a, b = np.asarray(g), np.asarray(r)
            out["same"] &= a.dtype == b.dtype and a.shape == b.shape and a.tobytes() == b.tobytes()
            out["same"] &= g.sharding.is_equivalent_to(r.sharding, g.ndim)
            out["replicated"] &= g.sharding.is_fully_replicated
            out["specs"].append(str(getattr(g.sharding, "spec", None)))
            out["devices"].append(sorted(d.id for d in g.sharding.device_set))
out["specs"] = sorted(set(out["specs"]))
out["devices"] = sorted(set(map(tuple, out["devices"])))
print(json.dumps(out))
"""


@pytest.mark.xdist_group("subprocess-heavy")
def test_four_node_views_are_bit_identical_and_replicated():
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(ROOT)], capture_output=True, text=True, timeout=600,
        cwd=ROOT, env={**os.environ, "PYTHONPATH": str(ROOT / "src")},
    )
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    # four queries in two finalize signatures (preagg and raw alike), two panes
    assert out["members"] == 2 * 4, out
    assert out["leaves"] > out["members"]
    assert out["same"], out
    assert out["replicated"] and out["specs"] == ["PartitionSpec()"], out
    assert out["devices"] == [[0, 1, 2, 3]], out
