"""End-to-end behaviour tests: paper-claim gates + pipeline equivalences.

These validate the paper's claims against its own numbers:
  * MAPE < 10% at 80% sampling (Geohash-6)           [paper Fig 16]
  * Geohash-5 error < Geohash-6 error at 80%          [paper Fig 17-18]
  * error decreases monotonically with fraction       [paper Fig 15]
  * edge-decentralized == cloud-centralized accuracy  [paper Fig 20]
  * preagg and raw transmission agree exactly         [paper §3.6.4]
"""

import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    SHENZHEN_BBOX,
    AggSpec,
    Query,
    StreamSession,
    estimators,
    make_table,
    sampling,
    windows,
)
from repro.core.pipeline import EdgeCloudPipeline, PipelineConfig
from repro.data.streams import materialize, shenzhen_taxi_stream


@pytest.fixture(scope="module")
def stream_data():
    return materialize(shenzhen_taxi_stream(num_chunks=10, seed=3))


def _stratum_accuracy(data, precision, fraction, key, min_count=20):
    table = make_table(*SHENZHEN_BBOX, precision=precision)
    lat = jnp.asarray(data["lat"])
    lon = jnp.asarray(data["lon"])
    val = jnp.asarray(data["value"])
    sidx = table.assign(lat, lon)
    res = sampling.edgesos(key, sidx, table.num_slots, fraction)
    stats = estimators.sample_stats(val, sidx, res.mask, table.num_slots, counts=res.counts)
    full = estimators.sample_stats(val, sidx, jnp.ones_like(res.mask), table.num_slots)
    counts = np.asarray(res.counts)[:-1]
    est = np.asarray(stats.mean)[:-1]
    true = np.asarray(full.mean)[:-1]
    ok = (counts >= min_count) & (np.abs(true) > 1e-9)
    return float(np.mean(np.abs(est[ok] - true[ok]) / np.abs(true[ok])) * 100)


def test_paper_gate_mape_below_10_at_80(stream_data):
    mape = _stratum_accuracy(stream_data, 6, 0.8, jax.random.key(0))
    assert mape < 10.0, f"MAPE@80%={mape}"


def test_paper_geohash5_beats_geohash6(stream_data):
    m6 = _stratum_accuracy(stream_data, 6, 0.8, jax.random.key(1))
    m5 = _stratum_accuracy(stream_data, 5, 0.8, jax.random.key(1))
    assert m5 < m6, (m5, m6)


def test_paper_error_monotone_in_fraction(stream_data):
    mapes = [
        _stratum_accuracy(stream_data, 6, f, jax.random.key(2)) for f in (0.2, 0.5, 0.8)
    ]
    assert mapes[0] > mapes[1] > mapes[2], mapes


def test_edge_decentralized_matches_centralized(stream_data):
    """Paper Fig 20: decentralized (per-edge) sampling vs one-pass
    centralized sampling — no significant accuracy difference."""
    table = make_table(*SHENZHEN_BBOX, precision=5)
    lat = jnp.asarray(stream_data["lat"])
    lon = jnp.asarray(stream_data["lon"])
    val = jnp.asarray(stream_data["value"])
    sidx = table.assign(lat, lon)
    full = estimators.estimate(
        estimators.sample_stats(val, sidx, jnp.ones_like(sidx, bool), table.num_slots)
    )
    # centralized
    res_c = sampling.edgesos(jax.random.key(0), sidx, table.num_slots, 0.8)
    est_c = estimators.estimate(
        estimators.sample_stats(val, sidx, res_c.mask, table.num_slots, counts=res_c.counts)
    )
    # decentralized: 8 edges, independent sampling, merged stats
    parts = []
    for i, chunk in enumerate(np.array_split(np.arange(val.shape[0]), 8)):
        c = jnp.asarray(chunk)
        r = sampling.edgesos(jax.random.key(100 + i), sidx[c], table.num_slots, 0.8)
        parts.append(
            estimators.sample_stats(val[c], sidx[c], r.mask, table.num_slots, counts=r.counts)
        )
    est_e = estimators.estimate(estimators.merge_all(parts))
    true = float(full.mean)
    ape_c = abs(float(est_c.mean) - true) / abs(true)
    ape_e = abs(float(est_e.mean) - true) / abs(true)
    assert ape_c < 0.01 and ape_e < 0.01
    assert abs(ape_e - ape_c) < 0.005  # parity


def test_preagg_equals_raw_single_device(stream_data):
    """§3.6.4: both transmission modes give identical estimates."""
    table = make_table(*SHENZHEN_BBOX, precision=5)
    n = 40_000
    lat = jnp.asarray(stream_data["lat"][:n])
    lon = jnp.asarray(stream_data["lon"][:n])
    val = jnp.asarray(stream_data["value"][:n])
    pipe = EdgeCloudPipeline(table, PipelineConfig(mode="preagg"))
    wr = pipe.process_window(jax.random.key(3), lat, lon, val, jnp.ones(n, bool), jnp.float32(0.7))
    sidx = table.assign(lat, lon)
    res = sampling.edgesos(jax.random.key(3), sidx, table.num_slots, 0.7)
    # "raw mode": recompute stats from the kept tuples directly
    stats_raw = estimators.sample_stats(val, sidx, res.mask, table.num_slots, counts=res.counts)
    est_raw = estimators.estimate(stats_raw)
    assert float(wr.estimate.mean) == pytest.approx(float(est_raw.mean), rel=1e-5)


@pytest.mark.xdist_group("subprocess-heavy")
def test_sharded_pipeline_modes_agree_subprocess():
    """preagg == raw on an 8-device mesh (runs in a subprocess so the
    device-count env var doesn't leak into this process's jax; grouped with
    the other subprocess spawners on one xdist worker)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core import make_table, SHENZHEN_BBOX
from repro.core.pipeline import EdgeCloudPipeline, PipelineConfig
from repro.sharding.compat import compat_make_mesh
mesh = compat_make_mesh((8,), ("data",))
t = make_table(*SHENZHEN_BBOX, precision=5)
rng = np.random.default_rng(0)
N = 64_000
lat = jnp.asarray(rng.uniform(22.45, 22.86, N), jnp.float32)
lon = jnp.asarray(rng.uniform(113.76, 114.64, N), jnp.float32)
val = jnp.asarray(rng.normal(40, 8, N), jnp.float32)
outs = []
for mode in ("preagg", "raw"):
    pipe = EdgeCloudPipeline(t, PipelineConfig(mode=mode, raw_capacity=8000), mesh=mesh)
    wr = pipe.process_window_sharded(jax.random.key(1), lat, lon, val, jnp.ones(N, bool), 0.8)
    outs.append((float(wr.estimate.mean), float(wr.estimate.moe)))
assert abs(outs[0][0] - outs[1][0]) < 1e-4, outs
assert abs(outs[0][1] - outs[1][1]) < 1e-5, outs
print("MODES_AGREE", outs[0])
"""
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "MODES_AGREE" in r.stdout


def test_session_soak_mixed_methods_50_windows():
    """Soak the continuous-query engine: a 3-query mixed-method session
    (one SRS query + two *differing-ROI* Bernoulli queries) over 50+ panes
    of a synthetic mobility stream at the paper's headline 80% fraction.

    Gates: per-query MAPE vs the full-population per-pane truth stays
    under the paper's 10% figure, every query answers every pane, and
    cross-signature fusion serves the two Bernoulli ROIs with exactly ONE
    edge pass per pane (two passes per pane total: srs group + bernoulli
    group)."""
    roi_south = ((22.45, 22.66), (113.76, 114.64))
    roi_north = ((22.64, 22.86), (113.76, 114.64))
    q_srs = Query(aggs=(AggSpec("mean", "value"),))
    q_south = Query(aggs=(AggSpec("mean", "value", name="south"),),
                    method="bernoulli", roi=roi_south)
    q_north = Query(aggs=(AggSpec("mean", "occupancy", name="north"),),
                    method="bernoulli", roi=roi_north)

    table = make_table(*SHENZHEN_BBOX, precision=5)
    pipe = EdgeCloudPipeline(table)
    sess = StreamSession(pipe, initial_fraction=0.8)
    regs = [sess.register(q) for q in (q_srs, q_south, q_north)]
    assert len(sess._groups()) == 2  # srs + ONE fused cross-ROI bernoulli group

    def in_roi(pane, roi):
        (a, b), (c, d) = roi
        lat, lon = np.asarray(pane.lat), np.asarray(pane.lon)
        return np.asarray(pane.valid) & (lat >= a) & (lat <= b) & (lon >= c) & (lon <= d)

    stream = shenzhen_taxi_stream(num_chunks=11, chunk_size=20_000, seed=17)
    panes = list(windows.count_windows(stream, 4_000))
    assert len(panes) >= 50
    apes = {r.qid: [] for r in regs}
    truth_cols = (("value", None), ("value", roi_south), ("occupancy", roi_north))
    for i, pane in enumerate(panes):
        step = sess.step(jax.random.fold_in(jax.random.key(99), i), pane)
        assert set(step.results) == {r.qid for r in regs}  # every query, every pane
        for reg, (col, roi) in zip(regs, truth_cols):
            sel = np.asarray(pane.valid) if roi is None else in_roi(pane, roi)
            truth = float(np.mean(np.asarray(pane.columns[col])[sel]))
            est = float(np.asarray(
                next(iter(step.results[reg.qid].estimates.values())).value
            ))
            apes[reg.qid].append(abs(est - truth) / abs(truth))
    for reg in regs:
        mape = 100.0 * float(np.mean(apes[reg.qid]))
        assert mape < 10.0, f"qid={reg.qid} MAPE@80%={mape:.2f}"
    # exactly one edge pass per fusion group per pane, soak-long
    assert sess.total_passes == 2 * len(panes)
    assert sess.pane_index == len(panes)
