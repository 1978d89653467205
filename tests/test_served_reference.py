"""The served path against the plain reference: every result a
``StreamSession`` emits over small Shenzhen panes is checked by
``bench.reference`` (NumPy, float64, nothing taken from the program).

Per granularity, window shape and query variant, the reference holds the
session to its guarantees: exact per-slot populations, SRS sizes
``round(f * N_k)``, exact extrema, float32-bounded sums and means, sampled
sums within what a sample of that size can give, and no raw-buffer
truncation (``PipelineConfig(raw_capacity=PANE)``).
"""

from __future__ import annotations

import functools
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
    StreamSession,
    WindowSpec,
    make_table,
    windows,
)
from repro.core.query import finalize_signature
from repro.data.streams import shenzhen_taxi_stream

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from bench import cell, reference  # noqa: E402

PANE = 2_000
NUM_PANES = 6
AGGS = (
    ("count", "value"),
    ("sum", "value"),
    ("mean", "value"),
    ("min", "value"),
    ("max", "value"),
    ("sum", "occupancy"),
)
# the south region of the dashboard_backlog traffic's "srs" query
SOUTH = ((22.44, 22.66), (113.75, 114.65))

WINDOWS = {
    "tumbling1": WindowSpec(),
    "tumbling2": WindowSpec(kind="tumbling", size=2),
    "sliding4": WindowSpec(kind="sliding", size=4),
    "hopping4/2": WindowSpec(kind="hopping", size=4, stride=2),
}
# each variant is the registrations of one session
VARIANTS = {
    "srs": [{"fraction": 1.0}],
    "grouped": [{"fraction": 1.0, "group_by": "neighborhood"}],
    "srs_half": [{"fraction": 0.5}],
    "srs_half_south": [{"fraction": 0.5, "roi": SOUTH}],
    "bernoulli": [{"fraction": 0.5, "method": "bernoulli"}],
    "raw": [{"fraction": 1.0, "mode": "raw"}],
    # equal finalize signatures: one batched finalize serves both
    "batched": [{"fraction": 1.0}, {"fraction": 0.5, "roi": SOUTH}],
}


@pytest.fixture(scope="module")
def panes():
    stream = shenzhen_taxi_stream(num_vehicles=64, chunk_size=PANE, num_chunks=NUM_PANES, seed=0)
    out = list(windows.count_windows(stream, PANE))
    assert len(out) == NUM_PANES
    return out


@pytest.fixture(scope="module")
def ref_panes(panes):
    return [
        (np.asarray(p.lat), np.asarray(p.lon),
         {"value": np.asarray(p.value), "occupancy": np.asarray(p.extra["occupancy"])})
        for p in panes
    ]


@functools.cache
def pipeline(precision: int) -> EdgeCloudPipeline:
    """One pipeline per granularity, shared by its cases: it caches its
    compiled pass and finalize programs by plan, so the cases reuse them."""
    table = make_table(*SHENZHEN_BBOX, precision=precision)
    return EdgeCloudPipeline(table, PipelineConfig(raw_capacity=PANE))


@pytest.mark.parametrize("variant", list(VARIANTS))
@pytest.mark.parametrize("window", list(WINDOWS))
@pytest.mark.parametrize("precision", [5, 6])
def test_session_results_match_reference(precision, window, variant, panes, ref_panes):
    pipe = pipeline(precision)
    ref_table = reference.Table(SHENZHEN_BBOX, precision, precision - 2)
    # slot k is the same cell on both sides only if the tables agree
    np.testing.assert_array_equal(np.asarray(pipe.table.codes), ref_table.codes)

    spec = WINDOWS[window]
    queries = [{"aggs": AGGS, **v} for v in VARIANTS[variant]]
    sess = StreamSession(pipe)
    regs = [
        sess.register(cell.build_query(q), window=spec, initial_fraction=q["fraction"])
        for q in queries
    ]
    assert len({finalize_signature(reg.plan) for reg in regs}) == 1
    checks = reference.empty_checks()
    checked = missing = 0
    key = jax.random.key(precision)
    for i, pane in enumerate(panes):
        step = sess.step(jax.random.fold_in(key, i), pane)
        if (i + 1) % spec.stride:
            assert not step.results
            continue
        covered = ref_panes[max(0, i + 1 - spec.size): i + 1]
        for reg, q in zip(regs, queries):
            assert step.fractions[reg.qid] == q["fraction"]
            if reg.qid not in step.results:
                missing += 1
                continue
            res = step.results[reg.qid]
            host = jax.device_get(res._replace(stats={
                c: {k: v for k, v in res.stats[c].items() if k in ("moments", "extrema")}
                for c in res.stats
            }))
            ref = reference.WindowRef(ref_table, q, covered, edge_nodes=1)
            checks = reference.merge_checks(checks, reference.check_result(host, ref))
            checked += 1
    checks["results_missing"] = float(missing)
    checks["steps_checked"] = float(checked)
    failed = {k: v for k, v in reference.verdicts(checks).items() if not v[2]}
    assert not failed, failed
