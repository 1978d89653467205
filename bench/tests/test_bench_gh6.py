"""The Geohash-6 cells: ``taxi_gh6`` under the paced and the backlogged
dashboard traffic, as ``BENCHMARK.json`` lists them, and the emit-stage
readers ``stack_ms``, ``finalize_ms`` and ``stacked_mb``."""

from __future__ import annotations

import json
import time
import types
from pathlib import Path

import pytest

from bench.tests.conftest import tiny

ROOT = Path(__file__).resolve().parents[2]
CELLS = ("taxi_gh6.dashboard_paced", "taxi_gh6.dashboard_backlog")
READERS = ("stack_ms", "finalize_ms", "stacked_mb")


def _reader(name):
    from bench import cell

    return cell.load_module(ROOT / "bench" / "metrics" / f"{name}.py").read


@pytest.fixture(scope="module")
def runs():
    """One tiny window of each cell, read through ``BENCHMARK.json``."""
    from bench import cell

    out = {}
    for name in CELLS:
        ctx = tiny(cell.load_cell(ROOT, name))
        out[name] = cell.run_window(ctx, 4000000041, 1.0, t_process=time.perf_counter(),
                                    log=lambda *a, **k: None)
    return out


@pytest.mark.parametrize("workload", CELLS)
def test_gh6_cell_is_correct(runs, workload):
    from bench import reference

    window, checks = runs[workload]
    verdicts = reference.verdicts(checks)
    assert all(ok for _, _, ok in verdicts.values()), checks
    assert checks["steps_checked"] >= 1
    assert 0.0 < checks["sum_err_x_f32_bound"] <= 1.0
    assert window.records and window.dropped_panes == 0


def test_gh6_paced_traffic_differs_only_in_its_rate():
    bench = ROOT / "bench" / "traffic"
    base = json.loads((bench / "dashboard_paced.json").read_text())
    gh6 = json.loads((bench / "dashboard_paced_gh6.json").read_text())
    rate = gh6.pop("tuples_per_s")
    assert base.pop("tuples_per_s") is None
    assert isinstance(rate, (int, float)) and not isinstance(rate, bool) and rate > 0
    assert gh6 == base


def test_emit_stage_readers(runs):
    window, _ = runs["taxi_gh6.dashboard_backlog"]
    values = {name: _reader(name)(window) for name in READERS}
    assert all(v is not None and v > 0.0 for v in values.values()), values
    # both spans lie inside session.emit
    assert values["stack_ms"] + values["finalize_ms"] <= _reader("emit_ms.backlog")(window)
    # the sliding-4 grouped query stacks at most four panes of its state a pane
    leaves = window.runtime_stats.counters["session.host_stacked_leaves"]
    assert leaves > 0 and values["stacked_mb"] > 0.0
    # a program without the spans and counter gives no reading, and no error
    bare = types.SimpleNamespace(runtime_stats=types.SimpleNamespace(panes_processed=3))
    assert all(_reader(name)(bare) is None for name in READERS)
    empty = types.SimpleNamespace(runtime_stats=types.SimpleNamespace(
        panes_processed=3, spans={}, counters={}))
    assert all(_reader(name)(empty) is None for name in READERS)


def test_new_entries_resolve_through_load_cell():
    from bench import cell

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (config,) = [c for c in spec["configs"] if c["name"] == "taxi_gh6"]
    assert config["reduced"] == [] and (ROOT / config["file"]).is_file()
    for name in CELLS:
        ctx = cell.load_cell(ROOT, name)
        assert ctx["cell"]["chips"] == 1 and ctx["config"]["precision"] == 6
        assert ctx["config"]["edge_nodes"] == 1
    for m in spec["per_layer"]:
        if m["name"] in READERS:
            assert set(CELLS) <= set(m["workloads"])
            assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    metrics = {m["name"]: m for m in spec["end_to_end"] + spec["per_layer"]}
    assert set(CELLS) <= set(metrics["tuples_per_s"]["workloads"])
