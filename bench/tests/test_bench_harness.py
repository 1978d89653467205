"""The benchmark's inputs, its data-driven layout and its refusal to run
without a chip, on the CPU."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]


def test_copied_generator_reproduces_the_program_stream():
    from bench import cell
    from repro.core import SHENZHEN_BBOX
    from repro.data.streams import shenzhen_taxi_stream

    gen = cell.load_module(ROOT / "bench" / "traffic" / "shenzhen_taxi.py")
    for seed in (0, 2**31 + 7):
        ours = list(gen.stream(seed, SHENZHEN_BBOX, chunk_size=500, num_chunks=3))
        theirs = list(shenzhen_taxi_stream(chunk_size=500, num_chunks=3, seed=seed))
        assert len(ours) == len(theirs) == 3
        for a, b in zip(ours, theirs):
            assert a.keys() == b.keys()
            for k in a:
                np.testing.assert_array_equal(a[k], b[k])


@pytest.mark.parametrize("precision", [5, 6])
def test_reference_table_matches_the_program_table(precision):
    from bench import reference
    from repro.core import SHENZHEN_BBOX, make_table

    ours = reference.Table(SHENZHEN_BBOX, precision, 3)
    theirs = make_table(*SHENZHEN_BBOX, precision=precision, neighborhood_precision=3)
    np.testing.assert_array_equal(ours.codes, np.asarray(theirs.codes))
    np.testing.assert_array_equal(ours.group, np.asarray(theirs.neighborhood)[:-1])
    assert ours.num_groups == theirs.num_neighborhoods


def test_reference_passes_on_a_fraction_one_session(tiny_cell):
    """Every pane of a session of the cell's fraction-1.0 query, stepped by
    hand, agrees with the reference of its sliding window."""
    import jax

    from bench import cell, reference

    ctx = tiny_cell("taxi_gh5.dashboard_backlog")
    ctx["traffic"]["queries"] = [q for q in ctx["traffic"]["queries"] if q["fraction"] == 1.0]
    (q,) = ctx["traffic"]["queries"]
    replay = cell.Replay(ctx["dir"], ctx["config"], 2000, seed=5)
    sess, regs = cell.build_session(ctx["config"], ctx["traffic"], jax.devices())
    table = reference.Table(ctx["config"]["bbox"], 5, 3)
    size = q["window"]["size"]
    for i in range(replay.period + 1):
        step = sess.step(jax.random.key(i), replay.pane(i, 0.0))
        res = jax.device_get(step.results[regs[q["name"]].qid])
        panes = [replay.host(j % replay.period) for j in range(max(0, i - size + 1), i + 1)]
        checks = reference.check_result(res, reference.WindowRef(table, q, panes, 1))
        assert all(ok for _, _, ok in reference.verdicts(checks).values()), checks
        assert checks["sum_err_x_f32_bound"] > 0.0  # sums were compared


def _copy(tmp_path, with_program: bool):
    dst = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", dst / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    if with_program:
        shutil.copytree(ROOT / "src", dst / "src", ignore=shutil.ignore_patterns("__pycache__"))
    return dst


def test_every_entry_resolves_and_added_files_are_found(tmp_path):
    from bench import cell, run

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        ctx = cell.load_cell(ROOT, w["name"])
        assert (ROOT / "bench" / "traffic" / f"{ctx['config']['stream']['generator']}.py").is_file()
        for trace in (False, True):
            for m in run.metric_entries(spec, w["name"], trace):
                assert callable(cell.load_module(ROOT / "bench" / "metrics" / f"{m['name']}.py").read)
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            assert set(m.get("workloads", [])) <= {w["name"] for w in spec["workloads"]}

    # a later cell and metric arrive as new files and new entries only
    dst = _copy(tmp_path, with_program=False)
    (dst / "bench" / "traffic" / "new_mix.json").write_text(
        json.dumps(dict(json.loads((ROOT / "bench/traffic/dashboard_paced.json").read_text()),
                        tuples_per_s=123))
    )
    (dst / "bench" / "metrics" / "new_metric.py").write_text("def read(window):\n    return 7.0\n")
    new = json.loads((dst / "BENCHMARK.json").read_text())
    new["workloads"].append({"name": "taxi_gh5.new_mix", "config": "taxi_gh5",
                             "traffic": "new_mix", "chips": 1, "why": "test"})
    new["per_layer"].append({"name": "new_metric", "unit": "ms", "better": "lower",
                             "source": "host_clock", "layer": "session",
                             "moves": "latency_p95_ms", "workloads": ["taxi_gh5.new_mix"]})
    (dst / "BENCHMARK.json").write_text(json.dumps(new))
    ctx = cell.load_cell(dst, "taxi_gh5.new_mix")
    assert ctx["traffic"]["tuples_per_s"] == 123 and ctx["dir"] == dst / "bench"
    (m,) = run.metric_entries(ctx["spec"], "taxi_gh5.new_mix", True)
    assert cell.load_module(ctx["dir"] / "metrics" / f"{m['name']}.py").read(None) == 7.0


def _run_bench(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "taxi_gh5.dashboard_backlog",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_run_refuses_without_a_tpu():
    proc = _run_bench(ROOT)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_run_refuses_outside_a_checkout_of_the_program(tmp_path):
    proc = _run_bench(_copy(tmp_path, with_program=False))
    assert proc.returncode != 0
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
