"""``correct`` on the CPU at a tiny size: a sound run of the cell's timed
path comes out true, and the control, the program's own bfloat16 staging
path, comes out false."""

from __future__ import annotations

import time

from bench import reference


def _run(ctx, **kw):
    window, checks = __import__("bench.cell").cell.run_window(
        ctx, 4000000021, 1.0, t_process=time.perf_counter(), log=lambda *a, **k: None, **kw
    )
    return window, checks, reference.verdicts(checks)


def test_sound_run_is_correct(tiny_cell):
    window, checks, verdicts = _run(tiny_cell("taxi_gh6.dashboard_paced"))
    assert all(ok for _, _, ok in verdicts.values()), checks
    assert window.records and window.offered >= len(window.records)
    assert checks["steps_checked"] >= 1
    assert 0.0 < checks["sum_err_x_f32_bound"] <= 1.0
    assert window.latencies_ms().size == 5 * len(window.records)


def test_bfloat16_control_is_not_correct(tiny_cell):
    _, checks, verdicts = _run(
        tiny_cell("taxi_gh5.dashboard_backlog"),
        pipeline_overrides={"backend": "fused", "staging_dtype": "bfloat16"},
    )
    assert not verdicts["sum_err_x_f32_bound"][2], checks
    assert checks["sum_err_x_f32_bound"] > 10.0
