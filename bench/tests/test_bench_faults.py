"""The rest of a run, with the timed path broken underneath, comes out not
correct: for each fault a one-chip cell can have."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

from bench import cell, reference
from repro.core import pipeline


def _stale_state(monkeypatch):
    """Every pane's edge pass returns the first pane's state."""
    orig = pipeline.EdgeCloudPipeline._pass_fn

    def pass_fn(self, plan, sharded):
        fn, first = orig(self, plan, sharded), []

        def run(*args):
            if not first:
                first.append(fn(*args))
            return first[0]

        run.lower = fn.lower
        return run

    monkeypatch.setattr(pipeline.EdgeCloudPipeline, "_pass_fn", pass_fn)


def _half_batch(monkeypatch):
    """The second half of every pane is left out; the rest is reduced."""
    orig = pipeline.EdgeCloudPipeline._window_arrays

    def window_arrays(self, window, plan):
        lat, lon, cols, valid = orig(self, window, plan)
        return lat, lon, cols, valid & (jnp.arange(valid.shape[0]) < valid.shape[0] // 2)

    monkeypatch.setattr(pipeline.EdgeCloudPipeline, "_window_arrays", window_arrays)


def _altered_answer(monkeypatch):
    """Every finalized sum comes out one part in a thousand high."""
    orig = pipeline.EdgeCloudPipeline._finalize_body

    def finalize_body(self, plan, num_panes):
        body = orig(self, plan, num_panes)

        def run(stats, key):
            est, merged = body(stats, key)
            est = {k: (v._replace(value=v.value * 1.001) if k.startswith("sum_") else v)
                   for k, v in est.items()}
            return est, merged

        return run

    monkeypatch.setattr(pipeline.EdgeCloudPipeline, "_finalize_body", finalize_body)


@pytest.mark.parametrize("fault", [_stale_state, _half_batch, _altered_answer],
                         ids=["state_unchanged", "half_batch", "answer_altered"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch, tiny_cell):
    fault(monkeypatch)
    _, checks = cell.run_window(tiny_cell("taxi_gh5.dashboard_backlog"), 4000000031, 1.0,
                                t_process=time.perf_counter(), log=lambda *a, **k: None)
    verdicts = reference.verdicts(checks)
    assert not all(ok for _, _, ok in verdicts.values()), checks
    assert jax.devices()[0].platform == "cpu"
