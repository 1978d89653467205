"""The reduction from a profiler trace to the per-layer numbers."""

from __future__ import annotations

import gzip
from pathlib import Path

import pytest

from bench import trace

ROOT = Path(__file__).resolve().parents[2]


def _plane(pid, name, lines):
    """A text-proto plane; ``lines`` is ``{line: [(event, start_ns, end_ns)]}``."""
    meta, body = {}, []
    for lid, (line, events) in enumerate(lines.items(), 1):
        evs = "\n".join(
            f"events {{ metadata_id: {meta.setdefault(n, len(meta) + 1)} "
            f"offset_ps: {a * 1000} duration_ps: {(b - a) * 1000} }}"
            for n, a, b in events
        )
        body.append(f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0\n{evs} }}')
    md = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                   for n, i in meta.items())
    return f'planes {{ id: {pid} name: "{name}"\n' + "\n".join(body) + "\n" + md + "\n}\n"


MS = 1_000_000  # ns

HAND = (
    _plane(1, "/device:TPU:0", {"XLA Ops": [
        ("sort.1", 1 * MS, 3 * MS), ("fusion.2", 2.5 * MS, 4 * MS),
        ("all-reduce.3", 6 * MS, 7 * MS), ("sort.1", 9 * MS, 11 * MS),
    ]})
    + _plane(2, "/host:CPU", {
        "loop": [("session.step", 0, 2 * MS), ("PjitFunction(run)", 0.2 * MS, 0.8 * MS),
                 ("session.step", 4.5 * MS, 5.5 * MS), ("session.step", 9.5 * MS, 9.8 * MS)],
        "gen": [("bench.window", 0, 10 * MS), ("loadgen.sleep", 7.5 * MS, 8.5 * MS)],
    })
).replace(".0 ", " ")


def _profile(text):
    import jax

    return jax.profiler.ProfileData.from_text_proto(text)


def test_reduction_of_a_hand_made_trace():
    r = trace.reduce(_profile(HAND), chips=1)
    # busy: [1, 4) + [6, 7) + [9, 10) ms of a 10 ms window
    assert r.window_s == pytest.approx(0.010)
    assert r.busy_s == pytest.approx(0.005)
    assert r.panes == 3
    assert r.op_s == pytest.approx({"sort.1": 0.003, "fusion.2": 0.0015, "all-reduce.3": 0.001})
    assert r.op_ms_per_pane("sort") == pytest.approx(1.0)
    assert r.op_ms_per_pane("all-reduce", "all-gather") == pytest.approx(1 / 3)
    assert r.op_ms_per_pane("copy") is None
    assert r.gaps == pytest.approx({"session.step/PjitFunction(run)": 0.001,
                                    "session.step": 0.002, "loadgen.sleep": 0.002})
    bd = r.breakdown()
    assert bd["device_ops"][0] == ["sort.1", pytest.approx(0.003)]
    assert [g[0] for g in bd["idle_gaps"]][0] in ("session.step", "loadgen.sleep")


def test_trimmed_text_proto_reduces_alike():
    pd = _profile(HAND)
    again = trace.reduce(_profile(trace.to_text_proto(pd, 0, 10 * MS, 1)), chips=1)
    first = trace.reduce(pd, chips=1)
    assert again.busy_s == pytest.approx(first.busy_s)
    assert again.gaps == pytest.approx(first.gaps)
    assert again.panes == first.panes


def test_union_merges_overlaps():
    import numpy as np

    s, e = trace.union(np.array([5.0, 0.0, 1.0, 8.0]), np.array([6.0, 2.0, 3.0, 9.0]))
    assert s.tolist() == [0.0, 5.0, 8.0] and e.tolist() == [3.0, 6.0, 9.0]


def test_reduction_of_a_recorded_chip_trace():
    """200 ms of a traced ``taxi_gh5.dashboard_backlog`` window on one TPU
    v5e, trimmed to the op line and the harness's host threads.  The
    expected numbers come from reading the slice by hand: walking its 1,339
    op events in start order, its 13 ``sort`` ops, its two ``session.step``
    spans (the first clipped at the slice's start) and the host events over
    each gap's middle."""
    text = gzip.open(ROOT / "bench/testdata/taxi_gh5.dashboard_backlog.trace.pbtxt.gz", "rt").read()
    r = trace.reduce(_profile(text), chips=1)
    assert r.window_s == pytest.approx(0.2)
    assert 100 * (1 - r.busy_s / r.window_s) == pytest.approx(19.922028, abs=1e-5)
    assert r.panes == 2
    assert r.op_ms_per_pane("sort") == pytest.approx(1.599602 / 2, rel=1e-6)
    assert r.op_ms_per_pane("all-reduce", "all-gather") is None
    bd = r.breakdown()
    assert bd["device_ops"][0][0].startswith("while.4 (s32[], s32[200000], s32[200000], u32[200000]")
    assert bd["device_ops"][0][1] == pytest.approx(0.049314358, rel=1e-6)
    assert bd["idle_gaps"][0] == ["session.step/PjitFunction(broadcast_in_dim)",
                                  pytest.approx(0.011022234, rel=1e-6)]
    assert dict(bd["idle_gaps"])["session.step"] == pytest.approx(
        0.004696388, rel=1e-6)
