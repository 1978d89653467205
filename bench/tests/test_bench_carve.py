"""The ``carve_ms.edge4`` reader: the ``session.carve`` span's host
milliseconds a pane, and nothing where the program records no such span or
the window processed no pane."""

from __future__ import annotations

import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def _read(stats):
    from bench import cell

    read = cell.load_module(ROOT / "bench" / "metrics" / "carve_ms.edge4.py").read
    return read(types.SimpleNamespace(runtime_stats=stats))


def _span(total_s):
    return {"count": 16, "total_s": total_s, "self_s": total_s}


def test_carve_reader_gives_ms_per_pane():
    stats = types.SimpleNamespace(panes_processed=4, spans={
        "session.carve": _span(0.018), "session.emit": _span(0.5),
        "runtime.dispatch": _span(1.2)})
    assert _read(stats) == pytest.approx(4.5)


@pytest.mark.parametrize("stats", [
    types.SimpleNamespace(panes_processed=4, spans={"session.emit": _span(0.5)}),
    types.SimpleNamespace(panes_processed=4, spans={}),
    types.SimpleNamespace(panes_processed=4),
    types.SimpleNamespace(panes_processed=0, spans={"session.carve": _span(0.0)}),
], ids=["other_spans", "no_spans", "no_span_field", "no_panes"])
def test_carve_reader_reads_nothing_without_span_or_panes(stats):
    assert _read(stats) is None
