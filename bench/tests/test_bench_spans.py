"""The per-layer readers of the program's own host spans, and the names the
program may not take from the harness."""

from __future__ import annotations

import re
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
READERS = ("pass_dispatch_ms.backlog", "emit_ms.backlog")


def _reader(name):
    from bench import cell

    return cell.load_module(ROOT / "bench" / "metrics" / f"{name}.py").read


def test_session_span_readers_read_a_tiny_window(tiny_cell):
    from bench import cell

    window, _ = cell.run_window(
        tiny_cell("taxi_gh5.dashboard_backlog"), 4000000031, 1.0,
        t_process=time.perf_counter(), log=lambda *a, **k: None,
    )
    values = {name: _reader(name)(window) for name in READERS}
    assert all(v is not None and v > 0.0 for v in values.values()), values
    # both spans lie inside the session.step call the harness times
    assert sum(values.values()) <= _reader("dispatch_ms.backlog")(window)
    # a program that records no spans gives no reading, and no error
    bare = types.SimpleNamespace(runtime_stats=types.SimpleNamespace(panes_processed=3))
    assert all(_reader(name)(bare) is None for name in READERS)


def test_program_spans_avoid_the_harness_span_names():
    """``bench/trace.py`` counts panes as the harness's ``session.step``
    spans: a program span of that name, or of any harness span's, would be
    read as the harness's."""
    harness = set()
    for path in (ROOT / "bench").glob("*.py"):
        text = path.read_text()
        harness |= set(re.findall(r'TraceAnnotation\("([^"]+)"\)', text))
        harness |= set(re.findall(r'\bspan="([^"]+)"', text))
    assert {"session.step", "bench.window", "bench.warm", "loadgen.sleep", "sink.wait"} <= harness
    program = set()
    for path in (ROOT / "src" / "repro" / "core").glob("*.py"):
        program |= set(re.findall(r'spans\.span\(\s*"([^"]+)"', path.read_text()))
    assert {"runtime.dispatch", "session.pass", "session.emit"} <= program
    assert not program & harness
