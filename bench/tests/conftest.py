"""Shared set-up of the benchmark's own tests: the checkout on ``sys.path``
and cells cut to a size the CPU runs in seconds."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny(ctx: dict) -> dict:
    """The cell's own queries and queue, over 12,000 tuples in panes of
    2,000 (8,000 on four edge nodes) at a paced rate the CPU keeps up with."""
    ctx["config"]["stream"].update(chunk_size=2000, num_chunks=6)
    t = ctx["traffic"]
    t["pane_tuples"] = 8000 if ctx["config"]["edge_nodes"] > 1 else 2000
    t["check_steps"] = 6
    if t["arrival"] == "paced":
        t["tuples_per_s"] = 10_000
    return ctx


def cell_of(config: str, traffic: str) -> dict:
    """The cell ``config.traffic`` read from its files, whether or not
    ``BENCHMARK.json`` lists it, cut to the tiny size."""
    from bench import cell

    path = ROOT / "bench" / "configs" / f"{config}.json"
    entry = {"name": f"{config}.{traffic}", "config": config, "traffic": traffic,
             "chips": json.loads(path.read_text())["edge_nodes"]}
    return tiny(cell.read_cell(ROOT / "bench", entry, path))


@pytest.fixture
def tiny_cell():
    return lambda workload: cell_of(*workload.split("."))
