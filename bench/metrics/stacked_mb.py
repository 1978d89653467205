"""Megabytes (10^6 bytes) of ring state a pane stacks for its multi-pane
windows: the ``session.stacked_bytes`` counter over the window's panes, as
the window runtime's ``RuntimeStats.counters`` reports it.  Grows with the
strata, not with the pane's tuples.  None where the program keeps no such
counter."""


def read(window):
    stats = window.runtime_stats
    n = (getattr(stats, "counters", None) or {}).get("session.stacked_bytes")
    if n is None or not stats.panes_processed:
        return None
    return n / 1e6 / stats.panes_processed
