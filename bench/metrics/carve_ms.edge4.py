"""Mean host milliseconds a pane spends carving members' views out of
batched finalize results (one compiled program a view read): the
``session.carve`` span's total over the window's panes, as the window
runtime's ``RuntimeStats.spans`` reports it.  None where the program
records no such span."""


def read(window):
    stats = window.runtime_stats
    span = (getattr(stats, "spans", None) or {}).get("session.carve")
    if span is None or not stats.panes_processed:
        return None
    return 1e3 * span["total_s"] / stats.panes_processed
