"""Mean host milliseconds a pane spends dispatching finalize programs
(single and batched, inside ``session.emit``): the ``session.finalize``
span's total over the window's panes, as the window runtime's
``RuntimeStats.spans`` reports it.  None where the program records no such
span."""


def read(window):
    stats = window.runtime_stats
    span = (getattr(stats, "spans", None) or {}).get("session.finalize")
    if span is None or not stats.panes_processed:
        return None
    return 1e3 * span["total_s"] / stats.panes_processed
