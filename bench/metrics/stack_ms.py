"""Mean host milliseconds a pane spends stacking multi-pane windows' rings
on a leading pane axis (inside ``session.emit``): the ``session.stack``
span's total over the window's panes, as the window runtime's
``RuntimeStats.spans`` reports it.  None where the program records no such
span."""


def read(window):
    stats = window.runtime_stats
    span = (getattr(stats, "spans", None) or {}).get("session.stack")
    if span is None or not stats.panes_processed:
        return None
    return 1e3 * span["total_s"] / stats.panes_processed
