"""Mean host milliseconds a pane spends in the session's fusion-group passes
(window arrays, program choice, pass dispatch, uplink codec, ring append):
the ``session.pass`` span's total over the window's panes, as the window
runtime's ``RuntimeStats.spans`` reports it.  None where the program records
no such span."""


def read(window):
    stats = window.runtime_stats
    span = (getattr(stats, "spans", None) or {}).get("session.pass")
    if span is None or not stats.panes_processed:
        return None
    return 1e3 * span["total_s"] / stats.panes_processed
