"""Mean host milliseconds of one ``StreamSession.step`` call (the session's
per-pane dispatch of every fusion group's pass and every due emit), timed
around the call by the harness's delegating wrapper."""


def read(window):
    if not window.records:
        return None
    return 1e3 * sum(r.dispatch_s for r in window.records) / len(window.records)
