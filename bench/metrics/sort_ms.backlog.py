"""Device milliseconds per pane of HLO ``sort`` ops (the SRS rank draws of
``core/sampling.py``), from the profiler trace."""


def read(window):
    return window.trace.op_ms_per_pane("sort") if window.trace is not None else None
