"""Device milliseconds per pane of the uplink's collectives (``all-reduce``
from the preagg psum, ``all-gather`` from the raw buffer), mean over the
chips, from the profiler trace."""


def read(window):
    t = window.trace
    return t.op_ms_per_pane("all-reduce", "all-gather") if t is not None else None
