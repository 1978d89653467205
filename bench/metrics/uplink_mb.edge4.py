"""Megabytes (10^6 bytes) a pane's fusion-group passes ship from each edge
node to the cloud tier: the ``uplink.preagg_bytes`` and ``uplink.raw_bytes``
counters over the window's panes, as the window runtime's
``RuntimeStats.counters`` reports them.  None where the program keeps
neither counter."""


def read(window):
    stats = window.runtime_stats
    counters = getattr(stats, "counters", None) or {}
    names = ("uplink.preagg_bytes", "uplink.raw_bytes")
    if not any(n in counters for n in names) or not stats.panes_processed:
        return None
    return sum(counters.get(n, 0) for n in names) / 1e6 / stats.panes_processed
