"""95th percentile of a pane's wait in the runtime's ingest queue (enqueue to
dequeue), as ``StreamRuntime.stats()`` reports it."""


def read(window):
    return window.runtime_stats.queue_wait["p95_ms"] if window.records else None
