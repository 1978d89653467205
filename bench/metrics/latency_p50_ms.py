"""Median, over every result of the window, of the time from its pane's due
time (its newest tuple's creation) to the client's ready stamp."""

import numpy as np


def read(window):
    lat = window.latencies_ms()
    return float(np.percentile(lat, 50)) if lat.size else None
