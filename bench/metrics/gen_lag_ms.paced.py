"""95th percentile of how late the load generator offered a pane against its
due time: a starved generator is not a fast system."""

import numpy as np


def read(window):
    return float(np.percentile(window.lags, 95)) * 1e3 if window.lags else None
