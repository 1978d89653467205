"""Share of the window in which no operation ran on the device (profiler
trace; mean over the cell's chips), in percent."""


def read(window):
    t = window.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
