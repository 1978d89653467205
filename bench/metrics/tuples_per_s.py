"""Tuples of the panes whose every result was ready inside the window, over
the window's seconds: all the work completed, over all the time of the
window.  A pane still in flight at the close counts for nothing, so the
rate moves in steps of one pane (a 10 s window of 200,000-tuple panes holds
about fifty)."""


def read(window):
    tuples, _ = window.completed_tuples()
    return tuples / window.seconds if tuples else None
