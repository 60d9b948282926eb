"""Idle share of the device over the traced top-k window, in %: one
minus the union of the intervals in which an operation ran, over the
window from the first scheduled request to the last answer."""


def read(win):
    if win.trace is None or not win.trace.ops or win.window_s() <= 0:
        return None
    return (1.0 - win.busy_s() / win.window_s()) * 100.0
