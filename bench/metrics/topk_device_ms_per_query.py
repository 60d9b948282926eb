"""Device time of the fused top-k rung programs per query row answered,
in ms: the programs of the trace that ran inside a ``rung_dispatch``
host span, over the requests answered (padding rows not counted)."""


def read(win):
    t = win.dispatch_device_s()
    if t is None or not win.answered:
        return None
    return t / win.answered * 1e3
