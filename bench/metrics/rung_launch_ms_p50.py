"""Median host time to launch one fused rung program, in ms: the
``rung_launch`` spans of the window (``core/segments.py``: argument
conversion and the jitted call, which enqueues the program and holds the
interpreter lock; the block on the device is ``rung_wait``)."""

import numpy as np

from bench import stages


def read(win):
    launches = stages.span_durations(win, "rung_launch")
    return float(np.median(launches)) * 1e3 if launches else None
