"""Median time a top-k request waited in the scheduler's queue, in ms:
the ``queue_wait`` spans (``serving/scheduler.py``) of the window."""

import numpy as np


def read(win):
    if not win.queue_wait_s:
        return None
    return float(np.median(win.queue_wait_s)) * 1e3
