"""Fused rung dispatches per top-k request answered in the window:
``segments.dispatch_stats()["fused"]`` over the window, over the
requests answered.  Batching pushes it below 1; τ-ladder and capacity
retries push it up."""


def read(win):
    if not win.answered or "fused" not in win.dispatch:
        return None
    return win.dispatch["fused"] / win.answered
