"""Seconds of backend compiles from process start to the first request
of the window: ``repro.obs.compile_stats(until=...)``, fed by the
program's one ``jax.monitoring`` listener (persistent compile cache hits
do not compile)."""


def read(win):
    try:
        from repro.obs import compile_stats
    except ImportError:
        return None
    return compile_stats(until=win.t_win)["compile_s"]
