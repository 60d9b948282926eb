"""Device idle time inside the scheduler's batches, per batch, in ms:
each ``batch`` span of the window (pop to last response, on the trace
clock) less the time in which an operation ran on the device.  This is
host work on a request's critical path (assembly, store and delta
preparation, launch, readback, respond); the idle time between batches
is arrival gaps."""

from bench import stages


def read(win):
    return stages.batch_host_gap_ms(win)
