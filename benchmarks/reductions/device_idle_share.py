"""``device_idle_share``: 1 - (union of the device's operation intervals)
/ (traced interval), in per cent, from the profiler trace taken inside the
kwok daemon.  Nothing to read without a device plane."""

from . import trace_model


def reduce(trace, ctx):
    got = trace_model.busy_and_window(trace)
    if got is None or got[1] <= 0:
        return None
    busy, win = got
    return 100.0 * (1.0 - busy / win)
