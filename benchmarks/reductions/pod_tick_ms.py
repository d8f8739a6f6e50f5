"""``pod_tick_device_ms``: device milliseconds a Pod tick takes.

The device time of the tick programs over the ticks they ran, both as
``tick_roofline`` counts them (``tick_roofline.ticks_and_seconds``, used
unchanged).  The Node player's macro-tick runs under a name of its own
(``jit__run_node_ticks_collect_impl``) that is not among
``tick_roofline.TICK_PROGRAMS``, so this is the Pod player's tick; a
program that plays both kinds under one name reads the mean of the two.

Nothing to read without a device plane or without a tick program in it."""

from __future__ import annotations

from .tick_roofline import ticks_and_seconds


def reduce(trace, ctx):
    ticks, seconds = ticks_and_seconds(trace)
    if not ticks or seconds <= 0:
        return None
    return 1000.0 * seconds / ticks
