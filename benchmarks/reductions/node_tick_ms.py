"""``node_tick_device_ms``: device milliseconds a Node tick takes.

The Node player's macro-tick runs under a jit name of its own
(``jit__run_node_ticks_collect_impl``), apart from the Pod player's that
``tick_roofline`` and ``pod_tick_ms`` read.  Its ticks and device time are
counted as ``tick_roofline.ticks_and_seconds`` counts the Pod player's: a
program execution holds as many ticks as its most repeated operation
repeats, the loop itself left out.

Nothing to read without a device plane or without the Node program in it,
as in a program that plays both kinds under one name."""

from __future__ import annotations

import bisect

from . import trace_model

#: ``ops/tick.py``'s jitted entry point of the Node player's macro-tick
NODE_PROGRAMS = ("jit__run_node_ticks_collect_impl",)


def ticks_and_seconds(trace, programs=NODE_PROGRAMS):
    """(ticks, device seconds) over the executions of ``programs``."""
    mods = [m for m in trace_model.module_events(trace)
            if trace_model.program_name(m[0]) in programs]
    ops = trace_model.op_events(trace)
    starts = [o[1] for o in ops]
    ticks, seconds = 0, 0.0
    for _name, start, dur in mods:
        lo = bisect.bisect_left(starts, start)
        hi = bisect.bisect_right(starts, start + dur)
        counts = {}
        for name, _s, _d in ops[lo:hi]:
            if name.startswith("%while"):
                continue  # the loop itself, once, spanning its iterations
            counts[name] = counts.get(name, 0) + 1
        ticks += max(counts.values(), default=1)
        seconds += dur
    return ticks, seconds


def reduce(trace, ctx):
    ticks, seconds = ticks_and_seconds(trace)
    if not ticks or seconds <= 0:
        return None
    return 1000.0 * seconds / ticks
