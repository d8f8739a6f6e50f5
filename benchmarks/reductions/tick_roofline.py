"""``tick_roofline``: how near the tick programs run to the chip's memory
bandwidth, in per cent.

Least time = ticks in the trace x bytes per tick / peak HBM bytes per
second.  The tick is an elementwise pass with gathers from small tables
and no matrix work, so bandwidth and not FLOP/s bounds it.  Bytes per tick
come from the SoA's shapes (``ops/tick.py::SoA``), the same whatever
implements the tick: every column of every row read once and written once,
and the fired stage written once.

Ticks are counted from the trace: a tick program runs ``num_ticks`` ticks
in one loop, and the trace shows each operation of the loop body once per
iteration inside the program's execution, so a program execution holds as
many ticks as its most repeated operation repeats (1 for the single-tick
program, which has no loop).  A program that unrolled its loop would be
counted as one tick: the share then reads low, never high.

Nothing to read without a device plane or without a tick program in it."""

from __future__ import annotations

import bisect

from . import trace_model

#: ``ops/tick.py``'s jitted entry points that advance the SoA
TICK_PROGRAMS = ("jit__run_ticks_collect_impl", "jit__run_ticks_impl", "jit__tick_impl")


def soa_bytes_per_tick(capacity: int, feature_columns: int) -> int:
    """Bytes one tick has to move for a SoA of ``capacity`` rows:
    ``features`` [N, C] int32; ``sig``, ``ovc``, ``stage``, ``fire_at``,
    ``del_ts`` [N] int32; ``active``, ``rematch`` [N] bool; each read and
    written; plus the fired stage [N] int8 written."""
    row = 4 * feature_columns + 4 * 5 + 2
    return capacity * (2 * row + 1)


def ticks_and_seconds(trace):
    """(ticks, device seconds) over the tick programs' executions."""
    mods = [m for m in trace_model.module_events(trace)
            if trace_model.program_name(m[0]) in TICK_PROGRAMS]
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
    peaks = ctx.get("peaks")
    if not peaks:
        return None
    ticks, seconds = ticks_and_seconds(trace)
    if not ticks or seconds <= 0:
        return None
    conf = ctx["config"]
    per_tick = soa_bytes_per_tick(conf["kwok_configuration"]["deviceCapacity"],
                                  conf["soa"]["pod_feature_columns"])
    least = ticks * per_tick / peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
