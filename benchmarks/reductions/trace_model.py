"""A profiler trace (``.xplane.pb``) as plain lists, and the reductions
every traced run makes: device busy time, the costliest device operations
and the longest idle gaps.

Layout of a trace taken inside the kwok daemon on one TPU v5e (looked at
by hand, PERF.md section 3): the device is the plane ``/device:TPU:0``;
its line ``XLA Modules`` holds one event per program execution, named
``<jit name>(<fingerprint>)``, and ``XLA Ops`` one event per HLO
operation; host threads are lines of the plane ``/host:CPU``."""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

#: (name, start_s, duration_s)
Event = Tuple[str, float, float]


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """plane name -> line name -> events, times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                evs.append((ev.name, ev.start_ns / 1e9, ev.duration_ns / 1e9))
    return out


def device_planes(trace) -> Dict[str, Dict[str, List[Event]]]:
    return {n: ls for n, ls in trace.items() if re.match(r"/device:TPU:\d+$", n)}


def module_events(trace) -> List[Event]:
    """Program executions on the first device."""
    planes = device_planes(trace)
    if not planes:
        return []
    return sorted(planes[min(planes)].get("XLA Modules", []), key=lambda e: e[1])


def op_events(trace) -> List[Event]:
    planes = device_planes(trace)
    if not planes:
        return []
    return sorted(planes[min(planes)].get("XLA Ops", []), key=lambda e: e[1])


def union_s(events: List[Event]) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, None
    for _n, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def window(trace) -> Optional[Tuple[float, float]]:
    """The traced interval: from the first to the last instant any line of
    any plane has an event."""
    lo = hi = None
    for lines in trace.values():
        for evs in lines.values():
            for _n, start, dur in evs:
                lo = start if lo is None else min(lo, start)
                hi = start + dur if hi is None else max(hi, start + dur)
    return None if lo is None else (lo, hi)


def busy_and_window(trace) -> Optional[Tuple[float, float]]:
    """(seconds in which an operation ran on the device, averaged over the
    devices; seconds of trace).  None without a device plane."""
    planes = device_planes(trace)
    win = window(trace)
    if not planes or win is None:
        return None
    busy = [union_s(ls.get("XLA Ops") or ls.get("XLA Modules") or []) for ls in planes.values()]
    return sum(busy) / len(busy), win[1] - win[0]


#: the python tracer's frames (``$file.py:line function``) that say what the
#: daemon's tick threads were doing; the program has no TraceAnnotation yet
HOST_FRAMES = ("$device_player.py", "$device_lease.py", "$simulator.py", "$informer.py",
               "$compiler.py", "$client.py:8")


def program_name(name: str) -> str:
    """``jit_f(<fingerprint>)`` -> ``jit_f``: one name for every compiled variant."""
    return re.sub(r"\(\d+\)$", "", name)


def breakdown(trace) -> Optional[dict]:
    """The ten device programs that took most time, and the ten longest
    gaps between program executions, each named by the innermost frame of the
    daemon's own code that spans most of it (or ``unattributed``)."""
    mods = module_events(trace)
    if not mods:
        return None
    per: Dict[str, float] = {}
    for name, _s, dur in mods:
        per[program_name(name)] = per.get(program_name(name), 0.0) + dur
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:10]
    host = [e for n, ls in trace.items() if n.startswith("/host:")
            for evs in ls.values() for e in evs if e[0].startswith(HOST_FRAMES)]
    gaps = []
    end = None
    for _name, start, dur in mods:
        if end is not None and start > end:
            gaps.append((end, start))
        end = max(end or 0.0, start + dur)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    named = []
    for lo, hi in gaps:
        # the innermost frame of the daemon's own code that spans most of the gap
        best, best_dur = "unattributed", None
        for name, start, dur in host:
            cover = min(hi, start + dur) - max(lo, start)
            if cover >= 0.6 * (hi - lo) and (best_dur is None or dur < best_dur):
                best, best_dur = name, dur
        named.append([best, hi - lo])
    return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": named}
