"""The program's own spans in a profiler trace, and the device's idle
time under them.

The kwok daemon's tick threads run every stage inside a
``jax.profiler.TraceAnnotation`` named ``kwok/<kind>/<stage>``
(``kwok_tpu/utils/telemetry.py::stage``), which the profiler stamps onto
a line of the plane ``/host:CPU`` on the clock of the device's plane.
Device idle time is the traced interval (``trace_model.window``) less the
union of the ``XLA Modules`` events of the first device plane; ``idle_share``
says which part of that interval it can judge.  A program
without annotations (the parent of the PR that brought them) has no
``kwok/`` event: every reduction here then reads nothing."""

from __future__ import annotations

from typing import List, Optional, Tuple

from . import trace_model

Interval = Tuple[float, float]

PREFIX = "kwok/"
#: the stages that run inside another stage of their thread
NESTED = ("store_bulk", "host_build", "compile")


def union(intervals: List[Interval]) -> List[Interval]:
    """Disjoint, sorted intervals covering the same instants."""
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        elif hi > lo:
            out.append((lo, hi))
    return out


def length(intervals: List[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Of two disjoint sorted lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """[lo, hi] less disjoint sorted ``intervals``."""
    out, at = [], lo
    for a, b in intervals:
        if a > at:
            out.append((at, min(a, hi)))
        at = max(at, b)
    if at < hi:
        out.append((at, hi))
    return [(a, b) for a, b in out if b > a]


def spans(trace, prefix: str = PREFIX) -> List[trace_model.Event]:
    """The events of any host line whose name starts with ``prefix``."""
    return [e for n, ls in trace.items() if n.startswith("/host:")
            for evs in ls.values() for e in evs if e[0].startswith(prefix)]


def device_idle(trace, within: Optional[Interval] = None) -> Optional[List[Interval]]:
    """The instants of the traced interval (or of the part of it inside
    ``within``) at which no program ran on the first device; None without a
    device plane."""
    win = trace_model.window(trace)
    if win is None or not trace_model.device_planes(trace):
        return None
    lo, hi = win if within is None else (max(win[0], within[0]), min(win[1], within[1]))
    busy = union([(s, s + d) for _n, s, d in trace_model.module_events(trace)])
    return complement(busy, lo, hi)


def idle_share(trace, kind: str, stage: str = "") -> Optional[float]:
    """Per cent of the device's idle time that lies under the spans
    ``kwok/<kind>/<stage>`` (any stage of the kind if none is named).

    The idle time is taken between the first start and the last end of a
    span of that kind: the profiler records an annotation only if the
    session saw it open and close, so the head of a trace up to the end of
    whatever stage was running when it started, and its tail while
    ``stop_trace`` runs, carry no span whatever the program does.  None
    without a device plane, without a span of the kind, or without idle
    time between them."""
    mine = [(s, s + d) for n, s, d in spans(trace, f"{PREFIX}{kind}/")]
    if not mine:
        return None
    idle = device_idle(trace, (min(a for a, _b in mine), max(b for _a, b in mine)))
    if not idle:
        return None
    named = f"{PREFIX}{kind}/{stage}"
    covered = union([(s, s + d) for _n, s, d in spans(trace, named)])
    return 100.0 * length(intersect(idle, covered)) / length(idle)


def name_gaps(trace, top: int = 10) -> List[list]:
    """The ``top`` longest idle gaps between the first and the last
    ``kwok/`` span, each as ``[seconds, {kind: [stage, share]}]``: for
    every kind whose spans touch the gap, the stage that covers most of it
    (of nested stages that cover alike, the inner one) and the share of the
    gap it covers.  A gap no span touches has an empty dict.  No metric
    reads this yet: PERF.md's table of gaps does, and ``trace_model.breakdown``
    should."""
    mine = spans(trace)
    if not mine:
        return []
    extent = (min(s for _n, s, _d in mine), max(s + d for _n, s, d in mine))
    by_name: dict = {}
    for name, s, d in mine:
        by_name.setdefault(name, []).append((s, s + d))
    by_name = {n: union(iv) for n, iv in by_name.items()}
    idle = sorted(device_idle(trace, extent) or [], key=lambda g: g[0] - g[1])[:top]
    named = []
    for gap in idle:
        best: dict = {}
        for name, iv in by_name.items():
            cover = length(intersect([gap], iv)) / (gap[1] - gap[0])
            kind, stage = name[len(PREFIX):].split("/", 1)
            # of stages that cover alike, one that nests in the others
            key = (round(cover, 2), stage in NESTED, -length(iv))
            if cover > 0 and (kind not in best or key > best[kind][0]):
                best[kind] = (key, [stage, cover])
        named.append([gap[1] - gap[0], {k: v[1] for k, v in best.items()}])
    return named
