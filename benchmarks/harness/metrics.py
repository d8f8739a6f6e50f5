"""The arithmetic of the end-to-end metrics, as pure functions of the
watching client's event lists, so that a recorded list tests them.

Times are ``time.monotonic()`` seconds of this process."""

from __future__ import annotations

import datetime
import math
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: (arrival, pod name, kind) with kind "transition" (phase or Ready
#: differs from that pod's last event), "running" (also a transition:
#: the first event with phase Running) or "deleted"
PodEvent = Tuple[float, str, str]
#: (arrival, node name, spec.renewTime)
LeaseEvent = Tuple[float, str, str]


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least ``q`` of
    the sample at or below it.  None for an empty sample."""
    if not values:
        return None
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def transitions_per_s(events: Iterable[PodEvent], t0: float, t1: float) -> float:
    """All status transitions that arrived in [t0, t1] over all of it."""
    n = sum(1 for t, _name, kind in events if kind != "deleted" and t0 <= t <= t1)
    return n / (t1 - t0)


def create_to_running(sent: Dict[str, float], running_at: Dict[str, float]
                      ) -> Tuple[List[float], List[str]]:
    """For every pod in ``sent`` (name -> instant its bulk request was
    sent): seconds until its Running event arrived.  Second value: the
    pods that have none."""
    lat, missing = [], []
    for name, t in sent.items():
        r = running_at.get(name)
        if r is None:
            missing.append(name)
        else:
            lat.append(r - t)
    return lat, missing


def _renewals(events: Iterable[LeaseEvent]) -> Dict[str, List[Tuple[float, str]]]:
    """node -> (arrival, renewTime) of the events in which renewTime changed."""
    out: Dict[str, List[Tuple[float, str]]] = {}
    for t, node, renew in sorted(events):
        seen = out.setdefault(node, [])
        if not seen or seen[-1][1] != renew:
            seen.append((t, renew))
    return out


def lease_intervals(events: Iterable[LeaseEvent], t0: float, t1: float,
                    nodes: Iterable[str]) -> Tuple[List[float], List[str]]:
    """Per node, seconds between the arrivals of consecutive events in
    which ``spec.renewTime`` changed, both inside [t0, t1]: what a
    controller that judges nodes by observed renewals sees.  Second
    value: nodes with fewer than two renewals in the window (a lane that
    stopped), which have no interval to give."""
    by_node = _renewals(events)
    arrivals = {n: [t for t, _r in by_node.get(n, []) if t0 <= t <= t1] for n in nodes}
    intervals, starved = [], []
    for node, ts in arrivals.items():
        if len(ts) < 2:
            starved.append(node)
        intervals.extend(b - a for a, b in zip(ts, ts[1:]))
    return intervals, starved


def lease_longest_gap(events: Iterable[LeaseEvent], t0: float, t_end: float,
                      nodes: Iterable[str]) -> Tuple[float, Optional[str]]:
    """The longest time any node went without an observed renewal between
    ``t0`` and ``t_end`` (the opening of the window and the final read of
    the leases): between consecutive arrivals, from ``t0`` to the first and
    from the last to ``t_end``.  A node with none reads the whole span.
    Second value: that node."""
    by_node = _renewals(events)
    worst, who = 0.0, None
    for node in nodes:
        ts = [t for t, _r in by_node.get(node, []) if t0 <= t <= t_end]
        edges = [t0] + ts + [t_end]
        gap = max(b - a for a, b in zip(edges, edges[1:]))
        if gap > worst:
            worst, who = gap, node
    return worst, who


def _stamp(renew: str) -> Optional[float]:
    try:
        return datetime.datetime.fromisoformat(renew.replace("Z", "+00:00")).timestamp()
    except (AttributeError, ValueError):
        return None


def lease_pace(events: Iterable[LeaseEvent], t0: float, t_end: float, every_s: float,
               tolerance: float) -> Tuple[float, int]:
    """By how many seconds the lease plane runs ahead of its configured
    pace: ``every_s`` less the median difference of two consecutive
    ``renewTime`` stamps of one node (the daemon's own clock, so delivery
    does not jitter it), over the renewals that arrived in [t0, t_end]; 0
    when the median is a whole period or more.  The median, because a
    renewal that failed is tried again at once and a sound run has a few
    such pairs: the second value counts the pairs closer than
    ``(1 - tolerance) * every_s``.  A stamp that is no time reads as a
    difference of nought."""
    diffs = []
    for seen in _renewals(events).values():
        inside = [_stamp(r) for t, r in seen if t0 <= t <= t_end]
        diffs.extend(0.0 if a is None or b is None else b - a
                     for a, b in zip(inside, inside[1:]))
    if not diffs:
        return 0.0, 0
    diffs.sort()
    median = diffs[len(diffs) // 2]
    return max(every_s - median, 0.0), sum(1 for d in diffs if d < (1 - tolerance) * every_s)
