"""The end-to-end arithmetic on recorded event lists: a window that holds
a stall has to move the rate and both percentiles."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.harness import metrics  # noqa: E402


def pod_events(stall_at=None, stall_s=0.0):
    """1,000 pods sent at t=0, Running one every 10 ms from t=1; a stall
    delays every event after ``stall_at``."""
    evs = []
    for i in range(1000):
        t = 1.0 + i * 0.01
        if stall_at is not None and t >= stall_at:
            t += stall_s
        evs.append((t, f"p{i}", "running"))
    return evs


def test_percentile_is_nearest_rank():
    assert metrics.percentile([], 0.95) is None
    assert metrics.percentile([3.0], 0.95) == 3.0
    assert metrics.percentile(list(range(1, 101)), 0.95) == 95
    assert metrics.percentile(list(range(1, 101)), 0.5) == 50


def test_transitions_rate_is_all_events_over_the_whole_window():
    evs = pod_events() + [(5.0, "p0", "deleted"), (30.0, "late", "running")]
    assert metrics.transitions_per_s(evs, 0.0, 20.0) == pytest.approx(1000 / 20.0)
    # the window cuts: only arrivals inside it count
    assert metrics.transitions_per_s(evs, 0.0, 6.0) == pytest.approx(501 / 6.0)


def test_a_stall_moves_the_rate_and_the_tail():
    sent = {f"p{i}": 0.0 for i in range(1000)}
    calm, stalled = pod_events(), pod_events(stall_at=9.0, stall_s=8.0)
    r_calm = metrics.transitions_per_s(calm, 0.0, 12.0)
    r_stall = metrics.transitions_per_s(stalled, 0.0, 12.0)
    assert r_stall < 0.85 * r_calm
    lat_calm, _ = metrics.create_to_running(sent, {n: t for t, n, _k in calm})
    lat_stall, _ = metrics.create_to_running(sent, {n: t for t, n, _k in stalled})
    assert metrics.percentile(lat_stall, 0.95) > metrics.percentile(lat_calm, 0.95) + 7.0


def test_create_to_running_names_the_pods_that_never_ran():
    lat, missing = metrics.create_to_running({"a": 1.0, "b": 1.0}, {"a": 3.5})
    assert lat == [2.5] and missing == ["b"]


def lease_events(nodes, period, t_end, stall=None):
    evs = []
    for k, node in enumerate(nodes):
        t = 0.1 * k
        while t < t_end:
            at = t + (stall[1] if stall and t >= stall[0] else 0.0)
            evs.append((at, node, f"renew-{t:.3f}"))
            evs.append((at + 0.2, node, f"renew-{t:.3f}"))  # an update that renews nothing
            t += period
    return evs


def test_lease_intervals_follow_observed_renewals():
    nodes = [f"node-{i}" for i in range(10)]
    iv, starved = metrics.lease_intervals(lease_events(nodes, 10.0, 45.0), 0.0, 45.0, nodes)
    assert not starved and len(iv) == 10 * 4
    assert metrics.percentile(iv, 0.95) == pytest.approx(10.0)
    # a lane that stalls 6 s stretches one interval per node
    iv, starved = metrics.lease_intervals(
        lease_events(nodes, 10.0, 45.0, stall=(20.0, 6.0)), 0.0, 60.0, nodes)
    assert not starved and metrics.percentile(iv, 0.95) == pytest.approx(16.0)


def test_a_node_with_fewer_than_two_renewals_is_starved():
    evs = lease_events(["node-0"], 10.0, 45.0) + [(3.0, "node-1", "r0")]
    iv, starved = metrics.lease_intervals(evs, 0.0, 45.0, ["node-0", "node-1", "node-2"])
    assert starved == ["node-1", "node-2"] and len(iv) == 4


def stamped(node, times, delivery=0.0):
    """Renewals of ``node`` stamped at ``times`` (seconds after 09:00 UTC),
    each arriving ``delivery`` later."""
    day = "2026-09-30T09:"
    return [(t + delivery, node, f"{day}{int(t) // 60:02d}:{t % 60:09.6f}Z") for t in times]


def test_the_longest_gap_counts_the_edges_of_the_span():
    evs = stamped("node-0", [5, 15, 25, 35]) + stamped("node-1", [2, 12, 30])
    # node-0: 35 -> 50 is 15 s; node-1: 12 -> 30 is 18 s, 30 -> 50 is 20 s
    assert metrics.lease_longest_gap(evs, 0.0, 50.0, ["node-0", "node-1"]) == (20.0, "node-1")
    assert metrics.lease_longest_gap(evs, 0.0, 40.0, ["node-0", "node-1"]) == (18.0, "node-1")
    # a node that was never renewed reads the whole span; one renewed before it only, too
    assert metrics.lease_longest_gap(evs, 40.0, 90.0, ["node-0", "node-2"])[0] == 50.0


def test_the_pace_goes_by_the_stamps_and_by_their_median():
    # stamps 10 s apart whose arrivals jitter by seconds keep the pace
    evs = stamped("node-0", [0, 10, 20, 30])
    evs[1] = (13.5, "node-0", evs[1][2])
    assert metrics.lease_pace(evs, 0.0, 60.0, 10.0, 0.25) == (0.0, 0)
    # one renewal 6.5 s after the last (a failed one tried again) is counted, not judged
    evs = stamped("node-0", [0, 10, 20]) + stamped("node-1", [1, 7.5, 17.5, 27.5])
    assert metrics.lease_pace(evs, 0.0, 60.0, 10.0, 0.25) == (0.0, 1)
    # a lane at twice the pace is 5 s ahead
    fast = stamped("node-0", range(0, 40, 5)) + stamped("node-1", range(2, 42, 5))
    assert metrics.lease_pace(fast, 0.0, 60.0, 10.0, 0.25) == (5.0, 14)
    # renewals that arrived outside the span are not judged; no renewals read nought
    assert metrics.lease_pace(fast, 36.0, 60.0, 10.0, 0.25) == (0.0, 0)
