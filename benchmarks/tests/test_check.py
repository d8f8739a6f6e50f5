"""``correct`` on evidence built by hand: a sound run reads 0 everywhere,
and every control of ``harness/controls.py`` fails the number that is its
to catch (the controls also run on the chip at the cells' own size, with
``run.py --controls``)."""

import datetime
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.generators import pod  # noqa: E402
from benchmarks.harness import check, controls, promtext  # noqa: E402
from benchmarks.references import fast_stages  # noqa: E402

NOW = "2026-09-30T09:00:00Z"
KWOK = """\
kwok_stage_backend_info{kind="Pod",backend="device"} 1
kwok_stage_backend_info{kind="Pod",backend="host"} 0
kwok_stage_backend_info{kind="Node",backend="device"} 1
kwok_stage_transitions_total{kind="Pod",backend="device"} 12
"""


def stamp(x):
    """The reference's status with a real time wherever it says ``TIME``."""
    if isinstance(x, dict):
        return {k: stamp(v) for k, v in x.items()}
    if isinstance(x, list):
        return [stamp(v) for v in x]
    return NOW if x == fast_stages.TIME else x


def served(p: dict, i: int) -> dict:
    """``p`` as a sound cluster serves it after ``pod-ready``."""
    status = stamp(fast_stages.pod_ready_status(p, "10.0.0.1"))
    status["podIP"] = f"10.0.1.{i + 1}"
    status["initContainerStatuses"] = None  # an empty template range renders null
    return {**p, "status": status}


def renew_time(t: float) -> str:
    when = datetime.datetime(2026, 9, 30, 9, tzinfo=datetime.timezone.utc) \
        + datetime.timedelta(seconds=t)
    return when.isoformat(timespec="microseconds").replace("+00:00", "Z")


def leases():
    """Three nodes renewed every 10.2 s, watched from 5 s before a window of
    50 s to a final LIST 8 s after it; arrivals lag the stamps by 0.1 s."""
    nodes = [f"node-{i}" for i in range(3)]
    events = [(t + 0.1, n, renew_time(t)) for k, n in enumerate(nodes)
              for t in [95.0 + k + 10.2 * j for j in range(7)]]
    listed = {n: {"metadata": {"name": n},
                  "spec": {"holderIdentity": "kwok-controller", "leaseDurationSeconds": 40,
                           "renewTime": max(e for e in events if e[1] == n)[2]}}
              for n in nodes}
    return {"events": events, "t0": 100.0, "t_end": 158.0, "nodes": nodes, "listed": listed,
            "duration_s": 40, "renew_every_s": 10.0, "early_tolerance": 0.25,
            "holder": "kwok-controller"}


def evidence():
    pods = {f"p{i}": pod(f"p{i}", f"node-{i % 3}", "kwok.x-k8s.io/fake") for i in range(12)}
    full = {n: served(p, i) for i, (n, p) in enumerate(pods.items())}
    deleted = {"p0", "p1", "p2"}
    return {
        "lease": leases(), "crash_expected": {"p5": full["p5"], "canary-0": {"metadata": {"name": "canary-0"}}},
        "after_crash": {"p5": dict(full["p5"]), "canary-0": pod("canary-0", "node-0")},
        "created": pods, "deleted": deleted, "in_window": list(pods)[2:],
        "running_seen": set(pods), "deleted_seen": set(deleted),
        "running_status": {n: full[n]["status"] for n in pods},
        "listed": {n: p for n, p in full.items() if n not in deleted},
        "kwok": list(promtext.iter_samples(KWOK)), "node_ip": "10.0.0.1",
    }


def test_a_sound_run_reads_zero_everywhere():
    nums, first = check.numbers(evidence(), fast_stages)
    assert first is None and check.correct(nums)
    seconds = {"lease_longest_gap_s": (pytest.approx(10.2), 40),
               "lease_pace_ahead_s": (0.0, 2.5)}
    assert {k: v for k, v in nums.items() if k in seconds} == seconds
    assert all(v == 0 and limit == 0 for k, (v, limit) in nums.items() if k not in seconds)


CAUGHT_BY = {"lost_ack": "acked_creates_missing", "undeleted": "acked_deletes_present",
             "stale_watch": "seen_running_not_running", "altered_status": "status_mismatch",
             "shared_address": "duplicate_pod_ips", "stuck_lease": "lease_longest_gap_s",
             "hasty_lease": "lease_pace_ahead_s", "released_lease": "leases_not_held",
             "lost_in_crash": "lost_after_crash"}


@pytest.mark.parametrize("name", sorted(controls.CONTROLS))
@pytest.mark.parametrize("seed", [0, 1, 2147483659])
def test_every_control_is_not_correct(name, seed):
    ev = evidence()
    nums, _ = check.numbers(controls.CONTROLS[name](ev, seed), fast_stages)
    value, limit = nums[CAUGHT_BY[name]]
    assert not check.correct(nums) and value > limit and value >= 1
    if name == "hasty_lease":
        assert value == pytest.approx(10.0 - 5.1)  # every 10.2 s became every 5.1 s
    # the control judged a copy: the run's own evidence still reads sound
    assert check.correct(check.numbers(ev, fast_stages)[0])


def test_what_else_fails_the_numbers():
    ev = evidence()
    ev["running_seen"].discard("p5")
    ev["deleted_seen"].discard("p1")
    ev["kwok"] = list(promtext.iter_samples(
        KWOK.replace('kind="Node",backend="device"} 1', 'kind="Node",backend="device"} 0')
        + 'kwok_stage_transitions_total{kind="Node",backend="host"} 3\n'))
    nums, _ = check.numbers(ev, fast_stages)
    assert nums["never_running"][0] == 1 and nums["never_deleted"][0] == 1
    assert nums["kinds_off_device"][0] == 1 and nums["host_backend_transitions"][0] == 3


def test_the_lease_plane_is_held_to_the_configuration():
    ev = evidence()
    lease = ev["lease"]
    # a lane that stopped at the opening of the window: every node reads the whole span
    lease["events"] = [e for e in lease["events"] if e[0] < lease["t0"]]
    nums, _ = check.numbers(ev, fast_stages)
    assert nums["lease_longest_gap_s"] == (pytest.approx(58.0), 40) and not check.correct(nums)
    # a node with no Lease, one with another duration
    ev = evidence()
    lease = ev["lease"]
    del lease["listed"]["node-0"]
    lease["listed"]["node-1"]["spec"]["leaseDurationSeconds"] = 80
    nums, _ = check.numbers(ev, fast_stages)
    assert nums["leases_not_held"][0] == 2
    # one renewal tried again at once is a sound run; every stamp no time is not
    ev = evidence()
    lease = ev["lease"]
    lease["events"].append((150.0, "node-2", renew_time(149.5)))
    assert check.numbers(ev, fast_stages)[0]["lease_pace_ahead_s"][0] == 0.0
    lease["events"] = [(t, n, "soon" + r) for t, n, r in lease["events"]]
    assert check.numbers(ev, fast_stages)[0]["lease_pace_ahead_s"][0] == 10.0
    # a pod that came back from the crash with another status
    ev = evidence()
    ev["after_crash"]["p5"]["status"] = {"phase": "Pending"}
    assert check.numbers(ev, fast_stages)[0]["lost_after_crash"][0] == 1


def test_the_reference_says_what_differs():
    p = pod("p", "node-0")
    ok = served(p, 0)["status"]
    assert fast_stages.pod_mismatch(p, ok, "10.0.0.1") is None
    assert "hostIP" in fast_stages.pod_mismatch(p, {**ok, "hostIP": "10.9.9.9"}, "10.0.0.1")
    assert "podIP" in fast_stages.pod_mismatch(p, {**ok, "podIP": "none"}, "10.0.0.1")
    assert "phase" in fast_stages.pod_mismatch(p, {"phase": "Pending", "podIP": "10.0.1.1"},
                                               "10.0.0.1")
    # a time that is no time is a difference, not masked away
    assert "startTime" in fast_stages.pod_mismatch(p, {**ok, "startTime": "soon"}, "10.0.0.1")
    gates = {**p, "spec": {**p["spec"], "readinessGates": [{"conditionType": "x/y"}],
                           "initContainers": [{"name": "i", "image": "img"}]}}
    want = fast_stages.pod_ready_status(gates, "10.0.0.1")
    assert [c["type"] for c in want["conditions"]][-1] == "x/y"
    assert want["initContainerStatuses"][0]["state"]["terminated"]["reason"] == "Completed"
