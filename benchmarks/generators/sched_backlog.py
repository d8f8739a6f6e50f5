"""``sched_backlog``: scheduler_perf's SchedulingBasic on a kwok cluster.
Every pod is one template, the workload's ``pod-default``: one container
that requests ``cpu_milli`` CPU and ``memory_mi`` MiB, and the kwok
toleration.  A pod the scheduler binds and a pod created bound to the same
node therefore have one spec, and one pod signature.

``warm``: ``standing_pods`` created with ``spec.nodeName``, round-robin over
all nodes in an order the seed draws (one a node where they are as many),
and waited to Running; then ``init_pods`` created WITHOUT ``spec.nodeName``
(the workload's initPods), which only the cluster's scheduler can bind, and
waited to Running.

``run``: one closed-loop client.  Whenever fewer than ``backlog`` of the
pods it created are not yet seen Running, it posts ``bulk_size`` more
unbound pods in one bulk; it polls every ``POLL_S``.  It stops at the close
of the window, or once ``max_pods`` are sent, a guard the loop should never
reach.  So the scheduler always has work queued and sets the pace.

``settle``: the backlog is waited for, up to a minute past the close; then
every pod and node is listed and held to the plain reference
(``references/sched_basic.py``: each bind feasible, no node over its
allocatable, no pod left unbound that a node could take).  A run with a
violation raises, with the count and the first findings.

Names are unique over the run: ``standing-<i>``, ``init-<i>``,
``sched-<i>``."""

from __future__ import annotations

import time

from ..harness.cluster import Failed
from ..references import sched_basic
from . import SETTLE_S, pod

POLL_S = 0.05
#: seconds the standing and the init pods may each take to reach Running
WARM_WAIT_S = 300.0


def sched_pod(name: str, params: dict, node: str = "") -> dict:
    """``pod-default`` with the kwok toleration; unbound where ``node`` is empty."""
    p = pod(name, node)
    if not node:
        del p["spec"]["nodeName"]
    p["spec"]["containers"][0]["resources"] = {"requests": {
        "cpu": f"{params['cpu_milli']}m", "memory": f"{params['memory_mi']}Mi"}}
    return p


def _bulks(load, items: list):
    size = load.params["bulk_size"]
    return (items[lo:lo + size] for lo in range(0, len(items), size))


class Backlog:
    """The client's pods that are created and not yet seen Running."""

    def __init__(self, load):
        self.load = load
        self.next = 0
        self.waiting: set = set()

    def outstanding(self) -> int:
        running = self.load.watcher.running_at
        self.waiting.difference_update([n for n in self.waiting if n in running])
        return len(self.waiting)

    def post(self, count: int, in_window: bool) -> None:
        pods = [sched_pod(f"sched-{i}", self.load.params)
                for i in range(self.next, self.next + count)]
        self.next += count
        self.load.bulk_create(pods, in_window)
        self.waiting.update(q["metadata"]["name"] for q in pods
                            if q["metadata"]["name"] in self.load.created)


def warm(load) -> None:
    if load.params.get("clients", 1) != 1:
        raise Failed("sched_backlog has one client")
    params = load.params
    nodes = [f"node-{i}" for i in range(load.sizes["nodes"])]
    load.rng.shuffle(nodes)
    standing = [sched_pod(f"standing-{i}", params, nodes[i % len(nodes)])
                for i in range(params["standing_pods"])]
    for bulk in _bulks(load, standing):
        load.bulk_create(bulk, in_window=False)
    if not load.watcher.wait_running(list(load.created), WARM_WAIT_S, poll=0.05):
        raise Failed(f"standing pods did not all reach Running in {WARM_WAIT_S:.0f} s")
    init = [sched_pod(f"init-{i}", params) for i in range(params["init_pods"])]
    for bulk in _bulks(load, init):
        load.bulk_create(bulk, in_window=False)
    t = time.monotonic()
    if not load.watcher.wait_running([p["metadata"]["name"] for p in init], WARM_WAIT_S,
                                     poll=0.05):
        raise Failed(f"init pods were not all bound and Running in {WARM_WAIT_S:.0f} s")
    load.log(f"{len(init)} init pods bound by the scheduler and Running in "
             f"{time.monotonic() - t:.1f} s")
    load.backlog = Backlog(load)


def run(load, t0: float, t1: float) -> None:
    backlog, params = load.backlog, load.params
    while time.monotonic() < t1:
        if backlog.outstanding() < params["backlog"] and backlog.next < params["max_pods"]:
            backlog.post(min(params["bulk_size"], params["max_pods"] - backlog.next),
                         in_window=True)
            continue
        time.sleep(POLL_S)


def settle(load, t1: float) -> None:
    left = t1 + SETTLE_S - time.monotonic()
    load.watcher.wait_running(load.in_window, max(left, 0.0), poll=0.25)
    pods, _rv = load.client.list_paged("Pod", namespace="default", page_size=5000)
    nodes = load.client.list("Node")[0]
    found = sched_basic.violations(pods, nodes)
    bound = sum(1 for p in pods if (p.get("spec") or {}).get("nodeName"))
    load.log(f"scheduling: {len(pods)} pods, {bound} bound, on {len(nodes)} nodes; "
             f"{len(found)} violations of the reference")
    if found:
        raise Failed(f"{len(found)} pods or nodes break the scheduling semantics "
                     f"(bind_violations); the first: {found[:3]}")
