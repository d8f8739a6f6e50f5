"""Scheduler (controllers/scheduler.py) — the kube-scheduler seat
(reference components/kube_scheduler.go:51): unbound pods get a node,
round-robin with capacity fit, over both store and HTTP client."""

import time

import pytest

from kwok_tpu.cluster.apiserver import APIServer
from kwok_tpu.cluster.client import ClusterClient
from kwok_tpu.cluster.store import ResourceStore
from kwok_tpu.controllers.scheduler import Scheduler


def make_node(name, cpu="4", memory="8Gi", pods="110", ready=True):
    return {
        "apiVersion": "v1",
        "kind": "Node",
        "metadata": {"name": name},
        "status": {
            "allocatable": {"cpu": cpu, "memory": memory, "pods": pods},
            "conditions": [
                {"type": "Ready", "status": "True" if ready else "False"}
            ],
        },
    }


def make_pod(name, cpu=None, memory=None):
    requests = {}
    if cpu:
        requests["cpu"] = cpu
    if memory:
        requests["memory"] = memory
    c = {"name": "c", "image": "i"}
    if requests:
        c["resources"] = {"requests": requests}
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": name, "namespace": "default"},
        "spec": {"containers": [c]},
        "status": {},
    }


def wait_until(cond, budget=10.0):
    deadline = time.monotonic() + budget
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return cond()


@pytest.fixture()
def sched_store():
    store = ResourceStore()
    sched = Scheduler(store).start()
    yield store
    sched.stop()


def bound_nodes(store):
    pods, _ = store.list("Pod")
    return {
        p["metadata"]["name"]: (p.get("spec") or {}).get("nodeName")
        for p in pods
    }


def test_binds_round_robin(sched_store):
    store = sched_store
    for i in range(3):
        store.create(make_node(f"node-{i}"))
    for i in range(6):
        store.create(make_pod(f"p{i}"))
    assert wait_until(lambda: all(bound_nodes(store).values()))
    counts = {}
    for node in bound_nodes(store).values():
        counts[node] = counts.get(node, 0) + 1
    # round-robin spread: every node got exactly 2 of the 6 pods
    assert counts == {"node-0": 2, "node-1": 2, "node-2": 2}
    # a Scheduled event was recorded, like the real scheduler emits
    events, _ = store.list("Event")
    assert any(e.get("reason") == "Scheduled" for e in events)


def test_pods_created_before_nodes_bind_on_retry(sched_store):
    store = sched_store
    store.create(make_pod("early"))
    time.sleep(0.5)  # scheduler sees it, has nowhere to put it
    assert bound_nodes(store)["early"] is None
    events, _ = store.list("Event")
    assert any(e.get("reason") == "FailedScheduling" for e in events)
    store.create(make_node("node-0"))
    assert wait_until(lambda: bound_nodes(store)["early"] == "node-0")


def test_capacity_fit_skips_full_nodes(sched_store):
    store = sched_store
    store.create(make_node("small", cpu="1"))
    store.create(make_node("big", cpu="8"))
    # each pod wants 2 cpus — only "big" fits, and only 4 times
    for i in range(5):
        store.create(make_pod(f"fat{i}", cpu="2"))
    assert wait_until(
        lambda: sum(1 for n in bound_nodes(store).values() if n == "big") == 4
    )
    nodes = bound_nodes(store)
    assert sum(1 for n in nodes.values() if n == "big") == 4
    assert sum(1 for n in nodes.values() if n is None) == 1
    assert "small" not in nodes.values()


def test_not_ready_and_unschedulable_nodes_skipped(sched_store):
    store = sched_store
    store.create(make_node("down", ready=False))
    cordoned = make_node("cordoned")
    cordoned["spec"] = {"unschedulable": True}
    store.create(cordoned)
    store.create(make_node("ok"))
    store.create(make_pod("p"))
    assert wait_until(lambda: bound_nodes(store)["p"] == "ok")


def test_respects_pod_count_cap(sched_store):
    store = sched_store
    store.create(make_node("tiny", pods="2"))
    for i in range(3):
        store.create(make_pod(f"p{i}"))
    # under co-load the scheduler may need more than a fixed second
    wait_until(lambda: sum(1 for n in bound_nodes(store).values() if n == "tiny") >= 2)
    time.sleep(0.5)  # and the third must stay unbound
    nodes = bound_nodes(store)
    assert sum(1 for n in nodes.values() if n == "tiny") == 2
    assert sum(1 for n in nodes.values() if n is None) == 1


def test_prebound_pods_untouched(sched_store):
    store = sched_store
    store.create(make_node("node-0"))
    pod = make_pod("placed")
    pod["spec"]["nodeName"] = "elsewhere"
    store.create(pod)
    time.sleep(0.5)
    assert bound_nodes(store)["placed"] == "elsewhere"


def test_scheduler_over_http_client():
    """The daemon topology: scheduler connects through ClusterClient
    (cmd/scheduler.py), pods bind across the wire."""
    store = ResourceStore()
    with APIServer(store) as srv:
        client = ClusterClient(srv.url)
        sched = Scheduler(client).start()
        try:
            store.create(make_node("node-0"))
            store.create(make_pod("remote"))
            assert wait_until(lambda: bound_nodes(store)["remote"] == "node-0")
        finally:
            sched.stop()


# ------------------------------------------------ selector/taint satellites


def test_node_selector_is_honored(sched_store):
    store = sched_store
    plain = make_node("plain")
    store.create(plain)
    ssd = make_node("ssd-node")
    ssd["metadata"]["labels"] = {"disk": "ssd"}
    store.create(ssd)
    pod = make_pod("picky")
    pod["spec"]["nodeSelector"] = {"disk": "ssd"}
    store.create(pod)
    assert wait_until(lambda: bound_nodes(store)["picky"] == "ssd-node")


def test_node_selector_with_no_matching_node_stays_pending(sched_store):
    store = sched_store
    store.create(make_node("plain"))
    pod = make_pod("stuck")
    pod["spec"]["nodeSelector"] = {"disk": "ssd"}
    store.create(pod)
    time.sleep(0.6)
    assert bound_nodes(store)["stuck"] is None
    events, _ = store.list("Event")
    assert any(e.get("reason") == "FailedScheduling" for e in events)


def test_noschedule_taint_requires_toleration(sched_store):
    store = sched_store
    tainted = make_node("tainted")
    tainted["spec"] = {
        "taints": [{"key": "tpu", "value": "only", "effect": "NoSchedule"}]
    }
    store.create(tainted)
    store.create(make_pod("ordinary"))
    assert wait_until(lambda: "ordinary" in bound_nodes(store))
    time.sleep(0.5)
    assert bound_nodes(store)["ordinary"] is None  # nowhere to go
    tolerant = make_pod("tolerant")
    tolerant["spec"]["tolerations"] = [{"key": "tpu", "operator": "Exists"}]
    store.create(tolerant)
    assert wait_until(lambda: bound_nodes(store)["tolerant"] == "tainted")


# -------------------------------------------- FailedScheduling event flood


def test_failed_scheduling_events_are_deduped_with_backoff():
    """_retry_pending re-binds every 2s; the warning must NOT re-emit
    every pass (per-pod exponential backoff, satellite of the gang
    PR — an event flood at 1M-pod scale)."""
    from kwok_tpu.controllers.scheduler import Scheduler
    from kwok_tpu.utils.clock import FakeClock

    store = ResourceStore()
    clock = FakeClock(100.0)
    events = []

    class Rec:
        def event(self, obj, etype, reason, msg):
            events.append(reason)

    sched = Scheduler(store, recorder=Rec(), clock=clock, gang_policy="none")
    pod = make_pod("pending")
    store.create(pod)
    stored = store.get("Pod", "pending")
    # drive the retry path directly (no threads): first pass warns
    sched._bind(stored)
    assert events.count("FailedScheduling") == 1
    # immediate retries inside the backoff window stay silent
    for _ in range(5):
        sched._bind(stored)
    assert events.count("FailedScheduling") == 1
    # past the first interval (2s) exactly one more fires
    clock.advance(2.1)
    sched._bind(stored)
    sched._bind(stored)
    assert events.count("FailedScheduling") == 2
    # the interval doubles: +2s is now inside the window, +4s is not
    clock.advance(2.1)
    sched._bind(stored)
    assert events.count("FailedScheduling") == 2
    clock.advance(2.0)
    sched._bind(stored)
    assert events.count("FailedScheduling") == 3
    # a successful bind clears the backoff state
    store.create(make_node("node-0"))
    sched._sorted_nodes = None
    sched._nodes._apply("ADDED", store.get("Node", "node-0"))
    sched._bind(store.get("Pod", "pending"))
    assert store.get("Pod", "pending")["spec"].get("nodeName") == "node-0"
    assert not sched._warn_pods
