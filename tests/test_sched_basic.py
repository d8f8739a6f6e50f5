"""The scheduler (controllers/scheduler.py) held to the benchmark's plain
reference of the scheduling semantics (``benchmarks/references/
sched_basic.py``), and the store's account of what the scheduler does
(``kwok_pod_binds_total``, ``kwok_pod_create_to_bind_seconds``).

Seeded random nodes (taints of every effect, labels, readiness, cordons,
allocatable) and pods (requests, tolerations, nodeSelectors) go to a live
scheduler in process and over HTTP: every bind is feasible by the
reference, no node holds more than it allocates, and a pod is left unbound
only where the reference finds no node for it."""

import json
import os
import random
import sys
import time
import urllib.request

import pytest

from kwok_tpu.cluster import store as store_mod
from kwok_tpu.cluster.apiserver import APIServer
from kwok_tpu.cluster.client import ClusterClient
from kwok_tpu.cluster.informer import InformerEvent
from kwok_tpu.cluster.store import ResourceStore
from kwok_tpu.controllers.scheduler import Scheduler

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.references import sched_basic  # noqa: E402

TAINT_KEYS = ("dedicated", "gpu")


def random_node(rng: random.Random, i: int) -> dict:
    taints = [{"key": rng.choice(TAINT_KEYS), "value": rng.choice(("a", "b")),
               "effect": rng.choice(("NoSchedule", "PreferNoSchedule", "NoExecute"))}
              for _ in range(rng.choice((0, 0, 1, 2)))]
    node = {
        "apiVersion": "v1",
        "kind": "Node",
        "metadata": {"name": f"node-{i}", "labels": {"zone": rng.choice(("x", "y"))}},
        "spec": {"taints": taints, "unschedulable": rng.random() < 0.1},
        "status": {
            "allocatable": {"cpu": str(rng.choice((1, 2, 4))),
                            "memory": f"{rng.choice((1, 2, 4))}Gi",
                            "pods": str(rng.randint(2, 6))},
            "conditions": [{"type": "Ready",
                            "status": "True" if rng.random() < 0.85 else "False"}],
        },
    }
    return node


def random_pod(rng: random.Random, i: int) -> dict:
    requests = {}
    if rng.random() < 0.8:
        requests["cpu"] = f"{rng.choice((100, 250, 500, 900))}m"
    if rng.random() < 0.8:
        requests["memory"] = f"{rng.choice((128, 256, 512, 1024))}Mi"
    tolerations = []
    if rng.random() < 0.4:
        tolerations.append({"key": rng.choice(TAINT_KEYS), "operator": "Exists",
                            "effect": "NoSchedule"})
    if rng.random() < 0.2:
        tolerations.append({"key": rng.choice(TAINT_KEYS), "operator": "Equal",
                            "value": rng.choice(("a", "b"))})
    if rng.random() < 0.05:
        tolerations.append({"operator": "Exists"})
    spec = {"containers": [{"name": "c", "image": "i",
                            "resources": {"requests": requests}}],
            "tolerations": tolerations}
    if rng.random() < 0.3:
        spec["nodeSelector"] = {"zone": rng.choice(("x", "y"))}
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": f"pod-{i}", "namespace": "default"}, "spec": spec}


def settled(store, deadline: float):
    """Pods and nodes once the reference finds nothing, or at ``deadline``."""
    while True:
        pods, nodes = store.list("Pod")[0], store.list("Node")[0]
        if not sched_basic.violations(pods, nodes) or time.monotonic() >= deadline:
            return pods, nodes
        time.sleep(0.1)


@pytest.mark.parametrize("seed", [4201, 4202, 4203])
@pytest.mark.parametrize("wire", ["store", "http"])
def test_every_bind_is_feasible_by_the_reference(seed, wire):
    rng = random.Random(seed)
    store = ResourceStore()
    srv = APIServer(store).start() if wire == "http" else None
    try:
        sched = Scheduler(ClusterClient(srv.url) if srv else store, gang_policy="none").start()
        try:
            for i in range(12):
                store.create(random_node(rng, i))
            time.sleep(0.2)  # the scheduler's node cache has them
            for i in range(60):
                store.create(random_pod(rng, i))
            pods, nodes = settled(store, time.monotonic() + 30)
        finally:
            sched.stop()
    finally:
        if srv is not None:
            srv.stop()
    assert sched_basic.violations(pods, nodes) == []
    bound = [p for p in pods if p["spec"].get("nodeName")]
    # the draw leaves both kinds: pods bound, and pods no node can take
    assert 0 < len(bound) < len(pods)


def test_the_reference_finds_each_kind_of_violation():
    rng = random.Random(42)
    nodes = [random_node(rng, i) for i in range(3)]
    for n in nodes:
        n["spec"] = {}
        n["status"]["conditions"] = [{"type": "Ready", "status": "True"}]
        n["status"]["allocatable"] = {"cpu": "1", "memory": "1Gi", "pods": "2"}
    nodes[1]["spec"]["taints"] = [{"key": "gpu", "value": "a", "effect": "NoSchedule"}]
    nodes[2]["status"]["conditions"][0]["status"] = "False"

    def pod(name, node="", cpu="100m"):
        p = random_pod(rng, 0)
        p["metadata"]["name"] = name
        p["spec"] = {"containers": [{"name": "c", "image": "i",
                                     "resources": {"requests": {"cpu": cpu}}}]}
        if node:
            p["spec"]["nodeName"] = node
        return p

    assert sched_basic.violations([pod("a", "node-0")], nodes) == []
    found = sched_basic.violations(
        [pod("gone", "node-9"), pod("tainted", "node-1"), pod("down", "node-2"),
         pod("fat", "node-0", cpu="2"), pod("waiting")], nodes)
    # "fat" is feasible but puts node-0 over its CPU; then node-0 is full,
    # node-1 tainted and node-2 not Ready: no node can take the unbound pod,
    # so it is no violation
    assert [f.split(":")[0] for f in found] == ["gone", "tainted", "down", "node-0"]
    assert sched_basic.bind_violations([pod("waiting")], nodes[1:]) == 0
    assert sched_basic.bind_violations([pod("waiting")], nodes) == 1
    assert sched_basic.quantity("500Mi") == 500 * 2 ** 20
    assert sched_basic.quantity("100m") == pytest.approx(0.1)


# ------------------------------------------- the store's account of binds


def bind_counts():
    binds = store_mod._C_POD_BINDS.snapshot().get((), 0)
    hist = store_mod._H_CREATE_TO_BIND.snapshot().get((), {"count": 0, "sum": 0.0})
    return binds, hist["count"]


def unbound_pod(name: str) -> dict:
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": "default"},
            "spec": {"containers": [{"name": "c", "image": "i"}]}}


def bind_by(verb: str, store, name: str, url: str) -> None:
    nn = {"spec": {"nodeName": "node-0"}}
    if verb == "patch":
        store.patch("Pod", name, nn, patch_type="merge", namespace="default")
    elif verb == "update":
        pod = store.get("Pod", name, namespace="default")
        pod["spec"]["nodeName"] = "node-0"
        store.update(pod)
    elif verb == "bulk":
        store.bulk([{"verb": "patch", "kind": "Pod", "name": name, "namespace": "default",
                     "data": nn, "patch_type": "merge"}])
    elif verb == "transact":
        store.transact([{"verb": "patch", "kind": "Pod", "name": name,
                         "namespace": "default", "data": nn, "patch_type": "merge"}])
    elif verb == "http-patch":
        ClusterClient(url).patch("Pod", name, nn, patch_type="merge", namespace="default")
    elif verb == "binding":
        req = urllib.request.Request(
            f"{url}/api/v1/namespaces/default/pods/{name}/binding", method="POST",
            data=json.dumps({"target": {"name": "node-0"}}).encode(),
            headers={"Content-Type": "application/json"})
        assert urllib.request.urlopen(req, timeout=10).status == 201
    else:
        raise AssertionError(verb)


@pytest.mark.parametrize("verb", ["patch", "update", "bulk", "transact", "http-patch",
                                  "binding"])
def test_a_bind_through_each_verb_is_counted_once(verb):
    store = ResourceStore()
    with APIServer(store) as srv:
        store.create({"apiVersion": "v1", "kind": "Node", "metadata": {"name": "node-0"}})
        binds0, timed0 = bind_counts()
        store.create(unbound_pod("p"))
        # a pod created bound is no bind; nor is a later write to a bound pod
        bound = unbound_pod("placed")
        bound["spec"]["nodeName"] = "node-0"
        store.create(bound)
        time.sleep(0.01)
        bind_by(verb, store, "p", srv.url)
        store.patch("Pod", "p", {"metadata": {"labels": {"a": "b"}}}, patch_type="merge",
                    namespace="default")
        binds1, timed1 = bind_counts()
        assert (binds1 - binds0, timed1 - timed0) == (1, 1)
        assert store.get("Pod", "p", namespace="default")["spec"]["nodeName"] == "node-0"
        assert store._state("Pod").unbound_since == {}
        text = urllib.request.urlopen(f"{srv.url}/metrics", timeout=10).read().decode()
        assert "kwok_pod_binds_total" in text and "kwok_pod_create_to_bind_seconds_count" in text


def test_a_pending_pod_deleted_leaves_no_state_behind():
    store = ResourceStore()
    binds0, timed0 = bind_counts()
    for i in range(5):
        store.create(unbound_pod(f"p{i}"))
    assert len(store._state("Pod").unbound_since) == 5
    store.delete("Pod", "p0", namespace="default")
    store.bulk([{"verb": "delete", "kind": "Pod", "name": "p1", "namespace": "default"}])
    store.patch("Pod", "p2", {"metadata": {"finalizers": ["f"]}}, patch_type="merge",
                namespace="default")
    store.delete("Pod", "p2", namespace="default")  # terminating: still pending
    assert set(k[1] for k in store._state("Pod").unbound_since) == {"p2", "p3", "p4"}
    rv = store.get("Pod", "p2", namespace="default")["metadata"]["resourceVersion"]
    store.apply_delete_batch("Pod", [["default", "p2", rv]])
    store.delete("Pod", "p3", namespace="default")
    store.transact([{"verb": "delete", "kind": "Pod", "name": "p4", "namespace": "default"}])
    assert store._state("Pod").unbound_since == {}
    assert bind_counts() == (binds0, timed0)


def test_a_stale_event_of_a_pod_bound_since_does_not_move_it():
    """The retry pass binds what a LIST finds unbound while that pod's
    ADDED event still waits in the queue: handled later, the event's copy
    is unbound, and the pod must stay where it was bound."""
    store = ResourceStore()
    for i in range(3):
        store.create({"apiVersion": "v1", "kind": "Node", "metadata": {"name": f"node-{i}"},
                      "status": {"conditions": [{"type": "Ready", "status": "True"}]}})
    sched = Scheduler(store, gang_policy="none")
    for n in store.list("Node")[0]:
        sched._nodes._apply(store_mod.ADDED, n)
    stale = InformerEvent(store_mod.ADDED, store.create(unbound_pod("p")))
    sched._retry_pending()
    first = store.get("Pod", "p", namespace="default")["spec"]["nodeName"]
    sched.handle_event(stale)
    assert store.get("Pod", "p", namespace="default")["spec"]["nodeName"] == first
    binds = [e for e in store.list("Event")[0] if e.get("reason") == "Scheduled"]
    assert len(binds) == 1
