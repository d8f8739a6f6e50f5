"""The scheduler binds a run of queued events at once
(controllers/scheduler.py): each pending pod is picked against a cache
that assumes the picks before it, the run's binds go out as one ``bulk``
of merge patches and their Scheduled Events as one more; a run of one
sends its ``PATCH`` and its Event alone.  And the store's account of it:
``kwok_pod_binds_per_request``, one observation a request that bound a
pod, whose ``_sum`` is ``kwok_pod_binds_total``."""

import json
import random
import urllib.request

import pytest

from kwok_tpu.cluster import store as store_mod
from kwok_tpu.cluster.apiserver import APIServer
from kwok_tpu.cluster.client import ClusterClient
from kwok_tpu.cluster.informer import InformerEvent
from kwok_tpu.cluster.store import ADDED, DELETED, MODIFIED, ResourceStore
from kwok_tpu.controllers.scheduler import RUN_MAX, Scheduler


def node(name, pods="110", cpu="4", memory="8Gi"):
    return {"apiVersion": "v1", "kind": "Node", "metadata": {"name": name},
            "status": {"allocatable": {"cpu": cpu, "memory": memory, "pods": pods},
                       "conditions": [{"type": "Ready", "status": "True"}]}}


def pod(name, cpu="100m", memory="128Mi", node_name=""):
    spec = {"containers": [{"name": "c", "image": "i",
                            "resources": {"requests": {"cpu": cpu, "memory": memory}}}]}
    if node_name:
        spec["nodeName"] = node_name
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": "default"}, "spec": spec}


class Spy:
    """A store or client whose writes are counted by method."""

    def __init__(self, inner, before_bulk=None):
        self.inner = inner
        self.calls = []
        self.before_bulk = before_bulk

    def patch(self, *a, **kw):
        self.calls.append("patch")
        return self.inner.patch(*a, **kw)

    def create(self, *a, **kw):
        self.calls.append("create")
        return self.inner.create(*a, **kw)

    def bulk(self, ops, **kw):
        self.calls.append("bulk")
        if self.before_bulk is not None:
            self.before_bulk(ops)
        return self.inner.bulk(ops, **kw)

    def __getattr__(self, name):
        return getattr(self.inner, name)


@pytest.fixture(params=["store", "http"])
def wire(request):
    """(the server's store, what the scheduler talks to)."""
    store = ResourceStore()
    if request.param == "store":
        yield store, store
        return
    with APIServer(store) as srv:
        yield store, ClusterClient(srv.url)


def scheduler(store, target, nodes, **kw):
    spy = Spy(target, **{k: kw.pop(k) for k in ("before_bulk",) if k in kw})
    sched = Scheduler(spy, gang_policy="none", **kw)
    for n in nodes:
        sched._nodes._apply(ADDED, store.create(n))
    return sched, spy


def run_of(sched, events):
    """Queue ``events`` and hand the loop's run to the scheduler, as
    ``_loop`` does once it wakes."""
    sched._events.extend(events)
    first, ok = sched._events.get()
    assert ok
    run = sched._take_run(first)
    sched._handle_run(run)
    return run


def round_trips(store, since):
    return [e[1] for e in store.audit_log()[since:] if e[0] in ("bulk", "txn")]


def placed(store):
    return {p["metadata"]["name"]: p["spec"].get("nodeName") for p in store.list("Pod")[0]}


def scheduled_events(store):
    return sorted(e["involvedObject"]["name"] for e in store.list("Event")[0]
                  if e.get("reason") == "Scheduled")


# ------------------------------------------------------------------ the batch


def test_a_queued_backlog_binds_in_one_bulk_and_one_bulk_of_events(wire):
    store, target = wire
    sched, spy = scheduler(store, target, [node(f"node-{i}") for i in range(3)])
    events = [InformerEvent(ADDED, store.create(pod(f"p{i}"))) for i in range(12)]
    since = len(store.audit_log())
    assert len(run_of(sched, events)) == 12
    assert round_trips(store, since) == ["Pod:12", "Event:12"]
    assert spy.calls == ["bulk", "bulk"]
    assert all(placed(store).values())
    assert scheduled_events(store) == sorted(f"p{i}" for i in range(12))
    ev = next(e for e in store.list("Event")[0] if e["involvedObject"]["name"] == "p0")
    assert ev["message"] == f"Successfully assigned default/p0 to {placed(store)['p0']}"
    # the binds' echoes change nothing: each pod is assumed where it went
    usage = dict(sched._used_agg)
    sched._handle_run([InformerEvent(MODIFIED, p) for p in store.list("Pod")[0]])
    assert sched._used_agg == usage and spy.calls == ["bulk", "bulk"]
    assert sorted(n for n, _c, _m in sched._pod_usage.values()) == sorted(placed(store).values())


def test_a_run_takes_what_is_queued_up_to_the_cap():
    sched = Scheduler(ResourceStore(), gang_policy="none")
    sched._events.extend(range(RUN_MAX + 7))
    first, _ok = sched._events.get()
    assert sched._take_run(first) == list(range(RUN_MAX))
    first, _ok = sched._events.get()
    assert sched._take_run(first) == list(range(RUN_MAX, RUN_MAX + 7))


def test_a_run_of_one_sends_a_patch_and_one_event(wire):
    store, target = wire
    sched, spy = scheduler(store, target, [node("node-0")])
    ev = InformerEvent(ADDED, store.create(pod("lone")))
    since = len(store.audit_log())
    sched.handle_event(ev)  # binds before it returns
    assert placed(store) == {"lone": "node-0"}
    assert spy.calls == ["patch", "create"] and round_trips(store, since) == []
    assert scheduled_events(store) == ["lone"]


@pytest.mark.parametrize("seed", [4301, 4302, 4303])
def test_a_batched_run_places_as_the_one_at_a_time_loop(seed):
    """Seeded nodes of every size and readiness, pods of every size,
    some created bound, some deleted and some finished in the stream:
    the same events in the same order place every pod on the same node,
    whether handled one at a time or in runs of any length."""
    rng = random.Random(seed)
    nodes = [node(f"node-{i}", pods=str(rng.randint(1, 5)), cpu=str(rng.choice((1, 2, 4))),
                  memory=f"{rng.choice((1, 2, 4))}Gi") for i in range(8)]
    for n in nodes:
        if rng.random() < 0.15:
            n["status"]["conditions"][0]["status"] = "False"
    plan = []
    for i in range(70):
        spec = pod(f"p{i}", cpu=f"{rng.choice((100, 250, 500, 900))}m",
                   memory=f"{rng.choice((128, 256, 512, 1024))}Mi")
        if rng.random() < 0.1:
            spec["spec"]["nodeName"] = rng.choice(nodes)["metadata"]["name"]
        plan.append(("create", spec))
        if i > 5 and rng.random() < 0.15:
            plan.append((rng.choice(("delete", "finish")), f"p{rng.randrange(i)}"))
    cuts = sorted(rng.sample(range(1, len(plan)), 6))

    def play(batched):
        store = ResourceStore()
        sched, _spy = scheduler(store, store, nodes)
        events = []
        for what, arg in plan:
            if what == "create":
                events.append(InformerEvent(ADDED, store.create(arg)))
                continue
            cur = store.get("Pod", arg, namespace="default")
            if what == "finish":
                cur = dict(cur, status={"phase": "Succeeded"})
                events.append(InformerEvent(MODIFIED, cur))
            else:
                events.append(InformerEvent(DELETED, cur))
        if batched:
            for lo, hi in zip([0] + cuts, cuts + [len(events)]):
                sched._handle_run(events[lo:hi])
        else:
            for ev in events:
                sched.handle_event(ev)
        return placed(store), dict(sched._used_agg)

    one, batched = play(False), play(True)
    assert one == batched
    assert 0 < sum(1 for n in one[0].values() if n) < len(one[0])


def test_a_run_never_puts_a_node_over_its_capacity(wire):
    store, target = wire
    sched, spy = scheduler(store, target, [node("tiny", pods="2")])
    run_of(sched, [InformerEvent(ADDED, store.create(pod(f"p{i}"))) for i in range(5)])
    got = placed(store)
    assert sorted(got.values(), key=str) == [None, None, None, "tiny", "tiny"]
    assert sched._used_agg["tiny"][2] == 2 and len(sched._pod_usage) == 2
    assert spy.calls.count("bulk") == 2  # the two binds, then their Events
    failed = [e for e in store.list("Event")[0] if e.get("reason") == "FailedScheduling"]
    assert len(failed) == 3


def test_a_pod_deleted_between_pick_and_write_fails_alone(wire):
    store, target = wire

    def delete_one(ops):
        if any(op.get("kind") == "Pod" for op in ops):
            store.delete("Pod", "p2", namespace="default")

    sched, _spy = scheduler(store, target, [node("node-0"), node("node-1")],
                            before_bulk=delete_one)
    events = [InformerEvent(ADDED, store.create(pod(f"p{i}"))) for i in range(5)]
    run_of(sched, events)
    got = placed(store)
    assert "p2" not in got and all(got.values()) and len(got) == 4
    # the assumption is released: the cache holds the four binds alone
    assert sorted(sched._pod_usage) == sorted(
        ev.object["metadata"]["uid"] for ev in events if ev.object["metadata"]["name"] != "p2")
    assert sum(n for _c, _m, n in sched._used_agg.values()) == 4
    assert scheduled_events(store) == ["p0", "p1", "p3", "p4"]
    # the pod's DELETED event, when it comes, finds nothing to release
    sched.handle_event(InformerEvent(DELETED, events[2].object))
    assert sum(n for _c, _m, n in sched._used_agg.values()) == 4


def test_two_events_of_one_pod_in_a_run_make_one_op(wire):
    store, target = wire
    sched, _spy = scheduler(store, target, [node("node-0")])
    p = store.create(pod("twice"))
    relabelled = store.patch("Pod", "twice", {"metadata": {"labels": {"a": "b"}}},
                             patch_type="merge", namespace="default")
    q = store.create(pod("once"))
    since = len(store.audit_log())
    run_of(sched, [InformerEvent(ADDED, p), InformerEvent(MODIFIED, relabelled),
                   InformerEvent(ADDED, q)])
    assert round_trips(store, since) == ["Pod:2", "Event:2"]
    assert placed(store) == {"twice": "node-0", "once": "node-0"}
    assert scheduled_events(store) == ["once", "twice"]


def test_a_standby_picks_and_writes_nothing(wire):
    store, target = wire
    asked = []

    def standby():
        asked.append(1)
        return False

    sched, spy = scheduler(store, target, [node("node-0")], active=standby)
    bound = store.create(pod("placed", node_name="node-0"))
    events = [InformerEvent(ADDED, store.create(pod(f"p{i}"))) for i in range(4)]
    run_of(sched, events + [InformerEvent(ADDED, bound)])
    assert spy.calls == [] and not any(placed(store)[f"p{i}"] for i in range(4))
    # caches stay current, no pending pod is assumed, and the gate is
    # asked once a run
    assert list(sched._pod_usage) == [bound["metadata"]["uid"]]
    assert asked == [1]


def test_the_retry_pass_binds_its_list_in_one_bulk():
    store = ResourceStore()
    for i in range(6):
        store.create(pod(f"p{i}"))
    sched, spy = scheduler(store, store, [node("node-0"), node("node-1")])
    since = len(store.audit_log())
    sched._retry_pending()
    assert round_trips(store, since) == ["Pod:6", "Event:6"]
    assert all(placed(store).values())
    sched._retry_pending()  # nothing left unbound, nothing written
    assert round_trips(store, since) == ["Pod:6", "Event:6"]


# --------------------------------------------------------------- the counter


def binds():
    total = store_mod._C_POD_BINDS.snapshot().get((), 0)
    hist = store_mod._H_BINDS_PER_REQUEST.snapshot().get((), {"count": 0, "sum": 0.0})
    return total, hist["count"], hist["sum"]


def nn_ops(names, verb="patch"):
    return [{"verb": verb, "kind": "Pod", "name": n, "namespace": "default",
             "data": {"spec": {"nodeName": "node-0"}}, "patch_type": "merge"} for n in names]


def k8s(url, method, path, body, ctype="application/json"):
    req = urllib.request.Request(f"{url}{path}", method=method, data=json.dumps(body).encode(),
                                 headers={"Content-Type": ctype})
    assert urllib.request.urlopen(req, timeout=10).status in (200, 201)


def bind_by(verb, store, url, names):
    """Bind ``names`` (one, or three for the batch verbs) in one request."""
    client = ClusterClient(url)
    if verb in ("bulk", "http-bulk"):
        (store if verb == "bulk" else client).bulk(nn_ops(names))
    elif verb in ("transact", "http-transact"):
        (store if verb == "transact" else client).transact(nn_ops(names))
    elif verb in ("patch", "http-patch"):
        (store if verb == "patch" else client).patch(
            "Pod", names[0], {"spec": {"nodeName": "node-0"}}, patch_type="merge",
            namespace="default")
    elif verb in ("update", "http-update"):
        cur = store.get("Pod", names[0], namespace="default")
        cur["spec"]["nodeName"] = "node-0"
        (store if verb == "update" else client).update(cur)
    elif verb == "k8s-patch":
        k8s(url, "PATCH", f"/api/v1/namespaces/default/pods/{names[0]}",
            {"spec": {"nodeName": "node-0"}}, "application/merge-patch+json")
    elif verb == "k8s-update":
        cur = store.get("Pod", names[0], namespace="default")
        cur["spec"]["nodeName"] = "node-0"
        k8s(url, "PUT", f"/api/v1/namespaces/default/pods/{names[0]}", cur)
    elif verb == "binding":
        k8s(url, "POST", f"/api/v1/namespaces/default/pods/{names[0]}/binding",
            {"target": {"name": "node-0"}})
    else:
        raise AssertionError(verb)


@pytest.mark.parametrize("verb", ["patch", "update", "bulk", "transact", "http-patch",
                                  "http-update", "http-bulk", "http-transact", "k8s-patch",
                                  "k8s-update", "binding"])
def test_binds_per_request_is_observed_once_a_request(verb):
    store = ResourceStore()
    with APIServer(store) as srv:
        store.create(node("node-0"))
        names = ["a", "b", "c"] if verb.endswith(("bulk", "transact")) else ["a"]
        for n in names:
            store.create(pod(n))
        store.create(pod("placed", node_name="node-0"))
        t0, c0, s0 = binds()
        bind_by(verb, store, srv.url, names)
        # a later write to a bound pod, or a batch that binds nothing, is
        # no observation
        store.patch("Pod", "a", {"metadata": {"labels": {"x": "y"}}}, patch_type="merge",
                    namespace="default")
        store.bulk(nn_ops(["placed"]))
        store.transact(nn_ops(["placed"]))
        t1, c1, s1 = binds()
        assert (t1 - t0, c1 - c0, s1 - s0) == (len(names), 1, len(names))
        assert all(placed(store)[n] == "node-0" for n in names)
        text = urllib.request.urlopen(f"{srv.url}/metrics", timeout=10).read().decode()
        assert "kwok_pod_binds_per_request_count" in text


def test_the_counter_sums_to_the_binds_a_scheduler_made(wire):
    store, target = wire
    t0, c0, s0 = binds()
    sched, _spy = scheduler(store, target, [node("node-0"), node("node-1")])
    run_of(sched, [InformerEvent(ADDED, store.create(pod(f"p{i}"))) for i in range(7)])
    sched.handle_event(InformerEvent(ADDED, store.create(pod("lone"))))
    t1, c1, s1 = binds()
    assert (t1 - t0, c1 - c0, s1 - s0) == (8, 2, 8)
