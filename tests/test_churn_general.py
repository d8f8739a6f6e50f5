"""Upstream's realistic pod lifecycle, ``pod-general`` + ``pod-chaos``,
through a ``DeviceStagePlayer`` with an ``EventRecorder`` (ISSUE 32: the
``churn-100k`` cell of the benchmark at a size for the CPU): finalizer
stages, Events, init containers, readiness gates, a crash loop, rows used
again.  The store is in this process or behind a real apiserver over HTTP.
Annotations shorten every stage's delay, so a test takes seconds."""

import contextlib
import datetime
import os
import random
import sys
import time

import pytest

from kwok_tpu.cluster.apiserver import APIServer
from kwok_tpu.cluster.client import ClusterClient
from kwok_tpu.cluster.informer import InformerEvent
from kwok_tpu.cluster.store import EventRecorder, NotFound, ResourceStore
from kwok_tpu.controllers.device_player import DeviceStagePlayer
from kwok_tpu.controllers.pod_controller import PodEnv
from kwok_tpu.engine.lifecycle import Lifecycle
from kwok_tpu.stages import load_builtin
from kwok_tpu.utils import telemetry
from kwok_tpu.utils.patch import apply_patch

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.references import general_stages  # noqa: E402

NODES = 20
NODE_IP = "10.0.0.1"
STORES = ("resource", "wire")
WAIT_S = 60.0
CHAOS = "pod-container-running-failed"
FINALIZER = "kwok.x-k8s.io/fake"


def stages():
    return load_builtin("pod-general") + load_builtin("pod-chaos")


#: every stage after 20 ms plus up to 40 ms, where the files say 1 s + 5 s
FAST = {f"{s.name}.stage.kwok.x-k8s.io/{k}": v for s in stages()
        for k, v in (("delay", "20ms"), ("jitter-delay", "60ms"))}
ANNOTATED_FAILURE = {f"{CHAOS}.stage.kwok.x-k8s.io/reason": "OOMKilled",
                     f"{CHAOS}.stage.kwok.x-k8s.io/message": "out of memory",
                     f"{CHAOS}.stage.kwok.x-k8s.io/exit-code": "137"}


def make_pod(name, i, containers=1, init=False, gate=False, chaos=False, annotations=None):
    meta = {"name": name, "namespace": "default",
            "annotations": {**FAST, **(annotations or {})}}
    if chaos:
        meta["labels"] = {f"{CHAOS}.stage.kwok.x-k8s.io": "true"}
    spec = {"nodeName": f"node-{i % NODES}",
            "containers": [{"name": f"c{k}", "image": f"image-{k}"} for k in range(containers)]}
    if init:
        spec["initContainers"] = [{"name": "init", "image": "init-image"}]
    if gate:
        spec["readinessGates"] = [{"conditionType": "example.com/gate"}]
    return {"apiVersion": "v1", "kind": "Pod", "metadata": meta, "spec": spec}


def seeded_pods(seed, count, prefix="pod"):
    """Pods of seeded shapes: 1-3 containers, an init container on some, a
    readiness gate on some, the chaos label on some, and of those some with
    an annotated reason, message and exit code."""
    rng = random.Random(seed)
    pods = []
    for i in range(count):
        chaos = rng.random() < 0.3
        pods.append(make_pod(
            f"{prefix}-{i}", i, containers=rng.randint(1, 3), init=rng.random() < 0.3,
            gate=rng.random() < 0.3, chaos=chaos,
            annotations=ANNOTATED_FAILURE if chaos and rng.random() < 0.5 else None))
    return pods


def normal(x):
    """Times by key to one word, nulls and empty lists dropped (a status
    batch stores the null a merge patch drops: ROADMAP D11)."""
    if isinstance(x, dict):
        return {k: "<time>" if k.endswith(("Time", "At")) else normal(v)
                for k, v in x.items() if v is not None and v != []}
    if isinstance(x, list):
        return [normal(v) for v in x]
    return x


def host_engine_statuses(lifecycle, pod, pod_ip, flips=0):
    """The statuses the host ``Lifecycle`` engine gives ``pod`` as sent,
    stage by stage, with the address the device gave; a crash-looper's
    chain goes through ``flips`` failures."""
    funcs = {"Now": lambda: "2026-01-01T00:00:00Z", "PodIPWith": lambda *a: pod_ip,
             "NodeIPWith": lambda *a: NODE_IP}
    obj = {**pod, "status": {}}
    out, failures = [], 0
    while True:
        meta = obj["metadata"]
        matched = lifecycle.match(meta.get("labels") or {}, meta.get("annotations") or {}, obj)
        if not matched:
            return out
        stage = max(matched, key=lambda s: s.name == CHAOS)  # weight 10000 against 1
        if stage.name == CHAOS:
            failures += 1
            if failures > flips:
                return out
        for patch in lifecycle.effects(stage).patches(obj, funcs):
            obj = apply_patch(obj, patch.data, patch.type)
        out.append(normal(obj["status"]))


def histogram(name, kind="Pod"):
    fam = telemetry.registry().histogram(name)
    return {lv[1:]: (d["sum"], d["count"]) for lv, d in fam.snapshot().items() if lv[0] == kind}


@pytest.fixture(autouse=True)
def fresh():
    """The series are the process's."""
    reg = telemetry.registry()
    for name in ("kwok_status_commit_rows", "kwok_stage_fired_rows", "kwok_events_recorded"):
        reg.histogram(name).clear()
    telemetry.tick_stage_family().clear()
    yield


class CountingRecorder(EventRecorder):
    """The recorder, with the Event requests it sent to the store counted
    and the Events of ``refuse`` (names of involved pods) made invalid."""

    def __init__(self, store, refuse=()):
        super().__init__(_Counted(store, self), "kwok")
        self.bulks, self.singles, self.refuse = [], 0, set(refuse)


class _Counted:
    def __init__(self, store, owner):
        self._store, self._owner = store, owner

    def bulk(self, ops):
        self._owner.bulks.append(len(ops))
        for op in ops:
            if op["verb"] == "create" and \
                    op["data"]["involvedObject"]["name"] in self._owner.refuse:
                op["verb"] = "refused"  # no store knows the verb: Invalid
        return self._store.bulk(ops)

    def __getattr__(self, name):
        if name in ("create", "patch"):
            self._owner.singles += 1
        return getattr(self._store, name)


class Bench:
    """A store (``handle`` is how a writer reaches it), a started player
    with a recorder, and a watch on the backing store that keeps every pod
    and Event event."""

    def __init__(self, flavor, stack, capacity=128, refuse=()):
        self.store = ResourceStore()
        self.handle = self.store
        if flavor == "wire":
            self.handle = ClusterClient(stack.enter_context(APIServer(self.store)).url)
        self._watches = [self.store.watch("Pod"), self.store.watch("Event")]
        for w in self._watches:
            stack.callback(w.stop)
        #: pod name -> [(event type, status)] in arrival order
        self.events = {}
        env = PodEnv(node_ip=NODE_IP)
        self.recorder = CountingRecorder(self.handle, refuse)
        self.player = DeviceStagePlayer(
            self.handle, "Pod", stages(), capacity=capacity, tick_ms=20,
            recorder=self.recorder, funcs_for=env.funcs, on_delete=env.release)
        self.player.start()
        stack.callback(self.player.stop)

    def pump(self):
        while True:
            ev = self._watches[0].next(timeout=0)
            if ev is None:
                return
            self.events.setdefault(ev.object["metadata"]["name"], []).append(
                (ev.type, ev.object.get("status")))

    def wait(self, pred, what):
        deadline = time.monotonic() + WAIT_S
        while not pred():
            assert time.monotonic() < deadline, f"timed out: {what}"
            time.sleep(0.01)

    def get(self, name):
        try:
            return self.store.get("Pod", name, namespace="default")
        except NotFound:
            return None

    def phase(self, name):
        return ((self.get(name) or {}).get("status") or {}).get("phase")

    def create(self, pods):
        results = self.handle.bulk([{"verb": "create", "data": p} for p in pods])
        assert [r["status"] for r in results] == ["ok"] * len(pods)

    def delete(self, names):
        results = self.handle.bulk([{"verb": "delete", "kind": "Pod", "name": n,
                                     "namespace": "default"} for n in names])
        assert [r["status"] for r in results] == ["ok"] * len(names)

    def statuses(self, name):
        """The distinct statuses ``name`` went through, normalised."""
        self.pump()
        out = []
        for _t, status in self.events.get(name, []):
            if status and (not out or normal(status) != out[-1]):
                out.append(normal(status))
        return out

    def failures(self, name):
        return sum(1 for s in self.statuses(name) if s.get("phase") == "Failed")

    def stored_events(self):
        return self.store.list("Event")[0]


def event_shape(ev):
    """An Event without what differs from run to run."""
    return {k: v for k, v in ev.items() if k not in ("metadata", "firstTimestamp",
                                                      "lastTimestamp")} | {
        "namespace": ev["metadata"]["namespace"],
        "name": ev["metadata"]["name"].rsplit(".", 1)[0],
        "involvedObject": {k: v for k, v in ev["involvedObject"].items() if k != "uid"}}


# ---------------------------------------------------------------- (a), (e)


@pytest.mark.parametrize("flavor", STORES)
def test_every_status_is_the_references_and_the_host_engines(flavor):
    pods = seeded_pods(32, 40)
    lifecycle = Lifecycle(stages())
    with contextlib.ExitStack() as stack:
        b = Bench(flavor, stack)
        b.create(pods)
        loopers = [p["metadata"]["name"] for p in pods if "labels" in p["metadata"]]
        others = [p["metadata"]["name"] for p in pods if "labels" not in p["metadata"]]
        assert loopers and others
        b.wait(lambda: all(b.phase(n) == "Running" for n in others)
               and all(b.failures(n) >= 2 for n in loopers), "Running, or failed twice")
        b.player.stop()
        played = {n: b.statuses(n) for n in loopers + others}
        transitions = b.player.transitions
    reasons = set()
    for pod in pods:
        name = pod["metadata"]["name"]
        seen = played[name]
        stored = b.get(name)
        assert FINALIZER in stored["metadata"]["finalizers"]
        for _t, status in b.events[name]:
            if status:
                assert general_stages.pod_mismatch(pod, status, NODE_IP) is None, (name, status)
        want = host_engine_statuses(lifecycle, pod, stored["status"]["podIP"],
                                    flips=b.failures(name))
        assert seen == want[:len(seen)], name
        assert len(seen) >= (2 if name in others else 4)
        for s in seen:
            if s["phase"] == "Failed":
                t = s["containerStatuses"][0]["state"]["terminated"]
                reasons.add((t["reason"], t["message"], t["exitCode"]))
    assert reasons == {("containerFailed", "container failed", 1),
                       ("OOMKilled", "out of memory", 137)}
    # (e) the mix a run played can be read: by stage and by path
    fired = histogram("kwok_stage_fired_rows")
    assert sum(s for s, _n in fired.values()) == transitions
    commits = histogram("kwok_status_commit_rows")
    for path in ("batch", "slow"):
        assert sum(s for (_st, p), (s, _n) in fired.items() if p == path) == commits[(path,)][0]
    assert {st for (st, p) in fired if p == "slow"} == {"pod-create"}
    assert fired[("pod-create", "slow")][0] == len(pods)
    assert fired[("pod-ready", "batch")][0] >= len(pods)
    assert fired[(CHAOS, "batch")][0] >= 2 * len(loopers)


# --------------------------------------------------------------------- (b)


@pytest.mark.parametrize("flavor", STORES)
def test_a_crash_looper_flips_and_settles_once_its_label_is_gone(flavor):
    pod = make_pod("looper", 0, containers=2, chaos=True)
    with contextlib.ExitStack() as stack:
        b = Bench(flavor, stack)
        b.create([pod])
        b.wait(lambda: b.failures("looper") >= 3, "three failures")
        b.handle.patch("Pod", "looper", {"metadata": {"labels": None}}, "merge",
                       namespace="default")

        def settled():
            # a flip that was due when the label went may still come
            played = b.player.transitions
            time.sleep(0.5)  # some twenty ticks
            return b.player.transitions == played and b.phase("looper") == "Running"

        b.wait(settled, "Running for good without the label")
        flips = b.failures("looper")
    phases = [s["phase"] for s in b.statuses("looper")]
    assert phases[:2] == ["Pending", "Running"] and phases[-1] == "Running"
    assert phases.count("Failed") == flips >= 3


# --------------------------------------------------------------------- (c)


class HandDriven:
    """A player that is not started: the test forwards the store's events
    and steps it through ``step_pipelined``, one tick a dispatch, so that
    a drain always meets an ingest between its dispatch and itself."""

    def __init__(self, capacity):
        self.store = ResourceStore()
        env = PodEnv(node_ip=NODE_IP)
        self.player = DeviceStagePlayer(
            self.store, "Pod", stages(), capacity=capacity, tick_ms=20,
            recorder=EventRecorder(self.store, "kwok"), funcs_for=env.funcs,
            on_delete=env.release)
        self.player.sim.epoch = datetime.datetime.now(datetime.timezone.utc)
        self._watch = self.store.watch("Pod")
        #: pod name -> [(event type, resourceVersion)]
        self.events = {}

    def turn(self):
        while True:
            ev = self._watch.next(timeout=0)
            if ev is None:
                break
            meta = ev.object["metadata"]
            self.events.setdefault(meta["name"], []).append((ev.type, meta["resourceVersion"]))
            self.player.events.add(InformerEvent(ev.type, ev.object))
        self.player._drain_events()
        self.player.step_pipelined(20, 1)

    def turns(self, pred, what, limit=600):
        for _ in range(limit):
            self.turn()
            if pred():
                return
        raise AssertionError(f"not after {limit} ticks: {what}")

    def phase(self, name):
        try:
            pod = self.store.get("Pod", name, namespace="default")
        except NotFound:
            return "gone"
        return (pod.get("status") or {}).get("phase")


def test_a_pod_admitted_into_a_released_row_is_not_deleted_in_its_place():
    """Ten rounds of create, Running, delete, gone, with new pods admitted
    as the old ones' rows are released: ``pod-delete`` fires one tick after
    ``pod-remove-finalizer``, so its row arrives in the dispatch that was in
    flight when the store reaped the pod, and is drained after the ingest
    that gave the row to a new pod (the fault of ISSUE 32: pods nobody
    deleted were gone)."""
    h = HandDriven(capacity=32)
    keepers = []
    for rnd in range(10):
        names = [f"round-{rnd}-{i}" for i in range(8)]
        for i, n in enumerate(names):
            h.store.create(make_pod(n, i))
        h.turns(lambda: all(h.phase(n) == "Running" for n in names), f"round {rnd} Running")
        for n in names:
            h.store.delete("Pod", n, namespace="default")
        new = [f"keeper-{rnd}-{i}" for i in range(8)]
        for i, n in enumerate(new):
            # one a tick, so that some are admitted in the very ingest
            # that releases a row
            h.store.create(make_pod(n, i))
            h.turn()
        keepers += new
        h.turns(lambda: all(h.phase(n) == "gone" for n in names), f"round {rnd} gone")
        h.turns(lambda: all(h.phase(n) == "Running" for n in new), f"keepers {rnd} Running")
        # each pod nobody deleted is still in the store
        assert [n for n in keepers if h.phase(n) != "Running"] == []
        assert h.player.sim.capacity == 32
        if len(keepers) > 8:  # keep the rows inside the capacity
            old, keepers = keepers[:8], keepers[8:]
            for n in old:
                h.store.delete("Pod", n, namespace="default")
            h.turns(lambda: all(h.phase(n) == "gone" for n in old), f"old keepers {rnd} gone")
    h.player.flush_pipeline()
    h.turn()
    for name, evs in h.events.items():
        kinds = [t for t, _rv in evs]
        if name in keepers:
            assert "DELETED" not in kinds, name
        else:
            # exactly one DELETED, and no write after it
            assert kinds.count("DELETED") == 1 and kinds[-1] == "DELETED", (name, kinds)
    assert sorted(n for _ns, n in h.player._rows) == sorted(keepers)


def test_a_stage_that_fired_for_an_object_since_changed_is_not_played_on_it():
    """A crash-looper loses its label while the failure that was due is in
    flight: played on the changed pod it would leave the store Failed and
    the device, which extracted the row again from a Running pod, with
    nothing to match (found by this PR's own test under six workers)."""
    h = HandDriven(capacity=32)
    names = [f"looper-{i}" for i in range(12)]
    for i, n in enumerate(names):
        h.store.create(make_pod(n, i, chaos=True))
    waiting = {n: i % 4 for i, n in enumerate(names)}  # turns after Running
    for _ in range(400):
        h.turn()
        for n in [n for n in waiting if h.phase(n) == "Running"]:
            waiting[n] -= 1
            if waiting[n] < 0:
                del waiting[n]
                h.store.patch("Pod", n, {"metadata": {"labels": None}}, "merge",
                              namespace="default")
        if not waiting:
            break
    assert not waiting
    h.turns(lambda: all(h.phase(n) == "Running" for n in names), "all Running for good", 200)
    played = h.player.transitions
    for _ in range(50):
        h.turn()
    assert h.player.transitions == played
    assert [h.phase(n) for n in names] == ["Running"] * len(names)


# --------------------------------------------------------------------- (d)


def run_one_life(flavor, refuse=()):
    """Twenty pods created, Running, deleted and gone; the Events left."""
    pods = seeded_pods(33, 20, prefix="life")
    for p in pods:
        p["metadata"].pop("labels", None)
    names = [p["metadata"]["name"] for p in pods]
    with contextlib.ExitStack() as stack:
        b = Bench(flavor, stack, refuse=refuse)
        b.create(pods)
        b.wait(lambda: all(b.phase(n) == "Running" for n in names), "Running")
        b.delete(names)
        b.wait(lambda: not any(b.get(n) for n in names), "gone")
        b.player.stop()
        return b, names, b.stored_events()


@pytest.mark.parametrize("flavor", STORES)
def test_a_pod_leaves_one_created_and_one_killing_event_sent_a_drain_at_a_time(flavor):
    b, names, events = run_one_life(flavor)
    by_pod = {n: sorted(e["reason"] for e in events if e["involvedObject"]["name"] == n)
              for n in names}
    assert by_pod == {n: ["Created", "Killing"] for n in names}
    assert all(e["count"] == 1 and e["source"] == {"component": "kwok"} for e in events)
    recorded = histogram("kwok_events_recorded")
    # the same objects, name suffix and times aside, as event() makes one by one
    other = ResourceStore()
    one_by_one = EventRecorder(other, "kwok")
    for n in names:
        involved = {"apiVersion": "v1", "kind": "Pod",
                    "metadata": {"name": n, "namespace": "default", "uid": n}}
        one_by_one.event(involved, "Normal", "Created", "Created container")
        one_by_one.event(involved, "Normal", "Killing", "Stopping container")
    key = lambda e: (e["name"], e["reason"])  # noqa: E731
    assert sorted(map(event_shape, events), key=key) == \
        sorted(map(event_shape, other.list("Event")[0]), key=key)
    # never one request an Event: a bulk a drain, and nothing else
    assert b.recorder.singles == 0
    assert sum(b.recorder.bulks) == 2 * len(names)
    slow_drains = histogram("kwok_status_commit_rows")[("slow",)][1]
    assert len(b.recorder.bulks) <= slow_drains
    assert recorded == {("created",): (2.0 * len(names), len(b.recorder.bulks))}
    posts = telemetry.tick_stage_family().snapshot()[("Pod", "event_post")]["count"]
    assert posts == len(b.recorder.bulks)


def test_an_event_the_store_refuses_is_dropped_and_its_row_commits():
    refuse = ("life-3", "life-7")
    b, names, events = run_one_life("resource", refuse=refuse)
    # the pods lived their whole life all the same
    assert not any(b.get(n) for n in names)
    assert {e["involvedObject"]["name"] for e in events} == set(names) - set(refuse)
    recorded = histogram("kwok_events_recorded")
    assert recorded[("dropped",)][0] == 2 * len(refuse)
    assert recorded[("created",)][0] == 2 * (len(names) - len(refuse))
    assert b.player.swallowed_errors == 0


def test_a_repeat_is_aggregated_from_the_cache_and_a_lost_event_made_anew():
    store = ResourceStore()
    rec = CountingRecorder(store)
    pod = store.create(make_pod("a", 0))
    item = (pod, "Warning", "BackOff", "Back-off restarting failed container")
    assert rec.record([item, item]) == 0 and rec.record([item]) == 0
    (ev,) = store.list("Event")[0]
    assert ev["count"] == 3 and rec.bulks == [2, 1] and rec.singles == 0
    # one by one the recorder reads the Event back, as it did
    assert rec.event(*item)["count"] == 4
    store.delete("Event", ev["metadata"]["name"], namespace="default")
    assert rec.record([item]) == 1  # the bump finds nothing: dropped, and forgotten
    assert rec.record([item]) == 0
    (ev,) = store.list("Event")[0]
    assert ev["count"] == 1
    recorded = histogram("kwok_events_recorded")
    assert recorded[("created",)][0] == 2 and recorded[("dropped",)][0] == 1
    assert recorded[("aggregated",)][0] == 3


# ------------------------------------------------------------ the plan cache


def test_a_full_plan_cache_gives_up_the_plan_longest_unused(monkeypatch):
    """1,000 nodes under this stage set use more (stage, signature) pairs
    than the cache once held, and it was emptied whole when full: a plan in
    use stays (ISSUE 32; PERF.md §6)."""
    from kwok_tpu.controllers import device_player

    compiled = []
    monkeypatch.setattr(device_player, "_PLAN_CACHE", 4)
    monkeypatch.setattr(device_player, "compile_plan",
                        lambda lc, stage, obj, funcs: compiled.append(stage.name) or object())
    env = PodEnv(node_ip=NODE_IP)
    player = DeviceStagePlayer(ResourceStore(), "Pod", stages(), capacity=16, tick_ms=20,
                               funcs_for=env.funcs, on_delete=env.release)
    pod = make_pod("a", 0)
    plans = [player._plan_for(0, sig, pod) for sig in range(4)]
    assert player._plan_for(0, 0, pod) is plans[0]  # in use again: the newest now
    player._plan_for(0, 4, pod)  # full: signature 1 is the longest unused
    assert list(player._plans) == [(0, 2), (0, 3), (0, 0), (0, 4)]
    assert player._plan_for(0, 0, pod) is plans[0] and len(compiled) == 5
    player._plan_for(0, 1, pod)
    assert len(compiled) == 6 and (0, 2) not in player._plans
