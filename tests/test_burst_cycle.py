"""Bursts of pods created, played to Running, deleted and gone, cycle
after cycle, through a ``DeviceStagePlayer`` on ``pod-fast`` (ISSUE 28: the
``burst-1k`` cell of the benchmark at a size for the CPU).  Every burst pod
carries a finalizer, so its delete is the ``pod-delete`` stage's: since
ISSUE 29 one item of a delete batch (``apply_delete_batch``), before it a
finalizer patch and a delete through ``_drain_slow``.  The store is in this
process or behind a real apiserver over HTTP."""

import contextlib
import os
import random
import sys
import time

import pytest

from kwok_tpu.cluster.apiserver import APIServer
from kwok_tpu.cluster.client import ClusterClient
from kwok_tpu.cluster.store import NotFound, ResourceStore
from kwok_tpu.controllers.device_player import DeviceStagePlayer
from kwok_tpu.controllers.pod_controller import PodEnv
from kwok_tpu.engine import simulator
from kwok_tpu.engine.lifecycle import Lifecycle
from kwok_tpu.stages import load_builtin
from kwok_tpu.utils import telemetry

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.references import fast_stages  # noqa: E402

NODES, STANDING, BURST, CYCLES = 20, 50, 20, 10
FINALIZER = "kwok.x-k8s.io/fake"
NODE_IP = "10.0.0.1"
STORES = ("resource", "wire")
WAIT_S = 60.0


def shapes_for(seed):
    """One pod shape a place in a burst (1-3 containers, an init container
    on some), so that every cycle meets the signatures of the first."""
    rng = random.Random(seed)
    return [(rng.randint(1, 3), rng.random() < 0.3) for _ in range(max(BURST, STANDING))]


def make_pod(name, i, shape, finalizer=""):
    n_containers, init = shape
    meta = {"name": name, "namespace": "default"}
    if finalizer:
        meta["finalizers"] = [finalizer]
    spec = {
        "nodeName": f"node-{i % NODES}",
        "containers": [{"name": f"c{k}", "image": f"image-{k}"} for k in range(n_containers)],
    }
    if init:
        spec["initContainers"] = [{"name": "init", "image": "init-image"}]
    return {"apiVersion": "v1", "kind": "Pod", "metadata": meta, "spec": spec}


def sans_times(x):
    if isinstance(x, dict):
        return {k: "<time>" if k.endswith(("Time", "At")) else sans_times(v)
                for k, v in x.items()}
    if isinstance(x, list):
        return [sans_times(v) for v in x]
    return x


def host_engine_status(lifecycle, pod, pod_ip):
    """What the host ``Lifecycle`` engine makes of ``pod`` as created: the
    one stage that matches it, rendered with the address the device gave."""
    meta = pod["metadata"]
    (stage,) = lifecycle.match(meta.get("labels") or {}, meta.get("annotations") or {}, pod)
    assert stage.name == "pod-ready"
    funcs = {"Now": lambda: "2026-01-01T00:00:00Z", "PodIPWith": lambda *a: pod_ip,
             "NodeIPWith": lambda *a: NODE_IP}
    (patch,) = lifecycle.effects(stage).patches(pod, funcs)
    return patch.data["status"]


def family(name, kind="Pod"):
    fam = telemetry.registry().histogram(name)
    return {lv[1:]: (d["sum"], d["count"]) for lv, d in fam.snapshot().items() if lv[0] == kind}


def new_shapes(causes=("capacity", "signatures")):
    fam = telemetry.registry().counter("kwok_device_new_shapes_total")
    return sum(n for lv, n in fam.snapshot().items() if lv[0] == "Pod" and lv[2] in causes)


@pytest.fixture(autouse=True)
def fresh():
    """The series and the remembered shape keys are the process's."""
    saved = {k: set(v) for k, v in simulator.ShapeLog._seen.items()}
    simulator.ShapeLog._seen.clear()
    reg = telemetry.registry()
    reg.counter("kwok_device_new_shapes_total").clear()
    for name in ("kwok_status_commit_rows", "kwok_delete_to_gone_seconds"):
        reg.histogram(name).clear()
    yield
    simulator.ShapeLog._seen.clear()
    simulator.ShapeLog._seen.update(saved)


class Bench:
    """A store (``handle`` is how a writer reaches it), a started player
    and a watch on the backing store that keeps every pod event."""

    def __init__(self, flavor, stack):
        self.store = ResourceStore()
        self.handle = self.store
        if flavor == "wire":
            self.handle = ClusterClient(stack.enter_context(APIServer(self.store)).url)
        self._watch = self.store.watch("Pod")
        stack.callback(self._watch.stop)
        #: pod name -> [(event type, status)] in arrival order
        self.events = {}
        env = PodEnv(node_ip=NODE_IP)
        self.player = DeviceStagePlayer(
            self.handle, "Pod", load_builtin("pod-fast"), capacity=128, tick_ms=20,
            funcs_for=env.funcs, on_delete=env.release)
        self.player.start()
        stack.callback(self.player.stop)

    def pump(self):
        while True:
            ev = self._watch.next(timeout=0)
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

    def create(self, pods):
        results = self.handle.bulk([{"verb": "create", "data": p} for p in pods])
        assert [r["status"] for r in results] == ["ok"] * len(pods)

    def delete(self, names):
        results = self.handle.bulk([{"verb": "delete", "kind": "Pod", "name": n,
                                     "namespace": "default"} for n in names])
        assert [r["status"] for r in results] == ["ok"] * len(names)

    def running(self, names):
        return all(((self.get(n) or {}).get("status") or {}).get("phase") == "Running"
                   for n in names)

    def rows_in_use(self):
        sim = self.player.sim
        return sim.num_rows - len(sim._free)


@pytest.mark.parametrize("flavor", STORES)
def test_bursts_come_and_go_and_their_rows_are_used_again(flavor):
    shapes = shapes_for(28)
    lifecycle = Lifecycle(load_builtin("pod-fast"))
    with contextlib.ExitStack() as stack:
        b = Bench(flavor, stack)
        sim = b.player.sim
        standing = [make_pod(f"standing-{i}", i, shapes[i]) for i in range(STANDING)]
        b.create(standing)
        b.wait(lambda: b.running(p["metadata"]["name"] for p in standing), "standing Running")
        capacity = sim.capacity
        after_first = None
        for cycle in range(CYCLES):
            pods = [make_pod(f"burst-{cycle}-{i}", i, shapes[i], FINALIZER)
                    for i in range(BURST)]
            names = [p["metadata"]["name"] for p in pods]
            b.create(pods)
            b.wait(lambda: b.running(names), f"cycle {cycle} Running")
            for pod in pods:
                status = b.get(pod["metadata"]["name"])["status"]
                want = host_engine_status(lifecycle, pod, status["podIP"])
                assert sans_times(status) == sans_times(want)
                assert fast_stages.pod_mismatch(pod, status, NODE_IP) is None
            b.delete(names)
            b.wait(lambda: not any(b.get(n) for n in names)
                   and b.rows_in_use() == STANDING, f"cycle {cycle} gone")
            assert sim.num_rows <= STANDING + 2 * BURST and sim.capacity == capacity
            if after_first is None:
                after_first = new_shapes()
        time.sleep(0.2)  # a write after a DELETED would have come by now
        b.pump()
        assert sorted(b.player._rows) == sorted(("default", p["metadata"]["name"])
                                                for p in standing)
        transitions = b.player.transitions
    created = CYCLES * BURST
    for cycle in range(CYCLES):
        for i in range(BURST):
            kinds = [t for t, _s in b.events[f"burst-{cycle}-{i}"]]
            # created, turned Running, marked for deletion, gone: and nothing after
            assert kinds.count("DELETED") == 1 and kinds[-1] == "DELETED", kinds
            assert kinds[0] == "ADDED" and kinds.count("MODIFIED") >= 2
    # rows are reused, no shape followed the deletes and creates
    assert new_shapes() == after_first
    commits = family("kwok_status_commit_rows")
    assert commits[("batch",)][0] == STANDING + created
    # every delete went by the delete batch and none through _drain_slow
    assert commits[("delete",)][0] == created
    assert commits.get(("slow",), (0.0, 0))[0] == 0
    assert transitions == STANDING + 2 * created
    gone = family("kwok_delete_to_gone_seconds")[()]
    # the store stamps whole seconds, rounded down
    assert gone[1] == created and 0.0 <= gone[0] / created < 1.0 + WAIT_S


@pytest.mark.parametrize("flavor", STORES)
def test_a_burst_deleted_before_it_is_running_is_gone_and_leaks_no_row(flavor):
    shapes = shapes_for(29)
    with contextlib.ExitStack() as stack:
        b = Bench(flavor, stack)
        standing = [make_pod(f"standing-{i}", i, shapes[i]) for i in range(STANDING)]
        b.create(standing)
        b.wait(lambda: b.running(p["metadata"]["name"] for p in standing), "standing Running")
        pods = [make_pod(f"hasty-{i}", i, shapes[i], FINALIZER) for i in range(BURST)]
        names = [p["metadata"]["name"] for p in pods]
        b.create(pods)
        b.delete(names)
        b.wait(lambda: not any(b.get(n) for n in names)
               and b.rows_in_use() == STANDING, "the hasty burst gone")
        time.sleep(0.2)
        b.pump()
        assert len(b.player._rows) == STANDING
    for n in names:
        kinds = [t for t, _s in b.events[n]]
        assert kinds.count("DELETED") == 1 and kinds[-1] == "DELETED", kinds
    assert family("kwok_delete_to_gone_seconds")[()][1] == BURST
