"""The Node player and the lease lane are sized to the nodes, the Pod
player to the pods (``deviceCapacity``): the first two start at
``controller.NODE_ROWS`` or less and double as nodes join, so a cluster of
10,000 nodes and a 1,048,576-row Pod SoA ticks 16,384 Node rows, not 1M.  The Node player's macro-tick runs under a jit name of its
own, so a profile tells the two kinds' ticks apart; a Pod SoA far larger
than its pods plays them as a small one does; the drain's pass over the
fired-stage output is a stage of its own (``fired_scan``); and the
daemon's scrape says how many rows each player and the lane hold
(``kwok_device_rows``)."""

import os
import sys
import time

import pytest

from kwok_tpu.api.config import KwokConfiguration
from kwok_tpu.cluster.store import ResourceStore
from kwok_tpu.cmd.kwok import _controller_self_metrics
from kwok_tpu.controllers import controller
from kwok_tpu.controllers.controller import Controller
from kwok_tpu.controllers.device_player import DeviceStagePlayer
from kwok_tpu.controllers.pod_controller import PodEnv
from kwok_tpu.ctl.scale import scale
from kwok_tpu.engine import simulator
from kwok_tpu.engine.simulator import DeviceSimulator
from kwok_tpu.metrics.collectors import Registry
from kwok_tpu.ops import tick
from kwok_tpu.stages import default_node_stages, default_pod_stages, load_builtin
from kwok_tpu.utils import telemetry
from kwok_tpu.utils.promtext import iter_samples

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.references import fast_stages  # noqa: E402

NODE_IP = "10.0.0.1"
PODS = 300
WAIT_S = 60.0


def wait_until(cond, what):
    deadline = time.monotonic() + WAIT_S
    while not cond():
        assert time.monotonic() < deadline, f"timed out: {what}"
        time.sleep(0.02)


@pytest.fixture(autouse=True)
def fresh():
    """Shape keys are remembered for the process, as the jit cache is,
    and the series are the process's."""
    saved = {k: set(v) for k, v in simulator.ShapeLog._seen.items()}
    simulator.ShapeLog._seen.clear()
    telemetry.tick_stage_family().clear()
    telemetry.registry().counter("kwok_device_new_shapes_total").clear()
    yield
    simulator.ShapeLog._seen.clear()
    simulator.ShapeLog._seen.update(saved)


def device_rows(ctr):
    """``kwok_device_rows`` by kind, as the daemon's scrape has it."""
    reg = Registry()
    _controller_self_metrics(lambda: ctr)(reg)
    return {ls["kind"]: v for n, ls, v in iter_samples(reg.expose())
            if n == "kwok_device_rows"}


# ------------------------------------------ the Node player's rows


@pytest.mark.parametrize("pod_rows,node_rows,nodes,want", [
    (1024, controller.NODE_ROWS, 3, 1024),
    (4096, controller.NODE_ROWS, 3, 4096),
    (131072, controller.NODE_ROWS, 3, 4096),
    (1024, 64, 70, 128),
], ids=["below-the-start", "at-the-start", "above-the-start", "grown-to-the-nodes"])
def test_the_node_player_and_the_lease_lane_start_small_and_grow_to_the_nodes(
        monkeypatch, pod_rows, node_rows, nodes, want):
    monkeypatch.setattr(controller, "NODE_ROWS", node_rows)
    store = ResourceStore()
    ctr = Controller(
        store,
        KwokConfiguration(manage_all_nodes=True, backend="device", device_tick_ms=20,
                          node_lease_duration_seconds=4, device_capacity=pod_rows),
        local_stages={"Node": default_node_stages(lease=True), "Pod": default_pod_stages()},
        seed=0,
    )
    ctr.start()
    try:
        scale(store, "node", nodes)
        wait_until(lambda: ctr.node_leases._lane is not None
                   and len(ctr.node_leases._lane) == nodes, "leases on the lane")
        wait_until(lambda: ctr.device_players["Node"].sim.num_rows == nodes,
                   "nodes on the Node player")
        assert ctr.device_players["Pod"].sim.capacity == pod_rows
        assert ctr.device_players["Node"].sim.capacity == want
        assert ctr.node_leases._lane.capacity == want
        assert device_rows(ctr) == {"Pod": pod_rows, "Node": want, "Lease": want}
    finally:
        ctr.stop()


# ------------------------------------------------- the tick programs' names


@pytest.mark.parametrize("kind,program", [("Node", "run_node_ticks_collect"),
                                          ("Pod", "run_ticks_collect")])
def test_each_kind_ticks_under_the_name_of_its_own_program(kind, program):
    stages = default_node_stages(lease=True) if kind == "Node" else load_builtin("pod-fast")
    sim = DeviceSimulator(stages, capacity=64, kind=kind)
    name, fn = tick.collect_program(kind)
    assert name == program and fn is getattr(tick, program)
    # the trace names a program jit_<the function's name>
    assert fn.__wrapped__.__name__ == f"_{program}_impl"
    other = tick.run_ticks_collect if kind == "Node" else tick.run_node_ticks_collect
    fn.clear_cache()  # a shape another test compiled would be no new entry
    before = (fn._cache_size(), other._cache_size())
    sim.tick_many(20, 2)
    assert (fn._cache_size(), other._cache_size()) == (before[0] + 1, before[1])
    lowered = fn.lower(*sim.to_device(), 1, dt_ms=20, num_ticks=8).as_text()
    assert f"jit__{program}_impl" in lowered
    shapes = telemetry.registry().counter("kwok_device_new_shapes_total").snapshot()
    assert shapes[(kind, program, "first")] == 1
    # one body under two names: the same rows play the same either way
    fired = []
    for as_kind in ("Node", "Widget"):
        other_sim = DeviceSimulator(stages, capacity=64, kind=as_kind)
        for i in range(5):
            other_sim.admit(make_object(kind, i))
        fired.append(other_sim.tick_many(20, 3)[0])
    assert (fired[0] == fired[1]).all() and (fired[0] >= 0).any()


# ------------------------------------------ a large Pod SoA, a few hundred pods


def make_pod(i):
    return {
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": f"pod-{i}", "namespace": "default"},
        "spec": {"nodeName": f"node-{i % 7}",
                 "containers": [{"name": "app", "image": "fake-image"}]},
    }


def make_object(kind, i):
    if kind == "Pod":
        return make_pod(i)
    return {"apiVersion": "v1", "kind": "Node", "metadata": {"name": f"node-{i}"}}


def play(capacity):
    """``PODS`` pods created in one bulk and played to Running by a Pod
    player of ``capacity`` rows; their statuses by name."""
    store = ResourceStore()
    env = PodEnv(node_ip=NODE_IP)
    player = DeviceStagePlayer(store, "Pod", load_builtin("pod-fast"), capacity=capacity,
                               tick_ms=20, funcs_for=env.funcs, on_delete=env.release)
    player.start()
    try:
        pods = [make_pod(i) for i in range(PODS)]
        results = store.bulk([{"verb": "create", "data": p} for p in pods])
        assert [r["status"] for r in results] == ["ok"] * PODS

        def running():
            return all(((p.get("status") or {}).get("phase") == "Running")
                       for p in store.list("Pod")[0])

        wait_until(running, f"{PODS} pods Running at {capacity} rows")
        assert player.sim.capacity == capacity
        return pods, {p["metadata"]["name"]: p["status"] for p in store.list("Pod")[0]}
    finally:
        player.stop()


def test_a_large_pod_soa_plays_a_few_hundred_pods_as_a_small_one_does():
    pods, small = play(512)
    _pods, large = play(65536)
    assert len(small) == len(large) == PODS
    for statuses in (small, large):
        for pod in pods:
            got = statuses[pod["metadata"]["name"]]
            assert fast_stages.pod_mismatch(pod, got, NODE_IP) is None
        assert fast_stages.duplicate_ips(list(statuses.values())) == 0
    # pod-fast draws no jitter: the two sizes give the same statuses, addresses too
    assert ({n: fast_stages._normal(st) for n, st in small.items()}
            == {n: fast_stages._normal(st) for n, st in large.items()})
    # the drain found the fired rows in one pass a drain, timed on its own
    scans = telemetry.tick_stage_family().snapshot()[("Pod", "fired_scan")]
    drains = telemetry.tick_stage_family().snapshot()[("Pod", "host_drain")]
    assert 0 < scans["count"] == drains["count"] and scans["sum"] > 0.0
