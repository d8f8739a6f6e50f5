"""SLO telemetry end-to-end: a live in-process cluster (WAL-backed
store + APF apiserver + scheduler/gang engine + device player) must
serve OBSERVED latency histograms for every control-plane hot path at
/metrics, and /debug/flightrecorder must return tick stage breakdowns
plus trace-id-linked slow-request samples (ISSUE 12 acceptance)."""

import json
import re
import threading
import time
import urllib.request

import pytest

from kwok_tpu.cluster.apiserver import APIServer
from kwok_tpu.cluster.flowcontrol import FlowController
from kwok_tpu.cluster.store import ResourceStore
from kwok_tpu.cluster.wal import WriteAheadLog
from kwok_tpu.controllers.scheduler import Scheduler
from kwok_tpu.sched.topology import TopologyModel
from kwok_tpu.utils import telemetry

#: every family the tentpole promises at /metrics, asserted nonzero
FAMILIES = (
    "kwok_apiserver_request_duration_seconds",
    "kwok_apiserver_flow_queue_wait_seconds",
    "kwok_wal_append_seconds",
    "kwok_wal_fsync_seconds",
    "kwok_watch_delivery_lag_seconds",
    "kwok_scheduler_bind_seconds",
    "kwok_gang_admit_seconds",
    "kwok_tick_stage_seconds",
    "kwok_lease_renew_delay_seconds",
)
#: and on the apiserver daemon's own /metrics (its save loop is
#: cmd/apiserver.py's, so a real process serves it)
APISERVER_DAEMON_FAMILIES = ("kwok_apiserver_save_seconds",)


def _node(i, topo):
    return {
        "apiVersion": "v1",
        "kind": "Node",
        "metadata": {"name": f"node-{i}", "labels": topo.labels_for(i)},
        "status": {
            "allocatable": {"cpu": "16", "memory": "64Gi", "pods": "110"},
            "conditions": [{"type": "Ready", "status": "True"}],
        },
    }


def _pod(name, gang=None):
    meta = {"name": name, "namespace": "default"}
    if gang:
        meta["annotations"] = {"kwok.io/pod-group": gang}
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": meta,
        "spec": {"containers": [{"name": "c", "image": "fake"}]},
        "status": {},
    }


def _wait(cond, budget=20.0):
    deadline = time.time() + budget
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return cond()


def _family_counts(text):
    """{family: total observed count} from the _count exposition lines."""
    counts = {}
    for line in text.splitlines():
        m = re.match(r"(\w+)_count(?:\{[^}]*\})? (\d+)", line)
        if m:
            counts[m.group(1)] = counts.get(m.group(1), 0) + int(m.group(2))
    return counts


@pytest.fixture
def cluster(tmp_path):
    store = ResourceStore()
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"), fsync="always")
    store.attach_wal(wal)
    flow = FlowController()
    srv = APIServer(store, flow=flow).start()
    topo = TopologyModel(slice_hosts=4)
    sched = Scheduler(store, gang_policy="binpack", topology=topo).start()
    rec = telemetry.flight_recorder()
    old_threshold = rec.slow_threshold_s
    rec.slow_threshold_s = 0.0  # sample every request (fast test box)
    try:
        yield store, srv, sched, topo
    finally:
        rec.slow_threshold_s = old_threshold
        sched.stop()
        srv.stop()


def _bound(store, name):
    try:
        pod = store.get("Pod", name, namespace="default")
    except KeyError:
        return False
    return bool((pod.get("spec") or {}).get("nodeName"))


def test_metrics_serves_every_observed_family(cluster):
    store, srv, sched, topo = cluster
    url = srv.url
    for i in range(4):
        store.create(_node(i, topo))

    # --- scheduler time-to-bind: a singleton pod binds
    store.create(_pod("single"))
    assert _wait(lambda: _bound(store, "single")), "singleton never bound"

    # --- gang time-to-admit: a 2-member PodGroup commits atomically
    store.create(
        {
            "apiVersion": "scheduling.kwok.io/v1alpha1",
            "kind": "PodGroup",
            "metadata": {"name": "g1", "namespace": "default"},
            "spec": {"minMember": 2},
        }
    )
    store.create(_pod("g1-a", gang="g1"))
    store.create(_pod("g1-b", gang="g1"))
    assert _wait(
        lambda: _bound(store, "g1-a") and _bound(store, "g1-b")
    ), "gang never admitted"

    # --- watch delivery lag: consume one live event over HTTP
    got = threading.Event()

    def watch():
        r = urllib.request.urlopen(url + "/r/pods?watch=1", timeout=10)
        for _line in r:
            got.set()
            return

    t = threading.Thread(target=watch, daemon=True)
    t.start()
    time.sleep(0.3)
    store.create(_pod("watch-probe"))
    assert got.wait(5.0), "watch stream delivered nothing"

    # --- request duration + queue wait: any HTTP verb (with a
    # traceparent so the slow sample carries the exemplar)
    req = urllib.request.Request(
        url + "/r/pods?namespace=default",
        headers={"traceparent": "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"},
    )
    urllib.request.urlopen(req, timeout=10).read()

    # --- tick stages incl. host_build: a device player macro-tick
    from kwok_tpu.controllers.device_player import DeviceStagePlayer
    from kwok_tpu.controllers.pod_controller import PodEnv
    from kwok_tpu.cluster.informer import InformerEvent
    from kwok_tpu.stages import load_builtin

    env = PodEnv()
    player = DeviceStagePlayer(
        store,
        "Pod",
        load_builtin("pod-fast"),
        capacity=8,
        tick_ms=20,
        funcs_for=env.funcs,
        on_delete=env.release,
    )
    objs, _ = store.list("Pod")
    for obj in objs:
        player.events.add(InformerEvent("ADDED", obj))
    player._drain_events()
    fired = 0
    for _ in range(10):
        fired += player.step(100)
        if fired:
            break
    assert fired > 0, "device player never fired a transition"

    # --- lease renewals over the lane: fire time to the write's return
    from kwok_tpu.controllers.device_lease import DeviceLeaseLane
    from kwok_tpu.controllers.node_lease_controller import NodeLeaseController

    leases = NodeLeaseController(store, "slo-e2e", lease_duration_seconds=40)
    lane = DeviceLeaseLane(leases, capacity=16)
    leases.attach_device_lane(lane)
    leases.start()
    try:
        leases.try_hold("node-0")
        assert _wait(lambda: len(lane) == 1), "lease not handed to the lane"
        assert lane.tick(lane.renew_ms + 100) == 1
    finally:
        leases.stop()

    # --- the scrape: every family present with nonzero counts
    text = urllib.request.urlopen(url + "/metrics", timeout=10).read().decode()
    counts = _family_counts(text)
    missing = [f for f in FAMILIES if counts.get(f, 0) <= 0]
    assert not missing, f"families without observations: {missing}\n{counts}"
    # host_build specifically: open item 1's wall is a live series now
    assert re.search(
        r'kwok_tick_stage_seconds_count\{[^}]*stage="host_build"[^}]*\} [1-9]',
        text,
    ), "host_build stage series missing"
    # request duration carries the full bounded label set
    assert re.search(
        r'kwok_apiserver_request_duration_seconds_bucket\{verb="GET",'
        r'kind="pods",level="[\w-]+",shard="-",le=',
        text,
    )


def test_flightrecorder_and_stats_latency(cluster):
    store, srv, sched, topo = cluster
    url = srv.url
    # a request with a traceparent -> slow sample (threshold 0) with
    # the trace id as exemplar
    tid = "fe" * 16
    req = urllib.request.Request(
        url + "/r/pods",
        headers={"traceparent": f"00-{tid}-{'ba' * 8}-01"},
    )
    urllib.request.urlopen(req, timeout=10).read()

    fr = json.loads(
        urllib.request.urlopen(url + "/debug/flightrecorder", timeout=10).read()
    )
    assert fr["size"] >= 1
    samples = fr["slow_requests"]
    assert samples, "no slow-request samples despite a zero threshold"
    assert any(s["trace_id"] == tid for s in samples), samples
    assert all(
        set(s) >= {"verb", "path", "level", "seconds", "trace_id"}
        for s in samples
    )

    # tick entries ride the same ring (a player stepped in the sibling
    # test or here; drive one tick to be self-contained)
    from kwok_tpu.controllers.device_player import DeviceStagePlayer
    from kwok_tpu.controllers.pod_controller import PodEnv
    from kwok_tpu.cluster.informer import InformerEvent
    from kwok_tpu.stages import load_builtin

    store.create(_node(0, topo))
    store.create(
        {
            "apiVersion": "v1",
            "kind": "Pod",
            "metadata": {"name": "fr-pod", "namespace": "default"},
            "spec": {
                "nodeName": "node-0",
                "containers": [{"name": "c", "image": "x"}],
            },
            "status": {},
        }
    )
    env = PodEnv()
    player = DeviceStagePlayer(
        store, "Pod", load_builtin("pod-fast"), capacity=4, tick_ms=20,
        funcs_for=env.funcs, on_delete=env.release,
    )
    objs, _ = store.list("Pod")
    for obj in objs:
        player.events.add(InformerEvent("ADDED", obj))
    player._drain_events()
    for _ in range(10):
        if player.step(100):
            break
    fr = json.loads(
        urllib.request.urlopen(url + "/debug/flightrecorder", timeout=10).read()
    )
    assert fr["ticks"], "no tick breakdowns recorded"
    tick = fr["ticks"][-1]
    assert tick["kind"] == "Pod" and tick["fired"] >= 1
    assert set(tick["stages"]) == {
        "device_tick_s",
        "host_drain_s",
        "host_build_s",
        "store_bulk_s",
    }

    # /stats latency summary (kwokctl get components renders it)
    stats = json.loads(urllib.request.urlopen(url + "/stats", timeout=10).read())
    lat = stats.get("latency") or {}
    req_row = lat.get("kwok_apiserver_request_duration_seconds")
    assert req_row and req_row["count"] >= 1
    assert "p99_s" in req_row and "p50_s" in req_row


def test_apiserver_daemon_serves_its_own_families(tmp_path):
    """The families only ``python -m kwok_tpu.cmd.apiserver`` observes:
    its periodic save, one observation a save."""
    import os
    import subprocess
    import sys

    from kwok_tpu.cluster.client import ClusterClient
    from kwok_tpu.ctl.components import free_port

    port = free_port()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-m", "kwok_tpu.cmd.apiserver", "--port", str(port),
         "--state-file", str(tmp_path / "state.json"), "--save-interval", "0.2"],
        stdout=open(tmp_path / "apiserver.log", "ab"), stderr=subprocess.STDOUT,
        env={**os.environ, "PYTHONPATH": root}, start_new_session=True,
    )
    try:
        client = ClusterClient(f"http://127.0.0.1:{port}")
        assert client.wait_ready(30)
        client.create(_pod("saved"))

        def counts():
            text = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
            return _family_counts(text), text

        assert _wait(lambda: all(counts()[0].get(f, 0) > 0
                                 for f in APISERVER_DAEMON_FAMILIES)), counts()[0]
        text = counts()[1]
        assert "# TYPE kwok_apiserver_save_seconds histogram" in text
        # a process with no device tick thread exposes no empty stage family
        assert "kwok_tick_stage_seconds" not in text
    finally:
        proc.terminate()
        proc.wait(timeout=20)
