"""The status-batch verb across the wire (ISSUE 27): a device player's
fired rows reach a remote apiserver as one columnar commit
(``POST /status-batch`` -> ``ResourceStore.apply_status_batch``), with
what a bulk of status merge patches guaranteed: the same objects and the
same watch events, another writer's fields kept, one durable WAL record
before the answer, a missing object released, a degraded store refused
with 503 and the rows fired again, APF admission and one audit line."""

import contextlib
import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from kwok_tpu.chaos.fs_pressure import FsPressure
from kwok_tpu.cluster.apiserver import APIServer
from kwok_tpu.cluster.client import ApiUnavailable, ClusterClient, RetryPolicy
from kwok_tpu.cluster.flowcontrol import FlowController
from kwok_tpu.cluster.informer import InformerEvent
from kwok_tpu.cluster.sharding import build_sharded_store, shard_of
from kwok_tpu.cluster.store import ResourceStore
from kwok_tpu.cluster.wal import StorageDegraded, WriteAheadLog
from kwok_tpu.controllers.device_player import DeviceStagePlayer
from kwok_tpu.stages import load_builtin
from kwok_tpu.utils.backoff import Backoff
from kwok_tpu.utils.patch import apply_merge_patch

STORES = ("resource", "sharded", "wire")
SHARDS = 4


def other_namespace():
    """A namespace on another shard than ``default``."""
    home = shard_of(True, "Pod", "default", SHARDS)
    return next(f"ns-{i}" for i in range(64)
                if shard_of(True, "Pod", f"ns-{i}", SHARDS) != home)


def make_pod(name, ns="default"):
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": name, "namespace": ns, "uid": f"uid-{ns}-{name}",
                     "labels": {"app": name}},
        "spec": {"nodeName": "node-0", "containers": [{"name": "app", "image": "x"}]},
        "status": {},
    }


@contextlib.contextmanager
def open_store(flavor, store=None, **server_kw):
    """``(backing store, the handle a writer uses)``: the store itself,
    or a ``ClusterClient`` on an ``APIServer`` over it."""
    if store is None:
        store = build_sharded_store(SHARDS) if flavor == "sharded" else ResourceStore()
    if flavor != "wire":
        yield store, store
        return
    with APIServer(store, **server_kw) as srv:
        yield store, ClusterClient(
            srv.url, client_id="kwok-controller",
            retry=RetryPolicy(max_attempts=1, backoff=Backoff(duration=0.0, cap=0.0)))


def drain(watcher, want, timeout=10.0):
    """``want`` events of a watch, as (type, namespace, name, status)."""
    out = []
    deadline = time.monotonic() + timeout
    while len(out) < want and time.monotonic() < deadline:
        ev = watcher.next(timeout=0.2)
        if ev is not None:
            meta = ev.object["metadata"]
            out.append((ev.type, meta["namespace"], meta["name"], ev.object.get("status")))
    return out


def sans_rv(objs):
    """By key, without what differs between two plays of the same writes:
    the resourceVersion and the wall clock of the create."""
    out = {}
    for o in objs:
        o = json.loads(json.dumps(o))
        o["metadata"].pop("resourceVersion")
        o["metadata"].pop("creationTimestamp", None)
        out[(o["metadata"]["namespace"], o["metadata"]["name"])] = o
    return out


ROUNDS = (
    lambda name: {"phase": "Running", "podIP": f"10.0.0.{len(name)}",
                  "conditions": [{"type": "Ready", "status": "True"}]},
    lambda name: {"phase": "Succeeded", "podIP": None},
)


def play(flavor, how):
    """Two rounds of status writes over six pods in two namespaces,
    through a bulk of merge patches or through the verb; what is stored
    at the end and what a watcher saw."""
    ns_b = other_namespace()
    with open_store(flavor) as (_store, handle):
        for ns in ("default", ns_b):
            for i in range(3):
                handle.create(make_pod(f"pod-{i}", ns))
        watcher = handle.watch("Pod")
        try:
            for patch_of in ROUNDS:
                objs, _ = handle.list("Pod")
                objs.sort(key=lambda o: (o["metadata"]["name"], o["metadata"]["namespace"]))
                if how == "bulk":
                    results = handle.bulk([
                        {"verb": "patch", "kind": "Pod", "name": o["metadata"]["name"],
                         "namespace": o["metadata"]["namespace"], "patch_type": "merge",
                         "subresource": "status",
                         "data": {"status": patch_of(o["metadata"]["name"])}}
                        for o in objs])
                    assert all(r["status"] == "ok" for r in results)
                else:
                    results = handle.apply_status_batch("Pod", [
                        (o["metadata"]["namespace"], o["metadata"]["name"],
                         apply_merge_patch(o.get("status") or {},
                                           patch_of(o["metadata"]["name"])),
                         o["metadata"]["resourceVersion"])
                        for o in objs])
                    assert all(r and r[0] > 0 for r in results)
            events = drain(watcher, 12)
        finally:
            watcher.stop()
        return sans_rv(handle.list("Pod")[0]), events


@pytest.mark.parametrize("flavor", STORES)
def test_the_verb_leaves_what_a_bulk_of_merge_patches_leaves(flavor):
    stored_bulk, events_bulk = play(flavor, "bulk")
    stored_verb, events_verb = play(flavor, "verb")
    assert stored_verb == stored_bulk
    assert all(o["status"]["phase"] == "Succeeded" and "podIP" not in o["status"]
               and o["status"]["conditions"] for o in stored_verb.values())
    assert len(events_verb) == 12 and {e[0] for e in events_verb} == {"MODIFIED"}
    # a batch commits shard by shard, so across shards only each
    # namespace's own order is the bulk's; within one store all of it is
    for ns in {e[1] for e in events_bulk}:
        assert [e for e in events_verb if e[1] == ns] == [e for e in events_bulk if e[1] == ns]
    if flavor != "sharded":
        assert events_verb == events_bulk


def make_player(handle, capacity=8):
    from kwok_tpu.controllers.pod_controller import PodEnv

    env = PodEnv()
    return DeviceStagePlayer(
        handle, "Pod", load_builtin("pod-fast"), capacity=capacity, tick_ms=20,
        funcs_for=env.funcs, on_delete=env.release,
    )


def admit_all(player, handle):
    for obj in handle.list("Pod")[0]:
        player.events.add(InformerEvent("ADDED", obj))
    player._drain_events()


def step_until(player, done, steps=40):
    for _ in range(steps):
        player.step(100)
        if done():
            return True
    return False


def phases(handle):
    return {o["metadata"]["name"]: (o.get("status") or {}).get("phase")
            for o in handle.list("Pod")[0]}


def audit_counts(store):
    log = store.audit_log()
    return (sum(1 for verb, _what, _user in log if verb == "patch-status-batch"),
            sum(1 for verb, _what, _user in log if verb == "patch"))


@pytest.mark.parametrize("flavor", STORES)
def test_another_writers_status_field_and_label_survive_the_commit(flavor):
    """The daemon read the pod at one resourceVersion; before its status
    lands another client sets a status field and a label.  The verb
    refuses to replace that object's status wholesale, the row goes as a
    merge patch, and the mirror takes what the other writer set."""
    with open_store(flavor) as (store, handle):
        for name in ("raced", "quiet"):
            handle.create(make_pod(name))
        player = make_player(handle)
        admit_all(player, handle)
        handle.patch("Pod", "raced", {"metadata": {"labels": {"tier": "gold"}},
                                      "status": {"qosClass": "Burstable"}},
                     "merge", namespace="default")
        assert step_until(player, lambda: player.transitions >= 2)
        raced = handle.get("Pod", "raced", namespace="default")
        assert raced["status"]["phase"] == "Running" and raced["status"]["podIP"]
        assert raced["status"]["qosClass"] == "Burstable"
        assert raced["metadata"]["labels"] == {"app": "raced", "tier": "gold"}
        assert handle.get("Pod", "quiet", namespace="default")["status"]["phase"] == "Running"
        # one batch took the row that nobody else wrote; the raced one
        # was refused there and committed by one merge patch (the other
        # client's patch is the second)
        assert audit_counts(store) == (1, 2)
        row = player._rows[("default", "raced")]
        mirror = player.sim.objects[row]
        assert mirror["metadata"]["labels"]["tier"] == "gold"
        assert mirror["status"]["qosClass"] == "Burstable"
        assert mirror["status"]["phase"] == "Running"
        assert player._written_rv[row] == raced["metadata"]["resourceVersion"]
        quiet = player.sim.objects[player._rows[("default", "quiet")]]
        assert quiet == handle.get("Pod", "quiet", namespace="default")
        assert player.swallowed_errors == 0


@pytest.mark.parametrize("flavor", STORES)
def test_a_missing_object_releases_its_row_and_the_rest_commits(flavor):
    with open_store(flavor) as (store, handle):
        for i in range(3):
            handle.create(make_pod(f"pod-{i}"))
        player = make_player(handle)
        admit_all(player, handle)
        store.delete("Pod", "pod-1", namespace="default")  # no event reaches the player
        assert step_until(player, lambda: player.transitions >= 2)
        assert phases(handle) == {"pod-0": "Running", "pod-2": "Running"}
        assert ("default", "pod-1") not in player._rows
        assert sorted(player._rows) == [("default", "pod-0"), ("default", "pod-2")]
        assert player.swallowed_errors == 0


def pressured(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"), fsync="off")
    store = ResourceStore()
    store.attach_wal(wal)
    return store, wal


@pytest.mark.parametrize("flavor", ("resource", "wire"))
def test_a_degraded_store_refuses_the_batch_and_the_rows_fire_again(flavor, tmp_path):
    backing, wal = pressured(tmp_path)
    with open_store(flavor, store=backing) as (store, handle):
        for i in range(3):
            handle.create(make_pod(f"pod-{i}"))
        player = make_player(handle)
        admit_all(player, handle)
        wal.set_pressure(FsPressure("disk-full"))
        store.create(make_pod("filler"))  # rides the reserve, flips degraded
        assert store.storage_degraded() is not None
        item = [("default", "pod-0", {"phase": "Running"})]
        if flavor == "wire":
            with pytest.raises(ApiUnavailable) as err:
                handle.apply_status_batch("Pod", item)
            assert err.value.last_status == 503
            req = urllib.request.Request(
                f"http://{handle._hostport}/status-batch", method="POST",
                data=json.dumps({"kind": "Pod", "items": item}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as raw:
                urllib.request.urlopen(req, timeout=10)
            assert raw.value.code == 503 and raw.value.headers["Retry-After"]
            assert json.loads(raw.value.read())["reason"] == "StorageDegraded"
        else:
            with pytest.raises(StorageDegraded):
                handle.apply_status_batch("Pod", item)
        player.step(100)
        player.step(100)
        assert player.swallowed_errors >= 1 and player.transitions == 0
        assert set(phases(handle).values()) == {None}
        wal.set_pressure(None)
        assert store.probe_writable()
        assert step_until(player, lambda: player.transitions >= 3)
        got = phases(handle)
        got.pop("filler")  # admitted by nobody: not the player's
        assert got == {f"pod-{i}": "Running" for i in range(3)}


def test_an_acknowledged_batch_survives_a_kill_of_the_apiserver(tmp_path):
    """The real daemon, its snapshot and WAL: a batch answered with 200,
    SIGKILL, a start from the files: every status is served."""
    from kwok_tpu.ctl.components import free_port

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def start(port):
        return subprocess.Popen(
            [sys.executable, "-m", "kwok_tpu.cmd.apiserver", "--port", str(port),
             "--state-file", str(tmp_path / "state.json"), "--save-interval", "3600",
             "--wal-file", str(tmp_path / "wal.jsonl")],
            stdout=open(tmp_path / "apiserver.log", "ab"), stderr=subprocess.STDOUT,
            env={**os.environ, "PYTHONPATH": root}, start_new_session=True)

    port = free_port()
    proc = start(port)
    try:
        client = ClusterClient(f"http://127.0.0.1:{port}")
        assert client.wait_ready(30)
        created = [client.create(make_pod(f"pod-{i}")) for i in range(50)]
        sent = {o["metadata"]["name"]: {"phase": "Running", "podIP": f"10.1.0.{i}"}
                for i, o in enumerate(created)}
        results = client.apply_status_batch("Pod", [
            ("default", o["metadata"]["name"], sent[o["metadata"]["name"]],
             o["metadata"]["resourceVersion"]) for o in created])
        acked = {o["metadata"]["name"]: r[0] for o, r in zip(created, results)}
        assert len(acked) == 50 and all(rv > 0 for rv in acked.values())
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait(timeout=20)
        proc = start(port)
        assert client.wait_ready(30)
        served = {o["metadata"]["name"]: o for o in client.list("Pod")[0]}
        assert {n: o["status"] for n, o in served.items()} == sent
        assert {n: int(o["metadata"]["resourceVersion"]) for n, o in served.items()} == acked
    finally:
        with contextlib.suppress(ProcessLookupError):
            os.killpg(proc.pid, signal.SIGKILL)
        proc.wait(timeout=20)


def test_the_request_is_admitted_like_a_bulk_and_audited_once(tmp_path):
    from kwok_tpu.cluster.apiserver import _H_REQ

    flow = FlowController()
    audit = tmp_path / "audit.jsonl"
    with open_store("wire", flow=flow, audit_path=str(audit)) as (store, client):
        level = flow.classify(client.client_id)
        created = [client.create(make_pod(f"pod-{i}")) for i in range(4)]

        def served(kind):
            return sum(d["count"] for lv, d in _H_REQ.snapshot().items()
                       if lv[0] == "POST" and lv[1] == kind and lv[2] == level)

        before = (flow.snapshot()[level]["dispatched"], served("bulk"), served("status-batch"))
        client.bulk([{"verb": "patch", "kind": "Pod", "name": "pod-0", "namespace": "default",
                      "patch_type": "merge", "subresource": "status",
                      "data": {"status": {"phase": "Pending"}}}])
        mid = flow.snapshot()[level]["dispatched"]
        results = client.apply_status_batch("Pod", [
            ("default", o["metadata"]["name"], {"phase": "Running"}, None) for o in created])
        assert all(r[0] > 0 and r[1] is None for r in results)
        after = (flow.snapshot()[level]["dispatched"], served("bulk"), served("status-batch"))
        # one seat at the level a /bulk of the same client takes, and a
        # duration under its own kind: a bulk's mean stays a bulk's
        assert mid - before[0] == 1 and after[0] - mid == 1
        assert after[1] - before[1] == 1 and after[2] - before[2] == 1
        assert flow.snapshot()[level]["inflight"] == 0
        lines = [json.loads(ln) for ln in audit.read_text().splitlines()]
        mine = [ln for ln in lines if ln["path"] == "/status-batch"]
        assert len(mine) == 1 and mine[0]["verb"] == "POST" and mine[0]["code"] == 200
        assert [what for verb, what, _u in store.audit_log()
                if verb == "patch-status-batch"] == ["Pod:4"]


@pytest.mark.parametrize("items", [
    [["default", "pod-0"]],
    [["default", 7, {}]],
    [["default", "pod-0", "Running"]],
    [["default", "pod-0", {}, 12]],
    [{"name": "pod-0"}],
], ids=["short", "name", "status", "rv", "mapping"])
def test_a_malformed_item_is_a_bad_request_and_commits_nothing(items):
    with open_store("wire") as (store, client):
        client.create(make_pod("pod-0"))
        rv = store.resource_version
        with pytest.raises(Exception) as err:
            client._request("POST", "/status-batch", body={"kind": "Pod", "items": items})
        assert getattr(err.value, "code", None) == 400
        assert store.resource_version == rv


def test_a_tenants_slice_has_no_status_batch_lane():
    """``TenantStore`` hands the call to the host store unmapped: a
    tenant-scoped request must not reach other namespaces through it."""
    from kwok_tpu.fleet import FleetRegistry

    store = ResourceStore()
    with APIServer(store, fleet=FleetRegistry(store, ["acme"])) as srv:
        host = ClusterClient(srv.url)
        pod = host.create(make_pod("pod-0"))
        body = {"kind": "Pod", "items": [["default", "pod-0", {"phase": "Running"}, None]]}
        req = urllib.request.Request(
            f"{srv.url}/status-batch", method="POST", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json", "X-Kwok-Tenant": "acme"})
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=10)
        assert err.value.code == 404
        assert host.get("Pod", "pod-0", namespace="default") == pod
        assert host.apply_status_batch("Pod", [tuple(body["items"][0])])[0][0] > 0
