"""The delete-batch verb across the wire (ISSUE 29): the rows a deleting
stage fires reach the apiserver as one columnar commit (``POST
/delete-batch`` -> ``ResourceStore.apply_delete_batch``) of ``[namespace,
name, resourceVersion]``, with what the per-row path's finalizer patch and
delete guaranteed: the same DELETED events and the same final LIST, an
object somebody else wrote refused and played op by op, a missing one
counted as gone, one durable WAL record before the answer that every replay
path reads.  A row whose outcome is more than one DELETED event stays on
the per-row path."""

import contextlib
import datetime
import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from kwok_tpu.api.loader import load_stages
from kwok_tpu.cluster import wal as walmod
from kwok_tpu.cluster.apiserver import APIServer
from kwok_tpu.cluster.client import ClusterClient, RetryPolicy
from kwok_tpu.cluster.informer import InformerEvent
from kwok_tpu.cluster.sharding import build_sharded_store, shard_of
from kwok_tpu.cluster.store import EventRecorder, ResourceStore
from kwok_tpu.controllers.device_player import DeviceStagePlayer
from kwok_tpu.snapshot.pitr import PitrArchive
from kwok_tpu.stages import load_builtin
from kwok_tpu.utils.backoff import Backoff

STORES = ("resource", "sharded", "wire")
SHARDS = 4
FAKE = "kwok.x-k8s.io/fake"


def other_namespace():
    """A namespace on another shard than ``default``."""
    home = shard_of(True, "Pod", "default", SHARDS)
    return next(f"ns-{i}" for i in range(64)
                if shard_of(True, "Pod", f"ns-{i}", SHARDS) != home)


def make_pod(name, ns="default", finalizers=(FAKE,), **meta):
    meta = {"name": name, "namespace": ns, "uid": f"uid-{ns}-{name}", **meta}
    if finalizers:
        meta["finalizers"] = list(finalizers)
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": meta,
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


def drain(watcher, want, timeout=10.0, quiet_s=0.3):
    """``want`` events of a watch and whatever follows them within
    ``quiet_s``, each as (type, namespace, name, the object less what two
    plays of the same writes differ in)."""
    out = []
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        ev = watcher.next(timeout=0.2)
        if ev is not None:
            meta = ev.object["metadata"]
            out.append((ev.type, meta["namespace"], meta["name"], sans_rv([ev.object])))
        elif len(out) >= want:
            break
        if len(out) == want:
            deadline = min(deadline, time.monotonic() + quiet_s)
    return out


def sans_rv(objs):
    """By key, without the resourceVersion and the wall clock's stamps."""
    out = {}
    for o in objs:
        o = json.loads(json.dumps(o))
        for stamp in ("resourceVersion", "creationTimestamp", "deletionTimestamp"):
            o["metadata"].pop(stamp, None)
        out[(o["metadata"]["namespace"], o["metadata"]["name"])] = o
    return out


def terminating(handle):
    objs = [o for o in handle.list("Pod")[0] if o["metadata"].get("deletionTimestamp")]
    objs.sort(key=lambda o: (o["metadata"]["name"], o["metadata"]["namespace"]))
    return objs


def play(flavor, how):
    """Six terminating pods in two namespaces beside two that stay, removed
    by the per-row path's two ops a pod or by the verb; what a watcher saw
    and what is listed at the end."""
    ns_b = other_namespace()
    with open_store(flavor) as (_store, handle):
        for ns in ("default", ns_b):
            for i in range(3):
                handle.create(make_pod(f"pod-{i}", ns))
            handle.create(make_pod("stays", ns))
        for ns in ("default", ns_b):
            for i in range(3):
                assert handle.delete("Pod", f"pod-{i}", namespace=ns) is not None
        watcher = handle.watch("Pod")
        try:
            objs = terminating(handle)
            assert len(objs) == 6
            if how == "ops":
                ops = []
                for o in objs:
                    ident = {"kind": "Pod", "name": o["metadata"]["name"],
                             "namespace": o["metadata"]["namespace"]}
                    ops.append({"verb": "patch", "patch_type": "json", **ident,
                                "data": [{"op": "remove", "path": "/metadata/finalizers"}]})
                    ops.append({"verb": "delete", **ident})
                results = handle.bulk(ops)
                # the patch reaps the pod; the delete finds nothing
                assert [r["status"] for r in results] == ["ok", "error"] * 6
            else:
                results = handle.apply_delete_batch("Pod", [
                    (o["metadata"]["namespace"], o["metadata"]["name"],
                     o["metadata"]["resourceVersion"]) for o in objs])
                assert all(isinstance(r, int) and r > 0 for r in results)
            events = drain(watcher, 6)
        finally:
            watcher.stop()
        return events, sans_rv(handle.list("Pod")[0])


@pytest.mark.parametrize("flavor", STORES)
def test_a_batch_leaves_the_events_and_the_list_of_a_finalizer_patch_and_a_delete(flavor):
    events_ops, listed_ops = play(flavor, "ops")
    events_verb, listed_verb = play(flavor, "verb")
    assert listed_verb == listed_ops and len(listed_verb) == 2
    assert len(events_verb) == 6 and {e[0] for e in events_verb} == {"DELETED"}
    for _type, ns, name, obj in events_verb:
        assert "finalizers" not in obj[(ns, name)]["metadata"]
    # a batch commits shard by shard, so across shards only each
    # namespace's own order is the ops'; within one store all of it is
    for ns in {e[1] for e in events_ops}:
        assert [e for e in events_verb if e[1] == ns] == [e for e in events_ops if e[1] == ns]
    if flavor != "sharded":
        assert events_verb == events_ops


def make_player(handle, stages=None, capacity=8, **kw):
    from kwok_tpu.controllers.pod_controller import PodEnv

    env = PodEnv()
    player = DeviceStagePlayer(
        handle, "Pod", stages or load_builtin("pod-fast"), capacity=capacity, tick_ms=20,
        funcs_for=env.funcs, on_delete=env.release, **kw,
    )
    # as start() sets it: a deletionTimestamp is milliseconds from here
    player.sim.epoch = datetime.datetime.now(datetime.timezone.utc)
    return player


def feed(player, handle, etype="ADDED"):
    for obj in handle.list("Pod")[0]:
        player.events.add(InformerEvent(etype, obj))
    player._drain_events()


def step_until(player, done, steps=60):
    for _ in range(steps):
        player.step(100)
        if done():
            return True
    return False


def audit(store, verb):
    return [what for v, what, _user in store.audit_log() if v == verb]


def running_then_terminating(handle, names):
    """A player that played ``names`` to Running and has seen each marked
    for deletion."""
    for name in names:
        handle.create(make_pod(name))
    player = make_player(handle)
    feed(player, handle)
    assert step_until(player, lambda: player.transitions >= len(names))
    for name in names:
        handle.delete("Pod", name, namespace="default")
    feed(player, handle, "MODIFIED")
    return player


@pytest.mark.parametrize("flavor", STORES)
def test_an_object_written_since_is_refused_played_op_by_op_and_gone_all_the_same(flavor):
    with open_store(flavor) as (store, handle):
        player = running_then_terminating(handle, ("raced", "quiet"))
        # after the player read it: its mirror is one resourceVersion behind
        handle.patch("Pod", "raced", {"metadata": {"labels": {"tier": "gold"}}}, "merge",
                     namespace="default")
        watcher = handle.watch("Pod")
        try:
            assert step_until(player, lambda: player.transitions >= 4)
            events = drain(watcher, 2)
        finally:
            watcher.stop()
        assert handle.list("Pod")[0] == [] and not player._rows
        # the batch took the row nobody else wrote; the other one went as
        # a finalizer patch (which reaps it) and a delete that finds nothing
        assert audit(store, "delete-batch") == ["Pod:1"]
        # (the two patches: the other writer's, the per-row path's; the two
        # deletes: the client's, for the per-row path's finds nothing)
        assert len(audit(store, "patch")) == 2 and len(audit(store, "delete")) == 2
        assert sorted((e[0], e[2]) for e in events) == [("DELETED", "quiet"), ("DELETED", "raced")]
        assert events[[e[2] for e in events].index("raced")][3][("default", "raced")][
            "metadata"]["labels"] == {"tier": "gold"}
        assert player.transitions == 4 and player.swallowed_errors == 0


@pytest.mark.parametrize("flavor", STORES)
def test_a_missing_object_counts_as_gone_and_the_rest_commits(flavor):
    with open_store(flavor) as (store, handle):
        player = running_then_terminating(handle, ("pod-0", "pod-1", "pod-2"))
        # no event of this reaches the player
        store.patch("Pod", "pod-1", [{"op": "remove", "path": "/metadata/finalizers"}], "json",
                    namespace="default")
        assert step_until(player, lambda: player.transitions >= 6)
        assert handle.list("Pod")[0] == [] and not player._rows
        assert audit(store, "delete-batch") == ["Pod:2"]
        assert player.transitions == 6 and player.swallowed_errors == 0


@pytest.mark.parametrize("flavor", ("resource", "wire"))
def test_a_degraded_store_refuses_the_batch_and_the_rows_fire_again(flavor, tmp_path):
    from kwok_tpu.chaos.fs_pressure import FsPressure
    from kwok_tpu.cluster.client import ApiUnavailable
    from kwok_tpu.cluster.wal import StorageDegraded

    wal = walmod.WriteAheadLog(str(tmp_path / "wal.jsonl"), fsync="off")
    backing = ResourceStore()
    backing.attach_wal(wal)
    with open_store(flavor, store=backing) as (store, handle):
        player = running_then_terminating(handle, ("pod-0", "pod-1", "pod-2"))
        wal.set_pressure(FsPressure("disk-full"))
        store.create(make_pod("filler"))  # rides the reserve, flips degraded
        assert store.storage_degraded() is not None
        item = [("default", "pod-0", store.get("Pod", "pod-0", namespace="default")[
            "metadata"]["resourceVersion"])]
        with pytest.raises(ApiUnavailable if flavor == "wire" else StorageDegraded) as err:
            handle.apply_delete_batch("Pod", item)
        if flavor == "wire":
            assert err.value.last_status == 503
        player.step(100)
        player.step(100)
        assert player.swallowed_errors >= 1 and player.transitions == 3
        assert len(terminating(handle)) == 3 and len(player._rows) == 3
        wal.set_pressure(None)
        assert store.probe_writable()
        assert step_until(player, lambda: player.transitions >= 6)
        assert [o["metadata"]["name"] for o in handle.list("Pod")[0]] == ["filler"]
        assert not player._rows and audit(store, "delete-batch") == ["Pod:3"]


STAGE = """
apiVersion: kwok.x-k8s.io/v1alpha1
kind: Stage
metadata:
  name: {name}
spec:
  resourceRef:
    apiGroup: v1
    kind: Pod
  selector:
    matchExpressions:
    - key: '{key}'
      operator: 'Exists'
  next:
{next}
"""

#: stage sets whose deleting stage leaves more than one DELETED event
SLOW = {
    # of two finalizers the stage removes one: the pod stays, terminating
    "a-finalizer-stays": (
        ".metadata.deletionTimestamp",
        "    finalizers:\n      remove:\n      - value: %s\n    delete: true" % FAKE,
        dict(finalizers=(FAKE, "example.com/held")), True),
    # a live pod whose finalizers the stage empties: MODIFIED, then DELETED
    "a-live-pod": (
        '.metadata.labels["evict"]',
        "    finalizers:\n      empty: true\n    delete: true",
        dict(labels={"evict": "now"}), False),
    # the stage records an event, and the player has a recorder for it
    "an-event": (
        ".metadata.deletionTimestamp",
        "    event:\n      type: Normal\n      reason: Killing\n      message: bye\n"
        "    finalizers:\n      empty: true\n    delete: true",
        {}, True),
}


@pytest.mark.parametrize("flavor", STORES)
@pytest.mark.parametrize("case", sorted(SLOW))
def test_a_row_whose_outcome_is_more_than_one_deleted_event_stays_slow(case, flavor):
    key, nxt, pod_kw, ask_delete = SLOW[case]
    stages = load_stages(STAGE.format(name="pod-delete", key=key, next=nxt))
    with open_store(flavor) as (store, handle):
        handle.create(make_pod("pod-0", **pod_kw))
        if ask_delete:
            handle.delete("Pod", "pod-0", namespace="default")
        recorder = EventRecorder(handle, "kwok") if case == "an-event" else None
        player = make_player(handle, stages=stages, recorder=recorder)
        feed(player, handle)
        watcher = handle.watch("Pod")
        try:
            assert step_until(player, lambda: player.transitions >= 1)
            kinds = [e[0] for e in drain(watcher, 1)]
        finally:
            watcher.stop()
        assert audit(store, "delete-batch") == [] and player.swallowed_errors == 0
        left = handle.list("Pod")[0]
        if case == "a-finalizer-stays":
            assert kinds == ["MODIFIED"]
            assert [o["metadata"]["finalizers"] for o in left] == [["example.com/held"]]
        elif case == "a-live-pod":
            assert kinds == ["MODIFIED", "DELETED"] and left == []
        else:
            assert kinds == ["DELETED"] and left == []
            assert [e["reason"] for e in handle.list("Event")[0]] == ["Killing"]


@pytest.mark.parametrize("flavor", STORES)
def test_pod_generals_delete_of_a_pod_without_finalizers_goes_by_batch(flavor):
    """``pod-general``'s ``pod-delete`` selects a terminating pod with no
    finalizer and only deletes: nothing to change, one DELETED event."""
    with open_store(flavor) as (store, handle):
        # this store removes such a pod at the delete; the stage is for
        # one that an apiserver left terminating, so it is created so
        asked = datetime.datetime.now(datetime.timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ")
        handle.create(make_pod("pod-0", finalizers=(), deletionTimestamp=asked))
        player = make_player(handle, stages=load_builtin("pod-general"))
        feed(player, handle)
        assert step_until(player, lambda: player.transitions >= 1, steps=200)
        assert handle.list("Pod")[0] == [] and not player._rows
        assert audit(store, "delete-batch") == ["Pod:1"] and audit(store, "delete") == []
        assert player.swallowed_errors == 0


@pytest.mark.parametrize("items", [
    [["default", "pod-0"]],
    [["default", 7, "3"]],
    [["default", "pod-0", 3]],
    [["default", "pod-0", None]],
    [["default", "pod-0", "3", {}]],
    [{"name": "pod-0"}],
], ids=["short", "name", "rv-number", "rv-null", "long", "mapping"])
def test_a_malformed_item_is_a_bad_request_and_commits_nothing(items):
    with open_store("wire") as (store, client):
        client.create(make_pod("pod-0", finalizers=()))
        rv = store.resource_version
        with pytest.raises(Exception) as err:
            client._request("POST", "/delete-batch", body={"kind": "Pod", "items": items})
        assert getattr(err.value, "code", None) == 400
        assert store.resource_version == rv and store.count("Pod") == 1


def test_a_tenants_slice_has_no_delete_batch_lane():
    """``TenantStore`` hands the call to the host store unmapped: a
    tenant-scoped request must not reach other namespaces through it."""
    from kwok_tpu.fleet import FleetRegistry

    store = ResourceStore()
    with APIServer(store, fleet=FleetRegistry(store, ["acme"])) as srv:
        host = ClusterClient(srv.url)
        pod = host.create(make_pod("pod-0"))
        body = {"kind": "Pod",
                "items": [["default", "pod-0", pod["metadata"]["resourceVersion"]]]}
        req = urllib.request.Request(
            f"{srv.url}/delete-batch", method="POST", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json", "X-Kwok-Tenant": "acme"})
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=10)
        assert err.value.code == 404
        assert host.get("Pod", "pod-0", namespace="default") == pod
        assert host.apply_delete_batch("Pod", [tuple(body["items"][0])])[0] > 0
        assert host.list("Pod")[0] == []


def test_the_request_is_admitted_like_a_bulk_timed_apart_and_audited_once(tmp_path):
    from kwok_tpu.cluster.apiserver import _H_REQ
    from kwok_tpu.cluster.flowcontrol import FlowController

    flow = FlowController()
    audit_file = tmp_path / "audit.jsonl"
    with open_store("wire", flow=flow, audit_path=str(audit_file)) as (store, client):
        level = flow.classify(client.client_id)
        created = [client.create(make_pod(f"pod-{i}")) for i in range(4)]

        def served(kind):
            return sum(d["count"] for lv, d in _H_REQ.snapshot().items()
                       if lv[0] == "POST" and lv[1] == kind and lv[2] == level)

        before = (flow.snapshot()[level]["dispatched"], served("bulk"), served("delete-batch"))
        results = client.apply_delete_batch("Pod", [
            ("default", o["metadata"]["name"], o["metadata"]["resourceVersion"])
            for o in created])
        assert all(isinstance(r, int) and r > 0 for r in results)
        after = (flow.snapshot()[level]["dispatched"], served("bulk"), served("delete-batch"))
        assert after[0] - before[0] == 1 and after[1] == before[1] and after[2] - before[2] == 1
        assert flow.snapshot()[level]["inflight"] == 0
        lines = [json.loads(ln) for ln in audit_file.read_text().splitlines()]
        mine = [ln for ln in lines if ln["path"] == "/delete-batch"]
        assert len(mine) == 1 and mine[0]["verb"] == "POST" and mine[0]["code"] == 200
        assert audit(store, "delete-batch") == ["Pod:4"]


def test_an_acknowledged_batch_survives_a_kill_of_the_apiserver(tmp_path):
    """The real daemon, its snapshot and WAL: a batch answered with 200,
    SIGKILL at once, a start from the files: the deleted stay gone, the
    others stand, a watch resumed from before the batch sees each DELETED,
    and neither ``record_rvs`` nor fsck finds a hole."""
    from kwok_tpu.ctl.components import free_port

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    wal_file = str(tmp_path / "wal.jsonl")

    def start(port):
        return subprocess.Popen(
            [sys.executable, "-m", "kwok_tpu.cmd.apiserver", "--port", str(port),
             "--state-file", str(tmp_path / "state.json"), "--save-interval", "3600",
             "--wal-file", wal_file],
            stdout=open(tmp_path / "apiserver.log", "ab"), stderr=subprocess.STDOUT,
            env={**os.environ, "PYTHONPATH": root}, start_new_session=True)

    port = free_port()
    proc = start(port)
    try:
        client = ClusterClient(f"http://127.0.0.1:{port}")
        assert client.wait_ready(30)
        for i in range(50):
            client.create(make_pod(f"pod-{i}"))
        doomed = [f"pod-{i}" for i in range(0, 50, 2)] + ["pod-1", "pod-3", "pod-5", "pod-7",
                                                          "pod-9"]
        client.bulk([{"verb": "delete", "kind": "Pod", "name": n, "namespace": "default"}
                     for n in doomed])
        objs = terminating(client)
        assert sorted(o["metadata"]["name"] for o in objs) == sorted(doomed)
        before = max(int(o["metadata"]["resourceVersion"]) for o in client.list("Pod")[0])
        results = client.apply_delete_batch("Pod", [
            ("default", o["metadata"]["name"], o["metadata"]["resourceVersion"]) for o in objs])
        acked = {o["metadata"]["name"]: rv for o, rv in zip(objs, results)}
        assert len(acked) == 30 and all(isinstance(rv, int) and rv > before
                                        for rv in acked.values())
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait(timeout=20)

        records = walmod.scan(wal_file).records
        logged = [r for r in records if r.get("t") == "delete"]
        assert len(logged) == 1 and logged[0]["k"] == "Pod" and logged[0]["rv"] == max(
            acked.values())
        assert {(ns, name): rv for ns, name, rv in logged[0]["i"]} == {
            ("default", n): rv for n, rv in acked.items()}
        covered = {rv for r in records for rv in walmod.record_rvs(r)}
        assert set(acked.values()) <= covered
        assert covered >= set(range(1, max(acked.values()) + 1))
        report = walmod.fsck(wal_file)
        assert report["ok"] and report["missing_rvs"] == [] and report["max_rv"] == max(
            acked.values())

        proc = start(port)
        assert client.wait_ready(30)
        served = {o["metadata"]["name"]: o for o in client.list("Pod")[0]}
        assert sorted(served) == sorted(set(f"pod-{i}" for i in range(50)) - set(doomed))
        assert not any(o["metadata"].get("deletionTimestamp") for o in served.values())
        watcher = client.watch("Pod", since_rv=before)
        try:
            seen = drain(watcher, 30)
        finally:
            watcher.stop()
        assert sorted((e[0], e[2]) for e in seen) == sorted(("DELETED", n) for n in doomed)
    finally:
        with contextlib.suppress(ProcessLookupError):
            os.killpg(proc.pid, signal.SIGKILL)
        proc.wait(timeout=20)


def test_a_point_in_time_rebuild_cuts_a_batch_at_the_item(tmp_path):
    """The PITR rebuild trims a delete record an item as it trims a
    status record: the state at a resourceVersion inside a batch has the
    objects deleted up to it gone and the later ones terminating."""
    wal_file = str(tmp_path / "wal.jsonl")
    root = str(tmp_path / "pitr")
    archive = PitrArchive(root)
    store = ResourceStore()
    store.attach_wal(walmod.WriteAheadLog(wal_file, fsync="off", archive_dir=root))
    for i in range(4):
        store.create(make_pod(f"pod-{i}"))
        store.delete("Pod", f"pod-{i}", namespace="default")
    objs = terminating(store)
    rvs = store.apply_delete_batch("Pod", [
        ("default", o["metadata"]["name"], o["metadata"]["resourceVersion"]) for o in objs[:3]])
    assert rvs == [9, 10, 11]
    store.create(make_pod("later"))
    built, _info = archive.build_state(10, live_wal=wal_file)
    assert built["resourceVersion"] == 10
    assert sorted(o["metadata"]["name"] for o in built["objects"]) == ["pod-2", "pod-3"]
    built, _info = archive.build_state(store.resource_version, live_wal=wal_file)
    assert json.dumps(built, sort_keys=True) == json.dumps(store.dump_state(), sort_keys=True)
    fresh = ResourceStore()
    assert fresh.replay_wal(wal_file) and fresh.dump_state() == store.dump_state()
    assert [e.type for e in fresh.watch("Pod", since_rv=8).drain()] == ["DELETED"] * 3 + ["ADDED"]
