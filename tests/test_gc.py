"""GC controller: ownerReference cascade + namespace lifecycle (the
kube-controller-manager behaviors; reference composes a real kcm into
every cluster, pkg/kwokctl/components/kube_controller_manager.go:46)."""

import time

import pytest

from kwok_tpu.api.config import KwokConfiguration
from kwok_tpu.cluster.store import NotFound, ResourceStore, ResourceType
from kwok_tpu.controllers import Controller
from kwok_tpu.controllers.gc_controller import NS_FINALIZER, GCController
from kwok_tpu.stages import default_node_stages, load_builtin

from tests.test_controllers import make_node, make_pod, wait_for

JOB_TYPE = ResourceType("batch/v1", "Job", "jobs")


@pytest.fixture
def gc_store():
    store = ResourceStore()
    store.register_type(JOB_TYPE)
    gc = GCController(store, resync_s=0.2).start()
    yield store, gc
    gc.stop()


def make_job(name="j1"):
    return {
        "apiVersion": "batch/v1",
        "kind": "Job",
        "metadata": {"name": name, "namespace": "default"},
        "spec": {},
    }


def owned_pod(name, owner, include_uid=True):
    pod = make_pod(name)
    ref = {
        "apiVersion": owner.get("apiVersion"),
        "kind": owner["kind"],
        "name": owner["metadata"]["name"],
    }
    if include_uid:
        ref["uid"] = owner["metadata"]["uid"]
    pod["metadata"]["ownerReferences"] = [ref]
    return pod


def test_job_delete_cascades_to_pods_via_stage_path(gc_store):
    """VERDICT r02 #3 done-criterion: delete a Job, its pods exit
    through the normal stage delete path (finalizer held by pod-create,
    removed by pod-remove-finalizer once terminating)."""
    store, gc = gc_store
    ctr = Controller(
        store,
        KwokConfiguration(
            manage_all_nodes=True, backend="device", device_tick_ms=20,
            node_lease_duration_seconds=0,
        ),
        local_stages={
            "Node": default_node_stages(),
            "Pod": load_builtin("pod-general"),
        },
        seed=0,
    )
    ctr.start()
    try:
        store.create(make_node("node-0"))
        job = store.create(make_job())
        for i in range(3):
            store.create(owned_pod(f"jp{i}", job))
        # pods progress (Job-owned pods complete via pod-complete) and
        # hold the kwok finalizer from pod-create
        def settled():
            for i in range(3):
                p = store.get("Pod", f"jp{i}", namespace="default")
                if (p.get("status") or {}).get("phase") not in ("Running", "Succeeded"):
                    return False
                if not p["metadata"].get("finalizers"):
                    return False
            return True

        assert wait_for(settled, timeout=30)
        store.delete("Job", "j1", namespace="default")
        # cascade -> graceful delete -> pod-remove-finalizer -> reaped
        assert wait_for(lambda: store.count("Pod") == 0, timeout=30), (
            store.list("Pod")[0]
        )
    finally:
        ctr.stop()


def test_child_kept_while_any_owner_alive(gc_store):
    store, gc = gc_store
    j1 = store.create(make_job("a"))
    j2 = store.create(make_job("b"))
    pod = make_pod("shared")
    pod["metadata"]["ownerReferences"] = [
        {"apiVersion": "batch/v1", "kind": "Job", "name": "a",
         "uid": j1["metadata"]["uid"]},
        {"apiVersion": "batch/v1", "kind": "Job", "name": "b",
         "uid": j2["metadata"]["uid"]},
    ]
    store.create(pod)
    store.delete("Job", "a", namespace="default")
    time.sleep(0.8)
    assert store.count("Pod") == 1, "child with a living owner must survive"
    store.delete("Job", "b", namespace="default")
    assert wait_for(lambda: store.count("Pod") == 0, timeout=10)


def test_uid_mismatch_counts_as_dead_owner(gc_store):
    """A new object reusing the owner's name is NOT the owner."""
    store, gc = gc_store
    job = store.create(make_job())
    store.create(owned_pod("p1", job))
    store.delete("Job", "j1", namespace="default")
    store.create(make_job())  # same name, new uid
    assert wait_for(lambda: store.count("Pod") == 0, timeout=10)


def test_ownerref_without_uid_cascades_by_name(gc_store):
    store, gc = gc_store
    job = store.create(make_job())
    store.create(owned_pod("p1", job, include_uid=False))
    time.sleep(0.5)
    assert store.count("Pod") == 1
    store.delete("Job", "j1", namespace="default")
    assert wait_for(lambda: store.count("Pod") == 0, timeout=10)


def test_namespace_lifecycle(gc_store):
    """Namespaces gain the finalizer on sight; deleting one reaps its
    contents and then the namespace itself."""
    store, gc = gc_store
    store.create({"apiVersion": "v1", "kind": "Namespace",
                  "metadata": {"name": "work"}})
    assert wait_for(
        lambda: NS_FINALIZER
        in (store.get("Namespace", "work")["metadata"].get("finalizers") or [])
    )
    pod = make_pod("wp")
    pod["metadata"]["namespace"] = "work"
    store.create(pod)
    store.create({"apiVersion": "v1", "kind": "ConfigMap",
                  "metadata": {"name": "cm", "namespace": "work"}, "data": {}})
    store.delete("Namespace", "work")

    def gone():
        try:
            store.get("Namespace", "work")
            return False
        except NotFound:
            return True

    assert wait_for(
        lambda: store.count("Pod") == 0 and store.count("ConfigMap") == 0,
        timeout=10,
    )
    assert wait_for(gone, timeout=10), "empty terminating namespace must finalize"


def test_object_created_into_terminating_namespace_is_reaped(gc_store):
    store, gc = gc_store
    store.create({"apiVersion": "v1", "kind": "Namespace",
                  "metadata": {"name": "tns"}})
    assert wait_for(
        lambda: NS_FINALIZER
        in (store.get("Namespace", "tns")["metadata"].get("finalizers") or [])
    )
    pod = make_pod("keeper")
    pod["metadata"]["namespace"] = "tns"
    store.create(pod)
    store.delete("Namespace", "tns")
    late = make_pod("late")
    late["metadata"]["namespace"] = "tns"
    try:
        store.create(late)
    except Exception:
        pass  # already reaped namespace may reject later; reap covers it
    assert wait_for(lambda: store.count("Pod") == 0, timeout=10)


def test_create_time_finalizer_closes_create_delete_race():
    """With namespace_finalizers=True (cluster composition), a namespace
    created and deleted before GC observes anything still terminates
    gracefully: the finalizer is present from create, so the store holds
    it until a (late-started) GC reaps the contents and finalizes."""
    store = ResourceStore(namespace_finalizers=True)
    store.create({"apiVersion": "v1", "kind": "Namespace",
                  "metadata": {"name": "racy"}})
    pod = make_pod("rp")
    pod["metadata"]["namespace"] = "racy"
    store.create(pod)
    store.delete("Namespace", "racy")  # no GC running yet
    ns = store.get("Namespace", "racy")
    assert ns["metadata"].get("deletionTimestamp"), "must be Terminating"
    gc = GCController(store, resync_s=0.2).start()
    try:
        assert wait_for(lambda: store.count("Pod") == 0, timeout=10)

        def gone():
            try:
                store.get("Namespace", "racy")
                return False
            except NotFound:
                return True

        assert wait_for(gone, timeout=10)
    finally:
        gc.stop()


def test_live_cluster_owner_cascade_through_kcm_daemon(tmp_path, monkeypatch):
    """The cascade through a REAL multi-process cluster: an owner
    ConfigMap and pods referencing it are created through the
    apiserver; deleting the owner makes the composed kcm daemon
    (cmd/kcm.py, a separate process) collect the pods
    (VERDICT r03 next-#6; reference clusters get this from the real
    kube-controller-manager, components/kube_controller_manager.go:46)."""
    import time as _time

    from kwok_tpu.cmd.kwokctl import main as kwokctl_main
    from kwok_tpu.ctl.runtime import BinaryRuntime

    monkeypatch.setenv("KWOK_TPU_HOME", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    name = "gccasc"
    assert kwokctl_main(["--name", name, "create", "cluster", "--wait", "90"]) == 0
    client = BinaryRuntime(name).client()
    try:
        owner = client.create(
            {"apiVersion": "v1", "kind": "ConfigMap",
             "metadata": {"name": "job-like-owner", "namespace": "default"}}
        )
        ref = {"apiVersion": "v1", "kind": "ConfigMap",
               "name": "job-like-owner",
               "uid": owner["metadata"]["uid"]}
        for i in range(3):
            client.create(
                {"apiVersion": "v1", "kind": "Pod",
                 "metadata": {"name": f"owned-{i}", "namespace": "default",
                               "ownerReferences": [ref]},
                 "spec": {"nodeName": "none", "containers": [{"name": "c"}]},
                 "status": {}}
            )
        # settle: the kcm daemon indexes the children
        deadline = _time.monotonic() + 30
        while client.count("Pod") != 3 and _time.monotonic() < deadline:
            _time.sleep(0.2)
        assert client.count("Pod") == 3

        client.delete("ConfigMap", "job-like-owner")
        deadline = _time.monotonic() + 60
        while client.count("Pod") != 0 and _time.monotonic() < deadline:
            _time.sleep(0.5)
        assert client.count("Pod") == 0, (
            f"{client.count('Pod')} owned pods survived the cascade"
        )
    finally:
        # no assert: a cleanup failure must not mask the real one
        kwokctl_main(["--name", name, "delete", "cluster"])


def test_status_indifferent_gc_is_handed_no_status_batch(monkeypatch):
    """A running GCController must not become a second drain: its
    watches declare status indifference, so a status batch hands its
    watcher nothing, while every other event still reaches it."""
    import time as _time

    store = ResourceStore()
    gc = GCController(store, resync_s=0.2).start()
    try:
        _time.sleep(0.5)  # GC informers subscribe
        st = store._state("Pod")
        (gc_watcher,) = [w for w in st.watchers if not w.status_interest]
        handed = []
        for name in ("_push", "_push_batch"):
            monkeypatch.setattr(
                gc_watcher, name, lambda evs, _name=name: handed.append(_name)
            )
        store.create(make_pod("p0"))
        assert handed == ["_push"]
        other = store.watch("Pod")
        out = store.apply_status_batch("Pod", [("default", "p0", {"phase": "Running"})])
        assert out[0][1]["status"] == {"phase": "Running"}
        assert [ev.rv for ev in other.drain()] == [out[0][0]]
        assert handed == ["_push"], "a status batch reached the GC's watcher"
        store.patch("Pod", "p0", {"metadata": {"labels": {"a": "b"}}}, "merge",
                    namespace="default")
        assert handed == ["_push", "_push"]
    finally:
        gc.stop()
