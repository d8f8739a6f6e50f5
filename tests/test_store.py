"""ResourceStore semantics: RV monotonicity, patch/subresource scoping,
finalizer-aware delete, watch resume, selectors, event aggregation."""

import pytest

from kwok_tpu.cluster.store import (
    ADDED,
    Conflict,
    DELETED,
    EventRecorder,
    MODIFIED,
    NotFound,
    ResourceStore,
    ResourceType,
)


def pod(name, ns="default", node="node-1", labels=None, finalizers=None):
    meta = {"name": name, "namespace": ns}
    if labels:
        meta["labels"] = labels
    if finalizers:
        meta["finalizers"] = finalizers
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": meta,
        "spec": {"nodeName": node},
        "status": {},
    }


def test_create_get_list_rv_monotonic():
    s = ResourceStore()
    p1 = s.create(pod("a"))
    p2 = s.create(pod("b"))
    assert int(p2["metadata"]["resourceVersion"]) > int(p1["metadata"]["resourceVersion"])
    assert p1["metadata"]["uid"] != p2["metadata"]["uid"]
    assert p1["metadata"]["creationTimestamp"].endswith("Z")
    items, rv = s.list("Pod")
    assert [i["metadata"]["name"] for i in items] == ["a", "b"]
    assert rv == s.resource_version
    with pytest.raises(Conflict):
        s.create(pod("a"))


def test_update_conflict_on_stale_rv():
    s = ResourceStore()
    p = s.create(pod("a"))
    p1 = dict(p)
    s.update(p)  # bumps rv
    with pytest.raises(Conflict):
        s.update(p1)


def test_patch_subresource_scoping():
    """A status patch cannot touch spec (apiserver subresource routing)."""
    s = ResourceStore()
    s.create(pod("a"))
    out = s.patch(
        "Pod",
        "a",
        {"spec": {"nodeName": "evil"}, "status": {"phase": "Running"}},
        "strategic",
        subresource="status",
    )
    assert out["status"]["phase"] == "Running"
    assert out["spec"]["nodeName"] == "node-1"


def test_patch_preserves_metadata_invariants():
    s = ResourceStore()
    p = s.create(pod("a"))
    out = s.patch("Pod", "a", {"metadata": {"uid": "forged"}}, "merge")
    assert out["metadata"]["uid"] == p["metadata"]["uid"]


def test_finalizer_graceful_delete():
    """Delete with finalizers -> deletionTimestamp; removing the last
    finalizer reaps the object (reference pod-general FSM depends on
    this: finalizer add -> delete -> remove finalizer -> gone)."""
    s = ResourceStore()
    s.create(pod("a", finalizers=["kwok.x-k8s.io/fake"]))
    w = s.watch("Pod")
    out = s.delete("Pod", "a")
    assert out is not None and out["metadata"]["deletionTimestamp"]
    assert s.count("Pod") == 1
    ev = w.next(timeout=1.0)
    assert ev.type == MODIFIED
    # clearing finalizers reaps
    s.patch("Pod", "a", [{"op": "replace", "path": "/metadata/finalizers", "value": []}], "json")
    assert s.count("Pod") == 0
    ev = w.next(timeout=1.0)
    assert ev.type == DELETED
    with pytest.raises(NotFound):
        s.get("Pod", "a")


def test_delete_without_finalizers_is_immediate():
    s = ResourceStore()
    s.create(pod("a"))
    assert s.delete("Pod", "a") is None
    assert s.count("Pod") == 0


def test_watch_stream_and_resume():
    s = ResourceStore()
    s.create(pod("a"))
    _, rv = s.list("Pod")
    w = s.watch("Pod", since_rv=rv)
    s.create(pod("b"))
    s.patch("Pod", "b", {"status": {"phase": "Running"}}, "merge", subresource="status")
    evs = [w.next(timeout=1.0) for _ in range(2)]
    assert [e.type for e in evs] == [ADDED, MODIFIED]
    assert evs[1].object["status"]["phase"] == "Running"
    # resume from an old rv replays history
    w2 = s.watch("Pod", since_rv=rv)
    evs2 = [w2.next(timeout=1.0) for _ in range(2)]
    assert [e.type for e in evs2] == [ADDED, MODIFIED]


def test_watch_selectors():
    s = ResourceStore()
    w = s.watch("Pod", field_selector={"spec.nodeName": "node-2"})
    s.create(pod("a", node="node-1"))
    s.create(pod("b", node="node-2"))
    ev = w.next(timeout=1.0)
    assert ev.object["metadata"]["name"] == "b"
    assert w.next(timeout=0.1) is None


def test_list_selectors():
    s = ResourceStore()
    s.create(pod("a", labels={"app": "x"}))
    s.create(pod("b", labels={"app": "y"}))
    items, _ = s.list("Pod", label_selector={"app": "x"})
    assert [i["metadata"]["name"] for i in items] == ["a"]
    items, _ = s.list("Pod", label_selector="app!=x")
    assert [i["metadata"]["name"] for i in items] == ["b"]
    items, _ = s.list("Pod", field_selector="spec.nodeName=node-1")
    assert len(items) == 2


def test_namespace_scoping():
    s = ResourceStore()
    s.create(pod("a", ns="ns1"))
    s.create(pod("a", ns="ns2"))
    items, _ = s.list("Pod", namespace="ns1")
    assert len(items) == 1
    assert s.get("Pod", "a", namespace="ns2")["metadata"]["namespace"] == "ns2"


def test_cluster_scoped_type():
    s = ResourceStore()
    n = s.create({"apiVersion": "v1", "kind": "Node", "metadata": {"name": "n1"}})
    assert "namespace" not in n["metadata"]
    assert s.get("Node", "n1")["metadata"]["name"] == "n1"


def test_register_dynamic_type_and_plural_lookup():
    s = ResourceStore()
    s.register_type(ResourceType("example.com/v1", "Widget", "widgets"))
    s.create({"apiVersion": "example.com/v1", "kind": "Widget", "metadata": {"name": "w"}})
    assert s.count("widgets") == 1
    assert s.get("widgets", "w")["kind"] == "Widget"


def test_event_recorder_aggregates():
    s = ResourceStore()
    p = s.create(pod("a"))
    rec = EventRecorder(s)
    rec.event(p, "Normal", "Created", "Pod created")
    rec.event(p, "Normal", "Created", "Pod created")
    events, _ = s.list("Event")
    assert len(events) == 1
    assert events[0]["count"] == 2
    rec.event(p, "Warning", "Failed", "boom")
    events, _ = s.list("Event")
    assert len(events) == 2


def test_field_index_matches_full_scan():
    """The spec.nodeName index returns exactly what a full scan does,
    through create/update/delete churn."""
    from kwok_tpu.cluster.store import ResourceStore

    store = ResourceStore()

    def pod(name, node):
        return {
            "apiVersion": "v1",
            "kind": "Pod",
            "metadata": {"name": name, "namespace": "default"},
            "spec": {"nodeName": node, "containers": [{"name": "c"}]},
            "status": {},
        }

    for i in range(30):
        store.create(pod(f"p{i}", f"n{i % 3}"))
    # move a pod between nodes via patch
    store.patch("Pod", "p0", {"spec": {"nodeName": "n9"}})
    store.delete("Pod", "p3")

    for node in ("n0", "n1", "n2", "n9", "missing"):
        indexed, _ = store.list("Pod", field_selector=f"spec.nodeName={node}")
        full = [
            o
            for o in store.list("Pod")[0]
            if o["spec"].get("nodeName") == node
        ]
        assert {o["metadata"]["name"] for o in indexed} == {
            o["metadata"]["name"] for o in full
        }, node

    # restore path keeps the index in sync too
    snap = store.dump_state()
    fresh = ResourceStore()
    fresh.restore_state(snap)
    indexed, _ = fresh.list("Pod", field_selector="spec.nodeName=n9")
    assert [o["metadata"]["name"] for o in indexed] == ["p0"]

    # non-equality / multi-requirement selectors fall back to scanning
    items, _ = store.list("Pod", field_selector="spec.nodeName!=n0")
    assert all(o["spec"]["nodeName"] != "n0" for o in items)


def test_index_empty_value_falls_back_to_scan():
    """spec.nodeName= (unscheduled pods) must match missing fields,
    which the index never holds — full-scan fallback required."""
    from kwok_tpu.cluster.store import ResourceStore

    store = ResourceStore()
    store.create({"apiVersion": "v1", "kind": "Pod",
                  "metadata": {"name": "scheduled", "namespace": "default"},
                  "spec": {"nodeName": "n1"}, "status": {}})
    store.create({"apiVersion": "v1", "kind": "Pod",
                  "metadata": {"name": "pending", "namespace": "default"},
                  "spec": {}, "status": {}})
    items, _ = store.list("Pod", field_selector="spec.nodeName=")
    assert [o["metadata"]["name"] for o in items] == ["pending"]


def test_index_on_non_string_field():
    """Indexed non-string scalars stringify like the field selector."""
    from kwok_tpu.cluster.store import ResourceStore

    store = ResourceStore()
    store.register_index("Node", "status.capacity.pods")
    store.create({"apiVersion": "v1", "kind": "Node",
                  "metadata": {"name": "n0"},
                  "spec": {}, "status": {"capacity": {"pods": 110}}})
    items, _ = store.list("Node", field_selector="status.capacity.pods=110")
    assert [o["metadata"]["name"] for o in items] == ["n0"]


# ------------------------------------------------------- status batch


def _mk_pod(name):
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": "default"},
            "spec": {"nodeName": "n"}, "status": {}}


def test_status_batch_with_only_the_excluded_watcher_still_commits_into_history():
    """With the only live watcher excluded, that watcher is handed
    nothing, and the commit is what it is with anybody watching: a new
    stored instance at the returned rv, its event in history, replayed
    to a watch that resumes from before it."""
    from kwok_tpu.cluster.store import ResourceStore

    store = ResourceStore()
    created = store.create(_mk_pod("p0"))
    rv0 = int(created["metadata"]["resourceVersion"])
    w = store.watch("Pod")
    st = store._state("Pod")
    inst_before = st.objects[("default", "p0")]
    hist_before = len(st.history)
    out = store.apply_status_batch(
        "Pod", [("default", "p0", {"phase": "Running"})], exclude=w
    )
    rv, obj = out[0]
    assert w.drain() == []  # nothing delivered to the excluded watcher
    assert obj is not inst_before and inst_before["status"] == {}
    assert st.objects[("default", "p0")] is obj
    assert obj["status"] == {"phase": "Running"}
    assert obj["metadata"]["resourceVersion"] == str(rv) and rv > rv0
    assert [ev.rv for ev in list(st.history)[hist_before:]] == [rv]
    evs = store.watch("Pod", since_rv=rv0).drain()
    assert [(ev.type, ev.rv, ev.object) for ev in evs] == [("MODIFIED", rv, obj)]
    # a GET still serves a fresh copy of the current state
    got = store.get("Pod", "p0", namespace="default")
    assert got["status"] == {"phase": "Running"} and got is not obj


def test_status_batch_delivers_to_every_watcher_but_the_excluded_one():
    """Any other live watcher is handed the event of a newly allocated
    object; the excluded one is not."""
    from kwok_tpu.cluster.store import ResourceStore

    store = ResourceStore()
    store.create(_mk_pod("p0"))
    mine = store.watch("Pod")
    other = store.watch("Pod")
    st = store._state("Pod")
    inst_before = st.objects[("default", "p0")]
    out = store.apply_status_batch(
        "Pod", [("default", "p0", {"phase": "Running"})], exclude=mine
    )
    rv, obj = out[0]
    assert obj is not inst_before  # copy-on-write commit
    evs = other.drain()
    assert len(evs) == 1 and evs[0].object["status"] == {"phase": "Running"}
    assert mine.drain() == []  # exclusion still honored


def test_status_batch_then_external_patch_keeps_semantics():
    """Interleaving status batches with ordinary patches stays
    consistent: the patch path is copy-on-write on top of the batch's
    instance and emits a real event."""
    from kwok_tpu.cluster.store import ResourceStore

    store = ResourceStore()
    store.create(_mk_pod("p0"))
    w = store.watch("Pod")
    store.apply_status_batch(
        "Pod", [("default", "p0", {"phase": "Running"})], exclude=w
    )
    out = store.patch("Pod", "p0", {"metadata": {"labels": {"a": "b"}}},
                      "merge", namespace="default")
    assert out["status"] == {"phase": "Running"}
    assert out["metadata"]["labels"] == {"a": "b"}
    evs = w.drain()
    assert len(evs) == 1 and evs[0].object["metadata"]["labels"] == {"a": "b"}


@pytest.mark.parametrize("branch", ["native", "python"])
def test_a_resume_from_before_a_status_batch_replays_all_of_it(branch, monkeypatch):
    """Every row either commit branch takes is an event in history at
    its own rv, whoever was or was not watching: a consumer that lists,
    misses two batches (one with the committer's watcher excluded and
    nobody else there) and resumes from its list's rv is handed each
    committed row once, in order, and neither the missing nor the
    refused one."""
    from kwok_tpu.cluster import store as store_mod

    if branch == "python":
        monkeypatch.setattr(store_mod, "_FAST", None)
    elif store_mod._FAST is None:
        pytest.skip("native fastdrain unavailable")
    store = store_mod.ResourceStore()
    for i in range(4):
        store.create(_mk_pod(f"p{i}"))
    _, listed_rv = store.list("Pod")
    at = {p["metadata"]["name"]: p["metadata"]["resourceVersion"] for p in store.list("Pod")[0]}
    mine = store.watch("Pod")
    first = store.apply_status_batch(
        "Pod",
        [("default", f"p{i}", {"phase": "Running"}, at[f"p{i}"]) for i in range(4)]
        + [("default", "gone", {"phase": "Running"}, "1")],
        exclude=mine,
    )
    assert first[4] is None and mine.drain() == []
    mine.stop()
    second = store.apply_status_batch(
        "Pod",
        [
            ("default", "p0", {"phase": "Failed"}, str(first[0][0])),
            ("default", "p1", {"phase": "Failed"}, at["p1"]),  # stale: refused
        ],
    )
    assert second[1] is False
    committed = [*first[:4], second[0]]
    assert [rv for rv, _obj in committed] == list(range(listed_rv + 1, listed_rv + 6))
    evs = store.watch("Pod", since_rv=listed_rv).drain()
    assert [(ev.type, ev.rv) for ev in evs] == [("MODIFIED", rv) for rv, _obj in committed]
    assert [ev.object for ev in evs] == [obj for _rv, obj in committed]
    assert [ev.object["status"]["phase"] for ev in evs] == ["Running"] * 4 + ["Failed"]
