"""Horizontally sharded ResourceStore (kwok_tpu/cluster/sharding).

Covers the tentpole contracts of the shard router: stable placement,
duck-typed routing, merged reads, ordered watch fan-in (per-object rv
monotonicity under concurrent multi-shard writers, resume-at-rv,
single-shard high-water eviction), the typed cross-shard transaction
rejection, per-shard WAL recovery with the union rv-continuity check,
the sharded fsck, snapshot split/restore, and KUBEDIRECT-style direct
dispatch over HTTP (unit + e2e).
"""

import json
import os
import subprocess
import sys
import threading

import pytest

from kwok_tpu.cluster.apiserver import APIServer
from kwok_tpu.cluster.client import ClusterClient
from kwok_tpu.cluster.sharding import (
    MergedWatcher,
    build_sharded_store,
    discover_shards,
    shard_of,
    shard_wal_path,
)
from kwok_tpu.cluster.sharding.dispatch import DirectClient, direct_dispatch
from kwok_tpu.cluster.sharding.recovery import recover_sharded
from kwok_tpu.cluster.sharding.router import RvSource, split_state
from kwok_tpu.cluster.store import (
    CrossShardTransaction,
    ResourceStore,
    TransactionAborted,
)
from kwok_tpu.cluster.wal import WriteAheadLog, fsck_sharded

N = 4


def two_namespaces(n=N):
    """Two namespaces guaranteed to live on different shards."""
    by_shard = {}
    i = 0
    while len(by_shard) < 2:
        by_shard.setdefault(shard_of(True, "Pod", f"ns-{i}", n), f"ns-{i}")
        i += 1
    return list(by_shard.values())[:2]


def pod(name, ns="default"):
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": name, "namespace": ns},
        "spec": {},
        "status": {},
    }


# ------------------------------------------------------------- placement


def test_placement_is_stable_and_namespace_affine():
    # placement must agree across processes/runs: pin one value
    assert shard_of(True, "Pod", "default", 1) == 0
    a = shard_of(True, "Pod", "team-a", 7)
    assert a == shard_of(True, "Pod", "team-a", 7)
    # every namespaced kind in one namespace lands on ONE shard
    assert shard_of(True, "ConfigMap", "team-a", 7) == a
    # a cluster-scoped kind lives whole on one shard
    n1 = shard_of(False, "Node", None, 7)
    assert n1 == shard_of(False, "Node", "ignored", 7)


def test_router_routes_and_merges():
    s = build_sharded_store(N)
    ns_a, ns_b = two_namespaces()
    for i in range(3):
        s.create(pod(f"a-{i}", ns_a))
        s.create(pod(f"b-{i}", ns_b))
    assert s.count("Pod") == 6
    items, rv = s.list("Pod")
    assert len(items) == 6 and rv > 0
    only_a, _ = s.list("Pod", namespace=ns_a)
    assert {p["metadata"]["name"] for p in only_a} == {"a-0", "a-1", "a-2"}
    got = s.get("Pod", "b-1", namespace=ns_b)
    assert got["metadata"]["namespace"] == ns_b
    s.delete("Pod", "a-0", namespace=ns_a)
    assert s.count("Pod") == 5
    # rvs come from ONE cluster-wide sequence: globally unique
    rvs = [int(p["metadata"]["resourceVersion"]) for p in items]
    assert len(set(rvs)) == len(rvs)


def test_rv_source_alloc_unalloc():
    src = RvSource()
    assert src.alloc() == 1
    assert src.alloc() == 2
    assert src.unalloc(2) and src.current() == 1
    src.alloc()
    # not the tip anymore: refuse
    src.advance_to(10)
    assert not src.unalloc(2)
    assert src.current() == 10


# ---------------------------------------------------------- watch fan-in


def test_fanin_per_object_rv_monotonic_under_concurrent_writers():
    s = build_sharded_store(N)
    ns_a, ns_b = two_namespaces()
    w = s.watch("Pod", since_rv=0)
    assert isinstance(w, MergedWatcher)
    stop = threading.Event()

    def writer(ns, prefix):
        for i in range(40):
            s.create(pod(f"{prefix}-{i}", ns))
            s.patch(
                "Pod",
                f"{prefix}-{i}",
                {"status": {"phase": "Running"}},
                namespace=ns,
                subresource="status",
            )

    ts = [
        threading.Thread(target=writer, args=(ns, p))
        for ns, p in ((ns_a, "wa"), (ns_b, "wb"))
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    stop.set()
    last = {}
    seen = 0
    while True:
        ev = w.next(timeout=0.2)
        if ev is None:
            break
        seen += 1
        m = ev.object["metadata"]
        key = (m["namespace"], m["name"])
        rv = int(m["resourceVersion"])
        assert key not in last or rv > last[key], (
            f"{key}: rv {rv} after {last[key]}"
        )
        last[key] = rv
    assert seen == 160  # 80 creates + 80 status patches
    w.stop()


def test_fanin_resume_at_rv_is_cluster_wide():
    s = build_sharded_store(N)
    ns_a, ns_b = two_namespaces()
    for i in range(5):
        s.create(pod(f"a-{i}", ns_a))
        s.create(pod(f"b-{i}", ns_b))
    mid = s.resource_version
    for i in range(5, 8):
        s.create(pod(f"a-{i}", ns_a))
        s.create(pod(f"b-{i}", ns_b))
    w = s.watch("Pod", since_rv=mid)
    names = set()
    while True:
        ev = w.next(timeout=0.2)
        if ev is None:
            break
        names.add(ev.object["metadata"]["name"])
    # exactly the post-mid writes replay, from BOTH shards
    assert names == {f"{p}-{i}" for p in ("a", "b") for i in range(5, 8)}
    w.stop()


def test_fanin_single_shard_eviction_evicts_whole_merge_then_resumes():
    s = build_sharded_store(N, watch_high_water=8)
    ns_a, ns_b = two_namespaces()
    s.create(pod("seed-a", ns_a))
    s.create(pod("seed-b", ns_b))
    w = s.watch("Pod", since_rv=0)
    # flood ONE shard past the high-water mark without consuming
    for i in range(20):
        s.create(pod(f"flood-{i}", ns_a))
    # draining hits the eviction: the merged stream ends as a WHOLE
    while w.next(timeout=0.05) is not None:
        pass
    assert w.evicted and w.stopped
    # ordinary reflector path: re-list, resume from the returned rv
    items, rv = s.list("Pod")
    assert len(items) == 22
    w2 = s.watch("Pod", since_rv=rv)
    s.create(pod("after", ns_b))
    ev = w2.next(timeout=2.0)
    assert ev is not None and ev.object["metadata"]["name"] == "after"
    w2.stop()


def test_merged_list_rv_not_pinned_by_idle_shard(monkeypatch):
    """One long-idle shard must not drag the merged list rv below a
    busy shard's history ring: a min-of-shards rv would make every
    list-then-watch resume raise Expired forever once the busy ring
    wraps (the re-list returns the same pinned rv), so the merged rv
    is floored at the pre-list global horizon instead."""
    monkeypatch.setattr(ResourceStore, "HISTORY", 32)
    s = build_sharded_store(N)
    ns_a, ns_b = two_namespaces()
    s.create(pod("lonely", ns_a))  # this shard now goes idle
    for i in range(100):  # wrap the busy shard's history ring
        s.create(pod(f"busy-{i}", ns_b))
    items, rv = s.list("Pod")
    assert len(items) == 101
    assert rv == s.resource_version
    # the reflector path stays live: watch from the list rv resumes
    w = s.watch("Pod", since_rv=rv)
    s.create(pod("after", ns_b))
    ev = w.next(timeout=2.0)
    assert ev is not None and ev.object["metadata"]["name"] == "after"
    w.stop()


def test_merged_rv_never_leaps_past_an_unwritten_shard():
    """A shard that has never allocated an rv pins the merged resume
    point at the pre-list horizon: its FIRST write can land mid-walk
    after its read, below the other shards' rvs — a resume above it
    (skipping zero-rv shards from the min) would make every
    list-then-watch cache silently miss that object until its next
    modification."""
    s = build_sharded_store(2)
    g0 = 7
    # unwritten shard (rv 0) + busy shard ahead of the horizon: resume
    # must stay at g0 so the empty shard's mid-walk first write replays
    assert s._merged_rv([0, g0 + 2], g0) == g0
    # all shards ahead: tighten to the smallest, not the horizon
    assert s._merged_rv([g0 + 1, g0 + 2], g0) == g0 + 1
    # idle shard below the horizon: clamp up (the Expired-livelock rule)
    assert s._merged_rv([3, g0 + 2], g0) == g0
    assert s._merged_rv([], g0) == g0


# ------------------------------------------------------------------ txn


def test_cross_shard_txn_typed_rejection():
    s = build_sharded_store(N)
    ns_a, ns_b = two_namespaces()
    with pytest.raises(CrossShardTransaction) as exc:
        s.transact(
            [
                {"verb": "create", "data": pod("x", ns_a)},
                {"verb": "create", "data": pod("y", ns_b)},
            ]
        )
    assert exc.value.reason == "CrossShard"
    # nothing committed on EITHER shard
    assert s.count("Pod") == 0
    # shard-affine batches stay atomic
    out = s.transact(
        [
            {"verb": "create", "data": pod("x", ns_a)},
            {"verb": "create", "data": pod("x2", ns_a)},
        ]
    )
    assert len(out) == 2 and s.count("Pod") == 2


def test_shard_lane_revalidates_ownership():
    s = build_sharded_store(N)
    ns_a, ns_b = two_namespaces()
    owner = s.shard_for("Pod", ns_a)
    other = s.shard_for("Pod", ns_b)
    assert owner != other
    # bulk: misrouted op gets a typed per-op error, routed op lands
    res = s.shard_bulk(
        other,
        [
            {"verb": "create", "data": pod("mis", ns_a)},
            {"verb": "create", "data": pod("ok", ns_b)},
        ],
    )
    assert res[0]["status"] == "error" and res[0]["reason"] == "Misrouted"
    assert res[1]["object"]["metadata"]["name"] == "ok"
    # txn: ownership violation refuses the whole batch
    with pytest.raises(CrossShardTransaction):
        s.shard_transact(
            other, [{"verb": "create", "data": pod("mis2", ns_a)}]
        )
    assert s.count("Pod") == 1


def test_bulk_splits_per_shard_and_preserves_op_order():
    s = build_sharded_store(N)
    ns_a, ns_b = two_namespaces()
    ops = []
    for i in range(6):
        ops.append(
            {"verb": "create", "data": pod(f"p-{i}", ns_a if i % 2 else ns_b)}
        )
    res = s.bulk(ops)
    assert [r["object"]["metadata"]["name"] for r in res] == [
        f"p-{i}" for i in range(6)
    ]


def test_direct_client_forwards_attribute_writes():
    """run_elected assigns `client.fence_provider = elector.fence`
    AFTER the daemon composed direct dispatch — the wrapper must
    forward attribute writes to the wrapped client, or every mutation
    silently loses the leader fence (split-brain writes no longer
    409-fenced on sharded clusters)."""

    class Stub:
        pass

    dc = DirectClient(Stub(), 2)
    marker = object()
    dc.fence_provider = marker
    assert dc._client.fence_provider is marker
    assert dc.fence_provider is marker


def test_list_page_resume_rv_not_pushed_past_mid_walk_write():
    """list_page must report read-time shard rvs like list(): writes
    landing on an already-paged shard mid-walk would otherwise push
    the resume point past themselves, and the follow-up watch would
    silently skip them."""
    s = build_sharded_store(2)
    by_shard = {
        shard_of(True, "Pod", ns, 2): ns for ns in two_namespaces(2)
    }
    ns0, ns1 = by_shard[0], by_shard[1]
    s.create(pod("a0", ns0))
    s.create(pod("b0", ns1))
    shard1 = s._shards[1]
    real = shard1._cut_pairs
    injected = {}

    def racing(kind):
        if not injected:
            # shard 0 was already cut; shard 1's own write drags the
            # at-return rvs past the shard-0 straggler
            injected["mid"] = s.create(pod("mid", ns0))
            s.create(pod("late", ns1))
        return real(kind)

    shard1._cut_pairs = racing
    try:
        items, rv, nxt = s.list_page("Pod")
    finally:
        shard1._cut_pairs = real
    mid_rv = int(injected["mid"]["metadata"]["resourceVersion"])
    assert nxt is None
    assert rv < mid_rv
    w = s.watch("Pod", since_rv=rv)
    names = set()
    while True:
        ev = w.next(timeout=1.0)
        if ev is None:
            break
        names.add(ev.object["metadata"]["name"])
    w.stop()
    assert "mid" in names


# ------------------------------------------------------- snapshot/restore


def test_split_state_and_restore_roundtrip():
    s = build_sharded_store(N)
    ns_a, ns_b = two_namespaces()
    for i in range(4):
        s.create(pod(f"a-{i}", ns_a))
        s.create(pod(f"b-{i}", ns_b))
    state = s.dump_state()
    slices = split_state(state, N)
    assert sum(len(sl["objects"]) for sl in slices) == len(state["objects"])
    for i, sl in enumerate(slices):
        for obj in sl["objects"]:
            ns = (obj.get("metadata") or {}).get("namespace")
            assert shard_of(True, obj["kind"], ns, N) == i
    # restore into a DIFFERENT shard count: placement re-derives
    s2 = build_sharded_store(2)
    s2.restore_state(state)
    assert s2.count("Pod") == 8
    assert {p["metadata"]["name"] for p in s2.list("Pod")[0]} == {
        p["metadata"]["name"] for p in s.list("Pod")[0]
    }


# ----------------------------------------------------------- WAL recovery


def test_recover_sharded_union_continuity(tmp_path):
    paths = [str(tmp_path / f"wal-{i}.jsonl") for i in range(2)]
    src = RvSource()
    shards = [
        ResourceStore(rv_source=src, uid_start=i, uid_step=2)
        for i in range(2)
    ]
    wals = [WriteAheadLog(p, fsync="off") for p in paths]
    for s, w in zip(shards, wals):
        s.attach_wal(w)
    ns_a, ns_b = two_namespaces(2)
    for i in range(6):
        shards[shard_of(True, "Pod", ns_a, 2)].create(pod(f"a-{i}", ns_a))
        shards[shard_of(True, "Pod", ns_b, 2)].create(pod(f"b-{i}", ns_b))
    live_rv = src.current()
    for w in wals:
        w.close()
    out = recover_sharded(paths)
    store, rep = out["store"], out["report"]
    # each shard's log is a sparse slice; the UNION is contiguous
    assert rep.missing_rvs == []
    assert rep.recovered_rv == live_rv
    assert store.count("Pod") == 12
    assert store.resource_version == live_rv
    # uid striding survives recovery: fresh creates stay collision-free
    store.create(pod("post-a", ns_a))
    store.create(pod("post-b", ns_b))
    uids = [
        (p["metadata"] or {}).get("uid") for p in store.list("Pod")[0]
    ]
    assert len(set(uids)) == 14


def test_fsck_sharded_detects_per_shard_damage(tmp_path):
    from kwok_tpu.chaos import disk_faults
    import random

    workdir = str(tmp_path)
    from kwok_tpu.snapshot.sharded import open_sharded_store

    opened = open_sharded_store(
        workdir, 2, namespace_finalizers=False, wal_fsync="off", pitr=False
    )
    store = opened["store"]
    ns_a, ns_b = two_namespaces(2)
    for i in range(8):
        store.create(pod(f"a-{i}", ns_a))
        store.create(pod(f"b-{i}", ns_b))
    for w in opened["wals"]:
        w.close()
    assert discover_shards(workdir) == 2
    clean = fsck_sharded(workdir)
    assert clean["ok"] and clean["shards"] == 2 and not clean["missing_rvs"]
    # CLI form: a workdir path triggers the sharded walk
    proc = subprocess.run(
        [sys.executable, "-m", "kwok_tpu.cluster.wal", "--fsck", workdir],
        capture_output=True,
        text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout)["shards"] == 2
    # damage ONE shard: the sharded verdict must fail
    disk_faults.bit_flip_line(
        shard_wal_path(workdir, 1), random.Random(7), exclude_last=True
    )
    bad = fsck_sharded(workdir)
    assert not bad["ok"]
    assert any(not rep["ok"] for rep in bad["per_shard"])
    proc = subprocess.run(
        [sys.executable, "-m", "kwok_tpu.cluster.wal", "--fsck", workdir],
        capture_output=True,
        text=True,
    )
    assert proc.returncode != 0


def test_open_sharded_store_boot_roundtrip(tmp_path):
    from kwok_tpu.snapshot.sharded import open_sharded_store

    workdir = str(tmp_path)
    opened = open_sharded_store(
        workdir, 3, namespace_finalizers=False, wal_fsync="off"
    )
    store = opened["store"]
    nss = {}
    i = 0
    while len(nss) < 3:
        nss.setdefault(shard_of(True, "Pod", f"ns-{i}", 3), f"ns-{i}")
        i += 1
    for s, ns in sorted(nss.items()):
        for j in range(4):
            store.create(pod(f"{ns}-p{j}", ns))
    live = store.dump_state()
    for w in opened["wals"]:
        w.close()
    # shard 0 keeps the single-store layout at the workdir root
    assert os.path.exists(os.path.join(workdir, "wal.jsonl"))
    assert os.path.isdir(os.path.join(workdir, "shards", "01"))
    reopened = open_sharded_store(
        workdir, 3, namespace_finalizers=False, wal_fsync="off"
    )
    try:
        assert reopened["report"].clean
        fresh = reopened["store"].dump_state()
        assert fresh == live
    finally:
        for w in reopened["wals"]:
            w.close()


def test_snapshot_only_sharded_boot_advances_rv_source(tmp_path):
    """DR shape: per-shard state.json at rv G with NO WAL segments (a
    snapshot-only backup copy).  The shared rv sequence must seed from
    the restored rv — recovered_rv alone is 0 here, and a sequence
    left at 0 would hand the first post-boot write an rv the restored
    objects already hold."""
    from kwok_tpu.cluster.sharding.layout import shard_dir, shard_state_path
    from kwok_tpu.cluster.wal import write_state_file
    from kwok_tpu.snapshot.sharded import open_sharded_store

    donor = build_sharded_store(N)
    ns_a, ns_b = two_namespaces()
    for i in range(4):
        donor.create(pod(f"a-{i}", ns_a))
        donor.create(pod(f"b-{i}", ns_b))
    g = donor.resource_version
    workdir = str(tmp_path)
    for i, piece in enumerate(split_state(donor.dump_state(), N)):
        os.makedirs(shard_dir(workdir, i), exist_ok=True)
        write_state_file(shard_state_path(workdir, i), piece)
    opened = open_sharded_store(
        workdir, N, namespace_finalizers=False, wal_fsync="off", pitr=False
    )
    store = opened["store"]
    try:
        assert store.count("Pod") == 8
        store.create(pod("post-boot", ns_a))
        created = store.get("Pod", "post-boot", namespace=ns_a)
        assert int(created["metadata"]["resourceVersion"]) > g
        items, _ = store.list("Pod")
        rvs = [int(p["metadata"]["resourceVersion"]) for p in items]
        assert len(set(rvs)) == len(rvs)
    finally:
        for w in opened["wals"]:
            w.close()


def test_sharded_pitr_archive_and_build_state(tmp_path):
    """kwokctl snapshot save --pitr / restore --to-rv on a sharded
    workdir: the merged snapshot splits into per-shard archives, and
    build_sharded_state rebuilds any retained rv over the union."""
    from kwok_tpu.snapshot.sharded import (
        archive_sharded_snapshot,
        build_sharded_state,
        open_sharded_store,
    )

    workdir = str(tmp_path)
    opened = open_sharded_store(
        workdir, 2, namespace_finalizers=False, wal_fsync="off"
    )
    store = opened["store"]
    ns_a, ns_b = two_namespaces(2)
    for i in range(4):
        store.create(pod(f"a-{i}", ns_a))
        store.create(pod(f"b-{i}", ns_b))
    cut_rv = store.resource_version
    cut = store.dump_state()
    names = archive_sharded_snapshot(workdir, cut)
    assert len(names) == 2
    for i in range(4, 7):
        store.create(pod(f"a-{i}", ns_a))
        store.create(pod(f"b-{i}", ns_b))
    mid_rv = store.resource_version
    mid = store.dump_state()
    for w in opened["wals"]:
        w.close()
    # rebuild at the archived cut AND at a later live-WAL rv
    for rv, want in ((cut_rv, cut), (mid_rv, mid)):
        state, info = build_sharded_state(workdir, rv)
        assert info["shards"] == 2
        assert json.dumps(
            sorted(
                state["objects"],
                key=lambda o: (
                    o["metadata"]["namespace"],
                    o["metadata"]["name"],
                ),
            ),
            sort_keys=True,
        ) == json.dumps(
            sorted(
                want["objects"],
                key=lambda o: (
                    o["metadata"]["namespace"],
                    o["metadata"]["name"],
                ),
            ),
            sort_keys=True,
        )


def test_sharded_build_state_refuses_pruned_shard_history(tmp_path):
    """One shard's base snapshot + early WAL pruned out from under the
    rebuild (the live save loop's prune racing a restore): the union
    retention check must refuse loudly, not silently merge a sparse
    slice — a max-over-bases floor would mask the damaged shard's
    missing history below the healthy shard's base."""
    import glob as _glob

    from kwok_tpu.cluster.sharding.layout import shard_pitr_dir
    from kwok_tpu.cluster.wal import SnapshotCorruption
    from kwok_tpu.snapshot.sharded import (
        archive_sharded_snapshot,
        build_sharded_state,
        open_sharded_store,
    )

    workdir = str(tmp_path)
    opened = open_sharded_store(
        workdir, 2, namespace_finalizers=False, wal_fsync="off"
    )
    store = opened["store"]
    ns_a, ns_b = two_namespaces(2)
    for i in range(4):
        store.create(pod(f"a-{i}", ns_a))
        store.create(pod(f"b-{i}", ns_b))
    cut_rv = store.resource_version
    archive_sharded_snapshot(workdir, store.dump_state())
    for i in range(4, 6):
        store.create(pod(f"a-{i}", ns_a))
        store.create(pod(f"b-{i}", ns_b))
    final_rv = store.resource_version
    for w in opened["wals"]:
        w.close()
    # damage ns_a's shard the way the prune race does: snapshot gone,
    # history below the cut compacted away, only the tail retained
    victim = shard_of(True, "Pod", ns_a, 2)
    for snap in _glob.glob(
        os.path.join(shard_pitr_dir(workdir, victim), "snap-*.json")
    ):
        os.unlink(snap)
    wal_file = shard_wal_path(workdir, victim)
    kept = []
    with open(wal_file) as f:
        for line in f:
            payload = line.split(None, 2)
            if len(payload) == 3:
                try:
                    rv = int(json.loads(payload[2]).get("rv", 0))
                except ValueError:
                    rv = 0
                if rv > cut_rv:
                    kept.append(line)
    with open(wal_file, "w") as f:
        f.writelines(kept)
    with pytest.raises(SnapshotCorruption):
        build_sharded_state(workdir, final_rv)
    with pytest.raises(SnapshotCorruption):
        build_sharded_state(workdir, cut_rv)


def test_open_sharded_store_refuses_shard_count_mismatch(tmp_path):
    """The shard count is fixed at creation (placement is a pure hash
    of N): booting an existing workdir under a different N must refuse
    loudly — a silent boot mis-routes every object (strands whole
    shards from routed reads, duplicates same-name creates)."""
    from kwok_tpu.snapshot.sharded import open_sharded_store

    workdir = str(tmp_path / "two")
    os.makedirs(workdir)
    opened = open_sharded_store(
        workdir, 2, namespace_finalizers=False, wal_fsync="off"
    )
    ns_a, _ = two_namespaces(2)
    opened["store"].create(pod("a", ns_a))
    for w in opened["wals"]:
        w.close()
    for wrong in (3, 1):
        with pytest.raises(ValueError):
            open_sharded_store(
                workdir, wrong, namespace_finalizers=False, wal_fsync="off"
            )
    # a populated single-store workdir cannot be resharded in place
    single = str(tmp_path / "one")
    os.makedirs(single)
    opened1 = open_sharded_store(
        single, 1, namespace_finalizers=False, wal_fsync="off"
    )
    opened1["store"].create(pod("a", "default"))
    for w in opened1["wals"]:
        w.close()
    with pytest.raises(ValueError):
        open_sharded_store(
            single, 4, namespace_finalizers=False, wal_fsync="off"
        )
    # same count reopens fine
    reopened = open_sharded_store(
        workdir, 2, namespace_finalizers=False, wal_fsync="off"
    )
    assert reopened["store"].count("Pod") == 1
    for w in reopened["wals"]:
        w.close()


def test_sharded_dump_state_is_rv_consistent_under_writers():
    """The merged dump's label must be an exact cut: every acked write
    with rv <= label appears in the objects (a label read after the
    shard walk would claim coverage of a write that committed on an
    already-dumped shard — once archived and pruned per shard, that
    write would be silently unrebuildable)."""
    s = build_sharded_store(2)
    ns_a, ns_b = two_namespaces(2)
    acked: list = []
    stop = threading.Event()

    def writer(ns):
        i = 0
        while not stop.is_set() and i < 500:
            obj = s.create(pod(f"w-{ns}-{i}", ns))
            acked.append(
                (
                    obj["metadata"]["name"],
                    int(obj["metadata"]["resourceVersion"]),
                )
            )
            i += 1

    threads = [
        threading.Thread(target=writer, args=(ns,)) for ns in (ns_a, ns_b)
    ]
    for t in threads:
        t.start()
    try:
        for _ in range(15):
            d = s.dump_state()
            label = int(d["resourceVersion"])
            names = {o["metadata"]["name"] for o in d["objects"]}
            for name, rv in list(acked):
                if rv <= label:
                    assert name in names, (name, rv, label)
    finally:
        stop.set()
        for t in threads:
            t.join()


def test_one_shard_layout_is_byte_compatible(tmp_path):
    """--store-shards 1 must produce exactly the single-store file
    set, readable by a plain ResourceStore boot."""
    from kwok_tpu.snapshot.sharded import open_sharded_store

    workdir = str(tmp_path)
    opened = open_sharded_store(
        workdir, 1, namespace_finalizers=False, wal_fsync="off", pitr=False
    )
    store = opened["store"]
    store.create(pod("solo"))
    live = store.shard_lane(0).dump_state()
    for w in opened["wals"]:
        w.close()
    assert not os.path.exists(os.path.join(workdir, "shards"))
    plain = ResourceStore()
    rep = plain.recover_wal(os.path.join(workdir, "wal.jsonl"))
    assert rep.clean
    assert plain.dump_state() == live


# -------------------------------------------------------------------- e2e


@pytest.fixture()
def sharded_cluster():
    store = build_sharded_store(N)
    with APIServer(store) as srv:
        yield store, ClusterClient(srv.url)


def test_e2e_topology_and_watch_fanin(sharded_cluster):
    store, client = sharded_cluster
    topo = client._request("GET", "/shards")
    assert topo == {"shards": N, "algo": "crc32-ns-kind"}
    ns_a, ns_b = two_namespaces()
    w = client.watch("Pod", since_rv=0)
    for i in range(4):
        client.create(pod(f"a-{i}", ns_a))
        client.create(pod(f"b-{i}", ns_b))
    seen = {}
    for _ in range(200):
        ev = w.next(timeout=0.1)
        if ev is None:
            if len(seen) == 8:
                break
            continue
        m = (ev.object or {}).get("metadata") or {}
        key = (m.get("namespace"), m.get("name"))
        rv = int(m.get("resourceVersion"))
        assert key not in seen or rv > seen[key]
        seen[key] = rv
    assert len(seen) == 8
    w.stop()


def test_e2e_cross_shard_txn_rejected_with_409(sharded_cluster):
    _store, client = sharded_cluster
    ns_a, ns_b = two_namespaces()
    with pytest.raises(CrossShardTransaction):
        client.transact(
            [
                {"verb": "create", "data": pod("x", ns_a)},
                {"verb": "create", "data": pod("y", ns_b)},
            ]
        )
    items, _ = client.list("Pod")
    assert items == []


def test_e2e_direct_dispatch(sharded_cluster):
    store, client = sharded_cluster
    direct = direct_dispatch(client)
    assert isinstance(direct, DirectClient)
    ns_a, ns_b = two_namespaces()
    # bulk splits across the per-shard lanes; results keep op order
    res = direct.bulk(
        [
            {"verb": "create", "data": pod(f"p-{i}", ns_a if i % 2 else ns_b)}
            for i in range(6)
        ]
    )
    assert [r["object"]["metadata"]["name"] for r in res] == [
        f"p-{i}" for i in range(6)
    ]
    assert store.count("Pod") == 6
    # shard-affine txn rides the per-shard txn lane
    out = direct.transact(
        [{"verb": "create", "data": pod("t-0", ns_a)}]
    )
    assert out[0]["metadata"]["name"] == "t-0"
    # cross-shard txn refused client-side, before any bytes move
    with pytest.raises(CrossShardTransaction):
        direct.transact(
            [
                {"verb": "create", "data": pod("t-a", ns_a)},
                {"verb": "create", "data": pod("t-b", ns_b)},
            ]
        )
    assert store.count("Pod") == 7
    # reads and single-object verbs pass through unchanged
    assert len(direct.list("Pod")[0]) == 7


def test_e2e_direct_dispatch_noop_on_single_store():
    store = ResourceStore()
    with APIServer(store) as srv:
        client = ClusterClient(srv.url)
        assert direct_dispatch(client) is client
