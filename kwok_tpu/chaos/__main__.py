"""Offline chaos driver: ``python -m kwok_tpu.chaos``.

Three modes over one seeded profile
(:mod:`kwok_tpu.chaos.plan`; reference chaos-as-data precedent
``kwok_tpu/stages/pod-chaos.yaml:1``):

- ``--print-schedule``  render the deterministic fault schedule as
  JSON (what WILL happen for this seed) without touching anything.
- ``--cluster NAME``    drive the profile's process faults against a
  live kwokctl cluster; ``--supervise`` also runs the component
  supervisor so kills recover.  HTTP faults live inside the apiserver
  daemon — create the cluster with ``--chaos-profile`` to enable them.
- ``--smoke``           self-contained durability check (seconds, no
  subprocesses): drive writes through an apiserver facade under
  injected 503s/resets/latency with the retrying client, then replay
  snapshot+WAL into a fresh store and assert byte-identical state —
  zero lost acknowledged writes.  tools/check.sh runs this on every
  check.
- ``--overload-smoke``  self-contained graceful-degradation check: a
  seeded best-effort flood (the plan's ``overload`` fault kind) against
  an apiserver running APF flow control
  (``kwok_tpu.cluster.flowcontrol``) while a system-priority canary
  keeps writing.  Asserts every canary write acks with bounded
  latency, the flood is shed with well-formed 429+Retry-After (zero
  connection errors), and no system-level request was rejected.
  tools/check.sh runs this on every check too.
- ``--corruption-smoke``  self-contained storage-integrity check:
  seeded disk faults (bit-flip, truncate, torn multi-record write,
  fsync-boundary crash, snapshot corruption —
  :mod:`kwok_tpu.chaos.disk_faults`) against the checksummed WAL and
  snapshot files.  Asserts every fault is *detected* (never silently
  absorbed), recovery is bounded and honest (recovered state +
  reported-lost set account for every acked write), and
  point-in-time recovery rebuilds a mid-run capture byte-identically.
  tools/check.sh runs this on every check too.
- ``--exhaustion-smoke``  self-contained resource-exhaustion check:
  seeded disk-full/fsync-error windows (:mod:`kwok_tpu.chaos.fs_pressure`)
  against a live apiserver+WAL.  Asserts degraded read-only mode
  (mutations 503+Retry-After with reason StorageDegraded; reads,
  watches and lease renewals stay live via the emergency reserve),
  /healthz-alive with zero supervisor restarts, re-arm on space
  return, and — after a crash — that durable ∪ visibly-rejected
  accounts for every acked write.  tools/check.sh runs this too.
- ``--failover-smoke``  self-contained HA check: three leader electors
  (cluster/election.py) on one APF-armed apiserver.  Asserts a single
  leader at a time, bounded takeover (2x leaseDuration after a silent
  kill, ~one renew interval after a graceful release), and that a
  stale leadership generation's writes are fenced with 409 while the
  live leader's pass.  tools/check.sh runs this on every check too.
- ``--dst``             deterministic simulation testing
  (kwok_tpu.dst): run the whole control plane in one process on a
  virtual clock, ``--seeds N`` seeded fault interleavings, Kivi-style
  invariant checks over every run's trace.  Any violating seed replays
  exactly (same seed ⇒ byte-identical trace digest).  Exits nonzero on
  any violation.  ``--dst-bug ungated-writer`` injects the test-only
  regression the acceptance gate uses to prove violations are caught;
  ``--dst-bug partial-gang`` un-atomics the gang scheduler's bind lane
  so the gang-atomicity invariant can prove it catches partial gangs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

from kwok_tpu.chaos.http_faults import HttpFaultInjector
from kwok_tpu.chaos.plan import FaultPlan, HttpFaultSpec, load_profile


def run_smoke(seed: int = 42, pods: int = 40, duration: float = 30.0) -> dict:
    """In-process chaos smoke; returns the report dict (raises on any
    lost write or non-convergence)."""
    from kwok_tpu.cluster.apiserver import APIServer
    from kwok_tpu.cluster.client import ApiUnavailable, ClusterClient, RetryPolicy
    from kwok_tpu.cluster.store import Conflict, NotFound, ResourceStore
    from kwok_tpu.cluster.wal import WriteAheadLog
    from kwok_tpu.utils.backoff import Backoff

    def must(fn, *a, **kw):
        """Drive a mutation to an acknowledged outcome, the way the
        controllers do: ApiUnavailable means the op MAY have applied
        (e.g. a chaos reset ate the response) — replay it, treating
        already-applied answers as success.  Conflict, not
        AlreadyExists: the REST client maps every 409 to the base
        Conflict, and nothing here carries rv preconditions, so a 409
        on replay can only mean the first attempt landed."""
        for _ in range(50):
            try:
                return fn(*a, **kw)
            except ApiUnavailable:
                continue
            except Conflict:
                return None  # first attempt applied; the ack was eaten
            except NotFound:
                return None  # delete applied; the ack was eaten
        raise SystemExit("chaos smoke FAILED: mutation never converged")

    plan = FaultPlan(
        seed=seed,
        duration=duration,
        http=HttpFaultSpec(
            latency_p=0.10,
            latency_s=0.01,
            reject_p=0.15,
            reject_status=503,
            retry_after=0.05,
            reset_p=0.08,
        ),
    )
    inj = HttpFaultInjector(plan)
    t_start = time.monotonic()
    with tempfile.TemporaryDirectory() as tmp:
        wal_path = os.path.join(tmp, "wal.jsonl")
        state_path = os.path.join(tmp, "state.json")
        store = ResourceStore()
        store.attach_wal(WriteAheadLog(wal_path, fsync="off"))
        with APIServer(store, fault_injector=inj) as srv:
            client = ClusterClient(
                srv.url,
                retry=RetryPolicy(
                    seed=seed,
                    max_attempts=10,
                    budget_s=30.0,
                    backoff=Backoff(duration=0.02, cap=0.5),
                ),
                client_id="chaos-smoke",
            )
            # every acked write below crossed the faulty boundary
            for i in range(pods):
                must(
                    client.create,
                    {
                        "apiVersion": "v1",
                        "kind": "Pod",
                        "metadata": {"name": f"smoke-{i}", "namespace": "default"},
                        "spec": {"nodeName": f"node-{i % 4}"},
                        "status": {},
                    },
                )
            for i in range(pods):
                must(
                    client.patch,
                    "Pod",
                    f"smoke-{i}",
                    {"status": {"phase": "Running"}},
                    "merge",
                    subresource="status",
                )
            for i in range(0, pods, 5):
                must(client.delete, "Pod", f"smoke-{i}")
            live = store.dump_state()
        # crash: throw the store away, recover snapshot-less from WAL
        recovered = ResourceStore()
        replayed = recovered.replay_wal(wal_path)
        t_recovered = time.monotonic()
        if recovered.dump_state() != live:
            raise SystemExit("chaos smoke FAILED: WAL replay diverged from live state")
        # and the snapshot+compact path: save, recover from both halves
        store.save_file(state_path)
        recovered2 = ResourceStore()
        recovered2.load_file(state_path)
        recovered2.replay_wal(wal_path)
        if recovered2.dump_state() != live:
            raise SystemExit(
                "chaos smoke FAILED: snapshot+WAL recovery diverged from live state"
            )
    expect_pods = pods - len(range(0, pods, 5))
    if recovered.count("Pod") != expect_pods:
        raise SystemExit(
            f"chaos smoke FAILED: {recovered.count('Pod')} pods after recovery, "
            f"want {expect_pods}"
        )
    return {
        "seed": seed,
        "acked_writes": pods * 2 + len(range(0, pods, 5)),
        "replayed_records": replayed,
        "faults": inj.snapshot(),
        "recovery_s": round(t_recovered - t_start, 3),
        "lost_writes": 0,
    }


def run_corruption_smoke(seed: int = 42, pods: int = 24) -> dict:
    """In-process storage-integrity smoke: every seeded disk fault —
    bit-flip, truncate, torn multi-record write, fsync-boundary crash,
    snapshot corruption — must be *detected* (never silently absorbed)
    and recovery must be bounded and honest: the recovered state plus
    the reported-lost set together account for every acked write.
    Also proves PITR: ``build_state(to_rv)`` reproduces a mid-run live
    capture byte-identically.  Raises on any silent loss."""
    import random
    import shutil

    from kwok_tpu.chaos import disk_faults
    from kwok_tpu.cluster.store import ResourceStore
    from kwok_tpu.cluster.wal import (
        WriteAheadLog,
        fsck,
        segment_files,
        write_state_file,
    )
    from kwok_tpu.snapshot.pitr import PitrArchive, boot_recover

    rng = random.Random(seed)
    t_start = time.monotonic()

    def fail(msg):
        raise SystemExit(f"corruption smoke FAILED: {msg}")

    def accounted(acked, boot):
        """Split acked rvs into (reported_lost, silent_lost) via the
        RecoveryReport's own honesty classification — the SAME
        predicate the DST recovery-honesty invariant audits."""
        rep = boot["recovery"]
        if rep is None:
            return [], sorted(acked)
        return rep.account(acked)

    results = {}
    with tempfile.TemporaryDirectory() as tmp:
        wal_p = os.path.join(tmp, "wal.jsonl")
        state_p = os.path.join(tmp, "state.json")
        pitr_root = os.path.join(tmp, "pitr")
        store = ResourceStore()
        store.attach_wal(
            WriteAheadLog(
                wal_p, fsync="off", segment_bytes=1500, archive_dir=pitr_root
            )
        )
        archive = PitrArchive(pitr_root)
        acked: set = set()

        def track(fn, *a, **kw):
            rv0 = store.resource_version
            out = fn(*a, **kw)
            acked.update(range(rv0 + 1, store.resource_version + 1))
            return out

        def daemon_save():
            state = store.dump_state(copy=False)
            write_state_file(state_p, state)
            archive.add_snapshot(state)
            store.compact_wal(int(state["resourceVersion"]))

        pod = lambda n: {  # noqa: E731
            "apiVersion": "v1",
            "kind": "Pod",
            "metadata": {"name": n, "namespace": "default"},
            "spec": {"nodeName": f"node-{rng.randrange(4)}"},
            "status": {},
        }
        cut = None
        for i in range(pods):
            track(store.create, pod(f"smoke-{i}"))
            if i == pods // 3:
                daemon_save()
            if i == pods // 2:
                track(
                    store.bulk,
                    [
                        {
                            "verb": "patch",
                            "kind": "Pod",
                            "name": f"smoke-{j}",
                            "data": {"status": {"phase": "Running"}},
                            "subresource": "status",
                        }
                        for j in range(i)
                    ],
                )
                cut = (store.resource_version, store.dump_state())
        for i in range(0, pods, 5):
            track(store.delete, "Pod", f"smoke-{i}")
        track(
            store.apply_status_batch,
            "Pod",
            [
                ("default", f"smoke-{i}", {"phase": "Succeeded"})
                for i in range(1, pods, 7)
            ],
        )
        # the delete batch's record type under the same faults
        track(
            store.apply_delete_batch,
            "Pod",
            [
                (
                    "default",
                    f"smoke-{i}",
                    store.get("Pod", f"smoke-{i}")["metadata"]["resourceVersion"],
                )
                for i in range(2, pods, 11)
                if i % 5
            ],
        )
        live = store.dump_state()

        # ---- point-in-time recovery: byte-identical rebuild ---------
        built, info = archive.build_state(cut[0], live_wal=wal_p)
        if json.dumps(built, sort_keys=True) != json.dumps(
            cut[1], sort_keys=True
        ):
            fail(
                f"PITR rebuild at rv {cut[0]} diverged from the live "
                f"capture (base rv {info['base_rv']})"
            )
        results["pitr"] = {
            "to_rv": cut[0],
            "base_rv": info["base_rv"],
            "byte_identical": True,
        }

        # pristine fsck must pass
        clean = fsck(wal_p, snapshot=state_p, archive=pitr_root)
        if not clean["ok"]:
            fail(f"fsck flagged a pristine log: {clean}")

        def clone(name):
            d = os.path.join(tmp, name)
            os.makedirs(d)
            for fp in segment_files(wal_p):
                shutil.copy(fp, os.path.join(d, os.path.basename(fp)))
            shutil.copy(state_p, os.path.join(d, "state.json"))
            shutil.copytree(pitr_root, os.path.join(d, "pitr"))
            return (
                os.path.join(d, "wal.jsonl"),
                os.path.join(d, "state.json"),
                os.path.join(d, "pitr"),
            )

        def recover(paths):
            t0 = time.monotonic()
            fresh = ResourceStore()
            boot = boot_recover(fresh, paths[1], paths[0], pitr_root=paths[2])
            return fresh, boot, time.monotonic() - t0

        # ---- bit-flip: mid-log corruption must be DETECTED ----------
        paths = clone("bitflip")
        target = rng.choice(
            [f for f in segment_files(paths[0]) if os.path.getsize(f) > 0]
        )
        flip = disk_faults.bit_flip_line(target, rng, exclude_last=True)
        fresh, boot, dt = recover(paths)
        rep = boot["recovery"]
        if not rep.corruptions and not rep.torn_tail:
            fail(f"bit-flip at {target}:{flip} was silently absorbed")
        bad = fsck(paths[0], snapshot=paths[1], archive=paths[2])
        if bad["ok"]:
            fail("fsck passed a bit-flipped log")
        reported, silent = accounted(acked, boot)
        if silent:
            fail(f"bit-flip: acked rvs {silent[:10]} lost WITHOUT report")
        results["bit-flip"] = {
            "detected": True,
            "acked_lost_reported": len(reported),
            "silent_lost": 0,
            "recovery_s": round(dt, 3),
        }

        # ---- truncate: lost tail cut mid-record ---------------------
        paths = clone("truncate")
        disk_faults.truncate_mid_record(paths[0], rng)
        fresh, boot, dt = recover(paths)
        rep = boot["recovery"]
        if not rep.torn_tail and not rep.corruptions:
            fail("truncation was silently absorbed")
        if rep.tail_after_rv is None:
            fail("truncation did not bound the possible tail loss")
        reported, silent = accounted(acked, boot)
        if silent:
            fail(f"truncate: acked rvs {silent[:10]} lost WITHOUT report")
        results["truncate"] = {
            "detected": True,
            "acked_lost_reported": len(reported),
            "silent_lost": 0,
            "recovery_s": round(dt, 3),
        }

        # ---- snapshot corruption: fall back + replay, zero loss -----
        paths = clone("snapcorrupt")
        disk_faults.bit_flip(paths[1], rng, 0.2, 0.8)
        fresh, boot, dt = recover(paths)
        if not boot["fell_back"]:
            fail("corrupt snapshot was loaded without detection")
        if fresh.dump_state() != live:
            fail(
                "snapshot-fallback recovery diverged from live state "
                f"(fallback rv {boot['fallback_rv']})"
            )
        results["snapshot-corrupt"] = {
            "detected": True,
            "fallback_rv": boot["fallback_rv"],
            "silent_lost": 0,
            "recovery_s": round(dt, 3),
        }

    # ---- torn multi-record write (standalone scene) -----------------
    with tempfile.TemporaryDirectory() as tmp:
        wal_p = os.path.join(tmp, "wal.jsonl")
        s2 = ResourceStore()
        s2.attach_wal(WriteAheadLog(wal_p, fsync="off"))
        # one deferred batch -> one multi-record append_many write
        s2.bulk(
            [
                {
                    "verb": "create",
                    "data": {
                        "apiVersion": "v1",
                        "kind": "Pod",
                        "metadata": {
                            "name": f"torn-{i}",
                            "namespace": "default",
                        },
                        "spec": {},
                        "status": {},
                    },
                }
                for i in range(8)
            ]
        )
        offsets, size = disk_faults.line_offsets(wal_p)
        keep_lines = rng.randrange(2, len(offsets) - 1)
        cut_off = offsets[keep_lines] + rng.randrange(
            1, offsets[keep_lines + 1] - offsets[keep_lines] - 1
        )
        disk_faults.cut_at(wal_p, cut_off)
        t0 = time.monotonic()
        fresh = ResourceStore()
        rep = fresh.recover_wal(wal_p)
        dt = time.monotonic() - t0
        if not rep.torn_tail:
            fail("torn multi-record write was silently absorbed")
        if fresh.count("Pod") != keep_lines:
            fail(
                f"torn write: {fresh.count('Pod')} records survive, "
                f"want the batch prefix {keep_lines}"
            )
        results["torn-write"] = {
            "detected": True,
            "batch_prefix_kept": keep_lines,
            "silent_lost": 0,
            "recovery_s": round(dt, 3),
        }

    # ---- fsync-boundary crash (standalone scene) --------------------
    with tempfile.TemporaryDirectory() as tmp:
        wal_p = os.path.join(tmp, "wal.jsonl")
        s3 = ResourceStore()
        wal = WriteAheadLog(wal_p, fsync="off")
        s3.attach_wal(wal)
        for i in range(10):
            s3.create(
                {
                    "apiVersion": "v1",
                    "kind": "Pod",
                    "metadata": {"name": f"sync-{i}", "namespace": "default"},
                    "spec": {},
                    "status": {},
                }
            )
        wal.sync()
        synced_state = s3.dump_state()
        synced_size = os.path.getsize(wal_p)
        for i in range(10, 16):
            s3.create(
                {
                    "apiVersion": "v1",
                    "kind": "Pod",
                    "metadata": {"name": f"sync-{i}", "namespace": "default"},
                    "spec": {},
                    "status": {},
                }
            )
        wal.close()
        # machine crash: the unsynced tail vanishes, typically leaving
        # a partial frame behind
        offsets, size = disk_faults.line_offsets(wal_p)
        first_unsynced = next(o for o in offsets if o >= synced_size)
        disk_faults.cut_at(
            wal_p, first_unsynced + rng.randrange(1, 20)
        )
        t0 = time.monotonic()
        fresh = ResourceStore()
        rep = fresh.recover_wal(wal_p)
        dt = time.monotonic() - t0
        if fresh.dump_state() != synced_state:
            fail("fsync-boundary crash lost SYNCED data")
        if not rep.torn_tail:
            fail("fsync-boundary crash tail was silently absorbed")
        results["fsync-crash"] = {
            "detected": True,
            "synced_rv_preserved": rep.recovered_rv,
            "silent_lost": 0,
            "recovery_s": round(dt, 3),
        }

    # ---- sharded scene: one shard's damage, union-accounted ---------
    # (kwok_tpu/cluster/sharding): mid-log corruption on ONE shard's
    # WAL must fail the sharded fsck, recovery must detect it and
    # account every acked rv over the UNION of the shards (honest,
    # bounded to the damaged shard's slice), and the intact shard's
    # objects must all survive.
    from kwok_tpu.cluster.sharding import namespaces_covering_shards
    from kwok_tpu.cluster.wal import fsck_sharded
    from kwok_tpu.snapshot.sharded import open_sharded_store

    with tempfile.TemporaryDirectory() as tmp:
        opened = open_sharded_store(
            tmp, 2, namespace_finalizers=False, wal_fsync="off", pitr=False
        )
        sstore = opened["store"]
        ns_by_shard = namespaces_covering_shards(2)
        sacked: set = set()

        def strack(fn, *a, **kw):
            rv0 = sstore.resource_version
            out = fn(*a, **kw)
            sacked.update(range(rv0 + 1, sstore.resource_version + 1))
            return out

        for j in range(10):
            for s, ns in enumerate(ns_by_shard):
                p = pod(f"sh-{j}")
                p["metadata"]["namespace"] = ns
                strack(sstore.create, p)
        shard0_names = {
            (o.get("metadata") or {}).get("name")
            for o in sstore.list("Pod", namespace=ns_by_shard[0])[0]
        }
        for w in opened["wals"]:
            w.close()

        clean = fsck_sharded(tmp)
        if not clean["ok"] or clean["shards"] != 2:
            fail(f"sharded fsck flagged a pristine workdir: {clean}")

        from kwok_tpu.cluster.sharding.layout import shard_wal_path

        disk_faults.bit_flip_line(
            shard_wal_path(tmp, 1), rng, exclude_last=True
        )
        bad = fsck_sharded(tmp)
        if bad["ok"]:
            fail("sharded fsck passed a workdir with one damaged shard")

        t0 = time.monotonic()
        reopened = open_sharded_store(
            tmp, 2, namespace_finalizers=False, wal_fsync="off", pitr=False
        )
        dt = time.monotonic() - t0
        rep = reopened["report"]
        if not rep.corruptions and not rep.torn_tail:
            fail("one-shard bit-flip was silently absorbed by recovery")
        reported, silent = rep.account(sacked)
        if silent:
            fail(f"sharded: acked rvs {silent[:10]} lost WITHOUT report")
        survivors = {
            (o.get("metadata") or {}).get("name")
            for o in reopened["store"].list(
                "Pod", namespace=ns_by_shard[0]
            )[0]
        }
        if survivors != shard0_names:
            fail(
                "damage on shard 1 cost shard 0 objects: "
                f"{sorted(shard0_names - survivors)[:5]}"
            )
        for w in reopened["wals"]:
            w.close()
        results["sharded-isolation"] = {
            "detected": True,
            "acked_lost_reported": len(reported),
            "silent_lost": 0,
            "intact_shard_preserved": True,
            "recovery_s": round(dt, 3),
        }

    return {
        "seed": seed,
        "acked_writes": len(acked),
        "faults": results,
        "total_s": round(time.monotonic() - t_start, 3),
        "silently_lost_acked_writes": 0,
    }


def run_exhaustion_smoke(seed: int = 42, pods: int = 16) -> dict:
    """In-process resource-exhaustion smoke: seeded disk-full and
    fsync-error windows against a live apiserver+WAL.  Asserts the
    acceptance contract of the degraded read-only mode:

    - zero silently-lost acked writes: after a crash at the end,
      durable-after-recovery ∪ visibly-rejected accounts for every ack
      (the ``RecoveryReport.account`` predicate, same as the DST
      ``exhaustion-honesty`` invariant);
    - during a window, mutations are refused with 503 + Retry-After +
      machine-readable reason StorageDegraded while reads, watches and
      lease renewals (via the emergency reserve) stay live;
    - /healthz stays 200 and the component supervisor performs ZERO
      restarts (degraded is tracked, not "fixed");
    - writes re-arm once pressure clears (``wait_writable``), and the
      degraded-aware client retry rides the window out.
    """
    import random
    import threading

    from kwok_tpu.chaos.fs_pressure import FsPressure
    from kwok_tpu.cluster.apiserver import APIServer
    from kwok_tpu.cluster.client import APIError, ClusterClient, RetryPolicy
    from kwok_tpu.cluster.election import LeaderElector
    from kwok_tpu.cluster.store import ResourceStore
    from kwok_tpu.cluster.wal import WriteAheadLog
    from kwok_tpu.ctl.runtime import ComponentSupervisor
    from kwok_tpu.snapshot.pitr import boot_recover
    from kwok_tpu.utils.backoff import Backoff

    rng = random.Random(seed)
    t_start = time.monotonic()

    def fail(msg):
        raise SystemExit(f"exhaustion smoke FAILED: {msg}")

    def pod(n):
        return {
            "apiVersion": "v1",
            "kind": "Pod",
            "metadata": {"name": n, "namespace": "default"},
            "spec": {"nodeName": f"node-{rng.randrange(4)}"},
            "status": {},
        }

    class _LiveRuntime:
        """In-process runtime stub over the live server: alive, never
        restartable — start_component firing at all IS the failure."""

        def __init__(self, client):
            self._client = client
            self.restarts = 0

        def load_components(self):
            from kwok_tpu.ctl.components import Component

            return [Component(name="apiserver", args=[])]

        def component_alive(self, name):
            return True

        def start_component(self, comp):
            self.restarts += 1

        def client(self, timeout=2.0):
            return self._client

    results = {}
    with tempfile.TemporaryDirectory() as tmp:
        wal_p = os.path.join(tmp, "wal.jsonl")
        store = ResourceStore()
        wal = WriteAheadLog(wal_p, fsync="always")
        store.attach_wal(wal)
        acked: set = set()

        def track(fn, *a, **kw):
            rv0 = store.resource_version
            out = fn(*a, **kw)
            acked.update(range(rv0 + 1, store.resource_version + 1))
            return out

        with APIServer(store) as srv:
            client = ClusterClient(
                srv.url,
                retry=RetryPolicy(
                    seed=seed,
                    max_attempts=20,
                    budget_s=30.0,
                    backoff=Backoff(duration=0.02, cap=0.2),
                    # production clients honor the degraded Retry-After
                    # (~5s); the smoke polls fast so the whole gate
                    # stays inside check.sh's budget
                    honor_retry_after=False,
                ),
                client_id="kwokctl",
            )
            # raw client sees the 503s instead of retrying them
            raw = ClusterClient(
                srv.url,
                retry=RetryPolicy(
                    max_attempts=1,
                    budget_s=5.0,
                    backoff=Backoff(duration=0.0, cap=0.0),
                    retry_statuses=(),
                ),
                client_id="exhaustion-raw",
            )
            elector = LeaderElector(
                ClusterClient(srv.url, client_id="system:smoke"),
                "kwok-controller",
                "smoke-replica",
                lease_duration=30.0,
                rng=random.Random(seed),
            )
            elector.try_acquire_or_renew()
            if not elector.is_leader():
                fail("elector never acquired its lease pre-window")
            rt = _LiveRuntime(client)
            sup = ComponentSupervisor(rt, rng=random.Random(seed))

            for i in range(pods):
                track(client.create, pod(f"pre-{i}"))
            watcher = client.watch("Lease", namespace="kube-system")

            def run_window(kind, tag, t0):
                # t0: per-window supervisor time base — ticks must stay
                # monotonic across windows (the supervisor's budget
                # bookkeeping assumes a forward clock)
                shim = FsPressure(kind)
                wal.set_pressure(shim)
                # the in-flight write rides the reserve: acked + durable
                track(raw.create, pod(f"{tag}-inflight"))
                if store.storage_degraded() is None:
                    fail(f"{kind}: window did not degrade storage")
                okz, reason = client.readiness()
                if okz or reason != "StorageDegraded":
                    fail(f"{kind}: /readyz did not report degraded "
                         f"({okz}, {reason})")
                if not client.healthy():
                    fail(f"{kind}: /healthz went down — degraded must "
                         "stay alive")
                rejected = 0
                for i in range(4):
                    try:
                        raw.create(pod(f"{tag}-rej-{i}"))
                        fail(f"{kind}: mutation acked while degraded")
                    except APIError as exc:
                        if exc.code != 503 or exc.reason != "StorageDegraded":
                            fail(
                                f"{kind}: rejection was {exc.code}/"
                                f"{exc.reason}, want 503/StorageDegraded"
                            )
                        rejected += 1
                if not rejected:
                    fail(f"{kind}: no visible rejections in the window")
                # Retry-After must ride the 503 (parseable back-off)
                import http.client as hc

                host, port = srv.address
                c = hc.HTTPConnection(host, port, timeout=5)
                c.request(
                    "POST",
                    "/r/pods",
                    body=json.dumps(pod(f"{tag}-ra")),
                    headers={"Content-Type": "application/json"},
                )
                resp = c.getresponse()
                resp.read()
                if resp.status != 503 or not resp.getheader("Retry-After"):
                    fail(f"{kind}: 503 without Retry-After")
                c.close()
                # lease renewals ride the reserve: HA must not collapse
                rv0 = store.resource_version
                for _ in range(3):
                    elector.renew_once()
                if not elector.is_leader():
                    fail(f"{kind}: leader lost its lease in the window")
                acked.update(range(rv0 + 1, store.resource_version + 1))
                # reads and watches stay live
                items, _ = client.list("Pod")
                if not items:
                    fail(f"{kind}: reads went dark while degraded")
                ev = watcher.next(timeout=5.0)
                if ev is None:
                    fail(f"{kind}: watch stream starved while degraded")
                # supervisor: degraded is tracked, never restarted
                for t in (0.0, 0.5, 1.0, 1.5):
                    sup.tick(now=t0 + t)
                if rt.restarts:
                    fail(f"{kind}: supervisor restarted a degraded "
                         "component")
                if sup.degraded.get("apiserver") != "StorageDegraded":
                    fail(f"{kind}: supervisor did not track degraded "
                         f"state ({sup.degraded})")
                # degraded-aware retry: a retrying client rides it out
                done = {}

                def late_write():
                    done["obj"] = client.create(pod(f"{tag}-retried"))

                th = threading.Thread(target=late_write, daemon=True)
                th.start()
                time.sleep(0.3)
                wal.set_pressure(None)
                if not client.wait_writable(timeout=10.0):
                    fail(f"{kind}: writes never re-armed after the "
                         "window cleared")
                th.join(timeout=10.0)
                if th.is_alive() or "obj" not in done:
                    fail(f"{kind}: retrying client never converged "
                         "after re-arm")
                rv = int(
                    (done["obj"].get("metadata") or {}).get(
                        "resourceVersion", 0
                    )
                )
                acked.add(rv)
                # post-window writes flow normally again
                track(client.create, pod(f"{tag}-post"))
                for t in (2.0, 2.5):
                    sup.tick(now=t0 + t)
                if sup.degraded:
                    fail(f"{kind}: supervisor still sees degraded "
                         "after re-arm")
                return {
                    "rejected": rejected,
                    "retry_stats": client.retry_stats(),
                    "shim": shim.snapshot(),
                }

            results["disk-full"] = run_window("disk-full", "df", t0=0.0)
            results["fsync-error"] = run_window(
                "fsync-error", "fe", t0=100.0
            )
            if client.retry_stats()["degraded"] == 0:
                fail("degraded retries were never counted distinctly")
            watcher.stop()
            elector.stop(release=True)
            live = store.dump_state()

        # crash: recover from the WAL alone; every ack must be
        # accounted durable (nothing was reported lost, nothing silent)
        wal.close()
        fresh = ResourceStore()
        boot = boot_recover(fresh, None, wal_p)
        rep = boot["recovery"]
        if rep is None:
            fail("no recovery report from boot_recover")
        reported, silent = rep.account(acked)
        if silent:
            fail(f"acked rvs {silent[:10]} lost WITHOUT report")
        if reported:
            fail(
                f"acked rvs {reported[:10]} reported lost — exhaustion "
                "windows must not lose acked writes at all"
            )
        if fresh.dump_state() != live:
            fail("post-crash recovery diverged from live state")

    # ---- sharded scene: one shard's full disk degrades ONLY it ------
    # (kwok_tpu/cluster/sharding): writes routed to the pressured
    # shard 503 with reason StorageDegraded, the other shard stays
    # writable, /readyz names the degraded shard set, and clearing
    # the pressure re-arms just that shard.
    from kwok_tpu.cluster.sharding import namespaces_covering_shards
    from kwok_tpu.snapshot.sharded import open_sharded_store

    with tempfile.TemporaryDirectory() as tmp:
        opened = open_sharded_store(
            tmp, 2, namespace_finalizers=False, wal_fsync="off", pitr=False
        )
        sstore = opened["store"]
        wals = opened["wals"]
        # one namespace per shard
        ns_by_shard = namespaces_covering_shards(2)

        def ns_pod(ns, n):
            p = pod(n)
            p["metadata"]["namespace"] = ns
            return p

        with APIServer(sstore) as srv:
            sraw = ClusterClient(
                srv.url,
                retry=RetryPolicy(
                    max_attempts=1,
                    budget_s=5.0,
                    backoff=Backoff(duration=0.0, cap=0.0),
                    retry_statuses=(),
                ),
                client_id="exhaustion-sharded",
            )
            for s, ns in enumerate(ns_by_shard):
                sraw.create(ns_pod(ns, "warm"))
            shim = FsPressure("disk-full")
            wals[1].set_pressure(shim)
            # the first write into the window rides shard 1's reserve
            # (acked + durable), then the shard degrades
            sraw.create(ns_pod(ns_by_shard[1], "inflight"))
            deg = sstore.storage_degraded()
            if deg is None or deg.get("shards") != [1]:
                fail(f"sharded: degraded shard set wrong: {deg}")
            try:
                sraw.create(ns_pod(ns_by_shard[1], "rej"))
                fail("sharded: degraded shard acked a write")
            except APIError as exc:
                if exc.code != 503 or exc.reason != "StorageDegraded":
                    fail(
                        f"sharded: rejection was {exc.code}/{exc.reason}, "
                        "want 503/StorageDegraded"
                    )
            # the OTHER shard keeps accepting writes mid-window
            sraw.create(ns_pod(ns_by_shard[0], "cross"))
            # /readyz names the degraded shard set
            import http.client as hc

            host, port = srv.address
            c = hc.HTTPConnection(host, port, timeout=5)
            c.request("GET", "/readyz")
            resp = c.getresponse()
            body = json.loads(resp.read() or b"{}")
            c.close()
            if resp.status != 503 or (
                (body.get("storage") or {}).get("shards") != [1]
            ):
                fail(
                    f"sharded: /readyz did not report the degraded "
                    f"shard set ({resp.status}, {body})"
                )
            # reads stay live across ALL shards
            items, _ = sraw.list("Pod")
            if len(items) < 3:
                fail("sharded: reads went dark while one shard degraded")
            wals[1].set_pressure(None)
            if not sstore.probe_writable():
                fail("sharded: shard never re-armed after the window")
            sraw.create(ns_pod(ns_by_shard[1], "post"))
            if sstore.storage_degraded() is not None:
                fail("sharded: still degraded after re-arm")
        for w in wals:
            w.close()
        results["sharded-isolation"] = {
            "degraded_shard": 1,
            "other_shard_writable": True,
            "readyz_shards": [1],
        }

    return {
        "seed": seed,
        "acked_writes": len(acked),
        "windows": results,
        "rearms": 2,
        "supervisor_restarts": 0,
        "silently_lost_acked_writes": 0,
        "total_s": round(time.monotonic() - t_start, 3),
    }


def run_overload_smoke(
    seed: int = 42, duration: float = 2.0
) -> dict:
    """In-process overload smoke; returns the report dict (raises on
    any lost canary write, hung/reset shed connection, or system-level
    rejection)."""
    from kwok_tpu.chaos.http_faults import OverloadDriver
    from kwok_tpu.chaos.plan import OverloadWindow
    from kwok_tpu.cluster.apiserver import APIServer
    from kwok_tpu.cluster.client import ClusterClient, RetryPolicy
    from kwok_tpu.cluster.flowcontrol import (
        DEFAULT_LEVELS,
        FlowConfig,
        FlowController,
        PriorityLevel,
    )
    from kwok_tpu.cluster.store import ResourceStore
    from kwok_tpu.utils.backoff import Backoff

    plan = FaultPlan(
        seed=seed,
        duration=duration + 30,
        http=HttpFaultSpec(
            overloads=[
                OverloadWindow(
                    at=0.0, duration=duration, rps=2000, clients=8
                )
            ]
        ),
    )
    # a deliberately tiny budget: best-effort gets one seat and almost
    # no queue, so the flood saturates it instantly while system keeps
    # its own seats
    levels = tuple(
        lv
        if lv.name != "best-effort"
        else PriorityLevel(
            "best-effort", shares=lv.shares, queues=2,
            queue_wait_s=0.1, queue_limit=2,
        )
        for lv in DEFAULT_LEVELS
    )
    flow = FlowController(
        FlowConfig(max_inflight=8, levels=levels), seed=seed
    )
    store = ResourceStore()
    # a populated cluster: the flood lists pods, and the point of the
    # smoke is a flood whose per-request cost outruns one best-effort
    # seat — an empty list would be served faster than it arrives
    store.bulk(
        [
            {
                "verb": "create",
                "data": {
                    "apiVersion": "v1",
                    "kind": "Pod",
                    "metadata": {
                        "name": f"ballast-{i}",
                        "namespace": "default",
                    },
                    "spec": {"nodeName": f"node-{i % 8}"},
                    "status": {"phase": "Running"},
                },
            }
            for i in range(2000)
        ]
    )
    t_start = time.monotonic()
    with APIServer(store, flow=flow) as srv:
        driver = OverloadDriver(plan, srv.url).start()
        client = ClusterClient(
            srv.url,
            retry=RetryPolicy(
                seed=seed,
                max_attempts=10,
                budget_s=30.0,
                backoff=Backoff(duration=0.02, cap=0.5),
            ),
            client_id="kwokctl",  # system priority by default schema
        )
        canaries = 0
        worst_latency = 0.0
        while time.monotonic() - t_start < duration:
            t0 = time.monotonic()
            client.create(
                {
                    "apiVersion": "v1",
                    "kind": "ConfigMap",
                    "metadata": {
                        "name": f"canary-{canaries}",
                        "namespace": "default",
                    },
                    "data": {"i": str(canaries)},
                }
            )
            worst_latency = max(worst_latency, time.monotonic() - t0)
            canaries += 1
            time.sleep(0.01)
        if not driver.wait(timeout=30):
            driver.stop()
            raise SystemExit("overload smoke FAILED: flood never finished")
        counters = driver.snapshot()
        levels_snap = flow.snapshot()
        if store.count("ConfigMap") != canaries:
            raise SystemExit(
                f"overload smoke FAILED: {store.count('ConfigMap')}/"
                f"{canaries} canary writes survived the flood"
            )
        if counters["shed"] == 0:
            raise SystemExit(
                "overload smoke FAILED: the flood was never shed "
                f"(flow control inactive? {counters})"
            )
        if counters["shed_without_retry_after"]:
            raise SystemExit(
                "overload smoke FAILED: "
                f"{counters['shed_without_retry_after']} 429s lacked "
                "Retry-After"
            )
        if counters["conn_errors"]:
            raise SystemExit(
                "overload smoke FAILED: "
                f"{counters['conn_errors']} flood connections hung/reset "
                "instead of a typed rejection"
            )
        if levels_snap["system"]["rejected"]:
            raise SystemExit(
                "overload smoke FAILED: system-priority traffic was shed "
                f"({levels_snap['system']})"
            )
    return {
        "seed": seed,
        "canary_writes": canaries,
        "canary_worst_latency_s": round(worst_latency, 3),
        "flood": counters,
        "levels": levels_snap,
        "lost_writes": 0,
    }


def run_fleet_smoke(
    seed: int = 42, tenants: int = 1000, flood_seconds: float = 1.5
) -> dict:
    """In-process fleet smoke (kwok_tpu.fleet): one apiserver hosting
    ``tenants`` virtual control planes.  Four phases, SystemExit on any
    violation:

    1. cold-start every tenant with its first write (per-tenant APF
       level + namespace bootstrap + shard pin on first touch) and
       bound the cold-start latency;
    2. seeded neighbor flood: saturate ONE tenant's priority level
       from threads while a victim tenant keeps issuing its own
       traffic — the victim must see ZERO 429s and a bounded p99, the
       host system level must shed nothing, and the flood itself must
       have been shed (else the probe is vacuous);
    3. scale-to-zero: advance the registry's injected clock past the
       cold threshold, sweep, assert every binding was dropped, then
       cold-start one tenant again within the bound — with its data
       intact across the park/unpark;
    4. leak check: sampled tenants each see exactly their own objects
       through the scoped surface while the host store carries every
       tenant's (prefixed) truth.
    """
    import random
    import threading
    import urllib.error
    import urllib.request

    from kwok_tpu.cluster.apiserver import APIServer
    from kwok_tpu.cluster.flowcontrol import FlowController
    from kwok_tpu.cluster.store import ResourceStore
    from kwok_tpu.fleet import FleetRegistry, fleet_flow_config
    from kwok_tpu.fleet.tenant import fleet_tenant_ids
    from kwok_tpu.utils.clock import FakeClock

    cold_start_bound_s = 2.0
    victim_p99_bound_s = 1.0

    ids = fleet_tenant_ids(tenants)
    clock = FakeClock(0.0)
    store = ResourceStore()
    registry = FleetRegistry(
        store, ids, clock=clock, idle_after_s=60.0, cold_after_s=120.0
    )
    # tiny per-tenant budget: one guaranteed seat, almost no queue —
    # the flood saturates its own level instantly while every other
    # level keeps its seats
    flow = FlowController(
        fleet_flow_config(ids, max_inflight=16, queue_wait_s=0.1, queue_limit=2),
        seed=seed,
    )

    def percentile(vals, q):
        if not vals:
            return 0.0
        s = sorted(vals)
        return s[min(len(s) - 1, int(q * (len(s) - 1) + 0.5))]

    with APIServer(store, flow=flow, fleet=registry) as srv:

        def req(method, path, tenant=None, body=None, timeout=10.0):
            data = json.dumps(body).encode() if body is not None else None
            r = urllib.request.Request(
                srv.url + path, data=data, method=method
            )
            if tenant is not None:
                r.add_header("X-Kwok-Tenant", tenant)
            if data is not None:
                r.add_header("Content-Type", "application/json")
            try:
                with urllib.request.urlopen(r, timeout=timeout) as resp:
                    return resp.status, json.loads(resp.read() or b"{}")
            except urllib.error.HTTPError as e:
                e.read()
                return e.code, None

        # ----- phase 1: cold-start every tenant with its first write
        cold_lat = []
        for tid in ids:
            t0 = time.monotonic()
            status, _ = req(
                "POST",
                "/r/configmaps",
                tenant=tid,
                body={
                    "apiVersion": "v1",
                    "kind": "ConfigMap",
                    "metadata": {"name": f"{tid}-cm", "namespace": "default"},
                    "data": {"owner": tid},
                },
            )
            cold_lat.append(time.monotonic() - t0)
            if status not in (200, 201):
                raise SystemExit(
                    f"fleet smoke FAILED: tenant {tid} first write -> {status}"
                )
        cold_p99 = percentile(cold_lat, 0.99)
        if cold_p99 > cold_start_bound_s:
            raise SystemExit(
                f"fleet smoke FAILED: cold-start p99 {cold_p99:.3f}s "
                f"exceeds {cold_start_bound_s}s across {tenants} tenants"
            )
        snap = registry.snapshot()
        if snap["warm"] != tenants or snap["cold_starts"] != tenants:
            raise SystemExit(
                f"fleet smoke FAILED: expected {tenants} warm tenants "
                f"after first touch, got {snap}"
            )

        # ----- phase 2: seeded neighbor flood -----------------------
        rng = random.Random(seed)
        flooder = ids[rng.randrange(len(ids))]
        victim = ids[(ids.index(flooder) + 1) % len(ids)]
        # quiet baseline for the victim: its own list latency with no
        # neighbor load, so the report can carry an isolation RATIO
        # (flooded p99 / quiet p99) alongside the absolute bound
        baseline_lat = []
        for _ in range(30):
            t0 = time.monotonic()
            req("GET", "/r/configmaps", tenant=victim)
            baseline_lat.append(time.monotonic() - t0)
        baseline_p99 = percentile(baseline_lat, 0.99)
        stop = threading.Event()
        flood_counts = {"ok": 0, "shed": 0, "errors": 0}
        lock = threading.Lock()

        def flood_worker():
            while not stop.is_set():
                try:
                    status, _ = req(
                        "GET", "/r/configmaps", tenant=flooder, timeout=5.0
                    )
                except Exception:  # noqa: BLE001 — hung/reset socket
                    with lock:
                        flood_counts["errors"] += 1
                    continue
                with lock:
                    if status == 429:
                        flood_counts["shed"] += 1
                    elif status == 200:
                        flood_counts["ok"] += 1
                    else:
                        flood_counts["errors"] += 1

        threads = [
            threading.Thread(target=flood_worker, daemon=True)
            for _ in range(6)
        ]
        for th in threads:
            th.start()
        victim_lat = []
        victim_shed = 0
        t_flood0 = time.monotonic()
        while time.monotonic() - t_flood0 < flood_seconds:
            t0 = time.monotonic()
            status, _ = req("GET", "/r/configmaps", tenant=victim)
            victim_lat.append(time.monotonic() - t0)
            if status == 429:
                victim_shed += 1
        stop.set()
        for th in threads:
            th.join(timeout=10.0)
        levels_snap = flow.snapshot()
        if flood_counts["shed"] == 0:
            raise SystemExit(
                "fleet smoke FAILED: the tenant flood was never shed "
                f"(per-tenant level inactive? {flood_counts})"
            )
        if flood_counts["errors"]:
            raise SystemExit(
                f"fleet smoke FAILED: {flood_counts['errors']} flood "
                "requests hung/reset instead of a typed rejection"
            )
        if victim_shed:
            raise SystemExit(
                f"fleet smoke FAILED: neighbor {victim} saw "
                f"{victim_shed} 429s while {flooder} was flooded"
            )
        victim_p99 = percentile(victim_lat, 0.99)
        if victim_p99 > victim_p99_bound_s:
            raise SystemExit(
                f"fleet smoke FAILED: neighbor p99 {victim_p99:.3f}s "
                f"exceeds {victim_p99_bound_s}s under {flooder}'s flood"
            )
        if levels_snap["system"]["rejected"]:
            raise SystemExit(
                "fleet smoke FAILED: the host system level was shed "
                f"during a tenant flood ({levels_snap['system']})"
            )
        if levels_snap[victim]["rejected"]:
            raise SystemExit(
                f"fleet smoke FAILED: victim level {victim} recorded "
                f"rejections ({levels_snap[victim]})"
            )

        # ----- phase 3: scale-to-zero + cold-start bound ------------
        clock.advance(300.0)
        registry.sweep(force=True)
        snap = registry.snapshot()
        if snap["cold"] != tenants:
            raise SystemExit(
                "fleet smoke FAILED: expected every tenant parked after "
                f"the idle horizon, got {snap}"
            )
        reborn = ids[rng.randrange(len(ids))]
        t0 = time.monotonic()
        status, listing = req("GET", "/r/configmaps", tenant=reborn)
        restart_s = time.monotonic() - t0
        if status != 200 or restart_s > cold_start_bound_s:
            raise SystemExit(
                f"fleet smoke FAILED: re-cold-start of {reborn} -> "
                f"{status} in {restart_s:.3f}s (bound {cold_start_bound_s}s)"
            )
        names = [
            (o.get("metadata") or {}).get("name")
            for o in (listing or {}).get("items", [])
        ]
        if names != [f"{reborn}-cm"]:
            raise SystemExit(
                f"fleet smoke FAILED: {reborn} lost or gained state "
                f"across scale-to-zero: {names}"
            )

        # ----- phase 4: cross-tenant leak check ---------------------
        sample = [ids[0], ids[len(ids) // 2], ids[-1], flooder, victim]
        for tid in dict.fromkeys(sample):
            _status, listing = req("GET", "/r/configmaps", tenant=tid)
            names = sorted(
                (o.get("metadata") or {}).get("name")
                for o in (listing or {}).get("items", [])
            )
            if names != [f"{tid}-cm"]:
                raise SystemExit(
                    f"fleet smoke FAILED: tenant {tid} sees {names} — "
                    "cross-tenant leak or lost write"
                )
        if store.count("ConfigMap") != tenants:
            raise SystemExit(
                "fleet smoke FAILED: host store carries "
                f"{store.count('ConfigMap')} ConfigMaps, want {tenants}"
            )

    return {
        "seed": seed,
        "tenants": tenants,
        "cold_start_p50_s": round(percentile(cold_lat, 0.5), 4),
        "cold_start_p99_s": round(cold_p99, 4),
        "flood": {"tenant": flooder, **flood_counts},
        "victim": {
            "tenant": victim,
            "requests": len(victim_lat),
            "shed": victim_shed,
            "p99_s": round(victim_p99, 4),
            "baseline_p99_s": round(baseline_p99, 4),
            # denominator floored at 5ms: a sub-millisecond quiet
            # baseline would turn pure GIL jitter into a huge ratio
            "isolation_ratio": round(victim_p99 / max(baseline_p99, 0.005), 2),
        },
        "recold_start_s": round(restart_s, 4),
        "leaks": 0,
    }


def run_failover_smoke(seed: int = 42, lease_duration: float = 2.5) -> dict:
    """In-process HA smoke: three electors on one apiserver (APF on).

    Asserts the acceptance bounds of the leader-election subsystem
    (cluster/election.py) with real wall-clock timing:

    - exactly one leader at a time (the standby never self-promotes
      while the leader renews),
    - after the leader goes silent (SIGKILL analog: stop WITHOUT
      releasing), a standby holds the lease within 2x leaseDuration,
    - after a graceful step-down (release, the SIGTERM path), a
      standby holds it within ~one renew interval (asserted at
      <= leaseDuration, reported exactly),
    - the dead ex-leader's fence token is rejected with 409 while the
      live leader's token passes (split-brain write fencing).
    """
    import random

    from kwok_tpu.cluster.apiserver import APIServer
    from kwok_tpu.cluster.client import ClusterClient
    from kwok_tpu.cluster.election import LeaderElector
    from kwok_tpu.cluster.flowcontrol import FlowConfig, FlowController
    from kwok_tpu.cluster.store import Conflict, ResourceStore

    lease_name = "kwok-controller"
    store = ResourceStore()
    flow = FlowController(FlowConfig(max_inflight=16), seed=seed)

    def wait_until(cond, timeout: float) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return True
            time.sleep(0.02)
        return cond()

    with APIServer(store, flow=flow) as srv:

        def mk(identity: str, bump: int) -> LeaderElector:
            return LeaderElector(
                # lease traffic rides the system priority level, like
                # the daemons' electors (X-Kwok-Client "system:...")
                ClusterClient(srv.url, client_id=f"system:{identity}"),
                lease_name,
                identity,
                lease_duration=lease_duration,
                rng=random.Random(seed + bump),
            )

        a = mk("replica-a", 1).start()
        if not wait_until(a.is_leader, 2 * lease_duration):
            raise SystemExit("failover smoke FAILED: first elector never led")
        b = mk("replica-b", 2).start()
        time.sleep(0.3)
        if b.is_leader():
            raise SystemExit("failover smoke FAILED: two concurrent leaders")
        stale_fence = a.fence()

        # --- hard failure: the leader falls silent (SIGKILL analog) ---
        t0 = time.monotonic()
        a.stop(release=False)
        if not wait_until(b.is_leader, 2 * lease_duration + 2.0):
            raise SystemExit(
                "failover smoke FAILED: standby never took over after kill"
            )
        takeover_kill_s = time.monotonic() - t0
        if takeover_kill_s > 2 * lease_duration:
            raise SystemExit(
                "failover smoke FAILED: takeover after kill took "
                f"{takeover_kill_s:.2f}s > 2x leaseDuration "
                f"({2 * lease_duration:.2f}s)"
            )

        # --- fencing: the dead generation cannot write, the live can ---
        cm = {
            "apiVersion": "v1",
            "kind": "ConfigMap",
            "metadata": {"name": "fence-probe", "namespace": "default"},
            "data": {},
        }
        stale_client = ClusterClient(
            srv.url, fence_provider=lambda: stale_fence
        )
        try:
            stale_client.create(dict(cm))
        except Conflict:
            pass
        else:
            raise SystemExit(
                "failover smoke FAILED: stale-leader write was NOT fenced"
            )
        ClusterClient(srv.url, fence_provider=b.fence).create(dict(cm))

        # --- graceful step-down: release -> immediate handover ---
        c = mk("replica-c", 3).start()
        time.sleep(0.3)  # let c start polling (and observe b's lease)
        t1 = time.monotonic()
        b.stop(release=True)
        if not wait_until(c.is_leader, 2 * lease_duration + 2.0):
            raise SystemExit(
                "failover smoke FAILED: standby never took over after release"
            )
        takeover_release_s = time.monotonic() - t1
        if takeover_release_s > lease_duration:
            raise SystemExit(
                "failover smoke FAILED: graceful takeover took "
                f"{takeover_release_s:.2f}s > leaseDuration "
                f"({lease_duration:.2f}s; expected ~one renew interval)"
            )
        transitions = c.transitions
        c.stop(release=True)
    return {
        "seed": seed,
        "lease_duration_s": lease_duration,
        "takeover_after_kill_s": round(takeover_kill_s, 3),
        "takeover_after_release_s": round(takeover_release_s, 3),
        "lease_transitions": transitions,
        "stale_writes_fenced": 1,
        "split_brain_writes": 0,
    }


def drive_cluster(plan: FaultPlan, cluster: str, supervise: bool) -> dict:
    from kwok_tpu.chaos.disk_faults import DiskFaultDriver
    from kwok_tpu.chaos.process_faults import ProcessFaultDriver
    from kwok_tpu.ctl.runtime import BinaryRuntime, ComponentSupervisor

    rt = BinaryRuntime(cluster)
    if not rt.exists():
        raise SystemExit(f"cluster {cluster!r} does not exist (kwokctl create cluster)")
    sup = None
    if supervise:
        import random

        sup = ComponentSupervisor(rt, rng=random.Random(plan.seed)).start()
    driver = ProcessFaultDriver(rt, plan, client=rt.client(timeout=5.0))
    disk = DiskFaultDriver(rt, plan).start() if plan.disk else None
    try:
        driver.run()
        if disk is not None:
            # the process schedule may finish first; scheduled disk
            # faults still fire at their own offsets
            disk.wait(
                timeout=max((s.at for s in plan.disk), default=0.0) + 15.0
            )
            disk.stop()
        if supervise:
            # let the supervisor finish recovering what the last fault
            # broke before reporting
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if all(rt.running_components().values()):
                    break
                time.sleep(0.25)
    finally:
        if disk is not None:
            disk.stop()
        if sup is not None:
            sup.stop()
    return {
        "process_events": driver.events,
        "disk_events": disk.events if disk is not None else [],
        "supervisor_events": sup.events if sup is not None else [],
        "recovery_times_s": (
            [round(r, 3) for r in sup.recovery_times] if sup is not None else []
        ),
    }


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="kwok-tpu-chaos", description=__doc__)
    p.add_argument("--profile", default="", help="chaos profile YAML")
    p.add_argument("--seed", type=int, default=None, help="override the profile seed")
    p.add_argument(
        "--print-schedule",
        action="store_true",
        help="print the deterministic fault schedule and exit",
    )
    p.add_argument("--cluster", default="", help="drive process faults against this cluster")
    p.add_argument(
        "--supervise",
        action="store_true",
        help="run the component supervisor while driving faults",
    )
    p.add_argument(
        "--smoke",
        action="store_true",
        help="run the in-process durability smoke (used by tools/check.sh)",
    )
    p.add_argument(
        "--overload-smoke",
        action="store_true",
        help="run the in-process overload/graceful-shedding smoke "
        "(used by tools/check.sh)",
    )
    p.add_argument(
        "--corruption-smoke",
        action="store_true",
        help="run the in-process storage-integrity smoke: seeded disk "
        "faults (bit-flip/truncate/torn-write/fsync-crash/snapshot "
        "corruption) must be detected, recovery bounded and honest, "
        "PITR byte-identical (used by tools/check.sh)",
    )
    p.add_argument(
        "--exhaustion-smoke",
        action="store_true",
        help="run the in-process resource-exhaustion smoke: seeded "
        "disk-full/fsync-error windows -> degraded read-only mode "
        "(503+Retry-After, reads/watches/lease renewals live, zero "
        "supervisor restarts), re-arm on space return, zero "
        "silently-lost acked writes (used by tools/check.sh)",
    )
    p.add_argument(
        "--failover-smoke",
        action="store_true",
        help="run the in-process leader-election failover smoke: "
        "bounded takeover after kill/release + stale-leader write "
        "fencing (used by tools/check.sh)",
    )
    p.add_argument(
        "--fleet-smoke",
        action="store_true",
        help="run the in-process multi-tenant fleet smoke: N virtual "
        "control planes on one apiserver — cold-start bound, neighbor "
        "flood shed WITHOUT starving the victim tenant or the system "
        "level, scale-to-zero + re-cold-start with state intact, zero "
        "cross-tenant leaks (used by tools/check.sh)",
    )
    p.add_argument(
        "--fleet-tenants",
        type=int,
        default=1000,
        help="fleet smoke tenant count",
    )
    p.add_argument(
        "--lease-seconds",
        type=float,
        default=2.5,
        help="failover smoke election lease duration",
    )
    p.add_argument(
        "--dst",
        action="store_true",
        help="deterministic simulation run(s): whole control plane on "
        "a virtual clock + invariant checks (kwok_tpu.dst)",
    )
    p.add_argument(
        "--seeds", type=int, default=10, help="how many DST seeds to explore"
    )
    p.add_argument(
        "--seed-start", type=int, default=0, help="first DST seed"
    )
    p.add_argument(
        "--dst-duration",
        type=float,
        default=40.0,
        help="virtual seconds of scenario+faults per DST seed",
    )
    p.add_argument(
        "--dst-bug",
        default=None,
        choices=[
            None,
            "ungated-writer",
            "partial-gang",
            "cross-shard-txn",
            "tenant-leak",
            "shard-void-leak",
            "fanin-stale-resume",
        ],
        help="inject a test-only regression (must be caught): "
        "ungated-writer reconciles without the lease, partial-gang "
        "binds PodGroups per-pod instead of atomically, "
        "cross-shard-txn makes the shard router place txn ops "
        "per-object and split atomic batches into per-shard sub-txns, "
        "tenant-leak un-scopes one fleet tenant's watch stream, "
        "shard-void-leak skips a rolled-back write's void accounting "
        "(union rv-continuity hole), fanin-stale-resume pins a "
        "caught-up shard's resume at horizon 0 in the watch fan-in "
        "(stale replay breaks per-stream rv monotonicity)",
    )
    p.add_argument(
        "--dst-fleet-tenants",
        type=int,
        default=2,
        help="fleet tenants the DST co-hosts (kwok_tpu.fleet; "
        "0 disables the fleet composition)",
    )
    p.add_argument(
        "--dst-shards",
        type=int,
        default=2,
        help="store shards the DST composes (kwok_tpu.cluster.sharding; "
        "1 = the single-store composition)",
    )
    p.add_argument(
        "--dst-verbose",
        action="store_true",
        help="print one JSON line per seed as it finishes",
    )
    p.add_argument(
        "--dst-search",
        action="store_true",
        help="coverage-guided fault search (kwok_tpu.dst.search): "
        "mutate fault schedules toward novel trace coverage instead "
        "of walking consecutive seeds; on violation, delta-debug to a "
        "minimal fault set and verify a byte-identical replay.  With "
        "--dst-bug armed, exit 0 iff the bug was found, minimized and "
        "replay-verified; without, exit 0 iff the budget ran clean",
    )
    p.add_argument(
        "--search-budget",
        type=int,
        default=48,
        help="schedule executions the guided search may spend",
    )
    p.add_argument(
        "--search-seed",
        type=int,
        default=0,
        help="seed of the search's own rng (mutations + corpus picks) "
        "— the whole search replays from this one value",
    )
    p.add_argument(
        "--search-out",
        default=None,
        metavar="FILE",
        help="write the minimized violation's replay artifact here "
        "(the --dst-replay regression-pinning format)",
    )
    p.add_argument(
        "--dst-replay",
        default=None,
        metavar="FILE",
        help="re-execute a --search-out artifact and verify the "
        "recorded trace digest + violations byte-identically "
        "(exit 0 iff both match)",
    )
    p.add_argument("--pods", type=int, default=40, help="smoke population")
    p.add_argument(
        "--flood-seconds",
        type=float,
        default=2.0,
        help="overload smoke flood duration",
    )
    return p


def run_dst(args) -> int:
    """Explore N seeds; print the aggregate report; nonzero exit on
    any invariant violation (the check.sh gate contract)."""
    from kwok_tpu.dst import SimOptions, run_seed

    opts = SimOptions(
        duration=args.dst_duration,
        bug=args.dst_bug,
        store_shards=args.dst_shards,
        fleet_tenants=args.dst_fleet_tenants,
    )
    violating = {}
    runs = []
    for i in range(args.seeds):
        seed = args.seed_start + i
        report = run_seed(seed, opts)
        runs.append(report)
        if args.dst_verbose:
            print(json.dumps(report), flush=True)
        if report["violations"]:
            violating[seed] = report["violations"]
    summary = {
        "seeds": args.seeds,
        "start": args.seed_start,
        "steps": sum(r["steps"] for r in runs),
        "crashes": sum(r["crashes"] for r in runs),
        "converged": sum(1 for r in runs if r["converged"]),
        "violating_seeds": sorted(violating),
        "violations": violating,
    }
    print(json.dumps(summary))
    return 1 if violating else 0


def run_dst_search(args) -> int:
    """Coverage-guided fault search; one JSON stats line.  Exit
    contract: with an injected bug armed, success means found +
    minimized + replay-verified; on a clean tree, success means the
    whole budget ran without a violation."""
    from kwok_tpu.dst import SimOptions
    from kwok_tpu.dst.search import (
        guided_search,
        replay_artifact,
        violation_artifact,
    )

    opts = SimOptions(
        duration=args.dst_duration,
        bug=args.dst_bug,
        store_shards=args.dst_shards,
        fleet_tenants=args.dst_fleet_tenants,
    )
    log = (lambda m: print(m, flush=True)) if args.dst_verbose else None
    res = guided_search(
        opts, budget=args.search_budget, search_seed=args.search_seed, log=log
    )
    stats = res.stats()
    stats["search_seed"] = args.search_seed
    stats["bug"] = args.dst_bug
    if res.found is not None:
        art = violation_artifact(opts, res.found, res.minimized)
        rep = replay_artifact(art)
        stats["replay_ok"] = rep["ok"]
        if args.search_out:
            with open(args.search_out, "w") as f:
                json.dump(art, f, indent=1, sort_keys=True)
            stats["artifact"] = args.search_out
        print(json.dumps(stats))
        # armed bug rediscovered and pinned = success; a violation on a
        # clean tree is a real finding = failure
        ok = rep["ok"] and (args.dst_bug is not None)
        return 0 if ok else 1
    print(json.dumps(stats))
    return 1 if args.dst_bug is not None else 0


def run_dst_replay(args) -> int:
    """Re-execute a pinned violation artifact; exit 0 iff the trace
    digest and the violation set replay byte-identically."""
    from kwok_tpu.dst.search import replay_artifact

    with open(args.dst_replay) as f:
        doc = json.load(f)
    rep = replay_artifact(doc)
    print(json.dumps(rep))
    return 0 if rep["ok"] else 1


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.dst_replay:
        return run_dst_replay(args)
    if args.dst_search:
        return run_dst_search(args)
    if args.dst:
        return run_dst(args)
    if args.smoke:
        report = run_smoke(seed=args.seed if args.seed is not None else 42, pods=args.pods)
        print(json.dumps(report))
        return 0
    if args.overload_smoke:
        report = run_overload_smoke(
            seed=args.seed if args.seed is not None else 42,
            duration=args.flood_seconds,
        )
        print(json.dumps(report))
        return 0
    if args.corruption_smoke:
        report = run_corruption_smoke(
            seed=args.seed if args.seed is not None else 42,
            pods=args.pods,
        )
        print(json.dumps(report))
        return 0
    if args.exhaustion_smoke:
        report = run_exhaustion_smoke(
            seed=args.seed if args.seed is not None else 42,
            pods=args.pods,
        )
        print(json.dumps(report))
        return 0
    if args.fleet_smoke:
        report = run_fleet_smoke(
            seed=args.seed if args.seed is not None else 42,
            tenants=args.fleet_tenants,
            flood_seconds=args.flood_seconds,
        )
        print(json.dumps(report))
        return 0
    if args.failover_smoke:
        report = run_failover_smoke(
            seed=args.seed if args.seed is not None else 42,
            lease_duration=args.lease_seconds,
        )
        print(json.dumps(report))
        return 0
    plan = load_profile(args.profile) if args.profile else FaultPlan()
    if args.seed is not None:
        plan.seed = args.seed
    if args.print_schedule:
        print(json.dumps(plan.to_dict(), indent=2))
        return 0
    if args.cluster:
        report = drive_cluster(plan, args.cluster, args.supervise)
        print(json.dumps(report, indent=2))
        return 0
    build_parser().print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
