"""Resource-exhaustion resilience: disk-full/fsync-failure safe WAL,
degraded read-only mode, readiness-gated supervision.

Covers the exhaustion layer end to end at unit scale (the live-window
integration is ``python -m kwok_tpu.chaos --exhaustion-smoke``):

- WAL: ENOSPC classified, the in-flight append rides the emergency
  reserve, fsync failure poisons (seals) the handle, re-arm probes;
- store: degraded read-only gate (503 semantics), Lease exemption,
  commit rollback when even the reserve cannot make a record durable —
  memory and log never diverge on a refused ack;
- apiserver: /healthz vs /readyz split, Retry-After on degraded 503s;
- client: wait_writable, retry accounting (degraded vs overload);
- supervisor: not-ready-but-alive consumes no restart budget and never
  parks as crash-loop; SIGKILL mid-window recovers via boot_recover
  with an honest RecoveryReport;
- DST: the exhaustion-honesty checker flags synthetic violations.
"""

from __future__ import annotations

import errno
import json
import os
import random
import threading

import pytest

from kwok_tpu.chaos.fs_pressure import FsPressure
from kwok_tpu.cluster.store import (
    DEGRADED_EXEMPT_KINDS,
    ResourceStore,
    StorageDegraded,
)
from kwok_tpu.cluster.wal import (
    WalExhausted,
    WriteAheadLog,
    classify_os_error,
    fsck,
    scan,
)
from kwok_tpu.utils.backoff import Backoff


def _pod(n, ns="default"):
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": n, "namespace": ns},
        "spec": {},
        "status": {},
    }


def _lease(name="test-lease"):
    return {
        "apiVersion": "coordination.k8s.io/v1",
        "kind": "Lease",
        "metadata": {"name": name, "namespace": "kube-system"},
        "spec": {"holderIdentity": "a", "leaseDurationSeconds": 10},
    }


# ------------------------------------------------------------------ wal unit


def test_classify_os_error_classes():
    assert classify_os_error(OSError(errno.ENOSPC, "x")) == "disk-full"
    assert classify_os_error(OSError(errno.EIO, "x")) == "io-error"
    if hasattr(errno, "EDQUOT"):
        assert classify_os_error(OSError(errno.EDQUOT, "x")) == "quota"


def test_reserve_saves_the_inflight_append_and_degrades(tmp_path):
    p = str(tmp_path / "wal.jsonl")
    wal = WriteAheadLog(p, fsync="off")
    assert os.path.exists(p + ".reserve")
    wal.append({"t": "ev", "rv": 1, "u": 1, "e": "ADDED", "o": {}})
    wal.set_pressure(FsPressure("disk-full"))
    # the write that hits ENOSPC still lands: reserve released, tail
    # repaired, frames rewritten on a fresh handle
    wal.append({"t": "ev", "rv": 2, "u": 2, "e": "ADDED", "o": {}})
    assert wal.degraded and wal.degraded["reason"] == "disk-full"
    assert not os.path.exists(p + ".reserve")
    assert wal.enospc_total >= 1
    # freed headroom keeps serving (the lease-renewal budget)
    wal.append({"t": "ev", "rv": 3, "u": 3, "e": "MODIFIED", "o": {}})
    wal.set_pressure(None)
    assert wal.try_rearm() is True
    assert wal.degraded is None
    assert os.path.exists(p + ".reserve")
    assert wal.rearms_total == 1
    wal.close()
    s = scan(p)
    assert s.clean, s.corruptions
    rvs = [r["rv"] for r in s.records if r.get("t") == "ev"]
    assert rvs == [1, 2, 3]
    assert fsck(p)["ok"]


def test_rearm_fails_while_pressure_holds(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "w.jsonl"), fsync="off")
    shim = FsPressure("disk-full")
    wal.set_pressure(shim)
    wal.append({"t": "ev", "rv": 1, "u": 1, "e": "ADDED", "o": {}})
    assert wal.degraded
    # the probe must not re-arm on leftovers of the freed reserve: it
    # requires the reserve itself to fit again
    assert wal.try_rearm() is False
    assert wal.degraded
    wal.close()


def test_quota_window_classifies_edquot(tmp_path):
    if not hasattr(errno, "EDQUOT"):
        pytest.skip("platform without EDQUOT")
    wal = WriteAheadLog(str(tmp_path / "w.jsonl"), fsync="off")
    wal.set_pressure(FsPressure("quota"))
    wal.append({"t": "ev", "rv": 1, "u": 1, "e": "ADDED", "o": {}})
    assert wal.degraded["reason"] == "quota"
    wal.close()


def test_fsync_failure_poisons_and_seals_the_handle(tmp_path):
    p = str(tmp_path / "w.jsonl")
    wal = WriteAheadLog(p, fsync="always")
    wal.append({"t": "ev", "rv": 1, "u": 1, "e": "ADDED", "o": {}})
    segs_before = len([f for f in os.listdir(tmp_path) if ".seg-" in f])
    wal.set_pressure(FsPressure("fsync-error"))
    wal.append({"t": "ev", "rv": 2, "u": 2, "e": "ADDED", "o": {}})
    assert wal.degraded and wal.degraded["reason"] == "fsync-error"
    assert wal.fsync_failures_total >= 1
    # fsyncgate: the active file was sealed whole (rename), a fresh
    # handle opened — the poisoned fd is never fsynced again
    segs_after = len([f for f in os.listdir(tmp_path) if ".seg-" in f])
    assert segs_after > segs_before
    wal.set_pressure(None)
    assert wal.try_rearm()
    wal.close()
    s = scan(p)
    assert s.clean and [r["rv"] for r in s.records if r.get("t") == "ev"] == [1, 2]


def test_exhausted_append_raises_after_reserve_is_spent(tmp_path):
    wal = WriteAheadLog(
        str(tmp_path / "w.jsonl"), fsync="off", reserve_bytes=64
    )
    shim = FsPressure("disk-full")
    wal.set_pressure(shim)
    big = {"t": "ev", "rv": 1, "u": 1, "e": "ADDED", "o": {"pad": "x" * 4096}}
    with pytest.raises(WalExhausted):
        wal.append(big)
    assert wal.degraded
    # sequence continuity survives the refused frame: the next append
    # (after pressure clears) must not leave a seq gap
    wal.set_pressure(None)
    assert wal.try_rearm()
    wal.append({"t": "ev", "rv": 1, "u": 1, "e": "ADDED", "o": {}})
    wal.close()
    s = scan(str(tmp_path / "w.jsonl"))
    assert s.clean, s.corruptions


# ------------------------------------------------------------- store gating


def _pressured_store(tmp_path, reserve_bytes=None):
    kw = {} if reserve_bytes is None else {"reserve_bytes": reserve_bytes}
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"), fsync="off", **kw)
    store = ResourceStore()
    store.attach_wal(wal)
    return store, wal


def test_degraded_gate_rejects_mutations_but_not_reads(tmp_path):
    store, wal = _pressured_store(tmp_path)
    store.create(_pod("a"))
    wal.set_pressure(FsPressure("disk-full"))
    store.create(_pod("b"))  # rides the reserve, flips degraded
    assert store.storage_degraded() is not None
    with pytest.raises(StorageDegraded) as ei:
        store.create(_pod("c"))
    assert ei.value.retry_after > 0
    with pytest.raises(StorageDegraded):
        store.patch("Pod", "a", {"status": {"phase": "Running"}}, "merge")
    with pytest.raises(StorageDegraded):
        store.delete("Pod", "a")
    # reads, lists, watches untouched
    items, _ = store.list("Pod")
    assert {(o["metadata"]["name"]) for o in items} == {"a", "b"}
    w = store.watch("Pod")
    assert w is not None
    w.stop()
    # bulk refuses up front with the machine-readable reason
    with pytest.raises(StorageDegraded):
        store.bulk([{"verb": "create", "data": _pod("d")}])
    wal.set_pressure(None)
    assert store.probe_writable()
    store.create(_pod("e"))
    wal.close()


def test_lease_writes_exempt_from_degraded_gate(tmp_path):
    assert "lease" in DEGRADED_EXEMPT_KINDS
    store, wal = _pressured_store(tmp_path)
    store.create(_lease())
    wal.set_pressure(FsPressure("disk-full"))
    store.create(_pod("trip"))  # flips degraded
    assert store.storage_degraded()
    # renewals (and takeovers) keep flowing on the freed reserve: HA
    # must not collapse because the disk filled
    store.patch(
        "Lease",
        "test-lease",
        {"spec": {"holderIdentity": "b"}},
        "merge",
        namespace="kube-system",
    )
    got = store.get("Lease", "test-lease", namespace="kube-system")
    assert got["spec"]["holderIdentity"] == "b"
    # per-node heartbeat leases are NOT exempt: a big cluster's
    # kube-node-lease churn would drain the reserve and starve the
    # election renewals the exemption exists to protect
    with pytest.raises(StorageDegraded):
        store.create(
            {
                "apiVersion": "coordination.k8s.io/v1",
                "kind": "Lease",
                "metadata": {"name": "node-1", "namespace": "kube-node-lease"},
                "spec": {"holderIdentity": "node-1"},
            }
        )
    wal.set_pressure(None)
    wal.close()


def test_refused_ack_rolls_back_memory_so_log_and_state_agree(tmp_path):
    """When even the reserve cannot take the record (WalExhausted), the
    in-memory commit is rolled back before the ack: a crash+replay must
    agree with what callers were told."""
    store, wal = _pressured_store(tmp_path, reserve_bytes=64)
    store.create(_pod("before"))
    wal.set_pressure(FsPressure("disk-full"))
    with pytest.raises(StorageDegraded):
        store.create(
            {
                "apiVersion": "v1",
                "kind": "Pod",
                "metadata": {"name": "huge", "namespace": "default"},
                "spec": {"pad": "x" * 4096},
                "status": {},
            }
        )
    assert store.count("Pod") == 1  # rolled back
    rv_after = store.resource_version
    wal.set_pressure(None)
    store.probe_writable()
    store.create(_pod("after"))
    live = store.dump_state()
    wal.close()
    fresh = ResourceStore()
    rep = fresh.recover_wal(str(tmp_path / "wal.jsonl"))
    assert rep.clean, rep.summary()
    assert fresh.dump_state() == live
    assert rv_after == int(live["resourceVersion"]) - 1


# ------------------------------------------------ apiserver + client surface


def test_readyz_splits_from_healthz_and_client_waits(tmp_path):
    from kwok_tpu.cluster.apiserver import APIServer
    from kwok_tpu.cluster.client import ClusterClient, RetryPolicy

    store, wal = _pressured_store(tmp_path)
    with APIServer(store) as srv:
        client = ClusterClient(
            srv.url,
            retry=RetryPolicy(
                seed=1,
                max_attempts=50,
                budget_s=20.0,
                backoff=Backoff(duration=0.01, cap=0.05),
                honor_retry_after=False,
            ),
        )
        assert client.healthy() and client.ready()
        wal.set_pressure(FsPressure("disk-full"))
        client.create(_pod("trip"))  # reserve-powered, flips degraded
        ok, reason = client.readiness()
        assert not ok and reason == "StorageDegraded"
        assert client.healthy(), "degraded must stay alive on /healthz"
        assert not client.wait_writable(timeout=0.2)
        # degraded-aware retry rides the window out; accounting splits
        # the cause from overload 429s
        done = {}

        def late():
            done["obj"] = client.create(_pod("late"))

        th = threading.Thread(target=late, daemon=True)
        th.start()
        th.join(timeout=0.3)
        assert th.is_alive(), "write should be retrying against 503s"
        wal.set_pressure(None)
        assert client.wait_writable(timeout=10.0)
        th.join(timeout=10.0)
        assert "obj" in done
        stats = client.retry_stats()
        assert stats["degraded"] >= 1
        assert stats["overload"] == 0
    wal.close()


def test_degraded_503_carries_retry_after_and_reason(tmp_path):
    import http.client

    from kwok_tpu.cluster.apiserver import APIServer

    store, wal = _pressured_store(tmp_path)
    with APIServer(store) as srv:
        wal.set_pressure(FsPressure("disk-full"))
        store.create(_pod("trip"))
        host, port = srv.address
        c = http.client.HTTPConnection(host, port, timeout=5)
        for path, body in (
            ("/r/pods", _pod("x")),
            ("/api/v1/namespaces/default/pods", _pod("y")),
        ):
            c = http.client.HTTPConnection(host, port, timeout=5)
            c.request(
                "POST",
                path,
                body=json.dumps(body),
                headers={"Content-Type": "application/json"},
            )
            resp = c.getresponse()
            payload = json.loads(resp.read())
            assert resp.status == 503
            assert payload.get("reason") == "StorageDegraded"
            assert resp.getheader("Retry-After") is not None
            c.close()
        wal.set_pressure(None)
    wal.close()


def test_overload_429_counts_separately_from_degraded(tmp_path):
    from kwok_tpu.chaos.http_faults import HttpFaultInjector
    from kwok_tpu.chaos.plan import FaultPlan, HttpFaultSpec
    from kwok_tpu.cluster.apiserver import APIServer
    from kwok_tpu.cluster.client import (
        ApiUnavailable,
        ClusterClient,
        RetryPolicy,
    )

    store = ResourceStore()
    inj = HttpFaultInjector(
        FaultPlan(
            seed=3,
            duration=60.0,
            http=HttpFaultSpec(reject_p=1.0, reject_status=429),
        )
    )
    with APIServer(store, fault_injector=inj) as srv:
        client = ClusterClient(
            srv.url,
            retry=RetryPolicy(
                seed=3,
                max_attempts=3,
                budget_s=2.0,
                backoff=Backoff(duration=0.0, cap=0.0),
                honor_retry_after=False,
            ),
        )
        with pytest.raises(ApiUnavailable):
            client.create(_pod("x"))
        stats = client.retry_stats()
        assert stats["overload"] >= 1
        assert stats["degraded"] == 0


# ------------------------------------------------------- supervisor semantics


class _StubClient:
    """healthy/ready toggles standing in for a live apiserver."""

    def __init__(self):
        self.is_healthy = True
        self.is_ready = True
        self.reason = "StorageDegraded"

    def healthy(self):
        return self.is_healthy

    def readiness(self):
        if self.is_ready:
            return True, None
        return False, (self.reason if self.is_healthy else None)


class _StubRuntime:
    def __init__(self):
        from kwok_tpu.ctl.components import Component

        self._comps = [Component(name="apiserver", args=[])]
        self.alive = {"apiserver": True}
        self.started = []
        self.stub_client = _StubClient()

    def load_components(self):
        return list(self._comps)

    def component_alive(self, name):
        return self.alive[name]

    def start_component(self, comp):
        self.started.append(comp.name)
        self.alive[comp.name] = True

    def client(self, timeout=2.0):
        return self.stub_client


def _mk_sup(rt, **kw):
    from kwok_tpu.ctl.runtime import ComponentSupervisor

    kw.setdefault("backoff", Backoff(duration=1.0, factor=2.0, jitter=0.0))
    kw.setdefault("rng", random.Random(0))
    return ComponentSupervisor(rt, **kw)


def test_supervisor_tracks_degraded_without_restarting():
    """Not-ready-but-alive (full disk) for longer than the crash-loop
    window: zero restarts, zero budget consumed, no parking — and the
    state is visible as degraded events."""
    rt = _StubRuntime()
    sup = _mk_sup(rt, crash_loop_threshold=3, crash_loop_window=10.0)
    sup.tick(now=0.0)
    assert sup.degraded == {}
    rt.stub_client.is_ready = False
    for t in range(1, 60):  # 60s >> crash_loop_window
        sup.tick(now=float(t))
    assert rt.started == []  # never restarted
    assert "apiserver" not in sup.crash_looped
    assert sup.degraded == {"apiserver": "StorageDegraded"}
    assert [e["action"] for e in sup.events] == ["degraded"]
    rt.stub_client.is_ready = True
    sup.tick(now=60.0)
    assert sup.degraded == {}
    assert [e["action"] for e in sup.events] == ["degraded", "ready"]


def test_supervisor_restart_budget_untouched_by_degraded_window():
    """After a long degraded window, a real death must restart on the
    FIRST backoff step — the window consumed no restart budget."""
    rt = _StubRuntime()
    sup = _mk_sup(rt, crash_loop_threshold=3, crash_loop_window=1000.0)
    rt.stub_client.is_ready = False
    for t in range(0, 30):
        sup.tick(now=float(t))
    assert rt.started == []
    # now it actually dies
    rt.alive["apiserver"] = False
    rt.stub_client.is_healthy = False
    sup.tick(now=30.0)  # death noticed, restart scheduled at 30+1.0
    sup.tick(now=31.1)
    assert rt.started == ["apiserver"]  # first-step backoff: no debt


def test_supervisor_unreachable_is_not_degraded():
    """A dead apiserver (readiness unreachable) is the liveness path's
    business — it must not be misfiled as degraded."""
    rt = _StubRuntime()
    sup = _mk_sup(rt)
    rt.alive["apiserver"] = False
    rt.stub_client.is_healthy = False
    rt.stub_client.is_ready = False
    sup.tick(now=0.0)
    assert sup.degraded == {}
    assert [e["action"] for e in sup.events] == ["died"]


# ------------------------------------------------- kill-during-window recovery


def test_sigkill_during_pressure_window_boot_recovers_honestly(tmp_path):
    """A process killed mid-window (no close, no final fsync) must come
    back through boot_recover with every acked write accounted: applied
    after replay, or reported — never silently gone."""
    from kwok_tpu.snapshot.pitr import boot_recover

    store, wal = _pressured_store(tmp_path)
    acked = set()

    def track(fn, *a, **kw):
        rv0 = store.resource_version
        out = fn(*a, **kw)
        acked.update(range(rv0 + 1, store.resource_version + 1))
        return out

    for i in range(8):
        track(store.create, _pod(f"p-{i}"))
    wal.set_pressure(FsPressure("disk-full"))
    track(store.create, _pod("inflight"))  # reserve-powered ack
    with pytest.raises(StorageDegraded):
        store.create(_pod("refused"))
    # SIGKILL: no close, no rearm — the file is whatever was flushed
    del wal
    fresh = ResourceStore()
    boot = boot_recover(fresh, None, str(tmp_path / "wal.jsonl"))
    rep = boot["recovery"]
    reported, silent = rep.account(acked)
    assert silent == [], f"silently lost acked writes: {silent}"
    assert reported == [], f"acked writes reported lost: {reported}"
    assert fresh.count("Pod") == 9


# --------------------------------------------------------- DST invariant unit


def test_exhaustion_honesty_checker_flags_synthetic_violations():
    from kwok_tpu.dst.harness import RunRecord
    from kwok_tpu.dst.invariants import run_checks
    from kwok_tpu.dst.trace import Trace

    rec = RunRecord(seed=0, trace=Trace())
    rec.replay_matches = True
    rec.converged = True
    rec.exhaustion_checks = [
        {
            "mode": "disk-full",
            "acked_during": 3,
            "rejections": 2,
            "silent_lost": [41],
            "rearmed": True,
        },
        {
            "mode": "quota",
            "acked_during": 0,
            "rejections": 0,
            "silent_lost": [],
            "rearmed": False,
        },
    ]
    found = run_checks(rec, names=["exhaustion-honesty"])
    msgs = "\n".join(found.get("exhaustion-honesty", []))
    assert "never made durable" in msgs
    assert "did not re-arm" in msgs
    rec.exhaustion_checks = [
        {
            "mode": "disk-full",
            "acked_during": 3,
            "rejections": 2,
            "silent_lost": [],
            "rearmed": True,
        }
    ]
    assert run_checks(rec, names=["exhaustion-honesty"]) == {}
