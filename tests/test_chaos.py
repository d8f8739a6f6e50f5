"""Chaos subsystem units: fault plans, the HTTP injector, the store
WAL (append/replay/compact/crash points), the client RetryPolicy, the
informer's resume-without-relist, and the component supervisor's
restart/crash-loop logic (driven clock, no subprocesses)."""

import json
import os
import random
import threading
import time

import pytest

from kwok_tpu.chaos import FaultPlan, HttpFaultInjector, load_profile
from kwok_tpu.chaos.plan import HttpFaultSpec, PartitionWindow, ProcessFaultSpec
from kwok_tpu.cluster.apiserver import APIServer
from kwok_tpu.cluster.client import (
    ApiUnavailable,
    ClusterClient,
    RetryPolicy,
)
from kwok_tpu.cluster.informer import Informer, WatchOptions
from kwok_tpu.cluster.store import Expired, NotFound, ResourceStore
from kwok_tpu.cluster.wal import WriteAheadLog, read_records
from kwok_tpu.utils.backoff import Backoff
from kwok_tpu.utils.queue import Queue


def pod(name, ns="default", node=None):
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": name, "namespace": ns},
        "spec": {"nodeName": node or "n0"},
        "status": {},
    }


# ----------------------------------------------------------------- fault plans


def test_profile_roundtrip_and_determinism(tmp_path):
    prof = tmp_path / "chaos.yaml"
    prof.write_text(
        """
kind: ChaosProfile
seed: 7
duration: 12
http:
  latency: {p: 0.5, seconds: 0.01}
  reject: {p: 0.25, status: 429, retryAfter: 0.1}
  reset: {p: 0.1}
  watchDrop: {p: 0.2}
  partitions:
    - {client: kwok-controller, at: 2, duration: 3}
process:
  - {component: apiserver, at: 5, action: kill}
  - {component: kwok-controller, at: 3, action: stop, resumeAfter: 1}
"""
    )
    plan = load_profile(str(prof))
    assert plan.seed == 7
    assert plan.http.reject_status == 429
    assert plan.http.partitions[0].client == "kwok-controller"
    # process faults sort by time: the schedule IS the execution order
    assert [p.at for p in plan.process] == [3.0, 5.0]
    # roundtrip through dict form is stable
    assert FaultPlan.from_dict(plan.to_dict()).to_dict() == plan.to_dict()

    # same seed -> same decision sequence; different seed -> different
    def decisions(seed):
        p = FaultPlan.from_dict(plan.to_dict())
        p.seed = seed
        inj = HttpFaultInjector(p, clock=lambda: 0.0)
        inj._clock = lambda: 0.0  # frozen inside the active window
        inj.start()
        return [
            (inj.on_request("GET", "/r/pods", "c") or {}).get("action")
            for _ in range(50)
        ]

    assert decisions(7) == decisions(7)
    assert decisions(7) != decisions(8)


def test_injector_partitions_and_exemptions():
    plan = FaultPlan(
        seed=1,
        duration=100.0,
        http=HttpFaultSpec(
            reject_p=1.0,
            reject_status=503,
            retry_after=0.5,
            partitions=[PartitionWindow(client="kwok", at=0.0, duration=10.0)],
        ),
    )
    t = [0.0]
    inj = HttpFaultInjector(plan, clock=lambda: t[0])
    # health endpoints are never faulted
    assert inj.on_request("GET", "/healthz", "kwok") is None
    # partitioned client is reset, others get the 503 with Retry-After
    assert inj.on_request("GET", "/r/pods", "kwok")["action"] == "reset"
    act = inj.on_request("GET", "/r/pods", "other")
    assert act["action"] == "reject" and act["status"] == 503
    assert act["retry_after"] == 0.5
    # partition window closes with time
    t[0] = 11.0
    assert inj.on_request("GET", "/r/pods", "kwok")["action"] == "reject"
    # the whole injector goes quiet past its duration
    t[0] = 101.0
    assert inj.on_request("GET", "/r/pods", "other") is None
    assert inj.snapshot()["partition"] == 1


def test_injector_watch_drop_deterministic():
    plan = FaultPlan(
        seed=3, duration=100.0, http=HttpFaultSpec(watch_drop_p=0.5)
    )
    inj = HttpFaultInjector(plan, clock=lambda: 1.0)
    seq = [inj.on_watch_tick("c") for _ in range(40)]
    inj2 = HttpFaultInjector(plan, clock=lambda: 1.0)
    assert seq == [inj2.on_watch_tick("c") for _ in range(40)]
    assert any(seq) and not all(seq)


# ------------------------------------------------------------------------ WAL


def test_wal_replay_restores_state_and_counters(tmp_path):
    wal_path = str(tmp_path / "wal.jsonl")
    s = ResourceStore()
    s.attach_wal(WriteAheadLog(wal_path, fsync="off"))
    s.create(pod("a"))
    s.create(pod("b"))
    s.patch("Pod", "a", {"status": {"phase": "Running"}}, "merge", subresource="status")
    s.apply_status_batch("Pod", [("default", "b", {"phase": "Succeeded"})])
    s.delete("Pod", "a")
    live = s.dump_state()

    r = ResourceStore()
    assert r.replay_wal(wal_path) > 0
    assert r.dump_state() == live
    assert r.resource_version == s.resource_version
    # uid continuity: the next create must not reuse a logged uid
    uid_a = (live["objects"][0].get("metadata") or {}).get("uid")
    new = r.create(pod("c"))
    assert new["metadata"]["uid"] != uid_a


def test_wal_snapshot_compaction_and_combined_recovery(tmp_path):
    wal_path = str(tmp_path / "wal.jsonl")
    state_path = str(tmp_path / "state.json")
    s = ResourceStore()
    s.attach_wal(WriteAheadLog(wal_path, fsync="off"))
    for i in range(5):
        s.create(pod(f"p{i}"))
    s.save_file(state_path)
    # snapshot covers the creates: the log compacts behind it
    assert list(read_records(wal_path)) == []
    s.patch("Pod", "p0", {"status": {"phase": "Running"}}, "merge", subresource="status")
    s.delete("Pod", "p4")
    live = s.dump_state()

    r = ResourceStore()
    r.load_file(state_path)
    r.replay_wal(wal_path)
    assert r.dump_state() == live


def test_wal_torn_tail_is_ignored(tmp_path):
    wal_path = str(tmp_path / "wal.jsonl")
    s = ResourceStore()
    s.attach_wal(WriteAheadLog(wal_path, fsync="off"))
    s.create(pod("a"))
    s.create(pod("b"))
    with open(wal_path, "a", encoding="utf-8") as f:
        f.write('{"t": "ev", "rv": 99, "e": "ADDED", "o": {"kind": "P')  # torn
    r = ResourceStore()
    assert r.replay_wal(wal_path) == 2
    assert r.count("Pod") == 2
    assert r.resource_version == 2


def test_wal_replay_populates_history_for_watch_resume(tmp_path):
    wal_path = str(tmp_path / "wal.jsonl")
    state_path = str(tmp_path / "state.json")
    s = ResourceStore()
    s.attach_wal(WriteAheadLog(wal_path, fsync="off"))
    s.create(pod("a"))
    s.save_file(state_path)
    rv_snapshot = s.resource_version
    s.create(pod("b"))
    s.create(pod("c"))

    r = ResourceStore()
    r.load_file(state_path)
    r.replay_wal(wal_path)
    # a watcher that saw the snapshot rv resumes and replays the two
    # creates from the rebuilt history ring — no re-list needed
    w = r.watch("Pod", since_rv=rv_snapshot)
    evs = w.drain()
    assert [e.object["metadata"]["name"] for e in evs] == ["b", "c"]
    # but a resume from BELOW the boot snapshot answers Expired (the
    # ring predates it): the informer then re-lists, never silently
    # missing events
    with pytest.raises(Expired):
        r.watch("Pod", since_rv=rv_snapshot - 1)


def test_store_crash_points(tmp_path):
    wal_path = str(tmp_path / "wal.jsonl")

    class Crash(RuntimeError):
        pass

    s = ResourceStore()
    s.attach_wal(WriteAheadLog(wal_path, fsync="off"))

    def crash_before(phase):
        if phase == "before-commit":
            raise Crash(phase)

    s.set_crash_hook(crash_before)
    with pytest.raises(Crash):
        s.create(pod("a"))
    # crashed before the commit: nothing visible, nothing logged
    assert s.count("Pod") == 0
    assert list(read_records(wal_path)) == []

    def crash_after(phase):
        if phase == "after-commit":
            raise Crash(phase)

    s.set_crash_hook(crash_after)
    with pytest.raises(Crash):
        s.create(pod("a"))
    # crashed after commit+WAL, before the ack: the write is durable —
    # a replayed store has it even though the caller saw a failure
    assert s.count("Pod") == 1
    r = ResourceStore()
    r.replay_wal(wal_path)
    assert r.count("Pod") == 1
    s.set_crash_hook(None)
    s.delete("Pod", "a")


# ------------------------------------------------------------- client retries


class _FlakyInjector:
    """Rejects the first N non-exempt requests, then stays clean."""

    def __init__(self, rejects, status=503, retry_after=0.01):
        self.remaining = rejects
        self.status = status
        self.retry_after = retry_after
        self.seen_clients = []

    def on_request(self, method, path, client_id):
        self.seen_clients.append(client_id)
        if self.remaining > 0:
            self.remaining -= 1
            return {
                "action": "reject",
                "status": self.status,
                "retry_after": self.retry_after,
            }
        return None

    def on_watch_tick(self, client_id):
        return False


def _fast_retry(**kw):
    kw.setdefault("max_attempts", 6)
    kw.setdefault("budget_s", 10.0)
    kw.setdefault("backoff", Backoff(duration=0.01, cap=0.05))
    kw.setdefault("seed", 0)
    return RetryPolicy(**kw)


def test_client_retries_through_503_and_stamps_client_id():
    store = ResourceStore()
    inj = _FlakyInjector(rejects=3)
    with APIServer(store, fault_injector=inj) as srv:
        c = ClusterClient(srv.url, retry=_fast_retry(), client_id="test-client")
        out = c.create(pod("a"))
        assert out["metadata"]["name"] == "a"
        assert store.count("Pod") == 1
        assert "test-client" in inj.seen_clients


def test_client_exhausted_retries_raise_typed_api_unavailable():
    store = ResourceStore()
    inj = _FlakyInjector(rejects=10_000, status=429)
    with APIServer(store, fault_injector=inj) as srv:
        c = ClusterClient(srv.url, retry=_fast_retry(max_attempts=3))
        with pytest.raises(ApiUnavailable) as ei:
            c.get("Pod", "nope")
        assert ei.value.attempts == 3
        assert ei.value.last_status == 429


def test_client_connection_refused_is_api_unavailable_not_oserror():
    c = ClusterClient(
        "http://127.0.0.1:1",  # nothing listens on port 1
        retry=_fast_retry(max_attempts=2),
    )
    with pytest.raises(ApiUnavailable):
        c.get("Pod", "nope")


def test_retry_schedule_is_seeded_and_reproducible():
    a = _fast_retry(seed=5)
    b = _fast_retry(seed=5)
    sched_a = [a.delay(i, None) for i in range(6)]
    sched_b = [b.delay(i, None) for i in range(6)]
    assert sched_a == sched_b
    # Retry-After puts a floor under the jittered delay
    assert _fast_retry(seed=5).delay(0, 3.0) >= 3.0


# ------------------------------------------------------ informer resume logic


def test_informer_resumes_watch_without_relist():
    store = ResourceStore()
    store.create(pod("a"))
    inf = Informer(store, "Pod")
    events: Queue = Queue()
    done = threading.Event()
    try:
        inf.watch_with_cache(WatchOptions(), events, done=done)
        deadline = time.monotonic() + 5
        while inf.relists < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert inf.relists == 1
        # kill the live stream the way a chaos drop does
        deadline = time.monotonic() + 5
        while inf.active_watcher is None and time.monotonic() < deadline:
            time.sleep(0.01)
        inf.active_watcher.stop()
        store.create(pod("b"))
        # the reflector reconnects at its last rv: the new event arrives
        # through a resume, not another list
        deadline = time.monotonic() + 5
        got = []
        while time.monotonic() < deadline:
            ev, ok = events.get_or_wait(timeout=0.2)
            if ok and ev.object.get("metadata", {}).get("name") == "b":
                got.append(ev)
                break
        assert got, "event after stream death never arrived"
        assert inf.resumes >= 1
        assert inf.relists == 1
    finally:
        done.set()


# ---------------------------------------------------------------- supervisor


class _StubRuntime:
    """Duck-typed BinaryRuntime for clock-driven supervisor tests."""

    def __init__(self, names):
        from kwok_tpu.ctl.components import Component

        self._comps = [Component(name=n, args=["x"]) for n in names]
        self.alive = {n: True for n in names}
        self.started = []

    def load_components(self):
        return list(self._comps)

    def component_alive(self, name):
        return self.alive[name]

    def start_component(self, comp):
        self.started.append(comp.name)
        self.alive[comp.name] = True

    def client(self, timeout=2.0):
        raise OSError("no cluster behind the stub")


def _mk_supervisor(rt, **kw):
    from kwok_tpu.ctl.runtime import ComponentSupervisor

    kw.setdefault("backoff", Backoff(duration=1.0, factor=2.0, jitter=0.0))
    kw.setdefault("rng", random.Random(0))
    return ComponentSupervisor(rt, **kw)


def test_supervisor_restarts_dead_component_with_backoff():
    rt = _StubRuntime(["kwok-controller"])
    sup = _mk_supervisor(rt)
    sup.tick(now=0.0)
    assert rt.started == []  # alive: nothing to do
    rt.alive["kwok-controller"] = False
    sup.tick(now=1.0)  # notices death, schedules restart at 1.0+1.0
    assert rt.started == []
    sup.tick(now=1.5)
    assert rt.started == []  # backoff not elapsed
    sup.tick(now=2.1)
    assert rt.started == ["kwok-controller"]
    sup.tick(now=2.2)  # alive again -> recovery recorded
    assert sup.recovery_times and sup.recovery_times[0] == pytest.approx(1.2)
    assert [e["action"] for e in sup.events] == ["died", "restarted", "recovered"]


def test_supervisor_detects_crash_loop_and_parks():
    rt = _StubRuntime(["kcm"])
    sup = _mk_supervisor(rt, crash_loop_threshold=3, crash_loop_window=1000.0)
    now = 0.0
    for _ in range(3):
        rt.alive["kcm"] = False
        sup.tick(now=now)  # died -> schedule
        due = sup._restart_due["kcm"]
        sup.tick(now=due)  # restart fires
        now = due + 0.5
        sup.tick(now=now)  # recovered
        now += 0.5
    assert rt.started == ["kcm"] * 3
    rt.alive["kcm"] = False
    sup.tick(now=now)
    sup.tick(now=now + 100.0)
    assert "kcm" in sup.crash_looped
    assert rt.started == ["kcm"] * 3  # parked: no fourth restart
    assert any(e["action"] == "crash-loop" for e in sup.events)


# ------------------------------------------------------------ chaos __main__


def test_chaos_print_schedule_roundtrip(tmp_path, capsys):
    from kwok_tpu.chaos.__main__ import main

    prof = tmp_path / "p.yaml"
    prof.write_text(
        "kind: ChaosProfile\nseed: 9\nduration: 5\n"
        "process:\n  - {component: apiserver, at: 1, action: kill}\n"
    )
    assert main(["--profile", str(prof), "--print-schedule"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["seed"] == 9
    assert doc["process"][0]["action"] == "kill"


def test_wal_compact_does_not_race_concurrent_appends(tmp_path):
    """save_file's compact closes and reopens the log; a concurrent
    create wave must never observe the closed handle (regression: the
    daemon's periodic save 400'd in-flight creates with 'I/O operation
    on closed file')."""
    wal_path = str(tmp_path / "wal.jsonl")
    state_path = str(tmp_path / "state.json")
    s = ResourceStore()
    s.attach_wal(WriteAheadLog(wal_path, fsync="off"))
    stop = threading.Event()
    errs = []
    threads = []
    for w in range(2):
        def writer_w(w=w):
            i = 0
            while not stop.is_set():
                try:
                    s.create(pod(f"w{w}-{i}"))
                except Exception as exc:  # noqa: BLE001
                    errs.append(exc)
                    return
                i += 1

        t = threading.Thread(target=writer_w)
        t.start()
        threads.append(t)
    for _ in range(25):
        s.save_file(state_path)
        time.sleep(0.004)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errs, errs[0]
    s.save_file(state_path)
    live = s.dump_state()
    r = ResourceStore()
    r.load_file(state_path)
    r.replay_wal(wal_path)
    assert r.count("Pod") == s.count("Pod")
    assert r.dump_state() == live
