"""Chaos e2e: the ISSUE 3 acceptance scenario.

1. Watch resume across a real apiserver crash: SIGKILL the daemon
   mid-watch, restart it from the WAL, and assert the reflector
   resumes at the right resourceVersion with NO full re-list while the
   backlog drains through.
2. Full-cluster convergence under a seeded fault plan: a kwokctl
   cluster with HTTP fault injection armed (503s with Retry-After,
   added latency, watch-stream drops), the apiserver SIGKILLed by the
   chaos process driver and resurrected by the component supervisor —
   the workload must converge to the fault-free final state, zero
   acknowledged writes lost (WAL replay, canary-verified), recovery
   time bounded and recorded as a self-metric.
"""

import os
import signal
import subprocess
import sys
import threading
import time

import pytest
import yaml

from kwok_tpu.cluster.client import ApiUnavailable, ClusterClient, RetryPolicy
from kwok_tpu.cluster.informer import Informer, WatchOptions
from kwok_tpu.cluster.store import Conflict, NotFound
from kwok_tpu.utils.backoff import Backoff
from kwok_tpu.utils.queue import Queue


def _wait(pred, timeout, poll=0.25):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(poll)
    return pred()


def _retry():
    return RetryPolicy(
        seed=42, max_attempts=8, budget_s=20.0, backoff=Backoff(duration=0.05, cap=1.0)
    )


def _must(fn, *a, **kw):
    """Ack a mutation under chaos: ApiUnavailable means the server may
    or may not have applied it — replay until a definitive answer."""
    deadline = time.monotonic() + 60
    while True:
        try:
            return fn(*a, **kw)
        except ApiUnavailable:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.2)
        # Conflict, not AlreadyExists: the REST client maps every 409
        # to the base Conflict, and no op here carries preconditions —
        # a 409 on replay means the first attempt landed
        except Conflict:
            return None
        except NotFound:
            return None


# ------------------------------------------------- watch resume across crash


def _spawn_apiserver(workdir, port):
    return subprocess.Popen(
        [
            sys.executable,
            "-m",
            "kwok_tpu.cmd.apiserver",
            "--port",
            str(port),
            "--state-file",
            os.path.join(workdir, "state.json"),
            "--wal-file",
            os.path.join(workdir, "wal.jsonl"),
            # huge save interval: recovery must come from the WAL, not
            # a lucky snapshot
            "--save-interval",
            "3600",
        ],
        stdout=open(os.path.join(workdir, "apiserver.log"), "ab"),
        stderr=subprocess.STDOUT,
        env={
            **os.environ,
            "PYTHONPATH": os.path.dirname(os.path.dirname(__file__)),
            "JAX_PLATFORMS": "cpu",
        },
        start_new_session=True,
    )


def test_informer_resumes_across_apiserver_restart(tmp_path):
    from kwok_tpu.ctl.components import free_port

    port = free_port()
    proc = _spawn_apiserver(str(tmp_path), port)
    second = None
    events: Queue = Queue()
    done = threading.Event()
    try:
        client = ClusterClient(f"http://127.0.0.1:{port}", retry=_retry())
        assert client.wait_ready(30)
        for i in range(3):
            client.create(
                {
                    "apiVersion": "v1",
                    "kind": "Pod",
                    "metadata": {"name": f"pre-{i}", "namespace": "default"},
                    "spec": {"nodeName": "n0"},
                    "status": {},
                }
            )
        inf = Informer(client, "Pod")
        cache = inf.watch_with_cache(WatchOptions(), events, done=done)
        assert _wait(lambda: len(cache) == 3, 15)
        assert inf.relists == 1

        # kill -9 mid-watch: no graceful save, no final snapshot
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        proc.wait(timeout=10)
        time.sleep(0.5)

        second = _spawn_apiserver(str(tmp_path), port)
        client2 = ClusterClient(f"http://127.0.0.1:{port}", retry=_retry())
        assert client2.wait_ready(30)
        # the restarted server recovered every acked write from the WAL
        pods, _ = client2.list("Pod")
        assert sorted(p["metadata"]["name"] for p in pods) == [
            "pre-0",
            "pre-1",
            "pre-2",
        ]
        # backlog created while the reflector is still reconnecting
        for i in range(2):
            client2.create(
                {
                    "apiVersion": "v1",
                    "kind": "Pod",
                    "metadata": {"name": f"post-{i}", "namespace": "default"},
                    "spec": {"nodeName": "n0"},
                    "status": {},
                }
            )
        # the reflector drains the backlog through a RESUME: the watch
        # reconnects at its last delivered rv (served from the
        # WAL-rebuilt history ring) — never a second list
        assert _wait(lambda: len(cache) == 5, 30), (
            f"cache={len(cache)} relists={inf.relists} resumes={inf.resumes}"
        )
        assert inf.relists == 1, "reflector was forced into a re-list"
        assert inf.resumes >= 1
        with open(os.path.join(str(tmp_path), "apiserver.log"), "rb") as f:
            log = f.read().decode(errors="replace")
        assert "replayed" in log, log  # WAL replay actually ran
    finally:
        done.set()
        for p in (proc, second):
            if p is not None and p.poll() is None:
                os.killpg(os.getpgid(p.pid), signal.SIGKILL)
                p.wait(timeout=10)


# ----------------------------------------- full cluster under a seeded plan


CHAOS_PROFILE = {
    "kind": "ChaosProfile",
    "seed": 42,
    # active across the whole scenario, including post-restart
    "duration": 600,
    "http": {
        "latency": {"p": 0.05, "seconds": 0.01},
        "reject": {"p": 0.05, "status": 503, "retryAfter": 0.1},
        "watchDrop": {"p": 0.02},
    },
}

N_REPLICAS = 3
N_CANARIES = 8
RECOVERY_BOUND_S = 60.0


def test_cluster_converges_under_seeded_fault_plan(tmp_path, monkeypatch):
    import random

    from kwok_tpu.chaos.plan import FaultPlan, ProcessFaultSpec
    from kwok_tpu.chaos.process_faults import ProcessFaultDriver
    from kwok_tpu.cmd.kwokctl import main as kwokctl_main
    from kwok_tpu.ctl.runtime import BinaryRuntime, ComponentSupervisor

    monkeypatch.setenv("KWOK_TPU_HOME", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    profile = tmp_path / "chaos.yaml"
    profile.write_text(yaml.safe_dump(CHAOS_PROFILE))

    name = "chaos-e2e"
    assert (
        kwokctl_main(
            [
                "--name",
                name,
                "create",
                "cluster",
                "--chaos-profile",
                str(profile),
                "--wait",
                "90",
            ]
        )
        == 0
    )
    rt = BinaryRuntime(name)
    client = rt.client()
    client._retry = _retry()
    sup = ComponentSupervisor(rt, rng=random.Random(42)).start()
    try:
        assert kwokctl_main(["--name", name, "scale", "node", "--replicas", "2"]) == 0
        _must(
            client.create,
            {
                "apiVersion": "apps/v1",
                "kind": "Deployment",
                "metadata": {"name": "web", "namespace": "default"},
                "spec": {
                    "replicas": N_REPLICAS,
                    "selector": {"matchLabels": {"app": "web"}},
                    "template": {
                        "metadata": {"labels": {"app": "web"}},
                        "spec": {"containers": [{"name": "c", "image": "img"}]},
                    },
                },
            },
        )

        def running_web():
            try:
                pods, _ = client.list("Pod", label_selector="app=web")
            except (ApiUnavailable, OSError):
                return -1
            return sum(
                1
                for p in pods
                if (p.get("status") or {}).get("phase") == "Running"
                and not (p.get("metadata") or {}).get("deletionTimestamp")
            )

        assert _wait(lambda: running_web() == N_REPLICAS, 180), (
            f"{running_web()}/{N_REPLICAS} Running under HTTP faults"
        )

        # our own reflector rides the same faulty boundary; its
        # counters are the no-forced-re-list observable
        events: Queue = Queue()
        done = threading.Event()
        inf = Informer(client, "ConfigMap")
        cache = inf.watch_with_cache(WatchOptions(), events, done=done)
        assert _wait(lambda: inf.relists == 1, 60)

        # acked canaries, then the seeded kill: every one must survive
        for i in range(N_CANARIES):
            _must(
                client.create,
                {
                    "apiVersion": "v1",
                    "kind": "ConfigMap",
                    "metadata": {"name": f"canary-{i}", "namespace": "default"},
                    "data": {"i": str(i)},
                },
            )

        plan = FaultPlan(
            seed=42,
            duration=10.0,
            process=[ProcessFaultSpec(component="apiserver", at=0.2, action="kill")],
        )
        t_kill = time.monotonic()
        ProcessFaultDriver(rt, plan).run()  # blocking; kill fires at 0.2s
        assert _wait(lambda: rt.ready(timeout=5), RECOVERY_BOUND_S), (
            f"apiserver not resurrected; supervisor events: {sup.events}"
        )
        recovery_s = time.monotonic() - t_kill
        assert any(e["action"] == "restarted" for e in sup.events), sup.events

        # zero lost acknowledged writes (WAL replay audit)
        def canaries():
            try:
                return client.count("ConfigMap")
            except (ApiUnavailable, OSError):
                return -1

        assert _wait(lambda: canaries() >= N_CANARIES, 30), (
            f"only {canaries()}/{N_CANARIES} canaries after WAL recovery"
        )

        # convergence continues to the fault-free final state: scale up
        _must(client.scale, "Deployment", "web", N_REPLICAS + 2)
        assert _wait(lambda: running_web() == N_REPLICAS + 2, 180), (
            f"{running_web()}/{N_REPLICAS + 2} Running after recovery"
        )

        # the reflector survived the crash without a forced re-list,
        # and saw the post-restart world (canaries via resume)
        assert _wait(lambda: len(cache) >= N_CANARIES, 30), (
            f"cache={len(cache)} relists={inf.relists} resumes={inf.resumes}"
        )
        assert inf.relists == 1, (
            f"re-list forced across restart (resumes={inf.resumes})"
        )

        # recovery time: recorded as a supervisor self-metric, bounded
        assert sup.recovery_times, sup.events
        assert max(sup.recovery_times) < RECOVERY_BOUND_S
        assert recovery_s < RECOVERY_BOUND_S
        done.set()
    finally:
        sup.stop()
        assert kwokctl_main(["--name", name, "delete", "cluster"]) == 0
