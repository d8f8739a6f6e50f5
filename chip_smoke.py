#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system starts on the chip.

Drives the main path once, through the entry points a user calls, at a
size users call real, and checks what comes out by the repo's own
means.  With no arguments it is the full-size run and needs a TPU:

1. **Deployed path.**  ``kwokctl create cluster --backend device`` with
   every component at its default (separate apiserver, scheduler, kcm
   and kwok processes, HTTP, WAL, APF, leader election), ``kwokctl
   scale node --replicas 1000``, then 100,000 pods at 100 per node
   through ``ClusterClient.bulk`` with ``spec.nodeName`` set (upstream's
   documented size, reference README.md:24-25; BASELINE.json's second
   configuration).  Then it answers requests as a client would: a watch
   that sees status events, a paged LIST of all pods (every one Running
   with a podIP and Ready=True), all nodes Ready, the kwok daemon's
   ``/metrics`` (what it runs on, transitions and lease renewals counted
   on the device backend, zero swallowed tick errors, native drain
   loaded), a delete of 1,000 pods that drains through the device
   player's delete stage, and a seeded sample of pods and nodes whose
   statuses equal what the host ``Lifecycle`` engine — the plain
   reference — produces for the same objects with time fields masked.
   The chip holder of this phase is the kwok daemon.
2. **Full-width SoA.**  1,000,000 pod rows of pod-general + pod-chaos
   and 10,000 node rows with a lease lane on one chip, ticked for a few
   macro-ticks of 8 (what ``bench.py::build_pod_sim`` builds), peak
   device memory reported.  In the same process a 65,536-row population
   runs on the chip and on ``jax.devices("cpu")[0]`` from the same seed:
   final ``stage``, ``fire_at`` and fired counts must be equal, and
   ``check_feature_parity`` must pass on the chip for rows that fired.

The parent process never imports ``jax`` (a process that has touched
JAX holds the chip); each phase runs in a child, one after the other,
and phase two starts only after phase one's chip holder is gone.

Exit code 0 and, as the last line of stdout, one JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
only if every check passed.  On any failure: a non-zero code, the
reasons on stderr, the component logs in the output directory, and no
result line.  Sizes may be overridden for a dry run on the CPU
(``JAX_PLATFORMS=cpu`` given explicitly); such a run says ``cpu`` and
its numbers are counts, not device measurements.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import re
import shutil
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, "chip_smoke_out")
CLUSTER = "chip-smoke"
#: the whole run, compilation included, must end inside the contract's
#: 1200 s; the phases' own limits sum to less
PHASE_LIMIT_S = {"deployed": 700, "soa": 420}

FULL = {
    "nodes": 1_000,
    "pods_per_node": 100,
    "delete_pods": 1_000,
    "soa_pods": 1_000_000,
    "soa_nodes": 10_000,
    "parity_rows": 65_536,
    "macro_ticks": 4,
}
SOA_DT_MS = 500
FINALIZER = "kwok.x-k8s.io/fake"


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


_T0 = time.monotonic()


class Failed(Exception):
    """A check did not hold; the message says which."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)


# ---------------------------------------------------------------- phase one


def _metrics(port: int) -> list:
    """The kwok daemon's /metrics as [(name, labels, value)]."""
    import urllib.request

    from kwok_tpu.utils.promtext import iter_samples

    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=30
    ).read().decode()
    return list(iter_samples(body))


def _metric(ms: list, name: str, **labels) -> float:
    """Sum of the series of ``name`` carrying ``labels``; -1 if none."""
    vals = [v for n, ls, v in ms if n == name and labels.items() <= ls.items()]
    return sum(vals) if vals else -1.0


def _mask_times(x):
    """Replace every time-valued field by a token: the cluster stamps
    wall time, the reference engine whatever ``Now`` it is given."""
    if isinstance(x, dict):
        return {
            k: "<time>"
            if isinstance(v, str) and re.search(r"(Time|At|Timestamp)$", k)
            else _mask_times(v)
            for k, v in x.items()
        }
    if isinstance(x, list):
        return [_mask_times(v) for v in x]
    return x


def _reference_status(lifecycle, obj: dict, funcs: dict) -> dict:
    """What the host Lifecycle engine makes of ``obj``: apply every
    stage that is due at once, stop at the first one that waits (the
    600 s node heartbeat) or when nothing matches."""
    import datetime

    from kwok_tpu.utils.patch import apply_patch

    now = datetime.datetime.now(datetime.timezone.utc)
    funcs = dict(funcs)
    funcs["Now"] = lambda: now.isoformat().replace("+00:00", "Z")
    rng = random.Random(0)
    for _ in range(16):
        meta = obj.get("metadata") or {}
        stage = lifecycle.select(
            meta.get("labels") or {}, meta.get("annotations") or {}, obj, rng
        )
        if stage is None or stage.delay(obj, now, rng)[0] > 0:
            return obj.get("status") or {}
        eff = lifecycle.effects(stage)
        fin = eff.finalizers_patch(meta.get("finalizers") or [])
        if fin is not None:
            obj = apply_patch(obj, fin.data, fin.type)
        for p in eff.patches(obj, funcs):
            obj = apply_patch(obj, p.data, p.type)
    raise Failed("reference engine did not settle in 16 stages")


def _ready(obj: dict) -> bool:
    return any(
        c.get("type") == "Ready" and c.get("status") == "True"
        for c in (obj.get("status") or {}).get("conditions") or []
    )


def _kwokctl(*argv: str, timeout: float = 300) -> None:
    cmd = [sys.executable, "-m", "kwok_tpu.cmd.kwokctl", "--name", CLUSTER, *argv]
    proc = subprocess.run(cmd, stdout=sys.stderr, stderr=sys.stderr, timeout=timeout)
    check(proc.returncode == 0, f"kwokctl {' '.join(argv)} exited {proc.returncode}")


def _wait(pred, timeout: float, what: str, poll: float = 1.0):
    deadline = time.monotonic() + timeout
    while True:
        got = pred()
        if got:
            return got
        check(time.monotonic() < deadline, f"timed out after {timeout:.0f}s: {what}")
        time.sleep(poll)


def _save_logs(rt, out: str) -> list:
    """Export the component logs to ``out`` as ``kwokctl export logs``
    does (all that comes back from the chip tool); returns the names
    of component logs that hold a Traceback."""
    dest = os.path.join(out, "logs")
    bad = []
    for fn in rt.collect_logs(dest):
        if fn.endswith(".log") and fn != "audit.log":
            with open(os.path.join(dest, fn), "rb") as f:
                if b"Traceback (most recent call last)" in f.read():
                    bad.append(fn)
    return bad


def phase_deployed(a) -> dict:
    import yaml

    from kwok_tpu.api.config import KwokConfiguration
    from kwok_tpu.controllers.node_controller import node_funcs
    from kwok_tpu.controllers.pod_controller import PodEnv
    from kwok_tpu.ctl.runtime import BinaryRuntime
    from kwok_tpu.ctl.scale import DEFAULT_NODE_TEMPLATE
    from kwok_tpu.engine.lifecycle import Lifecycle
    from kwok_tpu.stages import default_node_stages, default_pod_stages

    n_pods = a.nodes * a.pods_per_node
    check(a.delete_pods <= n_pods, "--delete-pods exceeds the population")
    res = {"nodes": a.nodes, "pods": n_pods, "deleted": a.delete_pods, "wall_s": {}}
    wall = res["wall_s"]
    rt = BinaryRuntime(CLUSTER)
    conf_path = os.path.join(WORK, "kwok-config.yaml")
    # capacity = the population rounded up to a power of two, so the
    # SoA never doubles (and recompiles every program) mid-run
    capacity = 1 << max(n_pods, a.nodes, 64).bit_length()
    with open(conf_path, "w", encoding="utf-8") as f:
        yaml.safe_dump(
            {
                "apiVersion": "config.kwok.x-k8s.io/v1alpha1",
                "kind": "KwokConfiguration",
                "options": {"deviceCapacity": capacity},
            },
            f,
        )
    res["device_capacity"] = capacity
    watcher = None
    try:
        t0 = time.monotonic()
        _kwokctl("create", "cluster", "--backend", "device", "--config", conf_path,
                 "--wait", "120")
        client = rt.client(timeout=180.0)
        kubelet = rt.load_config()["ports"]["kubelet"]
        with open(os.path.join(rt.workdir, "pids", "kwok-controller.pid"),
                  encoding="utf-8") as f:
            pid = res["kwok_pid"] = int(f.read().strip())

        def daemon_device():
            check(rt.component_alive("kwok-controller"),
                  "the kwok daemon exited at start (see logs/kwok-controller.log)")
            try:
                ms = _metrics(kubelet)
            except OSError:
                return None
            for name, d, _v in ms:
                if name == "kwok_device_info":
                    return {"platform": d["platform"], "kind": d["device_kind"],
                            "count": int(d["devices"])}
            return None

        res["device"] = _wait(daemon_device, 180, "kwok daemon reports its device")
        wall["create_cluster"] = round(time.monotonic() - t0, 1)
        log(f"kwok daemon pid {pid} runs on {res['device']}")

        t0 = time.monotonic()
        _kwokctl("scale", "node", "--replicas", str(a.nodes), timeout=600)
        def ready_nodes():
            nodes = client.list("Node")[0]
            return nodes if len(nodes) == a.nodes and all(map(_ready, nodes)) else None

        _wait(ready_nodes, 240, f"{a.nodes} nodes Ready")
        wall["nodes_ready"] = round(time.monotonic() - t0, 1)
        log(f"{a.nodes} nodes Ready in {wall['nodes_ready']}s")

        # pods as the reference's benchmark generator makes them (the
        # kwokctl scale pod template, spec.nodeName set).  The pods to
        # be deleted later carry a finalizer, so their delete is
        # graceful in the store and only the device player's pod-delete
        # stage can finish it.
        def pod(i: int) -> dict:
            meta = {"name": f"pod-{i}", "namespace": "default"}
            if i >= n_pods - a.delete_pods:
                meta["finalizers"] = [FINALIZER]
            return {
                "apiVersion": "v1",
                "kind": "Pod",
                "metadata": meta,
                "spec": {
                    "nodeName": f"node-{i // a.pods_per_node}",
                    "containers": [{"name": "app", "image": "fake-image"}],
                    "tolerations": [{"key": "kwok.x-k8s.io/node",
                                     "operator": "Exists", "effect": "NoSchedule"}],
                },
            }

        # a client that watches one node's pods, from before they exist
        watcher = client.watch("Pod", field_selector={"spec.nodeName": "node-0"})
        t0 = time.monotonic()
        for lo in range(0, n_pods, 5000):
            results = client.bulk(
                [{"verb": "create", "data": pod(i)}
                 for i in range(lo, min(lo + 5000, n_pods))]
            )
            bad = [r for r in results if r.get("status") != "ok"]
            check(not bad, f"bulk create failed: {bad[:1]}")
        wall["pods_created"] = round(time.monotonic() - t0, 1)

        def pod_transitions():
            return _metric(_metrics(kubelet), "kwok_stage_transitions_total",
                           kind="Pod", backend="device")

        last = [0.0]

        def all_played():
            n = pod_transitions()
            if n != last[0]:
                last[0] = n
                log(f"pod transitions on the device backend: {int(n)}/{n_pods}")
            return n >= n_pods

        _wait(all_played, 420, f"{n_pods} pod transitions on the device backend", 2.0)
        wall["pods_running"] = round(time.monotonic() - t0, 1)

        t0 = time.monotonic()
        pods, _rv = client.list_paged("Pod", page_size=5000)
        wall["list_pods"] = round(time.monotonic() - t0, 1)
        check(len(pods) == n_pods, f"LIST returned {len(pods)} pods, want {n_pods}")
        not_running = [
            p["metadata"]["name"] for p in pods
            if (p.get("status") or {}).get("phase") != "Running"
            or not (p.get("status") or {}).get("podIP") or not _ready(p)
        ]
        check(not not_running,
              f"{len(not_running)} pods not Running/podIP/Ready, e.g. {not_running[:3]}")
        ips = {p["status"]["podIP"] for p in pods}
        check(len(ips) == n_pods, f"{n_pods - len(ips)} duplicate pod IPs")
        nodes = ready_nodes()
        check(nodes is not None, "not all nodes Ready")
        log(f"LIST: {n_pods} pods Running with unique IPs, {a.nodes} nodes Ready")

        # the watch saw node-0's pods arrive and turn Running
        seen = set()
        deadline = time.monotonic() + 60
        while len(seen) < a.pods_per_node and time.monotonic() < deadline:
            ev = watcher.next(timeout=1.0)
            if ev is not None and ev.type == "MODIFIED" and (
                    ev.object.get("status") or {}).get("phase") == "Running":
                seen.add(ev.object["metadata"]["name"])
        res["watch_running_events"] = len(seen)
        check(len(seen) == a.pods_per_node,
              f"watch saw {len(seen)}/{a.pods_per_node} pods of node-0 turn Running")

        # statuses against the plain reference, on a seeded sample
        conf = KwokConfiguration()
        rng = random.Random(a.seed)
        node_by_name = {n["metadata"]["name"]: n for n in nodes}
        sample_nodes = rng.sample(nodes, min(len(nodes), max(10, a.nodes // 100)))
        sample_pods = rng.sample(pods, min(len(pods), max(100, n_pods // 1000)))
        node_lc = Lifecycle(default_node_stages(lease=True))
        pod_lc = Lifecycle(default_pod_stages())
        init_status = yaml.safe_load(
            DEFAULT_NODE_TEMPLATE.replace("{{ Name }}", "x"))["status"]
        nf = node_funcs(conf.node_ip, conf.node_name, conf.node_port)

        class _Nodes:  # the CacheGetter surface PodEnv reads
            @staticmethod
            def get(name):
                return node_by_name.get(name)

        env = PodEnv(cidr=conf.cidr, node_ip=conf.node_ip, node_getter=_Nodes)
        diffs = []
        for n in sample_nodes:
            want = _reference_status(node_lc, {**n, "status": init_status}, nf)
            if _mask_times(want) != _mask_times(n["status"]):
                diffs.append(("Node", n["metadata"]["name"]))
        for p in sample_pods:
            # which address a pod gets is the allocator's order, not the
            # object's: the reference is handed the one the cluster gave
            # (env.funcs reads it from the observed pod); everything
            # else in the status it has to produce itself
            want = _reference_status(pod_lc, {**p, "status": {}}, env.funcs(p))
            if _mask_times(want) != _mask_times(p["status"]):
                diffs.append(("Pod", p["metadata"]["name"]))
        res["reference_sample"] = {"pods": len(sample_pods), "nodes": len(sample_nodes)}
        check(not diffs, f"{len(diffs)} statuses differ from the host Lifecycle "
                         f"engine's, e.g. {diffs[:3]}")
        log(f"statuses equal the host Lifecycle engine's on {len(sample_pods)} pods "
            f"and {len(sample_nodes)} nodes")

        # scale-down through the device player's delete stage
        t0 = time.monotonic()
        doomed = [f"pod-{i}" for i in range(n_pods - a.delete_pods, n_pods)]
        results = client.bulk([{"verb": "delete", "kind": "Pod", "name": n,
                                "namespace": "default"} for n in doomed])
        check(all(r.get("status") == "ok" for r in results), "bulk delete failed")
        _wait(lambda: client.count("Pod") == n_pods - a.delete_pods, 240,
              f"{a.delete_pods} deletes drained by the device player")
        wall["pods_deleted"] = round(time.monotonic() - t0, 1)

        ms = _metrics(kubelet)
        m = {
            "pod_transitions": _metric(ms, "kwok_stage_transitions_total",
                                       kind="Pod", backend="device"),
            "node_transitions": _metric(ms, "kwok_stage_transitions_total",
                                        kind="Node", backend="device"),
            "host_transitions": _metric(ms, "kwok_stage_transitions_total",
                                        backend="host"),
            "lease_renewals": _metric(ms, "kwok_lease_renewals_total"),
            "tick_errors": _metric(ms, "kwok_tick_errors_total"),
            "pod_on_device": _metric(ms, "kwok_stage_backend_info",
                                     kind="Pod", backend="device"),
            "node_on_device": _metric(ms, "kwok_stage_backend_info",
                                      kind="Node", backend="device"),
            "native_drain_kwok": _metric(ms, "kwok_native_loaded", unit="fastdrain"),
            "compilations": _metric(ms, "kwok_jit_compilations_total"),
            "compile_seconds": _metric(ms, "kwok_jit_compile_seconds_total"),
            "cache_hits": _metric(ms, "kwok_jit_compile_cache_hits_total"),
            "cache_misses": _metric(ms, "kwok_jit_compile_cache_misses_total"),
            "tick_lag_mean_s": round(
                _metric(ms, "kwok_tick_lag_seconds_sum", kind="Pod")
                / max(_metric(ms, "kwok_tick_lag_seconds_count", kind="Pod"), 1), 6),
        }
        m["native_drain_apiserver"] = (client.stats().get("native") or {}).get("fastdrain")
        res["kwok_metrics"] = m
        check(m["pod_on_device"] == 1 and m["node_on_device"] == 1,
              "Pod and Node are not both on the device backend")
        check(m["host_transitions"] <= 0, "a host controller played transitions")
        check(m["pod_transitions"] >= n_pods + a.delete_pods,
              f"pod transitions {m['pod_transitions']} < {n_pods + a.delete_pods}")
        check(m["node_transitions"] >= a.nodes,
              f"node transitions {m['node_transitions']} < {a.nodes}")
        check(m["lease_renewals"] >= a.nodes,
              f"lease renewals {m['lease_renewals']} < {a.nodes}")
        check(m["tick_errors"] == 0, f"{m['tick_errors']} swallowed tick-loop exceptions")
        check(m["native_drain_kwok"] == 1, "native drain not loaded in the kwok daemon")
        check(m["native_drain_apiserver"] == "loaded",
              f"native drain in the apiserver: {m['native_drain_apiserver']}")

        # a clean exit inside the runtime's timeout: the daemon must
        # release the chip itself, not be SIGKILLed holding it
        watcher.stop()
        watcher = None
        t0 = time.monotonic()
        _kwokctl("stop", "cluster")
        wall["stop_cluster"] = round(time.monotonic() - t0, 1)
        with open(os.path.join(rt.workdir, "logs", "kwok-controller.log"),
                  encoding="utf-8", errors="replace") as f:
            check("kwok controller standing by" in f.read(),
                  "the kwok daemon did not finish its shutdown before the "
                  "runtime's SIGKILL")
    finally:
        if watcher is not None:
            watcher.stop()
        bad = _save_logs(rt, a.out)
        try:
            _kwokctl("delete", "cluster")
        finally:
            pid = res.get("kwok_pid")
            res["kwok_pid_gone"] = pid is None or _pid_gone(pid, 5)
    check(not bad, f"Traceback in component logs: {bad}")
    check(res["kwok_pid_gone"], f"kwok daemon pid {res['kwok_pid']} still alive")
    return res


# ---------------------------------------------------------------- phase two


def phase_soa(a) -> dict:
    import numpy as np

    from kwok_tpu.utils import accel

    cache_dir = accel.enable_compile_cache()
    dev = accel.require_accelerator()
    import jax

    import bench
    from kwok_tpu.engine.compiler import NEVER
    from kwok_tpu.engine.simulator import Transition
    from kwok_tpu.ops.tick import LeaseLane, lease_tick

    res = {
        "device": {"platform": dev["platform"], "kind": dev["device_kind"],
                   "count": dev["count"]},
        "compile_cache_dir": cache_dir,
        "soa_pods": a.soa_pods,
        "soa_nodes": a.soa_nodes,
        "wall_s": {},
    }
    wall = res["wall_s"]
    log(f"phase two runs on {res['device']}")

    # -- the north-star state on one chip --------------------------------
    t0 = time.monotonic()
    bench.N_PODS, bench.N_NODES = a.soa_pods, a.soa_nodes
    pod_sim = bench.build_pod_sim()
    node_sim = bench.build_node_sim()
    lane = LeaseLane(
        fire_at=jax.numpy.full(a.soa_nodes, 10_000, jax.numpy.int32),
        key=jax.random.PRNGKey(a.seed),
    )
    wall["build"] = round(time.monotonic() - t0, 1)
    n_stages = len(pod_sim.cset.compiled)
    fired = renewed = 0
    tick_s, pod_tick_s = [], []
    for k in range(a.macro_ticks):
        t0 = time.monotonic()
        stages, _t0_ms = pod_sim.tick_many(SOA_DT_MS, 8)
        # dispatch of 8 ticks + the blocking read of their [8, N] int8
        pod_tick_s.append(round(time.monotonic() - t0, 3))
        node_stages, _ = node_sim.tick_many(SOA_DT_MS, 8)
        lane, due, _lag = lease_tick(
            lane, jax.numpy.int32(node_sim.now_ms), jax.numpy.int32(10_000),
            jax.numpy.int32(400))
        renewed += int(np.asarray(due).sum())
        tick_s.append(round(time.monotonic() - t0, 3))
        check(stages.shape == (8, a.soa_pods) and stages.dtype == np.int8,
              f"fired-stage array is {stages.dtype}{stages.shape}")
        check(int(stages.min()) >= -1 and int(stages.max()) < n_stages,
              "fired-stage index out of range")
        fired += int((stages >= 0).sum()) + int((node_stages >= 0).sum())
        log(f"macro-tick {k}: {tick_s[-1]}s, {fired} rows fired so far")
    pod_sim._ensure_synced()
    check(int(pod_sim.active.sum()) == a.soa_pods, "pod rows went inactive")
    armed = pod_sim.fire_at[pod_sim.fire_at != NEVER]
    check(armed.size > 0 and int(armed.min()) >= 0, "no timers armed after ticking")
    check(fired > 0, "nothing fired at full width")
    check(renewed == a.soa_nodes,
          f"lease lane renewed {renewed} of {a.soa_nodes} leases in "
          f"{node_sim.now_ms} virtual ms")
    # first macro-tick includes the compiles; the others are one smoke
    # observation each, not a benchmark
    wall["macro_tick_first"] = tick_s[0]
    wall["macro_tick_rest"] = tick_s[1:]
    wall["pod_tick_many_rest"] = pod_tick_s[1:]
    res["fired"] = fired
    res["lease_renewals"] = renewed
    stats = jax.devices()[0].memory_stats() or {}
    res["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    res["bytes_limit"] = stats.get("bytes_limit")
    # what the SoA holds by its shapes, to read the peak against: a
    # compiler that tiled [N, C] int32 to 128 lanes would show ~10x this
    res["soa_logical_bytes"] = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for sim in (pod_sim, node_sim)
        for x in (sim.features, sim.sig, sim.ovc, sim.stage, sim.fire_at,
                  sim.active, sim.rematch, sim.del_ts)
    )
    del pod_sim, node_sim, lane

    # -- chip against the CPU backend, same seed -------------------------
    def trajectory(materialize_rows: int):
        bench.N_PODS = a.parity_rows
        sim = bench.build_pod_sim()
        count = 0
        touched = set()
        for _ in range(a.macro_ticks):
            stages, t0_ms = sim.tick_many(SOA_DT_MS, 8)
            count += int((stages >= 0).sum())
            for k in range(stages.shape[0]):
                for row in np.nonzero(stages[k, :materialize_rows] >= 0)[0]:
                    s_idx = int(stages[k, row])
                    sim.materialize(Transition(
                        int(row), s_idx, sim.cset.compiled[s_idx].name,
                        t0_ms + (k + 1) * SOA_DT_MS,
                        bool(sim.cset.stage_delete[s_idx]), None))
                    touched.add(int(row))
        sim._ensure_synced()
        return sim, count, sorted(touched)

    t0 = time.monotonic()
    chip, chip_fired, touched = trajectory(materialize_rows=512)
    check(len(touched) > 0, "no sampled row fired in the parity population")
    chip.check_feature_parity(touched)
    with jax.default_device(jax.devices("cpu")[0]):
        ref, ref_fired, _ = trajectory(materialize_rows=0)
    wall["parity"] = round(time.monotonic() - t0, 1)
    res["parity"] = {
        "rows": a.parity_rows,
        "fired": chip_fired,
        "feature_parity_rows": len(touched),
        "reference": "jax cpu backend" if dev["platform"] != "cpu"
        else "jax cpu backend (this run is on the cpu too)",
    }
    check(chip_fired == ref_fired and chip_fired > 0,
          f"fired {chip_fired} on {dev['platform']} vs {ref_fired} on the cpu backend")
    check(np.array_equal(chip.stage, ref.stage), "final stage differs from the cpu backend's")
    check(np.array_equal(chip.fire_at, ref.fire_at),
          "final fire_at differs from the cpu backend's")
    res["compile"] = accel.compile_stats()
    return res


# ------------------------------------------------------------------- parent


def _child(phase: str, a, env: dict) -> dict:
    """Run one phase in its own process; its result comes back through
    a file, its progress goes to stderr."""
    assert "jax" not in sys.modules, "the parent must never import jax"
    result = os.path.join(a.out, f"{phase}.json")
    if os.path.exists(result):
        os.remove(result)
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase, "--out", a.out,
           "--seed", str(a.seed)]
    for key in FULL:
        cmd += [f"--{key.replace('_', '-')}", str(getattr(a, key))]
    t0 = time.monotonic()
    proc = subprocess.Popen(cmd, env=env, start_new_session=True)
    try:
        rc = proc.wait(timeout=PHASE_LIMIT_S[phase])
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait()
        raise Failed(f"phase {phase} exceeded {PHASE_LIMIT_S[phase]}s") from None
    finally:
        _reap_cluster()
    check(rc == 0 and os.path.exists(result), f"phase {phase} failed (exit {rc})")
    with open(result, encoding="utf-8") as f:
        res = json.load(f)
    res["phase_wall_s"] = round(time.monotonic() - t0, 1)
    return res


def _reap_cluster() -> None:
    """Backstop for a phase-one child that died before its own finally:
    no daemon of the run may outlive it."""
    piddir = os.path.join(WORK, "home", "clusters", CLUSTER, "pids")
    if not os.path.isdir(piddir):
        return
    for fn in os.listdir(piddir):
        try:
            with open(os.path.join(piddir, fn), encoding="utf-8") as f:
                os.kill(int(f.read().strip()), signal.SIGKILL)
        except (OSError, ValueError):
            pass
    shutil.rmtree(os.path.join(WORK, "home"), ignore_errors=True)


def _pid_gone(pid: int, timeout: float) -> bool:
    deadline = time.monotonic() + timeout
    while os.path.exists(f"/proc/{pid}") and time.monotonic() < deadline:
        time.sleep(0.2)
    return not os.path.exists(f"/proc/{pid}")


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    for key, val in FULL.items():
        p.add_argument(f"--{key.replace('_', '-')}", type=int, default=val,
                       help=f"default {val:,} (the full-size run)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=WORK,
                   help="where results and component logs go (default chip_smoke_out/)")
    p.add_argument("--phase", choices=sorted(PHASE_LIMIT_S), help=argparse.SUPPRESS)
    return p.parse_args(argv)


def main(argv=None) -> int:
    a = parse(argv)
    a.out = os.path.abspath(a.out)
    if not os.path.isdir(os.path.join(ROOT, "kwok_tpu")):
        print("chip_smoke: kwok_tpu/ is not beside this script; run it from the "
              "root of a checkout", file=sys.stderr)
        return 2
    os.makedirs(a.out, exist_ok=True)
    os.makedirs(WORK, exist_ok=True)

    if a.phase:
        try:
            res = {"deployed": phase_deployed, "soa": phase_soa}[a.phase](a)
        except Failed as exc:
            print(f"chip_smoke[{a.phase}]: FAILED: {exc}", file=sys.stderr)
            return 1
        tmp = os.path.join(a.out, f"{a.phase}.json.tmp")
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(res, f, indent=1)
        os.replace(tmp, os.path.join(a.out, f"{a.phase}.json"))
        return 0

    from kwok_tpu.utils.accel import pinned_platforms

    full = all(getattr(a, k) == v for k, v in FULL.items())
    pinned = pinned_platforms()
    if full and pinned and pinned[0] == "cpu":
        print("chip_smoke: the full-size run needs the TPU and JAX_PLATFORMS pins "
              "the CPU; for a dry run on the CPU override the sizes "
              "(see --help)", file=sys.stderr)
        return 2

    env = dict(os.environ)
    env["KWOK_TPU_HOME"] = os.path.join(WORK, "home")
    env["PYTHONPATH"] = ROOT + (os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    try:
        _reap_cluster()  # a run that was killed may have left one behind
        one = _child("deployed", a, env)
        check(_pid_gone(one["kwok_pid"], 30),
              f"phase one's chip holder (pid {one['kwok_pid']}) is still alive")
        env2 = dict(env)
        if pinned and "cpu" not in pinned:
            # phase two compares against jax.devices("cpu")[0]: the CPU
            # backend has to exist beside the accelerator, which stays
            # first and so stays the default (and still must initialise)
            env2["JAX_PLATFORMS"] = ",".join(pinned + ["cpu"])
        two = _child("soa", a, env2)
        for name, res in (("one", one), ("two", two)):
            check(res["device"]["platform"] == "tpu" or not full,
                  f"phase {name} ran on {res['device']['platform']}, not on the tpu")
        check(one["device"] == two["device"],
              f"the phases saw different devices: {one['device']} vs {two['device']}")
    except Failed as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1

    km = one["kwok_metrics"]
    device = two["device"]
    label = device["platform"] if full else f"{device['platform']} (reduced sizes: dry run)"
    gib = (f"{two['peak_bytes_in_use'] / 2**20:.1f} MiB (SoA by its shapes: "
           f"{two['soa_logical_bytes'] / 2**20:.1f} MiB)"
           if two.get("peak_bytes_in_use") else "not reported by this backend")
    print(f"platform: {device['platform']}  device_kind: {device['kind']}  "
          f"devices: {device['count']}  [{label}]")
    print(f"phase one (deployed path, device read back from kwok daemon pid "
          f"{one['kwok_pid']}: {one['device']}): {one['nodes']} nodes / "
          f"{one['pods']} pods, {one['deleted']} deleted; wall {one['phase_wall_s']}s "
          f"{one['wall_s']}")
    print(f"  transitions on device: Pod {int(km['pod_transitions'])}, Node "
          f"{int(km['node_transitions'])}; lease renewals {int(km['lease_renewals'])}; "
          f"tick errors {int(km['tick_errors'])}; reference sample "
          f"{one['reference_sample']}; watch events {one['watch_running_events']}")
    print(f"  kwok daemon jit: {int(km['compilations'])} programs, "
          f"{km['compile_seconds']}s compiling, cache hits {int(km['cache_hits'])} / "
          f"misses {int(km['cache_misses'])}; native drain: apiserver "
          f"{km['native_drain_apiserver']}, kwok daemon "
          f"{'loaded' if km['native_drain_kwok'] == 1 else 'NOT loaded'}")
    print(f"phase two (full-width SoA): {two['soa_pods']} pod rows + {two['soa_nodes']} "
          f"node rows with leases, {two['fired']} rows fired, peak device memory {gib}; "
          f"wall {two['phase_wall_s']}s {two['wall_s']}")
    print(f"  parity: {two['parity']}")
    print(f"  jit: {two['compile']} (cache at {two['compile_cache_dir']})")
    summary = {"ok": True, "device": device, "full_size": full,
               "deployed": one, "soa": two}
    with open(os.path.join(a.out, "summary.json"), "w", encoding="utf-8") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
