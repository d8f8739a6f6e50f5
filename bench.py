"""Headline benchmark: sustained pod stage-transitions/sec.

Two measurements, one JSON line:

1. **Kernel** (the headline `value`): 1M simulated pods across 10k fake
   nodes on a single chip (BASELINE.json north star), chaos churn
   (pod-container-running-failed) keeping every pod in a
   CrashLoopBackOff-style transition cycle, node heartbeats ticking in
   a second simulator. Measures the device tick loop alone.
2. **End-to-end** (`e2e` field): the full pipeline at 100k pods —
   device tick -> dirty-row drain -> template render -> `store.bulk`
   against a live in-process ResourceStore, watch echoes fed back
   through the informer (SURVEY §7 "hard parts": the dirty-row rate is
   the real constraint). Reports sustained transitions/s, dirty-row
   (patch) rate, and which pipeline component is the bottleneck.

vs_baseline is against the north-star target of 100k transitions/sec
(BASELINE.md); the reference CPU controller's measured ceiling is ~20
object transitions/sec/worker x 4 workers (README.md:26-27, default
parallelism) — this kernel replaces that loop wholesale.

Where it runs: on the accelerator JAX finds, and nowhere else by
accident.  No accelerator and no explicit ``JAX_PLATFORMS=cpu`` is an
error (kwok_tpu/utils/accel.py) — there is no retry and no CPU
fallback.  Every result carries ``platform``, ``device_kind`` and
``device_count``.  A run pinned to the CPU (tools/check.sh's smoke)
executes every section for correctness and prints COUNTS for the two
device sections, never a rate under a device metric's name.  Any crash
still emits the one JSON line; a section that raised makes the exit
code non-zero.
"""

from __future__ import annotations

import json
import os
import sys
import time

N_PODS = int(os.environ.get("BENCH_PODS", 1_000_000))
N_NODES = int(os.environ.get("BENCH_NODES", 10_000))
TICKS = int(os.environ.get("BENCH_TICKS", 600))
DT_MS = int(os.environ.get("BENCH_DT_MS", 100))
E2E_PODS = int(os.environ.get("BENCH_E2E_PODS", 1_000_000))
#: sub-ticks per device dispatch in the e2e loop (macro-tick): amortizes
#: the blocking device read across K ticks; the drain still processes
#: each sub-tick's rows at its own virtual time
E2E_MACRO = int(os.environ.get("BENCH_E2E_MACRO", 8))
#: wall-clock cap for each e2e phase (admission, warm-up, measure): an
#: over-ambitious population must degrade to a shorter measurement, not
#: an unbounded bench run
E2E_BUDGET_S = float(os.environ.get("BENCH_E2E_BUDGET_S", 180))
#: measurement: best of N windows of W seconds (the steady-state drain
#: is bursty per macro-tick, so windows must cover several)
E2E_WINDOWS = max(1, int(os.environ.get("BENCH_E2E_WINDOWS", 4)))
E2E_WINDOW_S = float(os.environ.get("BENCH_E2E_WINDOW_S", 30))
#: run the ownerReference-GC / namespace controller alongside the
#: measurement (default ON: production clusters always compose the kcm
#: seat, so the headline number should include it)
E2E_GC = os.environ.get("BENCH_E2E_GC", "1") not in ("0", "false")
TARGET_TPS = 100_000.0
#: seconds of seeded best-effort flood for the overload/shedding
#: measurement (0 disables)
OVERLOAD_S = float(os.environ.get("BENCH_OVERLOAD_S", 1.5))
#: scheduling-scenario bench (kwok_tpu.sched): node fleet size; 0
#: disables the section.  Scenario mixes scale off it.
SCHED_NODES = int(os.environ.get("BENCH_SCHED_NODES", 32))
#: gangs of SCHED_GANG_SIZE in the training mix
SCHED_GANGS = int(os.environ.get("BENCH_SCHED_GANGS", 6))
SCHED_GANG_SIZE = int(os.environ.get("BENCH_SCHED_GANG_SIZE", 8))
#: sharded-vs-single store A/B (kwok_tpu.cluster.sharding): target
#: population for the direct-dispatch leg (0 disables the section)
STORE_PODS = int(os.environ.get("BENCH_STORE_PODS", min(N_PODS, 1_000_000)))
STORE_SHARDS = int(os.environ.get("BENCH_STORE_SHARDS", 4))
STORE_WRITERS = int(os.environ.get("BENCH_STORE_WRITERS", 4))
#: wall budget for the routed-HTTP baseline leg (it is the slow one —
#: the whole point of the A/B)
STORE_HTTP_BUDGET_S = float(os.environ.get("BENCH_STORE_HTTP_BUDGET_S", 45))
#: SLO-telemetry overhead guard: pods pushed through the bulk lane
#: with instrumentation armed vs disarmed (0 disables the section;
#: scales down with BENCH_PODS so check.sh's smoke stays fast)
OBS_PODS = int(
    os.environ.get("BENCH_OBS_PODS", min(40_000, max(5_000, N_PODS)))
)
#: fleet-isolation bench (kwok_tpu.fleet): N virtual control planes on
#: one apiserver — per-tenant time-to-first-write after cold-start and
#: the victim-neighbor p99 while another tenant's APF level is flooded
#: (0 disables the section)
FLEET_TENANTS = int(os.environ.get("BENCH_FLEET_TENANTS", 200))
FLEET_FLOOD_S = float(os.environ.get("BENCH_FLEET_FLOOD_S", 1.5))
#: isolation gate: the flooded-neighbor p99 may be at most this
#: multiple of the victim's quiet baseline p99 (the smoke floors the
#: denominator at 5ms so a sub-ms baseline doesn't inflate GIL jitter
#: into a fake starvation signal)
FLEET_ISOLATION_RATIO = float(
    os.environ.get("BENCH_FLEET_ISOLATION_RATIO", 20.0)
)


def run_overload_bench() -> dict:
    """Graceful-degradation counters for the perf trajectory: run the
    in-process overload smoke and distill its shed/queued/latency
    numbers into one compact dict."""
    from kwok_tpu.chaos.__main__ import run_overload_smoke

    rep = run_overload_smoke(seed=42, duration=OVERLOAD_S)
    flood = rep["flood"]
    be = rep["levels"]["best-effort"]
    return {
        "flood_sent": flood["sent"],
        "shed": flood["shed"],
        "served": flood["ok"],
        "queued_peak": be["queued_peak"],
        "canary_writes": rep["canary_writes"],
        "canary_worst_latency_s": rep["canary_worst_latency_s"],
    }


def run_fleet_bench() -> dict:
    """Multi-tenant isolation trajectory: run the in-process fleet
    smoke (N tenants on one apiserver, seeded neighbor flood,
    scale-to-zero) and distill its cold-start/isolation numbers.  On
    top of the smoke's absolute bounds this asserts the isolation
    RATIO — the flooded neighbor's p99 relative to its own quiet
    baseline — so a per-tenant APF regression that merely *slows*
    neighbors (without breaching the absolute bound) still fails."""
    from kwok_tpu.chaos.__main__ import run_fleet_smoke

    rep = run_fleet_smoke(
        seed=42, tenants=FLEET_TENANTS, flood_seconds=FLEET_FLOOD_S
    )
    victim = rep["victim"]
    ratio = victim["isolation_ratio"]
    assert ratio <= FLEET_ISOLATION_RATIO, (
        f"fleet bench: victim p99 {victim['p99_s']}s is {ratio}x its "
        f"quiet baseline {victim['baseline_p99_s']}s under a flooded "
        f"neighbor (gate {FLEET_ISOLATION_RATIO}x)"
    )
    return {
        "tenants": rep["tenants"],
        "cold_start_p50_s": rep["cold_start_p50_s"],
        "cold_start_p99_s": rep["cold_start_p99_s"],
        "flood_shed": rep["flood"]["shed"],
        "victim_p99_s": victim["p99_s"],
        "victim_baseline_p99_s": victim["baseline_p99_s"],
        "victim_shed": victim["shed"],
        "isolation_ratio": ratio,
        "recold_start_s": rep["recold_start_s"],
    }


def run_store_bench() -> dict:
    """Sharded-vs-single bulk-lane write throughput (ROADMAP item 2,
    KUBEDIRECT shape): how fast can writers push pods through the
    store's bulk lane at the 1M-pod scale point?

    Legs (same workload: STORE_WRITERS threads, shard-affine 10k-op
    create batches, one namespace per writer chosen to spread across
    the shards):

    - ``routed_http``: the single-store baseline — the production
      write path, ``ClusterClient.bulk`` through the apiserver
      facade.  Time-boxed (STORE_HTTP_BUDGET_S): it is the slow leg.
    - ``direct_sharded``: STORE_SHARDS shards, colocated KUBEDIRECT
      direct dispatch — the router hands each shard-affine batch to
      the owning shard's bulk lane in-process (the scheduler/workload
      daemon posture after PR 11).  Runs to the full STORE_PODS.
    - no-regression check: the same in-process workload against a
      plain ResourceStore vs the 1-shard router composition — the
      default configuration must not pay for the feature.

    Asserted: direct-dispatch throughput >= 2x the routed baseline,
    and the 1-shard composition within 20% of the plain store (noise
    floor on a loaded 1-core host)."""
    import gc
    import threading

    from kwok_tpu.cluster.apiserver import APIServer
    from kwok_tpu.cluster.client import ClusterClient
    from kwok_tpu.cluster.sharding import (
        build_sharded_store,
        namespaces_covering_shards,
    )
    from kwok_tpu.cluster.store import ResourceStore

    batch = 10_000
    # one namespace per writer, spread across the shard count
    namespaces = namespaces_covering_shards(STORE_SHARDS, "bench-ns")

    def ops_for(writer, start, n):
        ns = namespaces[writer % len(namespaces)]
        return [
            {
                "verb": "create",
                "data": {
                    "apiVersion": "v1",
                    "kind": "Pod",
                    "metadata": {
                        "name": f"w{writer}-{start + j}",
                        "namespace": ns,
                    },
                    "spec": {"nodeName": f"node-{writer}"},
                    "status": {},
                },
            }
            for j in range(n)
        ]

    def drive(bulk_fn, target, budget_s=None):
        """Run the writers; returns (pods_created, seconds)."""
        per = target // STORE_WRITERS
        deadline = (time.time() + budget_s) if budget_s else None
        created = [0] * STORE_WRITERS

        def writer(wi):
            done = 0
            while done < per:
                if deadline and time.time() >= deadline:
                    break
                n = min(batch, per - done)
                bulk_fn(ops_for(wi, done, n))
                done += n
                created[wi] = done

        threads = [
            threading.Thread(target=writer, args=(i,))
            for i in range(STORE_WRITERS)
        ]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return sum(created), time.time() - t0

    # ---- legs 1+2: routed HTTP baseline vs sharded direct dispatch ---
    # best-of-windows, alternating, fresh stores per round — the same
    # measurement discipline leg 3 adopted (r13): single-shot legs on
    # the shared 1-core host skew 20%+ under co-load, and the 2x gate
    # paid that noise with flakes.  Both legs are time-boxed per round
    # (throughput = pods/secs is box-size independent), each round
    # updates both legs' best, and the gate is checked after EVERY
    # round — a clean box pays one round, a noisy one gets up to
    # BENCH_STORE_MULTI_ROUNDS chances before asserting.
    multi_rounds = max(
        1, int(os.environ.get("BENCH_STORE_MULTI_ROUNDS", "3"))
    )
    round_budget = max(5.0, STORE_HTTP_BUDGET_S / multi_rounds)
    routed = {"tps": 0, "pods": 0, "seconds": 0.0}
    direct = {"tps": 0, "pods": 0, "seconds": 0.0}
    speedup = 0.0
    for _ in range(multi_rounds):
        single = ResourceStore()
        with APIServer(single) as srv:
            local = threading.local()

            def http_bulk(ops):
                if not hasattr(local, "client"):
                    local.client = ClusterClient(srv.url)
                local.client.bulk(ops)

            pods, secs = drive(http_bulk, STORE_PODS, budget_s=round_budget)
        if secs and pods / secs > routed["tps"]:
            routed = {
                "tps": round(pods / secs),
                "pods": pods,
                "seconds": round(secs, 1),
            }
        # a leg's dead store must not tax the next leg's gen2 collections
        del single
        gc.collect()

        sharded = build_sharded_store(STORE_SHARDS)
        pods, secs = drive(
            lambda ops: sharded.bulk(ops, copy_results=False),
            STORE_PODS,
            budget_s=round_budget,
        )
        if secs and pods / secs > direct["tps"]:
            direct = {
                "tps": round(pods / secs),
                "pods": pods,
                "seconds": round(secs, 1),
            }
        del sharded
        gc.collect()
        speedup = direct["tps"] / max(1, routed["tps"])
        if speedup >= 2.0:
            break
    assert speedup >= 2.0, (
        f"sharded direct dispatch {direct['tps']} pods/s is only "
        f"{speedup:.2f}x the routed single-store baseline "
        f"{routed['tps']} pods/s over {multi_rounds} best-of windows "
        "(want >= 2x)"
    )

    # ---- leg 3: 1-shard no-regression --------------------------------
    # best-of-windows, alternating, fresh store per round — the e2e
    # leg's measurement discipline: co-load and gen2 pressure on the
    # shared 1-core host skew single runs by 20%+ (r08's in-run 0.69x
    # passed an immediate isolated rerun at 0.94x).  Each round updates
    # both legs' best; the gate checks after EVERY round and stops as
    # soon as it holds, so a clean box pays one round and a noisy one
    # gets up to STORE_ONE_SHARD_ROUNDS chances before asserting.
    small = max(20_000, STORE_PODS // 8)
    rounds = max(1, int(os.environ.get("BENCH_STORE_ONE_SHARD_ROUNDS", "4")))
    plain_tps = one_tps = ratio = 0.0
    for _ in range(rounds):
        plain = ResourceStore()
        p_pods, p_secs = drive(
            lambda ops: plain.bulk(ops, copy_results=False), small
        )
        plain_tps = max(plain_tps, p_pods / p_secs if p_secs else 0.0)
        del plain
        gc.collect()
        one = build_sharded_store(1)
        o_pods, o_secs = drive(
            lambda ops: one.bulk(ops, copy_results=False), small
        )
        one_tps = max(one_tps, o_pods / o_secs if o_secs else 0.0)
        del one
        gc.collect()
        ratio = one_tps / max(1.0, plain_tps)
        if ratio >= 0.8:
            break
    assert ratio >= 0.8, (
        f"1-shard composition regressed the plain store over {rounds} "
        f"best-of windows: {one_tps:.0f} vs {plain_tps:.0f} pods/s "
        f"({ratio:.2f}x)"
    )

    return {
        "shards": STORE_SHARDS,
        "writers": STORE_WRITERS,
        "target_pods": STORE_PODS,
        "routed_http": routed,
        "direct_sharded": direct,
        "speedup": round(speedup, 2),
        "one_shard": {
            "plain_tps": round(plain_tps),
            "sharded1_tps": round(one_tps),
            "ratio": round(ratio, 2),
        },
    }


def run_obs_bench() -> dict:
    """SLO-telemetry overhead guard (the observability tentpole's
    don't-regress contract): the same WAL-backed, watched bulk-lane
    create wave with instrumentation ARMED vs DISARMED, asserted
    within 5%.

    The workload deliberately maximizes the instrumented surface: a
    WAL is attached (per-batch append observation) and a live watcher
    subscribes (per-event commit-time notes feeding the delivery-lag
    series) — the costliest observation paths the armed cluster pays.
    Best-of-3 alternating runs with fresh stores: single runs on the
    shared 1-core host skew past the 5% band on noise alone."""
    import gc
    import tempfile
    import threading

    from kwok_tpu.cluster.store import ResourceStore
    from kwok_tpu.cluster.wal import WriteAheadLog
    from kwok_tpu.utils import telemetry

    batch = 5_000

    def ops_for(start, n):
        return [
            {
                "verb": "create",
                "data": {
                    "apiVersion": "v1",
                    "kind": "Pod",
                    "metadata": {"name": f"obs-{start + j}", "namespace": "default"},
                    "spec": {"nodeName": "node-0"},
                    "status": {},
                },
            }
            for j in range(n)
        ]

    def one_run(tmpdir, tag) -> float:
        store = ResourceStore()
        wal = WriteAheadLog(os.path.join(tmpdir, f"wal-{tag}.jsonl"))
        store.attach_wal(wal)
        watcher = store.watch("Pod")
        stop = threading.Event()

        def drain():
            while not stop.is_set():
                if not watcher.drain():
                    watcher.next(timeout=0.05)

        t = threading.Thread(target=drain, daemon=True)
        t.start()
        t0 = time.time()
        done = 0
        while done < OBS_PODS:
            n = min(batch, OBS_PODS - done)
            store.bulk(ops_for(done, n), copy_results=False)
            done += n
        secs = time.time() - t0
        stop.set()
        watcher.stop()
        t.join(timeout=2)
        del store, wal
        gc.collect()
        return done / secs if secs else 0.0

    armed_tps = disarmed_tps = 0.0
    with tempfile.TemporaryDirectory() as tmpdir:
        for i in range(3):
            prev = telemetry.set_enabled(False)
            try:
                disarmed_tps = max(disarmed_tps, one_run(tmpdir, f"off-{i}"))
            finally:
                telemetry.set_enabled(prev)
            telemetry.set_enabled(True)
            try:
                armed_tps = max(armed_tps, one_run(tmpdir, f"on-{i}"))
            finally:
                telemetry.set_enabled(prev)
    overhead = 1.0 - armed_tps / max(1.0, disarmed_tps)
    assert armed_tps >= 0.95 * disarmed_tps, (
        f"telemetry overhead {overhead * 100:.1f}% exceeds the 5% "
        f"budget ({armed_tps:.0f} armed vs {disarmed_tps:.0f} "
        "disarmed pods/s)"
    )
    return {
        "pods": OBS_PODS,
        "armed_tps": round(armed_tps),
        "disarmed_tps": round(disarmed_tps),
        "overhead_pct": round(overhead * 100, 2),
    }


def _pct(sorted_vals, q: float) -> float:
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1) + 0.5))
    return sorted_vals[i]


def run_sched_bench() -> dict:
    """Scheduling-scenario suite (ROADMAP item 4): seeded workload
    mixes against a live in-process scheduler + gang engine —

    - **burst**: a serverless-style wave of small singleton pods
      (KUBEDIRECT's traffic shape), measuring per-pod time-to-schedule
      (create -> bind observed on the watch stream);
    - **gangs**: long-running training PodGroups placed all-or-nothing
      through the atomic txn lane, measuring gang time-to-schedule
      (last member created -> whole gang bound) and topology locality
      (fraction of each gang on its modal slice — 1.0 = co-located);
    - **churn**: HPA-style scale-down mid-wave (delete half, add more),
      measuring bind latency under membership churn.

    Asserted: every surviving pod binds (a stuck scheduler fails the
    section loudly) and gang locality stays >= 0.9 — binpack must
    actually co-locate on an uncontended fleet.
    """
    import random as _random

    from kwok_tpu.cluster.store import ResourceStore
    from kwok_tpu.controllers.scheduler import Scheduler
    from kwok_tpu.sched.topology import TopologyModel

    rng = _random.Random(42)
    topo = TopologyModel(slice_hosts=8)
    store = ResourceStore()
    sched = Scheduler(store, gang_policy="binpack", topology=topo).start()

    def node(i):
        return {
            "apiVersion": "v1",
            "kind": "Node",
            "metadata": {"name": f"node-{i}", "labels": topo.labels_for(i)},
            "status": {
                "allocatable": {"cpu": "16", "memory": "64Gi", "pods": "110"},
                "conditions": [{"type": "Ready", "status": "True"}],
            },
        }

    def pod(name, cpu="100m", gang=None):
        meta = {"name": name, "namespace": "default"}
        if gang:
            meta["annotations"] = {"kwok.io/pod-group": gang}
        return {
            "apiVersion": "v1",
            "kind": "Pod",
            "metadata": meta,
            "spec": {
                "containers": [
                    {
                        "name": "c",
                        "image": "fake",
                        "resources": {"requests": {"cpu": cpu}},
                    }
                ]
            },
            "status": {},
        }

    out: dict = {"nodes": SCHED_NODES, "scenarios": {}}
    try:
        for i in range(SCHED_NODES):
            store.create(node(i))
        watcher = store.watch("Pod")
        created: dict = {}
        bound: dict = {}
        pod_node: dict = {}

        def drain():
            for ev in watcher.drain():
                meta = ev.object.get("metadata") or {}
                name = meta.get("name")
                nd = (ev.object.get("spec") or {}).get("nodeName")
                if nd and name in created and name not in bound:
                    bound[name] = time.time()
                    pod_node[name] = nd

        def wait_bound(names, budget=60.0):
            deadline = time.time() + budget
            while time.time() < deadline:
                drain()
                if all(n in bound for n in names):
                    return True
                time.sleep(0.005)
            drain()
            return all(n in bound for n in names)

        def tts(names):
            lat = sorted(
                bound[n] - created[n] for n in names if n in bound
            )
            return {
                "tts_p50_s": round(_pct(lat, 0.50), 4),
                "tts_p99_s": round(_pct(lat, 0.99), 4),
            }

        # ---- burst: serverless singleton wave -----------------------
        burst = [f"burst-{i}" for i in range(4 * SCHED_NODES)]
        for n in burst:
            created[n] = time.time()
            store.create(pod(n))
        ok_burst = wait_bound(burst)
        out["scenarios"]["burst"] = {
            "pods": len(burst),
            "bound": sum(1 for n in burst if n in bound),
            **tts(burst),
        }

        # ---- gangs: training PodGroups, all-or-nothing --------------
        gang_stats = []
        gang_names = []
        for g in range(SCHED_GANGS):
            gname = f"train-{g}"
            store.create(
                {
                    "apiVersion": "scheduling.kwok.io/v1alpha1",
                    "kind": "PodGroup",
                    "metadata": {"name": gname, "namespace": "default"},
                    "spec": {"minMember": SCHED_GANG_SIZE, "priority": 10},
                }
            )
            members = [f"{gname}-{i}" for i in range(SCHED_GANG_SIZE)]
            for m in members:
                created[m] = time.time()
                store.create(pod(m, cpu="1", gang=gname))
            t_full = time.time()
            okg = wait_bound(members)
            gang_names.extend(members)
            if okg:
                slices = [
                    topo.coords({"metadata": {"name": pod_node[m]}})[0]
                    for m in members
                ]
                gang_stats.append(
                    {
                        "tts_s": max(bound[m] for m in members) - t_full,
                        "locality": topo.locality(slices),
                    }
                )
        lat = sorted(g["tts_s"] for g in gang_stats)
        locality = (
            sum(g["locality"] for g in gang_stats) / len(gang_stats)
            if gang_stats
            else 0.0
        )
        out["scenarios"]["gangs"] = {
            "gangs": SCHED_GANGS,
            "gang_size": SCHED_GANG_SIZE,
            "placed": len(gang_stats),
            "tts_p50_s": round(_pct(lat, 0.50), 4),
            "tts_p99_s": round(_pct(lat, 0.99), 4),
            "locality": round(locality, 3),
        }

        # ---- churn: HPA-style scale-down mid-wave -------------------
        wave1 = [f"churn-a-{i}" for i in range(2 * SCHED_NODES)]
        for n in wave1:
            created[n] = time.time()
            store.create(pod(n))
        victims = set(rng.sample(wave1, len(wave1) // 2))
        for n in victims:
            store.delete("Pod", n, namespace="default")
        wave2 = [f"churn-b-{i}" for i in range(SCHED_NODES)]
        for n in wave2:
            created[n] = time.time()
            store.create(pod(n))
        churn = [n for n in wave1 if n not in victims] + wave2
        ok_churn = wait_bound(churn)
        out["scenarios"]["churn"] = {
            "pods": len(churn),
            "deleted": len(victims),
            "bound": sum(1 for n in churn if n in bound),
            **tts(churn),
        }

        ok = ok_burst and ok_churn and len(gang_stats) == SCHED_GANGS
        if not ok:
            out["error"] = "unbound pods or unplaced gangs at deadline"
        elif locality < 0.9:
            out["error"] = f"gang locality {locality:.3f} < 0.9"
        out["gangs_scheduled"] = (
            sched.gang.gangs_scheduled if sched.gang else 0
        )
    finally:
        sched.stop()
    return out


def init_backend() -> dict:
    """Place the compile cache, initialise JAX and say what it runs on
    (``platform``, ``device_kind``, ``count``).  Raises when JAX found
    no accelerator and ``JAX_PLATFORMS`` does not name ``cpu``."""
    from kwok_tpu.utils import accel

    accel.enable_compile_cache()
    return accel.require_accelerator()


def make_pod(name: str = "pod") -> dict:
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {
            "name": name,
            "namespace": "default",
            "uid": "uid",
            "labels": {"pod-container-running-failed.stage.kwok.x-k8s.io": "true"},
        },
        "spec": {
            "nodeName": "node",
            "containers": [{"name": "app", "image": "fake"}],
        },
        "status": {},
    }


def build_pod_sim():
    from kwok_tpu.engine.simulator import DeviceSimulator
    from kwok_tpu.stages import load_builtin

    stages = load_builtin("pod-general") + load_builtin("pod-chaos")
    sim = DeviceSimulator(stages, capacity=N_PODS, seed=0)
    sim.admit_bulk(make_pod(), N_PODS)
    return sim


def build_node_sim():
    from kwok_tpu.engine.simulator import DeviceSimulator
    from kwok_tpu.stages import default_node_stages

    sim = DeviceSimulator(default_node_stages(lease=True), capacity=N_NODES, seed=1)
    node = {
        "apiVersion": "v1",
        "kind": "Node",
        "metadata": {"name": "node", "creationTimestamp": "2026-01-01T00:00:00Z"},
        "status": {},
    }
    sim.admit_bulk(node, N_NODES)
    return sim


def run_kernel_bench() -> dict:
    """Device tick loop at 1M pods / 10k nodes; returns the best
    window's rate (``tps``) and the rows that window fired (``fired``)."""
    from kwok_tpu.ops.tick import run_ticks

    pod_sim = build_pod_sim()
    node_sim = build_node_sim()

    pod_params, pod_soa = pod_sim.to_device()
    node_params, node_soa = node_sim.to_device()

    # warm-up: compile + let the FSM reach steady-state churn
    pod_soa, c = run_ticks(pod_params, pod_soa, DT_MS, 100)
    node_soa, _ = run_ticks(node_params, node_soa, DT_MS, 100)
    c.block_until_ready()

    # Best of up to six windows, stopping early above 5M tps.  The
    # early stop was written for a chip that other users throttled;
    # nothing is shared or throttled now, so it has lost its reason.
    # The sampling itself (median over many windows, ROADMAP D4/S0b)
    # belongs to the `benchmark` PR and is left as it was.
    tps, fired = 0.0, 0
    for _ in range(6):
        t0 = time.time()
        pod_soa, pod_count = run_ticks(pod_params, pod_soa, DT_MS, TICKS)
        pod_count.block_until_ready()
        wall = time.time() - t0
        if int(pod_count) / wall > tps:
            tps, fired = int(pod_count) / wall, int(pod_count)
        if tps > 5_000_000:
            break
    # node heartbeats tick alongside (cheap at 10k rows)
    node_soa, node_count = run_ticks(node_params, node_soa, DT_MS, TICKS)
    node_count.block_until_ready()
    return {"tps": tps, "fired": fired}


def run_e2e_bench() -> dict:
    """Full-pipeline bench through the front door: the player is
    constructed and started exactly as the kwok daemon does (VERDICT
    r03 next-#7) — ``start(paced=False)`` runs the production tick
    loop in saturation mode (overlapped macro-ticks back to back,
    measuring sustained capacity, not cadence).  The main thread only
    reads counters over wall-clock windows."""
    import gc

    from kwok_tpu.cluster.store import ResourceStore
    from kwok_tpu.controllers.device_player import DeviceStagePlayer
    from kwok_tpu.controllers.pod_controller import PodEnv
    from kwok_tpu.stages import load_builtin

    store = ResourceStore()
    gc_ctrl = None
    if E2E_GC:
        # the kube-controller-manager seat every production cluster
        # composes: its status-indifferent watches must not disturb the
        # drain (VERDICT r03 next-#6 asks for <10% tps with GC on)
        from kwok_tpu.controllers.gc_controller import GCController

        gc_ctrl = GCController(store).start()
    stages = load_builtin("pod-general") + load_builtin("pod-chaos")
    env = PodEnv()
    player = DeviceStagePlayer(
        store,
        "Pod",
        stages,
        capacity=E2E_PODS,
        tick_ms=DT_MS,
        funcs_for=env.funcs,
        on_delete=env.release,
        seed=2,
    )
    player.macro_ticks = E2E_MACRO

    t_setup0 = time.time()
    ops = [{"verb": "create", "data": make_pod(f"pod-{i}")} for i in range(E2E_PODS)]
    for i in range(0, len(ops), 10_000):
        store.bulk(ops[i : i + 10_000])

    player.start(paced=False)
    # admission: the informer's initial list feeds every pod into the SoA
    deadline = time.time() + E2E_BUDGET_S
    while len(player._rows) < E2E_PODS and time.time() < deadline:
        time.sleep(0.5)
    setup_s = time.time() - t_setup0
    admitted = len(player._rows)

    # warm-up: every pod through its initial transition (the slow-path
    # wave — pod-create adds a finalizer, a two-op bulk group per pod)
    # and then through a full churn cycle so the per-(row, stage) vals
    # caches are populated; the budget scales with the population on
    # top of the configured cap.  r04 post-mortem: the driver's windows
    # once measured the create wave itself because warm-up ran out of
    # budget on a loaded 1-core host — the scale term assumes a
    # conservative 2.5k transitions/s for the wave, and progress goes
    # to stderr so a stuck warm-up is diagnosable from the bench tail.
    deadline = time.time() + E2E_BUDGET_S + admitted / 2_500
    last_report = time.time()
    while player.transitions < 3 * admitted and time.time() < deadline:
        time.sleep(0.5)
        if time.time() - last_report >= 30:
            last_report = time.time()
            print(
                f"bench: warm-up {player.transitions}/{3 * admitted} "
                f"transitions ({player.patches} patches)",
                file=sys.stderr,
            )
    if player.transitions < 3 * admitted:
        print(
            f"bench: warm-up budget exhausted at {player.transitions}/"
            f"{3 * admitted} — windows may catch the admission wave",
            file=sys.stderr,
        )

    # the steady-state drain allocates only acyclic JSON containers
    # (reclaimed by refcounting); without freezing, gen2 cycles scan the
    # ~millions of live pod-dict objects and tax every bucket ~30%.
    # Raised gen0 threshold: at ~100k dict allocations/s the default
    # 700-alloc trigger costs ~20% of the drain (same tuning a real
    # apiserver applies via GOGC).
    gc.collect()
    gc.freeze()
    gc.set_threshold(200_000, 100, 100)

    best = None
    window_s = min(E2E_WINDOW_S, max(E2E_BUDGET_S / (E2E_WINDOWS + 1), 5))
    tr_start, p_start = player.transitions, player.patches
    for _ in range(E2E_WINDOWS):
        tr0, p0 = player.transitions, player.patches
        d0, s0, h0 = player.t_device, player.t_store, player.t_host
        b0 = player.t_build
        t0 = time.time()
        time.sleep(window_s)
        wall = time.time() - t0
        build = player.t_build - b0
        sample = {
            "tps": (player.transitions - tr0) / wall,
            "dirty": (player.patches - p0) / wall,
            "breakdown_s": {
                "device_tick_s": round(player.t_device - d0, 2),
                "store_bulk_s": round(player.t_store - s0, 2),
                "host_build_s": round(build, 2),
                "host_drain_s": round(player.t_host - h0 - build, 2),
            },
        }
        if best is None or sample["tps"] > best["tps"]:
            best = sample
    player.stop()
    if gc_ctrl is not None:
        gc_ctrl.stop()

    breakdown = best["breakdown_s"]
    bottleneck = max(breakdown, key=breakdown.get).removesuffix("_s")
    return {
        "pods": admitted,
        "transitions": player.transitions - tr_start,
        "patches": player.patches - p_start,
        "tick_errors": player.swallowed_errors,
        "transitions_per_sec": round(best["tps"]),
        "dirty_rows_per_sec": round(best["dirty"]),
        "gc": bool(gc_ctrl is not None),
        "setup_s": round(setup_s, 1),
        "window_s": round(window_s, 1),
        "windows": E2E_WINDOWS,
        "bottleneck": bottleneck,
        "breakdown_s": breakdown,
    }


#: e2e keys that are times or rates of the device pipeline: a run on
#: the CPU backend keeps the counts beside them and drops these
_E2E_DEVICE_TIMINGS = (
    "transitions_per_sec",
    "dirty_rows_per_sec",
    "setup_s",
    "bottleneck",
    "breakdown_s",
)


def _section(out: dict, key: str, fn) -> None:
    """Run one section into ``out[key]``.  A section that raises (the
    chaos smokes raise SystemExit on a failed assert) is recorded under
    ``out[key]["error"]`` and the remaining sections still run; main()
    turns any recorded error into a non-zero exit."""
    try:
        out[key] = fn()
    except (Exception, SystemExit) as e:  # noqa: BLE001 — keep the JSON line
        import traceback

        traceback.print_exc()
        out[key] = {"error": f"{type(e).__name__}: {e}"}


def main() -> int:
    out = {
        "metric": f"pod_stage_transitions_per_sec_{N_PODS}_pods_{N_NODES}_nodes",
        "value": 0,
        "unit": "transitions/s",
        "vs_baseline": 0.0,
    }
    try:
        device = init_backend()
    except RuntimeError as e:  # NoAccelerator, or JAX's own init failure
        # no device, no result line: nothing was measured
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    out["platform"] = device["platform"]
    out["device_kind"] = device["device_kind"]
    out["device_count"] = device["count"]
    on_cpu = device["platform"] == "cpu"
    try:
        t0 = time.time()
        kernel = run_kernel_bench()
        if on_cpu:
            # counts only: a rate of the XLA CPU backend is nobody's
            # device number and is not written under its name
            out["value"] = None
            out["vs_baseline"] = None
            out["note"] = (
                "cpu run: counts only; device rates and times are not measured"
            )
            out["kernel_fired"] = kernel["fired"]
        else:
            out["value"] = round(kernel["tps"])
            out["vs_baseline"] = round(kernel["tps"] / TARGET_TPS, 2)
            out["kernel_wall_s"] = round(time.time() - t0, 1)

        if E2E_PODS > 0:
            _section(out, "e2e", run_e2e_bench)
            if on_cpu:
                for key in _E2E_DEVICE_TIMINGS:
                    out["e2e"].pop(key, None)
        if SCHED_NODES > 0:
            # scheduling-scenario suite (kwok_tpu.sched): seeded burst /
            # training-gang / churn mixes with time-to-schedule and
            # topology-locality metrics
            _section(out, "sched", run_sched_bench)
        if STORE_PODS > 0:
            # sharded-vs-single bulk-lane write throughput A/B
            # (kwok_tpu.cluster.sharding; asserts the >=2x direct
            # dispatch win and the 1-shard no-regression floor)
            _section(out, "store", run_store_bench)
        if OBS_PODS > 0:
            # SLO-telemetry overhead A/B: the instrumented bulk lane
            # must stay within 5% of the disarmed one (the observed-
            # histogram layer's don't-regress guard)
            _section(out, "obs", run_obs_bench)
        if OVERLOAD_S > 0:
            # degradation trajectory: a short seeded best-effort flood
            # against a flow-controlled apiserver; records how much was
            # shed vs queued and what the system-priority canary paid
            # (kwok_tpu.chaos overload smoke, scaled down)
            _section(out, "overload", run_overload_bench)
        if FLEET_TENANTS > 0:
            # multi-tenant isolation: N virtual control planes on one
            # apiserver; cold-start time-to-first-write, victim p99
            # under a flooded neighbor, asserted isolation ratio
            # (kwok_tpu.chaos fleet smoke, scaled down)
            _section(out, "fleet", run_fleet_bench)
    except Exception as e:  # noqa: BLE001 — always emit the one JSON line
        import traceback

        traceback.print_exc()
        out["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(out))
    failed = [
        k for k, v in out.items() if isinstance(v, dict) and "error" in v
    ]
    if failed:
        print(f"bench: sections failed: {', '.join(failed)}", file=sys.stderr)
    return 1 if "error" in out or failed else 0


if __name__ == "__main__":
    rc = main()
    # hard exit: the JSON line is out, so a straggler daemon thread
    # (hung device transfer) must not be allowed to die mid-XLA-dispatch
    # during interpreter teardown and turn rc into 134 ("terminate
    # called ... FATAL: exception not rethrown").  os._exit skips
    # teardown entirely — the kernel reaps the threads.
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
