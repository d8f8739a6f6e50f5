#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

builds a default ``kwokctl create cluster --backend device`` cluster, warms
it, measures for ``--seconds`` from the benchmark's own watching client
(the window opens as a save of the apiserver ends), waits for the answers
still due, reads pods and leases back, stops the controllers (the kwok
daemon's pid is gone, so the next run gets the chip), kills the apiserver
and reads a sample back from its restart, checks it all and prints one JSON
line.  This process never imports ``jax``: the kwok daemon holds the
chip, and the device named in the result is read back from it.

Everything that belongs to one cell is data found by name: the cell in
``BENCHMARK.json``, its configuration in ``configs/``, its traffic in
``traffic/`` (whose ``kind`` names a module in ``generators/``), the
per-layer metrics in ``layer_metrics/``.  See ``README.md`` beside this
file.

``--override k=v,...`` shrinks sizes for a rehearsal on the CPU
(``JAX_PLATFORMS=cpu`` given explicitly); such a run labels itself and its
numbers are counts, not device measurements.  The driver never passes it.
"""

from __future__ import annotations

import time

_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT]

from benchmarks import generators  # noqa: E402
from benchmarks.generators import Load  # noqa: E402
from benchmarks.harness import check, metrics, promtext  # noqa: E402
from benchmarks.harness.cluster import Cluster, Failed, log  # noqa: E402
from benchmarks.harness.watch import LEASE_NAMESPACE, Watcher  # noqa: E402

#: seconds of profiler trace taken in the middle of a ``--trace 1`` window; its
#: python tracer and ``stop_trace`` cost the daemon some three times as long
TRACE_S = 2.0
#: pods read back after the apiserver's crash: so many drawn from the seed,
#: so many of the newest, so many created as it is killed
CRASH_SAMPLE = 100


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts), encoding="utf-8") as f:
        return json.load(f)


def find_cell(name: str):
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Failed(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    return bench, cells[name]


def apply_overrides(spec: str, config: dict, traffic: dict) -> bool:
    """``k=v,...`` onto the configuration's sizes, its KwokConfiguration
    options or the traffic's parameters, wherever the key exists."""
    if not spec:
        return False
    for item in spec.split(","):
        key, _, val = item.partition("=")
        homes = [d for d in (config["sizes"], config["kwok_configuration"], traffic["params"])
                 if key in d]
        if not homes:
            raise Failed(f"--override {key}: no such size or parameter")
        for d in homes:
            d[key] = int(val)
    return True


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def layer_readers(bench: dict, cell: str):
    """(metric entry, its file's ``reader``) for the per-layer metrics of
    ``BENCHMARK.json`` that ``cell`` reports."""
    for m in bench["per_layer"]:
        if applies(m, cell):
            yield m, load_json("layer_metrics", f"{m['name']}.json")["reader"]


def layer_values(bench: dict, cell: str, before: dict, after: dict, traced: dict,
                 client: dict) -> dict:
    """The per-layer metrics of one traced run.  ``before``/``after`` are
    the scrapes around the window, ``traced`` the trace reductions' values
    by reduction name, ``client`` the watching client's values.  A reader
    that finds nothing to read leaves its metric out."""
    out = {}
    for m, spec in layer_readers(bench, cell):
        if spec["kind"] == "prom_delta":
            v = promtext.read(spec, before, after)
        elif spec["kind"] == "trace":
            v = traced.get(spec["reduction"])
        elif spec["kind"] == "client":
            v = client.get(spec["value"])
        else:
            raise Failed(f"layer metric {m['name']}: unknown reader {spec['kind']!r}")
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def take_trace(cluster: Cluster, t_start: float, seconds: float, out: str, done: dict) -> None:
    """Thread body: ``seconds`` of profiler trace inside the daemon,
    starting at ``t_start``."""
    try:
        time.sleep(max(t_start - time.monotonic(), 0))
        cluster.ask_daemon("start_trace", "trace_started", 60, arg=out)
        time.sleep(seconds)
        cluster.ask_daemon("stop_trace", "trace_done", 180)
        done["ok"] = True
    except Failed as exc:
        done["error"] = str(exc)


def reduce_trace(trace_dir: str, names: list, context: dict) -> dict:
    """Run the trace reductions in a child that may import jax, pinned to
    the CPU (the chip is free by now, and stays so)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    ctx = os.path.join(trace_dir, "context.json")
    with open(ctx, "w", encoding="utf-8") as f:
        json.dump({"names": names, **context}, f)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "harness", "reduce_trace.py"), trace_dir, ctx],
        env=env, stdout=subprocess.PIPE, timeout=200, text=True)
    if proc.returncode != 0:
        raise Failed(f"trace reduction exited {proc.returncode}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def lease_evidence(cluster: Cluster, watcher: Watcher, config: dict, t0: float) -> dict:
    """What ``check.numbers`` holds the lease plane to: the watcher's
    events, a final LIST and the configuration's lease block."""
    leases = cluster.client.list("Lease", namespace=LEASE_NAMESPACE)[0]
    duration = config["kwok_configuration"]["nodeLeaseDurationSeconds"]
    return {
        "events": list(watcher.lease_events), "t0": t0, "t_end": time.monotonic(),
        "nodes": [f"node-{i}" for i in range(config["sizes"]["nodes"])],
        "listed": {le["metadata"]["name"]: le for le in leases},
        "duration_s": duration,
        "renew_every_s": duration * config["lease"]["renew_fraction"],
        "early_tolerance": config["lease"]["early_tolerance"],
        "holder": config["lease"]["holder"],
    }


def crash_evidence(cluster: Cluster, load: Load, listed: dict, seed: int) -> dict:
    """Durability: pods of the window drawn from the seed, its newest ones,
    and pods created as the apiserver is killed, read back after it came up
    again from its snapshot and WAL."""
    alive = [n for n in load.in_window if n in listed]
    sample = set(random.Random(seed).sample(alive, min(CRASH_SAMPLE, len(alive))))
    sample.update(alive[-CRASH_SAMPLE:])
    canaries = [generators.pod(f"canary-{i}", "node-0") for i in range(CRASH_SAMPLE)]
    expected = {n: listed[n] for n in sample}
    expected.update((p["metadata"]["name"], {"metadata": p["metadata"]}) for p in canaries)
    return {"crash_expected": expected,
            "after_crash": cluster.crash_and_read_back(canaries, sorted(sample))}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--override", default="", help="k=v,...: rehearsal sizes (CPU)")
    p.add_argument("--controls", action="store_true",
                   help="also judge the evidence with each control of harness/controls.py")
    p.add_argument("--trace-seconds", type=float, default=TRACE_S)
    p.add_argument("--keep", default="", help="copy component logs and the trace here")
    a = p.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "kwok_tpu")):
        print("benchmarks/run.py: kwok_tpu/ is not beside benchmarks/: nothing to measure",
              file=sys.stderr)
        return 2
    try:
        return run(a)
    except Failed as exc:
        print(f"benchmarks/run.py: FAILED: {exc}", file=sys.stderr)
        return 1


def run(a) -> int:
    bench, cell = find_cell(a.workload)
    config = load_json("configs", f"{cell['config']}.json")
    traffic = load_json("traffic", f"{cell['traffic']}.json")
    rehearsal = apply_overrides(a.override, config, traffic)
    if rehearsal:
        # the daemons of a rehearsal keep their programs apart from the
        # checkout's cache: with a rehearsal's XLA:CPU entries in it,
        # tests/test_distributed.py's device-backend daemons stop playing
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(HERE, "out", "rehearsal_jax_cache"))
    generator = importlib.import_module(f"benchmarks.generators.{traffic['kind']}")
    peaks = load_json("harness", "peaks.json")
    work = os.path.join(HERE, "out", a.workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    cluster = Cluster("bench", work, config)
    watcher = None
    trace_dir = os.path.join(work, "trace")
    trace_state: dict = {}
    result: dict = {}
    gone = True
    try:
        device = cluster.create()
        log(f"kwok daemon pid {cluster.kwok_pid} runs on {device}")
        if device["platform"] != "tpu" and not rehearsal:
            raise Failed(f"the kwok daemon runs on {device['platform']}, not on a TPU; "
                         "a CPU rehearsal overrides the sizes (--override)")
        if device["count"] < cell["chips"]:
            raise Failed(f"the cell asks for {cell['chips']} chips, the daemon has "
                         f"{device['count']}")
        if device["platform"] == "tpu" and device["kind"] not in peaks:
            raise Failed(f"device kind {device['kind']!r} is not in harness/peaks.json")
        cluster.scale_nodes(config["sizes"]["nodes"])
        log(f"{config['sizes']['nodes']} nodes Ready")
        watcher = Watcher(cluster.client).start()
        load = Load(cluster.client, watcher, config["sizes"], traffic["params"], a.seed, log)
        generator.warm(load)
        log(f"warm: {len(load.created)} pods created, {len(watcher.running_at)} seen Running")

        if not cluster.wait_save_end(40):
            log("no save of the apiserver ended in 40 s: the window opens unphased")
        before = cluster.scrape()
        t0 = time.monotonic()
        t1 = t0 + a.seconds
        setup_s = t0 - _START
        tracer = None
        if a.trace:
            os.makedirs(trace_dir)
            tracer = threading.Thread(
                target=take_trace, daemon=True,
                args=(cluster, t0 + max(a.seconds - a.trace_seconds, 0) / 2, a.trace_seconds,
                      trace_dir, trace_state))
            tracer.start()
        generator.run(load, t0, t1)
        time.sleep(max(t1 - time.monotonic(), 0))
        after = cluster.scrape()
        if a.keep:
            os.makedirs(a.keep, exist_ok=True)
            with open(os.path.join(a.keep, "scrapes.json"), "w", encoding="utf-8") as f:
                json.dump({"before": before, "after": after}, f)
        log(f"window closed: {len(load.in_window)} pods created in it, "
            f"{sum(1 for n in load.in_window if n in watcher.running_at)} of them Running; "
            f"{promtext.delta(before['kwok'], after['kwok'], 'kwok_jit_compilations_total', {})}"
            " programs asked of the XLA backend in it")
        memory_peak = cluster.memory_peak_bytes()
        if tracer is not None:
            tracer.join(timeout=240)
        generator.settle(load, t1)
        log(f"settled: {sum(1 for n in load.in_window if n in watcher.running_at)} Running")
        pods, _rv = cluster.client.list_paged("Pod", namespace="default", page_size=5000)
        evidence = {
            "lease": lease_evidence(cluster, watcher, config, t0),
            "created": load.created, "deleted": load.deleted, "in_window": load.in_window,
            "running_seen": set(watcher.running_at), "deleted_seen": set(watcher.deleted_at),
            "running_status": watcher.running_status,
            "listed": {p_["metadata"]["name"]: p_ for p_ in pods},
            "kwok": cluster.kwok_metrics(), "node_ip": config["node_ip"],
        }
        if watcher.evicted:
            raise Failed("the watching client fell behind and its stream was cut")
        watcher.stop()
        gone = cluster.stop_controllers()
        evidence.update(crash_evidence(cluster, load, evidence["listed"], a.seed))
        result = {"t0": t0, "t1": t1, "setup_s": setup_s, "before": before, "after": after,
                  "memory_peak": memory_peak, "evidence": evidence, "load": load,
                  "device": device}
    finally:
        if watcher is not None:
            watcher.stop()
        bad_logs = cluster.save_logs(os.path.join(a.keep, "logs")) if a.keep else []
        gone = cluster.stop_controllers() and gone
        cluster.kill_apiserver()
        cluster.reap()
    if not gone:
        raise Failed(f"kwok daemon pid {cluster.kwok_pid} is still alive after teardown")
    if bad_logs:
        log(f"Traceback in component logs: {bad_logs}")

    return report(a, bench, cell, config, rehearsal, watcher, result,
                  trace_dir if a.trace else None, trace_state)


def report(a, bench, cell, config, rehearsal, watcher, r, trace_dir, trace_state) -> int:
    load, t0, t1, ev = r["load"], r["t0"], r["t1"], r["evidence"]
    reference = importlib.import_module(f"benchmarks.references.{config['reference']}")
    nums, first = check.numbers(ev, reference)
    ok = check.correct(nums)

    nodes = ev["lease"]["nodes"]
    intervals, starved = metrics.lease_intervals(watcher.lease_events, t0, t1, nodes)
    sent = {n: load.sent_at[n] for n in load.in_window}
    latencies, never = metrics.create_to_running(sent, watcher.running_at)
    values = {
        "transitions_per_s": metrics.transitions_per_s(watcher.pod_events, t0, t1),
        "create_to_running_p95_s": metrics.percentile(latencies, 0.95),
        "lease_renew_interval_p95_s": metrics.percentile(intervals, 0.95),
        "setup_s": r["setup_s"],
    }
    attempted = len(load.in_window) + len(load.deleted) + len(load.refused) + len(nodes)
    failed = (len(never) + len(load.refused) + len(starved)
              + int(nums["never_deleted"][0]))

    device = {"platform": r["device"]["platform"], "kind": r["device"]["kind"],
              "count": r["device"]["count"], "memory_peak_bytes": r["memory_peak"]}
    breakdown = None
    if not a.trace:
        mine = [(m, values.get(m["name"])) for m in bench["end_to_end"]
                if applies(m, cell["name"])]
        out_metrics = {m["name"]: {"value": v, "unit": m["unit"]} for m, v in mine
                       if v is not None}
    else:
        traced = {}
        if trace_state.get("ok"):
            names = sorted({spec["reduction"] for _m, spec in layer_readers(bench, cell["name"])
                            if spec["kind"] == "trace"})
            traced = reduce_trace(trace_dir, names, {
                "device_kind": r["device"]["kind"], "config": config})
            if a.keep:
                shutil.copytree(trace_dir, os.path.join(a.keep, "trace"), dirs_exist_ok=True)
        else:
            log(f"no trace: {trace_state.get('error', 'the daemon did not answer')}")
        out_metrics = layer_values(bench, cell["name"], r["before"], r["after"],
                                   traced.get("metrics") or {}, values)
        if traced.get("busy_s") is not None:
            device["busy_s"] = traced["busy_s"]
            device["window_s"] = traced["window_s"]
        breakdown = traced.get("breakdown")

    compared = {k: {"value": v, "limit": lim} for k, (v, lim) in nums.items()}
    line = {"correct": ok, "attempted": attempted, "failed": failed,
            "metrics": out_metrics, "device": device}
    if rehearsal:
        line["rehearsal"] = f"sizes overridden ({a.override}) on {device['platform']}: " \
                            "counts, not device measurements"
    if breakdown:
        line["breakdown"] = breakdown
    if a.controls:
        from benchmarks.harness import controls

        line["controls"] = {}
        for name, control in controls.CONTROLS.items():
            try:
                broken = control(ev, a.seed)
            except ValueError as exc:  # nothing of that kind in this run
                print(f"control {name}: {exc}", file=sys.stderr)
                continue
            c_nums, _ = check.numbers(broken, reference)
            failing = {k: v for k, (v, lim) in c_nums.items() if v > lim}
            line["controls"][name] = {"correct": check.correct(c_nums), "failed_by": failing}
            print(f"control {name}: correct={check.correct(c_nums)} by {failing}",
                  file=sys.stderr)
    line["compared"] = compared
    shutil.rmtree(os.path.join(HERE, "out", a.workload), ignore_errors=True)
    if first:
        print(f"first mismatch: {first}", file=sys.stderr)
    le = ev["lease"]
    log(f"leases: {len(intervals)} intervals in the window, the longest "
        f"{max(intervals, default=0):.2f} s; longest gap at "
        f"{metrics.lease_longest_gap(le['events'], le['t0'], le['t_end'], nodes)[1]}; "
        f"{metrics.lease_pace(le['events'], le['t0'], le['t_end'], le['renew_every_s'], le['early_tolerance'])[1]}"
        " renewals came early (a failed renewal is tried again at once)")
    for k, (v, lim) in nums.items():
        print(f"compared {k}: {v} (limit {lim})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
