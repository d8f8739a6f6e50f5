"""kwok controller daemon: ``python -m kwok_tpu.cmd.kwok``.

Mirrors the reference's ``kwok`` binary startup (reference
pkg/kwok/cmd/root.go:61 NewCommand, runE:121): load config docs, pick
default stages when none are configured (root.go:463-490), build the
cluster client, wait for the apiserver (root.go:434-460), start the
controller facade, then serve the fake-kubelet HTTP surface
(root.go:288-424).  Flags mirror root.go:79-102.
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading
from typing import Dict, List, Optional

from kwok_tpu.api.config import KwokConfiguration
from kwok_tpu.api.loader import load_documents
from kwok_tpu.api.types import Stage
from kwok_tpu.cluster.client import ClusterClient
from kwok_tpu.controllers.controller import Controller
from kwok_tpu.server.server import Server, ServerConfig
from kwok_tpu.stages import default_node_stages, default_pod_stages


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="kwok", description=__doc__)
    p.add_argument("--server", default="http://127.0.0.1:2718", help="apiserver URL")
    p.add_argument("--ca-cert", default="", help="CA bundle for https apiservers")
    p.add_argument("--client-cert", default="")
    p.add_argument("--client-key", default="")
    p.add_argument(
        "--config",
        action="append",
        default=[],
        help="multi-doc YAML (Stages, KwokConfiguration, endpoint CRs); repeatable",
    )
    p.add_argument("--manage-all-nodes", action="store_true", default=None)
    p.add_argument("--manage-nodes-with-annotation-selector", default=None)
    p.add_argument("--manage-nodes-with-label-selector", default=None)
    p.add_argument("--disregard-status-with-annotation-selector", default=None)
    p.add_argument("--disregard-status-with-label-selector", default=None)
    p.add_argument("--node-lease-duration-seconds", type=int, default=None)
    p.add_argument(
        "--enable-crds",
        action="store_true",
        default=None,
        help="source Stages from cluster CRs instead of local config",
    )
    p.add_argument("--backend", choices=["host", "device"], default=None)
    p.add_argument(
        "--enable-metrics-usage",
        action="store_true",
        help="install the builtin metrics-usage asset (kubelet "
        "/metrics/resource emulation + annotation-driven usage)",
    )
    p.add_argument("--id", default=None, help="controller identity (lease holder)")
    p.add_argument("--server-address", default="127.0.0.1:10247",
                   help="fake-kubelet server host:port ('' disables)")
    # kubelet-surface TLS (reference kwok --tls-cert-file /
    # --tls-private-key-file, server.go:446-533): the one port then
    # speaks BOTH https and plain http, cmux-style
    p.add_argument("--tls-cert-file", default="",
                   help="serve the kubelet port over TLS too (cmux)")
    p.add_argument("--tls-private-key-file", default="")
    p.add_argument("--node-client-ca-file", default="",
                   help="CA for (optional) client-cert auth on the kubelet port")
    p.add_argument("--wait-timeout", type=float, default=60.0)
    p.add_argument("--seed", type=int, default=None)
    from kwok_tpu.cmd.kcm import add_leader_elect_flags

    add_leader_elect_flags(p, lease_name="kwok-controller")
    p.add_argument("-v", "--verbosity", action="count", default=0)
    return p


def load_config_docs(paths: List[str]) -> List[dict]:
    docs: List[dict] = []
    for path in paths:
        with open(path, "r", encoding="utf-8") as f:
            docs.extend(load_documents(f.read()))
    return docs


def config_from(docs: List[dict], args) -> KwokConfiguration:
    """Config docs merge in order, CLI flags override (reference
    config.go:194-252 merge + cobra flag precedence)."""
    conf = KwokConfiguration()
    merged: Dict = {}
    for d in docs:
        if d.get("kind") == "KwokConfiguration":
            merged.update(d.get("options") or {})
    if merged:
        conf = KwokConfiguration.from_dict({"options": merged})
    overrides = {
        "manage_all_nodes": args.manage_all_nodes,
        "manage_nodes_with_annotation_selector": args.manage_nodes_with_annotation_selector,
        "manage_nodes_with_label_selector": args.manage_nodes_with_label_selector,
        "disregard_status_with_annotation_selector": args.disregard_status_with_annotation_selector,
        "disregard_status_with_label_selector": args.disregard_status_with_label_selector,
        "node_lease_duration_seconds": args.node_lease_duration_seconds,
        "enable_crds": args.enable_crds,
        "backend": args.backend,
        "id": args.id,
    }
    for key, val in overrides.items():
        if val is not None:
            setattr(conf, key, val)
    if not (
        conf.manage_all_nodes
        or conf.manage_nodes_with_annotation_selector
        or conf.manage_nodes_with_label_selector
    ):
        conf.manage_all_nodes = True
    return conf


def stages_from(docs: List[dict], enable_crds: bool) -> Optional[Dict[str, List[Stage]]]:
    """Group configured stages by resourceRef kind; None → watch CRs.
    Defaults when nothing is configured (root.go:463-490)."""
    if enable_crds:
        return None
    grouped: Dict[str, List[Stage]] = {}
    for d in docs:
        if d.get("kind") == "Stage":
            st = Stage.from_dict(d)
            grouped.setdefault(st.resource_ref.kind, []).append(st)
    if "Node" not in grouped:
        grouped["Node"] = default_node_stages(lease=True)
    if "Pod" not in grouped:
        grouped["Pod"] = default_pod_stages()
    return grouped


def take_device(backend: str) -> Optional[dict]:
    """Under the device backend, take the accelerator NOW, not at the
    first tick, and say what it is (utils/accel.device_info); None on
    the host backend.  A daemon that cannot get one — no TPU, or
    another process holds the chip (one kwok replica per chip) — must
    fail at start instead of simulating on the XLA CPU backend under a
    "device" label: raises unless ``JAX_PLATFORMS`` names ``cpu``."""
    if backend != "device":
        return None
    from kwok_tpu.utils import accel

    accel.enable_compile_cache()
    return accel.require_accelerator()


def _config_cr_kinds() -> List[str]:
    """Config CR kinds the server consumes when --enable-crds is on
    (reference server.go:154-419 switches each to a DynamicGetter) —
    derived from the typed-config registry so a new kind is watched
    automatically; ResourcePatch is the record/replay wire format, not
    server config."""
    from kwok_tpu.api.extra_types import CONFIG_KINDS

    return [k for k in CONFIG_KINDS if k != "ResourcePatch"]


def start_config_watcher(client, srv, done: threading.Event, base_configs=None) -> None:
    """Watch config CRs and swap the server's config set on change.

    ``base_configs`` are locally configured typed docs (e.g. the
    --enable-metrics-usage asset); every swap re-installs them alongside
    the cluster CRs so a CR event cannot wipe local configuration."""
    import time
    import traceback

    from kwok_tpu.api.extra_types import from_document
    from kwok_tpu.cluster.informer import Informer, WatchOptions
    from kwok_tpu.utils.queue import Queue

    base_configs = list(base_configs or [])
    kinds = _config_cr_kinds()
    events: Queue = Queue()
    for kind in kinds:
        Informer(client, kind).watch(WatchOptions(), events, done=done)

    def loop():
        while not done.is_set():
            _, ok = events.get_or_wait(timeout=0.5)
            if not ok:
                continue
            time.sleep(0.2)  # debounce a burst of CR changes
            while events.get()[1]:
                pass
            docs = []
            for kind in kinds:
                try:
                    docs.extend(client.list(kind)[0])
                except Exception:  # noqa: BLE001 — kind may be unregistered
                    continue
            try:
                srv.replace_configs(
                    base_configs
                    + [from_document(d) for d in docs if d.get("kind") in kinds]
                )
            except Exception:  # noqa: BLE001 — a bad CR must not kill the loop
                traceback.print_exc()

    threading.Thread(target=loop, daemon=True).start()


_DEVICE_ROWS_HELP = (
    "Rows of a device player's SoA by kind (Pod: deviceCapacity; Node: "
    "at most 4096 to start; doubled as rows join past them), and under "
    "kind=Lease the slots of the device lease lane."
)


def _controller_self_metrics(get_ctr, elector=None, device=None):
    """Self-metrics updater: the process's start-up milestones, stage
    transitions/patches per kind (host and device paths), the rows each
    device player and the lease lane hold, the Node
    player's Ready wave, lease heartbeat health (SURVEY §7 step 5), and
    this replica's leader-election state.  (The tick loop's lag is a
    histogram of ``utils/telemetry``'s registry, observed where it
    happens: controllers/device_player.py.)  ``get_ctr`` indirects
    through the election holder — a standby replica has no Controller
    yet (None), but its election gauges still publish.  ``device`` is what the device
    backend runs on (utils/accel.device_info), None on the host
    backend: with it the scrape says which platform ticks, what the
    jit compiles cost, and whether the native units loaded — nothing
    about where the simulation runs is left to inference."""

    def update(registry) -> None:
        from kwok_tpu.metrics.collectors import Counter, Gauge

        def _set(cls, name, help_, value, **labels):
            key = name + "".join(f"|{k}={v}" for k, v in sorted(labels.items()))
            c = registry.get_or_register(
                key, lambda: cls(name, help_, const_labels=labels or None)
            )
            c.set(value)

        def gauge(name, help_, value, **labels):
            _set(Gauge, name, help_, value, **labels)

        def counter(name, help_, value, **labels):
            # _total series must expose TYPE counter so rate()/increase()
            # treat restarts (player rebuilds) as counter resets
            _set(Counter, name, help_, value, **labels)

        if elector is not None:
            gauge(
                "kwok_leader_election_is_leader",
                "1 while this replica holds the election lease.",
                1 if elector.is_leader() else 0,
                lease=elector.lease_name,
            )
            gauge(
                "kwok_leader_election_transitions",
                "Lease transition count of this replica's generation.",
                elector.transitions,
                lease=elector.lease_name,
            )
            counter(
                "kwok_leader_election_stepdowns_total",
                "Voluntary renew-deadline step-downs.",
                elector.stepdowns,
                lease=elector.lease_name,
            )

        from kwok_tpu.utils import telemetry

        marks = telemetry.milestones()
        for milestone, labels, seconds in marks.snapshot():
            gauge(
                "kwok_process_milestone_seconds",
                f"Seconds from {marks.origin} to a milestone of this daemon, "
                "each set once: main (arguments parsed), device_ready, "
                "apiserver_ready, leading, reconciling, first_tick by kind.",
                round(seconds, 3),
                milestone=milestone,
                **labels,
            )

        from kwok_tpu.native import status as native_status

        for unit, state in native_status().items():
            if state != "not-requested":
                gauge(
                    "kwok_native_loaded",
                    "1 if the native unit is loaded in this process "
                    "(0: pure-Python path; the log says why).",
                    1 if state == "loaded" else 0,
                    unit=unit,
                )
        if device is not None:
            from kwok_tpu.utils import accel

            gauge(
                "kwok_device_info",
                "What the device backend runs on, as JAX reports it.",
                1,
                platform=device["platform"],
                device_kind=device["device_kind"],
                devices=str(device["count"]),
            )
            # the fullest local device, as memory_stats() has it (an
            # XLA:CPU device has none: the series is then absent)
            mem = accel.memory_stats()
            for stat, field in (("in_use", "bytes_in_use"), ("peak", "peak_bytes_in_use")):
                if mem.get(field) is not None:
                    gauge(
                        "kwok_device_memory_bytes",
                        "Device memory of the fullest local device.",
                        mem[field],
                        stat=stat,
                    )
            cs = accel.compile_stats()
            counter(
                "kwok_jit_compilations_total",
                "Programs requested from the XLA backend (cold compiles "
                "and persistent-cache retrievals).",
                cs["compilations"],
            )
            counter(
                "kwok_jit_compile_seconds_total",
                "Seconds the backend spent compiling or retrieving them.",
                cs["compile_seconds"],
            )
            counter(
                "kwok_jit_compile_cache_hits_total",
                "Persistent compile-cache hits.",
                cs["cache_hits"],
            )
            counter(
                "kwok_jit_compile_cache_misses_total",
                "Persistent compile-cache misses (entries written).",
                cs["cache_misses"],
            )

        ctr = get_ctr()
        if ctr is None:
            return  # standby: no players running

        for kind, backend, p in ctr.players():
            for b in ("host", "device"):
                # both series every scrape: a kind whose stage set
                # changes may move between backends
                gauge(
                    "kwok_stage_backend_info",
                    "1 for the backend that plays this kind's stages.",
                    1 if b == backend else 0,
                    kind=kind,
                    backend=b,
                )
            if backend == "device":
                counter(
                    "kwok_tick_errors_total",
                    "Exceptions the device tick loop caught and survived.",
                    p.swallowed_errors,
                    kind=kind,
                )
                gauge(
                    "kwok_device_rows",
                    _DEVICE_ROWS_HELP,
                    p.sim.capacity,
                    kind=kind,
                )
            counter(
                "kwok_stage_transitions_total",
                "Stage transitions played.",
                getattr(p, "transitions", 0),
                kind=kind,
                backend=backend,
            )
            if getattr(p, "wave_wall_s", None) is not None:
                gauge(
                    "kwok_node_wave_wall_seconds",
                    "Seconds from the Node player's first admission to the "
                    "newest acknowledged status commit that held a node row "
                    "never committed before: the Ready wave's wall.",
                    round(p.wave_wall_s, 3),
                )

        # lease heartbeat health (SURVEY §7 step 5): renewals + p99 lag,
        # covering both the host syncWorker path and the device lane
        nl = ctr.node_leases
        if nl is not None:
            lane = getattr(nl, "_lane", None)
            if lane is not None:
                gauge("kwok_device_rows", _DEVICE_ROWS_HELP, lane.capacity, kind="Lease")
            counter(
                "kwok_lease_renewals_total",
                "Node lease renewals written.",
                nl.renew_count,
            )
            lag_samples = []
            raw_lags = getattr(lane, "renew_lags", None)
            if raw_lags:
                # the lane tick thread appends concurrently; a mid-copy
                # mutation raises RuntimeError — retry once, else skip
                for _ in range(2):
                    try:
                        lag_samples = list(raw_lags)
                        break
                    except RuntimeError:
                        continue
            if not lag_samples:
                for _ in range(2):
                    try:
                        lag_samples = list(nl.renew_lag.values())
                        break
                    except RuntimeError:
                        continue
            if lag_samples:
                lag_samples.sort()
                for q in (0.5, 0.99):
                    gauge(
                        "kwok_lease_renew_lag_seconds",
                        "Lease renewal lag past its scheduled time.",
                        lag_samples[min(len(lag_samples) - 1, int(q * len(lag_samples)))],
                        quantile=str(q),
                    )

    return update


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from kwok_tpu.utils.telemetry import milestones

    mark = milestones().mark
    mark("main")
    # install the process tracer at boot (KWOK_TRACE_ENDPOINT /
    # KWOK_TRACE_SERVICE from the runtime): watch streams opened
    # before the first traced request must already see it to
    # resolve rv→span contexts at delivery
    from kwok_tpu.utils.trace import get_tracer

    get_tracer('kwok')
    if bool(args.tls_cert_file) != bool(args.tls_private_key_file):
        print(
            "error: --tls-cert-file and --tls-private-key-file must be "
            "given together",
            file=sys.stderr,
        )
        return 1
    from kwok_tpu.utils.log import setup as log_setup

    log_setup(args.verbosity)
    # server-process GC tuning (the GOGC knob a real apiserver exposes):
    # the drain allocates acyclic JSON containers at ~100k/s, reclaimed
    # by refcounting — the default 700-allocation gen0 trigger costs a
    # measured ~20% of steady-state drain throughput
    import gc

    gc.set_threshold(200_000, 100, 100)
    # NOTE: kwok daemons deliberately do NOT auto-join a jax.distributed
    # world: each daemon runs an independent tick loop, and asymmetric
    # programs across a shared collective world deadlock.  Multi-host
    # daemons shard by lease ownership on process-local meshes
    # (parallel/distributed.py docstring); cross-host global-mesh
    # compute is for symmetric SPMD workers (tests/distributed_worker.py).
    docs = load_config_docs(args.config)
    if args.enable_metrics_usage:
        from kwok_tpu.stages import METRICS_USAGE, load_builtin_docs

        docs.extend(load_builtin_docs(METRICS_USAGE))
    conf = config_from(docs, args)
    stages = stages_from(docs, bool(conf.enable_crds))

    try:
        device = take_device(conf.backend)
    except RuntimeError as exc:  # NoAccelerator, or JAX's own "unable
        # to initialize backend" under JAX_PLATFORMS=tpu
        print(f"error: --backend device: {exc}", file=sys.stderr)
        return 1
    mark("device_ready")

    client = ClusterClient(
        args.server,
        ca_cert=args.ca_cert or None,
        client_cert=args.client_cert or None,
        client_key=args.client_key or None,
    )
    if not client.wait_ready(timeout=args.wait_timeout):
        print(f"apiserver {args.server} not ready", file=sys.stderr)
        return 1
    mark("apiserver_ready")

    # the Controller lives behind the leader election: built and
    # started on acquisition, stopped (node leases released) on
    # deposition — a standby replica keeps informer-free and write-free
    holder = {"ctr": None}
    ctr_mut = threading.Lock()

    def start_controllers(active=None) -> None:
        mark("leading")
        with ctr_mut:
            if holder["ctr"] is not None:
                return
            c = Controller(client, conf, local_stages=stages, seed=args.seed)
            c.start()
            holder["ctr"] = c
        mark("reconciling")
        print("kwok controller reconciling", flush=True)

    def stop_controllers() -> None:
        with ctr_mut:
            c, holder["ctr"] = holder["ctr"], None
        if c is None:
            return
        leases = c.node_leases
        c.stop()
        if leases is not None:
            # proactive handoff: null our node-lease holds so the next
            # leader (or a sharding peer) takes the nodes immediately
            # instead of waiting out each lease's expiry
            leases.release_all()
        print("kwok controller standing by", flush=True)

    from kwok_tpu.cmd.kcm import run_elected

    elector = run_elected(
        args,
        conf.id,
        client,
        start_controllers,
        stop_controllers,
        ClusterClient(
            args.server,
            ca_cert=args.ca_cert or None,
            client_cert=args.client_cert or None,
            client_key=args.client_key or None,
            client_id=f"system:{conf.id}",
        ),
    )
    where = ""
    if device is not None:
        where = (
            f" platform={device['platform']}"
            f" device_kind={device['device_kind']!r}"
            f" devices={device['count']}"
        )
    print(f"kwok controller started (backend={conf.backend}{where})", flush=True)

    # long-lived setup objects out of the GC's sight: the drain hot path
    # allocates only acyclic JSON containers (reclaimed by refcounting),
    # while recurring gen2 collections would rescan every live pod dict
    import gc

    gc.collect()
    gc.freeze()

    done = threading.Event()
    srv = None
    if args.server_address:
        host, _, port = args.server_address.rpartition(":")
        cfg = ServerConfig(
            get_node=lambda name: _try(client.get, "Node", name),
            get_pod=lambda ns, name: _try(client.get, "Pod", name, ns),
            list_pods=lambda node: [
                p
                for p in client.list("Pod", field_selector=f"spec.nodeName={node}")[0]
            ],
            list_nodes=lambda: [
                n["metadata"]["name"] for n in client.list("Node")[0]
            ],
        )
        srv = Server(cfg)
        # only endpoint/metric config kinds feed the server; Stages and
        # KwokConfiguration docs belong to the controller path above
        from kwok_tpu.api.extra_types import from_document

        server_kinds = set(_config_cr_kinds())
        local_configs = [
            from_document(d) for d in docs if d.get("kind") in server_kinds
        ]
        srv.set_configs(local_configs)
        srv.add_self_updater(
            _controller_self_metrics(lambda: holder["ctr"], elector, device)
        )
        bound = srv.serve(
            port=int(port or 10247),
            host=host or "127.0.0.1",
            tls_cert=args.tls_cert_file or None,
            tls_key=args.tls_private_key_file or None,
            client_ca=args.node_client_ca_file or None,
        )
        scheme = "https+http" if args.tls_cert_file else "http"
        print(
            f"fake-kubelet server on {host or '127.0.0.1'}:{bound} ({scheme})",
            flush=True,
        )
        if conf.enable_crds:
            start_config_watcher(client, srv, done, base_configs=local_configs)

    def _stop(signum, frame):
        done.set()

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)
    done.wait()

    if srv is not None:
        srv.close()
    # teardown writes (node-lease releases) happen while the election
    # fence is still valid; only then release the election lease so
    # the standby takes over in ~one retry interval
    stop_controllers()
    if elector is not None:
        elector.stop(release=True)
    return 0


def _try(fn, *a):
    try:
        return fn(*a)
    except KeyError:
        return None


if __name__ == "__main__":
    sys.exit(main())
