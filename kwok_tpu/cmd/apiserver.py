"""Apiserver daemon: ``python -m kwok_tpu.cmd.apiserver``.

The binary runtime's stand-in for etcd + kube-apiserver (reference
runtime/binary/cluster.go:316-420 starts both; our store folds the
pair into one process).  State persists to ``--state-file`` as the
etcd-snapshot analog: loaded on boot, written on SIGTERM and every
``--save-interval`` seconds.  ``--wal-file`` adds the etcd-WAL seat
(``kwok_tpu.cluster.wal``): every acked mutation is logged between
snapshots and replayed on boot, so a crashed daemon loses nothing and
restarted watch streams resume without re-lists.  ``--chaos-profile``
arms the HTTP fault injector (``kwok_tpu.chaos``) from a seeded
profile — latency/429/503/resets/watch-drops at this boundary, plus
best-effort request floods when the profile carries ``overload``
windows.  ``--max-inflight`` / ``--flow-config`` arm APF-style flow
control (``kwok_tpu.cluster.flowcontrol``): per-priority-level
concurrency shares with fair queues, 429+Retry-After shedding, and
per-level metrics at ``/metrics``.
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import threading
import time

from kwok_tpu.cluster.apiserver import APIServer
from kwok_tpu.cluster.store import ResourceStore


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="kwok-tpu-apiserver", description=__doc__)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=2718)
    p.add_argument("--state-file", default="", help="persist store state here")
    p.add_argument("--save-interval", type=float, default=10.0)
    p.add_argument(
        "--wal-file",
        default="",
        help="write-ahead log for crash durability between snapshots",
    )
    p.add_argument(
        "--wal-fsync",
        choices=["always", "interval", "off"],
        default="interval",
        help="WAL fsync policy (process-crash safety needs none of "
        "them; machine-crash safety wants 'always')",
    )
    p.add_argument(
        "--wal-segment-bytes",
        type=int,
        default=0,
        help="WAL segment rotation threshold (0 = library default)",
    )
    p.add_argument(
        "--pitr-dir",
        default="",
        help="point-in-time-recovery archive: retired WAL segments + "
        "periodic snapshots land here, enabling `kwokctl snapshot "
        "restore --to-rv` and boot fallback past a corrupt state file",
    )
    p.add_argument(
        "--store-shards",
        type=int,
        default=1,
        help="horizontally shard the store by namespace/kind hash "
        "across N independent shards, each with its own mutex family, "
        "WAL and PITR archive (kwok_tpu.cluster.sharding; 1 = the "
        "single-store layout, byte-compatible with existing workdirs)",
    )
    p.add_argument(
        "--pitr-keep",
        type=int,
        default=5,
        help="archived snapshots to retain (older ones and the "
        "segments they cover are pruned after each save)",
    )
    p.add_argument(
        "--chaos-profile",
        default="",
        help="arm the HTTP fault injector from this seeded profile YAML",
    )
    p.add_argument(
        "--max-inflight",
        type=int,
        default=64,
        help="global concurrent-request budget, split across priority "
        "levels (0 disables flow control, like a pre-APF apiserver)",
    )
    p.add_argument(
        "--flow-config",
        default="",
        help="YAML flow schema overriding the default priority levels "
        "and client classification",
    )
    p.add_argument(
        "--fleet-tenants",
        type=int,
        default=0,
        help="host N virtual control planes (fleet tenants) on this "
        "apiserver: tenant-scoped routing via the X-Kwok-Tenant header "
        "or the /fleet/t/{tenant}/ path prefix, per-tenant APF levels, "
        "cold-start/scale-to-zero lifecycle (kwok_tpu.fleet; 0 = a "
        "plain single-tenant apiserver)",
    )
    p.add_argument(
        "--fleet-idle-s",
        type=float,
        default=300.0,
        help="seconds without a request before a fleet tenant is idle",
    )
    p.add_argument(
        "--fleet-cold-s",
        type=float,
        default=900.0,
        help="seconds without a request before a fleet tenant scales "
        "to zero (binding dropped; durable state stays in the store)",
    )
    p.add_argument(
        "--watch-timeout",
        type=float,
        default=3600.0,
        help="default server-side watch deadline in seconds "
        "(?timeoutSeconds= overrides per request; 0 disables)",
    )
    p.add_argument(
        "--slow-request-s",
        type=float,
        default=0.0,
        help="flight-recorder slow-request threshold in seconds: "
        "requests at/over it are sampled (with their trace ids) into "
        "the bounded /debug/flightrecorder ring (0 keeps the default, "
        "0.5s or KWOK_SLOW_REQUEST_S)",
    )
    p.add_argument("--tls-cert", default="")
    p.add_argument("--tls-key", default="")
    p.add_argument("--client-ca", default="")
    p.add_argument("--audit-file", default="", help="append mutation audit JSONL here")
    p.add_argument(
        "--kubelet-url",
        default="",
        help="fake-kubelet base URL for pod log/exec subresource proxying",
    )
    p.add_argument("-v", "--verbosity", action="count", default=0)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    # install the process tracer at boot (KWOK_TRACE_ENDPOINT /
    # KWOK_TRACE_SERVICE from the runtime): watch streams opened
    # before the first traced request must already see it to
    # resolve rv→span contexts at delivery
    from kwok_tpu.utils.trace import get_tracer

    get_tracer('apiserver')
    from kwok_tpu.utils.log import setup as log_setup

    log_setup(args.verbosity)
    n_shards = max(1, int(args.store_shards))
    if n_shards > 1:
        store, wals, pitrs = _boot_sharded(args, n_shards)
        wal = wals[0] if wals else None
        return _serve(args, store, wal, wals, pitrs, sharded=True)
    # namespace finalizers ON: cluster compositions always include the
    # controller-manager seat that finalizes them (ctl/runtime.py)
    store = ResourceStore(namespace_finalizers=True)
    pitr = None
    if args.pitr_dir:
        from kwok_tpu.snapshot.pitr import PitrArchive

        pitr = PitrArchive(args.pitr_dir)
    if args.state_file or args.wal_file:
        # snapshot-then-WAL boot with integrity: a corrupt state file
        # falls back to the newest verifiable archived snapshot, and
        # WAL recovery is tolerant — every verifiable record applies,
        # corruption and missing resourceVersions are REPORTED (the
        # recovery-honesty contract), never silently skipped
        from kwok_tpu.snapshot.pitr import boot_recover

        boot = boot_recover(
            store,
            args.state_file or None,
            args.wal_file or None,
            pitr_root=args.pitr_dir or None,
        )
        _print_boot(args, store, boot)
        rec = boot["recovery"]
        if rec is not None and not rec.clean:
            import json as _json

            print(
                "WAL recovery was lossy (detected, bounded): "
                + _json.dumps(rec.summary()),
                flush=True,
            )
    wal = None
    if args.wal_file:
        # attach AFTER replay — the log keeps covering its records
        # until a snapshot compacts them
        from kwok_tpu.cluster.wal import WriteAheadLog

        wal = WriteAheadLog(
            args.wal_file,
            fsync=args.wal_fsync,
            **(
                {"segment_bytes": args.wal_segment_bytes}
                if args.wal_segment_bytes
                else {}
            ),
            archive_dir=args.pitr_dir or None,
        )
        store.attach_wal(wal)
    return _serve(
        args,
        store,
        wal,
        [wal] if wal is not None else [],
        [pitr],
        sharded=False,
    )


def _print_boot(args, store, boot, which: str = "", state_file: str = "") -> None:
    """Boot-report lines shared by the single and sharded paths."""
    state_file = state_file or args.state_file
    if boot["state_loaded"]:
        where = (
            f"archived snapshot rv={boot['fallback_rv']} "
            f"(state file corrupt: {boot['snapshot_error']})"
            if boot["fell_back"]
            else state_file
        )
        print(f"restored state{which} from {where}", flush=True)
    rec = boot.get("recovery")
    if rec is not None and rec.applied:
        print(
            f"replayed {rec.applied} WAL records{which} "
            f"(rv {store.resource_version})",
            flush=True,
        )


def _boot_sharded(args, n_shards: int):
    """Build the N-shard store: per-shard snapshot-then-WAL recovery
    with the union rv-continuity check (kwok_tpu.cluster.sharding).
    The workdir is the state/WAL file's directory — shard 0 keeps the
    single-store file names at the root (byte-compatible), shards
    1..N-1 live under ``shards/NN/``."""
    if not (args.state_file or args.wal_file):
        from kwok_tpu.cluster.sharding.router import build_sharded_store

        return build_sharded_store(
            n_shards, namespace_finalizers=True
        ), [], []
    from kwok_tpu.cluster.sharding.layout import (
        shard_state_path,
        shard_wal_path,
    )
    from kwok_tpu.snapshot.sharded import open_sharded_store

    workdir = os.path.dirname(
        os.path.abspath(args.state_file or args.wal_file)
    )
    # the sharded layout owns the file names inside the workdir; a
    # mismatched --state-file/--wal-file spelling would silently boot
    # an empty shard 0 next to the real files
    expect = {
        args.state_file: shard_state_path(workdir, 0),
        args.wal_file: shard_wal_path(workdir, 0),
    }
    for given, canonical in expect.items():
        if given and os.path.abspath(given) != canonical:
            raise SystemExit(
                f"--store-shards needs the sharded workdir layout: "
                f"{given!r} should be {canonical!r}"
            )
    opened = open_sharded_store(
        workdir,
        n_shards,
        namespace_finalizers=True,
        wal_fsync=args.wal_fsync,
        wal_segment_bytes=args.wal_segment_bytes,
        pitr=bool(args.pitr_dir),
    )
    store = opened["store"]
    for i, boot in enumerate(opened["boots"]):
        _print_boot(
            args,
            store,
            boot,
            which=f" [shard {i}]",
            state_file=shard_state_path(workdir, i),
        )
    rep = opened["report"]
    if rep is not None and not rep.clean:
        import json as _json

        print(
            "sharded WAL recovery was lossy (detected, bounded): "
            + _json.dumps(rep.summary()),
            flush=True,
        )
    print(
        f"store sharded {n_shards} ways under {workdir} "
        f"(rv {store.resource_version})",
        flush=True,
    )
    return store, opened["wals"], opened["pitrs"]


def _serve(args, store, wal, wals, pitrs, sharded: bool) -> int:
    if args.slow_request_s > 0:
        from kwok_tpu.utils import telemetry

        telemetry.flight_recorder().slow_threshold_s = args.slow_request_s
    injector = None
    plan = None
    if args.chaos_profile:
        from kwok_tpu.chaos import HttpFaultInjector, load_profile

        plan = load_profile(args.chaos_profile)
        injector = HttpFaultInjector(plan)
        print(
            f"chaos: HTTP fault injection armed (seed={plan.seed}, "
            f"duration={plan.duration}s)",
            flush=True,
        )

    fleet = None
    tenant_ids = []
    if args.fleet_tenants > 0:
        from kwok_tpu.fleet import FleetRegistry, fleet_tenant_ids

        tenant_ids = fleet_tenant_ids(args.fleet_tenants)
        fleet = FleetRegistry(
            store,
            tenant_ids,
            idle_after_s=args.fleet_idle_s,
            cold_after_s=args.fleet_cold_s,
            kubelet_url=args.kubelet_url or None,
        )
        print(
            f"fleet: hosting {len(tenant_ids)} virtual control planes "
            f"(idle after {args.fleet_idle_s}s, cold after "
            f"{args.fleet_cold_s}s)",
            flush=True,
        )

    flow = None
    if args.max_inflight > 0 or args.flow_config:
        from kwok_tpu.cluster.flowcontrol import (
            FlowConfig,
            FlowController,
            load_flow_config,
        )

        if args.flow_config:
            config = load_flow_config(args.flow_config)
        elif tenant_ids:
            # one APF level per tenant (shares=0 = guaranteed-minimum
            # seat) on top of the default split — the fleet isolation
            # contract (kwok_tpu.fleet.flow)
            from kwok_tpu.fleet import fleet_flow_config

            config = fleet_flow_config(
                tenant_ids, max_inflight=args.max_inflight
            )
        else:
            config = FlowConfig(max_inflight=args.max_inflight)
        flow = FlowController(
            config, seed=plan.seed if plan is not None else 0
        )
        levels = [lv.name for lv in config.levels]
        shown = (
            f"{levels[:4]} + {len(levels) - 4} tenant levels"
            if tenant_ids and len(levels) > 4
            else f"{levels}"
        )
        print(
            "flowcontrol: APF armed "
            f"(max_inflight={config.max_inflight}, levels={shown})",
            flush=True,
        )

    srv = APIServer(
        store,
        host=args.host,
        port=args.port,
        tls_cert=args.tls_cert or None,
        tls_key=args.tls_key or None,
        client_ca=args.client_ca or None,
        audit_path=args.audit_file or None,
        kubelet_url=args.kubelet_url or None,
        fault_injector=injector,
        flow=flow,
        watch_timeout=args.watch_timeout,
        fleet=fleet,
    )
    srv.start()
    print(f"apiserver listening on {srv.url}", flush=True)

    overload = None
    if plan is not None and plan.http.overloads:
        from kwok_tpu.chaos import OverloadDriver

        overload = OverloadDriver(plan, srv.url).start()
        print(
            f"chaos: overload flood armed "
            f"({len(plan.http.overloads)} windows)",
            flush=True,
        )

    pressure = None
    if plan is not None and wal is not None:
        from kwok_tpu.chaos import PressureDriver

        if PressureDriver.specs(plan):
            # exhaustion windows (disk-full/fsync-error/quota) run
            # inside this process against the live WAL handles — the
            # external DiskFaultDriver only applies corruption kinds.
            # On a sharded store each spec's `shard:` picks its target
            # handle, so a window degrades ONE shard's writes
            pressure = PressureDriver(
                plan, wal, store=store, wals=wals
            ).start()
            print(
                "chaos: filesystem pressure armed "
                f"({len(PressureDriver.specs(plan))} windows)",
                flush=True,
            )

    done = threading.Event()

    def _stop(signum, frame):
        done.set()

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)

    pitr = pitrs[0] if pitrs else None

    from kwok_tpu.snapshot.child import save_in_child
    from kwok_tpu.utils import telemetry

    # what a save costs: the whole of it (cut to prune, mostly the wait
    # for the child that serializes), and the part of it that ran in this
    # interpreter, which the request threads share
    h_save = telemetry.histogram(
        "kwok_apiserver_save_seconds",
        help="one periodic save of the store (state file, PITR copy, WAL compaction)",
        buckets=telemetry.DEFAULT_BUCKETS + (30.0, 60.0),
    )
    h_inprocess = telemetry.histogram(
        "kwok_apiserver_save_inprocess_seconds",
        help="one save less its wait for the snapshot child: cut, fork, renames, WAL compaction, prune",
    )
    waited = 0.0

    def commit(lane, state, path, arch, log, which: str = "") -> bool:
        """Every snapshot's way to the disk (kwok_tpu/snapshot/child.py):
        a forked child serializes and fsyncs ``state``, this thread waits
        without the GIL and renames; then ``lane``'s WAL is compacted up
        to the snapshot's rv and the archive pruned."""
        nonlocal waited
        try:
            waited += save_in_child(
                state,
                path,
                arch,
                guard=log.guard_io if log is not None else None,
            )
            lane.compact_wal(int(state["resourceVersion"]))
            if arch is not None:
                arch.prune(
                    keep_snapshots=args.pitr_keep,
                    sealed=log.take_archived() if log is not None else None,
                )
        except OSError as exc:
            # a full/failing disk cannot take a snapshot — skip this
            # tick instead of killing the daemon (the WAL keeps its
            # coverage because compaction only retires what a durable,
            # renamed snapshot covers)
            print(f"snapshot save skipped{which}: {exc}", flush=True)
            return False
        return True

    def save_single() -> bool:
        # online consistent cut: refs captured under one brief mutex
        # hold (copy-on-write store), serialized outside the lock and
        # outside this process — live writers are never stalled for it
        return commit(
            store, store.dump_state(copy=False), args.state_file, pitr, wal
        )

    def save_shards() -> bool:
        from kwok_tpu.cluster.sharding.layout import shard_state_path

        workdir = os.path.dirname(os.path.abspath(args.state_file))
        # One captured horizon per shard stamps its snapshot: an rv a
        # shard owns that is <= g was fully committed before the
        # capture (allocation happens inside the shard's commit hold,
        # which the dump also takes), so a dump whose own cut has NOT
        # advanced past g covers exactly this shard's slice of (0, g].
        # A dump that HAS advanced (a write landed in the capture->dump
        # window) would archive future state under an rv-g label —
        # restore --to-rv g would then resurrect objects that did not
        # exist at g — so that shard skips this tick and retries at
        # the next one, exactly like the full-disk skip.
        # Records landing after a capture stay in their shard's WAL
        # (compaction stops at g).
        ok = True
        for i in range(store.shard_count):
            shard = store.shard_lane(i)
            g = store.resource_version
            state = shard.dump_state(copy=False)
            if int(state.get("resourceVersion") or 0) > g:
                print(
                    f"snapshot save deferred [shard {i}]: write raced "
                    "the horizon capture",
                    flush=True,
                )
                ok = False
                continue
            state["resourceVersion"] = g
            # one shard's full disk must not stop the healthy shards'
            # snapshots — commit skips ITS tick only
            ok &= commit(
                shard,
                state,
                shard_state_path(workdir, i),
                pitrs[i] if i < len(pitrs) else None,
                wals[i] if i < len(wals) else None,
                which=f" [shard {i}]",
            )
        return ok

    def save_once() -> bool:
        nonlocal waited
        waited = 0.0
        t_save = time.perf_counter()
        ok = save_shards() if sharded else save_single()
        whole = time.perf_counter() - t_save
        h_save.observe(whole)
        h_inprocess.observe(whole - waited)
        return ok

    def rearm_loop() -> None:
        # background re-arm probe: degraded mode also clears when NO
        # traffic is hitting the /readyz probe (an idle cluster on a
        # disk that freed up must not stay read-only).  probe_writable
        # returns immediately when healthy, so one call per tick is
        # one probe, not two.  On the degraded→armed transition,
        # re-run the bootstrap namespace creation — a boot onto a full
        # disk skipped it.
        while not done.wait(1.0):
            # read the flag without probing (wal_health is probe-free)
            # so the transition is observable
            was = bool((store.wal_health() or {}).get("degraded"))
            if store.probe_writable() and was:
                srv.ensure_namespaces()

    if args.wal_file:
        threading.Thread(target=rearm_loop, daemon=True).start()

    saved_rv = -1
    while not done.wait(args.save_interval):
        if args.state_file and store.resource_version != saved_rv:
            # capture BEFORE the dump: writes landing while the
            # snapshot serializes must re-trigger the next tick (and
            # the shutdown save), not be marked covered
            rv = store.resource_version
            if save_once():
                saved_rv = rv
    if args.state_file and store.resource_version != saved_rv:
        save_once()
    if pressure is not None:
        pressure.stop()
        print(f"chaos: pressure windows {pressure.events}", flush=True)
    if overload is not None:
        overload.stop()
        print(f"chaos: overload flood {overload.snapshot()}", flush=True)
    srv.stop()
    if injector is not None:
        print(f"chaos: injected faults {injector.snapshot()}", flush=True)
    if flow is not None:
        print(f"flowcontrol: levels {flow.snapshot()}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
