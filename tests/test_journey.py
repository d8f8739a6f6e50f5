"""Causal lifecycle tracing (ISSUE 13): rv→span stitching across the
watch boundary, the per-object journey timeline, and critical-path
attribution — the store's commit ring carries the committing span
context per rv, both watch dialects resolve it at delivery, consumers
continue/link the causing trace, /debug/journey serves the timeline,
and the collector joins spans by links into waterfalls."""

import json
import threading
import time
import urllib.request

import pytest

from kwok_tpu.cluster.apiserver import APIServer
from kwok_tpu.cluster.client import ClusterClient
from kwok_tpu.cluster.informer import Informer, WatchOptions
from kwok_tpu.cluster.store import ResourceStore
from kwok_tpu.cmd.tracing import TraceStore, serve
from kwok_tpu.controllers.scheduler import Scheduler
from kwok_tpu.utils import telemetry
from kwok_tpu.utils.queue import Queue
from kwok_tpu.utils.trace import (
    Tracer,
    build_journey,
    critical_path,
    set_global,
)


@pytest.fixture()
def collector():
    store = TraceStore()
    httpd = serve(store, "127.0.0.1", 0)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    port = httpd.server_address[1]
    yield store, f"http://127.0.0.1:{port}"
    httpd.shutdown()
    httpd.server_close()


@pytest.fixture(autouse=True)
def clean_tracing():
    telemetry.journey().reset()
    yield
    set_global(None)
    telemetry.journey().reset()


def _pod(name, ns="default"):
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": name, "namespace": ns},
        "spec": {"containers": [{"name": "c", "image": "fake"}]},
        "status": {},
    }


def _node(i):
    return {
        "apiVersion": "v1",
        "kind": "Node",
        "metadata": {"name": f"node-{i}"},
        "status": {
            "allocatable": {"cpu": "16", "memory": "64Gi", "pods": "110"},
            "conditions": [{"type": "Ready", "status": "True"}],
        },
    }


def _wait(cond, budget=20.0):
    deadline = time.time() + budget
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return cond()


# ------------------------------------------------------- commit ring ctx


def test_commit_ring_carries_committing_span_context():
    tracer = Tracer("t", endpoint="http://127.0.0.1:9/v1/traces")
    set_global(tracer)
    store = ResourceStore()
    w = store.watch("Pod")  # ring only populates with a watcher
    try:
        with tracer.span("writer") as sp:
            out = store.create(_pod("ctxed"))
        rv = int(out["metadata"]["resourceVersion"])
        assert store.commit_context(rv) == (sp.trace_id, sp.span_id)
        meta = store.commit_meta(rv)
        assert meta[1] == out["metadata"]["uid"]
        assert (meta[2], meta[3], meta[4]) == ("Pod", "default", "ctxed")
        # an untraced write records identity but no ctx
        out2 = store.create(_pod("bare"))
        rv2 = int(out2["metadata"]["resourceVersion"])
        assert store.commit_context(rv2) is None
        assert store.commit_meta(rv2)[1] == out2["metadata"]["uid"]
    finally:
        w.stop()
        tracer.stop()


def test_commit_ring_is_bounded():
    store = ResourceStore()
    store.COMMIT_RING = 8
    w = store.watch("ConfigMap")
    try:
        rvs = []
        for i in range(20):
            out = store.create(
                {
                    "apiVersion": "v1",
                    "kind": "ConfigMap",
                    "metadata": {"name": f"c{i}", "namespace": "default"},
                }
            )
            rvs.append(int(out["metadata"]["resourceVersion"]))
        assert len(store._commit_times) <= store.COMMIT_RING + 1
        assert len(store._commit_meta) <= store.COMMIT_RING + 1
        assert store.commit_meta(rvs[0]) is None  # aged out
        assert store.commit_meta(rvs[-1]) is not None
    finally:
        w.stop()


# --------------------------------------------------------- journey ring


def test_journey_timeline_records_commit_and_watch_hops():
    store = ResourceStore()
    w = store.watch("Pod")
    try:
        out = store.create(_pod("traveler"))
        rv = int(out["metadata"]["resourceVersion"])
        store.patch(
            "Pod", "traveler", {"status": {"phase": "Running"}},
            subresource="status",
        )
        from kwok_tpu.cluster.store import observe_watch_delivery

        observe_watch_delivery(store, rv)
        observe_watch_delivery(store, rv)  # second delivery dedupes
        tl = telemetry.journey().lookup(kind="Pod", name="traveler")
        assert tl is not None and tl["namespace"] == "default"
        hops = tl["hops"]
        kinds = [h["hop"] for h in hops]
        assert kinds.count("commit") == 2
        assert kinds.count("watch") == 1
        running = [h for h in hops if h.get("phase") == "Running"]
        assert running, hops
        assert all(h["rv"] for h in hops)
    finally:
        w.stop()


def test_journey_metrics_exposed_with_drop_counters():
    from kwok_tpu.cluster.flowcontrol import expose_metrics

    jr = telemetry.journey()
    jr.record("u1", "Pod", "default", "m1", "commit", rv=1)
    text = expose_metrics(None, None)
    assert "kwok_journey_objects_evicted_total" in text
    assert "kwok_journey_hops_dropped_total" in text
    assert "kwok_journey_objects 1" in text


def test_debug_journey_endpoint():
    store = ResourceStore()
    with APIServer(store) as srv:
        client = ClusterClient(srv.url)
        w = store.watch("Pod")
        try:
            client.create(_pod("served"))
            tl = client.debug_journey(kind="pod", name="served")
            assert tl["name"] == "served"
            assert any(h["hop"] == "commit" for h in tl["hops"])
            listing = client.debug_journey()
            assert listing["stats"]["objects"] >= 1
            assert any(j["name"] == "served" for j in listing["journeys"])
            # unknown object → 404, not a crash
            from kwok_tpu.cluster.store import NotFound

            with pytest.raises(NotFound):
                client.debug_journey(kind="pod", name="never-existed")
        finally:
            w.stop()


# --------------------------------------------- ctx across the boundary


def test_remote_watch_stream_carries_ctx_side_channel():
    tracer = Tracer("t", endpoint="http://127.0.0.1:9/v1/traces")
    set_global(tracer)
    store = ResourceStore()
    with APIServer(store) as srv:
        client = ClusterClient(srv.url)
        w = client.watch("Pod")
        try:
            with tracer.span("cause") as sp:
                client.create(_pod("wired"))
            ev = w.next(timeout=5.0)
            assert ev is not None and ev.type == "ADDED"
            assert ev.ctx is not None
            # the apiserver's POST span continues the client trace, so
            # the delivered ctx shares the cause's trace id
            assert ev.ctx[0] == sp.trace_id
        finally:
            w.stop()
    tracer.stop()


def test_informer_resolves_ctx_in_process():
    tracer = Tracer("t", endpoint="http://127.0.0.1:9/v1/traces")
    set_global(tracer)
    store = ResourceStore()
    events: Queue = Queue()
    done = threading.Event()
    inf = Informer(store, "Pod")
    inf.watch(WatchOptions(), events, done=done)
    try:
        _wait(lambda: inf.relists >= 1)
        with tracer.span("creator") as sp:
            store.create(_pod("observed"))

        def got():
            ev, ok = events.get()
            return ev if ok else None

        ev = None

        def fetch():
            nonlocal ev
            nxt = got()
            if nxt is not None and nxt.type == "ADDED":
                ev = nxt
            return ev is not None

        assert _wait(fetch), "informer never forwarded the create"
        assert getattr(ev, "ctx", None) is not None
        assert ev.ctx[0] == sp.trace_id
    finally:
        done.set()
        tracer.stop()


def test_sharded_router_resolves_commit_context():
    from kwok_tpu.cluster.sharding import build_sharded_store

    tracer = Tracer("t", endpoint="http://127.0.0.1:9/v1/traces")
    set_global(tracer)
    router = build_sharded_store(2)
    w = router.watch("Pod")  # MergedWatcher over both shards
    try:
        with tracer.span("sharded-writer") as sp:
            out = router.create(_pod("split", ns="ns-a"))
        rv = int(out["metadata"]["resourceVersion"])
        assert router.commit_context(rv) == (sp.trace_id, sp.span_id)
        assert router.commit_meta(rv)[4] == "split"
    finally:
        w.stop()
        tracer.stop()


# ----------------------------------------- one trace create -> bind


def test_one_trace_from_create_through_bind(collector):
    """The causal chain crosses the watch boundary: the scheduler's
    bind span CONTINUES the client create's trace (resolved from the
    commit ring at watch delivery) and links the causing write."""
    cstore, url = collector
    tracer = Tracer("e2e", endpoint=f"{url}/v1/traces")
    set_global(tracer)
    store = ResourceStore()
    with APIServer(store) as srv:
        # daemon topology: the scheduler consumes the REMOTE watch, so
        # ctx rides the wire side channel
        sched_client = ClusterClient(srv.url)
        sched = Scheduler(sched_client, gang_policy="none").start()
        try:
            client = ClusterClient(srv.url)
            client.create(_node(0))
            # the commit ring carries a write's context only while a
            # watcher exists: a pod created before the scheduler's watch
            # is open reaches it through its LIST, with no trace to
            # continue (seen under -n 6, where the watch opens late)
            assert _wait(lambda: bool(store._state("Pod").watchers), 20.0)
            # and a pod that arrives before the node is refused (an Event,
            # no PATCH) and bound two seconds later under a trace of its own
            assert _wait(lambda: len(sched._nodes) >= 1, 20.0)
            with tracer.span("client.create-pod") as sp:
                client.create(_pod("journeyed"))
                trace_id = sp.trace_id

            def bound():
                pod = store.get("Pod", "journeyed", namespace="default")
                return bool((pod.get("spec") or {}).get("nodeName"))

            assert _wait(bound, 20.0), "pod never bound"
        finally:
            sched.stop()
    # the apiserver's span closes after the response is written, so a
    # pod read as bound may have its PATCH span still open in the
    # handler thread: flush until it has been exported
    def exported():
        tracer.flush()
        got = TraceStore.get(cstore, trace_id)
        return got is not None and "apiserver.PATCH" in {
            s["name"] for s in got["spans"]
        }

    _wait(exported, 10.0)
    tr = TraceStore.get(cstore, trace_id)
    assert tr is not None
    names = sorted(s["name"] for s in tr["spans"])
    assert "client.create-pod" in names
    assert "apiserver.POST" in names
    assert "schedule.bind" in names, names
    assert "apiserver.PATCH" in names, names
    bind = next(s for s in tr["spans"] if s["name"] == "schedule.bind")
    # the bind span links the causing write's context too
    assert bind.get("links"), bind
    tracer.stop()


# -------------------------------------------------- collector surfaces


def test_collector_stats_and_journey_join(collector):
    cstore, url = collector
    tracer = Tracer("svc", endpoint=f"{url}/v1/traces")
    with tracer.span("apiserver.POST") as cause:
        cause.set("apf.wait_s", 0.01)
    child = tracer.span(
        "schedule.bind", trace_id=None, parent_id=None
    )  # separate trace, linked
    child.set("pod", "default/joined")
    child.add_link(cause.trace_id, cause.span_id)
    with tracer.span("play.Pod") as play:
        play.set("object", "default/joined")
    child.end()
    tracer.flush()
    tracer.stop()

    stats = json.loads(urllib.request.urlopen(f"{url}/api/stats").read())
    assert stats["received"] == 3
    assert stats["traces"] >= 2
    assert "dropped" in stats and "evicted_traces" in stats

    j = json.loads(
        urllib.request.urlopen(f"{url}/api/journey?name=default/joined").read()
    )
    got = {h["name"] for h in j["hops"]}
    # the link join pulls the causing trace in alongside both
    # object-attributed spans
    assert {"apiserver.POST", "schedule.bind", "play.Pod"} <= got
    assert len(j["traces"]) >= 2
    assert abs(sum(j["breakdown_s"].values()) - j["total_s"]) < 1e-6

    # ns+name form resolves the same journey
    j2 = json.loads(
        urllib.request.urlopen(f"{url}/api/journey?ns=default&name=joined").read()
    )
    assert {h["name"] for h in j2["hops"]} == got

    cp = json.loads(
        urllib.request.urlopen(f"{url}/api/critical-path").read()
    )
    assert cp["journeys"] >= 1
    assert "sched" in cp["stages"] or "commit" in cp["stages"]

    # unknown object → 404
    try:
        urllib.request.urlopen(f"{url}/api/journey?name=default/none")
        assert False
    except urllib.error.HTTPError as exc:
        assert exc.code == 404


def test_build_journey_partitions_extent():
    ns = 1_000_000_000

    def span(name, start_s, end_s, **attrs):
        return {
            "traceId": "t1",
            "spanId": name,
            "name": name,
            "startTimeUnixNano": str(int(start_s * ns)),
            "endTimeUnixNano": str(int(end_s * ns)),
            "attributes": [
                {"key": k, "value": {"doubleValue": v}} for k, v in attrs.items()
            ],
        }

    spans = [
        # (t=0 exactly would hit the malformed-span filter: ingest
        # coerces bad timestamps to 0)
        span("client.create", 1.0, 1.5),
        # apf wait carved out of commit into queue
        span("apiserver.POST", 1.1, 1.3, **{"apf.wait_s": 0.1}),
        # gap 1.5-2.0 is watch
        span("schedule.bind", 2.0, 3.0),
        # nested commit wins the overlap (innermost work)
        span("apiserver.PATCH", 2.2, 2.4),
        span("play.Pod", 3.5, 4.0),
    ]
    j = build_journey(spans)
    bd = j["breakdown_s"]
    assert j["total_s"] == pytest.approx(3.0)
    assert sum(bd.values()) == pytest.approx(3.0)
    assert bd["queue"] == pytest.approx(0.1)
    assert bd["commit"] == pytest.approx(0.3)  # 0.2 POST + 0.2 PATCH - 0.1 queue
    assert bd["client"] == pytest.approx(0.3)  # 0.5 minus nested POST
    assert bd["sched"] == pytest.approx(0.8)  # bind minus nested PATCH
    assert bd["stage"] == pytest.approx(0.5)
    assert bd["watch"] == pytest.approx(1.0)  # the two gaps

    agg = critical_path([j, j])
    assert agg["journeys"] == 2
    assert agg["stages"]["watch"]["mean_s"] == pytest.approx(1.0)
    assert agg["total_s"]["mean"] == pytest.approx(3.0)


def test_flight_recorder_renders_collector_deep_links(collector):
    _, url = collector
    tracer = Tracer("fr", endpoint=f"{url}/v1/traces")
    set_global(tracer)
    try:
        rec = telemetry.FlightRecorder(size=8)
        rec.slow_threshold_s = 0.0
        rec.note_request("POST", "/r/pods", "system", 0.7, trace_id="abc123")
        dump = rec.dump()
        sample = dump["slow_requests"][-1]
        assert sample["trace_url"] == f"{url}/trace/abc123"
    finally:
        tracer.stop()


def test_flight_recorder_no_links_without_collector():
    rec = telemetry.FlightRecorder(size=8)
    rec.slow_threshold_s = 0.0
    rec.note_request("POST", "/r/pods", "system", 0.7, trace_id="abc123")
    assert "trace_url" not in rec.dump()["slow_requests"][-1]


# ------------------------------------------------ live-cluster e2e


def test_live_cluster_journey_create_to_running(tmp_path, monkeypatch, capsys):
    """ISSUE 13 acceptance: on a live cluster with --trace armed, one
    causally-linked chain create→commit→watch→bind→stage→Running is
    reconstructable via `kwokctl trace` / /api/journey, with per-hop
    durations summing to (within tolerance of) the observed
    time-to-running."""
    import urllib.error

    from kwok_tpu.cmd.kwokctl import main as kwokctl_main
    from kwok_tpu.ctl.runtime import BinaryRuntime

    monkeypatch.setenv("KWOK_TPU_HOME", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    name = "journey-e2e"
    assert (
        kwokctl_main(
            ["--name", name, "create", "cluster", "--trace", "--wait", "60"]
        )
        == 0
    )
    tracer = None
    try:
        rt = BinaryRuntime(name)
        tport = rt.load_config()["ports"]["tracing"]
        turl = f"http://127.0.0.1:{tport}"
        assert kwokctl_main(["--name", name, "scale", "node", "--replicas", "1"]) == 0
        client = rt.client(timeout=10.0)

        # warmup: the commit ring only carries contexts while watchers
        # exist, so prove the control plane's watch streams are live
        # (scheduler binds + kwok controller plays) before starting the
        # measured journey
        client.create(_pod("warmup"))

        def warm():
            try:
                pod = client.get("Pod", "warmup", namespace="default")
            except Exception:  # noqa: BLE001 — booting
                return False
            return (pod.get("status") or {}).get("phase") == "Running"

        assert _wait(warm, 60.0), "warmup pod never reached Running"

        # export this test's client span to the cluster's collector so
        # the journey starts at the originating create
        tracer = Tracer("kwokctl-e2e", endpoint=f"{turl}/v1/traces")
        set_global(tracer)
        t_create = time.time()
        with tracer.span("client.create-pod") as sp:
            client.create(_pod("journey-pod"))
            trace_id = sp.trace_id

        def running():
            try:
                pod = client.get("Pod", "journey-pod", namespace="default")
            except Exception:  # noqa: BLE001 — transient while booting
                return False
            return (pod.get("status") or {}).get("phase") == "Running"

        assert _wait(running, 60.0), "pod never reached Running"
        observed = time.time() - t_create
        tracer.flush()

        # daemons flush their exporters every ~2s; poll the collector
        # until the full causal chain landed
        def fetch_journey():
            try:
                return json.loads(
                    urllib.request.urlopen(
                        f"{turl}/api/journey?name=default/journey-pod",
                        timeout=5,
                    ).read()
                )
            except (urllib.error.URLError, urllib.error.HTTPError, OSError):
                return None

        j = None

        def complete():
            nonlocal j
            cand = fetch_journey()
            if cand is None:
                return False
            names = {h["name"] for h in cand["hops"]}
            if (
                "client.create-pod" in names
                and "apiserver.POST" in names
                and "schedule.bind" in names
                and any(n.startswith("play.") for n in names)
            ):
                j = cand
                return True
            return False

        assert _wait(complete, 30.0), fetch_journey()

        # ONE causally-linked chain: the originating create's trace id
        # is part of the stitched journey
        assert trace_id in j["traces"], (trace_id, j["traces"])
        # per-hop attribution PARTITIONS the journey extent...
        bd = j["breakdown_s"]
        assert abs(sum(bd.values()) - j["total_s"]) < 1e-3, bd
        assert bd["sched"] > 0 and bd["stage"] > 0 and bd["commit"] > 0, bd
        # ...and the extent tracks the observed time-to-running (the
        # observation adds polling + status-flush slop on a busy box)
        assert j["total_s"] <= observed + 2.0, (j["total_s"], observed)
        assert abs(j["total_s"] - observed) <= max(2.0, 0.75 * observed), (
            j["total_s"],
            observed,
        )

        # the apiserver's journey timeline shows the store-side half:
        # commits up to phase Running, watch deliveries, and the
        # rv→trace stitch on the commits
        tl = client.debug_journey(kind="pod", name="journey-pod")
        hops = tl["hops"]
        assert any(
            h["hop"] == "commit" and h.get("phase") == "Running" for h in hops
        ), hops
        assert any(h["hop"] == "watch" for h in hops), hops
        assert any(h["hop"] == "commit" and h.get("trace_id") for h in hops)

        # kwokctl trace renders the merged waterfall + attribution
        capsys.readouterr()
        assert (
            kwokctl_main(["--name", name, "trace", "pod", "default/journey-pod"])
            == 0
        )
        out = capsys.readouterr().out
        assert "schedule.bind" in out
        assert "attribution:" in out
        assert "commit" in out
    finally:
        set_global(None)
        if tracer is not None:
            tracer.stop()
        kwokctl_main(["--name", name, "delete", "cluster"])


# ------------------------------------------------------- CLI rendering


def test_critical_path_cli(collector, capsys):
    _, url = collector
    tracer = Tracer("cli", endpoint=f"{url}/v1/traces")
    with tracer.span("apiserver.POST"):
        pass
    with tracer.span("schedule.bind") as sp:
        sp.set("pod", "default/cli-pod")
    tracer.flush()
    tracer.stop()
    from kwok_tpu.utils.trace import _cli_main

    assert _cli_main(["--critical-path", "--collector", url]) == 0
    out = capsys.readouterr().out
    assert "critical path over" in out
    assert _cli_main(["--critical-path", "--collector", url, "--json"]) == 0
    assert "journeys" in capsys.readouterr().out
