"""Tracing subsystem (utils/trace.py + cmd/tracing.py): span nesting,
W3C propagation across the client→apiserver boundary, OTLP ingest, the
collector query surface, and the kwokctl --enable-tracing composition
(reference: jaeger component components/jaeger.go:42 + apiserver OTLP
config k8s/kube_apiserver_tracing_config.go:34-47)."""

import json
import threading
import time
import urllib.request

import pytest

from kwok_tpu.cluster.apiserver import APIServer
from kwok_tpu.cluster.client import ClusterClient
from kwok_tpu.cluster.store import ResourceStore
from kwok_tpu.cmd.tracing import TraceStore, serve
from kwok_tpu.utils.trace import (
    Tracer,
    from_traceparent,
    get_tracer,
    set_global,
    traceparent,
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
def reset_global_tracer():
    yield
    set_global(None)


def test_span_nesting_and_propagation():
    tr = Tracer("t")  # disabled: no endpoint
    with tr.span("outer") as outer:
        with tr.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
            hdr = traceparent(inner)
        tid, pid = from_traceparent(hdr)
        assert tid == outer.trace_id and pid == inner.span_id
    assert from_traceparent("garbage") == (None, None)
    assert from_traceparent(None) == (None, None)
    # remote continuation
    child = tr.span("remote", trace_id=tid, parent_id=pid)
    assert child.trace_id == tid and child.parent_id == pid


def test_export_to_collector_and_query(collector):
    store, url = collector
    tr = Tracer("svc-a", endpoint=f"{url}/v1/traces")
    with tr.span("op") as sp:
        sp.set("answer", 42).set("ok", True).set("ratio", 0.5)
    with tr.span("failing") as sp:
        sp.error("boom")
    tr.flush()
    assert store.received == 2

    # query API — jaeger-flavored
    services = json.loads(
        urllib.request.urlopen(f"{url}/api/services").read()
    )["data"]
    assert services == ["svc-a"]
    traces = json.loads(
        urllib.request.urlopen(f"{url}/api/traces?service=svc-a").read()
    )["data"]
    assert len(traces) == 2
    all_spans = [s for t in traces for s in t["spans"]]
    op = next(s for s in all_spans if s["name"] == "op")
    attrs = {a["key"]: a["value"] for a in op["attributes"]}
    assert attrs["answer"] == {"intValue": "42"}
    assert attrs["ok"] == {"boolValue": True}
    failing = next(s for s in all_spans if s["name"] == "failing")
    assert failing["status"]["code"] == 2
    # single-trace endpoint + HTML browser
    one = json.loads(
        urllib.request.urlopen(f"{url}/api/traces/{op['traceId']}").read()
    )["data"][0]
    assert one["traceID"] == op["traceId"]
    page = urllib.request.urlopen(f"{url}/trace/{op['traceId']}").read()
    assert b"op" in page
    assert urllib.request.urlopen(url).status == 200


def test_trace_crosses_client_apiserver_boundary(collector):
    """A span around a client mutation and the apiserver's span for
    that request share one trace (W3C traceparent over the wire)."""
    store, url = collector
    tracer = Tracer("e2e", endpoint=f"{url}/v1/traces")
    set_global(tracer)
    rstore = ResourceStore()
    with APIServer(rstore) as srv:
        client = ClusterClient(srv.url)
        with tracer.span("client.create-pod") as sp:
            client.create(
                {
                    "apiVersion": "v1",
                    "kind": "Pod",
                    "metadata": {"name": "traced", "namespace": "default"},
                    "spec": {"nodeName": "n", "containers": [{"name": "c"}]},
                    "status": {},
                }
            )
            client.patch(
                "Pod", "traced", {"metadata": {"labels": {"x": "1"}}}
            )
            trace_id = sp.trace_id
    tracer.flush()
    spans = (TraceStore.get(store, trace_id) or {}).get("spans") or []
    names = sorted(s["name"] for s in spans)
    assert "client.create-pod" in names
    assert "apiserver.POST" in names and "apiserver.PATCH" in names
    post = next(s for s in spans if s["name"] == "apiserver.POST")
    client_span = next(s for s in spans if s["name"] == "client.create-pod")
    assert post["parentSpanId"] == client_span["spanId"]


def test_disabled_tracer_is_inert():
    tr = Tracer("noop")
    with tr.span("x") as sp:
        sp.set("k", "v")
    assert tr.exported == 0 and tr.dropped == 0
    assert not tr._buf


def test_collector_coerces_malformed_spans(collector):
    """Untrusted OTLP ingest: bad field types are coerced at ingest so
    later query/browser GETs never crash."""
    store, url = collector
    payload = {
        "resourceSpans": [
            {
                "resource": {"attributes": [{"key": "service.name", "value": {"stringValue": "evil"}}]},
                "scopeSpans": [
                    {
                        "spans": [
                            {
                                "traceId": "abc",
                                "spanId": "d",
                                "name": 123,
                                "startTimeUnixNano": "abc",
                                "attributes": [{"bogus": 1}, "junk"],
                            },
                            "not-a-span",
                        ]
                    }
                ],
            }
        ]
    }
    req = urllib.request.Request(
        f"{url}/v1/traces",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    assert urllib.request.urlopen(req).status == 200
    # query + browser endpoints keep working
    traces = json.loads(urllib.request.urlopen(f"{url}/api/traces").read())["data"]
    assert traces and traces[0]["spans"][0]["startTimeUnixNano"] == "0"
    assert urllib.request.urlopen(f"{url}/trace/abc").status == 200
    # bad query params answer 400, not a dropped connection
    try:
        urllib.request.urlopen(f"{url}/api/traces?limit=abc")
        assert False
    except urllib.error.HTTPError as exc:
        assert exc.code == 400


def test_collector_survives_garbage_and_bounds(collector):
    store, url = collector
    req = urllib.request.Request(
        f"{url}/v1/traces", data=b"not json", headers={"Content-Type": "application/json"}
    )
    try:
        urllib.request.urlopen(req)
        assert False, "expected 400"
    except urllib.error.HTTPError as exc:
        assert exc.code == 400
    # unknown routes 404
    try:
        urllib.request.urlopen(f"{url}/api/traces/nope")
        assert False
    except urllib.error.HTTPError as exc:
        assert exc.code == 404


def test_cluster_with_tracing_component(tmp_path, monkeypatch):
    """kwokctl --enable-tracing: collector component runs, every
    component exports, and one scheduling trace spans scheduler +
    apiserver processes."""
    import urllib.error

    from kwok_tpu.cmd.kwokctl import main as kwokctl_main
    from kwok_tpu.ctl.runtime import BinaryRuntime

    monkeypatch.setenv("KWOK_TPU_HOME", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    name = "traced"
    assert (
        kwokctl_main(
            ["--name", name, "create", "cluster", "--enable-tracing", "--wait", "60"]
        )
        == 0
    )
    try:
        rt = BinaryRuntime(name)
        conf = rt.load_config()
        tport = conf["ports"]["tracing"]
        turl = f"http://127.0.0.1:{tport}"
        assert "tracing" in rt.running_components()
        assert kwokctl_main(["--name", name, "scale", "node", "--replicas", "1"]) == 0
        assert kwokctl_main(["--name", name, "scale", "pod", "--replicas", "1"]) == 0

        def services():
            try:
                return json.loads(
                    urllib.request.urlopen(f"{turl}/api/services", timeout=5).read()
                )["data"]
            except (urllib.error.URLError, OSError):
                return []

        # the bind trace crosses processes: scheduler span + apiserver
        # PATCH span with the same traceId.  Each component exports on
        # its own timer, so the apiserver's half can reach the
        # collector after the scheduler's: wait for the whole of it.
        def bind_traces():
            try:
                traces = json.loads(
                    urllib.request.urlopen(
                        f"{turl}/api/traces?service=scheduler&limit=50", timeout=5
                    ).read()
                )["data"]
            except (urllib.error.URLError, OSError):
                return []
            return [
                t
                for t in traces
                if any(s["name"] == "schedule.bind" for s in t["spans"])
            ]

        def crossed():
            return any(
                {s["service"] for s in t["spans"]} >= {"scheduler", "apiserver"}
                for t in bind_traces()
            )

        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and not crossed():
            time.sleep(0.5)
        assert {"apiserver", "scheduler"} <= set(services()), services()
        assert crossed(), bind_traces()
    finally:
        kwokctl_main(["--name", name, "delete", "cluster"])


# ------------------------------------------- retry traceparent continuity


class _ShedOnce:
    """Fault-injector duck type: reject the first matching mutation
    with a 429 + Retry-After, pass everything after — the
    deterministic 429-then-success sequence."""

    def __init__(self, status=429):
        self.status = status
        self.fired = 0

    def on_request(self, method, path, client_id):
        if method == "POST" and path.startswith("/r/") and self.fired == 0:
            self.fired += 1
            return {
                "action": "reject",
                "status": self.status,
                "retry_after": 0.05,
            }
        return None

    def on_watch_tick(self, client_id):
        return False


@pytest.mark.parametrize("status", [429, 503])
def test_retry_attempts_are_child_spans_of_originating_span(collector, status):
    """Traceparent continuity across client retries: a 429/503-then-
    success sequence yields ONE trace in which each retry attempt is a
    child span of the originating client span, and the eventually-
    successful server span parents to the retry attempt that carried
    it."""
    store, url = collector
    tracer = Tracer("retry-e2e", endpoint=f"{url}/v1/traces")
    set_global(tracer)
    rstore = ResourceStore()
    shed = _ShedOnce(status=status)
    with APIServer(rstore, fault_injector=shed) as srv:
        client = ClusterClient(srv.url)
        with tracer.span("client.create-pod") as sp:
            client.create(
                {
                    "apiVersion": "v1",
                    "kind": "Pod",
                    "metadata": {"name": "retried", "namespace": "default"},
                    "spec": {"nodeName": "n", "containers": [{"name": "c"}]},
                    "status": {},
                }
            )
            trace_id = sp.trace_id
            origin_span_id = sp.span_id
    assert shed.fired == 1, "the injector never shed"
    tracer.flush()
    tracer.stop()
    spans = (TraceStore.get(store, trace_id) or {}).get("spans") or []
    names = [s["name"] for s in spans]
    assert "client.create-pod" in names
    retries = [s for s in spans if s["name"] == "client.retry"]
    assert retries, f"no retry spans in {names}"
    # every retry attempt is a CHILD of the originating client span —
    # one trace, not N disconnected ones
    for r in retries:
        assert r["traceId"] == trace_id
        assert r["parentSpanId"] == origin_span_id
        attrs = {a["key"]: a["value"] for a in r["attributes"]}
        assert attrs["attempt"] == {"intValue": "2"}
        assert attrs["http.status"] == {"intValue": "201"}
    # the successful server-side span parents to the retry attempt
    posts = [s for s in spans if s["name"] == "apiserver.POST"]
    assert any(p["parentSpanId"] == retries[0]["spanId"] for p in posts), (
        [(p["name"], p["parentSpanId"]) for p in posts]
    )


# ------------------------------------------------- exporter drop accounting


def test_exporter_outage_counts_drops_and_logs_once(caplog):
    import logging

    # nothing listens on port 9: every flush fails
    tr = Tracer("t-outage", endpoint="http://127.0.0.1:9/v1/traces")
    with caplog.at_level(logging.WARNING, logger="kwok.tracer"):
        for _ in range(3):
            with tr.span("s"):
                pass
            tr.flush()
    tr.stop()
    stats = tr.stats()
    assert stats["dropped"] == 3 and stats["outage"] is True
    outage_lines = [
        r for r in caplog.records if "collector unreachable" in r.getMessage()
    ]
    assert len(outage_lines) == 1, "outage must log ONCE, not per batch"


def test_exporter_recovery_logs_once_and_resumes(caplog, collector):
    import logging

    store, url = collector
    # same endpoint, but reach it through a port that is dead first:
    # construct against the live collector, then simulate the outage by
    # pointing at a dead port and back (endpoint is a plain attribute)
    tr = Tracer("t-recover", endpoint=url + "/v1/traces")
    good = tr.endpoint
    tr.endpoint = "http://127.0.0.1:9/v1/traces"
    with caplog.at_level(logging.INFO, logger="kwok.tracer"):
        with tr.span("lost"):
            pass
        tr.flush()  # outage edge
        assert tr.stats()["outage"] is True
        tr.endpoint = good
        with tr.span("delivered"):
            pass
        tr.flush()  # recovery edge
    tr.stop()
    stats = tr.stats()
    assert stats["outage"] is False
    assert stats["exported"] >= 1 and stats["dropped"] >= 1
    recoveries = [
        r for r in caplog.records if "resuming span export" in r.getMessage()
    ]
    assert len(recoveries) == 1


def test_tracer_drop_counter_exposed_at_metrics():
    from kwok_tpu.cluster.flowcontrol import expose_metrics

    tr = Tracer("t-metrics", endpoint="http://127.0.0.1:9/v1/traces")
    set_global(tr)
    try:
        with tr.span("s"):
            pass
        tr.flush()
        text = expose_metrics(None, None)
        assert "kwok_tracer_dropped_spans_total 1" in text
        assert "kwok_tracer_exported_spans_total 0" in text
    finally:
        tr.stop()
        set_global(None)


def test_buffer_overflow_drops_are_counted(caplog):
    import logging

    tr = Tracer("t-buf", endpoint="http://127.0.0.1:9/v1/traces")
    tr.MAX_BUFFER = 2
    with caplog.at_level(logging.WARNING, logger="kwok.tracer"):
        for _ in range(5):
            with tr.span("s"):
                pass
    tr.stop()
    assert tr.dropped >= 3
    full = [r for r in caplog.records if "buffer full" in r.getMessage()]
    assert len(full) == 1


def test_buffer_overpressure_with_healthy_collector_logs_once(caplog, collector):
    """Sustained overpressure against a HEALTHY collector: one
    buffer-full warn per episode, and NO bogus 'collector reachable
    again' recovery line (the two edges are independent)."""
    import logging

    store, url = collector
    tr = Tracer("t-press", endpoint=url + "/v1/traces")
    tr.MAX_BUFFER = 1
    with caplog.at_level(logging.INFO, logger="kwok.tracer"):
        for _ in range(3):
            with tr.span("kept"):
                pass
            with tr.span("dropped"):  # overflows the 1-slot buffer
                pass
            tr.flush()  # healthy export of the kept span
    tr.stop()
    msgs = [r.getMessage() for r in caplog.records]
    assert sum("buffer full" in m for m in msgs) == 1, msgs
    assert not any("resuming span export" in m for m in msgs), msgs
    assert tr.stats()["outage"] is False
    assert tr.stats()["dropped"] == 3 and tr.stats()["exported"] >= 3
