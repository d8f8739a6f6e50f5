"""Fake-kubelet server tests: routes, logs/exec/attach/port-forward
resolution, Metric endpoints, and service discovery (reference behaviors
from pkg/kwok/server)."""

import http.client
import json
import socket
import threading
import time

import pytest

from kwok_tpu.api.extra_types import from_document
from kwok_tpu.server import Router, Server, ServerConfig

# -- router -----------------------------------------------------------------


def test_router_templates_and_precedence():
    r = Router()
    hits = []
    r.add("GET", "/exec/{ns}/{pod}/{container}", lambda req, **p: hits.append(("c3", p)))
    r.add("GET", "/exec/{ns}/{pod}/{uid}/{container}", lambda req, **p: hits.append(("c4", p)))
    r.add("GET", "/metrics", lambda req, **p: hits.append(("m", p)))
    r.add("GET", "/logs/", lambda req, **p: hits.append(("sub", p)))

    h, p = r.resolve("GET", "/exec/default/pod-0/app")
    h(None, **p)
    assert hits[-1] == ("c3", {"ns": "default", "pod": "pod-0", "container": "app"})
    h, p = r.resolve("GET", "/exec/default/pod-0/uid-1/app")
    h(None, **p)
    assert hits[-1][0] == "c4"
    h, p = r.resolve("GET", "/metrics")
    h(None, **p)
    assert hits[-1][0] == "m"
    h, p = r.resolve("GET", "/logs/anything/below")
    h(None, **p)
    assert hits[-1][0] == "sub"
    assert r.resolve("GET", "/nope") is None
    assert r.resolve("POST", "/metrics") is None


def test_router_literal_beats_template():
    r = Router()
    r.add("GET", "/metrics", lambda req, **p: "self")
    r.add("GET", "/metrics/nodes/{nodeName}/metrics/resource", lambda req, **p: "node")
    h, p = r.resolve("GET", "/metrics/nodes/n0/metrics/resource")
    assert h(None, **p) == "node" and p == {"nodeName": "n0"}
    h, _ = r.resolve("GET", "/metrics")
    assert h(None) == "self"


# -- server fixture ---------------------------------------------------------

PODS = [
    {
        "metadata": {"name": "pod-0", "namespace": "default",
                     "annotations": {"kwok.x-k8s.io/usage-cpu": "250m"}},
        "spec": {"nodeName": "node-0", "containers": [{"name": "app"}]},
        "status": {"phase": "Running"},
    },
    {
        "metadata": {"name": "pod-1", "namespace": "default", "annotations": {}},
        "spec": {"nodeName": "node-0", "containers": [{"name": "app"}]},
        "status": {"phase": "Running"},
    },
]
NODES = {"node-0": {"metadata": {"name": "node-0"}}}


@pytest.fixture()
def server(tmp_path):
    logf = tmp_path / "pod.log"
    logf.write_text("line1\nline2\nline3\n")

    cfg = ServerConfig(
        get_node=lambda n: NODES.get(n),
        get_pod=lambda ns, n: next(
            (p for p in PODS if p["metadata"]["name"] == n and p["metadata"]["namespace"] == ns),
            None,
        ),
        list_pods=lambda node: [p for p in PODS if p["spec"]["nodeName"] == node],
        list_nodes=lambda: list(NODES),
    )
    srv = Server(cfg)
    srv.set_configs(
        [
            from_document(
                {
                    "kind": "ClusterLogs",
                    "metadata": {"name": "all"},
                    "spec": {"logs": [{"logsFile": str(logf)}]},
                }
            ),
            from_document(
                {
                    "kind": "ClusterAttach",
                    "metadata": {"name": "all"},
                    "spec": {"attaches": [{"logsFile": str(logf)}]},
                }
            ),
            from_document(
                {
                    "kind": "Exec",
                    "metadata": {"name": "pod-0", "namespace": "default"},
                    "spec": {
                        "execs": [
                            {
                                "local": {
                                    "envs": [{"name": "KWOK_TEST_ENV", "value": "42"}],
                                }
                            }
                        ]
                    },
                }
            ),
            from_document(
                {
                    "kind": "ClusterResourceUsage",
                    "metadata": {"name": "usage"},
                    "spec": {
                        "usages": [
                            {
                                "usage": {
                                    "cpu": {
                                        "expression": '"kwok.x-k8s.io/usage-cpu" in pod.metadata.annotations ? Quantity(pod.metadata.annotations["kwok.x-k8s.io/usage-cpu"]) : Quantity("1m")'
                                    }
                                }
                            }
                        ]
                    },
                }
            ),
            from_document(
                {
                    "kind": "Metric",
                    "metadata": {"name": "metrics-resource"},
                    "spec": {
                        "path": "/metrics/nodes/{nodeName}/metrics/resource",
                        "metrics": [
                            {
                                "name": "pod_cpu_usage",
                                "dimension": "pod",
                                "kind": "gauge",
                                "labels": [{"name": "pod", "value": "pod.metadata.name"}],
                                "value": 'pod.Usage("cpu")',
                            }
                        ],
                    },
                }
            ),
        ]
    )
    port = srv.serve(0)
    yield srv, port
    srv.close()


def get(port, path, method="GET", body=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request(method, path, body=body)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def test_healthz(server):
    _, port = server
    for p in ("/healthz", "/livez", "/readyz"):
        status, data = get(port, p)
        assert status == 200 and data == b"ok"


def test_404_and_disabled(server):
    _, port = server
    status, _ = get(port, "/nope")
    assert status == 404
    status, _ = get(port, "/logs/var/log/foo")
    assert status == 405


def test_self_metrics(server):
    _, port = server
    status, data = get(port, "/metrics")
    assert status == 200
    assert b"kwok_up 1" in data


def test_container_logs(server):
    _, port = server
    status, data = get(port, "/containerLogs/default/pod-0/app")
    assert status == 200
    assert data == b"line1\nline2\nline3\n"
    status, data = get(port, "/containerLogs/default/pod-0/app?tailLines=1")
    assert data == b"line3\n"
    status, _ = get(port, "/containerLogs/default/ghost/app")
    assert status == 404


def test_tail_lines_zero_is_empty(server):
    _, port = server
    status, data = get(port, "/containerLogs/default/pod-0/app?tailLines=0")
    assert status == 200 and data == b""


def test_previous_logs(server, tmp_path):
    srv, port = server
    prev = tmp_path / "prev.log"
    prev.write_text("old incarnation\n")
    srv.set_configs(
        [
            from_document(
                {
                    "kind": "Logs",
                    "metadata": {"name": "pod-1", "namespace": "default"},
                    "spec": {
                        "logs": [
                            {
                                "logsFile": str(tmp_path / "pod.log"),
                                "previousLogsFile": str(prev),
                            }
                        ]
                    },
                }
            )
        ]
    )
    status, data = get(port, "/containerLogs/default/pod-1/app?previous=true")
    assert status == 200 and data == b"old incarnation\n"
    # pod-0 resolves via ClusterLogs which has no previous file
    status, _ = get(port, "/containerLogs/default/pod-0/app?previous=true")
    assert status == 404


def test_invalid_metric_path_not_advertised(server):
    srv, port = server
    with pytest.raises(ValueError):
        srv.set_configs(
            [
                from_document(
                    {
                        "kind": "Metric",
                        "metadata": {"name": "bad"},
                        "spec": {"path": "/not-metrics", "metrics": []},
                    }
                )
            ]
        )
    _, data = get(port, "/discovery/prometheus")
    assert b"bad" not in data


def test_port_forward_exact_beats_default(server):
    from kwok_tpu.api.extra_types import PortForward

    pf = PortForward.from_dict(
        {
            "kind": "PortForward",
            "metadata": {"name": "p", "namespace": "default"},
            "spec": {
                "forwards": [
                    {"command": ["cat"]},
                    {"ports": [8080], "target": {"port": 80, "address": "127.0.0.1"}},
                ]
            },
        }
    )
    assert pf.find(8080).target is not None  # exact match wins over default
    assert pf.find(9999).command == ["cat"]


def test_attach(server):
    _, port = server
    status, data = get(port, "/attach/default/pod-0/app")
    assert status == 200 and b"line1" in data


def test_exec_with_env(server):
    _, port = server
    status, data = get(
        port, "/exec/default/pod-0/app?command=sh&command=-c&command=echo+-n+%24KWOK_TEST_ENV"
    )
    assert status == 200
    assert data == b"42"
    # pod-1 has no exec config
    status, _ = get(port, "/exec/default/pod-1/app?command=true")
    assert status == 404


def test_exec_failure_propagates(server):
    _, port = server
    status, data = get(port, "/exec/default/pod-0/app?command=sh&command=-c&command=exit+3")
    assert status == 500


def test_metric_endpoint_per_node(server):
    _, port = server
    status, data = get(port, "/metrics/nodes/node-0/metrics/resource")
    assert status == 200
    text = data.decode()
    assert 'pod_cpu_usage{pod="pod-0"} 0.25' in text
    assert 'pod_cpu_usage{pod="pod-1"} 0.001' in text


def test_discovery(server):
    _, port = server
    status, data = get(port, "/discovery/prometheus")
    assert status == 200
    targets = json.loads(data)
    assert len(targets) == 1  # one metric x one node
    assert targets[0]["labels"]["__metrics_path__"] == "/metrics/nodes/node-0/metrics/resource"
    assert targets[0]["labels"]["metrics_name"] == "metrics-resource"


def test_port_forward_to_target(server):
    srv, port = server

    # tiny echo server as the forward target
    lsock = socket.socket()
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)
    target_port = lsock.getsockname()[1]

    def echo_once():
        conn, _ = lsock.accept()
        data = b""
        while True:
            chunk = conn.recv(4096)
            if not chunk:
                break
            data += chunk
        conn.sendall(b"echo:" + data)
        conn.close()

    t = threading.Thread(target=echo_once, daemon=True)
    t.start()

    srv.set_configs(
        [
            from_document(
                {
                    "kind": "PortForward",
                    "metadata": {"name": "pod-0", "namespace": "default"},
                    "spec": {
                        "forwards": [
                            {
                                "ports": [8080],
                                "target": {"port": target_port, "address": "127.0.0.1"},
                            }
                        ]
                    },
                }
            )
        ]
    )
    status, data = get(port, "/portForward/default/pod-0?port=8080", method="POST", body=b"hi")
    assert status == 200
    assert data == b"echo:hi"
    lsock.close()

    # unconfigured port
    status, _ = get(port, "/portForward/default/pod-0?port=9999")
    assert status == 404


def test_port_forward_command(server):
    srv, port = server
    srv.set_configs(
        [
            from_document(
                {
                    "kind": "ClusterPortForward",
                    "metadata": {"name": "cmd"},
                    "spec": {"forwards": [{"ports": [7000], "command": ["cat"]}]},
                }
            )
        ]
    )
    status, data = get(port, "/portForward/default/pod-1?port=7000", method="POST", body=b"pipe-through")
    assert status == 200
    assert data == b"pipe-through"


def test_logs_follow_streams(server):
    srv, port = server
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request("GET", "/containerLogs/default/pod-0/app?follow=true&timeoutSeconds=2")
    resp = conn.getresponse()
    first = resp.read(6)
    assert first == b"line1\n"
    rest = resp.read()
    conn.close()
    assert b"line3" in rest


def test_started_containers_metric(server):
    srv, port = server
    srv.record_container_start("node-0", 5)
    srv.set_configs(
        [
            from_document(
                {
                    "kind": "Metric",
                    "metadata": {"name": "starts"},
                    "spec": {
                        "path": "/metrics/nodes/{nodeName}/metrics/starts",
                        "metrics": [
                            {
                                "name": "kubelet_started_containers_total",
                                "dimension": "node",
                                "kind": "counter",
                                "value": "node.StartedContainersTotal()",
                            }
                        ],
                    },
                }
            )
        ]
    )
    status, data = get(port, "/metrics/nodes/node-0/metrics/starts")
    assert status == 200
    assert b"kubelet_started_containers_total 5" in data


def test_debug_profile_samples_all_threads(server):
    """/debug/pprof/profile?seconds=N (reference profiling.go:26): a
    real sampling CPU profile across threads, collapsed-stack format."""
    _, port = server
    stop = threading.Event()

    def spin():
        # a busy thread with a recognizable frame name
        while not stop.is_set():
            sum(range(500))

    t = threading.Thread(target=spin, name="spinner", daemon=True)
    t.start()
    try:
        status, data = get(port, "/debug/pprof/profile?seconds=0.4")
    finally:
        stop.set()
        t.join()
    assert status == 200
    text = data.decode()
    lines = [ln for ln in text.splitlines() if ln.strip()]
    assert lines, "empty profile"
    # collapsed format: frame;frame;... count
    head, n = lines[0].rsplit(" ", 1)
    assert int(n) >= 1 and (";" in head or ":" in head)
    assert "spin" in text  # the busy thread was sampled
    # on-CPU filter: the server's parked accept loop must not appear —
    # only assertable where the per-thread CPU accounting exists (the
    # profiler's documented wall-clock fallback samples parked threads)
    import os as _os

    if _os.path.exists("/proc/self/task"):
        assert "serve_forever" not in text


def test_debug_device_trace_writes_a_profile(server, tmp_path):
    """POST /debug/device/trace?seconds=N&dir=<path>: a jax.profiler
    trace from inside the process that holds the device, with the tick
    threads' stage annotations in it."""
    import glob
    import os
    import urllib.parse

    import jax  # noqa: F401 — the process "runs a device backend"

    from kwok_tpu.utils import telemetry

    _, port = server
    assert get(port, "/debug/device/trace?seconds=0.1", method="POST")[0] == 400
    assert get(port, "/debug/device/trace?seconds=x&dir=/tmp/t", method="POST")[0] == 400
    stop = threading.Event()

    def staged():
        while not stop.is_set():
            with telemetry.stage("TSrv", "device_tick"):
                time.sleep(0.005)

    t = threading.Thread(target=staged, daemon=True)
    t.start()
    out = str(tmp_path / "prof")
    try:
        status, data = get(
            port,
            "/debug/device/trace?seconds=0.2&dir=" + urllib.parse.quote(out),
            method="POST",
        )
    finally:
        stop.set()
        t.join()
    assert status == 200 and data.decode().strip() == out
    (path,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    from jax.profiler import ProfileData

    names = {
        ev.name
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for ev in line.events
    }
    assert "kwok/TSrv/device_tick" in names


def test_debug_pprof_goroutine_alias(server):
    _, port = server
    status, data = get(port, "/debug/pprof/goroutine")
    assert status == 200 and b"--- thread" in data
