"""HTTP facade over :class:`ResourceStore` — the cluster's API server.

In the reference, the communication backend *is* a real kube-apiserver:
controllers watch over HTTP/2 streams and write back PATCH/DELETE
(SURVEY §2.9; reference informer pkg/utils/informer/informer.go:33+,
patch writers pkg/kwok/controllers/pod_controller.go:370-390).  The
rebuild keeps that topology — components run as separate OS processes
wired through an apiserver — but the apiserver itself is this thin HTTP
layer over the in-process store (kwokctl's binary runtime launches it
the way the reference launches etcd+kube-apiserver,
reference runtime/binary/cluster.go:316-728).

Two dialects on one port:

1. the **Kubernetes wire protocol** (``/api``, ``/apis``, ``/version``,
   ``/openapi`` — see :mod:`kwok_tpu.cluster.k8s_api`), which stock
   kubectl/client-go tooling speaks, and
2. a compact legacy REST surface used by in-repo components, below.

REST surface (kind-keyed rather than group/version-keyed; our
``ResourceType`` carries the apiVersion):

- ``GET  /healthz``                        liveness (components poll it
  the way kwokctl polls a real apiserver's /healthz)
- ``GET  /apis``                           type discovery
- ``POST /apis``                           register a type (CRD create)
- ``GET  /r/{plural}``                     list; query params
  ``namespace`` ``labelSelector`` ``fieldSelector``
- ``GET  /r/{plural}?watch=1&resourceVersion=N``  newline-delimited
  JSON watch stream (``{"type","object","rv"}``, BOOKMARK heartbeats).
  An event's line is shared and immutable, like the object it carries:
  the store hands every watcher of a kind the same event instance; the
  line is an envelope round the object's JSON, which the committing
  thread encoded for the WAL's record and left on the event (without a
  WAL, and for a status or delete batch's events, the first stream
  that delivers it encodes, ``store.watch_line``), and every stream
  (selected, namespaced or resumed from the history ring alike; a
  Kubernetes-wire stream cuts its frame from them) writes those
  bytes.  Only with a tracer armed does each stream encode its own
  (the envelope then carries the delivery's ``ctx``)
- ``POST /r/{plural}``                     create
- ``GET/PUT/PATCH/DELETE /r/{plural}/{name}``     single object; query
  params ``namespace`` ``subresource``; PATCH type from Content-Type
  (application/{merge-patch,json-patch,strategic-merge-patch}+json)
- ``POST /bulk``, ``POST /txn``            many mutations in one
  round trip (``ResourceStore.bulk``; all-or-nothing ``transact``);
  the answer's entries are envelopes round the same bytes as the WAL
  record and the watch line (``_send_results``): an object is turned
  into JSON once a resourceVersion
- ``POST /status-batch``                   ``{kind, items}``, items
  ``[namespace, name, status(, resourceVersion)]``: the columnar status
  commit (``ResourceStore.apply_status_batch``) a device player drains
  its fired rows through; answers ``{"rvs": [...]}``, one number an item
  (the new resourceVersion, 0 not found, -1 refused as stale), no object
- ``POST /delete-batch``                   ``{kind, items}``, items
  ``[namespace, name, resourceVersion]``: the columnar commit of
  stage-driven deletes (``ResourceStore.apply_delete_batch``: finalizers
  emptied, object gone, one DELETED event each); answers ``{"rvs":
  [...]}`` in the status batch's code (the DELETED event's
  resourceVersion, 0 not found, -1 refused as stale)
- ``GET  /stats``                          resourceVersion + counts

Impersonation rides the ``Impersonate-User`` header (reference
stage_controller.go:341-378 patchResource w/ impersonation).

Errors map NotFound→404, Conflict→409, Expired→410, bad input→400,
each with a JSON body ``{"error", "reason"}``.
"""

from __future__ import annotations

import functools
import json
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional, Tuple
from urllib.parse import parse_qs, unquote, urlsplit

from kwok_tpu.cluster.flowcontrol import FlowRejected, expose_metrics
from kwok_tpu.native import status as _native_status
from kwok_tpu.utils import telemetry as _telemetry
from kwok_tpu.cluster.k8s_api import (
    PATCH_CONTENT_TYPES,
    K8sFacade,
    decode_continue as _decode_continue,
    encode_continue as _encode_continue,
    error_code_reason,
)
from kwok_tpu.cluster.store import (
    ResourceStore,
    ResourceType,
    observe_watch_burst,
    observe_watch_delivery,
    results_body,
    watch_line,
)

__all__ = ["APIServer", "PATCH_CONTENT_TYPES"]

#: Paths owned by the Kubernetes wire-protocol facade (k8s_api.py);
#: everything else stays on the legacy custom REST surface.
_K8S_HEADS = {"api", "apis", "version", "openapi"}

#: watch heartbeat cadence; lets both ends detect dead peers
_BOOKMARK_EVERY = 15.0

#: route heads that bypass flow control: liveness and the metrics
#: scrape must stay truthful under overload, or shedding hides itself
#: (same reason the chaos injector exempts them)
_FLOW_EXEMPT = {"healthz", "readyz", "livez", "metrics"}

#: fleet tenant-routing header (duck-type seam, same pattern as the
#: chaos injector: this module never imports kwok_tpu.fleet — the
#: attached registry object carries the behavior; fleet/tenant.py
#: declares the same literal as TENANT_HEADER)
_TENANT_HEADER = "X-Kwok-Tenant"

#: path dialect equivalent of the header: /fleet/t/{tenant}/{path...}
_TENANT_PREFIX = "t"

#: default server-side watch deadline (seconds): a real apiserver caps
#: every watch at --min-request-timeout-ish horizons and clients resume
#: transparently; this bounds how long a dead peer can pin a thread
DEFAULT_WATCH_TIMEOUT = 3600.0

#: observed request-duration histogram (SLO telemetry; the
#: apiserver_request_duration_seconds analog).  Labels are all drawn
#: from bounded sets: HTTP verb, route-derived resource plural (the
#: registered-type registry), APF priority level, and the direct-
#: dispatch shard index ("-" off the /shards lanes).
_H_REQ = _telemetry.histogram(
    "kwok_apiserver_request_duration_seconds",
    help="observed request duration (admission wait included; watches excluded)",
    labelnames=("verb", "kind", "level", "shard"),
    # the legitimate label product (verbs x registered kinds x levels
    # x shards) is wide; the cap stays a leak backstop, not a quota
    max_children=512,
)

#: what a ``POST /bulk`` costs the one interpreter every request and
#: stream shares: CPU seconds of the request's thread (thread time, so
#: without the turns it waited for) round the store call and the
#: building of the answer, one observation a request, and its ops
_H_BULK_CPU = _telemetry.histogram(
    "kwok_bulk_cpu_seconds",
    help="thread CPU seconds of a /bulk's store call and answer",
    buckets=(0.0001, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5),
)
_C_BULK_OPS = _telemetry.counter(
    "kwok_bulk_ops_total",
    help="ops of the /bulk requests observed in kwok_bulk_cpu_seconds",
)

#: non-resource route heads that may appear as a ``kind`` label; any
#: other unrecognized path collapses to one junk bucket so a client
#: spraying 404 paths cannot mint label values
_ROUTE_HEADS = frozenset(
    {
        "r",
        "api",
        "apis",
        "bulk",
        "txn",
        "status-batch",
        "delete-batch",
        "shards",
        "state",
        "stats",
        "debug",
        "dashboard",
        "version",
        "openapi",
        "fleet",
    }
)

def _route_kind(head: str, rest: list) -> str:
    """Bounded ``kind`` label for a request path: the resource plural
    for resource routes (legacy ``/r/{plural}`` and both k8s dialect
    shapes), else the route head.  Object names/namespaces NEVER reach
    the label (kwoklint ``metric-cardinality``) — only fixed path
    positions that hold resource words do."""
    if head == "r":
        return rest[0] if rest else "r"
    if head in ("api", "apis"):
        # /api/v1/... vs /apis/{group}/{version}/...
        parts = rest[1:] if head == "api" else rest[2:]
        if not parts:
            return head
        if parts[0] == "namespaces":
            # /namespaces/{ns}/{resource}[/...]; bare /namespaces[/{n}]
            return parts[2] if len(parts) >= 3 else "namespaces"
        return parts[0]
    return head


def _status_items(raw) -> list:
    """A ``/status-batch`` body's items as the tuples the store's
    committers index without looking: ``[namespace, name, status]`` or
    ``[namespace, name, status, resourceVersion]``."""
    items = []
    for it in raw or []:
        if not (
            isinstance(it, list)
            and len(it) in (3, 4)
            and (it[0] is None or isinstance(it[0], str))
            and isinstance(it[1], str)
            and isinstance(it[2], dict)
            and (len(it) == 3 or it[3] is None or isinstance(it[3], str))
        ):
            raise ValueError(
                "a status-batch item is [namespace, name, status] or "
                "[namespace, name, status, resourceVersion]"
            )
        items.append(tuple(it))
    return items


def _delete_items(raw) -> list:
    """A ``/delete-batch`` body's items as tuples: ``[namespace, name,
    resourceVersion]``, the version the sender read the object at."""
    items = []
    for it in raw or []:
        if not (
            isinstance(it, list)
            and len(it) == 3
            and (it[0] is None or isinstance(it[0], str))
            and isinstance(it[1], str)
            and isinstance(it[2], str)
        ):
            raise ValueError(
                "a delete-batch item is [namespace, name, resourceVersion]"
            )
        items.append(tuple(it))
    return items


def _traced(fn):
    """Span per mutating request, continuing the caller's W3C trace
    (the kube-apiserver OTLP tracing analog; reference
    k8s/kube_apiserver_tracing_config.go:34-47 samples everything)."""
    verb = fn.__name__[3:]

    def wrapper(self):
        from kwok_tpu.utils.trace import from_traceparent, get_tracer

        tr = get_tracer("apiserver")
        if not tr.enabled:
            return fn(self)
        tid, pid = from_traceparent(self.headers.get("traceparent"))
        with tr.span(f"apiserver.{verb}", trace_id=tid, parent_id=pid) as sp:
            sp.set("http.target", self.path)
            return fn(self)

    return wrapper


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    server_version = "kwok-tpu-apiserver"

    # the server object stuffs the store onto the class
    store: ResourceStore = None  # type: ignore[assignment]

    def log_message(self, fmt, *args):  # quiet; audit lives in the store
        pass

    def log_request(self, code="-", size="-"):
        """Append mutations to the audit sink as JSON lines (the
        kube-apiserver audit-log analog; reference kwokctl AuditLogs,
        runtime/config.go).  The sink is an unbuffered O_APPEND binary
        file, so each line lands as one atomic write even with many
        handler threads."""
        sink = getattr(self.server, "audit_sink", None)
        if sink is None or self.command == "GET":
            return
        try:
            status = int(code)  # handles both int and HTTPStatus
        except (TypeError, ValueError):
            status = 0
        try:
            sink.write(
                (
                    json.dumps(
                        {
                            "ts": time.time(),
                            "verb": self.command,
                            "path": self.path,
                            "user": self.headers.get("Impersonate-User") or "",
                            "code": status,
                        }
                    )
                    + "\n"
                ).encode()
            )
        except (OSError, ValueError):
            pass

    # ------------------------------------------------------------- plumbing

    def _send_json(self, code: int, payload, retry_after=None) -> None:
        self._send_body(code, json.dumps(payload).encode(), retry_after)

    def _send_body(self, code: int, body: bytes, retry_after=None) -> None:
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        if retry_after is not None:
            self.send_header("Retry-After", str(retry_after))
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _send_results(self, fn, body, cpu: bool = False) -> None:
        """Answer ``/bulk``, ``/txn`` and their per-shard lanes from
        what ``fn`` (the store's ``bulk`` or ``transact``) gives for the
        body's ops.  The store's entries are JSON already, envelopes
        round the bytes each commit left on its event, so nothing is
        copied or encoded for an answer that is written and dropped.  A
        tenant's proxy maps namespaces, so its answer is not the host's
        bytes: it is encoded here, from the stored instances."""
        ops = (body or {}).get("ops") or []
        t0 = time.thread_time()
        if self._tenant is None:
            answer = results_body(fn(ops, as_user=self._user(), encoded=True))
        else:
            results = fn(ops, as_user=self._user(), copy_results=False)
            answer = json.dumps({"results": results}).encode()
        if cpu:
            _H_BULK_CPU.observe(time.thread_time() - t0)
            _C_BULK_OPS.inc(len(ops))
        self._send_body(200, answer)

    def _send_error(self, exc: Exception) -> None:
        # same exception→code mapping as the k8s Status path, rendered
        # in the legacy body shape clients of this dialect expect.
        # Degraded read-only rejections carry Retry-After, same as the
        # APF shed path — a parseable back-off signal, never a bare 503
        code, reason = error_code_reason(exc)
        self._send_json(
            code,
            {"error": str(exc), "reason": reason},
            retry_after=getattr(exc, "retry_after", None),
        )

    def _read_body(self):
        length = int(self.headers.get("Content-Length") or 0)
        raw = self.rfile.read(length) if length else b""
        return json.loads(raw) if raw else None

    def _route(self) -> Tuple[str, list, dict]:
        # memoized per path: the flow gate (_dispatch) and the verb
        # handler both parse the same request, and this sits on the
        # hot path the whole overload layer exists to protect
        cached = getattr(self, "_route_cache", None)
        if cached is not None and cached[0] == self.path:
            return cached[1]
        u = urlsplit(self.path)
        parts = [unquote(p) for p in u.path.split("/") if p]
        q = {k: v[-1] for k, v in parse_qs(u.query).items()}
        parsed = ((parts[0] if parts else ""), parts[1:], q)
        self._route_cache = (self.path, parsed)
        return parsed

    def _user(self) -> Optional[str]:
        return self.headers.get("Impersonate-User") or None

    # --------------------------------------------------------------- chaos

    def _inject_fault(self) -> bool:
        """Consult the attached fault injector (kwok_tpu.chaos duck
        type: ``on_request(method, path, client_id) -> action|None``)
        before dispatching.  Returns True when the request was consumed
        by the fault (rejected or reset); latency faults sleep and fall
        through to normal handling."""
        inj = getattr(self.server, "fault_injector", None)
        if inj is None:
            return False
        act = inj.on_request(
            self.command, self.path, self.headers.get("X-Kwok-Client") or ""
        )
        if act is None:
            return False
        kind = act.get("action")
        if kind == "latency":
            # deliberately wall-clock: this stalls a REAL HTTP handler
            # thread to simulate network latency — never on the DST
            # virtual-time path (which injects faults in-process)
            time.sleep(float(act.get("seconds", 0.0)))  # kwoklint: disable=untestable-sleep
            return False
        if kind == "reject":
            code = int(act.get("status", 503))
            reason = (
                "TooManyRequests" if code == 429 else "ServiceUnavailable"
            )
            body = json.dumps(
                {"error": "chaos: injected fault", "reason": reason}
            ).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            ra = act.get("retry_after")
            if ra is not None:
                self.send_header("Retry-After", str(ra))
            self.send_header("Content-Length", str(len(body)))
            # the request body was never read — the keep-alive framing
            # is gone, so the connection must die with the rejection
            self.send_header("Connection", "close")
            self.end_headers()
            self.close_connection = True
            try:
                self.wfile.write(body)
            except (BrokenPipeError, ConnectionError, OSError):
                pass
            return True
        if kind == "reset":
            # abrupt close without a status line: the client observes a
            # connection reset / empty reply, exactly like a crashed or
            # partitioned server
            self.close_connection = True
            try:
                self.connection.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            return True
        return False

    @staticmethod
    def _ns(q: dict) -> Optional[str]:
        return q.get("namespace") or None

    # ------------------------------------------------------- leader fencing

    def _fenced_out(self) -> bool:
        """Validate a mutating request's ``X-Kwok-Leader-Fence`` header
        against the live election Lease (cluster/election.py fence
        tokens).  A mismatched holder or lease-transition count means
        the writer's leadership generation is stale — a paused-then-
        resumed (SIGSTOP/SIGCONT) ex-leader, or one deposed mid-flight
        — and its write is rejected with 409 before it can split-brain
        the store.  Reads never carry the header."""
        if self.command in ("GET", "HEAD"):
            return False
        from kwok_tpu.cluster.election import FENCE_HEADER, validate_fence

        raw = self.headers.get(FENCE_HEADER)
        if not raw:
            return False

        stale = validate_fence(self.store, raw)
        if stale is None:
            return False
        body = json.dumps(
            {
                "error": f"stale leader fence ({stale}): write rejected",
                "reason": "Conflict",
            }
        ).encode()
        self.send_response(409)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        # the request body was never read — the keep-alive framing is
        # gone, so the connection must die with the rejection
        self.send_header("Connection", "close")
        self.end_headers()
        self.close_connection = True
        try:
            self.wfile.write(body)
        except (BrokenPipeError, ConnectionError, OSError):
            pass
        return True

    # -------------------------------------------------------- fleet tenancy

    _tenant: Optional[str] = None
    _k8s = None

    def _facade(self):
        """The wire-protocol facade for this request: the tenant's own
        (bound to its prefixed object space) when routed by the fleet,
        else the server-wide one."""
        return getattr(self, "_k8s", None) or self.server.k8s

    def _enter_tenant(self) -> bool:
        """Resolve fleet tenancy for this request (header or path
        dialect) and scope ``self.store`` / the k8s facade to the
        tenant's virtual control plane.  Returns False when the request
        was consumed (unknown tenant → 404).

        Handler instances persist across keep-alive requests, so the
        per-request tenant state is RESET here first — a tenant-scoped
        store left on the instance would leak into the connection's
        next request."""
        self.__dict__.pop("store", None)  # back to the class-level host store
        self._k8s = None
        self._tenant = None
        fleet = getattr(self.server, "fleet", None)
        if fleet is None:
            return True
        tenant = self.headers.get(_TENANT_HEADER) or None
        # path dialect: /fleet/t/{tenant}/{path...} — rewrite to the
        # inner path; _route() re-parses on the changed self.path
        u = urlsplit(self.path)
        parts = [p for p in u.path.split("/") if p]
        if len(parts) >= 2 and parts[0] == "fleet" and parts[1] == _TENANT_PREFIX:
            if len(parts) < 3:
                self._send_json(
                    404, {"error": "no tenant in path", "reason": "NotFound"}
                )
                return False
            tenant = unquote(parts[2])
            inner_path = "/" + "/".join(parts[3:])
            self.path = inner_path + (f"?{u.query}" if u.query else "")
        if tenant is None:
            return True
        head = self._route()[0]
        if head in _FLOW_EXEMPT:
            # liveness and scrapes are host surfaces even when a client
            # stamps every request with its tenant header
            return True
        try:
            binding, _cold = fleet.touch(tenant)
        except KeyError as exc:
            self._send_json(404, {"error": str(exc), "reason": "NotFound"})
            return False
        # instance attribute shadows the class-level host store: every
        # verb handler and the watch loop below sees the tenant slice
        self.store = binding.store
        self._k8s = binding.k8s
        self._tenant = tenant
        return True

    # --------------------------------------------------------- flow control

    def _dispatch(self, inner) -> None:
        """Chaos seam first, then the leader fence, then APF admission:
        classify the caller's X-Kwok-Client into a priority level, take
        (or queue for) an inflight seat, shed with a well-formed 429 +
        Retry-After when the level's queue wait runs out.  Watches are
        long-running: admitted through the same gate but holding no
        seat."""
        if self._inject_fault():
            return
        if self._fenced_out():
            return
        if not self._enter_tenant():
            return
        flow = getattr(self.server, "flow", None)
        self._flow_level = None
        head, rest, q = self._route()
        # watches are long-running (minutes of held connection): their
        # duration is a stream lifetime, not a latency — they stay out
        # of the request histogram, same as real APF's WATCH exemption.
        # Exempt heads (healthz/metrics) stay unobserved too so the
        # scrape loop does not dominate the distribution.
        observe = (
            q.get("watch") not in ("1", "true")
            and head not in _FLOW_EXEMPT
        )
        t_req0 = time.monotonic()
        try:
            if flow is None or head in _FLOW_EXEMPT:
                inner()
                return
            cid = self.headers.get("X-Kwok-Client") or ""
            if self._tenant is not None:
                # tenant traffic is classified into the tenant's OWN
                # priority level before admission (the fleet isolation
                # contract: one tenant's flood saturates its own seats
                # and queues, never a neighbor's); admit() falls back
                # to client classification if the level is undeclared
                cid = cid or f"tenant:{self._tenant}"
                self._flow_level = self._tenant
            else:
                self._flow_level = flow.classify(cid)
            t_admit = time.monotonic()
            try:
                ticket = flow.admit(
                    cid,
                    self.command,
                    self.path,
                    # same truthiness as both dialects' watch routing —
                    # "watch=false" is an ordinary (seat-holding) list
                    long_running=q.get("watch") in ("1", "true"),
                    level=self._flow_level,
                )
                # stamp the admission wait on the request's live span
                # (observation-only): the critical-path analyzer reads
                # it back as the journey's "queue" share
                from kwok_tpu.utils.trace import peek_global

                tracer = peek_global()
                if tracer is not None and tracer.enabled:
                    sp = tracer.current()
                    if sp is not None:
                        sp.set(
                            "apf.wait_s",
                            round(time.monotonic() - t_admit, 6),
                        )
            except FlowRejected as rej:
                # sheds are counted by the rejected counter; observing
                # their queue wait as a "request duration" would read
                # as served-request latency (real APF excludes them)
                observe = False
                self._send_shed(rej)
                return
            try:
                inner()
            finally:
                flow.release(ticket)
        finally:
            if observe and _telemetry.enabled():
                self._observe_request(head, rest, t_req0)

    def _observe_request(self, head: str, rest: list, t0: float) -> None:
        """Observed request duration (bounded labels) plus the flight
        recorder's threshold-gated slow-request sample — the sample
        keeps the raw path and the request's trace id as the exemplar
        linking the latency outlier to its distributed trace."""
        dur = time.monotonic() - t0
        shard = "-"
        if head == "shards" and rest and str(rest[0]).isdigit():
            # same bounded-label discipline as the kind below: the
            # digit string is client-supplied, so only indexes the
            # store actually has become label values ("007" and
            # out-of-range spray collapse instead of minting children)
            idx = int(rest[0])
            n = int(getattr(self.store, "shard_count", 0) or 0)
            shard = str(idx) if 0 <= idx < n else "(invalid)"
        level = self._flow_level or "-"
        kind = _route_kind(head, rest)
        # the kind label must come from the BOUNDED registered-type
        # registry (or the fixed route-head set) — path segments are
        # client-supplied, and 404-spraying junk paths must collapse
        # into one bucket instead of minting label values until the
        # family's child cap folds every legit series into "(other)"
        if head not in _ROUTE_HEADS:
            kind = "(unknown)"
        elif kind not in _ROUTE_HEADS:
            try:
                self.store.resource_type(kind)
            except Exception:  # noqa: BLE001 — NotFound on junk plurals
                kind = "(unknown)"
        _H_REQ.observe(dur, self.command, kind, level, shard)
        if self._tenant is not None:
            # per-tenant duration via the fleet seam (the registry
            # observes into the bounded tenant-labeled family,
            # kwok_tpu/fleet/views.py — this module stays below fleet
            # in the layer map)
            fleet = getattr(self.server, "fleet", None)
            if fleet is not None:
                fleet.observe(self._tenant, dur)
        rec = _telemetry.flight_recorder()
        tid = ""
        if dur >= rec.slow_threshold_s:
            # the exemplar is only worth computing for a sample the
            # ring will actually keep
            from kwok_tpu.utils.trace import from_traceparent, peek_global

            tid = from_traceparent(self.headers.get("traceparent"))[0] or ""
            if not tid:
                tracer = peek_global()
                cur = tracer.current() if tracer is not None else None
                tid = cur.trace_id if cur is not None else ""
        rec.note_request(self.command, self.path, level, dur, trace_id=tid)

    def _send_shed(self, rej: FlowRejected) -> None:
        """429 with Retry-After — the graceful-shedding contract: the
        client always gets a parseable rejection, never a hung socket
        or an unexplained reset."""
        body = json.dumps(
            {"error": f"overloaded: {rej}", "reason": "TooManyRequests"}
        ).encode()
        self.send_response(429)
        self.send_header("Content-Type", "application/json")
        self.send_header("Retry-After", str(rej.retry_after))
        self.send_header("Content-Length", str(len(body)))
        # the request body was never read — the keep-alive framing is
        # gone, so the connection must die with the rejection
        self.send_header("Connection", "close")
        self.end_headers()
        self.close_connection = True
        try:
            self.wfile.write(body)
        except (BrokenPipeError, ConnectionError, OSError):
            pass

    # --------------------------------------------------------------- verbs

    def do_GET(self):
        self._dispatch(self._handle_get)

    def _handle_get(self):
        head, rest, q = self._route()
        if head in _K8S_HEADS and self._facade().handle(self, "GET", head, rest, q):
            return
        try:
            if head == "healthz" or head == "livez":
                # liveness: the process is up and serving.  Deliberately
                # NOT readiness — a daemon on a full disk is alive, and
                # the supervisor must not restart-loop it (a restart
                # cannot fix the disk)
                self._send_json(200, {"status": "ok"})
            elif head == "readyz":
                # readiness: liveness AND storage can accept writes.
                # Split from /healthz so degraded mode is visible to
                # kwokctl / the supervisor without reading as "crashed";
                # polling it doubles as the throttled re-arm probe.
                deg = self.store.storage_degraded()
                if deg is None:
                    self._send_json(200, {"status": "ok"})
                else:
                    self._send_json(
                        503,
                        {
                            "status": "degraded",
                            "reason": "StorageDegraded",
                            "storage": deg,
                        },
                        retry_after=5,
                    )
            elif head == "metrics":
                # per-priority-level flow-control gauges + watch
                # eviction counters, Prometheus text format
                body = expose_metrics(
                    getattr(self.server, "flow", None), self.store
                ).encode()
                self.send_response(200)
                self.send_header(
                    "Content-Type", "text/plain; version=0.0.4"
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif head == "dashboard":
                # built-in live dashboard — the kubernetes-dashboard
                # component seat (reference components/dashboard.go runs
                # the real dashboard image; a source-tree framework
                # serves its own page off the cluster state)
                body = _DASHBOARD_HTML.encode()
                self.send_response(200)
                self.send_header("Content-Type", "text/html")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif head == "shards":
                # shard route table (kwok_tpu/cluster/sharding): the
                # per-shard direct-dispatch clients derive their own
                # copy of the placement from this.  A single store has
                # no topology — 404 tells the probe to stay routed.
                topo = getattr(self.store, "shard_topology", None)
                if topo is None:
                    self._send_json(
                        404, {"error": "store is not sharded", "reason": "NotFound"}
                    )
                else:
                    self._send_json(200, topo())
            elif head == "state":
                # raw store dump — the etcd-snapshot analog (reference
                # kwokctl snapshot save, etcd/save.go)
                self._send_json(200, self.store.dump_state())
            elif head == "stats":
                counts = {
                    t.plural: self.store.count(t.kind) for t in self.store.kinds()
                }
                body = {
                    "resourceVersion": self.store.resource_version,
                    "counts": counts,
                }
                wal = self.store.wal_health()
                if wal is not None:
                    # storage-integrity surface: segment count, live
                    # bytes, last-fsync age, recovery/corruption
                    # counters (kwokctl get components reads these)
                    body["wal"] = wal
                lat = _telemetry.registry().summary()
                if lat:
                    # compact per-family p50/p99 of the observed SLO
                    # histograms (kwokctl get components renders the
                    # request-duration row as its latency column)
                    body["latency"] = lat
                fleet = getattr(self.server, "fleet", None)
                if fleet is not None:
                    # tenant count + cold/warm/idle split (kwokctl get
                    # components grows a fleet= column from this)
                    body["fleet"] = fleet.snapshot()
                # per-unit native load state of THIS process: a store
                # on the pure-Python drain is slower, never wrong, so
                # nothing else would tell (kwok_tpu/native/_artifact.py)
                body["native"] = _native_status()
                self._send_json(200, body)
            elif head == "debug" and rest == ["flightrecorder"]:
                # the flight recorder: last-N tick stage breakdowns +
                # slow-request samples (trace-id exemplars), bounded
                # ring — the after-the-fact answer to "what was slow
                # two minutes ago" without a profiler attached
                self._send_json(200, _telemetry.flight_recorder().dump())
            elif head == "debug" and rest == ["journey"]:
                # per-object journey timeline (bounded uid-keyed ring,
                # utils/telemetry.JourneyRecorder): every commit/watch
                # hop this apiserver observed for the named object, with
                # the committing trace ids — `kwokctl trace` joins this
                # with the collector's span view
                jr = _telemetry.journey()
                if q.get("name") or q.get("uid"):
                    tl = jr.lookup(
                        kind=q.get("kind"),
                        namespace=q.get("ns") or q.get("namespace"),
                        name=q.get("name"),
                        uid=q.get("uid"),
                    )
                    if tl is None:
                        self._send_json(
                            404,
                            {
                                "error": "no journey recorded for that "
                                "object (aged out of the ring, or "
                                "telemetry disarmed)",
                                "reason": "NotFound",
                            },
                        )
                    else:
                        self._send_json(200, tl)
                else:
                    self._send_json(
                        200,
                        {
                            "stats": jr.stats(),
                            "journeys": jr.journeys(
                                kind=q.get("kind"),
                                limit=int(q.get("limit") or 20),
                            ),
                        },
                    )
            elif head == "fleet":
                # fleet status (host surface): per-tenant lifecycle
                # state, pinned shard, and latency quantiles — what
                # `kwokctl get fleet` renders.  ?tenant= adds the
                # tenant's journey/critical-path slice.
                fleet = getattr(self.server, "fleet", None)
                if fleet is None:
                    self._send_json(
                        404,
                        {"error": "not a fleet apiserver", "reason": "NotFound"},
                    )
                elif q.get("tenant"):
                    try:
                        self._send_json(200, fleet.tenant_detail(q["tenant"]))
                    except KeyError as exc:
                        self._send_json(
                            404, {"error": str(exc), "reason": "NotFound"}
                        )
                else:
                    self._send_json(200, fleet.report())
            elif head == "r" and len(rest) == 1:
                # canonical watch values only — must stay in lockstep
                # with _dispatch's long-running classification, or a
                # seat-holding request could be served as an
                # indefinite stream
                if q.get("watch") in ("1", "true"):
                    self._serve_watch(rest[0], q)
                elif q.get("limit") or q.get("continue"):
                    items, rv, nxt = self.store.list_page(
                        rest[0],
                        namespace=self._ns(q),
                        label_selector=q.get("labelSelector"),
                        field_selector=q.get("fieldSelector"),
                        limit=int(q.get("limit") or 0),
                        continue_from=_decode_continue(q.get("continue")),
                        copy=False,  # encoded at once, below
                    )
                    body = {"items": items, "resourceVersion": str(rv)}
                    if nxt is not None:
                        body["continue"] = _encode_continue(nxt)
                    self._send_json(200, body)
                else:
                    items, rv = self.store.list(
                        rest[0],
                        namespace=self._ns(q),
                        label_selector=q.get("labelSelector"),
                        field_selector=q.get("fieldSelector"),
                    )
                    self._send_json(200, {"items": items, "resourceVersion": str(rv)})
            elif head == "r" and len(rest) == 2:
                obj = self.store.get(rest[0], rest[1], namespace=self._ns(q))
                self._send_json(200, obj)
            else:
                self._send_json(404, {"error": "no such route", "reason": "NotFound"})
        except Exception as exc:  # noqa: BLE001 — translated to HTTP
            try:
                self._send_error(exc)
            except (BrokenPipeError, ConnectionError):
                pass

    @_traced
    def do_POST(self):
        self._dispatch(self._handle_post)

    def _handle_post(self):
        head, rest, q = self._route()
        if head in _K8S_HEADS and self._facade().handle(self, "POST", head, rest, q):
            return
        try:
            body = self._read_body()
            if head == "apis":
                self.store.register_type(
                    ResourceType(
                        api_version=body["api_version"],
                        kind=body["kind"],
                        plural=body["plural"],
                        namespaced=bool(body.get("namespaced", True)),
                    )
                )
                self._send_json(201, {"status": "registered"})
            elif head == "bulk":
                self._send_results(self.store.bulk, body, cpu=True)
            elif head == "txn":
                # all-or-nothing sibling of /bulk (gang scheduling's
                # commit lane); TransactionAborted → 409 via the shared
                # error mapping, with the failing op index in the body
                self._send_results(self.store.transact, body)
            elif head == "status-batch" and self._tenant is None:
                # the columnar sibling of a /bulk of status patches
                # (module docstring); inside _dispatch like /bulk.  A
                # tenant's slice has no such lane: its proxy would hand
                # the host's namespaces through unmapped
                body = body or {}
                results = self.store.apply_status_batch(
                    body.get("kind") or "", _status_items(body.get("items"))
                )
                self._send_json(
                    200,
                    {
                        "rvs": [
                            r[0] if r else (0 if r is None else -1)
                            for r in results
                        ]
                    },
                )
            elif head == "delete-batch" and self._tenant is None:
                # the status batch's sibling for stage-driven deletes,
                # inside _dispatch and closed to a tenant's slice alike
                body = body or {}
                results = self.store.apply_delete_batch(
                    body.get("kind") or "", _delete_items(body.get("items"))
                )
                self._send_json(
                    200,
                    {"rvs": [r or (0 if r is None else -1) for r in results]},
                )
            elif head == "shards" and len(rest) == 2 and rest[1] in ("bulk", "txn"):
                # per-shard direct-dispatch lanes (KUBEDIRECT shape,
                # kwok_tpu/cluster/sharding/dispatch.py): the caller
                # routed with its own route table; the shard
                # re-validates ownership.  Sitting inside _dispatch
                # keeps APF admission and the leader fence at this
                # boundary, exactly like the routed lanes.
                fn = getattr(
                    self.store,
                    "shard_bulk" if rest[1] == "bulk" else "shard_transact",
                    None,
                )
                if fn is None:
                    self._send_json(
                        404, {"error": "store is not sharded", "reason": "NotFound"}
                    )
                else:
                    self._send_results(
                        functools.partial(fn, int(rest[0])),
                        body,
                        cpu=rest[1] == "bulk",
                    )
            elif head == "r" and len(rest) == 1:
                out = self.store.create(
                    body, namespace=self._ns(q), as_user=self._user()
                )
                self._send_json(201, out)
            else:
                self._send_json(404, {"error": "no such route", "reason": "NotFound"})
        except Exception as exc:  # noqa: BLE001
            self._send_error(exc)

    @_traced
    def do_PUT(self):
        self._dispatch(self._handle_put)

    def _handle_put(self):
        head, rest, q = self._route()
        if head in _K8S_HEADS and self._facade().handle(self, "PUT", head, rest, q):
            return
        try:
            body = self._read_body()
            if head == "state":
                n = self.store.restore_state(body or {})
                self._send_json(200, {"restored": n})
            elif head == "r" and len(rest) == 2:
                out = self.store.update(
                    body, subresource=q.get("subresource") or "", as_user=self._user()
                )
                self._send_json(200, out)
            else:
                self._send_json(404, {"error": "no such route", "reason": "NotFound"})
        except Exception as exc:  # noqa: BLE001
            self._send_error(exc)

    @_traced
    def do_PATCH(self):
        self._dispatch(self._handle_patch)

    def _handle_patch(self):
        head, rest, q = self._route()
        if head in _K8S_HEADS and self._facade().handle(self, "PATCH", head, rest, q):
            return
        try:
            ctype = (self.headers.get("Content-Type") or "").split(";")[0].strip()
            patch_type = PATCH_CONTENT_TYPES.get(ctype, "merge")
            body = self._read_body()
            if head == "r" and len(rest) == 2:
                out = self.store.patch(
                    rest[0],
                    rest[1],
                    body,
                    patch_type=patch_type,
                    namespace=self._ns(q),
                    subresource=q.get("subresource") or "",
                    as_user=self._user(),
                )
                self._send_json(200, out)
            else:
                self._send_json(404, {"error": "no such route", "reason": "NotFound"})
        except Exception as exc:  # noqa: BLE001
            self._send_error(exc)

    @_traced
    def do_DELETE(self):
        self._dispatch(self._handle_delete)

    def _handle_delete(self):
        head, rest, q = self._route()
        if head in _K8S_HEADS and self._facade().handle(self, "DELETE", head, rest, q):
            return
        try:
            if head == "r" and len(rest) == 2:
                out = self.store.delete(
                    rest[0], rest[1], namespace=self._ns(q), as_user=self._user()
                )
                if out is None:
                    # fully gone → 204; graceful (finalizers pending) → 200
                    # with the live object. Status code, not body sniffing,
                    # distinguishes the two.
                    self.send_response(204)
                    self.send_header("Content-Length", "0")
                    self.end_headers()
                else:
                    self._send_json(200, out)
            else:
                self._send_json(404, {"error": "no such route", "reason": "NotFound"})
        except Exception as exc:  # noqa: BLE001
            self._send_error(exc)

    # --------------------------------------------------------------- watch

    def _serve_watch(self, plural: str, q: dict) -> None:
        since = q.get("resourceVersion")
        w = self.store.watch(
            plural,
            namespace=self._ns(q),
            since_rv=int(since) if since else None,
            label_selector=q.get("labelSelector"),
            field_selector=q.get("fieldSelector"),
        )
        # Connection: close + unframed NDJSON until either side hangs up
        # (one TCP connection per watch, like a real apiserver watch).
        self.send_response(200)
        self.send_header("Content-Type", "application/json; stream=watch")
        self.send_header("Connection", "close")
        self.end_headers()
        self.close_connection = True
        # the metrics' label: a registered type's kind (the watch above
        # raised NotFound for anything else), so a bounded set
        kind = self.store.resource_type(plural).kind
        shutdown = getattr(self.server, "shutting_down", None)
        inj = getattr(self.server, "fault_injector", None)
        cid = self.headers.get("X-Kwok-Client") or ""
        # server-side deadline: ?timeoutSeconds=N, else the server
        # default — a clean EOF the reflector resumes from, so no dead
        # peer can pin this handler thread forever
        timeout_s = float(q.get("timeoutSeconds") or 0) or getattr(
            self.server, "watch_timeout", 0
        )
        deadline = time.monotonic() + timeout_s if timeout_s else None
        # rv→span stitching across the watch boundary: with a tracer
        # armed, each event envelope carries the committing span's
        # context resolved from the store's commit ring (side channel —
        # the OBJECT payload is untouched; with tracing off the bytes
        # are exactly the pre-existing envelope).  Resolution is ONE
        # batched ring lookup per flushed burst — the ring lives under
        # the writers' mutex, so per-event holds would multiply lock
        # pressure by watcher fan-out.
        from kwok_tpu.utils.trace import peek_global

        _tr = peek_global()
        ctx_many = (
            getattr(self.store, "commit_contexts", None)
            if _tr is not None and _tr.enabled
            else None
        )

        def _encode_burst(burst):
            """The burst's lines, and how many this stream had to encode."""
            if ctx_many is not None:
                # traced: the envelope carries what THIS delivery
                # resolved from the ring, so it is nobody else's line
                ctxs = ctx_many([e.rv for e in burst])
                out = []
                for e in burst:
                    payload = {"type": e.type, "object": e.object, "rv": e.rv}
                    ctx = ctxs.get(e.rv)
                    if ctx is not None:
                        payload["ctx"] = list(ctx)
                    out.append(self._encode_line(payload))
                return out, len(out)
            # a line is on the event since its commit, or encoded by
            # the first stream that delivers it and kept there
            # (store.watch_line): the store hands every watcher of a
            # kind the same instances, so the other streams, of this
            # dialect and of the Kubernetes wire, write these bytes
            out = []
            fresh = 0
            for e in burst:
                line, encoded = watch_line(e)
                fresh += encoded
                out.append(line)
            return out, fresh

        try:
            idle = 0.0
            last_chaos = time.monotonic()
            while shutdown is None or not shutdown.is_set():
                if deadline is not None and time.monotonic() >= deadline:
                    break
                if inj is not None:
                    # at most one drop draw per 0.25s: under event load
                    # the loop spins per burst, and a per-iteration draw
                    # would scale the drop rate with traffic instead of
                    # the per-tick probability the profile documents
                    now = time.monotonic()
                    if now - last_chaos >= 0.25:
                        last_chaos = now
                        if inj.on_watch_tick(cid):
                            # chaos watch-stream drop: hang up
                            # mid-stream; the client reflector resumes
                            # from its last rv
                            break
                ev = w.next(timeout=0.25)
                if ev is None:
                    if w.stopped:
                        # evicted by backpressure (slow consumer): hang
                        # up so the client resumes at its last rv — the
                        # watch-cache-gone answer, not unbounded buffering
                        if getattr(w, "evicted", False):
                            flow = getattr(self.server, "flow", None)
                            if flow is not None:
                                flow.note_evicted(
                                    getattr(self, "_flow_level", None)
                                )
                        break
                    idle += 0.25
                    if idle >= _BOOKMARK_EVERY:
                        idle = 0.0
                        self._write_line(
                            {"type": "BOOKMARK", "rv": self.store.resource_version}
                        )
                    continue
                idle = 0.0
                # drain the burst (e.g. a bulk tick's worth of MODIFIED
                # events) into one buffered write + single flush
                burst = [ev]
                while len(burst) < 512:
                    ev = w.next(timeout=0)
                    if ev is None:
                        break
                    burst.append(ev)
                last_rv = burst[-1].rv
                t_enc = time.thread_time()
                lines, fresh = _encode_burst(burst)
                t_enc = time.thread_time() - t_enc
                self.wfile.write(b"".join(lines))
                self.wfile.flush()
                observe_watch_burst(kind, fresh, len(lines), t_enc)
                # observed rv-commit -> delivery lag, one sample per
                # flushed burst (shared with the k8s dialect)
                observe_watch_delivery(self.store, last_rv)
        except (BrokenPipeError, ConnectionError, socket.timeout, OSError):
            pass
        finally:
            w.stop()

    @staticmethod
    def _encode_line(payload: dict) -> bytes:
        return json.dumps(payload).encode() + b"\n"

    def _write_line(self, payload: dict) -> None:
        self.wfile.write(self._encode_line(payload))
        self.wfile.flush()


#: one self-contained page; data comes from the k8s-protocol routes the
#: page shares a port with, refreshed client-side
_DASHBOARD_HTML = """<!doctype html>
<html><head><title>kwok-tpu dashboard</title><style>
body{font-family:sans-serif;margin:2em}table{border-collapse:collapse}
td,th{border:1px solid #999;padding:4px 8px;text-align:left}
.ok{color:#0a0}.bad{color:#a00}</style></head><body>
<h1>kwok-tpu cluster</h1><div id=counts></div>
<h2>Nodes</h2><table id=nodes></table>
<h2>Pods</h2><table id=pods></table>
<script>
async function j(u){return (await fetch(u)).json()}
// object names are attacker-controlled input: always escape before
// interpolating into markup (stored-XSS guard)
const esc=s=>String(s??'').replace(/[&<>"']/g,
  c=>({'&':'&amp;','<':'&lt;','>':'&gt;','"':'&quot;',"'":'&#39;'}[c]));
function cond(o,t){for(const c of (o.status&&o.status.conditions)||[])
  if(c.type===t)return c.status==='True';return false}
async function refresh(){
  const s=await j('/stats');
  document.getElementById('counts').textContent=
    'resourceVersion '+s.resourceVersion+' — '+
    Object.entries(s.counts).filter(e=>e[1]>0)
      .map(e=>e[0]+': '+e[1]).join(', ');
  const ns=await j('/api/v1/nodes');
  document.getElementById('nodes').innerHTML=
    '<tr><th>name</th><th>ready</th><th>created</th></tr>'+
    ns.items.map(n=>'<tr><td>'+esc(n.metadata.name)+'</td><td class='+
      (cond(n,'Ready')?'ok>Ready':'bad>NotReady')+'</td><td>'+
      esc(n.metadata.creationTimestamp||'')+'</td></tr>').join('');
  const ps=await j('/api/v1/pods?limit=500');
  document.getElementById('pods').innerHTML=
    '<tr><th>namespace</th><th>name</th><th>node</th><th>phase</th></tr>'+
    ps.items.map(p=>'<tr><td>'+esc(p.metadata.namespace||'')+'</td><td>'+
      esc(p.metadata.name)+'</td><td>'+esc((p.spec&&p.spec.nodeName)||'')+
      '</td><td>'+esc((p.status&&p.status.phase)||'')+'</td></tr>').join('');
}
refresh();setInterval(refresh,2000);
</script></body></html>"""


class APIServer:
    """Serve a :class:`ResourceStore` over HTTP.

    The kwokctl binary runtime runs one of these per cluster (stand-in
    for the reference's etcd + kube-apiserver pair) and points every
    other component's ``--kubeconfig``-equivalent at it.
    """

    def __init__(
        self,
        store: ResourceStore,
        host: str = "127.0.0.1",
        port: int = 0,
        tls_cert: Optional[str] = None,
        tls_key: Optional[str] = None,
        client_ca: Optional[str] = None,
        audit_path: Optional[str] = None,
        kubelet_url: Optional[str] = None,
        fault_injector=None,
        flow=None,
        watch_timeout: float = DEFAULT_WATCH_TIMEOUT,
        fleet=None,
    ):
        # acquire the audit file before binding the port so a bad path
        # fails without leaking a listening socket; unbuffered O_APPEND
        # binary mode makes each line one atomic write across threads
        self._audit_file = None
        if audit_path:
            self._audit_file = open(audit_path, "ab", buffering=0)
        handler = type("BoundHandler", (_Handler,), {"store": store})
        try:
            self._httpd = ThreadingHTTPServer((host, port), handler)
            self._httpd.daemon_threads = True
            # watch handler loops poll this so stop() actually ends them
            self._httpd.shutting_down = threading.Event()
            self._httpd.audit_sink = self._audit_file
            # chaos seam (kwok_tpu.chaos duck type); None = no faults.
            # cmd/apiserver wires it from --chaos-profile — this module
            # only carries the hook, keeping cluster below chaos in the
            # layer map.
            self._httpd.fault_injector = fault_injector
            # APF seam (cluster.flowcontrol.FlowController); None = no
            # admission control (bare in-process test servers)
            self._httpd.flow = flow
            # fleet seam (kwok_tpu.fleet.FleetRegistry duck type:
            # touch/observe/snapshot/report/tenant_detail); None = a
            # plain single-tenant apiserver.  cmd/apiserver wires it
            # from --fleet-tenants — only the hook lives here, keeping
            # cluster below fleet in the layer map.
            self._httpd.fleet = fleet
            # default server-side watch deadline; 0 disables
            self._httpd.watch_timeout = float(watch_timeout or 0)
            # Kubernetes wire-protocol facade (k8s_api.py): /api, /apis,
            # /version, /openapi — what stock kubectl/client-go speak
            self._httpd.k8s = K8sFacade(store, kubelet_url=kubelet_url)
            self._tls = bool(tls_cert and tls_key)
            if self._tls:
                from kwok_tpu.utils.tlsutil import build_server_ssl_context

                ctx = build_server_ssl_context(tls_cert, tls_key, client_ca)
                self._httpd.socket = ctx.wrap_socket(
                    self._httpd.socket, server_side=True
                )
        except Exception:
            if self._audit_file is not None:
                self._audit_file.close()
            httpd = getattr(self, "_httpd", None)
            if httpd is not None:
                httpd.server_close()
            raise
        self._thread: Optional[threading.Thread] = None
        self.store = store

    @property
    def address(self) -> Tuple[str, int]:
        return self._httpd.server_address[:2]

    @property
    def url(self) -> str:
        host, port = self.address
        scheme = "https" if self._tls else "http"
        return f"{scheme}://{host}:{port}"

    @property
    def flow(self):
        """The attached FlowController (None when admission is off)."""
        return self._httpd.flow

    @property
    def fleet(self):
        """The attached fleet registry (None for single-tenant)."""
        return self._httpd.fleet

    def ensure_namespaces(self) -> None:
        """Re-run the bootstrap namespace creation (idempotent) — the
        daemon calls this when degraded storage re-arms, because a boot
        onto a full disk skipped it (K8sFacade.ensure_namespaces)."""
        self._httpd.k8s.ensure_namespaces()

    def set_fault_injector(self, injector) -> None:
        """Attach/detach (None) the chaos fault injector on a live
        server; in-flight requests keep the injector they started
        with."""
        self._httpd.fault_injector = injector

    def start(self) -> "APIServer":
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, kwargs={"poll_interval": 0.1}, daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._httpd.shutting_down.set()
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread:
            self._thread.join(timeout=5)
        if self._audit_file is not None:
            try:
                self._audit_file.close()
            except OSError:
                pass

    # context-manager sugar for tests
    def __enter__(self) -> "APIServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
