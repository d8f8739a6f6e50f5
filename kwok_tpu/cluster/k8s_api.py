"""Kubernetes wire-protocol facade over :class:`ResourceStore`.

The reference's entire ecosystem value is that it speaks the *real*
Kubernetes API: it launches a genuine kube-apiserver
(reference runtime/binary/cluster.go:316-728) and its informers use the
standard list/watch protocol (reference
pkg/utils/informer/informer.go:33-319).  This module gives the rebuild
the same wire surface on top of the existing store, so stock ecosystem
clients — kubectl, client-go tooling, schedulers, prometheus kubernetes
service discovery — can connect to a kwok-tpu cluster:

- ``GET /version``                         version info
- ``GET /api`` / ``GET /api/v1``           core discovery
- ``GET /apis`` / ``/apis/{g}`` / ``/apis/{g}/{v}``  group discovery
- ``GET /openapi/v2`` / ``/openapi/v3``    minimal documents
- resource routes under ``/api/v1`` and ``/apis/{group}/{version}``:
  ``/{plural}``, ``/{plural}/{name}[/{subresource}]``,
  ``/namespaces/{ns}/{plural}[/{name}[/{subresource}]]`` with k8s verbs
  (GET list/get, POST create, PUT update, PATCH with the three k8s
  patch content types, DELETE object + deletecollection),
  ``?watch=true`` chunk-streamed ``{"type","object"}`` frames with
  optional BOOKMARK events (a frame is cut from the event's line,
  ``store.watch_line``, whose object the commit encoded for the WAL
  or else the first stream of either dialect: one ``json.dumps`` an
  object and resourceVersion whatever the number of streams; Table
  and traced streams encode their own), ``limit``/``continue`` paging
  over one snapshot a LIST (every page carries the first page's
  resourceVersion and serves from it; a token whose snapshot is gone
  answers 410 ``Expired``, as a real apiserver's after compaction),
  and ``labelSelector``/``fieldSelector``/``resourceVersion`` params
- ``POST .../pods/{name}/binding``         scheduler binding subresource
- ``GET/PUT/PATCH .../deployments/{name}/scale`` (and replicasets) —
  the autoscaling/v1 Scale subresource kubectl scale drives; writes
  land as one merge patch of ``spec.replicas`` on the parent
- ``POST /apis/apiextensions.k8s.io/v1/customresourcedefinitions``
  registers new resource types from a CRD manifest

Errors are returned as ``kind: Status`` objects with the reference's
reason/code mapping (NotFound→404, AlreadyExists/Conflict→409,
Expired→410, BadRequest→400).
"""

from __future__ import annotations

import base64
import json
import socket
import time
from typing import List, Optional, Tuple

from kwok_tpu.cluster.store import (
    AlreadyExists,
    Conflict,
    CrossShardTransaction,
    Expired,
    NotFound,
    ResourceStore,
    ResourceType,
    StorageDegraded,
    k8s_frame,
    observe_watch_burst,
    observe_watch_delivery,
    selector_to_string,
    watch_line,
)
from kwok_tpu.cluster.tables import to_table, wants_table

__all__ = ["K8sFacade", "encode_continue", "decode_continue", "status_body"]

#: Content-Type → store patch_type.  ``application/apply-patch+yaml``
#: (server-side apply) is routed separately to ``store.apply`` with
#: field-manager tracking and conflict detection.
PATCH_CONTENT_TYPES = {
    "application/merge-patch+json": "merge",
    "application/json-patch+json": "json",
    "application/strategic-merge-patch+json": "strategic",
}

APPLY_CONTENT_TYPE = "application/apply-patch+yaml"

#: kinds serving the ``/scale`` subresource (what a real apiserver
#: registers it for among the kinds this store carries)
SCALABLE_KINDS = frozenset({"Deployment", "ReplicaSet"})

_BOOKMARK_EVERY = 15.0


def scale_of(obj: dict) -> dict:
    """Project a scalable workload object into an autoscaling/v1
    Scale (the subresource's wire shape)."""
    meta = obj.get("metadata") or {}
    spec = obj.get("spec") or {}
    replicas = spec.get("replicas")
    return {
        "kind": "Scale",
        "apiVersion": "autoscaling/v1",
        "metadata": {
            "name": meta.get("name"),
            "namespace": meta.get("namespace"),
            "uid": meta.get("uid"),
            "resourceVersion": meta.get("resourceVersion"),
        },
        "spec": {"replicas": 1 if replicas is None else int(replicas)},
        "status": {
            "replicas": int((obj.get("status") or {}).get("replicas") or 0),
            "selector": selector_to_string(spec.get("selector")) or "",
        },
    }


def encode_continue(token) -> str:
    """Opaque continue token: base64(json(token)) of what
    ``ResourceStore.list_page`` hands out (a LIST snapshot's id and a
    position in it)."""
    return base64.urlsafe_b64encode(json.dumps(list(token)).encode()).decode()


def decode_continue(raw):
    if not raw:
        return None
    return tuple(json.loads(base64.urlsafe_b64decode(raw.encode())))


def group_version(rtype: ResourceType) -> Tuple[str, str]:
    """Split apiVersion into (group, version); core group is ""."""
    if "/" in rtype.api_version:
        g, v = rtype.api_version.split("/", 1)
        return g, v
    return "", rtype.api_version


def status_body(
    code: int, reason: str, message: str, details: Optional[dict] = None
) -> dict:
    body = {
        "kind": "Status",
        "apiVersion": "v1",
        "metadata": {},
        "status": "Failure" if code >= 400 else "Success",
        "message": message,
        "reason": reason,
        "code": code,
    }
    if details:
        body["details"] = details
    return body


def error_code_reason(exc: Exception) -> Tuple[int, str]:
    """Store exception → (HTTP code, k8s reason); the one mapping both
    the legacy dialect and the k8s Status path share."""
    if isinstance(exc, NotFound):
        return 404, "NotFound"
    if isinstance(exc, AlreadyExists):
        return 409, "AlreadyExists"
    if isinstance(exc, CrossShardTransaction):
        # sharded router refused a multi-shard atomic batch: typed so
        # callers can tell a design violation (fix the batch) from an
        # ordinary retryable Conflict
        return 409, "CrossShard"
    if isinstance(exc, Conflict):
        # update/patch rv or CAS precondition: client-go
        # retry.RetryOnConflict keys on this exact reason string
        return 409, "Conflict"
    if isinstance(exc, Expired):
        return 410, "Expired"
    if isinstance(exc, StorageDegraded):
        # degraded read-only mode (disk full / poisoned fsync): the
        # machine-readable rejection clients key their degraded-aware
        # retry on — 503 + Retry-After, distinct from APF's 429
        return 503, "StorageDegraded"
    if isinstance(exc, (ValueError, KeyError, json.JSONDecodeError)):
        return 400, "BadRequest"
    return 500, "InternalError"


def status_for(exc: Exception) -> dict:
    code, reason = error_code_reason(exc)
    details = None
    causes = getattr(exc, "causes", None)
    if causes:
        # ApplyConflict: the FieldManagerConflict causes kubectl parses
        # to print its per-field "conflict with ..." hint
        details = {
            "causes": [
                {
                    "reason": "FieldManagerConflict",
                    "message": f'conflict with "{manager}"',
                    "field": field,
                }
                for manager, field in causes
            ]
        }
    return status_body(code, reason, str(exc), details)


def _usage_quantities(cpu_cores: float, mem_bytes: float) -> dict:
    """k8s resource.Quantity strings: cpu in nanocores, memory in Ki."""
    return {
        "cpu": f"{int(cpu_cores * 1e9)}n",
        "memory": f"{int(mem_bytes) // 1024}Ki",
    }


class _Route:
    """Parsed resource route below a group/version prefix."""

    __slots__ = ("rtype", "namespace", "name", "subresource", "all_namespaces")

    def __init__(self, rtype, namespace, name, subresource, all_namespaces):
        self.rtype = rtype
        self.namespace = namespace
        self.name = name
        self.subresource = subresource
        self.all_namespaces = all_namespaces


class K8sFacade:
    """Handle k8s-protocol requests for an apiserver handler.

    ``handle`` returns True when it owned the route; the legacy custom
    REST surface (``/r/{plural}``, ``/bulk``, …) remains available for
    in-repo clients.
    """

    def __init__(self, store: ResourceStore, kubelet_url: Optional[str] = None):
        self.store = store
        self.kubelet_url = kubelet_url
        self.ensure_namespaces()

    def ensure_namespaces(self) -> None:
        """A fresh cluster exposes the conventional namespaces, like a
        real control plane after bootstrap.  Idempotent — the daemon
        re-runs it when degraded storage re-arms (a boot onto a full
        disk skips the creates below)."""
        try:
            self.store.resource_type("Namespace")
        except (KeyError, NotFound):
            return
        for name in ("default", "kube-system", "kube-public"):
            try:
                self.store.create(
                    {
                        "apiVersion": "v1",
                        "kind": "Namespace",
                        "metadata": {"name": name},
                        "spec": {"finalizers": ["kubernetes"]},
                        "status": {"phase": "Active"},
                    }
                )
            except Conflict:
                pass
            except StorageDegraded:
                # booting onto a full disk: reads must still come up;
                # the daemon's re-arm loop calls ensure_namespaces()
                # again once space returns (cmd/apiserver.py)
                return

    # ------------------------------------------------------------ discovery

    def _groups(self) -> dict:
        """group name → sorted set of versions, from registered types."""
        groups: dict = {}
        for rt in self.store.kinds():
            g, v = group_version(rt)
            groups.setdefault(g, set()).add(v)
        return groups

    def _openapi_v3(self) -> dict:
        """OpenAPI v3 document carrying the strategic-merge metadata
        (x-kubernetes-patch-merge-key / x-kubernetes-patch-strategy) for
        every kind with typed metadata — the discovery source the
        reference consumes for unstructured no-op detection and merges
        (reference pkg/utils/patch/openapi.go:43-248).  The tables in
        utils/patch.py are the single source of truth; this route just
        projects them, so server and in-process appliers can never
        disagree."""
        from kwok_tpu.utils.patch import STRATEGIC_META

        schemas = {}
        for kind, table in sorted(STRATEGIC_META.items()):
            props: dict = {}
            for path, (strategy, key) in sorted(table.items()):
                node = props
                for seg in path[:-1]:
                    node = node.setdefault(seg, {"type": "object"}).setdefault(
                        "properties", {}
                    )
                leaf = node.setdefault(path[-1], {"type": "array"})
                leaf["x-kubernetes-patch-strategy"] = strategy
                if key is not None:
                    leaf["x-kubernetes-patch-merge-key"] = key
            schemas[f"io.k8s.api.core.v1.{kind}"] = {
                "type": "object",
                "properties": props,
            }
        return {
            "openapi": "3.0.0",
            "info": {"title": "kwok-tpu", "version": "v1.29.0"},
            "paths": {},
            "components": {"schemas": schemas},
        }

    def _api_versions(self) -> dict:
        return {
            "kind": "APIVersions",
            "versions": ["v1"],
            "serverAddressByClientCIDRs": [
                {"clientCIDR": "0.0.0.0/0", "serverAddress": ""}
            ],
        }

    def _api_group(self, g: str, versions) -> dict:
        vs = sorted(versions)
        return {
            "name": g,
            "versions": [
                {"groupVersion": f"{g}/{v}", "version": v} for v in vs
            ],
            "preferredVersion": {"groupVersion": f"{g}/{vs[-1]}", "version": vs[-1]},
        }

    def _api_group_list(self) -> dict:
        groups = {g: vs for g, vs in self._groups().items() if g}
        if self.kubelet_url:
            # the metrics-server seat: resource metrics are served from
            # kubelet scrapes (see _metrics_api), so advertise the group
            groups.setdefault("metrics.k8s.io", {"v1beta1"})
        return {
            "kind": "APIGroupList",
            "apiVersion": "v1",
            "groups": [
                self._api_group(g, vs) for g, vs in sorted(groups.items())
            ],
        }

    def _api_resource_list(self, group: str, version: str) -> dict:
        gv = f"{group}/{version}" if group else version
        resources = []
        for rt in self.store.kinds():
            if rt.api_version != gv:
                continue
            resources.append(
                {
                    "name": rt.plural,
                    "singularName": rt.kind.lower(),
                    "namespaced": rt.namespaced,
                    "kind": rt.kind,
                    "verbs": [
                        "create",
                        "delete",
                        "deletecollection",
                        "get",
                        "list",
                        "patch",
                        "update",
                        "watch",
                    ],
                }
            )
            resources.append(
                {
                    "name": f"{rt.plural}/status",
                    "singularName": "",
                    "namespaced": rt.namespaced,
                    "kind": rt.kind,
                    "verbs": ["get", "patch", "update"],
                }
            )
        return {
            "kind": "APIResourceList",
            "apiVersion": "v1",
            "groupVersion": gv,
            "resources": resources,
        }

    # -------------------------------------------------------------- routing

    def _resolve(self, gv: str, parts: List[str]) -> _Route:
        """Parse the resource path below a group/version prefix."""
        namespace: Optional[str] = None
        all_namespaces = False
        if parts and parts[0] == "namespaces" and len(parts) >= 3:
            namespace = parts[1]
            parts = parts[2:]
        plural, name, subresource = (
            parts[0],
            parts[1] if len(parts) > 1 else None,
            parts[2] if len(parts) > 2 else None,
        )
        try:
            rtype = self.store.resource_type(plural)
        except (KeyError, NotFound):
            raise NotFound(f"the server could not find the requested resource {plural!r}")
        if rtype.api_version != gv:
            raise NotFound(
                f"resource {plural!r} is not in group/version {gv!r}"
            )
        if rtype.namespaced and namespace is None and name is None:
            all_namespaces = True
        return _Route(rtype, namespace, name, subresource, all_namespaces)

    # ------------------------------------------------------------- the verb

    def handle(self, handler, method: str, head: str, rest: List[str], q: dict) -> bool:
        """Route a request.  ``handler`` is the BaseHTTPRequestHandler;
        returns False when the path is not a k8s-protocol route."""
        try:
            return self._handle(handler, method, head, rest, q)
        except Exception as exc:  # noqa: BLE001 — becomes a Status
            st = status_for(exc)
            # degraded read-only mode carries a Retry-After so stock
            # clients back off instead of hammering a full disk
            self._send(
                handler,
                st["code"],
                st,
                retry_after=getattr(exc, "retry_after", None),
            )
            return True

    def _handle(self, handler, method, head, rest, q) -> bool:
        if head == "version" and method == "GET":
            self._send(
                handler,
                200,
                {
                    "major": "1",
                    "minor": "29",
                    "gitVersion": "v1.29.0-kwok-tpu",
                    "gitCommit": "",
                    "gitTreeState": "clean",
                    "goVersion": "n/a",
                    "compiler": "n/a",
                    "platform": "tpu/jax",
                },
            )
            return True
        if head == "openapi" and method == "GET":
            if rest and rest[0] == "v2":
                self._send(
                    handler,
                    200,
                    {
                        "swagger": "2.0",
                        "info": {"title": "kwok-tpu", "version": "v1.29.0"},
                        "paths": {},
                        "definitions": {},
                    },
                )
            else:
                self._send(handler, 200, self._openapi_v3())
            return True
        if head == "api":
            if not rest:
                if method != "GET":
                    return self._method_not_allowed(handler, method)
                self._send(handler, 200, self._api_versions())
                return True
            version, parts = rest[0], rest[1:]
            if not parts:
                if method != "GET":
                    return self._method_not_allowed(handler, method)
                self._send(handler, 200, self._api_resource_list("", version))
                return True
            return self._resource(handler, method, version, parts, q)
        if head == "apis":
            if not rest:
                if method != "GET":
                    return False  # legacy POST /apis registers a type
                # merged payload: k8s APIGroupList plus the legacy
                # "resources" field consumed by ClusterClient discovery
                body = self._api_group_list()
                from dataclasses import asdict

                body["resources"] = [asdict(t) for t in self.store.kinds()]
                self._send(handler, 200, body)
                return True
            if len(rest) == 1:
                if method != "GET":
                    return self._method_not_allowed(handler, method)
                groups = self._groups()
                if self.kubelet_url:
                    groups.setdefault("metrics.k8s.io", {"v1beta1"})
                if rest[0] not in groups:
                    raise NotFound(f"no API group {rest[0]!r}")
                self._send(handler, 200, self._api_group(rest[0], groups[rest[0]]))
                return True
            group, version, parts = rest[0], rest[1], rest[2:]
            if (
                group == "apiextensions.k8s.io"
                and parts
                and parts[0] == "customresourcedefinitions"
            ):
                return self._crd(handler, method, parts, q)
            if group == "metrics.k8s.io":
                return self._metrics_api(handler, method, version, parts)
            if not parts:
                if method != "GET":
                    return self._method_not_allowed(handler, method)
                self._send(
                    handler, 200, self._api_resource_list(group, version)
                )
                return True
            return self._resource(
                handler, method, f"{group}/{version}", parts, q
            )
        return False

    def _method_not_allowed(self, handler, method) -> bool:
        self._send(
            handler,
            405,
            status_body(405, "MethodNotAllowed", f"method {method} not allowed"),
        )
        return True

    # ---------------------------------------------------------------- CRDs

    def _crd(self, handler, method, parts, q) -> bool:
        """Minimal CustomResourceDefinition support: registering a CRD
        creates a live resource type (the reference reaches the same
        state via kwokctl InitCRDs, reference runtime/config.go)."""
        if method == "POST":
            body = self._read_body(handler)
            spec = (body or {}).get("spec") or {}
            names = spec.get("names") or {}
            versions = spec.get("versions") or []
            version = next(
                (v["name"] for v in versions if v.get("served", True)),
                versions[0]["name"] if versions else "v1",
            )
            rtype = ResourceType(
                api_version=f"{spec['group']}/{version}",
                kind=names["kind"],
                plural=names["plural"],
                namespaced=(spec.get("scope", "Namespaced") == "Namespaced"),
            )
            self.store.register_type(rtype)
            body.setdefault("metadata", {}).setdefault(
                "name", f"{names['plural']}.{spec['group']}"
            )
            body["status"] = {
                "acceptedNames": names,
                "conditions": [
                    {"type": "Established", "status": "True"},
                    {"type": "NamesAccepted", "status": "True"},
                ],
            }
            self._send(handler, 201, body)
            return True
        if method == "GET":
            # synthesize the CRD list from registered non-builtin types
            items = []
            for rt in self.store.kinds():
                g, v = group_version(rt)
                if g in ("", "coordination.k8s.io"):
                    continue
                items.append(
                    {
                        "apiVersion": "apiextensions.k8s.io/v1",
                        "kind": "CustomResourceDefinition",
                        "metadata": {"name": f"{rt.plural}.{g}"},
                        "spec": {
                            "group": g,
                            "names": {"kind": rt.kind, "plural": rt.plural},
                            "scope": "Namespaced" if rt.namespaced else "Cluster",
                            "versions": [{"name": v, "served": True, "storage": True}],
                        },
                    }
                )
            if len(parts) > 1:
                for it in items:
                    if it["metadata"]["name"] == parts[1]:
                        self._send(handler, 200, it)
                        return True
                raise NotFound(f"CRD {parts[1]!r} not found")
            self._send(
                handler,
                200,
                {
                    "kind": "CustomResourceDefinitionList",
                    "apiVersion": "apiextensions.k8s.io/v1",
                    "metadata": {"resourceVersion": str(self.store.resource_version)},
                    "items": items,
                },
            )
            return True
        return self._method_not_allowed(handler, method)

    # ----------------------------------------------------------- resources

    def _resource(self, handler, method, gv, parts, q) -> bool:
        r = self._resolve(gv, parts)
        ns = r.namespace if r.rtype.namespaced else None
        if r.rtype.namespaced and not r.all_namespaces and ns is None and r.name:
            # cluster path to a namespaced type without /namespaces/{ns}
            ns = "default"
        if r.name and r.subresource in ("exec", "attach", "portforward") and method in (
            "GET",
            "POST",
        ):
            return self._proxy_streaming(handler, r)
        if r.name and r.subresource == "scale":
            return self._scale_subresource(handler, method, r, ns)
        if method == "GET":
            if r.name is None:
                if q.get("watch") in ("true", "1"):
                    self._serve_watch(handler, r, q)
                else:
                    self._serve_list(handler, r, q)
                return True
            if r.subresource == "log":
                return self._proxy_log(handler, r, q)
            obj = self._stamp(
                r.rtype, self.store.get(r.rtype.kind, r.name, namespace=ns)
            )
            if self._maybe_send_table(handler, r, [obj], q):
                return True
            self._send(handler, 200, obj)
            return True
        if method == "POST":
            body = self._read_body(handler)
            if r.name and r.subresource == "binding":
                target = ((body or {}).get("target") or {}).get("name") or ""
                self.store.patch(
                    r.rtype.kind,
                    r.name,
                    {"spec": {"nodeName": target}},
                    patch_type="merge",
                    namespace=ns,
                    as_user=self._user(handler),
                )
                self._send(
                    handler, 201, status_body(201, "", "binding created")
                )
                return True
            if r.name and r.subresource == "eviction":
                # eviction == graceful delete (reference pods are
                # evictable like real ones)
                self.store.delete(
                    r.rtype.kind, r.name, namespace=ns, as_user=self._user(handler)
                )
                self._send(handler, 201, status_body(201, "", "eviction created"))
                return True
            body = body or {}
            body.setdefault("kind", r.rtype.kind)
            body.setdefault("apiVersion", r.rtype.api_version)
            out = self.store.create(
                body, namespace=ns, as_user=self._user(handler)
            )
            self._send(handler, 201, self._stamp(r.rtype, out))
            return True
        if method == "PUT":
            body = self._read_body(handler) or {}
            body.setdefault("kind", r.rtype.kind)
            body.setdefault("apiVersion", r.rtype.api_version)
            if r.rtype.namespaced and ns and not (body.get("metadata") or {}).get(
                "namespace"
            ):
                body.setdefault("metadata", {})["namespace"] = ns
            out = self.store.update(
                body,
                subresource=r.subresource or "",
                as_user=self._user(handler),
            )
            self._send(handler, 200, self._stamp(r.rtype, out))
            return True
        if method == "PATCH":
            ctype = (handler.headers.get("Content-Type") or "").split(";")[0].strip()
            body = self._read_body(handler)
            if ctype == APPLY_CONTENT_TYPE and r.subresource:
                # subresource apply (kubectl --subresource=status):
                # degrade to a scoped merge patch — field ownership is
                # tracked on the main resource only (pre-SSA behavior
                # of this facade, kept so status managers don't regress)
                out = self.store.patch(
                    r.rtype.kind,
                    r.name,
                    body,
                    patch_type="merge",
                    namespace=ns,
                    subresource=r.subresource,
                    as_user=self._user(handler),
                )
                self._send(handler, 200, self._stamp(r.rtype, out))
                return True
            if ctype == APPLY_CONTENT_TYPE:
                # server-side apply: field-manager tracked, kubectl
                # conflict contract (store.apply docstring)
                out, created = self.store.apply(
                    r.rtype.kind,
                    r.name,
                    body or {},
                    field_manager=q.get("fieldManager") or "unknown",
                    force=str(q.get("force")).lower() in ("true", "1"),
                    namespace=ns,
                    as_user=self._user(handler),
                )
                self._send(
                    handler, 201 if created else 200, self._stamp(r.rtype, out)
                )
                return True
            patch_type = PATCH_CONTENT_TYPES.get(ctype, "merge")
            out = self.store.patch(
                r.rtype.kind,
                r.name,
                body,
                patch_type=patch_type,
                namespace=ns,
                subresource=r.subresource or "",
                as_user=self._user(handler),
            )
            self._send(handler, 200, self._stamp(r.rtype, out))
            return True
        if method == "DELETE":
            self._read_body(handler)  # DeleteOptions — accepted, unused
            if r.name is None:
                return self._delete_collection(handler, r, q)
            out = self.store.delete(
                r.rtype.kind, r.name, namespace=ns, as_user=self._user(handler)
            )
            if out is None:
                self._send(handler, 200, status_body(200, "", "deleted"))
            else:
                self._send(handler, 200, self._stamp(r.rtype, out))
            return True
        return self._method_not_allowed(handler, method)

    def _scale_subresource(self, handler, method, r: _Route, ns) -> bool:
        """``/scale`` over the scalable workload kinds — kubectl
        scale's wire path (a real apiserver registers the
        autoscaling/v1 Scale subresource for deployments and
        replicasets the same way).  GET projects the parent into a
        Scale; PUT/PATCH of a Scale-shaped body lands as one merge
        patch of ``spec.replicas`` on the parent, which the workload
        controllers then fan out through the bulk lane."""
        if r.rtype.kind not in SCALABLE_KINDS:
            raise NotFound(
                f"{r.rtype.plural} does not have a scale subresource"
            )
        if method == "GET":
            obj = self.store.get(r.rtype.kind, r.name, namespace=ns)
            self._send(handler, 200, scale_of(obj))
            return True
        if method in ("PUT", "PATCH"):
            body = self._read_body(handler) or {}
            replicas = (body.get("spec") or {}).get("replicas")
            if replicas is None:
                raise ValueError("Scale.spec.replicas is required")
            out = self.store.patch(
                r.rtype.kind,
                r.name,
                {"spec": {"replicas": int(replicas)}},
                patch_type="merge",
                namespace=ns,
                as_user=self._user(handler),
            )
            self._send(handler, 200, scale_of(out))
            return True
        return self._method_not_allowed(handler, method)

    def _delete_collection(self, handler, r: _Route, q) -> bool:
        ns = None if r.all_namespaces else r.namespace
        items, rv = self.store.list(
            r.rtype.kind,
            namespace=ns,
            label_selector=q.get("labelSelector"),
            field_selector=q.get("fieldSelector"),
        )
        deleted = []
        for obj in items:
            meta = obj.get("metadata") or {}
            try:
                self.store.delete(
                    r.rtype.kind,
                    meta.get("name") or "",
                    namespace=meta.get("namespace"),
                    as_user=self._user(handler),
                )
                deleted.append(self._stamp(r.rtype, obj))
            except NotFound:
                pass
        self._send(
            handler,
            200,
            {
                "kind": f"{r.rtype.kind}List",
                "apiVersion": r.rtype.api_version,
                "metadata": {"resourceVersion": str(rv)},
                "items": deleted,
            },
        )
        return True

    def _serve_list(self, handler, r: _Route, q) -> None:
        ns = None if r.all_namespaces else r.namespace
        limit = int(q.get("limit") or 0)
        body = {
            "kind": f"{r.rtype.kind}List",
            "apiVersion": r.rtype.api_version,
        }
        if limit or q.get("continue"):
            items, rv, nxt = self.store.list_page(
                r.rtype.kind,
                namespace=ns,
                label_selector=q.get("labelSelector"),
                field_selector=q.get("fieldSelector"),
                limit=limit,
                continue_from=decode_continue(q.get("continue")),
                copy=False,  # _stamp copies what it has to change
            )
            body["metadata"] = {"resourceVersion": str(rv)}
            if nxt is not None:
                body["metadata"]["continue"] = encode_continue(nxt)
        else:
            items, rv = self.store.list(
                r.rtype.kind,
                namespace=ns,
                label_selector=q.get("labelSelector"),
                field_selector=q.get("fieldSelector"),
            )
            body["metadata"] = {"resourceVersion": str(rv)}
        body["items"] = [self._stamp(r.rtype, o) for o in items]
        if self._maybe_send_table(
            handler, r, body["items"], q, list_meta=body["metadata"]
        ):
            return
        self._send(handler, 200, body)

    def _maybe_send_table(
        self, handler, r: _Route, items, q, list_meta=None
    ) -> bool:
        """Answer kubectl's Table accept chain with the real printed
        columns like the kube-apiserver does; False when the request
        did not negotiate a Table."""
        if not wants_table(handler.headers.get("Accept")):
            return False
        self._send(
            handler,
            200,
            to_table(
                r.rtype.kind,
                items,
                list_meta=list_meta,
                include_object=q.get("includeObject") or "Metadata",
            ),
        )
        return True

    # ---------------------------------------------------------------- watch

    def _serve_watch(self, handler, r: _Route, q) -> None:
        ns = None if r.all_namespaces else r.namespace
        since = q.get("resourceVersion")
        bookmarks = q.get("allowWatchBookmarks") in ("true", "1")
        # server-side deadline: explicit ?timeoutSeconds, else the
        # server default (cluster.apiserver wires it) — watches end
        # with a clean EOF the reflector resumes from
        timeout_s = (
            float(q.get("timeoutSeconds") or 0)
            or float(getattr(handler.server, "watch_timeout", 0) or 0)
            or None
        )
        # k8s "Get State and Start at Most Recent" semantics: a watch
        # without a resourceVersion (or rv=0) first streams synthetic
        # ADDED events for all existing objects, then goes live — plain
        # curl-style watchers must not see an empty cluster
        initial: list = []
        if not since or since == "0":
            initial, rv0 = self.store.list(
                r.rtype.kind,
                namespace=ns,
                label_selector=q.get("labelSelector"),
                field_selector=q.get("fieldSelector"),
            )
            since = str(rv0)
        try:
            w = self.store.watch(
                r.rtype.kind,
                namespace=ns,
                since_rv=int(since),
                label_selector=q.get("labelSelector"),
                field_selector=q.get("fieldSelector"),
            )
        except Expired as exc:
            # k8s semantics: 200 stream whose single frame is an ERROR
            # event carrying a 410 Status — clients re-list on seeing it
            handler.send_response(200)
            handler.send_header("Content-Type", "application/json")
            handler.send_header("Connection", "close")
            handler.end_headers()
            handler.close_connection = True
            frame = json.dumps(
                {"type": "ERROR", "object": status_body(410, "Expired", str(exc))}
            ).encode() + b"\n"
            handler.wfile.write(frame)
            return
        handler.send_response(200)
        handler.send_header("Content-Type", "application/json; stream=watch")
        handler.send_header("Connection", "close")
        handler.end_headers()
        handler.close_connection = True
        shutdown = getattr(handler.server, "shutting_down", None)
        deadline = time.monotonic() + timeout_s if timeout_s else None
        # rv→span stitch: with a tracer armed each live event envelope
        # gains the committing span's context from the commit ring —
        # resolved as ONE batched ring lookup per flushed burst (same
        # lock-pressure discipline as the legacy dialect)
        from kwok_tpu.utils.trace import peek_global

        _tr = peek_global()
        ctx_many = (
            getattr(self.store, "commit_contexts", None)
            if _tr is not None and _tr.enabled
            else None
        )
        # kubectl get -w sends the same Table accept chain on the watch
        # request: once the list came back as a Table, event objects
        # must be Table-typed too (single-row tables, like the real
        # apiserver) or kubectl's table decoder rejects the stream
        as_table = wants_table(handler.headers.get("Accept"))
        include_object = q.get("includeObject") or "Metadata"
        try:
            if initial:
                # incremental chunks, not one giant join: an rv=0 watch
                # over a 1M-pod set would otherwise build a multi-GB
                # bytes object in this handler thread (ADVICE r02)
                chunk: list = []
                for o in initial:
                    if as_table:
                        payload = {
                            "type": "ADDED",
                            "object": to_table(
                                r.rtype.kind,
                                [self._stamp(r.rtype, o)],
                                include_object=include_object,
                            ),
                        }
                    else:
                        payload = {"type": "ADDED", "object": self._stamp(r.rtype, o)}
                    chunk.append(json.dumps(payload).encode() + b"\n")
                    if len(chunk) >= 512:
                        handler.wfile.write(b"".join(chunk))
                        chunk.clear()
                if chunk:
                    handler.wfile.write(b"".join(chunk))
                handler.wfile.flush()
            idle = 0.0
            while shutdown is None or not shutdown.is_set():
                if deadline and time.monotonic() >= deadline:
                    break
                ev = w.next(timeout=0.25)
                if ev is None:
                    if w.stopped:
                        if getattr(w, "evicted", False):
                            # slow consumer dropped by backpressure:
                            # k8s watch-cache-gone shape — one ERROR
                            # frame carrying a 410 Status, then EOF;
                            # informed clients resume at their last rv
                            flow = getattr(handler.server, "flow", None)
                            if flow is not None:
                                flow.note_evicted(
                                    getattr(handler, "_flow_level", None)
                                )
                            # the peer was evicted for being slow, so
                            # its receive buffer may be full: bound the
                            # farewell write or this thread re-creates
                            # the pinned-handler problem eviction
                            # exists to solve (timeout lands in the
                            # outer except and we just hang up)
                            try:
                                handler.connection.settimeout(5.0)
                            # best-effort: a socket already torn down
                            # cannot take a timeout, and the write
                            # below will fail fast on it anyway
                            except OSError:  # kwoklint: disable=swallowed-errors
                                pass
                            self._write_frame(
                                handler,
                                {
                                    "type": "ERROR",
                                    "object": status_body(
                                        410,
                                        "Expired",
                                        "watch backlog exceeded the "
                                        "high-water mark; resume from "
                                        "your last resourceVersion",
                                    ),
                                },
                            )
                        break
                    idle += 0.25
                    if bookmarks and idle >= _BOOKMARK_EVERY:
                        idle = 0.0
                        bm_meta = {
                            "resourceVersion": str(
                                self.store.resource_version
                            )
                        }
                        if as_table:
                            # a Table-negotiated watch must be
                            # uniformly Table-typed: kubectl's table
                            # decoder rejects mixed streams, so the
                            # bookmark rides an EMPTY-row Table whose
                            # metadata carries the resourceVersion —
                            # what the real apiserver emits
                            bm_obj = to_table(r.rtype.kind, [])
                            bm_obj["metadata"] = bm_meta
                        else:
                            bm_obj = {
                                "kind": r.rtype.kind,
                                "apiVersion": r.rtype.api_version,
                                "metadata": bm_meta,
                            }
                        self._write_frame(
                            handler,
                            {"type": "BOOKMARK", "object": bm_obj},
                        )
                    continue
                idle = 0.0
                burst = [ev]
                while len(burst) < 512:
                    ev = w.next(timeout=0)
                    if ev is None:
                        break
                    burst.append(ev)
                last_rv = burst[-1].rv
                t_enc = time.thread_time()
                if as_table or ctx_many is not None:
                    # Table-typed or traced: nobody else's bytes
                    ctxs = (
                        ctx_many([e.rv for e in burst])
                        if ctx_many is not None
                        else {}
                    )
                    frames = [
                        self._encode_event(
                            r.rtype, e, as_table, include_object, ctx=ctxs.get(e.rv)
                        )
                        for e in burst
                    ]
                    fresh = len(frames)
                else:
                    frames, fresh = self._shared_frames(r.rtype, burst)
                t_enc = time.thread_time() - t_enc
                handler.wfile.write(b"".join(frames))
                handler.wfile.flush()
                observe_watch_burst(r.rtype.kind, fresh, len(frames), t_enc)
                # observed rv-commit -> delivery lag, one sample per
                # flushed burst (shared with the legacy dialect)
                observe_watch_delivery(self.store, last_rv)
        except (BrokenPipeError, ConnectionError, socket.timeout, OSError):
            pass
        finally:
            w.stop()

    def _shared_frames(self, rtype, burst) -> Tuple[List[bytes], int]:
        """The burst's frames, and how many this stream had to encode:
        a frame is cut from the line that the commit, or else the
        first stream of either dialect, left on the event
        (``store.watch_line``), so N streams of a kind write the same
        bytes for one ``json.dumps``.  An object stored without its kind
        or apiVersion (none is, by ``create``) gets a frame of its own."""
        frames = []
        fresh = 0
        for e in burst:
            obj = e.object
            if "kind" in obj and "apiVersion" in obj:
                line = e.line  # most often there: one attribute read a frame
                if line is None:
                    line = watch_line(e)[0]
                    fresh += 1
                frames.append(k8s_frame(line))
            else:
                frames.append(self._encode_event(rtype, e))
                fresh += 1
        return frames, fresh

    def _encode_event(
        self,
        rtype,
        ev,
        as_table: bool = False,
        include_object: str = "Metadata",
        ctx=None,
    ) -> bytes:
        # watch events share the stored instance (store._emit contract):
        # _stamp grafts a missing kind/apiVersion onto a shallow copy
        obj = self._stamp(rtype, ev.object)
        if as_table:
            obj = to_table(rtype.kind, [obj], include_object=include_object)
        payload = {"type": ev.type, "object": obj}
        # rv→span stitch, k8s dialect: with a tracer armed (ctx
        # batch-resolved per burst by _serve_watch) the envelope
        # carries the committing span context as an EXTRA top-level key
        # (object payload untouched; client-go/kubectl ignore unknown
        # watch-event fields, and Table streams stay pristine — kubectl
        # is the only Table consumer).  Tracing off ⇒ the frame every
        # untraced stream of the kind writes (_shared_frames).
        if ctx is not None and not as_table:
            payload["ctx"] = list(ctx)
        return json.dumps(payload).encode() + b"\n"

    @staticmethod
    def _write_frame(handler, payload: dict) -> None:
        handler.wfile.write(json.dumps(payload).encode() + b"\n")
        handler.wfile.flush()

    # ------------------------------------------------------------ log proxy

    def _proxy_log(self, handler, r: _Route, q) -> bool:
        """Proxy ``GET .../pods/{name}/log`` to the fake kubelet (the
        real apiserver proxies to the node's kubelet the same way;
        reference server debugging_logs.go:68-79)."""
        if not self.kubelet_url:
            raise NotFound("no kubelet registered for log proxying")
        import urllib.request

        ns = r.namespace or "default"
        container = q.get("container") or ""
        url = f"{self.kubelet_url}/containerLogs/{ns}/{r.name}/{container}"
        follow = q.get("follow") in ("true", "1")
        if follow:
            url += "?follow=true"
        try:
            # follow streams idle between log lines — no read deadline
            # (the 30s timeout silently ended quiet follows, ADVICE r02)
            resp = urllib.request.urlopen(url, timeout=None if follow else 30)
        except Exception as exc:  # noqa: BLE001
            raise NotFound(f"kubelet log fetch failed: {exc}")
        handler.send_response(200)
        handler.send_header("Content-Type", "text/plain")
        handler.send_header("Connection", "close")
        handler.end_headers()
        handler.close_connection = True
        try:
            while True:
                chunk = resp.read(8192)
                if not chunk:
                    break
                handler.wfile.write(chunk)
                handler.wfile.flush()
        except (BrokenPipeError, ConnectionError, OSError):
            pass
        return True

    # ----------------------------------------------------- metrics.k8s.io

    def _metrics_api(self, handler, method, version, parts) -> bool:
        """The metrics-server seat: serve ``metrics.k8s.io/v1beta1``
        NodeMetrics/PodMetrics from kubelet resource-metrics scrapes —
        exactly how the real metrics-server works (scrape kubelets,
        rate the cpu counter between scrapes).  Enables stock
        ``kubectl top`` against the cluster (reference runs a real
        metrics-server component, components/metrics_server.go; the
        scrape source is the metrics-usage Metric CR asset)."""
        if method != "GET":
            return self._method_not_allowed(handler, method)
        if not self.kubelet_url:
            raise NotFound("no kubelet registered for resource metrics")
        if not parts:
            self._send(
                handler,
                200,
                {
                    "kind": "APIResourceList",
                    "apiVersion": "v1",
                    "groupVersion": f"metrics.k8s.io/{version}",
                    "resources": [
                        {
                            "name": "nodes",
                            "singularName": "",
                            "namespaced": False,
                            "kind": "NodeMetrics",
                            "verbs": ["get", "list"],
                        },
                        {
                            "name": "pods",
                            "singularName": "",
                            "namespaced": True,
                            "kind": "PodMetrics",
                            "verbs": ["get", "list"],
                        },
                    ],
                },
            )
            return True
        namespace = None
        if parts[0] == "namespaces" and len(parts) >= 3:
            namespace = parts[1]
            parts = parts[2:]
        plural, name = parts[0], parts[1] if len(parts) > 1 else None
        pods_u, nodes_u, window = self._usage_rates()
        ts = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        win = f"{window:.0f}s"
        if plural == "nodes":
            items = [
                {
                    "metadata": {"name": n},
                    "timestamp": ts,
                    "window": win,
                    "usage": _usage_quantities(cpu, mem),
                }
                for n, (cpu, mem) in sorted(nodes_u.items())
                if name is None or n == name
            ]
            if name is not None:
                if not items:
                    raise NotFound(f"node metrics for {name!r} not found")
                self._send(
                    handler,
                    200,
                    dict(items[0], kind="NodeMetrics", apiVersion=f"metrics.k8s.io/{version}"),
                )
                return True
            self._send(
                handler,
                200,
                {
                    "kind": "NodeMetricsList",
                    "apiVersion": f"metrics.k8s.io/{version}",
                    "metadata": {},
                    "items": items,
                },
            )
            return True
        if plural == "pods":
            items = []
            for (ns, pod), containers in sorted(pods_u.items()):
                if namespace is not None and ns != namespace:
                    continue
                if name is not None and pod != name:
                    continue
                items.append(
                    {
                        "metadata": {"name": pod, "namespace": ns},
                        "timestamp": ts,
                        "window": win,
                        "containers": [
                            {"name": c, "usage": _usage_quantities(cpu, mem)}
                            for c, (cpu, mem) in sorted(containers.items())
                        ],
                    }
                )
            if name is not None:
                if not items:
                    raise NotFound(f"pod metrics for {name!r} not found")
                self._send(
                    handler,
                    200,
                    dict(items[0], kind="PodMetrics", apiVersion=f"metrics.k8s.io/{version}"),
                )
                return True
            self._send(
                handler,
                200,
                {
                    "kind": "PodMetricsList",
                    "apiVersion": f"metrics.k8s.io/{version}",
                    "metadata": {},
                    "items": items,
                },
            )
            return True
        raise NotFound(f"no metrics resource {plural!r}")

    def _usage_rates(self):
        """(pod_containers, node_usage, window_s): cpu cores (rated
        between this scrape and the cached previous one) + memory
        working-set bytes.  First call takes a short double-scrape."""
        now = time.monotonic()
        cur = self._scrape_all()
        prev = getattr(self, "_usage_prev", None)
        if prev is None or now - prev[0] <= 0:
            # deliberately wall-clock: a usage *rate* needs two scrapes
            # separated by real time on this first-call path
            time.sleep(0.25)  # kwoklint: disable=untestable-sleep
            prev = (now, cur)
            now = time.monotonic()
            cur = self._scrape_all()
        self._usage_prev = (now, cur)
        t0, (pods0, nodes0) = prev
        dt = max(now - t0, 1e-3)
        pods1, nodes1 = cur
        pod_rates = {}
        for key, containers in pods1.items():
            out = {}
            for c, (cpu1, mem1) in containers.items():
                cpu0 = (pods0.get(key) or {}).get(c, (cpu1, mem1))[0]
                out[c] = (max(cpu1 - cpu0, 0.0) / dt, mem1)
            pod_rates[key] = out
        node_rates = {}
        for n, (cpu1, mem1) in nodes1.items():
            cpu0 = nodes0.get(n, (cpu1, mem1))[0]
            node_rates[n] = (max(cpu1 - cpu0, 0.0) / dt, mem1)
        return pod_rates, node_rates, dt

    def _scrape_all(self):
        """Scrape every node's resource metrics off the kubelet.
        Returns ({(ns, pod): {container: (cpu_s, mem_b)}},
        {node: (cpu_s, mem_b)})."""
        import urllib.request

        pods: dict = {}
        nodes: dict = {}
        try:
            node_objs, _ = self.store.list("Node")
        except (KeyError, NotFound):
            return pods, nodes
        from kwok_tpu.utils.promtext import iter_samples

        for node in node_objs:
            nname = (node.get("metadata") or {}).get("name") or ""
            url = f"{self.kubelet_url}/metrics/nodes/{nname}/metrics/resource"
            try:
                body = urllib.request.urlopen(url, timeout=10).read().decode()
            except OSError:
                continue
            for mname, labels, fval in iter_samples(body):
                key = (labels.get("namespace", ""), labels.get("pod", ""))
                container = labels.get("container", "")
                if mname == "container_cpu_usage_seconds_total":
                    cur = pods.setdefault(key, {}).setdefault(container, [0.0, 0.0])
                    cur[0] = fval
                elif mname == "container_memory_working_set_bytes":
                    cur = pods.setdefault(key, {}).setdefault(container, [0.0, 0.0])
                    cur[1] = fval
                elif mname == "node_cpu_usage_seconds_total":
                    nodes.setdefault(nname, [0.0, 0.0])[0] = fval
                elif mname == "node_memory_working_set_bytes":
                    nodes.setdefault(nname, [0.0, 0.0])[1] = fval
        return (
            {k: {c: tuple(v) for c, v in cs.items()} for k, cs in pods.items()},
            {n: tuple(v) for n, v in nodes.items()},
        )

    # --------------------------------------------------------- stream proxy

    def _proxy_streaming(self, handler, r: _Route) -> bool:
        """Tunnel pod exec/attach/portforward subresources to the fake
        kubelet as a raw byte pipe, preserving WebSocket upgrades — the
        apiserver role for `kubectl exec/attach/port-forward` (a real
        apiserver proxies the upgraded connection to the kubelet the
        same way; reference server debugging.go:36-102 is the far end)."""
        if not self.kubelet_url:
            raise NotFound("no kubelet registered for streaming subresources")
        import socket as _socket
        from urllib.parse import parse_qs, urlsplit

        u = urlsplit(handler.path)
        q = parse_qs(u.query)
        ns = r.namespace or "default"
        if r.subresource == "portforward":
            path = f"/portForward/{ns}/{r.name}"
        else:
            container = (q.get("container") or [""])[0]
            if not container:
                # default to the first container name kubectl would pick;
                # the kubelet handler resolves per-container config
                try:
                    pod = self.store.get("Pod", r.name, namespace=ns)
                    containers = (pod.get("spec") or {}).get("containers") or []
                    container = (containers[0].get("name") if containers else "") or ""
                except NotFound:
                    container = ""
            sub = "exec" if r.subresource == "exec" else "attach"
            path = f"/{sub}/{ns}/{r.name}/{container}"
        if u.query:
            path += f"?{u.query}"

        ku = urlsplit(self.kubelet_url)
        upstream = _socket.create_connection(
            (ku.hostname, ku.port or 80), timeout=30
        )
        # the 30s deadline covers CONNECT only: an idle exec waiting for
        # input, a quiet attach, or a parked port-forward must live
        # indefinitely (kubectl documents no server-side deadline) —
        # recv raising socket.timeout here used to read as EOF and tear
        # the tunnel down (ADVICE r02 medium)
        upstream.settimeout(None)
        upgrading = "upgrade" in (handler.headers.get("Connection") or "").lower()
        try:
            lines = [f"{handler.command} {path} HTTP/1.1"]
            lines.append(f"Host: {ku.netloc}")
            for k, v in handler.headers.items():
                if k.lower() in ("host", "content-length"):
                    continue
                if not upgrading and k.lower() == "connection":
                    continue
                lines.append(f"{k}: {v}")
            length = int(handler.headers.get("Content-Length") or 0)
            body = handler.rfile.read(length) if length else b""
            if body:
                lines.append(f"Content-Length: {len(body)}")
            if not upgrading:
                lines.append("Connection: close")
            upstream.sendall("\r\n".join(lines).encode() + b"\r\n\r\n" + body)

            handler.close_connection = True

            def client_to_upstream():
                try:
                    while True:
                        chunk = handler.rfile.read1(65536)
                        if not chunk:
                            break
                        upstream.sendall(chunk)
                except (OSError, ValueError):
                    pass
                finally:
                    try:
                        upstream.shutdown(_socket.SHUT_WR)
                    except OSError:
                        pass

            import threading

            t = threading.Thread(target=client_to_upstream, daemon=True)
            t.start()
            try:
                while True:
                    chunk = upstream.recv(65536)
                    if not chunk:
                        break
                    handler.wfile.write(chunk)
                    handler.wfile.flush()
            except (BrokenPipeError, ConnectionError, OSError):
                pass
            return True
        finally:
            try:
                upstream.close()
            except OSError:
                pass

    # ------------------------------------------------------------- plumbing

    @staticmethod
    def _stamp(rtype: ResourceType, obj: dict) -> dict:
        """``obj`` with its kind and apiVersion; grafted onto a shallow
        copy where one is missing, since ``obj`` may be the stored
        instance (a paged LIST's, a watch event's)."""
        if "kind" in obj and "apiVersion" in obj:
            return obj
        obj = dict(obj)
        obj.setdefault("kind", rtype.kind)
        obj.setdefault("apiVersion", rtype.api_version)
        return obj

    @staticmethod
    def _user(handler) -> Optional[str]:
        return handler.headers.get("Impersonate-User") or None

    @staticmethod
    def _read_body(handler):
        length = int(handler.headers.get("Content-Length") or 0)
        raw = handler.rfile.read(length) if length else b""
        if not raw:
            return None
        ctype = (handler.headers.get("Content-Type") or "").split(";")[0].strip()
        if ctype.endswith("+yaml") or ctype == "application/yaml":
            import yaml

            return yaml.safe_load(raw)
        return json.loads(raw)

    @staticmethod
    def _send(handler, code: int, payload, retry_after=None) -> None:
        body = json.dumps(payload).encode()
        handler.send_response(code)
        handler.send_header("Content-Type", "application/json")
        if retry_after is not None:
            handler.send_header("Retry-After", str(retry_after))
        handler.send_header("Content-Length", str(len(body)))
        handler.end_headers()
        handler.wfile.write(body)
