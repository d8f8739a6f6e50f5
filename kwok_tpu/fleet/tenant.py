"""Tenant object spaces and the fleet lifecycle registry.

A tenant is one virtual control plane: its API objects live in the
shared store under namespaces prefixed ``<tenant>--`` (the separator is
:data:`kwok_tpu.cluster.sharding.router.TENANT_SEP`, which the
placement hash truncates at — so every namespace of one tenant lands on
one shard and the tenant's transactions stay single-shard).
:class:`TenantStore` is the namespace-prefixing proxy that builds the
virtual-cluster illusion — duck-typed to :class:`ResourceStore` exactly
like ``ClusterClient`` is, so every apiserver handler (and the k8s
wire-protocol facade) works unchanged on top of it.

:class:`FleetRegistry` owns tenant lifecycle: a tenant is *cold* until
its first request (no binding, no memory), *warm* while requests keep
arriving, *idle* after ``idle_after_s`` without one, and back to *cold*
(binding dropped — scale-to-zero; durable state stays in the store)
after ``cold_after_s``.  All lifecycle arithmetic runs on the injected
clock (:mod:`kwok_tpu.utils.clock`), so FakeClock tests and the DST
virtual clock drive it without a single sleep.

Reference: kwokctl's multi-cluster surface manages one runtime dir per
cluster (reference pkg/kwokctl/cmd/create/cluster/cluster.go:60,
pkg/kwokctl/cmd/get/clusters/clusters.go:40); a fleet collapses those
clusters into tenants of one store.
"""

from __future__ import annotations

import inspect
import time
from typing import Dict, List, Optional, Tuple

from kwok_tpu.cluster.sharding.router import TENANT_SEP, shard_of
from kwok_tpu.cluster.store import AlreadyExists, NotFound
from kwok_tpu.utils.clock import Clock, MonotonicClock
from kwok_tpu.utils.locks import guarded, make_lock

__all__ = [
    "TENANT_HEADER",
    "COLD",
    "WARM",
    "IDLE",
    "FleetRegistry",
    "TenantStore",
    "TenantWatcher",
    "UnknownTenant",
    "fleet_tenant_ids",
]

#: request header naming the tenant; the path dialect
#: ``/fleet/t/<tenant>/...`` is equivalent (cluster/apiserver.py)
TENANT_HEADER = "X-Kwok-Tenant"

#: lifecycle states (computed, never stored — state is a pure function
#: of ``clock.now() - last_seen``)
COLD = "cold"
WARM = "warm"
IDLE = "idle"


class UnknownTenant(NotFound):
    """Request named a tenant outside the fleet's fixed set (404 — the
    set is pinned at fleet creation so APF levels stay bounded)."""


def fleet_tenant_ids(n: int) -> List[str]:
    """The fleet's tenant id set: ``t000..t{n-1}`` (zero-padded to the
    fleet's width so ids sort, tabulate, and label consistently).  Ids
    are the APF level names and metric label values — fixed at create
    time, which is what keeps both sets bounded."""
    n = max(0, int(n))
    width = max(3, len(str(max(0, n - 1))))
    return [f"t{i:0{width}d}" for i in range(n)]


def _map_ns(tenant: str, namespace: Optional[str]) -> str:
    return f"{tenant}{TENANT_SEP}{namespace or 'default'}"


def _strip_ns(tenant: str, namespace: str) -> str:
    prefix = tenant + TENANT_SEP
    return namespace[len(prefix):] if namespace.startswith(prefix) else namespace


class TenantWatcher:
    """Filtering/stripping wrapper over a store :class:`Watcher`.

    Used for a tenant's all-namespaces watches: the inner watcher sees
    the whole kind, this wrapper delivers only the tenant's objects
    (namespace — or Namespace-kind name — carries the tenant prefix)
    with the prefix stripped, so the consumer sees its virtual cluster
    and nothing else.  Duck-typed to the Watcher surface the watch
    servers drive (``drain``/``next``/``stop``/``stopped``)."""

    def __init__(self, inner, tenant: str, namespace_kind: bool = False):
        self._inner = inner
        self._tenant = tenant
        self._prefix = tenant + TENANT_SEP
        self._namespace_kind = namespace_kind

    # ----------------------------------------------------------- filtering

    def _match(self, obj: dict) -> bool:
        meta = (obj or {}).get("metadata") or {}
        field = meta.get("name") if self._namespace_kind else meta.get("namespace")
        return bool(field) and str(field).startswith(self._prefix)

    def _wrap(self, ev):
        return ev.__class__(
            ev.type,
            _strip_object(self._tenant, ev.object, self._namespace_kind),
            ev.rv,
        )

    # ------------------------------------------------------------- surface

    def drain(self) -> list:
        return [self._wrap(e) for e in self._inner.drain() if self._match(e.object)]

    def next(self, timeout: Optional[float] = 0.5):
        deadline = (
            None if timeout is None else time.monotonic() + max(0.0, timeout)
        )
        while True:
            left = (
                timeout
                if deadline is None
                else max(0.0, deadline - time.monotonic())
            )
            ev = self._inner.next(timeout=left)
            if ev is None:
                return None
            if self._match(ev.object):
                return self._wrap(ev)
            if deadline is not None and time.monotonic() >= deadline:
                return None

    def __iter__(self):
        for ev in self._inner:
            if self._match(ev.object):
                yield self._wrap(ev)

    def stop(self) -> None:
        self._inner.stop()

    @property
    def stopped(self) -> bool:
        return self._inner.stopped

    @property
    def evicted(self) -> bool:
        return bool(getattr(self._inner, "evicted", False))


def _strip_object(tenant: str, obj: dict, namespace_kind: bool) -> dict:
    """Shallow-copied view of ``obj`` with the tenant prefix removed
    from its namespace (or its name, for the Namespace kind).  Stored
    instances are never mutated — watch rings and copy=False lists hand
    out shared references."""
    if not isinstance(obj, dict):
        return obj
    meta = obj.get("metadata")
    if not isinstance(meta, dict):
        return obj
    prefix = tenant + TENANT_SEP
    field = "name" if namespace_kind else "namespace"
    val = meta.get(field)
    if not (isinstance(val, str) and val.startswith(prefix)):
        return obj
    out = dict(obj)
    m = dict(meta)
    m[field] = val[len(prefix):]
    out["metadata"] = m
    return out


class TenantStore:
    """Namespace-prefixing store proxy — one tenant's virtual cluster.

    Mapping rules (the whole isolation contract lives here):

    - **namespaced kinds**: the effective namespace maps to
      ``<tenant>--<ns or default>`` on the way in and strips on the way
      out; an all-namespaces list/watch is restricted to the tenant's
      prefix.
    - **the Namespace kind**: cluster-scoped, but its *name* is a
      namespace — so the name maps/strips the same way, and lists show
      only the tenant's namespaces.  The virtual cluster looks complete.
    - **other cluster-scoped kinds** (Nodes, ...): shared pass-through —
      the fleet shares its simulated infrastructure pool, exactly the
      kwok posture (tenants own workloads, the host owns the substrate).

    Anything not overridden delegates to the inner store, so the proxy
    keeps working over :class:`ClusterClient` too (the duck-typing
    convention of this repo)."""

    def __init__(self, store, tenant: str):
        self._store = store
        self.tenant = tenant
        self._prefix = tenant + TENANT_SEP
        # the store duck varies: ResourceStore.list takes copy=, the
        # sharded router and the REST client do not — forward it only
        # where it exists (everything here strips via shallow copies
        # anyway, so copy=False is purely a hot-path hint)
        try:
            self._list_copy_kw = (
                "copy" in inspect.signature(type(store).list).parameters
            )
        except (AttributeError, TypeError, ValueError):
            self._list_copy_kw = False

    def __getattr__(self, name):
        return getattr(self._store, name)

    def _list(self, kind: str, copy: bool = True, **kw):
        if self._list_copy_kw:
            kw["copy"] = copy
        return self._store.list(kind, **kw)

    # ---------------------------------------------------------- ns helpers

    def _rt(self, kind: str):
        return self._store.resource_type(kind)

    def _is_ns_kind(self, kind: str) -> bool:
        try:
            return self._rt(kind).kind == "Namespace"
        except Exception:  # noqa: BLE001 — unknown kinds resolve downstream
            return False

    def _namespaced(self, kind: str) -> bool:
        try:
            return bool(self._rt(kind).namespaced)
        except Exception:  # noqa: BLE001
            return True

    def _strip(self, kind: str, obj):
        if obj is None:
            return None
        return _strip_object(self.tenant, obj, self._is_ns_kind(kind))

    # --------------------------------------------------------------- reads

    def get(self, kind: str, name: str, namespace: Optional[str] = None) -> dict:
        if self._is_ns_kind(kind):
            return self._strip(
                kind, self._store.get(kind, _map_ns(self.tenant, name))
            )
        if self._namespaced(kind):
            return self._strip(
                kind,
                self._store.get(kind, name, namespace=_map_ns(self.tenant, namespace)),
            )
        return self._store.get(kind, name, namespace=namespace)

    def list(
        self,
        kind: str,
        namespace: Optional[str] = None,
        label_selector=None,
        field_selector=None,
        copy: bool = True,
    ) -> Tuple[List[dict], int]:
        if self._is_ns_kind(kind):
            items, rv = self._list(
                kind, copy, label_selector=label_selector,
                field_selector=field_selector,
            )
            mine = [
                _strip_object(self.tenant, o, True)
                for o in items
                if str((o.get("metadata") or {}).get("name") or "").startswith(
                    self._prefix
                )
            ]
            return mine, rv
        if not self._namespaced(kind):
            return self._list(
                kind, copy, namespace=namespace, label_selector=label_selector,
                field_selector=field_selector,
            )
        if namespace is not None:
            items, rv = self._list(
                kind,
                copy,
                namespace=_map_ns(self.tenant, namespace),
                label_selector=label_selector,
                field_selector=field_selector,
            )
            return [_strip_object(self.tenant, o, False) for o in items], rv
        items, rv = self._list(
            kind, copy, label_selector=label_selector,
            field_selector=field_selector,
        )
        mine = [
            _strip_object(self.tenant, o, False)
            for o in items
            if str((o.get("metadata") or {}).get("namespace") or "").startswith(
                self._prefix
            )
        ]
        return mine, rv

    def list_page(
        self,
        kind: str,
        namespace: Optional[str] = None,
        label_selector=None,
        field_selector=None,
        limit: int = 0,
        continue_from=None,
        copy: bool = True,
    ):
        # continue tokens stay store-global (the store's LIST snapshot
        # and a position in it); pages filter to the tenant afterwards
        # (a page may come back short — the token still advances, so
        # pagination terminates correctly)
        ns = (
            _map_ns(self.tenant, namespace)
            if namespace is not None and self._namespaced(kind)
            and not self._is_ns_kind(kind)
            else namespace
        )
        items, rv, nxt = self._store.list_page(
            kind,
            namespace=ns,
            label_selector=label_selector,
            field_selector=field_selector,
            limit=limit,
            continue_from=continue_from,
            copy=copy,
        )
        if self._is_ns_kind(kind):
            items = [
                _strip_object(self.tenant, o, True)
                for o in items
                if str((o.get("metadata") or {}).get("name") or "").startswith(
                    self._prefix
                )
            ]
        elif self._namespaced(kind) and namespace is None:
            items = [
                _strip_object(self.tenant, o, False)
                for o in items
                if str((o.get("metadata") or {}).get("namespace") or "").startswith(
                    self._prefix
                )
            ]
        elif self._namespaced(kind):
            items = [_strip_object(self.tenant, o, False) for o in items]
        return items, rv, nxt

    def count(self, kind: str) -> int:
        if self._is_ns_kind(kind) or self._namespaced(kind):
            return len(self.list(kind, copy=False)[0])
        return self._store.count(kind)

    def watch(
        self,
        kind: str,
        namespace: Optional[str] = None,
        since_rv: Optional[int] = None,
        label_selector=None,
        field_selector=None,
        status_interest: bool = True,
    ):
        if self._is_ns_kind(kind):
            w = self._store.watch(
                kind, since_rv=since_rv, label_selector=label_selector,
                field_selector=field_selector, status_interest=status_interest,
            )
            return TenantWatcher(w, self.tenant, namespace_kind=True)
        if not self._namespaced(kind):
            return self._store.watch(
                kind, namespace=namespace, since_rv=since_rv,
                label_selector=label_selector, field_selector=field_selector,
                status_interest=status_interest,
            )
        if namespace is not None:
            w = self._store.watch(
                kind,
                namespace=_map_ns(self.tenant, namespace),
                since_rv=since_rv,
                label_selector=label_selector,
                field_selector=field_selector,
                status_interest=status_interest,
            )
            # exact-namespace watch needs no filtering, only stripping;
            # TenantWatcher's match passes everything the inner filter
            # admitted (all carry the tenant prefix)
            return TenantWatcher(w, self.tenant)
        w = self._store.watch(
            kind, since_rv=since_rv, label_selector=label_selector,
            field_selector=field_selector, status_interest=status_interest,
        )
        return TenantWatcher(w, self.tenant)

    # -------------------------------------------------------------- writes

    def _map_obj_in(self, obj: dict, namespace: Optional[str]) -> dict:
        """Inbound copy of ``obj`` with its effective namespace (or
        Namespace-kind name) mapped into the tenant prefix."""
        kind = (obj or {}).get("kind") or ""
        out = dict(obj)
        meta = dict(out.get("metadata") or {})
        if self._is_ns_kind(kind) if kind else False:
            if meta.get("name"):
                meta["name"] = _map_ns(self.tenant, meta["name"])
        elif not kind or self._namespaced(kind):
            meta["namespace"] = _map_ns(
                self.tenant, meta.get("namespace") or namespace
            )
        out["metadata"] = meta
        return out

    def create(
        self,
        obj: dict,
        namespace: Optional[str] = None,
        as_user: Optional[str] = None,
        copy_result: bool = True,
    ) -> dict:
        kind = (obj or {}).get("kind") or ""
        if kind and not self._namespaced(kind) and not self._is_ns_kind(kind):
            return self._store.create(
                obj, namespace=namespace, as_user=as_user, copy_result=copy_result
            )
        mapped = self._map_obj_in(obj, namespace)
        return self._strip(
            kind,
            self._store.create(mapped, as_user=as_user, copy_result=copy_result),
        )

    def update(
        self,
        obj: dict,
        subresource: str = "",
        as_user: Optional[str] = None,
    ) -> dict:
        kind = (obj or {}).get("kind") or ""
        if kind and not self._namespaced(kind) and not self._is_ns_kind(kind):
            return self._store.update(obj, subresource=subresource, as_user=as_user)
        mapped = self._map_obj_in(obj, None)
        return self._strip(
            kind,
            self._store.update(mapped, subresource=subresource, as_user=as_user),
        )

    def patch(
        self,
        kind: str,
        name: str,
        data,
        patch_type: str = "merge",
        namespace: Optional[str] = None,
        subresource: str = "",
        as_user: Optional[str] = None,
        **kw,
    ) -> dict:
        if self._is_ns_kind(kind):
            return self._strip(
                kind,
                self._store.patch(
                    kind, _map_ns(self.tenant, name), data, patch_type,
                    subresource=subresource, as_user=as_user, **kw,
                ),
            )
        if self._namespaced(kind):
            return self._strip(
                kind,
                self._store.patch(
                    kind, name, data, patch_type,
                    namespace=_map_ns(self.tenant, namespace),
                    subresource=subresource, as_user=as_user, **kw,
                ),
            )
        return self._store.patch(
            kind, name, data, patch_type, namespace=namespace,
            subresource=subresource, as_user=as_user, **kw,
        )

    def apply(
        self,
        kind: str,
        name: str,
        applied: dict,
        field_manager: str,
        force: bool = False,
        namespace: Optional[str] = None,
        as_user: Optional[str] = None,
    ):
        if self._is_ns_kind(kind):
            obj, created = self._store.apply(
                kind, _map_ns(self.tenant, name),
                self._map_obj_in(applied, None), field_manager,
                force=force, as_user=as_user,
            )
            return self._strip(kind, obj), created
        if self._namespaced(kind):
            obj, created = self._store.apply(
                kind, name, self._map_obj_in(applied, namespace),
                field_manager, force=force,
                namespace=_map_ns(self.tenant, namespace), as_user=as_user,
            )
            return self._strip(kind, obj), created
        return self._store.apply(
            kind, name, applied, field_manager, force=force,
            namespace=namespace, as_user=as_user,
        )

    def delete(
        self,
        kind: str,
        name: str,
        namespace: Optional[str] = None,
        as_user: Optional[str] = None,
        copy_result: bool = True,
    ):
        if self._is_ns_kind(kind):
            return self._strip(
                kind,
                self._store.delete(
                    kind, _map_ns(self.tenant, name),
                    as_user=as_user, copy_result=copy_result,
                ),
            )
        if self._namespaced(kind):
            return self._strip(
                kind,
                self._store.delete(
                    kind, name, namespace=_map_ns(self.tenant, namespace),
                    as_user=as_user, copy_result=copy_result,
                ),
            )
        return self._store.delete(
            kind, name, namespace=namespace, as_user=as_user,
            copy_result=copy_result,
        )

    def _map_op(self, op: dict) -> dict:
        kind = str(op.get("kind") or "")
        out = dict(op)
        if self._is_ns_kind(kind):
            if out.get("name"):
                out["name"] = _map_ns(self.tenant, out["name"])
            if isinstance(out.get("data"), dict):
                out["data"] = self._map_obj_in(out["data"], None)
        elif self._namespaced(kind):
            out["namespace"] = _map_ns(self.tenant, out.get("namespace"))
            data = out.get("data")
            if op.get("verb") == "create" and isinstance(data, dict):
                out["data"] = self._map_obj_in(data, out["namespace"] and None)
        return out

    def bulk(self, ops: List[dict], copy_results: bool = True, as_user=None):
        mapped = [self._map_op(op) for op in ops]
        res = self._store.bulk(mapped, copy_results=copy_results, as_user=as_user)
        return [
            self._strip(str(op.get("kind") or ""), r) if isinstance(r, dict) else r
            for op, r in zip(ops, res)
        ]

    def transact(self, ops: List[dict], as_user=None, copy_results: bool = True):
        # namespace-affinity after mapping: every op's namespace shares
        # the tenant prefix, and the placement hash truncates at the
        # separator — so a tenant txn is single-shard by construction
        mapped = [self._map_op(op) for op in ops]
        res = self._store.transact(mapped, as_user=as_user, copy_results=copy_results)
        return [
            self._strip(str(op.get("kind") or ""), r) if isinstance(r, dict) else r
            for op, r in zip(ops, res)
        ]

    # ------------------------------------------------------- host surfaces

    def dump_state(self, *a, **kw):
        raise NotFound("state dump is a fleet-host surface, not a tenant one")

    def restore_state(self, *a, **kw):
        raise NotFound("state restore is a fleet-host surface, not a tenant one")


class _Binding:
    """One warm tenant's in-memory materialization: the prefixing store
    proxy plus its k8s wire-protocol facade.  Dropped whole on
    scale-to-zero — durable state lives in the shared store."""

    __slots__ = ("store", "k8s")

    def __init__(self, store: TenantStore, k8s) -> None:
        self.store = store
        self.k8s = k8s


class FleetRegistry:
    """Lifecycle + routing authority for a fixed tenant set.

    State machine per tenant, computed from ``clock.now() - last_seen``
    (never stored, never ticked by a thread):

    - ``cold``: no binding (never seen, or swept after
      ``cold_after_s``); the first request cold-starts it.
    - ``warm``: a request arrived within ``idle_after_s``.
    - ``idle``: quiet past ``idle_after_s`` but not yet past
      ``cold_after_s``; the binding survives, so the next request is
      still warm-path.

    The sweep that drops cold bindings is opportunistic and
    rate-limited (piggybacks on ``touch``/``snapshot`` at most once per
    ``SWEEP_EVERY_S`` of the injected clock) — no background thread, no
    sleeps, fully deterministic under FakeClock/VirtualClock."""

    SWEEP_EVERY_S = 1.0

    def __init__(
        self,
        store,
        tenants: List[str],
        clock: Optional[Clock] = None,
        idle_after_s: float = 300.0,
        cold_after_s: float = 900.0,
        kubelet_url: Optional[str] = None,
    ):
        self._store = store
        self._ids = list(tenants)
        self._set = frozenset(self._ids)
        self._clock = clock or MonotonicClock()
        self.idle_after_s = float(idle_after_s)
        self.cold_after_s = max(float(cold_after_s), self.idle_after_s)
        self._kubelet_url = kubelet_url
        self._mut = make_lock("fleet.tenant.FleetRegistry._mut")
        self._bindings: Dict[str, _Binding] = {}
        # request threads + the lifecycle sweep share the binding map —
        # declared to the runtime race sentinel (KWOK_RACE_SENTINEL=1)
        guarded(self, "_bindings", "fleet.tenant.FleetRegistry._mut")
        self._last_seen: Dict[str, float] = {}
        self._cold_starts: Dict[str, int] = {t: 0 for t in self._ids}
        self._requests: Dict[str, int] = {t: 0 for t in self._ids}
        self._next_sweep = self._clock.now()
        n = int(getattr(store, "shard_count", 1) or 1)
        #: tenant -> pinned shard (stable: crc32 of the tenant segment)
        self.shards: Dict[str, int] = {
            t: shard_of(True, "Pod", _map_ns(t, "default"), n) for t in self._ids
        }

    # ------------------------------------------------------------- routing

    def tenants(self) -> List[str]:
        return list(self._ids)

    def is_tenant(self, tenant: str) -> bool:
        return tenant in self._set

    @staticmethod
    def level_for(tenant: str) -> str:
        """The tenant's APF priority level name IS its id (bounded:
        the fleet's tenant set is fixed at creation)."""
        return tenant

    # ----------------------------------------------------------- lifecycle

    def touch(self, tenant: str) -> Tuple[_Binding, bool]:
        """Route one request: returns the tenant's binding, cold-
        starting it if needed, and whether this request cold-started
        it.  Raises :class:`UnknownTenant` outside the fixed set."""
        if tenant not in self._set:
            raise UnknownTenant(f"unknown fleet tenant {tenant!r}")
        now = self._clock.now()
        cold_started = False
        t0 = time.monotonic()
        with self._mut:
            binding = self._bindings.get(tenant)
            if binding is None:
                binding = self._bind(tenant)
                self._bindings[tenant] = binding
                cold_started = True
                self._cold_starts[tenant] += 1
            self._last_seen[tenant] = now
            self._requests[tenant] += 1
        if cold_started:
            # first request materializes the virtual cluster's bootstrap
            # namespaces (default/kube-system, tenant-prefixed in the
            # shared store) — outside the registry lock, the store has
            # its own
            self._ensure_bootstrap(binding)
            from kwok_tpu.fleet import views

            views.observe_cold_start(time.monotonic() - t0)
        self.sweep(now=now)
        return binding, cold_started

    def _bind(self, tenant: str) -> _Binding:
        from kwok_tpu.cluster.k8s_api import K8sFacade

        ts = TenantStore(self._store, tenant)
        return _Binding(ts, K8sFacade(ts, kubelet_url=self._kubelet_url))

    def _ensure_bootstrap(self, binding: _Binding) -> None:
        ensure = getattr(binding.k8s, "ensure_namespaces", None)
        if ensure is not None:
            try:
                ensure()
                return
            except AlreadyExists:
                return
            except Exception:  # noqa: BLE001 — degraded storage: serve reads
                return
        try:
            binding.store.create({"kind": "Namespace", "metadata": {"name": "default"}})
        except AlreadyExists:
            pass
        except Exception:  # noqa: BLE001
            pass

    def state_of(self, tenant: str, now: Optional[float] = None) -> str:
        if tenant not in self._set:
            raise UnknownTenant(f"unknown fleet tenant {tenant!r}")
        now = self._clock.now() if now is None else now
        with self._mut:
            return self._state_locked(tenant, now)

    def _state_locked(self, tenant: str, now: float) -> str:
        if tenant not in self._bindings:
            return COLD
        age = now - self._last_seen.get(tenant, now)
        if age >= self.cold_after_s:
            return COLD  # due for the next sweep; already reads cold
        if age >= self.idle_after_s:
            return IDLE
        return WARM

    def sweep(self, now: Optional[float] = None, force: bool = False) -> int:
        """Drop bindings whose tenants went cold (scale-to-zero).
        Rate-limited on the injected clock unless ``force``; returns
        how many bindings were dropped."""
        now = self._clock.now() if now is None else now
        with self._mut:
            if not force and now < self._next_sweep:
                return 0
            self._next_sweep = now + self.SWEEP_EVERY_S
            dead = [
                t
                for t in self._bindings
                if now - self._last_seen.get(t, now) >= self.cold_after_s
            ]
            for t in dead:
                del self._bindings[t]
            return len(dead)

    # --------------------------------------------------------- observation

    @staticmethod
    def observe(tenant: str, seconds: float) -> None:
        """Per-tenant request-duration observation (the apiserver calls
        this through the duck-typed fleet seam so cluster/ never
        imports fleet/)."""
        from kwok_tpu.fleet import views

        views.observe_request(tenant, seconds)

    # ----------------------------------------------------------- reporting

    def snapshot(self) -> Dict[str, int]:
        """Bounded summary for ``/stats`` and ``kwokctl get
        components``: tenant count + cold/warm/idle split."""
        now = self._clock.now()
        self.sweep(now=now)
        counts = {COLD: 0, WARM: 0, IDLE: 0}
        with self._mut:
            for t in self._ids:
                counts[self._state_locked(t, now)] += 1
            return {
                "tenants": len(self._ids),
                "warm": counts[WARM],
                "idle": counts[IDLE],
                "cold": counts[COLD],
                "cold_starts": sum(self._cold_starts.values()),
            }

    def describe(self) -> List[Dict[str, object]]:
        """Per-tenant rows for ``GET /fleet`` / ``kwokctl get fleet``."""
        now = self._clock.now()
        self.sweep(now=now)
        with self._mut:
            return [
                {
                    "tenant": t,
                    "state": self._state_locked(t, now),
                    "shard": self.shards[t],
                    "cold_starts": self._cold_starts[t],
                    "requests": self._requests[t],
                }
                for t in self._ids
            ]

    def report(self) -> Dict[str, object]:
        """The ``GET /fleet`` body: the lifecycle summary plus
        per-tenant rows joined with each tenant's observed latency
        quantiles and the fleet-wide cold-start distribution."""
        from kwok_tpu.fleet import views

        lat = views.latency_summary()
        rows = self.describe()
        for row in rows:
            row["latency"] = lat.get(row["tenant"])
        out: Dict[str, object] = dict(self.snapshot())
        out["cold_start_latency"] = views.cold_start_quantiles()
        out["rows"] = rows
        return out

    def tenant_detail(self, tenant: str) -> Dict[str, object]:
        """One tenant's deep view (``GET /fleet?tenant=``): lifecycle
        row + latency + journey timelines + critical-path budget.
        Raises :class:`UnknownTenant` outside the fleet."""
        from kwok_tpu.fleet import views

        state = self.state_of(tenant)  # raises UnknownTenant
        with self._mut:
            row: Dict[str, object] = {
                "tenant": tenant,
                "state": state,
                "shard": self.shards[tenant],
                "cold_starts": self._cold_starts[tenant],
                "requests": self._requests[tenant],
            }
        row["latency"] = views.tenant_latency(tenant)
        row["journeys"] = views.tenant_journeys(tenant)
        row["critical_path"] = views.tenant_critical_path(tenant)
        return row
