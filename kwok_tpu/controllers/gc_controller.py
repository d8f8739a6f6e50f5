"""Garbage collection + namespace lifecycle: the kube-controller-manager
behaviors every reference cluster gets for free.

The reference composes a real kube-controller-manager into each cluster
(reference pkg/kwokctl/components/kube_controller_manager.go:46;
runtime/binary/cluster.go:316-728), so deleting a Job cascades to its
pods and deleting a Namespace reaps its contents.  This controller is
the rebuild's seat for those two behaviors (VERDICT r02 missing #1):

- **ownerReference GC** (background cascade): an object is deleted once
  ALL of its owners are gone.  Before any delete the owners are
  re-verified against the store (the authoritative read k8s's GC calls
  "virtual node verification") so out-of-order watch delivery can never
  orphan-delete a child whose owner simply has not been observed yet.
  ``blockOwnerDeletion`` and the foreground/orphan deleteOptions are
  simplified away: deletion is always background-cascade (documented
  divergence; the store API carries no deleteOptions).
- **namespace lifecycle**: namespaces get a ``kwok.x-k8s.io/namespace``
  finalizer on sight (the apiserver's ``spec.finalizers: [kubernetes]``
  analog).  A terminating namespace has its namespaced objects deleted;
  once empty, the finalizer is removed and the store reaps it.

Deletes go through the normal graceful path, so owned pods holding the
kwok finalizer exit via the stage machinery (pod-remove-finalizer ->
delete) exactly like a user-initiated delete.

Store-duck-typed: works over a ResourceStore or a ClusterClient (the
separate-daemon topology, ``python -m kwok_tpu.cmd.kcm``).
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Set, Tuple

from kwok_tpu.cluster.informer import Informer, WatchOptions
from kwok_tpu.cluster.store import DELETED, NS_FINALIZER, NotFound
from kwok_tpu.utils.log import get_logger
from kwok_tpu.utils.queue import Queue

__all__ = ["GCController", "NS_FINALIZER"]

logger = get_logger("gc")

#: kinds that are never GC'd or namespace-reaped (infrastructure)
_EXEMPT = {"Namespace", "Event"}

ChildKey = Tuple[str, str, str]  # (kind, namespace, name)


def _owner_keys(ref: dict, child_ns: str):
    """Index keys an ownerReference resolves under: by uid when present,
    and by (kind, namespace-or-cluster, name)."""
    keys = []
    uid = ref.get("uid")
    if uid:
        keys.append(f"u:{uid}")
    kind = ref.get("kind") or ""
    name = ref.get("name") or ""
    if kind and name:
        keys.append(f"k:{kind}/{child_ns}/{name}")
        keys.append(f"k:{kind}//{name}")  # cluster-scoped owner
    return keys


class GCController:
    """Background owner-reference cascade + namespace reaper."""

    RESYNC_S = 2.0

    def __init__(self, store, resync_s: Optional[float] = None, active=None):
        self.store = store
        #: leadership gate (cluster/election.py LeaderElector.is_leader
        #: duck type): each loop round re-checks it, so a deposed kcm
        #: replica never issues deletes.  None = always active.
        self._active = active
        self.events: Queue = Queue()
        self.resync_s = resync_s if resync_s is not None else self.RESYNC_S
        self._done = threading.Event()
        self._threads = []
        self._watched: Set[str] = set()
        self._informers = []
        self._mut = threading.Lock()
        #: owner index key -> children holding a ref to it
        self._children: Dict[str, Set[ChildKey]] = {}
        #: child -> its owner index keys (for unregistering)
        self._child_refs: Dict[ChildKey, Tuple[dict, ...]] = {}
        #: namespaces currently terminating
        self._terminating: Set[str] = set()
        #: deletes already issued (avoid re-delete loops on MODIFIED
        #: events of terminating objects)
        self._deleting: Set[ChildKey] = set()
        #: failed collections, retried each resync
        self._retry: Set[ChildKey] = set()
        #: span context of the event being handled (loop-thread-only)
        self._event_ctx = None
        self.deleted_total = 0

    # ------------------------------------------------------------------ wiring

    def start(self) -> "GCController":
        self._refresh_watches()
        t = threading.Thread(target=self._loop, daemon=True)
        t.start()
        self._threads.append(t)
        return self

    def stop(self) -> None:
        self._done.set()
        for t in self._threads:
            t.join(timeout=2.0)

    def _refresh_watches(self) -> None:
        """Watch every kind the store knows (CR kinds appear later —
        re-checked each resync, the DynamicGetter analog)."""
        try:
            kinds = self.store.kinds()
        except Exception:  # noqa: BLE001 — remote store hiccup
            return
        for rt in kinds:
            if rt.kind in self._watched:
                continue
            self._watched.add(rt.kind)
            inf = Informer(self.store, rt.kind)
            # status-indifferent: GC reads ownerReferences /
            # deletionTimestamp / finalizers — never status.  In-process
            # stores then skip this watcher on status batches (the
            # "GC must not become a second drain" contract,
            # VERDICT r03 next-#6)
            inf.watch(
                WatchOptions(status_interest=False), self.events, done=self._done
            )
            self._informers.append(inf)

    # ------------------------------------------------------------------- loop

    def _loop(self) -> None:
        import time as _time

        next_resync = _time.monotonic() + self.resync_s
        while not self._done.is_set():
            wait = max(0.05, next_resync - _time.monotonic())
            ev, ok = self.events.get_or_wait(
                timeout=min(wait, self.resync_s), done=self._done
            )
            gated = self._active is not None and not self._active()
            if ok and ev is not None and not gated:
                try:
                    self._handle(ev)
                except Exception:  # noqa: BLE001 — one event must not kill GC
                    import traceback

                    traceback.print_exc()
            # deadline-based, NOT idle-based: a steady event stream (the
            # device player's per-tick echoes) must not starve namespace
            # reaping, delete retries, or new-kind pickup
            if _time.monotonic() < next_resync:
                continue
            next_resync = _time.monotonic() + self.resync_s
            if gated:
                continue  # standby/deposed: no reaping, no retries
            try:
                self._refresh_watches()
                self.sync_once()
            except Exception:  # noqa: BLE001
                import traceback

                traceback.print_exc()

    # ------------------------------------------------------- synchronous seams

    def handle_event(self, ev) -> None:
        """Public synchronous seam: index/collect one informer event.
        The thread loop feeds this; a simulated-time harness
        (kwok_tpu.dst) drives it directly from pumped watch events."""
        self._handle(ev)

    def sync_once(self) -> None:
        """One resync sweep without the thread loop: reap terminating
        namespaces, retry failed collections.  The `_loop` resync body
        and the DST harness share this."""
        self._event_ctx = None  # sweeps have no single causing write
        for ns in sorted(self._terminating):
            self._reap_namespace(ns)
        with self._mut:
            retry, self._retry = self._retry, set()
        for child in sorted(retry):
            self._maybe_collect(child)

    # ---------------------------------------------------------------- indexing

    def _handle(self, ev) -> None:
        obj = ev.object
        kind = obj.get("kind") or ""
        meta = obj.get("metadata") or {}
        ns = meta.get("namespace") or ""
        name = meta.get("name") or ""
        child: ChildKey = (kind, ns, name)
        # causing write's span context (watch-boundary stitch): held
        # for the duration of this event's handling so a resulting
        # delete's span can continue/link the causing trace.  All index
        # mutation happens on this one loop thread, so a plain
        # attribute is safe.
        self._event_ctx = getattr(ev, "ctx", None)

        # steady-churn fast path: an ADDED/MODIFIED object with no
        # ownerReferences that we have never indexed, outside any
        # terminating namespace, is of no GC interest — two lock-free
        # dict probes and out (all index mutation happens on this loop
        # thread, so the unlocked reads cannot race a writer)
        if (
            ev.type != DELETED
            and kind != "Namespace"
            and not meta.get("ownerReferences")
            and child not in self._child_refs
            and (not ns or ns not in self._terminating)
        ):
            return

        if kind == "Namespace":
            self._handle_namespace(ev, obj, name)
            return

        if ev.type == DELETED:
            with self._mut:
                self._deleting.discard(child)
                refs = self._child_refs.pop(child, ())
                for ref in refs:
                    for k in _owner_keys(ref, ns):
                        bucket = self._children.get(k)
                        if bucket is not None:
                            bucket.discard(child)
                            if not bucket:
                                del self._children[k]
                # this object may itself be an owner: its children are
                # now candidates
                dependents: Set[ChildKey] = set()
                for k in (f"u:{meta.get('uid')}", f"k:{kind}/{ns}/{name}", f"k:{kind}//{name}"):
                    dependents |= self._children.get(k, set())
            # sorted: set order varies with the per-process hash seed,
            # and deterministic-simulation runs (kwok_tpu.dst) replay
            # audit traces byte-identically across processes
            for dep in sorted(dependents):
                self._maybe_collect(dep)
            return

        if kind in _EXEMPT:
            return

        # terminating namespace: reap new arrivals too
        if ns and ns in self._terminating:
            self._delete(child)

        refs = tuple(meta.get("ownerReferences") or ())
        with self._mut:
            old = self._child_refs.get(child)
            if old == refs:
                changed = False
            else:
                changed = True
                for ref in old or ():
                    for k in _owner_keys(ref, ns):
                        bucket = self._children.get(k)
                        if bucket is not None:
                            bucket.discard(child)
                            if not bucket:
                                del self._children[k]
                if refs:
                    self._child_refs[child] = refs
                    for ref in refs:
                        for k in _owner_keys(ref, ns):
                            self._children.setdefault(k, set()).add(child)
                else:
                    self._child_refs.pop(child, None)
        if changed and refs:
            self._maybe_collect(child)

    # --------------------------------------------------------------- collection

    def _owner_alive(self, ref: dict, child_ns: str) -> bool:
        """Authoritative store read (never trust the index alone: watch
        delivery across kinds is unordered, so a child can be seen
        before its owner)."""
        kind = ref.get("kind") or ""
        name = ref.get("name") or ""
        if not kind or not name:
            return True  # malformed ref: never collect on it
        # one probe in the child's namespace: k8s owners live in the
        # child's namespace or are cluster-scoped (store.get ignores the
        # namespace for cluster-scoped kinds).  No fallback probe — it
        # would resolve against the "default" namespace and a same-name
        # stranger there would keep a dead owner alive.
        try:
            owner = self.store.get(kind, name, namespace=child_ns or None)
        except NotFound:
            return False
        except Exception:  # noqa: BLE001 — remote hiccup: assume alive
            return True
        want_uid = ref.get("uid")
        have_uid = (owner.get("metadata") or {}).get("uid")
        if want_uid and have_uid and want_uid != have_uid:
            return False  # a NEW object reusing the name: owner is gone
        return True

    def _maybe_collect(self, child: ChildKey) -> None:
        kind, ns, name = child
        with self._mut:
            refs = self._child_refs.get(child)
            if not refs or child in self._deleting:
                return
        if any(self._owner_alive(ref, ns) for ref in refs):
            return
        self._delete(child)

    def _delete(self, child: ChildKey) -> None:
        kind, ns, name = child
        with self._mut:
            if child in self._deleting:
                return
            self._deleting.add(child)
        from kwok_tpu.utils.trace import get_tracer

        tracer = get_tracer()
        span = None
        if tracer.enabled:
            # the GC cascade continues the causing write's trace (the
            # owner delete that orphaned this child) when the event ctx
            # is in hand; resync-sweep deletes open a fresh root
            ctx = getattr(self, "_event_ctx", None)
            tid, pid = ctx if ctx else (None, None)
            span = tracer.span("gc.delete", trace_id=tid, parent_id=pid)
            if ctx:
                span.add_link(*ctx)
            span.set("object", f"{kind}:{ns}/{name}")
        try:
            self.store.delete(kind, name, namespace=ns or None)
            self.deleted_total += 1
            logger.info("gc: deleted %s %s/%s (owners gone)", kind, ns, name)
        except NotFound:
            pass
        except Exception:  # noqa: BLE001 — retried on next resync/event
            if span is not None:
                span.error("delete failed; queued for retry")
            with self._mut:
                self._deleting.discard(child)
                self._retry.add(child)
        finally:
            if span is not None:
                span.end()

    # ---------------------------------------------------------------- namespaces

    def _handle_namespace(self, ev, obj: dict, name: str) -> None:
        if ev.type == DELETED:
            self._terminating.discard(name)
            return
        meta = obj.get("metadata") or {}
        fins = list(meta.get("finalizers") or [])
        if meta.get("deletionTimestamp"):
            self._terminating.add(name)
            self._reap_namespace(name)
            return
        if NS_FINALIZER not in fins:
            # the apiserver's namespace finalizer seat: added on sight so
            # a later delete holds the namespace in Terminating until
            # its contents are reaped
            try:
                self.store.patch(
                    "Namespace",
                    name,
                    {"metadata": {"finalizers": fins + [NS_FINALIZER]}},
                    "merge",
                )
            except Exception:  # noqa: BLE001 — next event retries
                pass

    def _reap_namespace(self, ns: str) -> None:
        """Delete the namespace's remaining contents; drop the finalizer
        once empty (the namespace lifecycle controller's finalize)."""
        remaining = 0
        try:
            kinds = self.store.kinds()
        except Exception:  # noqa: BLE001
            return
        for rt in kinds:
            if not rt.namespaced or rt.kind in _EXEMPT:
                continue
            try:
                items, _ = self.store.list(rt.kind, namespace=ns)
            except Exception:  # noqa: BLE001
                continue
            for obj in items:
                remaining += 1
                meta = obj.get("metadata") or {}
                if meta.get("deletionTimestamp"):
                    continue  # already terminating (stage path finishes it)
                self._delete((rt.kind, ns, meta.get("name") or ""))
        if remaining:
            return
        # empty: finalize the namespace
        try:
            cur = self.store.get("Namespace", ns)
        except NotFound:
            self._terminating.discard(ns)
            return
        except Exception:  # noqa: BLE001
            return
        fins = [
            f
            for f in (cur.get("metadata") or {}).get("finalizers") or []
            if f != NS_FINALIZER
        ]
        try:
            self.store.patch(
                "Namespace", ns, {"metadata": {"finalizers": fins or None}}, "merge"
            )
            self._terminating.discard(ns)
            logger.info("gc: namespace %s finalized", ns)
        except NotFound:
            self._terminating.discard(ns)
        except Exception:  # noqa: BLE001 — next resync retries
            pass
