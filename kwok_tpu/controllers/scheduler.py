"""Pod scheduler: binds unbound pods to simulated nodes.

Reference clusters run a real kube-scheduler as a component
(reference pkg/kwokctl/components/kube_scheduler.go:51;
runtime/binary/cluster.go:316-728 composes it after the apiserver), so
a pod created without ``spec.nodeName`` still reaches Running.  This is
the rebuild's equivalent: round-robin placement with a
resource-capacity fit (requests vs allocatable cpu/memory/pods), which
covers the scheduling semantics simulated clusters exercise — the full
predicate/priority framework of kube-scheduler is out of scope since
nodes here are data, not machines.

Like every controller in this package it is store-duck-typed: give it a
:class:`ResourceStore` or a :class:`ClusterClient` (the separate-daemon
topology, ``python -m kwok_tpu.cmd.scheduler``).  Binds go through the
merge-patch path the facade's ``pods/{name}/binding`` subresource uses
(cluster/k8s_api.py), so both entrances converge on the same write.

Feasibility (readiness, ``spec.nodeSelector``, ``NoSchedule`` taints
vs tolerations, capacity) is shared with the gang engine via
``kwok_tpu/sched/predicates.py:1``; pods carrying the
``kwok.io/pod-group`` annotation are delegated wholesale to the gang
engine (``kwok_tpu/sched/engine.py:1``), which binds each PodGroup
all-or-nothing through the store's atomic transaction lane.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Tuple

from kwok_tpu.cluster.informer import CacheGetter, Informer, WatchOptions
from kwok_tpu.cluster.store import DELETED, EventRecorder
from kwok_tpu.sched.engine import GangEngine
from kwok_tpu.sched.group import gang_key
from kwok_tpu.sched.predicates import (
    node_allocatable as _allocatable,
    node_feasible,
    pod_requests as _requests,
)
from kwok_tpu.sched.topology import TopologyModel
from kwok_tpu.utils import telemetry as _telemetry
from kwok_tpu.utils.backoff import WarnGate
from kwok_tpu.utils.clock import Clock, MonotonicClock
from kwok_tpu.utils.log import get_logger
from kwok_tpu.utils.queue import Queue

__all__ = ["Scheduler"]

logger = get_logger("scheduler")

#: observed time-to-bind (SLO telemetry): first-seen-unbound -> bind
#: patch acknowledged, on the scheduler's injected clock.  No labels —
#: per-pod identity is exactly what the metric-cardinality rule forbids
_H_BIND = _telemetry.histogram(
    "kwok_scheduler_bind_seconds",
    help="pod time-to-bind (scheduler first sight to acked bind)",
)


class Scheduler:
    """Round-robin + capacity-fit pod binder."""

    def __init__(
        self,
        store,
        recorder: Optional[EventRecorder] = None,
        name: str = "kwok-scheduler",
        active=None,
        clock: Optional[Clock] = None,
        gang_policy: Optional[str] = "binpack",
        topology: Optional[TopologyModel] = None,
    ):
        self.store = store
        self.name = name
        #: leadership gate (cluster/election.py LeaderElector.is_leader
        #: duck type): each bind round re-checks it, so a deposed
        #: replica stops scheduling before it is even torn down.  None
        #: = always active (in-process single-instance composition).
        self._active = active
        self.recorder = recorder or EventRecorder(store, source=name)
        #: monotonic by default (wallclock-deadline discipline); the
        #: DST injects its virtual clock so warn backoff replays
        self._clock = clock or MonotonicClock()
        self._done = threading.Event()
        self._events: Queue = Queue()
        self._nodes: CacheGetter = CacheGetter()
        #: uid → (node, cpu, mem): usage of every live bound pod, built
        #: incrementally from bind results and watch events — the
        #: kube-scheduler cache equivalent (no per-bind re-list; uid
        #: keying makes the bind-then-watch-echo sequence idempotent)
        self._pod_usage: Dict[str, Tuple[str, float, float]] = {}
        self._used_agg: Dict[str, Tuple[float, float, int]] = {}
        self._rr = 0  # round-robin cursor
        #: name-sorted node objects; invalidated on node events and
        #: rebuilt lazily at the next bind (not per bind)
        self._sorted_nodes: Optional[list] = None
        #: per-pod FailedScheduling backoff (utils.backoff.WarnGate).
        #: _retry_pending re-binds every 2s; without this every pending
        #: pod re-emits the same warning each pass — an event flood at
        #: 1M-pod scale
        self._warn_pods = WarnGate(self.WARN_BASE_S, self.WARN_CAP_S)
        #: uid -> clock instant this scheduler first saw the pod
        #: unbound (observed time-to-bind anchor; popped on bind,
        #: cleared on delete so the map stays bounded by pending pods)
        self._first_seen: Dict[str, float] = {}
        self._threads = []
        self._mut = threading.Lock()
        #: gang engine (kwok_tpu.sched): pods annotated with
        #: kwok.io/pod-group bypass _bind and go through all-or-nothing
        #: admission; None disables (gang pods then bind individually)
        self.gang: Optional[GangEngine] = None
        if gang_policy and gang_policy != "none":
            self.gang = GangEngine(
                store,
                recorder=self.recorder,
                policy=gang_policy,
                topology=topology,
                nodes=self._sorted,
                usage=self._usage_snapshot,
                track=self._track,
                clock=self._clock,
            )

    # ----------------------------------------------------------- usage cache

    def _track(self, pod: dict, node: str) -> None:
        uid = (pod.get("metadata") or {}).get("uid") or ""
        cpu, mem = _requests(pod)
        with self._mut:
            # bound (by us, the gang engine's txn, or another binder):
            # drop any pending time-to-bind anchor so _first_seen stays
            # bounded by pending pods (_untrack mirrors this for
            # terminal/deleted pods)
            self._first_seen.pop(uid, None)
            if uid in self._pod_usage:
                return
            self._pod_usage[uid] = (node, cpu, mem)
            c0, m0, n0 = self._used_agg.get(node, (0.0, 0.0, 0))
            self._used_agg[node] = (c0 + cpu, m0 + mem, n0 + 1)

    def _untrack(self, pod: dict) -> None:
        uid = (pod.get("metadata") or {}).get("uid") or ""
        with self._mut:
            self._warn_pods.clear(uid)
            self._first_seen.pop(uid, None)
            entry = self._pod_usage.pop(uid, None)
            if entry is None:
                return
            node, cpu, mem = entry
            c0, m0, n0 = self._used_agg.get(node, (0.0, 0.0, 0))
            if n0 <= 1:
                self._used_agg.pop(node, None)
            else:
                self._used_agg[node] = (c0 - cpu, m0 - mem, n0 - 1)

    def _usage_snapshot(self) -> Dict[str, Tuple[float, float, int]]:
        """Per-node (cpu, mem, pods) in use — the gang engine's view of
        the same cache binds maintain, copied under the lock."""
        with self._mut:
            return dict(self._used_agg)

    # --------------------------------------------------------------- fitting

    def _sorted(self) -> list:
        """Node objects in name order, maintained from informer events
        (ADVICE r02: re-sorting the cache per bind made scheduling
        O(pods x nodes log nodes) at reference scale)."""
        nodes = self._sorted_nodes
        if nodes is None:
            nodes = self._sorted_nodes = sorted(
                self._nodes.list(), key=lambda n: n["metadata"]["name"]
            )
        return nodes

    def _pick_node(self, pod: dict) -> Optional[str]:
        nodes = self._sorted()
        if not nodes:
            return None
        cpu, mem = _requests(pod)
        n = len(nodes)
        with self._mut:
            used = self._used_agg  # read under the same lock binds write
            for i in range(n):
                node = nodes[(self._rr + i) % n]
                # readiness + nodeSelector + NoSchedule-taint
                # feasibility (sched/predicates.py — both were silently
                # ignored before, landing selector-bearing workloads on
                # arbitrary nodes)
                if not node_feasible(pod, node):
                    continue
                name = node["metadata"]["name"]
                a_cpu, a_mem, a_pods = _allocatable(node)
                u_cpu, u_mem, u_pods = used.get(name, (0.0, 0.0, 0))
                if (
                    u_cpu + cpu <= a_cpu
                    and u_mem + mem <= a_mem
                    and u_pods + 1 <= a_pods
                ):
                    self._rr = (self._rr + i + 1) % n
                    return name
        return None

    # --------------------------------------------------------------- binding

    def _bind(self, pod: dict, ctx=None) -> None:
        from kwok_tpu.utils.trace import get_tracer

        tracer = get_tracer()
        if tracer.enabled:
            meta = pod.get("metadata") or {}
            # continue the causing write's trace across the watch
            # boundary (ctx = the commit's span context resolved at
            # delivery): the bind span joins the SAME trace id the
            # client's create started, and also records the link — so
            # one trace follows the pod from create to Running
            tid, pid = (ctx or (None, None))[:2] if ctx else (None, None)
            with tracer.span(
                "schedule.bind", trace_id=tid, parent_id=pid
            ) as sp:
                if ctx:
                    sp.add_link(*ctx)
                sp.set("pod", f"{meta.get('namespace', 'default')}/{meta.get('name')}")
                self._bind_inner(pod, sp)
        else:
            self._bind_inner(pod, None)

    #: FailedScheduling re-emit cadence: base doubles per miss up to cap
    WARN_BASE_S = 2.0
    WARN_CAP_S = 60.0

    def _warn_unschedulable(self, pod: dict) -> None:
        """Per-pod deduplicated FailedScheduling with exponential
        backoff — _retry_pending re-binds every 2s, and re-emitting the
        identical warning each pass is an event flood at scale."""
        meta = pod.get("metadata") or {}
        uid = meta.get("uid") or (
            f"{meta.get('namespace') or 'default'}/{meta.get('name')}"
        )
        now = self._clock.now()
        with self._mut:
            if not self._warn_pods.ready(uid, now):
                return
        self.recorder.event(
            pod,
            "Warning",
            "FailedScheduling",
            "0/%d nodes are available" % len(self._nodes),
        )

    def _note_pending(self, pod: dict) -> None:
        """Anchor the pod's time-to-bind at first unbound sight
        (idempotent; the DST's virtual clock rides the same seam)."""
        if not _telemetry.enabled():
            return
        uid = (pod.get("metadata") or {}).get("uid") or ""
        if not uid:
            return
        with self._mut:
            self._first_seen.setdefault(uid, self._clock.now())

    def _bind_inner(self, pod: dict, span) -> None:
        meta = pod.get("metadata") or {}
        name, ns = meta.get("name") or "", meta.get("namespace") or "default"
        uid = meta.get("uid")
        with self._mut:
            if uid and uid in self._pod_usage:
                # bound since this event was queued (by the retry pass,
                # or an earlier event of the same pod): a bound pod is
                # never bound again, as kube's binding answers Conflict
                return
        target = self._pick_node(pod)
        if span is not None:
            span.set("node", target or "")
        if target is None:
            self._warn_unschedulable(pod)
            return
        try:
            self.store.patch(
                "Pod",
                pod["metadata"]["name"],
                {"spec": {"nodeName": target}},
                patch_type="merge",
                namespace=ns,
            )
            # pop the anchor BEFORE _track (which also pops, for the
            # binds that happen outside this method)
            with self._mut:
                self._warn_pods.clear(meta.get("uid") or "")
                t_seen = self._first_seen.pop(meta.get("uid") or "", None)
            if t_seen is not None:
                # observed time-to-bind; observation-only, clock-seamed
                _H_BIND.observe(self._clock.now() - t_seen)
            self._track(pod, target)
            self.recorder.event(
                pod,
                "Normal",
                "Scheduled",
                f"Successfully assigned {ns}/{name} to {target}",
            )
        except Exception as exc:  # noqa: BLE001 — pod may be gone
            logger.info("bind failed", pod=f"{ns}/{name}", err=str(exc))

    # ------------------------------------------------------------------ loop

    def _loop(self) -> None:
        pending_retry = 0.0
        while not self._done.is_set():
            ev, _ok = self._events.get_or_wait(timeout=0.25, done=self._done)
            if ev is None:
                # nodes may have appeared/recovered; re-list unschedulable
                # pods at a gentle cadence
                pending_retry += 0.25
                if pending_retry >= 2.0:
                    pending_retry = 0.0
                    self._retry_pending()
                continue
            self.handle_event(ev)

    def handle_event(self, ev) -> None:
        """Process one node/pod event (the `_loop` body, factored out
        so a simulated-time harness can drive the same state machine
        synchronously — kwok_tpu.dst)."""
        obj = ev.object
        if obj.get("kind") == "Node":
            # cache updated by the informer; drop the sorted view so
            # the next bind rebuilds it (retry path covers pods)
            self._sorted_nodes = None
            return
        gang = self.gang if (
            self.gang is not None and GangEngine.is_gang_pod(obj)
        ) else None
        ctx = getattr(ev, "ctx", None)
        if ev.type == DELETED:
            self._untrack(obj)
            if gang is not None:
                gang.observe(DELETED, obj)
            return
        node = (obj.get("spec") or {}).get("nodeName")
        if node:
            # _track/_untrack both drop the pod's time-to-bind anchor,
            # so _first_seen stays bounded by pending pods even for
            # gang members and pods bound by a peer (which never pass
            # through _bind_inner's pop)
            if (obj.get("status") or {}).get("phase") in ("Succeeded", "Failed"):
                self._untrack(obj)  # terminal pods free their slot
            else:
                self._track(obj, node)
            if gang is not None:
                gang.observe(ev.type, obj)  # membership, like the cache
            return
        if (obj.get("metadata") or {}).get("deletionTimestamp"):
            return
        self._note_pending(obj)
        if gang is not None:
            # membership is cache maintenance (standbys stay current);
            # the bind attempt below is leader-gated like _bind
            gang.observe(ev.type, obj, ctx=ctx)
        if self._active is not None and not self._active():
            return  # standby/deposed: track caches, never bind
        if gang is not None:
            gang.try_schedule(gang_key(obj))
            return
        self._bind(obj, ctx=ctx)

    def _retry_pending(self) -> None:
        if self._active is not None and not self._active():
            return
        try:
            pods, _ = self.store.list("Pod", field_selector="spec.nodeName=")
        except Exception:  # noqa: BLE001 — apiserver outage; informer retries
            return
        for pod in pods:
            if (pod.get("metadata") or {}).get("deletionTimestamp"):
                continue
            self._note_pending(pod)
            if self.gang is not None and GangEngine.is_gang_pod(pod):
                # heal membership the watch may have missed, then let
                # the engine's own retry pass below attempt the gang
                self.gang.observe("ADDED", pod)
                continue
            self._bind(pod)
        if self.gang is not None:
            self.gang.retry_pending()

    def start(self) -> "Scheduler":
        node_informer = Informer(self.store, "Node")
        node_informer.watch(
            WatchOptions(), self._events, done=self._done, cache=self._nodes
        )
        pod_informer = Informer(self.store, "Pod")
        pod_informer.watch(WatchOptions(), self._events, done=self._done)
        t = threading.Thread(target=self._loop, daemon=True, name="scheduler")
        t.start()
        self._threads.append(t)
        return self

    def stop(self) -> None:
        self._done.set()
        for t in self._threads:
            t.join(timeout=5)
