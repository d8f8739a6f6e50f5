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
topology, ``python -m kwok_tpu.cmd.scheduler``).  The loop handles a
run of queued events at once (up to :data:`RUN_MAX`, whatever is queued
when it wakes): each pending pod is picked against a cache that already
holds the picks before it (kube-scheduler's "assume"), so a run places
exactly as one event at a time would, and the run's binds are then
written as one ``bulk`` of the merge patch the facade's
``pods/{name}/binding`` subresource applies (cluster/k8s_api.py) — both
entrances converge on the same write — followed by one
``EventRecorder.record`` of their Scheduled Events.  A run that picked
one pod sends that ``PATCH`` and its Event alone.

Feasibility (readiness, ``spec.nodeSelector``, ``NoSchedule`` taints
vs tolerations, capacity) is shared with the gang engine via
``kwok_tpu/sched/predicates.py:1``; pods carrying the
``kwok.io/pod-group`` annotation are delegated wholesale to the gang
engine (``kwok_tpu/sched/engine.py:1``), which binds each PodGroup
all-or-nothing through the store's atomic transaction lane.
"""

from __future__ import annotations

import threading
from contextlib import nullcontext
from typing import Dict, List, Optional, Tuple

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

__all__ = ["Scheduler", "RUN_MAX"]

logger = get_logger("scheduler")

#: observed time-to-bind (SLO telemetry): first-seen-unbound -> bind
#: patch acknowledged, on the scheduler's injected clock.  No labels —
#: per-pod identity is exactly what the metric-cardinality rule forbids
_H_BIND = _telemetry.histogram(
    "kwok_scheduler_bind_seconds",
    help="pod time-to-bind (scheduler first sight to acked bind)",
)

#: most events one run takes off the queue, and so most binds one
#: request carries: half a client's bulk of 1,000, under the kwok
#: daemon's commit of a quarter of a watcher's high-water mark
RUN_MAX = 500

#: (pod, node it is assumed on, its schedule.bind span or None)
_Pick = Tuple[dict, str, object]


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
        #: duck type): each run re-checks it before its first pick, so a
        #: deposed replica stops scheduling before it is even torn down.
        #: None = always active (in-process single-instance composition).
        self._active = active
        self.recorder = recorder or EventRecorder(store, source=name)
        #: monotonic by default (wallclock-deadline discipline); the
        #: DST injects its virtual clock so warn backoff replays
        self._clock = clock or MonotonicClock()
        self._done = threading.Event()
        self._events: Queue = Queue()
        self._nodes: CacheGetter = CacheGetter()
        #: uid → (node, cpu, mem): usage of every live bound pod, built
        #: incrementally from picks and watch events — the
        #: kube-scheduler cache equivalent (no per-bind re-list; a pick
        #: assumes its pod here before the bind is written, a refused
        #: bind releases it; uid keying makes the bind-then-watch-echo
        #: sequence idempotent)
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
            self._release_locked(uid)

    def _release_locked(self, uid: str) -> None:
        """Take a pod's usage off its node (caller holds ``_mut``)."""
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
        """The first node from the round-robin cursor that ``pod`` fits,
        assumed there at once: the pod's requests join the node's usage
        in the same lock hold, so the next pick of a run sees them
        before the bind is written."""
        nodes = self._sorted()
        if not nodes:
            return None
        uid = (pod.get("metadata") or {}).get("uid") or ""
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
                    self._pod_usage[uid] = (name, cpu, mem)
                    used[name] = (u_cpu + cpu, u_mem + mem, u_pods + 1)
                    return name
        return None

    # --------------------------------------------------------------- binding

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

    @staticmethod
    def _bind_span(pod: dict, ctx):
        """The pod's ``schedule.bind`` span where a tracer is armed, else
        None.  It continues the causing write's trace across the watch
        boundary (``ctx`` = the commit's span context resolved at
        delivery) and also records the link — so one trace follows the
        pod from create to Running."""
        from kwok_tpu.utils.trace import get_tracer

        tracer = get_tracer()
        if not tracer.enabled:
            return None
        meta = pod.get("metadata") or {}
        tid, pid = ctx[:2] if ctx else (None, None)
        sp = tracer.span("schedule.bind", trace_id=tid, parent_id=pid)
        if ctx:
            sp.add_link(*ctx)
        sp.set("pod", f"{meta.get('namespace', 'default')}/{meta.get('name')}")
        return sp

    def _pick(self, pod: dict, ctx=None) -> Optional[_Pick]:
        """Choose and assume a node for one pending pod; None where the
        pod is bound or picked already, or fits nowhere (then a
        backed-off FailedScheduling)."""
        uid = (pod.get("metadata") or {}).get("uid")
        with self._mut:
            if uid and uid in self._pod_usage:
                # bound or picked since this event was queued (by the
                # retry pass, or an earlier event of the same pod): a
                # bound pod is never bound again, as kube's binding
                # answers Conflict
                return None
        span = self._bind_span(pod, ctx)
        target = self._pick_node(pod)
        if span is not None:
            span.set("node", target or "")
        if target is None:
            with span or nullcontext():
                self._warn_unschedulable(pod)
            return None
        return pod, target, span

    def _bound(self, pod: dict) -> None:
        """A bind the store acknowledged: its time-to-bind is observed
        and its warning backoff forgotten (the assumption stands)."""
        uid = (pod.get("metadata") or {}).get("uid") or ""
        with self._mut:
            self._warn_pods.clear(uid)
            t_seen = self._first_seen.pop(uid, None)
        if t_seen is not None:
            # observed time-to-bind; observation-only, clock-seamed
            _H_BIND.observe(self._clock.now() - t_seen)

    def _bind_failed(self, pod: dict, err) -> None:
        """A bind the store refused (the pod may be gone): its
        assumption is released; the retry pass binds it later if it is
        still unbound."""
        meta = pod.get("metadata") or {}
        with self._mut:
            self._release_locked(meta.get("uid") or "")
        ns = meta.get("namespace") or "default"
        logger.info("bind failed", pod=f"{ns}/{meta.get('name')}", err=str(err))

    @staticmethod
    def _scheduled(pod: dict, node: str) -> Tuple[dict, str, str, str]:
        meta = pod.get("metadata") or {}
        ns = meta.get("namespace") or "default"
        msg = f"Successfully assigned {ns}/{meta.get('name') or ''} to {node}"
        return pod, "Normal", "Scheduled", msg

    def _write_binds(self, picks: List[_Pick]) -> None:
        """Write a run's binds, in pick order: one ``bulk`` of merge
        patches of ``spec.nodeName`` (results align with the ops: an
        error fails its pod alone), then one ``record`` of the Scheduled
        Events of those that bound.  A run of one sends its ``PATCH``
        and its Event alone: an envelope saves nothing for one op."""
        ops = [
            {
                "verb": "patch",
                "kind": "Pod",
                "name": pod["metadata"]["name"],
                "namespace": pod["metadata"].get("namespace") or "default",
                "data": {"spec": {"nodeName": node}},
                "patch_type": "merge",
            }
            for pod, node, _span in picks
        ]
        if len(ops) == 1:
            (pod, node, span), op = picks[0], ops[0]
            with span or nullcontext():
                try:
                    self.store.patch(
                        "Pod",
                        op["name"],
                        op["data"],
                        patch_type="merge",
                        namespace=op["namespace"],
                    )
                except Exception as exc:  # noqa: BLE001 — pod may be gone
                    self._bind_failed(pod, exc)
                    return
                self._bound(pod)
                try:
                    self.recorder.event(*self._scheduled(pod, node))
                except Exception as exc:  # noqa: BLE001 — the bind stands
                    logger.info("event failed", pod=op["name"], err=str(exc))
            return
        try:
            results = self.store.bulk(ops)
        except Exception as exc:  # noqa: BLE001 — no bind of the run is known
            results = [{"status": "error", "error": str(exc)}] * len(ops)
        events = []
        for i, (pod, node, span) in enumerate(picks):
            res = results[i] if i < len(results) else None
            if isinstance(res, dict) and res.get("status") == "ok":
                self._bound(pod)
                events.append(self._scheduled(pod, node))
            else:
                err = res.get("error") if isinstance(res, dict) else "no result"
                self._bind_failed(pod, err)
                if span is not None:
                    span.error(str(err))
            if span is not None:
                span.end()
        self.recorder.record(events)

    def _bind(self, pod: dict, ctx=None) -> None:
        """Pick, assume and bind one pod: a run of one."""
        pick = self._pick(pod, ctx)
        if pick is not None:
            self._write_binds([pick])

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
            self._handle_run(self._take_run(ev))

    def _take_run(self, first) -> list:
        """``first`` and what is queued behind it, without waiting, up to
        :data:`RUN_MAX` events: a backlog makes a long run, one pending
        pod a run of one."""
        run = [first]
        while len(run) < RUN_MAX:
            ev, ok = self._events.get()
            if not ok:
                break
            run.append(ev)
        return run

    def handle_event(self, ev) -> None:
        """Process one node/pod event, binding before it returns (the
        `_loop` body for a run of one, factored out so a simulated-time
        harness can drive the same state machine synchronously —
        kwok_tpu.dst)."""
        self._handle_run([ev])

    def _handle_run(self, events: list) -> None:
        """Process a run of events in order — caches, gang membership,
        and a pick (with its assumption) for each pending pod — then
        write the run's binds at once."""
        picks: List[_Pick] = []
        active: Optional[bool] = None
        for ev in events:
            obj = ev.object
            if obj.get("kind") == "Node":
                # cache updated by the informer; drop the sorted view so
                # the next pick rebuilds it (retry path covers pods)
                self._sorted_nodes = None
                continue
            gang = self.gang if (
                self.gang is not None and GangEngine.is_gang_pod(obj)
            ) else None
            ctx = getattr(ev, "ctx", None)
            if ev.type == DELETED:
                self._untrack(obj)
                if gang is not None:
                    gang.observe(DELETED, obj)
                continue
            node = (obj.get("spec") or {}).get("nodeName")
            if node:
                # _track/_untrack both drop the pod's time-to-bind anchor,
                # so _first_seen stays bounded by pending pods even for
                # gang members and pods bound by a peer (which never pass
                # through _bound's pop)
                if (obj.get("status") or {}).get("phase") in ("Succeeded", "Failed"):
                    self._untrack(obj)  # terminal pods free their slot
                else:
                    self._track(obj, node)
                if gang is not None:
                    gang.observe(ev.type, obj)  # membership, like the cache
                continue
            if (obj.get("metadata") or {}).get("deletionTimestamp"):
                continue
            self._note_pending(obj)
            if gang is not None:
                # membership is cache maintenance (standbys stay current);
                # the bind attempt below is leader-gated like a pick
                gang.observe(ev.type, obj, ctx=ctx)
            if active is None:
                # once a run, before its first pick or gang attempt
                active = self._active is None or bool(self._active())
            if not active:
                continue  # standby/deposed: track caches, never bind
            if gang is not None:
                gang.try_schedule(gang_key(obj))
                continue
            pick = self._pick(obj, ctx)
            if pick is not None:
                picks.append(pick)
        if picks:
            self._write_binds(picks)

    def _retry_pending(self) -> None:
        if self._active is not None and not self._active():
            return
        try:
            pods, _ = self.store.list("Pod", field_selector="spec.nodeName=")
        except Exception:  # noqa: BLE001 — apiserver outage; informer retries
            return
        for lo in range(0, len(pods), RUN_MAX):
            picks: List[_Pick] = []
            for pod in pods[lo : lo + RUN_MAX]:
                if (pod.get("metadata") or {}).get("deletionTimestamp"):
                    continue
                self._note_pending(pod)
                if self.gang is not None and GangEngine.is_gang_pod(pod):
                    # heal membership the watch may have missed, then let
                    # the engine's own retry pass below attempt the gang
                    self.gang.observe("ADDED", pod)
                    continue
                pick = self._pick(pod)
                if pick is not None:
                    picks.append(pick)
            if picks:
                self._write_binds(picks)
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
