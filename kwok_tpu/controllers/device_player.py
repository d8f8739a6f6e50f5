"""DeviceStagePlayer: the TPU execution backend behind the controller
seam.

Where ``StagePlayer`` (host backend) runs the reference's per-object
loop, this player keeps every object as a row of the device-resident
SoA and replaces informer-dedup + Lifecycle.Match + WeightDelayingQueue
+ N play workers with ONE batched tick kernel (SURVEY.md:202-218
§2.9, §7.3):

    watch deltas -> admit/refresh rows (host, batched between ticks)
    -> tick() on device (match + weighted choice + timers + effects)
    -> dirty rows drain -> store PATCH/DELETE/events (host)
    -> store result refreshes the row (features stay parity-exact)

Only dirty rows cross the host<->device boundary. Stage sets the AOT
compiler cannot lower raise StageCompileError at construction; the
facade falls back to the host backend for that kind.
"""

from __future__ import annotations

import sys
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from kwok_tpu.api.types import Stage
from kwok_tpu.cluster.informer import Informer, InformerEvent, WatchOptions
from kwok_tpu.cluster.store import DELETED, EventRecorder, NotFound, ResourceStore
from kwok_tpu.engine.render_plan import RenderPlan, compile_plan
from kwok_tpu.engine.render_plan import build as _plan_build
from kwok_tpu.engine.simulator import (
    COLLECT_TICKS,
    DEFAULT_EPOCH,
    DeviceSimulator,
    Transition,
)
from kwok_tpu.native.fastdrain import load as _load_fastdrain
from kwok_tpu.utils import telemetry as _telemetry
from kwok_tpu.utils.clock import Clock, RealClock
from kwok_tpu.utils.expression import parse_rfc3339
from kwok_tpu.utils.log import get_logger
from kwok_tpu.utils.patch import apply_merge_patch as _merge_patch
from kwok_tpu.utils.patch import apply_patch as _apply_patch
from kwok_tpu.utils.patch import is_noop_patch
from kwok_tpu.utils.queue import Queue

# drain accelerator (native/kwok_fastdrain.c); None -> pure Python
_FAST = _load_fastdrain()

_LOG = get_logger("device-player")

#: every stage of the tick thread runs inside one ``_stage(kind, name)``
#: (utils/telemetry.stage): a TraceAnnotation ``kwok/<kind>/<name>`` on
#: the profiler's clock and ``kwok_tick_stage_seconds{kind,stage}``.
#: Outermost: ingest, device_tick, host_drain, post_tick, pace_wait;
#: fired_scan (the pass over the dense fired-stage output that finds the
#: rows that fired, one a drain), host_build and store_bulk (the status
#: batch), delete_commit (the delete batch), slow_build and slow_commit
#: (``_drain_slow``: the per-row Python around its bulk, and the bulk)
#: and event_post (the drain's Events handed to the recorder in one
#: request, last) nest in host_drain, which reports self time; compile
#: (engine/simulator.py) overlays the stage it stalls.
_stage = _telemetry.stage

#: rows in one ``store.apply_status_batch`` or ``apply_delete_batch``
#: call (2,048).  A commit hands every watcher its events in one push,
#: and a watcher more than ``WATCH_HIGH_WATER`` events behind is
#: evicted: at 8,192 rows a request one chip run of two never saw its
#: standing pods Running (PERF.md §6, PR 27); a quarter leaves a
#: consumer three bursts of room.  In process the row dicts also stay in
#: the CPU cache across build, commit and confirm at this size
_COMMIT_ROWS = ResourceStore.WATCH_HIGH_WATER // 4

#: RenderPlans kept, by (stage, signature); a plan takes ~5 KB and ~0.5 ms
#: to compile.  A node's name and a pod's labels are in the signature, so
#: 1,000 nodes under ``pod-general`` + ``pod-chaos`` have some 13,000
#: pairs in use, and a cache that holds fewer than are in use compiles
#: plans again in every drain (PERF.md §6, PR 32)
_PLAN_CACHE = 32768

#: one observation a commit request, valued with the rows it committed:
#: ``path`` is ``batch`` (``apply_status_batch``), ``delete``
#: (``apply_delete_batch``) or ``slow`` (``_drain_slow``'s bulk).
#: ``_sum`` over ``kwok_stage_transitions_total`` is the share of played
#: rows each path carried, ``_sum`` over ``_count`` the rows a request
_H_COMMIT_ROWS = _telemetry.histogram(
    "kwok_status_commit_rows",
    help="rows committed by one status commit request of a device player",
    buckets=(1, 4, 16, 64, 256, 1024, 2048, 4096, 8192, 16384, 65536),
    labelnames=("kind", "path"),
)

#: one observation a drain (``_drain_stages``) and stage, valued with the
#: rows of that stage the drain played, by the way they reached the
#: store (``path`` as in ``kwok_status_commit_rows``; a row that had
#: nothing to send counts where it was routed).  ``_sum`` adds up to
#: ``kwok_stage_transitions_total``: the mix a window played
_H_FIRED_ROWS = _telemetry.histogram(
    "kwok_stage_fired_rows",
    help="rows one drain of a device player played, by stage and commit path",
    buckets=(1, 4, 16, 64, 256, 1024, 2048, 4096, 8192, 16384, 65536),
    labelnames=("kind", "stage", "path"),
)

#: per object whose stage-driven delete the store acknowledged as gone:
#: seconds from its ``metadata.deletionTimestamp`` (the store stamps whole
#: seconds, rounded down: a reading is up to a second long, half of one
#: in the mean) to that acknowledgement, on the player's clock
_H_DELETE_TO_GONE = _telemetry.histogram(
    "kwok_delete_to_gone_seconds",
    help="deletionTimestamp of an object to the acknowledgement that its "
    "stage-driven delete left nothing behind",
    buckets=(0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 240.0),
    labelnames=("kind",),
)

#: how far past its schedule an iteration of the paced tick loop began
#: (0 in saturation mode): one observation an iteration, so a window
#: reads its own mean, where a ring of the last samples read the daemon's
_H_TICK_LAG = _telemetry.histogram(
    "kwok_tick_lag_seconds",
    help="device tick loop: seconds an iteration began past its schedule",
    buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0),
    labelnames=("kind",),
)

#: virtual seconds the dispatches covered (sub-ticks times ``tick_ms``):
#: over a stretch of wall time, 1.0 a second while the loop keeps its pace
_VIRTUAL_PLAYED = _telemetry.counter(
    "kwok_virtual_seconds_played_total",
    help="virtual seconds covered by the device dispatches of a tick loop",
    labelnames=("kind",),
)

#: live players for the interpreter-exit safety net: a daemon tick
#: thread killed mid-XLA-dispatch at teardown aborts the whole process
#: ("terminate called ... FATAL: exception not rethrown", rc=134), so
#: an atexit hook aborts every live drain and joins the threads BEFORE
#: teardown — even when the embedding program never called stop()
#: (e.g. it crashed on an assert).  WeakSet: players die with their
#: owners; the hook must not keep them alive.
import atexit as _atexit
import weakref as _weakref

_LIVE_PLAYERS: "_weakref.WeakSet[DeviceStagePlayer]" = _weakref.WeakSet()
_EXIT_HOOKED = False


def _stop_all_players_at_exit() -> None:
    players = list(_LIVE_PLAYERS)
    for p in players:
        try:
            p._done.set()
        except Exception:  # noqa: BLE001 — best effort at teardown
            pass
    for p in players:
        for t in p._threads:
            try:
                # the drain is abort-aware per chunk, so this converges
                # quickly; the bound covers a hung device transfer
                t.join(timeout=60.0)
            except Exception:  # noqa: BLE001
                pass


class DeviceStagePlayer:
    """Vectorized stage player for one resource kind."""

    def __init__(
        self,
        store: ResourceStore,
        kind: str,
        stages: List[Stage],
        capacity: int = 1024,
        tick_ms: int = 100,
        clock: Optional[Clock] = None,
        recorder: Optional[EventRecorder] = None,
        read_only: Optional[Callable[[dict], bool]] = None,
        predicate: Optional[Callable[[dict], bool]] = None,
        funcs_for: Optional[Callable[[dict], Dict[str, Callable]]] = None,
        on_delete: Optional[Callable[[dict], None]] = None,
        seed: int = 0,
        mesh=None,
    ):
        self.store = store
        self.kind = kind
        self.clock = clock or RealClock()
        self.recorder = recorder
        self.read_only = read_only
        self._predicate = predicate
        self.funcs_for = funcs_for or (lambda obj: {})
        self.on_delete = on_delete
        self.tick_ms = tick_ms
        self.sim = DeviceSimulator(
            stages, capacity=capacity, seed=seed, mesh=mesh, kind=kind
        )
        self._informer = Informer(store, kind)
        self.events: Queue = Queue()
        #: (namespace, name) -> row
        self._rows: Dict[Tuple[str, str], int] = {}
        #: row-indexed resourceVersion we last wrote (echo
        #: suppression); grown alongside sim.capacity — at 1M rows an
        #: indexed load beats a big-dict probe on every hot path
        self._written_rv: List[Optional[str]] = [None] * capacity
        #: device dispatches made so far, and, row-indexed, how many had
        #: been made when the host last put an object's features into
        #: the row (admitted it, or extracted them again because the
        #: object was not what the device took it for): the output of
        #: dispatch ``d`` says nothing of a row stamped ``d`` or later
        #: (see _drain_stages_inner)
        self._dispatches = 0
        self._row_since = np.zeros(capacity, np.int64)
        self._mut = threading.Lock()
        self._paced = True
        self._done = threading.Event()
        #: tick-pacing wake signal: pinged when a virtual clock
        #: advances, so the paced loop never blocks on wall time
        self._tick_wake = threading.Event()
        self.clock.subscribe(self._tick_wake)
        self._threads: List[threading.Thread] = []
        self.transitions = 0
        self.patches = 0
        #: exceptions the tick loop, the lease hook and the drain caught
        #: and survived.  The loop stays alive by design; a tick that
        #: cannot compile or run on the device would otherwise spin
        #: forever with zero transitions and nothing to show for it
        #: (exported as kwok_tick_errors_total)
        self.swallowed_errors = 0
        #: cumulative step() time split (seconds): device tick kernel,
        #: store round-trips (bulk), and host drain (materialize/render
        #: + any sequential-path store calls) — the e2e bench reads
        #: these to name the pipeline bottleneck (VERDICT r01 #2).  Fed
        #: from the stage spans' own ``elapsed``: one clock per stage
        self.t_device = 0.0
        self.t_store = 0.0
        self.t_host = 0.0
        #: subset of t_host spent in the per-row patch build loop
        #: (native fast_group) — reported separately by the bench so
        #: the breakdown names the real bottleneck
        self.t_build = 0.0
        #: the Ready wave of a Node player (None on every other kind):
        #: row-indexed, whether a status commit the store acknowledged
        #: has held the row since it was admitted (cleared at release);
        #: the instant of the first admission; seconds from it to the
        #: newest commit that held a row never committed before
        #: (``kwok_node_wave_wall_seconds``; see _note_committed)
        self._committed: Optional[np.ndarray] = (
            np.zeros(capacity, np.bool_) if kind == "Node" else None
        )
        self._wave_t0: Optional[float] = None
        self.wave_wall_s: Optional[float] = 0.0 if kind == "Node" else None
        # which object state the stage templates read: gates whether a
        # multi-op transition may render every patch from one base (see
        # _collect_ops)
        rp = set(self.sim.cset._read_paths)
        self._reads_finalizers = ("metadata", "finalizers") in rp
        self._reads_state = bool(rp)
        #: row -> stage_idx -> rendered patches with a Now sentinel.
        #: Sound only when templates read no mutable object state
        #: (self._reads_state False — the compiler's own read-path
        #: analysis): then a row's render for a stage depends only on
        #: its admission-time identity, its row-stable funcs (pod/node
        #: IPs), and Now, which is substituted per use.  Invalidated
        #: whenever the row's identity changes (full refresh, release,
        #: re-admit).
        self._render_cache: Dict[int, Dict[int, List]] = {}
        #: (stage_idx, sig) -> RenderPlan | None — the cross-row fast
        #: drain (engine/render_plan.py).  Only sound when the stage
        #: set's templates have no tracked read paths (identity reads
        #: are sentinel-substituted; spec/labels/annotations are part of
        #: the sig key).
        self._plans: "OrderedDict[Tuple[int, int], Optional[RenderPlan]]" = (
            OrderedDict()
        )
        #: (stage_idx, finalizers, terminating) -> whether such a row of
        #: a deleting stage goes by the delete batch (_delete_is_one_event)
        self._delete_verdicts: Dict[tuple, bool] = {}
        #: (stage_idx, path) -> rows played since the drain began
        #: (tick thread only; observed and emptied by _drain_stages_inner)
        self._fired: Dict[Tuple[int, str], int] = {}
        #: the Events of the rows the drain has played so far, as
        #: ``EventRecorder.record`` takes them (tick thread only)
        self._pending_events: List[tuple] = []
        #: the stage by row of the sub-tick being drained, for the
        #: commits' accounting (set by _drain_tick)
        self._drain_st: Optional[np.ndarray] = None
        self._fast_ok = not self.sim.cset._read_paths
        # in-process stores hand back stored instances from bulk
        # (immutable by contract): the slow-path drain adopts them into
        # row mirrors, so skipping the deep copy of every result is the
        # create wave's single biggest win
        self._bulk_no_copy = False
        if hasattr(store, "bulk"):
            import inspect

            try:
                self._bulk_no_copy = (
                    "copy_results" in inspect.signature(store.bulk).parameters
                )
            except (TypeError, ValueError):
                self._bulk_no_copy = False
        #: row-indexed {stage_idx -> resolved sentinel values}
        #: (identity + env funcs; both row-stable) — dropped with the
        #: render cache on any identity change
        self._vals_cache: List[Optional[Dict]] = [None] * capacity
        #: in-flight macro-tick (stages device array, t0_ms, dt) for
        #: the overlapped step_pipelined path
        self._inflight = None
        # virtual-time anchor: device ms 0 == clock.now() at start
        self._t0: Optional[float] = None
        self.cache = None
        #: optional per-tick hook fed the post-tick virtual now (ms);
        #: carries the device lease lane (controllers/device_lease.py)
        self.post_tick: Optional[Callable[[int], None]] = None

    # ------------------------------------------------------------------- wiring

    def start(self, paced: bool = True) -> None:
        """Wire the informer and start the tick loop.

        ``paced=True`` (production): one tick per ``tick_ms`` of wall
        clock; when the loop falls behind cadence it catches up with
        ONE overlapped macro-tick (step_pipelined) covering the missed
        ticks instead of spiraling.  ``paced=False`` (bench / replay):
        saturate — overlapped macro-ticks back to back, measuring
        sustained capacity rather than cadence.  Both modes run the
        same drain pipeline, so what the bench measures is what the
        daemon runs (VERDICT r03 next-#2/#7)."""
        self._paced = paced
        self._t0 = self.clock.now()
        self.sim.epoch = _epoch_from(self._t0)
        if isinstance(self.store, ResourceStore):
            # in-process: no mirror to maintain — reads go straight to
            # the store, and the reflector runs cache-less (its event
            # stream alone feeds the SoA)
            from kwok_tpu.cluster.informer import StoreBackedGetter

            self.cache = StoreBackedGetter(self.store, self.kind)
            self._informer.watch(
                WatchOptions(predicate=self._predicate),
                self.events,
                done=self._done,
            )
        else:
            self.cache = self._informer.watch_with_cache(
                WatchOptions(predicate=self._predicate), self.events, done=self._done
            )
        t = threading.Thread(target=self._tick_loop, daemon=True)
        t.start()
        self._threads.append(t)
        global _EXIT_HOOKED
        _LIVE_PLAYERS.add(self)
        if not _EXIT_HOOKED:
            _EXIT_HOOKED = True
            _atexit.register(_stop_all_players_at_exit)

    def stop(self) -> None:
        """Stop the tick loop and join it — unconditionally.

        The drain is abort-aware at chunk granularity (_drain_stages /
        _drain_tick / _drain_slow all check ``_done``), so the thread
        converges within one chunk plus one device transfer; the join
        bound only covers a hung transfer.  A daemon
        thread left alive into interpreter teardown dies mid-XLA-
        dispatch and aborts the whole process (rc=134, VERDICT r04
        weak-#2) — the atexit hook re-joins as a final net for
        embedders that never call stop()."""
        self._done.set()
        for t in self._threads:
            t.join(timeout=120.0)
        if any(t.is_alive() for t in self._threads):
            # hung device transfer: leave the flush to the tick thread
            # (racing it on _inflight would apply sub-ticks out of
            # order); the atexit hook will join once more at exit
            print(
                f"kwok: {self.kind} tick thread did not stop within "
                "120s (hung device transfer?)",
                file=sys.stderr,
            )
            return
        # covers callers driving step_pipelined by hand around a stop
        try:
            self.flush_pipeline()
        except Exception as exc:  # noqa: BLE001 — best effort at shutdown
            _LOG.debug("final pipeline flush failed at shutdown", error=exc)

    def _grow_row_arrays(self) -> None:
        """Keep the row-indexed caches sized to the SoA capacity (the
        sim grows by doubling on admit)."""
        cap = self.sim.capacity
        if len(self._written_rv) < cap:
            self._written_rv.extend([None] * (cap - len(self._written_rv)))
        if len(self._vals_cache) < cap:
            self._vals_cache.extend([None] * (cap - len(self._vals_cache)))
        if len(self._row_since) < cap:
            self._row_since = np.concatenate(
                [self._row_since, np.zeros(cap - len(self._row_since), np.int64)]
            )
        if self._committed is not None and len(self._committed) < cap:
            self._committed = np.concatenate(
                [self._committed, np.zeros(cap - len(self._committed), np.bool_)]
            )

    def _rematch_row(self, row: int) -> None:
        """Extract the row's features from its mirror again and have
        the device match it anew: what a dispatch already made fired
        for the row, it fired for what the row held before."""
        self.sim.refresh_row(row)
        self._row_since[row] = self._dispatches

    # ------------------------------------------------------------ event ingest

    def _key(self, obj: dict) -> Tuple[str, str]:
        meta = obj.get("metadata") or {}
        return (meta.get("namespace") or "", meta.get("name") or "")

    def _drain_events(self) -> None:
        """Apply queued watch deltas to the SoA (batched: one lock hold
        for the whole backlog, at most one device re-upload per tick).
        Self-echoes — MODIFIED events at or below the row's last written
        resourceVersion, the per-write common case — are dropped in one
        native pass when the accelerator is present."""
        evs = self.events.drain()
        if not evs:
            return
        with self._mut:
            self._grow_row_arrays()
            if _FAST is not None:
                evs = _FAST.filter_stale(evs, self._rows, self._written_rv)
            for ev in evs:
                self._apply_event_locked(ev)
            if self._wave_t0 is None and self._committed is not None and self._rows:
                self._wave_t0 = time.perf_counter()

    def _apply_event_locked(self, ev: InformerEvent) -> None:
        obj = ev.object
        meta = obj.get("metadata") or {}
        key = (meta.get("namespace") or "", meta.get("name") or "")
        row = self._rows.get(key)
        if ev.type == DELETED:
            self._release_locked(key)
            if self.on_delete is not None:
                self.on_delete(obj)
            return
        if row is not None and _rv_stale(
            meta.get("resourceVersion"),
            self._written_rv[row] if row < len(self._written_rv) else None,
        ):
            # echo of one of our own patches (possibly an intermediate
            # state of a multi-patch transition — finalizer patch then
            # status patch); the row already reflects the final write.
            # Checked FIRST: self-echo suppression is the per-write
            # common case and must not pay the read_only predicate.
            return
        if self.read_only is not None and self.read_only(obj):
            return
        if row is None:
            row = self.sim.admit(obj)
            self._rows[key] = row
            self._grow_row_arrays()
            self._row_since[row] = self._dispatches
            self._drop_render_cache(row)
        else:
            old = self.sim.objects[row]
            self.sim.objects[row] = obj
            self._rematch_row(row)
            if not self._render_identity_same(old, obj):
                self._drop_render_cache(row)

    # --------------------------------------------------------------- tick loop

    def sync_node(self, node_name: str) -> None:
        """Re-feed this kind's objects tied to a node that just became
        owned (the device analog of the host sync_node / manage_node
        catch-up, reference controller.go:559-573): events dropped while
        read-only or unmanaged are replayed as SYNC."""
        if self.kind == "Node":
            opt = WatchOptions(
                field_selector={"metadata.name": node_name}, predicate=self._predicate
            )
        else:
            opt = WatchOptions(
                field_selector={"spec.nodeName": node_name}, predicate=self._predicate
            )
        self._informer.sync(opt, self.events)

    #: catch-up / saturation macro-tick width (sub-ticks per device
    #: dispatch); bounds how much virtual time one dispatch covers, and
    #: every count up to it runs one program
    macro_ticks = COLLECT_TICKS

    def _tick_loop(self) -> None:
        dt_s = self.tick_ms / 1000.0
        next_tick = self.clock.now()
        while not self._done.is_set():
            try:
                with _stage(self.kind, "ingest"):
                    self._drain_events()
                if not self._paced:
                    # saturation mode: overlapped macro-ticks back to
                    # back — device computes batch N+1 while the host
                    # drains batch N
                    self.step_pipelined(self.tick_ms, self.macro_ticks)
                    _H_TICK_LAG.observe(0.0, self.kind)
                    continue
                behind = self.clock.now() - next_tick
                # one lag sample per paced iteration: how far this
                # tick started past its schedule
                _H_TICK_LAG.observe(behind, self.kind)
                if behind > dt_s:
                    # behind cadence: cover the missed ticks with ONE
                    # overlapped macro-tick instead of spiraling (the
                    # next paced step flushes the in-flight batch)
                    k = min(int(behind / dt_s) + 1, self.macro_ticks)
                    self.step_pipelined(self.tick_ms, k)
                    next_tick += k * dt_s
                    if behind > 8 * self.macro_ticks * dt_s:
                        # hopelessly behind (sustained overload): drop
                        # the backlog instead of chasing it forever —
                        # the old loop's don't-spiral reset
                        next_tick = self.clock.now()
                else:
                    self.step()
                    next_tick += dt_s
            except Exception:  # noqa: BLE001 — one bad batch must not
                # kill the simulation for this kind
                self._swallow()
                next_tick += dt_s
            sleep = next_tick - self.clock.now()
            if sleep > 0:
                # pace on the injected clock (never bare time.sleep) so
                # a virtual clock can fast-forward the tick cadence;
                # the wait is bounded by dt_s, which also bounds stop()
                # latency exactly like the old bare sleep did
                with _stage(self.kind, "pace_wait"):
                    self._tick_wake.clear()
                    self.clock.wait_signal(self._tick_wake, min(sleep, dt_s))
        # drain the last in-flight macro-tick so stop() never strands
        # fired rows
        try:
            self.flush_pipeline()
        except Exception:  # noqa: BLE001 — best effort at shutdown
            self._swallow()

    def _swallow(self) -> None:
        """Print the exception being handled and count it: the loop
        goes on, the daemon's /metrics says that it had to."""
        import traceback

        traceback.print_exc()
        self.swallowed_errors += 1

    def step(self, dt_ms: Optional[int] = None) -> int:
        """One device tick + host drain; returns the fired-row count."""
        return self.step_batch(dt_ms, 1)

    def step_batch(self, dt_ms: Optional[int] = None, n_ticks: int = 1) -> int:
        """``n_ticks`` device ticks in one dispatch (macro-tick), then a
        per-sub-tick host drain of dirty rows.

        Drain routing per fired row:

        - **fast path** — rows whose stage compiles to a RenderPlan
          (merge patches on the status subresource, no finalizers, no
          delete, no recorder-bound event): the patch is rebuilt from
          the cross-row plan (sentinel substitution, no gotpl render)
          and the tick's rows commit through ``store.apply_status_batch``,
          ``_COMMIT_ROWS`` a call, in this process or over the wire.
        - **delete path** — rows of a deleting stage whose whole outcome
          is one DELETED event (``_delete_is_one_event``, read off the
          row's mirror) commit as ``(namespace, name, resourceVersion)``
          through ``store.apply_delete_batch``, ``_COMMIT_ROWS`` a call.
        - **slow path** — everything else keeps the per-row semantics:
          grouped ops through ``store.bulk``, sequential fallback for
          order-dependent shapes.  A row either batch refuses (its
          object was written by somebody else since the mirror read it)
          goes this way too, as a merge patch or as a finalizer patch
          and a delete."""
        from kwok_tpu.utils.trace import get_tracer

        tracer = get_tracer()
        if not tracer.enabled:
            return self._step_batch_inner(dt_ms, n_ticks)
        # one span per firing macro-tick (empty ticks are never
        # finished, so they are not exported); store round-trips inside
        # inherit it via the thread-local stack.  push/pop balance is
        # guarded by the finally — an unbalanced stack would mis-parent
        # every later span on this thread.
        span = tracer.span(f"tick.{self.kind}")
        tok = tracer._push(span)
        fired = 0
        try:
            fired = self._step_batch_inner(dt_ms, n_ticks)
            return fired
        except Exception as exc:
            span.error(str(exc))
            span.end()
            span = None
            raise
        finally:
            tracer._pop(tok)
            if span is not None and fired:
                span.set("kind", self.kind)
                span.set("fired", fired)
                span.end()

    def _step_batch_inner(self, dt_ms: Optional[int], n_ticks: int) -> int:
        # a pending pipelined batch must drain FIRST or transitions
        # apply out of order when callers mix the two step flavors
        self.flush_pipeline()
        base = (self.t_device, self.t_store, self.t_host, self.t_build)
        dt = dt_ms if dt_ms is not None else self.tick_ms
        with _stage(self.kind, "device_tick") as sp:
            stages_np, t0_ms = self.sim.tick_many(dt, n_ticks)
        self._dispatches += 1
        self._note_dispatch(dt, n_ticks)
        self.t_device += sp.elapsed
        fired_total = self._drain_stages(stages_np, t0_ms, dt, self._dispatches)
        self._run_post_tick()
        self._observe_tick(base, fired_total)
        return fired_total

    def _note_dispatch(self, dt_ms: int, n_ticks: int) -> None:
        """A ``device_tick`` stage that dispatched ``n_ticks`` sub-ticks
        has closed: the virtual time it covers and, the first time in
        the process, the ``first_tick`` milestone of this kind (its
        compile is inside the stage)."""
        _VIRTUAL_PLAYED.inc(n_ticks * dt_ms / 1000.0, self.kind)
        _telemetry.milestones().mark("first_tick", kind=self.kind)

    def _observe_tick(
        self, base: Tuple[float, float, float, float], fired: int
    ) -> None:
        """A flight-recorder breakdown entry for a firing macro-tick
        (the stage spans observe ``kwok_tick_stage_seconds`` themselves).
        Observation-only: nothing here feeds back into pacing or drain
        routing."""
        if not fired or not _telemetry.enabled():
            return
        d_build = self.t_build - base[3]
        _telemetry.flight_recorder().record_tick(
            self.kind,
            fired,
            {
                "device_tick_s": self.t_device - base[0],
                # host_drain excludes the patch-build subset, matching
                # the bench's breakdown_s split (host_drain_s = t_host - build)
                "host_drain_s": max(self.t_host - base[2] - d_build, 0.0),
                "host_build_s": d_build,
                "store_bulk_s": self.t_store - base[1],
            },
        )

    def _run_post_tick(self) -> None:
        if self.post_tick is None:
            return
        # wall-anchored ms, not the sim's virtual clock: lease renewal
        # is a real-time contract (expiry is judged on wall time by
        # peers), so a tick loop running behind schedule must not slow
        # the heartbeat cadence
        if self._t0 is not None:
            lane_now = int((self.clock.now() - self._t0) * 1000)
        else:
            lane_now = self.sim.now_ms
        try:
            with _stage(self.kind, "post_tick"):
                self.post_tick(lane_now)
        except Exception:  # noqa: BLE001 — lane trouble must not
            # stall the stage loop
            self._swallow()

    def _drain_stages(
        self, stages_np: np.ndarray, t0_ms: int, dt: int, dispatch: int
    ) -> int:
        """Drain the output of the ``dispatch``-th device dispatch."""
        store_before = self.t_store
        with _stage(self.kind, "host_drain") as sp:
            fired_total = self._drain_stages_inner(stages_np, t0_ms, dt, dispatch)
        # t_host: the drain less its store round-trips (build included)
        self.t_host += sp.elapsed - (self.t_store - store_before)
        return fired_total

    def _drain_stages_inner(
        self, stages_np: np.ndarray, t0_ms: int, dt: int, dispatch: int
    ) -> int:
        fired_total = 0
        t_start = time.perf_counter()
        # shared grace anchor for the abort checks at every granularity
        # (sub-tick here, group/chunk in _drain_tick, rows in
        # _drain_slow): a stop() during a SMALL flush must still
        # complete it (stop's contract: the in-flight batch is not
        # stranded), while a huge drain aborts within ~a second
        self._drain_t0 = t_start
        with _stage(self.kind, "fired_scan"):
            # every sub-tick's fired rows in one pass over the dense
            # [K, N] output: row-major, so by sub-tick, rows ascending
            ks, fired_rows = np.nonzero(stages_np >= 0)
            bounds = np.searchsorted(ks, np.arange(stages_np.shape[0] + 1))
        for k in range(stages_np.shape[0]):
            if self._done.is_set() and time.perf_counter() - t_start > 1.0:
                # shutdown mid-macro-tick: small flushes complete, but a
                # huge drain stops between sub-ticks (and, inside one,
                # between chunks — see _drain_tick) so stop()'s join
                # converges (the abandoned sub-ticks re-fire after a
                # restart — rows re-admit from the store like any
                # resume)
                break
            st = stages_np[k]
            rows = fired_rows[bounds[k] : bounds[k + 1]]
            if rows.size:
                # a row the host filled after this output was dispatched
                # fired for what it held before: an overlapped macro-tick
                # is drained one ingest late.  A pod-delete that fired
                # for a pod by now gone must not take the new pod admitted
                # into its row (which the delete batch, given the new
                # pod's own name and resourceVersion, would do), and a
                # stage that fired for an object somebody has changed
                # since must not be played on the changed one: the
                # device matches the row again from what it holds now
                rows = rows[self._row_since[rows] < dispatch]
            if rows.size:
                fired_total += int(rows.size)
                try:
                    self._drain_tick(rows, st, t0_ms + (k + 1) * dt)
                except Exception:  # noqa: BLE001 — one bad sub-tick must
                    # not kill the loop for this kind
                    self._swallow()
        if self._pending_events:
            # the Events of every row the drain played, after the rows, in
            # one request of their own: an Event the store refuses is the
            # recorder's to drop and count
            events, self._pending_events = self._pending_events, []
            with _stage(self.kind, "event_post") as sp:
                try:
                    self.recorder.record(events)
                except Exception:  # noqa: BLE001 — an Event never fails a row
                    self._swallow()
            self.t_store += sp.elapsed
        if self._fired:
            compiled = self.sim.cset.compiled
            for (s_idx, path), n in self._fired.items():
                _H_FIRED_ROWS.observe(n, self.kind, compiled[s_idx].name, path)
            self._fired.clear()
        return fired_total

    def _note_fired(self, s_idx: int, path: str, n: int = 1) -> None:
        if n:
            key = (s_idx, path)
            self._fired[key] = self._fired.get(key, 0) + n

    def step_pipelined(self, dt_ms: Optional[int] = None, n_ticks: int = 1) -> int:
        """Overlapped macro-tick: dispatch the NEXT n_ticks on device,
        then drain the PREVIOUS dispatch's output — device compute and
        host drain run concurrently (the device queues the new scan
        behind the in-flight one; JAX dispatch is async).

        Host mutations from the drain (scatters, releases) therefore
        reach the device one macro-tick late — the same eventual
        semantics the reference has between its informer and play
        workers.  Rows released mid-flight may fire once more; the
        drain drops them (object already None, or another object
        admitted into the row since the dispatch).  Call
        :meth:`flush_pipeline` to drain the final in-flight batch.

        Runs the post_tick hook (lease lanes) like step_batch does, so
        switching a run loop between the two flavors never silently
        stops heartbeats."""
        dt = dt_ms if dt_ms is not None else self.tick_ms
        if self.sim.mesh is not None or self.sim.num_stages_over_int8():
            # step_batch flushes any in-flight batch first (ordering)
            return self.step_batch(dt, n_ticks)
        import jax

        base = (self.t_device, self.t_store, self.t_host, self.t_build)
        prev = self._inflight
        with _stage(self.kind, "device_tick") as sp:
            stages_dev, t0_ms = self.sim.tick_many_async(dt, n_ticks)
            self._dispatches += 1
            self._inflight = (stages_dev, n_ticks, t0_ms, dt, self._dispatches)
            # start the device->host copy NOW so it overlaps the drain
            # below: the next call's device_get finds the bytes on the host
            # instead of paying a blocking read
            stages_dev.copy_to_host_async()
            if prev is not None:
                p_stages, p_n, p_t0, p_dt, p_dispatch = prev
                stages_np = np.asarray(jax.device_get(p_stages))[:p_n]
        self._note_dispatch(dt, n_ticks)
        self.t_device += sp.elapsed
        fired = 0
        if prev is not None:
            fired = self._drain_stages(stages_np, p_t0, p_dt, p_dispatch)
        self._run_post_tick()
        self._observe_tick(base, fired)
        return fired

    def flush_pipeline(self) -> int:
        """Drain the last in-flight macro-tick (pipelined mode)."""
        prev, self._inflight = self._inflight, None
        if prev is None:
            return 0
        import jax

        stages_dev, n_ticks, t0_ms, dt, dispatch = prev
        with _stage(self.kind, "device_tick") as sp:
            stages_np = np.asarray(jax.device_get(stages_dev))[:n_ticks]
        self.t_device += sp.elapsed
        return self._drain_stages(stages_np, t0_ms, dt, dispatch)

    _PLAN_MISS = object()

    def _plan_for(self, s_idx: int, sig: int, obj: dict) -> Optional[RenderPlan]:
        key = (s_idx, sig)
        plan = self._plans.get(key, self._PLAN_MISS)
        if plan is not self._PLAN_MISS:
            self._plans.move_to_end(key)
        else:
            if len(self._plans) >= _PLAN_CACHE:
                self._plans.popitem(last=False)  # the one longest unused
            try:
                plan = compile_plan(
                    self.sim.cset.lifecycle,
                    self.sim.cset.compiled[s_idx],
                    obj,
                    list(self.funcs_for(obj)),
                )
            except Exception:  # noqa: BLE001 — plan trouble = slow path
                plan = None
            self._plans[key] = plan
        return plan

    def _past_abort_grace(self) -> bool:
        return time.perf_counter() - getattr(self, "_drain_t0", 0.0) > 1.0

    def _drain_tick(self, rows: np.ndarray, st: np.ndarray, t_ms: int) -> None:
        """Drain one sub-tick's fired rows: fast rows through the
        columnar status batch, deletes whose whole outcome is one
        DELETED event through the delete batch, the rest through the
        legacy group path.
        Rows are grouped by (stage, sig) so each group resolves its
        RenderPlan and tick binding once and the inner loop is pure
        per-row substitution.  The same drain runs whether the store is
        in this process or behind the apiserver: rows are built, then
        committed and confirmed in requests of ``_COMMIT_ROWS``."""
        cset = self.sim.cset
        stage_delete = cset.stage_delete
        sigs = self.sim.sig
        objects = self.sim.objects
        slow: List[Transition] = []
        fast_rows: List[int] = []
        #: (namespace, name, new status, the mirror's resourceVersion)
        fast_items: List[tuple] = []
        gone_rows: List[int] = []
        #: (namespace, name, the mirror's resourceVersion)
        gone_items: List[tuple] = []
        now_s: Optional[str] = None
        # the native per-row loops; without them the Python loop below
        # builds the same items
        use_c = _FAST is not None
        self._grow_row_arrays()
        self._drain_st = st
        srow = st[rows]
        sigrow = sigs[rows]
        order = np.lexsort((sigrow, srow))
        rows_l = rows[order].tolist()
        srow_l = srow[order].tolist()
        sig_l = sigrow[order].tolist()
        n = len(rows_l)
        vals_cache = self._vals_cache
        chunk = _COMMIT_ROWS

        def _flush_locked() -> None:
            nonlocal fast_rows, fast_items
            if not fast_items:
                return
            batch_rows, batch_items = fast_rows, fast_items
            fast_rows, fast_items = [], []
            for row in self._commit_batch_locked(batch_rows, batch_items):
                slow.append(self._make_transition(row, int(st[row]), t_ms))

        def _flush_gone_locked() -> None:
            nonlocal gone_rows, gone_items
            if not gone_items:
                return
            batch_rows, batch_items = gone_rows, gone_items
            gone_rows, gone_items = [], []
            for row in self._commit_delete_locked(batch_rows, batch_items):
                slow.append(self._make_transition(row, int(st[row]), t_ms))

        with self._mut:
            i = 0
            while i < n:
                if self._done.is_set() and self._past_abort_grace():
                    # shutdown mid-sub-tick: stop between (stage, sig)
                    # groups; committed chunks stand, the rest re-fires
                    # after a restart
                    break
                s_idx = srow_l[i]
                sig = sig_l[i]
                j = i
                while j < n and srow_l[j] == s_idx and sig_l[j] == sig:
                    j += 1
                group = rows_l[i:j]
                i = j
                rep = None
                for row in group:
                    rep = objects[row]
                    if rep is not None:
                        break
                if rep is None:
                    continue
                if stage_delete[s_idx]:
                    for row in group:
                        obj = objects[row]
                        if obj is None:
                            continue
                        meta = obj.get("metadata") or {}
                        if self._delete_is_one_event(s_idx, meta):
                            gone_rows.append(row)
                            gone_items.append(
                                (
                                    meta.get("namespace"),
                                    meta.get("name") or "",
                                    meta["resourceVersion"],
                                )
                            )
                            if len(gone_items) >= chunk:
                                _flush_gone_locked()
                        else:
                            slow.append(self._make_transition(row, s_idx, t_ms))
                    continue
                plan = None
                if self._fast_ok:
                    plan = self._plan_for(s_idx, sig, rep)
                if plan is None or not plan.fast or (
                    plan.has_event and self.recorder is not None
                ):
                    # finalizer ops, recorder-bound events, non-status
                    # patches: per-row path (which still renders
                    # through the plan when one exists)
                    for row in group:
                        if objects[row] is not None:
                            slow.append(self._make_transition(row, s_idx, t_ms))
                    continue
                if now_s is None:
                    now_s = self.sim.now_string(t_ms)
                bound, comp = plan.bind_tick(now_s)
                check_noop = not plan.has_now
                if use_c:
                    row_vals_cb = (
                        lambda obj, _p=plan: _p.row_vals(obj, self.funcs_for(obj))
                    )
                    for k in range(0, len(group), chunk):
                        if k and self._done.is_set() and self._past_abort_grace():
                            break
                        sub = group[k : k + chunk]
                        with _stage(self.kind, "host_build") as sp:
                            noops, slow_rows = _FAST.fast_group(
                                objects,
                                sub,
                                s_idx,
                                comp,
                                bound,
                                vals_cache,
                                row_vals_cb,
                                check_noop,
                                plan.has_null,
                                plan.all_top_plain,
                                plan.top_plain,
                                _merge_patch,
                                fast_rows,
                                fast_items,
                            )
                        self.t_build += sp.elapsed
                        self.transitions += noops
                        self._note_fired(s_idx, "batch", noops)
                        for row in slow_rows:
                            slow.append(self._make_transition(row, s_idx, t_ms))
                        if len(fast_items) >= chunk:
                            _flush_locked()
                    continue
                transitions_local = 0
                for row in group:
                    obj = objects[row]
                    if obj is None:
                        continue
                    try:
                        if comp is None:
                            patch = bound  # tick-static: shared by rows
                        else:
                            rowc = vals_cache[row]
                            if rowc is None:
                                rowc = vals_cache[row] = {}
                            vals = rowc.get(s_idx)
                            if vals is None:
                                vals = rowc[s_idx] = plan.row_vals(
                                    obj, self.funcs_for(obj)
                                )
                            patch = _plan_build(comp, vals)
                        cur_status = obj.get("status") or {}
                        new_status = plan.new_status(cur_status, patch)
                    except Exception:  # noqa: BLE001 — fall back per row
                        slow.append(self._make_transition(row, s_idx, t_ms))
                        continue
                    # a Now-stamping patch can never no-op against an
                    # earlier tick's status (timestamps strictly increase)
                    if check_noop and new_status == cur_status:
                        transitions_local += 1  # pure no-op transition
                        continue
                    meta = obj.get("metadata") or {}
                    fast_rows.append(row)
                    fast_items.append(
                        (
                            meta.get("namespace"),
                            meta.get("name") or "",
                            new_status,
                            meta.get("resourceVersion"),
                        )
                    )
                self.transitions += transitions_local
                self._note_fired(s_idx, "batch", transitions_local)
                if len(fast_items) >= chunk:
                    _flush_locked()
            _flush_locked()
            _flush_gone_locked()

        if slow:
            self._drain_slow(slow)

    def _commit_batch_locked(self, rows: List[int], items: List[tuple]) -> List[int]:
        """One ``store.apply_status_batch`` request for the built rows
        and its accounting (``self._mut`` held).  Returns the rows the
        store refused because their object is no longer at the
        resourceVersion the status was merged onto (somebody else wrote
        in between): the caller plays those as merge patches."""
        exclude = self._informer.active_watcher
        sp = _stage(self.kind, "store_bulk")
        try:
            with sp:
                results = self.store.apply_status_batch(
                    self.kind, items, exclude=exclude
                )
        except Exception:  # noqa: BLE001 — the store did not take the
            # batch (degraded storage, an apiserver away past the retry
            # budget): match the rows again from their mirrors, so that
            # they fire again instead of waiting on a write nobody made
            self._swallow()
            results = None
        self.t_store += sp.elapsed
        if results is None:
            objects = self.sim.objects
            for row in rows:
                if objects[row] is not None:
                    self._rematch_row(row)
            return []
        if _FAST is not None:
            n_ok, refused = self._confirm_native_locked(
                results, rows, items, exclude is not None
            )
        else:
            n_ok, refused = self._confirm_python_locked(results, rows, items)
        _H_COMMIT_ROWS.observe(n_ok, self.kind, "batch")
        st = self._drain_st
        committed = rows
        if n_ok == len(rows):
            for s_idx, n in enumerate(np.bincount(st[rows]).tolist()):
                self._note_fired(s_idx, "batch", n)
        else:
            # a refused row is played, and counted, by _drain_slow; one
            # whose object is gone is released and counts nowhere
            committed = [
                row for row, res in zip(rows, results)
                if res is not None and res is not False
            ]
            for row in committed:
                self._note_fired(int(st[row]), "batch")
        if self._committed is not None:
            self._note_committed(committed)
        return [rows[idx] for idx in refused]

    def _note_committed(self, rows: List[int]) -> None:
        """The store acknowledged a status commit of these rows of the
        Node player: if any had not been in one since its admission, the
        Ready wave reaches to now.  One test a request; a heartbeat's
        commit of rows long Ready finds none and moves nothing."""
        if not rows:
            return
        idx = np.asarray(rows)
        if not self._committed[idx].all():
            self._committed[idx] = True
            self.wave_wall_s = time.perf_counter() - self._wave_t0

    def _delete_is_one_event(self, s_idx: int, meta: dict) -> bool:
        """Whether all that the per-row path would make of this fired
        row of a deleting stage is one DELETED event, which is what a
        delete-batch item makes: the stage's finalizer change leaves the
        mirror's list empty; the mirror is terminating already, or no
        finalizer changes (else a MODIFIED comes first); no event goes
        to a recorder.  The store refuses an item whose object is not at
        the mirror's resourceVersion, so the mirror's finalizers are the
        stored ones where this reckoning is used (``self._mut`` held)."""
        fins = meta.get("finalizers") or ()
        terminating = meta.get("deletionTimestamp") is not None
        key = (s_idx, tuple(fins), terminating)
        verdict = self._delete_verdicts.get(key)
        if verdict is None:
            cset = self.sim.cset
            effects = cset.lifecycle.effects(cset.compiled[s_idx])
            verdict = False
            if (
                effects is not None
                and effects.delete
                and not (cset.stage_event[s_idx] >= 0 and self.recorder is not None)
            ):
                fin = effects.finalizers_patch(list(fins))
                if fin is None:
                    verdict = not fins
                elif terminating:
                    left = _apply_patch(
                        {"metadata": {"finalizers": list(fins)}}, fin.data, fin.type
                    )
                    verdict = not left["metadata"].get("finalizers")
            if len(self._delete_verdicts) >= 8192:
                self._delete_verdicts.clear()  # coarse bound, as _plans
            self._delete_verdicts[key] = verdict
        return verdict and isinstance(meta.get("resourceVersion"), str)

    def _commit_delete_locked(self, rows: List[int], items: List[tuple]) -> List[int]:
        """One ``store.apply_delete_batch`` request for the rows of
        deleting stages and its accounting (``self._mut`` held), the
        twin of ``_commit_batch_locked``.  A row the store removed, or
        did not find, is a transition played and a row released, as
        ``_finish_delete`` with nothing left; returns the rows the store
        refused because somebody else wrote their object since the
        mirror was read: the caller plays those op by op."""
        sp = _stage(self.kind, "delete_commit")
        try:
            with sp:
                # no watcher is excluded: the informer's own DELETED is
                # what runs on_delete and empties a mirrored cache
                results = self.store.apply_delete_batch(self.kind, items)
        except Exception:  # noqa: BLE001 — as _commit_batch_locked: the
            # store did not take the batch; match the rows again from
            # their mirrors, so that they fire again
            self._swallow()
            results = None
        self.t_store += sp.elapsed
        if results is None:
            for row in rows:
                self._rematch_row(row)
            return []
        refused: List[int] = []
        now = self.clock.now()
        st = self._drain_st
        for row, item, res in zip(rows, items, results):
            if res is False:
                refused.append(row)
            else:
                self._gone_locked((item[0] or "", item[1]), now)
                self._note_fired(int(st[row]), "delete")
        n_ok = len(rows) - len(refused)
        self.transitions += n_ok
        _H_COMMIT_ROWS.observe(n_ok, self.kind, "delete")
        return refused

    def _confirm_native_locked(
        self, results, fast_rows, fast_items, own_cache: bool
    ) -> Tuple[int, List[int]]:
        """Adopt a status-batch's results via the C loop (self._mut
        held); when the store excluded our watcher (own_cache) AND the
        cache is a real mirror (hand-wired CacheGetter — the start()
        path uses a StoreBackedGetter with nothing to maintain), also
        maintain it here (under its lock — the informer thread still
        applies non-batch events to it).  Returns the rows committed
        and the indexes of the refused ones."""
        cache = self.cache if own_cache and hasattr(self.cache, "_items") else None
        if cache is not None:
            with cache._mut:
                n_ok, releases, fallback_idx, refused_idx = _FAST.confirm_batch(
                    results,
                    fast_rows,
                    fast_items,
                    self.sim.objects,
                    self._written_rv,
                    cache._items,
                )
        else:
            n_ok, releases, fallback_idx, refused_idx = _FAST.confirm_batch(
                results,
                fast_rows,
                fast_items,
                self.sim.objects,
                self._written_rv,
                None,
            )
        self.transitions += n_ok
        self.patches += n_ok
        for key in releases:
            self._release_locked(key)
        objects = self.sim.objects
        for idx in fallback_idx:
            # echo carried more than our status write: full refresh
            row = fast_rows[idx]
            if objects[row] is not None:
                self._adopt_changed_locked(row, results[idx][1])
        return n_ok, refused_idx

    def _confirm_python_locked(
        self, results, fast_rows, fast_items
    ) -> Tuple[int, List[int]]:
        """``_confirm_native_locked`` without the native unit."""
        objects = self.sim.objects
        written = self._written_rv
        n_ok = 0
        refused_idx: List[int] = []
        for idx, (row, item, res) in enumerate(zip(fast_rows, fast_items, results)):
            if res is False:
                refused_idx.append(idx)
                continue
            if res is None:
                self._release_locked((item[0] or "", item[1]))
                continue
            rv, new_obj = res
            written[row] = str(rv)
            n_ok += 1
            old = objects[row]
            if old is None:
                continue
            if new_obj is None:
                # a store across the wire echoes no object, and took
                # the row only because the mirror was current: the new
                # mirror is the old one, the status sent, the rv given
                new_obj = dict(old)
                new_obj["status"] = item[2]
                new_obj["metadata"] = dict(old["metadata"], resourceVersion=str(rv))
            # confirm_row guards against an interleaved external
            # write (e.g. a scheduler spec patch committed between
            # our object read and the store batch): the store's
            # echo carries it, and since _written_rv now covers
            # its rv, this is the only place it can be noticed —
            # fall back to a full feature re-extraction
            if not self.sim.confirm_row(row, new_obj):
                self._adopt_changed_locked(row, new_obj)
        self.transitions += n_ok
        self.patches += n_ok
        return n_ok, refused_idx

    def _adopt_changed_locked(self, row: int, new_obj: dict) -> None:
        """The store's object differs from the mirror beyond our own
        status write: take it and extract the row's features again."""
        old = self.sim.objects[row]
        self.sim.objects[row] = new_obj
        self._rematch_row(row)
        if not self._render_identity_same(old, new_obj):
            self._drop_render_cache(row)

    def _make_transition(self, row: int, s_idx: int, t_ms: int) -> Transition:
        cset = self.sim.cset
        event = None
        eid = int(cset.stage_event[s_idx])
        if eid >= 0:
            event = cset.events[eid]
        return Transition(
            row=row,
            stage_idx=s_idx,
            stage_name=cset.compiled[s_idx].name,
            t_ms=t_ms,
            deleted=bool(cset.stage_delete[s_idx]),
            event=event,
        )

    def _drain_slow(self, transitions: List[Transition]) -> None:
        """Legacy per-transition drain (deletes, finalizers, events,
        non-status patches): grouped ops through store.bulk with the
        sequential fallback.  ``slow_build`` is the Python a row on both
        sides of the bulk, ``slow_commit`` the bulk; the rows' Events
        wait for the end of the drain (``event_post``)."""
        can_bulk = hasattr(self.store, "bulk")
        #: (key, ops, stage_idx) a transition with something to send
        groups: List[Tuple[Tuple[str, str], List[dict], int]] = []
        played = self.transitions
        with _stage(self.kind, "slow_build"):
            for j, tr in enumerate(transitions):
                if (
                    (j & 0xFF) == 0xFF
                    and self._done.is_set()
                    and self._past_abort_grace()
                ):
                    break  # shutdown: unplayed transitions re-fire on restart
                before = self.transitions
                try:
                    g = self._collect_ops(tr) if can_bulk else None
                    if g is not None:
                        key, ops = g
                        if ops:
                            groups.append((key, ops, tr.stage_idx))
                    else:
                        self._play_transition(tr)
                except Exception:  # noqa: BLE001 — one bad row must not stop the drain
                    self._swallow()
                self._note_fired(tr.stage_idx, "slow", self.transitions - before)
            flat = [
                {k: v for k, v in op.items() if k != "_fin"}
                for _, ops, _s in groups
                for op in ops
            ]
        if groups:
            with _stage(self.kind, "slow_commit") as sp:
                try:
                    if self._bulk_no_copy:
                        results = self.store.bulk(flat, copy_results=False)
                    else:
                        results = self.store.bulk(flat)
                except Exception:  # noqa: BLE001 — drop to per-op on bulk failure
                    results = None
            self.t_store += sp.elapsed
            with _stage(self.kind, "slow_build"):
                if results is None:
                    results = [self._op_sequential_result(op) for op in flat]
                idx = 0
                for key, ops, s_idx in groups:
                    rs = results[idx : idx + len(ops)]
                    idx += len(ops)
                    before = self.transitions
                    try:
                        self._apply_group_results(key, ops, rs)
                    except Exception:  # noqa: BLE001 — per-group isolation
                        self._swallow()
                    self._note_fired(s_idx, "slow", self.transitions - before)
        if groups or self.transitions != played:
            # whatever was played: through the bulk, by _play_transition,
            # or with nothing to send
            _H_COMMIT_ROWS.observe(self.transitions - played, self.kind, "slow")
            if self._committed is not None and self.transitions != played:
                with self._mut:
                    objects = self.sim.objects
                    # a row whose object is gone was released, not committed
                    self._note_committed(
                        [tr.row for tr in transitions if objects[tr.row] is not None]
                    )

    def _finish_delete(self, key: Tuple[str, str], out: Optional[dict]) -> None:
        """Complete a stage-driven delete: fully gone → release the
        row; terminating (finalizers pending) → refresh from the
        store's result.  Counts the transition either way."""
        self.transitions += 1
        if out is not None:
            self._refresh(key, out)
            return
        with self._mut:
            self._gone_locked(key, self.clock.now())

    def _gone_locked(self, key: Tuple[str, str], now: float) -> None:
        """The store acknowledged that a stage-driven delete left
        nothing behind: release the row and observe how long the object
        had been terminating (``self._mut`` held)."""
        row = self._rows.get(key)
        gone = self.sim.objects[row] if row is not None else None
        self._release_locked(key)
        asked = ((gone or {}).get("metadata") or {}).get("deletionTimestamp")
        at = parse_rfc3339(asked) if isinstance(asked, str) else None
        if at is not None:
            _H_DELETE_TO_GONE.observe(max(now - at.timestamp(), 0.0), self.kind)

    #: timestamp that can never occur in real renders (pre-epoch)
    _NOW_SENTINEL = "1987-06-05T04:03:02.000001Z"

    def _render(self, tr: Transition, obj: dict, effects) -> List:
        """Template patches for a transition: cross-row RenderPlan when
        available (sentinel substitution, no gotpl), else the per-row
        render cache when sound (see _render_cache), else a full gotpl
        render + YAML parse per row."""
        if self._fast_ok:
            plan = self._plan_for(tr.stage_idx, int(self.sim.sig[tr.row]), obj)
            if plan is not None:
                return plan.build_patches(
                    obj, self.sim.now_string(tr.t_ms), self.funcs_for(obj)
                )
        if self._reads_state:
            funcs = dict(self.funcs_for(obj))
            funcs.setdefault("Now", lambda: self.sim.now_string(tr.t_ms))
            return list(effects.patches(obj, funcs))
        row_cache = self._render_cache.setdefault(tr.row, {})
        cached = row_cache.get(tr.stage_idx)
        if cached is None:
            funcs = dict(self.funcs_for(obj))
            funcs["Now"] = lambda: self._NOW_SENTINEL
            cached = row_cache[tr.stage_idx] = list(effects.patches(obj, funcs))
        now_s = self.sim.now_string(tr.t_ms)
        sent = self._NOW_SENTINEL

        def sub(x):
            t = type(x)
            if t is str:
                return x.replace(sent, now_s) if sent in x else x
            if t is dict:
                return {k: sub(v) for k, v in x.items()}
            if t is list:
                return [sub(v) for v in x]
            return x

        from kwok_tpu.engine.lifecycle import Patch

        return [
            Patch(
                data=sub(p.data),
                type=p.type,
                subresource=p.subresource,
                impersonation=p.impersonation,
            )
            for p in cached
        ]

    def _drop_render_cache(self, row: int) -> None:
        self._render_cache.pop(row, None)
        if row < len(self._vals_cache):
            self._vals_cache[row] = None

    def _render_identity_same(self, old: Optional[dict], new: dict) -> bool:
        """Whether a row's cached renders survive this object change:
        with no state read paths, renders depend only on spec, labels,
        and annotations (name/ns/uid are immutable per row)."""
        if self._reads_state or old is None:
            return False
        om = old.get("metadata") or {}
        nm = new.get("metadata") or {}
        return (
            old.get("spec") == new.get("spec")
            and om.get("labels") == nm.get("labels")
            and om.get("annotations") == nm.get("annotations")
        )

    def _op_sequential_result(self, op: dict) -> dict:
        """Per-op fallback when the bulk round-trip itself failed:
        apply the op directly and shape the outcome like a bulk result
        so the group handler stays the single accounting path."""
        try:
            if op["verb"] == "delete":
                out = self.store.delete(
                    op["kind"], op["name"], namespace=op.get("namespace")
                )
            else:
                out = self.store.patch(
                    op["kind"],
                    op["name"],
                    op["data"],
                    op.get("patch_type", "merge"),
                    namespace=op.get("namespace"),
                    subresource=op.get("subresource") or "",
                    as_user=op.get("as_user"),
                )
            return {"status": "ok", "object": out}
        except NotFound as exc:
            return {"status": "error", "reason": "NotFound", "error": str(exc)}
        except Exception as exc:  # noqa: BLE001 — shaped like bulk's guard
            return {"status": "error", "reason": "Invalid", "error": str(exc)}

    def _apply_group_results(
        self, key: Tuple[str, str], ops: List[dict], results: List[dict]
    ) -> None:
        """Account one transition's ordered op results (bulk or the
        sequential fallback): deletes finish the row, patch successes
        count once per transition, the last patch result refreshes the
        row (fast confirm when it was a lone status patch)."""
        last_obj = None
        last_simple = False
        own_fin = any(op.get("_fin") for op in ops)
        n_ok = 0
        for op, res in zip(ops, results):
            ok = res.get("status") == "ok"
            if op["verb"] == "delete":
                if ok:
                    self._finish_delete(key, res.get("object"))
                elif res.get("reason") == "NotFound":
                    # already gone counts as a completed delete
                    # transition (sequential-path parity)
                    self._finish_delete(key, None)
                else:
                    print(
                        f"device bulk delete failed for {key}: "
                        f"{res.get('reason')}: {res.get('error')}",
                        file=sys.stderr,
                    )
                return
            if ok:
                n_ok += 1
                self.patches += 1
                if res.get("object") is not None:
                    last_obj = res["object"]
                    last_simple = op.get("subresource") == "status"
            elif res.get("reason") == "NotFound":
                self._release(key)
                return
            else:
                # Conflict/Invalid: surface it like the sequential
                # path's per-transition traceback did.  Keep consuming
                # the group — bulk already executed the later ops (its
                # contract: per-op failures do not abort the batch), so
                # their results must still be accounted.
                print(
                    f"device bulk op failed for {key}: "
                    f"{res.get('reason')}: {res.get('error')}",
                    file=sys.stderr,
                )
        if n_ok:
            self.transitions += 1
        if last_obj is not None:
            # confirm_row falls back to a full refresh on any
            # unexpected delta; our own finalizer write is expected
            # (its effect is lowered on device)
            self._refresh(
                key, last_obj, simple=last_simple, own_finalizers=own_fin
            )

    def _note_event(self, tr: Transition, obj: dict) -> None:
        """The transition's Event, if its stage has one, joins those
        the drain hands to the recorder in one request once its rows
        are committed (``_drain_stages_inner``)."""
        if tr.event is not None and self.recorder is not None:
            self._pending_events.append(
                (obj, tr.event.type or "Normal", tr.event.reason, tr.event.message)
            )

    def _collect_ops(self, tr: Transition):
        """Lower a transition to an ORDERED op group for the bulk drain:
        returns (key, [op, ...]) — empty list means pure no-op (counted
        as a transition, nothing to send); returns None for transitions
        that genuinely need the sequential path (a later render would
        depend on an earlier op's server-side result).

        Multi-op groups render every template patch from the SAME
        pre-transition base; that matches the sequential path exactly
        unless a template reads state an earlier op in the group mutates
        (finalizers for finalizer+patch groups, any read path for
        patch+patch groups) — those shapes stay sequential."""
        with self._mut:
            obj = self.sim.objects[tr.row]
        if obj is None:
            return ("", ""), []
        meta = obj.get("metadata") or {}
        cs = self.sim.cset.compiled[tr.stage_idx]
        effects = self.sim.cset.lifecycle.effects(cs)
        if effects is None:
            return (self._key(obj), [])
        key = self._key(obj)
        name = meta.get("name") or ""
        ns = meta.get("namespace")
        ops: List[dict] = []

        fin = effects.finalizers_patch(meta.get("finalizers") or [])
        if fin is not None:
            if self._reads_finalizers:
                return None  # a template depends on the finalizer write
            ops.append(
                {
                    "verb": "patch",
                    "kind": self.kind,
                    "name": name,
                    "namespace": ns,
                    "data": fin.data,
                    "patch_type": fin.type,
                    "_fin": True,  # local marker, stripped before send
                }
            )

        if effects.delete:
            self._note_event(tr, obj)
            ops.append(
                {
                    "verb": "delete",
                    "kind": self.kind,
                    "name": name,
                    "namespace": ns,
                }
            )
            return (key, ops)

        patches = [
            p
            for p in self._render(tr, obj, effects)
            if not is_noop_patch(obj, p.data, p.type)
        ]
        if len(patches) > 1 and (
            self._reads_state or any(p.subresource != "status" for p in patches)
        ):
            # multiple template patches only batch when none can read
            # what an earlier one writes: all status-subresource writes
            # with no state read paths.  A non-status patch could write
            # labels/spec, which templates may read without appearing in
            # _read_paths (the compiler excludes identity reads) — those
            # shapes keep the sequential base-chaining path.
            return None
        self._note_event(tr, obj)
        if not patches and not ops:
            # nothing to send — the transition is complete here; ops
            # that DO ship count only once their patch lands (parity
            # with the sequential path's post-success increment)
            self.transitions += 1
            return (key, [])
        for p in patches:
            ops.append(
                {
                    "verb": "patch",
                    "kind": self.kind,
                    "name": name,
                    "namespace": ns,
                    "data": p.data,
                    "patch_type": p.type,
                    "subresource": p.subresource,
                    "as_user": p.impersonation,
                }
            )
        return (key, ops)

    # ----------------------------------------------------------- store effects

    def _play_transition(self, tr: Transition) -> None:
        """Route one fired row's effects to the store (same semantics as
        StagePlayer.play_stage), then refresh the row from the store's
        result so device features stay parity-exact."""
        with self._mut:
            obj = self.sim.objects[tr.row]
        if obj is None:
            return
        meta = obj.get("metadata") or {}
        name = meta.get("name") or ""
        ns = meta.get("namespace")
        key = self._key(obj)
        cs = self.sim.cset.compiled[tr.stage_idx]
        effects = self.sim.cset.lifecycle.effects(cs)
        if effects is None:
            return

        self._note_event(tr, obj)

        result: Optional[dict] = None
        fin = effects.finalizers_patch(meta.get("finalizers") or [])
        if fin is not None:
            try:
                result = self.store.patch(self.kind, name, fin.data, fin.type, namespace=ns)
            except NotFound:
                self._release(key)
                return

        if effects.delete:
            try:
                out = self.store.delete(self.kind, name, namespace=ns)
            except NotFound:
                out = None
            self._finish_delete(key, out)
            return

        funcs = dict(self.funcs_for(obj))
        funcs.setdefault("Now", lambda: self.sim.now_string(tr.t_ms))
        base = result if result is not None else obj
        for patch in effects.patches(base, funcs):
            if is_noop_patch(base, patch.data, patch.type):
                continue
            try:
                result = self.store.patch(
                    self.kind,
                    name,
                    patch.data,
                    patch.type,
                    namespace=ns,
                    subresource=patch.subresource,
                    as_user=patch.impersonation,
                )
                base = result
                self.patches += 1
            except NotFound:
                self._release(key)
                return
        self.transitions += 1
        if result is not None:
            self._refresh(key, result)

    def _release(self, key: Tuple[str, str]) -> None:
        with self._mut:
            self._release_locked(key)

    def _release_locked(self, key: Tuple[str, str]) -> None:
        row = self._rows.pop(key, None)
        if row is not None:
            self.sim.release(row)
            if row < len(self._written_rv):
                self._written_rv[row] = None
            self._drop_render_cache(row)
            if self._committed is not None:
                self._committed[row] = False

    def _refresh(
        self,
        key: Tuple[str, str],
        obj: dict,
        simple: bool = False,
        own_finalizers: bool = False,
    ) -> None:
        with self._mut:
            row = self._rows.get(key)
            if row is None:
                return
            # store reaped it (deletionTimestamp + no finalizers)?
            mm = obj.get("metadata") or {}
            self._grow_row_arrays()
            self._written_rv[row] = mm.get("resourceVersion")
            if simple and self.sim.confirm_row(
                row, obj, ignore_finalizers=own_finalizers
            ):
                # our own patch echoed back unchanged elsewhere: device
                # state already reflects it (no re-extract, no SoA
                # re-upload)
                return
            old = self.sim.objects[row]
            self.sim.objects[row] = obj
            self._rematch_row(row)
            if not self._render_identity_same(old, obj):
                self._drop_render_cache(row)


def _rv_stale(rv, last) -> bool:
    """True when a watch event's resourceVersion is at or before our
    last write for the row. The store's resourceVersions are a
    monotonic counter, so numeric comparison suppresses stale
    intermediate echoes; opaque rvs fall back to exact match."""
    if last is None:
        return False
    if rv == last:
        return True
    try:
        return int(rv) <= int(last)
    except (TypeError, ValueError):
        return False


def _epoch_from(t: float):
    import datetime

    return datetime.datetime.fromtimestamp(t, datetime.timezone.utc)
