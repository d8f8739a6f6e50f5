"""Horizontally sharded ResourceStore: hash router over N shards.

KUBEDIRECT's shape (PAPERS.md): partition the state, keep one thin
router in front, and let hot-path writers dispatch straight to the
owning partition.  :class:`ShardedStore` holds N independent
:class:`~kwok_tpu.cluster.store.ResourceStore` shards
(``kwok_tpu/cluster/store.py:592``) — each with its own mutex family,
its own checksummed segmented WAL + PITR archive
(``kwok_tpu/cluster/sharding/recovery.py`` composes the on-disk form)
and its own watch rings — and routes every verb by a stable
namespace/kind hash.  The router itself is duck-typed to
``ResourceStore`` exactly like ``ClusterClient`` is (CLAUDE.md
conventions), so the apiserver facade, controllers, workloads, sched
and the DST actors run unchanged on top of it.

Placement (``shard_of``): a namespaced object lives on
``crc32(namespace) % N``; a cluster-scoped KIND lives whole on
``crc32("kind:<kind>") % N``.  Consequences the rest of the design
leans on:

- a namespace's objects are co-located, so a PodGroup and its pods are
  **shard-affine** and :meth:`ShardedStore.transact` stays
  single-shard-atomic — cross-shard transactions are a design
  violation and are refused with the typed
  :class:`~kwok_tpu.cluster.store.CrossShardTransaction` (409
  ``CrossShard``), never resolved by a 2PC;
- a single-namespace (or cluster-scoped-kind) list/watch is served by
  ONE shard with no merge cost; only all-namespaces reads fan out
  (``kwok_tpu/cluster/sharding/fanin.py`` merges the watches).

resourceVersions are drawn from ONE cluster-wide sequence
(:class:`RvSource`, handed to every shard as ``rv_source``), so rvs
stay globally unique and monotonic: resume-at-rv means the same
instant on every shard, and the watch fan-in preserves per-object rv
ordering with no cross-shard coordination.  uids stride
(``uid_start=i, uid_step=N``) so shards never collide without shared
state.
"""

from __future__ import annotations

import contextlib
import json
import operator
import zlib
from typing import Any, Dict, List, Optional, Tuple

from kwok_tpu.cluster.store import (
    CrossShardTransaction,
    ListSnapshots,
    NotFound,
    ResourceStore,
    ResourceType,
    Selector,
    Watcher,
    list_page_from,
)
from kwok_tpu.cluster.sharding.fanin import MergedWatcher
from kwok_tpu.utils.locks import make_lock

__all__ = [
    "RvSource",
    "ShardedStore",
    "build_sharded_store",
    "shard_of",
    "shard_key",
    "split_state",
]


class RvSource:
    """The cluster-wide resourceVersion sequence every shard draws
    from (``ResourceStore._bump`` calls :meth:`alloc` under the
    shard's own mutex).  The critical section is a counter increment —
    deliberately tiny, so the shared sequence never becomes the new
    global store mutex.  Lock order: a shard's ``_mut`` is held while
    acquiring this lock, never the reverse (the PR 9 lock-order gate
    and runtime sentinel cover the pair)."""

    def __init__(self, start: int = 0):
        self._mut = make_lock("cluster.sharding.router.RvSource._mut")
        self._rv = int(start)

    def alloc(self) -> int:
        with self._mut:
            self._rv += 1
            return self._rv

    def unalloc(self, rv: int) -> bool:
        """Reclaim ``rv`` if it is still the sequence tip (the
        WAL-exhausted rollback path, ``ResourceStore._unbump``);
        False when another shard already allocated past it."""
        with self._mut:
            if self._rv == int(rv):
                self._rv -= 1
                return True
            return False

    def current(self) -> int:
        with self._mut:
            return self._rv

    def advance_to(self, rv: int) -> None:
        """Never-backwards catch-up (boot recovery seeds the sequence
        with the highest rv any shard's WAL reproduced)."""
        with self._mut:
            self._rv = max(self._rv, int(rv))


#: fleet tenant separator: namespaces named ``<tenant>--<ns>`` hash by
#: the tenant segment alone, so every namespace of one fleet tenant —
#: and therefore every tenant transaction — lands on one shard
#: (kwok_tpu/fleet/).  Plain namespaces are unaffected.
TENANT_SEP = "--"


def shard_key(namespaced: bool, kind: str, namespace: Optional[str]) -> str:
    """The stable placement key: namespace for namespaced kinds (the
    store's own ``ns or "default"`` convention, truncated at the fleet
    tenant separator so a tenant's namespaces co-locate), a kind-tagged
    key for cluster-scoped kinds (the whole kind lives on one shard,
    keeping its lists/watches single-shard)."""
    if namespaced:
        return (namespace or "default").split(TENANT_SEP, 1)[0]
    return "kind:" + (kind or "").lower()


def shard_of(
    namespaced: bool, kind: str, namespace: Optional[str], n: int
) -> int:
    """Owning shard index — crc32, NOT ``hash()``: the route table must
    agree across processes (clients compute the same placement for the
    per-shard direct-dispatch lanes) and across runs (a restarted
    daemon must route to where the objects already live)."""
    if n <= 1:
        return 0
    return zlib.crc32(shard_key(namespaced, kind, namespace).encode()) % n


def namespaces_covering_shards(n: int, prefix: str = "ns") -> List[str]:
    """One namespace name per shard, ordered by owning shard index —
    the probe shape chaos smokes and the store bench use to address
    every shard of an n-shard cluster with plain namespaced writes."""
    n = max(1, int(n))
    by_shard: Dict[int, str] = {}
    i = 0
    while len(by_shard) < n:
        name = f"{prefix}-{i}"
        by_shard.setdefault(shard_of(True, "Pod", name, n), name)
        i += 1
    return [by_shard[s] for s in sorted(by_shard)]


def split_state(
    state: dict, n: int, namespaced_of=None
) -> List[dict]:
    """Split one ``dump_state``-shaped snapshot into N per-shard
    snapshots by the live placement hash (the snapshot-splitting twin
    of routing).  Every slice carries the full type registry and the
    snapshot's resourceVersion; per-shard uid counters restart above
    the snapshot's in each shard's own stride residue.  ``namespaced_of``
    maps a kind to its namespaced flag (defaults to the snapshot's own
    ``types`` table, then namespaced)."""
    n = max(1, int(n))
    types = state.get("types", [])
    rv = int(state.get("resourceVersion", 0))
    uc = int(state.get("uidCounter", 0))
    ns_of = {
        t.get("kind"): bool(t.get("namespaced", True)) for t in types
    }
    by_shard: Dict[int, List[dict]] = {i: [] for i in range(n)}
    for obj in state.get("objects", []):
        kind = obj.get("kind") or ""
        ns = (obj.get("metadata") or {}).get("namespace")
        if namespaced_of is not None:
            namespaced = namespaced_of(kind)
        else:
            namespaced = ns_of.get(kind, True)
        by_shard[shard_of(namespaced, kind, ns, n)].append(obj)
    return [
        {
            "resourceVersion": rv,
            # smallest counter at or above the snapshot's, in this
            # shard's residue class: uids it mints stay ≡ i (mod n)
            # and above every uid the snapshot holds — and for n == 1
            # this is uc itself, keeping a dump→restore→dump through
            # the 1-shard composition byte-identical to the plain store
            "uidCounter": uc + ((i - uc) % n),
            "types": types,
            "objects": by_shard[i],
        }
        for i in range(n)
    ]


def build_sharded_store(
    n: int,
    clock=None,
    namespace_finalizers: bool = False,
    watch_high_water: Optional[int] = None,
) -> "ShardedStore":
    """In-memory sharded store (no WALs): N shards on one shared rv
    sequence with strided uids.  The on-disk composition (per-shard
    WAL + PITR + tolerant recovery) lives in
    ``kwok_tpu/cluster/sharding/recovery.py``.  A 1-shard store skips
    the shared sequence entirely (no per-bump lock, fast lanes stay
    armed) — the no-regression contract of the default
    configuration."""
    n = max(1, int(n))
    source = RvSource()
    shards = [
        ResourceStore(
            clock=clock,
            namespace_finalizers=namespace_finalizers,
            watch_high_water=watch_high_water,
            rv_source=source if n > 1 else None,
            uid_start=i if n > 1 else 0,
            uid_step=n if n > 1 else 1,
        )
        for i in range(n)
    ]
    for i, s in enumerate(shards):
        # bounded shard index on the observed latency series (watch
        # delivery lag; the on-disk composition also stamps its WALs)
        s.telemetry_shard = i
    return ShardedStore(shards, source)


class ShardedStore:
    """Shard router, duck-typed to :class:`ResourceStore`.

    Single-key verbs route to the owning shard.  All-namespaces reads
    fan out and merge; ``bulk`` splits per shard (each sub-batch takes
    the owning shard's bulk lane directly — the in-process form of
    KUBEDIRECT direct dispatch); ``transact`` refuses cross-shard
    batches with the typed 409.  Aggregate surfaces (``dump_state``,
    ``wal_health``, ``storage_degraded``, counters) merge the shards'
    answers; degradation is PER SHARD — one shard on a full disk turns
    only ITS writes into 503 ``StorageDegraded`` while the other
    shards stay writable, and ``/readyz`` reports the degraded shard
    set."""

    def __init__(self, shards: List[ResourceStore], source: RvSource):
        if not shards:
            raise ValueError("a sharded store needs at least one shard")
        self._shards = list(shards)
        self._source = source
        #: what the continue tokens of a LIST across every shard name
        self._snapshots = ListSnapshots()
        #: test-only injected regression (`--dst-bug cross-shard-txn`):
        #: stripes txn ops across shards per-OP (a load-balancing
        #: "optimization" instead of the per-namespace placement) —
        #: so a shard-affine gang's binds suddenly span shards — and
        #: commits the per-shard sub-txns in sequence.  This is the
        #: buggy router design the typed CrossShard rejection exists
        #: to forbid: an abort (or crash) after an earlier sub-txn
        #: committed strands a bound strict subset, exactly the
        #: partial state the DST gang-atomicity invariant catches
        self.unsafe_split_cross_shard_txns = False
        #: test-only injected regression (`--dst-bug
        #: fanin-stale-resume`): the merged-watch resume classifies a
        #: shard as "never written since the resume point" by testing
        #: its CURRENT rv against the resume horizon (a plausible
        #: optimization that intends rv == 0) and pins such a shard at
        #: rv 0 — so a shard that merely went quiet replays its whole
        #: history ring into a stream that already consumed those
        #: events.  The duplicate (key, rv) deliveries violate the
        #: per-object ordering the DST watch-rv-monotonic invariant
        #: asserts, but only in the narrow interleaving where a
        #: consumer resumes while fully caught up with the shard —
        #: the window the coverage-guided search exists to find
        self.unsafe_fanin_stale_resume = False

    # ------------------------------------------------------------- routing

    @property
    def shard_count(self) -> int:
        return len(self._shards)

    def shard_lane(self, index: int) -> ResourceStore:
        """The shard itself — the colocated direct-dispatch lane (and
        the seam chaos/DST use to aim per-shard faults)."""
        self._check_index(index)
        return self._shards[index]

    def _check_index(self, index: int) -> None:
        if not 0 <= int(index) < len(self._shards):
            raise NotFound(
                f"no shard {index} (store has {len(self._shards)})"
            )

    def delivery_lag(self, rv: int):
        """(seconds since rv committed, owning shard) for a recently
        committed rv, or None — the sharded twin of
        ``ResourceStore.delivery_lag``.  Every rv lives on exactly one
        shard (one shared sequence), so the first ring that knows it
        answers; the probe is O(shards) dict lookups and feeds the
        ``kwok_watch_delivery_lag_seconds{shard=}`` series for events
        delivered through the ``MergedWatcher`` fan-in."""
        for s in self._shards:
            lag = s.delivery_lag(rv)
            if lag is not None:
                return lag
        return None

    def commit_context(self, rv: int):
        """Sharded twin of ``ResourceStore.commit_context``: the
        committing span's (trace_id, span_id) for a recent rv, resolved
        from whichever shard's ring committed it — so the rv→span
        stitch survives the ``MergedWatcher`` fan-in unchanged."""
        for s in self._shards:
            ctx = s.commit_context(rv)
            if ctx is not None:
                return ctx
        return None

    def commit_meta(self, rv: int):
        """Sharded twin of ``ResourceStore.commit_meta`` (journey join
        at watch delivery): first owning ring answers."""
        for s in self._shards:
            meta = s.commit_meta(rv)
            if meta is not None:
                return meta
        return None

    def commit_contexts(self, rvs):
        """Batch twin of :meth:`commit_context`: one lock hold PER
        SHARD resolves the whole burst (each rv lives on exactly one
        shard, so later shards only probe the leftovers)."""
        out = {}
        pending = list(rvs)
        for s in self._shards:
            if not pending:
                break
            hit = s.commit_contexts(pending)
            if hit:
                out.update(hit)
                pending = [rv for rv in pending if rv not in hit]
        return out

    def shard_topology(self) -> Dict[str, Any]:
        """The route table the per-shard HTTP dispatch lanes are
        derived from (``GET /shards``); ``algo`` names the placement
        function so a client can refuse an unknown scheme instead of
        misrouting."""
        return {"shards": len(self._shards), "algo": "crc32-ns-kind"}

    def _rtype(self, kind: str) -> ResourceType:
        return self._shards[0].resource_type(kind)

    def shard_for(self, kind: str, namespace: Optional[str] = None) -> int:
        """Owning shard for (kind, namespace) — raises NotFound for an
        unregistered kind, like every store verb."""
        rt = self._rtype(kind)
        return shard_of(
            rt.namespaced, rt.kind, namespace, len(self._shards)
        )

    def _route(self, kind: str, namespace: Optional[str]) -> ResourceStore:
        return self._shards[self.shard_for(kind, namespace)]

    def _obj_shard(self, op: dict) -> int:
        """Owning shard for one bulk/txn op (kind from the op or its
        data, namespace likewise)."""
        data = op.get("data") if isinstance(op.get("data"), dict) else {}
        kind = op.get("kind") or data.get("kind") or ""
        ns = (
            op.get("namespace")
            or (data.get("metadata") or {}).get("namespace")
        )
        return self.shard_for(kind, ns)

    # ------------------------------------------------------------ registry

    def register_type(self, rtype: ResourceType) -> None:
        for s in self._shards:
            s.register_type(rtype)

    def register_index(self, kind: str, path: str) -> None:
        for s in self._shards:
            s.register_index(kind, path)

    def resource_type(self, kind: str) -> ResourceType:
        return self._rtype(kind)

    def kinds(self) -> List[ResourceType]:
        return self._shards[0].kinds()

    # ----------------------------------------------------------------- CRUD

    def create(
        self,
        obj: dict,
        namespace: Optional[str] = None,
        as_user: Optional[str] = None,
        copy_result: bool = True,
    ) -> dict:
        kind = (obj or {}).get("kind") or ""
        ns = ((obj or {}).get("metadata") or {}).get("namespace") or namespace
        return self._route(kind, ns).create(
            obj, namespace=namespace, as_user=as_user, copy_result=copy_result
        )

    def get(self, kind: str, name: str, namespace: Optional[str] = None) -> dict:
        return self._route(kind, namespace).get(kind, name, namespace=namespace)

    def update(
        self, obj: dict, subresource: str = "", as_user: Optional[str] = None
    ) -> dict:
        kind = (obj or {}).get("kind") or ""
        ns = ((obj or {}).get("metadata") or {}).get("namespace")
        return self._route(kind, ns).update(
            obj, subresource=subresource, as_user=as_user
        )

    def patch(
        self,
        kind: str,
        name: str,
        data: Any,
        patch_type: str = "merge",
        namespace: Optional[str] = None,
        subresource: str = "",
        as_user: Optional[str] = None,
        expect: Optional[Dict[str, Any]] = None,
        copy_result: bool = True,
    ) -> dict:
        return self._route(kind, namespace).patch(
            kind,
            name,
            data,
            patch_type=patch_type,
            namespace=namespace,
            subresource=subresource,
            as_user=as_user,
            expect=expect,
            copy_result=copy_result,
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
    ) -> Tuple[dict, bool]:
        return self._route(kind, namespace).apply(
            kind,
            name,
            applied,
            field_manager,
            force=force,
            namespace=namespace,
            as_user=as_user,
        )

    def delete(
        self,
        kind: str,
        name: str,
        namespace: Optional[str] = None,
        as_user: Optional[str] = None,
        copy_result: bool = True,
    ) -> Optional[dict]:
        return self._route(kind, namespace).delete(
            kind,
            name,
            namespace=namespace,
            as_user=as_user,
            copy_result=copy_result,
        )

    # ---------------------------------------------------------------- reads

    def _fanout(self, kind: str, namespace: Optional[str]) -> bool:
        """True when (kind, namespace) spans every shard: a namespaced
        kind read across all namespaces."""
        return self._rtype(kind).namespaced and namespace is None

    def _merged_rv(self, shard_rvs: List[int], g0: int) -> int:
        """The resume point a merged read reports, never below the
        global pre-list horizon ``g0``: every event with rv <= g0 was
        committed before its shard was read (``_bump`` allocates under
        the shard mutex the read also takes), so the merged list
        already contains it, and a watch from g0 at worst redundantly
        replays events that landed mid-walk (benign: shard order
        preserves per-object ordering, so caches converge).  The
        participating shards' own rvs only ever tighten the resume
        point upward — taking their raw minimum instead would let one
        long-idle shard pin the resume below a busy shard's history
        ring and livelock every list-then-watch in permanent
        ``Expired`` re-lists once that ring wraps.  A shard that has
        never allocated (rv 0) counts as g0, NOT skipped: its first
        write can land mid-walk after its read, at an rv the other
        shards' larger rvs would leap past — a resume above it would
        silently drop that object from every list-then-watch cache
        until its next modification."""
        vals = [rv if rv > 0 else g0 for rv in shard_rvs]
        return max(g0, min(vals)) if vals else g0

    def list(
        self,
        kind: str,
        namespace: Optional[str] = None,
        label_selector: Selector = None,
        field_selector: Selector = None,
    ) -> Tuple[List[dict], int]:
        if not self._fanout(kind, namespace):
            return self._route(kind, namespace).list(
                kind,
                namespace=namespace,
                label_selector=label_selector,
                field_selector=field_selector,
            )
        g0 = self._source.current()
        items: List[dict] = []
        rvs: List[int] = []
        for s in self._shards:
            its, rv = s.list(
                kind,
                namespace=namespace,
                label_selector=label_selector,
                field_selector=field_selector,
            )
            items.extend(its)
            rvs.append(rv)
        return items, self._merged_rv(rvs, g0)

    def list_paged(self, *a, **kw):
        # same facade the single store provides: page through list_page
        items: List[dict] = []
        token = None
        while True:
            page, rv, token = self.list_page(*a, continue_from=token, **kw)
            items.extend(page)
            if token is None:
                return items, rv

    def list_page(
        self,
        kind: str,
        namespace: Optional[str] = None,
        label_selector: Selector = None,
        field_selector: Selector = None,
        limit: int = 0,
        continue_from: Optional[Tuple[int, int]] = None,
        copy: bool = True,
    ) -> Tuple[List[dict], int, Optional[Tuple[int, int]]]:
        if not self._fanout(kind, namespace):
            return self._route(kind, namespace).list_page(
                kind,
                namespace=namespace,
                label_selector=label_selector,
                field_selector=field_selector,
                limit=limit,
                continue_from=continue_from,
                copy=copy,
            )
        # a kind read across every shard pages over ONE snapshot of its
        # own, as a single store's LIST does (store.ListSnapshots): the
        # first page cuts every shard, the later ones serve from it
        return list_page_from(
            self._snapshots,
            self._rtype(kind),
            lambda: self._cut_pairs(kind),
            namespace,
            label_selector,
            field_selector,
            limit,
            continue_from,
            copy,
        )

    def _cut_pairs(self, kind: str) -> Tuple[list, int]:
        """Every shard's pairs in key order, at the resume point a
        merged read reports: read-time rvs, like ``list()`` — a write
        that lands on a shard already cut must not push the resume
        point past itself, or a list-then-watch would skip it."""
        g0 = self._source.current()
        pairs: list = []
        rvs: List[int] = []
        for s in self._shards:
            cut, rv = s._cut_pairs(kind)
            pairs.extend(cut)
            rvs.append(rv)
        pairs.sort(key=operator.itemgetter(0))
        return pairs, self._merged_rv(rvs, g0)

    def count(self, kind: str) -> int:
        if not self._rtype(kind).namespaced:
            return self._route(kind, None).count(kind)
        return sum(s.count(kind) for s in self._shards)

    # ---------------------------------------------------------------- watch

    def watch(
        self,
        kind: str,
        namespace: Optional[str] = None,
        since_rv: Optional[int] = None,
        label_selector: Selector = None,
        field_selector: Selector = None,
        status_interest: bool = True,
    ):
        if not self._fanout(kind, namespace) or len(self._shards) == 1:
            return self._route(kind, namespace).watch(
                kind,
                namespace=namespace,
                since_rv=since_rv,
                label_selector=label_selector,
                field_selector=field_selector,
                status_interest=status_interest,
            )
        parts: List[Watcher] = []
        try:
            for s in self._shards:
                shard_since = since_rv
                if (
                    self.unsafe_fanin_stale_resume
                    and since_rv is not None
                    and s.resource_version <= since_rv
                ):
                    # injected regression: "this shard has written
                    # nothing since the resume point, start it from
                    # the beginning" — true for a never-written shard
                    # (rv 0), catastrophically wrong for a caught-up
                    # one, whose whole history replays as duplicates
                    shard_since = 0
                parts.append(
                    s.watch(
                        kind,
                        namespace=namespace,
                        since_rv=shard_since,
                        label_selector=label_selector,
                        field_selector=field_selector,
                        status_interest=status_interest,
                    )
                )
        except Exception:
            # Expired from any shard aborts the merge whole — the
            # consumer re-lists, same answer a single store gives
            for w in parts:
                w.stop()
            raise
        return MergedWatcher(parts)

    # ----------------------------------------------------------- bulk lanes

    def _group_ops(self, ops) -> Dict[int, List[Tuple[int, dict]]]:
        """(shard -> [(original index, op)]); unroutable ops (malformed
        / unknown kind) go to shard 0, whose per-op validation renders
        the same error a single store would."""
        groups: Dict[int, List[Tuple[int, dict]]] = {}
        for i, op in enumerate(ops):
            try:
                shard = self._obj_shard(op) if isinstance(op, dict) else 0
            except NotFound:
                shard = 0
            groups.setdefault(shard, []).append((i, op))
        return groups

    def bulk(
        self,
        ops: List[dict],
        copy_results: bool = True,
        as_user: Optional[str] = None,
        encoded: bool = False,
    ) -> List[dict]:
        """``ResourceStore.bulk`` over the shards that own the ops;
        ``encoded`` entries (JSON bytes) are merged like any others."""
        groups = self._group_ops(ops)
        if not groups:
            return self._shards[0].bulk(
                [], copy_results=copy_results, as_user=as_user
            )
        if len(groups) == 1:
            # the common shard-affine batch: straight to the owning
            # shard's bulk lane (in-process direct dispatch)
            (shard, pairs), = groups.items()
            return self._shards[shard].bulk(
                [op for _, op in pairs],
                copy_results=copy_results,
                as_user=as_user,
                encoded=encoded,
            )
        results: List[Optional[dict]] = [None] * len(ops)
        for shard in sorted(groups):
            pairs = groups[shard]
            out = self._shards[shard].bulk(
                [op for _, op in pairs],
                copy_results=copy_results,
                as_user=as_user,
                encoded=encoded,
            )
            for (i, _op), res in zip(pairs, out):
                results[i] = res
        return results  # type: ignore[return-value]

    def transact(
        self,
        ops: List[dict],
        as_user: Optional[str] = None,
        copy_results: bool = True,
        encoded: bool = False,
    ) -> List[Optional[dict]]:
        ops = list(ops)
        if self.unsafe_split_cross_shard_txns:
            # INJECTED REGRESSION (test-only): per-OP striping splits
            # a shard-affine atomic batch into per-shard sub-txns
            # committed independently (highest shard first, "walking
            # the route table from the top") — an abort or a crash
            # after an earlier sub-txn committed strands a committed
            # prefix, exactly the partial state the typed rejection
            # below makes impossible under the real placement
            buggy: Dict[int, List[Tuple[int, dict]]] = {}
            for i, op in enumerate(ops):
                buggy.setdefault(i % len(self._shards), []).append((i, op))
            results: List[Optional[dict]] = [None] * len(ops)
            for shard in sorted(buggy, reverse=True):
                pairs = buggy[shard]
                out = self._shards[shard].transact(
                    [op for _, op in pairs],
                    as_user=as_user,
                    copy_results=copy_results,
                    encoded=encoded,
                )
                for (i, _op), res in zip(pairs, out):
                    results[i] = res
            return results
        groups = self._group_ops(ops)
        if not groups:
            return self._shards[0].transact(
                [], as_user=as_user, copy_results=copy_results
            )
        if len(groups) > 1:
            first = min(i for pairs in groups.values() for i, _ in pairs)
            home = None
            for shard, pairs in groups.items():
                for i, _op in pairs:
                    if i == first:
                        home = shard
            offender = min(
                i
                for shard, pairs in groups.items()
                if shard != home
                for i, _ in pairs
            )
            raise CrossShardTransaction(
                offender,
                f"txn op {offender}: routes to shard "
                f"{self._obj_shard(ops[offender])}, op 0 to shard {home} "
                "— transactions are single-shard-atomic by design "
                "(keep an atomic batch in one namespace)",
            )
        (shard, pairs), = groups.items()
        return self._shards[shard].transact(
            [op for _, op in pairs],
            as_user=as_user,
            copy_results=copy_results,
            encoded=encoded,
        )

    def shard_bulk(
        self,
        index: int,
        ops: List[dict],
        copy_results: bool = True,
        as_user: Optional[str] = None,
        encoded: bool = False,
    ) -> List[dict]:
        """The per-shard HTTP dispatch lane (``POST /shards/{i}/bulk``):
        the caller routed with its own copy of the route table, the
        shard re-validates ownership — a misrouted op gets a typed
        per-op error instead of landing on (and corrupting the
        placement of) the wrong shard."""
        self._check_index(index)
        checked: List[Tuple[int, dict]] = []
        results: List[Optional[dict]] = [None] * len(ops)
        for i, op in enumerate(ops):
            try:
                owner = self._obj_shard(op) if isinstance(op, dict) else index
            except NotFound:
                owner = index
            if owner != index:
                entry = {
                    "status": "error",
                    "reason": "Misrouted",
                    "error": (
                        f"op {i} belongs to shard {owner}, not {index} "
                        "(stale route table?)"
                    ),
                }
                results[i] = json.dumps(entry).encode() if encoded else entry
            else:
                checked.append((i, op))
        if checked:
            out = self._shards[index].bulk(
                [op for _, op in checked],
                copy_results=copy_results,
                as_user=as_user,
                encoded=encoded,
            )
            for (i, _op), res in zip(checked, out):
                results[i] = res
        return results  # type: ignore[return-value]

    def shard_transact(
        self,
        index: int,
        ops: List[dict],
        as_user: Optional[str] = None,
        copy_results: bool = True,
        encoded: bool = False,
    ) -> List[Optional[dict]]:
        """``POST /shards/{i}/txn``: ownership re-validated for every
        op (atomicity would silently narrow to "the subset that landed
        here" otherwise), then the shard's atomic lane."""
        self._check_index(index)
        for i, op in enumerate(ops):
            try:
                owner = self._obj_shard(op) if isinstance(op, dict) else index
            except NotFound:
                continue  # shard.transact renders the NotFound abort
            if owner != index:
                raise CrossShardTransaction(
                    i,
                    f"txn op {i}: belongs to shard {owner}, posted to "
                    f"shard lane {index}",
                )
        return self._shards[index].transact(
            ops, as_user=as_user, copy_results=copy_results, encoded=encoded
        )

    # ---------------------------------------------------------- batch verbs

    def apply_status_batch(
        self,
        kind: str,
        items: List[tuple],
        exclude=None,
    ) -> list:
        return self._batch_by_shard("apply_status_batch", kind, items, exclude)

    def apply_delete_batch(
        self,
        kind: str,
        items: List[tuple],
        exclude=None,
    ) -> list:
        return self._batch_by_shard("apply_delete_batch", kind, items, exclude)

    def _batch_by_shard(
        self, verb: str, kind: str, items: List[tuple], exclude
    ) -> list:
        """A batch verb (items lead with their namespace) shard by
        shard, in shard order; results align with ``items``."""
        rt = self._rtype(kind)
        n = len(self._shards)
        if not rt.namespaced or n == 1:
            shard = self.shard_for(kind, None)
            return getattr(self._shards[shard], verb)(
                kind, items, exclude=self._exclude_for(exclude, shard)
            )
        groups: Dict[int, List[Tuple[int, Tuple]]] = {}
        for i, item in enumerate(items):
            shard = shard_of(True, rt.kind, item[0], n)
            groups.setdefault(shard, []).append((i, item))
        results: list = [None] * len(items)
        for shard in sorted(groups):
            pairs = groups[shard]
            out = getattr(self._shards[shard], verb)(
                kind,
                [it for _, it in pairs],
                exclude=self._exclude_for(exclude, shard),
            )
            for (i, _it), res in zip(pairs, out):
                results[i] = res
        return results

    @staticmethod
    def _exclude_for(exclude, shard: int):
        if isinstance(exclude, MergedWatcher):
            return exclude.part_for(shard)
        return exclude

    # ------------------------------------------------------------ lifecycle

    def set_crash_hook(self, hook) -> None:
        for s in self._shards:
            s.set_crash_hook(hook)

    def dump_state(self, copy: bool = True) -> dict:
        """Merged snapshot in the single-store shape (``/state``, the
        DST replay-equality probe): shard-major concatenation is
        deterministic because each shard's own dump is.

        Every shard's mutex is held across the walk AND the label read
        (one multi-lock acquirer, same lock class — re-entrancy, not
        inversion), so the cut is rv-consistent: a write landing
        between one shard's dump and the label would otherwise stamp
        rv G onto a merge missing a committed rv <= G — and once
        ``archive_sharded_snapshot`` splits that merge per shard and
        pruning retires the record's segment, ``restore --to-rv``
        would silently rebuild without it (its holes check trusts the
        snapshot label)."""
        with contextlib.ExitStack() as stack:
            for s in self._shards:
                stack.enter_context(s._mut)
            dumps = [s.dump_state(copy=copy) for s in self._shards]
            rv = self.resource_version
        objects: List[dict] = []
        for d in dumps:
            objects.extend(d["objects"])
        return {
            "resourceVersion": rv,
            "uidCounter": max(d["uidCounter"] for d in dumps),
            "types": dumps[0]["types"],
            "objects": objects,
        }

    def restore_state(self, state: dict) -> int:
        """Split a single-store snapshot across the shards by the same
        hash the live traffic uses (:func:`split_state`); registered
        types win over the snapshot's own table for the namespaced
        flag."""
        types = state.get("types", [])

        def namespaced_of(kind: str) -> bool:
            try:
                return self._rtype(kind).namespaced
            except NotFound:
                # type arrives with this snapshot; honor its own flag
                return next(
                    (
                        bool(t.get("namespaced", True))
                        for t in types
                        if t.get("kind") == kind
                    ),
                    True,
                )

        slices = split_state(
            state, len(self._shards), namespaced_of=namespaced_of
        )
        total = 0
        for s, piece in zip(self._shards, slices):
            total += s.restore_state(piece)
        self._source.advance_to(int(state.get("resourceVersion", 0)))
        return total

    # ---------------------------------------------------------- health/stats

    @property
    def resource_version(self) -> int:
        # max covers both wirings: sharded (the source leads every
        # shard) and the 1-shard composition, whose only shard
        # allocates locally and never touches the source
        return max(
            self._source.current(),
            max(s.resource_version for s in self._shards),
        )

    def storage_degraded(self) -> Optional[dict]:
        """Degraded shard set for ``/readyz`` (polling doubles as the
        throttled re-arm probe, per shard).  None while every shard
        accepts writes."""
        degraded: List[int] = []
        first: Optional[dict] = None
        for i, s in enumerate(self._shards):
            deg = s.storage_degraded()
            if deg is not None:
                degraded.append(i)
                if first is None:
                    first = deg
        if first is None:
            return None
        out = dict(first)
        out["shards"] = degraded
        return out

    def probe_writable(self) -> bool:
        ok = True
        for s in self._shards:
            ok = s.probe_writable() and ok
        return ok

    def wal_health(self) -> Optional[dict]:
        """Aggregate WAL surface plus the per-shard breakdown
        (``kwokctl get components`` renders the per-shard column)."""
        per = [s.wal_health() for s in self._shards]
        if all(h is None for h in per):
            return None
        live = [h for h in per if h is not None]
        ages = [
            h["last_fsync_age_s"]
            for h in live
            if h.get("last_fsync_age_s") is not None
        ]
        degraded = [
            {"shard": i, **h["degraded"]}
            for i, h in enumerate(per)
            if h is not None and h.get("degraded")
        ]
        out = {
            "segments": sum(h.get("segments", 0) for h in live),
            "bytes": sum(h.get("bytes", 0) for h in live),
            "last_fsync_age_s": min(ages) if ages else None,
            "enospc_total": sum(h.get("enospc_total", 0) for h in live),
            "fsync_failures_total": sum(
                h.get("fsync_failures_total", 0) for h in live
            ),
            "io_errors_total": sum(h.get("io_errors_total", 0) for h in live),
            "rearms_total": sum(h.get("rearms_total", 0) for h in live),
            "recoveries": sum(h.get("recoveries", 0) for h in live),
            "corruptions": sum(h.get("corruptions", 0) for h in live),
            "missing_rvs": sum(h.get("missing_rvs", 0) for h in live),
            "snapshot_fallbacks": sum(
                h.get("snapshot_fallbacks", 0) for h in live
            ),
            "degraded": (degraded[0] if degraded else None),
            "degraded_shards": [d["shard"] for d in degraded],
            "shards": per,
        }
        return out

    def audit_log(self) -> List[Tuple[str, str, Optional[str]]]:
        out: List[Tuple[str, str, Optional[str]]] = []
        for s in self._shards:
            out.extend(s.audit_log())
        return out

    @property
    def audit_overflow(self) -> int:
        return sum(s.audit_overflow for s in self._shards)

    @property
    def watch_evictions(self) -> int:
        return sum(s.watch_evictions for s in self._shards)

    @property
    def wal_recoveries(self) -> int:
        return sum(s.wal_recoveries for s in self._shards)

    @property
    def wal_corruptions(self) -> int:
        return sum(s.wal_corruptions for s in self._shards)

    @property
    def wal_missing_rvs(self) -> int:
        return sum(s.wal_missing_rvs for s in self._shards)

    @property
    def snapshot_fallbacks(self) -> int:
        return sum(s.snapshot_fallbacks for s in self._shards)
