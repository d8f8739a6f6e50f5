"""Lock-order analyzer: interprocedural may-hold-while-acquiring
cycles are deadlock candidates.

The single store-mutex family the repo has today becomes N per-shard
lock families crossed by router/fan-in threads under the sharded-store
and fleet refactors (ROADMAP.md:53-82), and a latent ABBA inversion
there deadlocks the whole control plane.  Kivi-style mechanical
checking (PAPERS.md:9) is the posture: derive the lock-order graph
from the code, don't trust review to see it.

How it works, over the shared :mod:`kwok_tpu.analysis.callgraph`
artifact:

- every ``threading.Lock/RLock/Condition`` (or
  ``kwok_tpu.utils.locks`` sentinel factory) creation site defines a
  **named lock class** ``module.Class.attr`` — all instances of
  ``ResourceStore._mut`` are one node, the standard lock-order
  abstraction;
- inside each lexical hold (a ``with <lock>:`` body, or a raw
  ``.acquire()`` to end-of-function — an ``__enter__`` that leaves
  the release to ``__exit__``),
  every *direct* nested acquisition and every acquisition in any
  function **transitively reachable** through the call graph adds a
  may-hold-while-acquiring edge ``held -> acquired``, with the witness
  call chain retained for the report;
- a cycle in that graph (Tarjan SCC, self-loops included for
  non-reentrant kinds) is reported as a deadlock candidate with one
  witness site and chain per edge.

Self-edges on re-entrant kinds (RLock, Condition's default RLock) are
legal recursion, not hazards, and are dropped.  The dynamic complement
— the ``KWOK_LOCK_SENTINEL=1`` runtime order sentinel
(``kwok_tpu/utils/locks.py``) — catches the holds this lexical view
cannot see (locks carried across context-manager boundaries,
attribute receivers too dynamic to type).
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from kwok_tpu.analysis import Finding, SourceFile
from kwok_tpu.analysis.callgraph import (
    Acquisition,
    CallGraph,
    _body_calls,
    get_callgraph,
)

RULE = "lock-order"


class _Edge:
    """held -> acquired, with one witness."""

    __slots__ = ("held", "acquired", "path", "line", "chain")

    def __init__(self, held, acquired, path, line, chain):
        self.held = held
        self.acquired = acquired
        self.path = path  # witness file (the holding site)
        self.line = line  # witness line (the holding site)
        self.chain = chain  # [func qnames] from holder to acquirer


def build_lock_graph(cg: CallGraph) -> List[_Edge]:
    """Every may-hold-while-acquiring edge, with witnesses."""
    edges: List[_Edge] = []
    seen: Set[Tuple[str, str]] = set()
    #: func qname -> its acquisitions (anywhere in the body): what a
    #: call into the function may acquire
    acq_of = cg.acquisitions

    for q in sorted(cg.functions):
        fi = cg.functions[q]
        holds = acq_of.get(q, ())
        if not holds:
            continue
        ctx = cg.ctx(q)
        for i, hold in enumerate(holds):
            # (a) direct nested acquisitions within the lexical hold.
            # A multi-item ``with a, b:`` acquires left-to-right on ONE
            # line, so same-With items are ordered by position, not
            # lineno (a same-line ABBA pair is the textbook deadlock)
            scope = hold.node if isinstance(hold.node, (ast.With, ast.AsyncWith)) \
                else fi.node
            for j, other in enumerate(holds):
                if other is hold:
                    continue
                nested = hold.line < other.line <= hold.hold_until
                same_with_later = other.node is hold.node and j > i
                if nested or same_with_later:
                    _add_edge(edges, seen, hold, other.lock, other.kind,
                              fi.path, hold.line, [q])
            # (b) acquisitions reached through calls made under the hold
            callees: Set[str] = set()
            for call in _body_calls(scope):
                if not (hold.line <= call.lineno <= hold.hold_until):
                    continue
                hit, _ = ctx.resolve_call(call)
                callees.update(hit)
            if not callees:
                continue
            reach = set(callees) | cg.reachable(callees)
            acquiring = {f for f in reach if f in acq_of}
            for f in sorted(acquiring):
                chain = cg.sample_path(q, {f}) or [q, f]
                for other in acq_of[f]:
                    _add_edge(edges, seen, hold, other.lock, other.kind,
                              fi.path, hold.line, chain)
    return edges


def _add_edge(edges, seen, hold: Acquisition, acquired: str, kind: str,
              path: str, line: int, chain: List[str]) -> None:
    if hold.lock == acquired:
        # re-entrant kinds recurse legally; a non-reentrant self-edge
        # is a self-deadlock candidate and stays
        if hold.kind != "lock" or kind != "lock":
            return
    key = (hold.lock, acquired)
    if key in seen:
        return
    seen.add(key)
    edges.append(_Edge(hold.lock, acquired, path, line, chain))


def _find_cycles(edges: List[_Edge]) -> List[List[_Edge]]:
    """SCCs of the lock graph, rendered as edge lists (one witness edge
    per ordered pair inside the SCC)."""
    graph: Dict[str, Set[str]] = {}
    by_pair: Dict[Tuple[str, str], _Edge] = {}
    for e in edges:
        graph.setdefault(e.held, set()).add(e.acquired)
        by_pair[(e.held, e.acquired)] = e

    index: Dict[str, int] = {}
    low: Dict[str, int] = {}
    on_stack: Set[str] = set()
    stack: List[str] = []
    counter = [0]
    sccs: List[List[str]] = []

    def strongconnect(v: str) -> None:
        work = [(v, iter(sorted(graph.get(v, ()))))]
        index[v] = low[v] = counter[0]
        counter[0] += 1
        stack.append(v)
        on_stack.add(v)
        while work:
            node, it = work[-1]
            advanced = False
            for w in it:
                if w not in index:
                    index[w] = low[w] = counter[0]
                    counter[0] += 1
                    stack.append(w)
                    on_stack.add(w)
                    work.append((w, iter(sorted(graph.get(w, ())))))
                    advanced = True
                    break
                elif w in on_stack:
                    low[node] = min(low[node], index[w])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == index[node]:
                scc = []
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    scc.append(w)
                    if w == node:
                        break
                sccs.append(scc)

    nodes = set(graph)
    for tgts in graph.values():
        nodes.update(tgts)
    for v in sorted(nodes):
        if v not in index:
            strongconnect(v)

    cycles: List[List[_Edge]] = []
    for scc in sccs:
        members = set(scc)
        if len(scc) == 1:
            v = scc[0]
            e = by_pair.get((v, v))
            if e is not None:
                cycles.append([e])
            continue
        witness = [
            by_pair[(a, b)]
            for a in sorted(members)
            for b in sorted(members)
            if (a, b) in by_pair
        ]
        cycles.append(witness)
    return cycles


def _chain_text(chain: List[str]) -> str:
    if len(chain) <= 1:
        return ""
    short = [c.split(".", 1)[-1] if c.startswith("kwok_tpu.") else c
             for c in chain]
    return " via " + " -> ".join(short)


def analyze(files: Iterable[SourceFile], config) -> List[Finding]:
    files = [sf for sf in files if sf.path.startswith("kwok_tpu/")]
    if not files:
        return []
    cg = get_callgraph(files, config)
    edges = build_lock_graph(cg)
    findings: List[Finding] = []
    for cycle in _find_cycles(edges):
        locks = sorted({e.held for e in cycle} | {e.acquired for e in cycle})
        parts = [
            f"{e.held} -> {e.acquired} at {e.path}:{e.line}{_chain_text(e.chain)}"
            for e in cycle
        ]
        anchor = min(cycle, key=lambda e: (e.path, e.line))
        findings.append(
            Finding(
                rule=RULE,
                path=anchor.path,
                line=anchor.line,
                message=(
                    "deadlock candidate: lock-order cycle between "
                    + ", ".join(locks)
                    + " ["
                    + "; ".join(parts)
                    + "] — break the cycle by ordering the acquisitions "
                    "or narrowing a hold (suppress with the invariant "
                    "that makes it safe)"
                ),
            )
        )
    return findings
