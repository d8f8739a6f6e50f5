"""Project-wide conservative call graph shared by the lock analyzers.

The reference gates its concurrency invariants with whole-program
tooling (golangci-lint's SSA-based passes run over every package at
once; PARITY.md:175) — a per-file view is structurally blind to the
cross-module acquisition chains the sharded-store refactor will create
(ROADMAP.md:53-82).  This module is the Python stand-in for that
package load: one parse-once pass over the already-shared
:class:`~kwok_tpu.analysis.SourceFile` list builds

- a **name-resolution environment** per module (import aliases, class
  and function tables, attribute and parameter types gathered from
  annotations and ``self.x = Class()`` assignments),
- a **call graph** over module-qualified function paths
  (``kwok_tpu.cluster.store.ResourceStore.create``), resolved only
  where a qualified path is derivable — unresolvable dynamic calls are
  dropped rather than guessed, so downstream rules err toward missed
  edges, never invented ones, and
- a **lock table**: every ``threading.Lock/RLock/Condition`` (and
  ``kwok_tpu.utils.locks`` sentinel factory) creation site becomes a
  named lock class ``module.Class.attr``, with the acquisition sites
  (``with``-blocks and raw ``.acquire()`` holds) recorded per
  function.

Consumers: ``lock_order`` derives the may-hold-while-acquiring graph
from the lock table + call-graph reachability; ``lock_discipline``
closes its blocking-I/O set over the edges.  Built once per driver run
and memoized on the Config (the same lifetime the layering import
graph enjoys); ``build_seconds`` is exported through the CLI's JSON
output so the analysis-pass cost stays visible.
"""

from __future__ import annotations

import ast
import hashlib
import os
import pickle
import time
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from kwok_tpu.analysis import SourceFile, dotted_name

#: lock-constructor terminals -> lock kind (re-entrancy matters to the
#: order analysis: an RLock self-edge is legal, a Lock self-edge is a
#: guaranteed single-thread deadlock)
_LOCK_CTORS = {
    "Lock": "lock",
    "RLock": "rlock",
    "Condition": "rlock",  # Condition() wraps an RLock by default
}

#: kwok_tpu.utils.locks sentinel factories (adoption replaces direct
#: threading constructors at the instrumented sites)
_SENTINEL_CTORS = {
    "make_lock": "lock",
    "make_rlock": "rlock",
    "make_condition": "rlock",
}


def _module_name(path: str) -> Optional[str]:
    if not path.startswith("kwok_tpu/") or not path.endswith(".py"):
        return None
    mod = path[: -len(".py")].replace("/", ".")
    if mod.endswith(".__init__"):
        mod = mod[: -len(".__init__")]
    return mod


def _annotation_names(node: Optional[ast.AST]) -> List[str]:
    """Candidate class names mentioned by an annotation, outermost
    first — handles ``Optional["ResourceStore"]``, ``"Clock"``,
    ``Dict[str, Pod]`` (all Name/Attribute/str leaves are candidates;
    resolution against the class tables filters the noise)."""
    if node is None:
        return []
    out: List[str] = []
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            out.append(n.id)
        elif isinstance(n, ast.Attribute):
            d = dotted_name(n)
            if d:
                out.append(d)
        elif isinstance(n, ast.Constant) and isinstance(n.value, str):
            # quoted forward reference; may itself be a subscripted
            # expression — take bare identifiers only
            for part in n.value.replace("[", " ").replace("]", " ").split():
                token = part.strip(",\"' ")
                if token.isidentifier() or all(
                    p.isidentifier() for p in token.split(".") if p
                ):
                    out.append(token)
    return out


class FuncInfo:
    __slots__ = ("qname", "module", "cls", "path", "node")

    def __init__(self, qname, module, cls, path, node):
        self.qname = qname  # module.[Class.]name
        self.module = module
        self.cls = cls  # class qname or None
        self.path = path
        self.node = node


class ClassInfo:
    __slots__ = ("qname", "module", "path", "node", "methods", "bases",
                 "attr_types", "lock_attrs", "named_locks")

    def __init__(self, qname, module, path, node):
        self.qname = qname
        self.module = module
        self.path = path
        self.node = node
        self.methods: Dict[str, str] = {}  # name -> func qname
        self.bases: List[str] = []  # raw dotted names, resolved later
        #: attr name -> set of candidate class qnames
        self.attr_types: Dict[str, Set[str]] = {}
        #: attr name -> lock kind for lock-creating assignments
        self.lock_attrs: Dict[str, str] = {}
        #: the subset of lock_attrs created through the named
        #: ``kwok_tpu.utils.locks`` sentinel factories — the classes the
        #: guarded-by analyzer scopes to (adopting the factory is the
        #: opt-in to lockset checking)
        self.named_locks: Set[str] = set()


class ModuleEnv:
    __slots__ = ("name", "path", "imports", "functions", "classes",
                 "module_locks")

    def __init__(self, name, path):
        self.name = name
        self.path = path
        #: bound alias -> dotted target ("from kwok_tpu.x import y as z"
        #: binds z -> kwok_tpu.x.y; "import threading" binds
        #: threading -> threading)
        self.imports: Dict[str, str] = {}
        self.functions: Dict[str, str] = {}  # local name -> func qname
        self.classes: Dict[str, str] = {}  # local name -> class qname
        self.module_locks: Dict[str, str] = {}  # global name -> kind


class Acquisition:
    """One lock-acquisition site inside a function."""

    __slots__ = ("lock", "kind", "line", "hold_until", "node")

    def __init__(self, lock, kind, line, hold_until, node):
        self.lock = lock  # lock class id: module.Class.attr
        self.kind = kind  # lock | rlock
        self.line = line
        #: last line of the lexical hold (with-block end; raw .acquire()
        #: conservatively holds to the end of the function)
        self.hold_until = hold_until
        self.node = node  # the with-statement or acquire call


class CallGraph:
    def __init__(self) -> None:
        self.modules: Dict[str, ModuleEnv] = {}
        self.functions: Dict[str, FuncInfo] = {}
        self.classes: Dict[str, ClassInfo] = {}
        #: caller qname -> callee qnames (project functions only)
        self.edges: Dict[str, Set[str]] = {}
        #: caller qname -> [(callee qname, line)] (evidence for chains)
        self.edge_sites: Dict[str, List[Tuple[str, int]]] = {}
        #: lock class id -> kind
        self.locks: Dict[str, str] = {}
        #: func qname -> acquisition sites
        self.acquisitions: Dict[str, List[Acquisition]] = {}
        self.build_seconds: float = 0.0
        #: "hit" / "miss" when a disk cache was consulted, else None
        self.cache_state: Optional[str] = None
        self._ctx_cache: Dict[str, "_Ctx"] = {}

    def ctx(self, qname: str) -> "_Ctx":
        """Memoized per-function resolution context — the local-type
        scan is pure on the parsed AST, so one instance serves every
        analyzer in the run."""
        c = self._ctx_cache.get(qname)
        if c is None:
            c = self._ctx_cache[qname] = _Ctx(self, self.functions[qname])
        return c

    # ------------------------------------------------------- reachability

    def reachable(self, roots: Iterable[str]) -> Set[str]:
        """Transitive closure of ``edges`` from ``roots`` (roots not
        included unless reached)."""
        seen: Set[str] = set()
        stack = [c for r in roots for c in self.edges.get(r, ())]
        while stack:
            f = stack.pop()
            if f in seen:
                continue
            seen.add(f)
            stack.extend(self.edges.get(f, ()))
        return seen

    def closure_reaching(self, targets: Set[str]) -> Set[str]:
        """All functions that can reach a target through ``edges``
        (targets included) — the interprocedural taint set."""
        rev: Dict[str, Set[str]] = {}
        for src, dsts in self.edges.items():
            for d in dsts:
                rev.setdefault(d, set()).add(src)
        seen = set(targets)
        stack = list(targets)
        while stack:
            f = stack.pop()
            for caller in rev.get(f, ()):
                if caller not in seen:
                    seen.add(caller)
                    stack.append(caller)
        return seen

    def sample_path(self, src: str, dst_set: Set[str]) -> List[str]:
        """One shortest edge path from ``src`` into ``dst_set`` (BFS),
        as a qname list starting at src; [] when unreachable."""
        if src in dst_set:
            return [src]
        prev: Dict[str, str] = {}
        seen = {src}
        queue = [src]
        while queue:
            nxt: List[str] = []
            for f in queue:
                for c in sorted(self.edges.get(f, ())):
                    if c in seen:
                        continue
                    prev[c] = f
                    if c in dst_set:
                        path = [c]
                        while path[-1] != src:
                            path.append(prev[path[-1]])
                        return list(reversed(path))
                    seen.add(c)
                    nxt.append(c)
            queue = nxt
        return []

    # -------------------------------------------------------- resolution

    def method_of(self, cls_qname: str, name: str) -> Optional[str]:
        """Method lookup through the (resolved) base chain."""
        seen: Set[str] = set()
        stack = [cls_qname]
        while stack:
            c = stack.pop(0)
            if c in seen:
                continue
            seen.add(c)
            ci = self.classes.get(c)
            if ci is None:
                continue
            if name in ci.methods:
                return ci.methods[name]
            stack.extend(ci.bases)
        return None

    def attr_types_of(self, cls_qname: str, attr: str) -> Set[str]:
        seen: Set[str] = set()
        out: Set[str] = set()
        stack = [cls_qname]
        while stack:
            c = stack.pop(0)
            if c in seen:
                continue
            seen.add(c)
            ci = self.classes.get(c)
            if ci is None:
                continue
            out.update(ci.attr_types.get(attr, ()))
            stack.extend(ci.bases)
        return out

    def lock_attr_kind(self, cls_qname: str, attr: str) -> Optional[Tuple[str, str]]:
        """(owning class qname, kind) for a lock attribute, searching
        the base chain — the lock class is named after the class that
        CREATES it, so subclasses share the parent's lock identity."""
        seen: Set[str] = set()
        stack = [cls_qname]
        while stack:
            c = stack.pop(0)
            if c in seen:
                continue
            seen.add(c)
            ci = self.classes.get(c)
            if ci is None:
                continue
            if attr in ci.lock_attrs:
                return c, ci.lock_attrs[attr]
            stack.extend(ci.bases)
        return None


class _Ctx:
    """Per-function resolution context: parameter + local variable
    types, bound to the module env and enclosing class."""

    def __init__(self, cg: CallGraph, fi: FuncInfo):
        self.cg = cg
        self.env = cg.modules[fi.module]
        self.fi = fi
        self.var_types: Dict[str, Set[str]] = {}
        node = fi.node
        args = node.args
        all_args = list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs)
        for a in all_args:
            types = self._resolve_class_names(_annotation_names(a.annotation))
            if types:
                self.var_types[a.arg] = types
        # single forward pass over top-level assignments: x = Class(),
        # x = annotated_param, x = self.attr
        for stmt in ast.walk(node):
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 and isinstance(
                stmt.targets[0], ast.Name
            ):
                types = self.expr_types(stmt.value)
                if types:
                    self.var_types.setdefault(stmt.targets[0].id, set()).update(types)
            elif isinstance(stmt, ast.AnnAssign) and isinstance(
                stmt.target, ast.Name
            ):
                types = self._resolve_class_names(
                    _annotation_names(stmt.annotation)
                )
                if types:
                    self.var_types.setdefault(stmt.target.id, set()).update(types)

    def _resolve_class_names(self, names: Sequence[str]) -> Set[str]:
        out: Set[str] = set()
        for n in names:
            q = self._class_qname(n)
            if q:
                out.add(q)
        return out

    def _class_qname(self, name: str) -> Optional[str]:
        """A (possibly dotted) source-level name -> project class qname."""
        if name in self.env.classes:
            return self.env.classes[name]
        if name in self.env.imports:
            tgt = self.env.imports[name]
            mod, _, leaf = tgt.rpartition(".")
            tenv = self.cg.modules.get(mod)
            if tenv and leaf in tenv.classes:
                return tenv.classes[leaf]
            if tgt in self.cg.classes:
                return tgt
        if "." in name:
            base, _, leaf = name.rpartition(".")
            tgt = self.env.imports.get(base) or base
            tenv = self.cg.modules.get(tgt)
            if tenv and leaf in tenv.classes:
                return tenv.classes[leaf]
        return None

    # ------------------------------------------------------ typing exprs

    def expr_types(self, expr: ast.AST) -> Set[str]:
        """Candidate project-class types of an expression (empty when
        unknown — never guessed)."""
        if isinstance(expr, ast.BoolOp):
            out: Set[str] = set()
            for v in expr.values:
                out.update(self.expr_types(v))
            return out
        if isinstance(expr, ast.IfExp):
            return self.expr_types(expr.body) | self.expr_types(expr.orelse)
        if isinstance(expr, ast.Name):
            if expr.id == "self" and self.fi.cls:
                return {self.fi.cls}
            return set(self.var_types.get(expr.id, ()))
        if isinstance(expr, ast.Attribute):
            base_types = self.expr_types(expr.value)
            out = set()
            for b in base_types:
                out.update(self.cg.attr_types_of(b, expr.attr))
            return out
        if isinstance(expr, ast.Call):
            _, constructed = self.resolve_call(expr)
            return constructed
        return set()

    # ------------------------------------------------------ call targets

    def resolve_call(self, call: ast.Call) -> Tuple[Set[str], Set[str]]:
        """(callee qnames, constructed class qnames) for one call."""
        func = call.func
        callees: Set[str] = set()
        constructed: Set[str] = set()
        if isinstance(func, ast.Name):
            name = func.id
            if name in self.env.functions:
                callees.add(self.env.functions[name])
            elif name in self.env.classes:
                constructed.add(self.env.classes[name])
            elif name in self.env.imports:
                tgt = self.env.imports[name]
                mod, _, leaf = tgt.rpartition(".")
                tenv = self.cg.modules.get(mod)
                if tenv:
                    if leaf in tenv.functions:
                        callees.add(tenv.functions[leaf])
                    elif leaf in tenv.classes:
                        constructed.add(tenv.classes[leaf])
        elif isinstance(func, ast.Attribute):
            dotted = dotted_name(func)
            if dotted:
                hit = self._resolve_dotted_callable(dotted)
                if hit is not None:
                    kind, q = hit
                    if kind == "func":
                        callees.add(q)
                    else:
                        constructed.add(q)
            if not callees and not constructed:
                # method call through a typed receiver
                for t in self.expr_types(func.value):
                    m = self.cg.method_of(t, func.attr)
                    if m:
                        callees.add(m)
        for c in constructed:
            init = self.cg.method_of(c, "__init__")
            if init:
                callees.add(init)
        return callees, constructed

    def _resolve_dotted_callable(self, dotted: str) -> Optional[Tuple[str, str]]:
        """``alias.attr[.attr2]`` against the import table: returns
        ("func"|"class", qname) or None."""
        parts = dotted.split(".")
        for cut in range(len(parts) - 1, 0, -1):
            base = ".".join(parts[:cut])
            tgt = self.env.imports.get(base)
            if tgt is None:
                continue
            rest = parts[cut:]
            tenv = self.cg.modules.get(tgt)
            if tenv is None:
                # target may itself be module.Class (from m import C)
                mod, _, leaf = tgt.rpartition(".")
                tenv2 = self.cg.modules.get(mod)
                if tenv2 and leaf in tenv2.classes and len(rest) == 1:
                    m = self.cg.method_of(tenv2.classes[leaf], rest[0])
                    if m:
                        return "func", m
                return None
            if len(rest) == 1:
                if rest[0] in tenv.functions:
                    return "func", tenv.functions[rest[0]]
                if rest[0] in tenv.classes:
                    return "class", tenv.classes[rest[0]]
            elif len(rest) == 2 and rest[0] in tenv.classes:
                m = self.cg.method_of(tenv.classes[rest[0]], rest[1])
                if m:
                    return "func", m
        return None

    # ------------------------------------------------------- lock idents

    def resolve_lock(self, expr: ast.AST) -> Optional[Tuple[str, str]]:
        """(lock class id, kind) for an acquisition receiver, or None
        when the receiver is not a statically-known lock."""
        if isinstance(expr, ast.Name):
            kind = self.env.module_locks.get(expr.id)
            if kind:
                return f"{self.env.name}.{expr.id}", kind
            return None
        if isinstance(expr, ast.Attribute):
            for t in self.expr_types(expr.value):
                hit = self.cg.lock_attr_kind(t, expr.attr)
                if hit:
                    owner, kind = hit
                    return f"{owner}.{expr.attr}", kind
        return None


def _lock_ctor_info(call: ast.Call, env: ModuleEnv) -> Optional[Tuple[str, bool]]:
    """(kind, named) when ``call`` constructs a lock: ``named`` is True
    for the ``kwok_tpu.utils.locks`` sentinel factories, False for
    direct ``threading.Lock/RLock/Condition`` (or bare imports)."""
    func = call.func
    name = None
    if isinstance(func, ast.Attribute):
        d = dotted_name(func)
        if d.startswith("threading."):
            name = d[len("threading."):]
        elif d.startswith("locks."):
            name = d[len("locks."):]
    elif isinstance(func, ast.Name):
        tgt = env.imports.get(func.id, "")
        if tgt.startswith("threading.") or tgt.startswith("kwok_tpu.utils.locks."):
            name = func.id
    if name is None:
        return None
    kind = _LOCK_CTORS.get(name)
    if kind is not None:
        return kind, False
    kind = _SENTINEL_CTORS.get(name)
    if kind is not None:
        return kind, True
    return None


def _lock_ctor_kind(call: ast.Call, env: ModuleEnv) -> Optional[str]:
    """Lock kind when ``call`` constructs a lock (named or not)."""
    hit = _lock_ctor_info(call, env)
    return hit[0] if hit else None


def _iter_defs(tree: ast.Module):
    """(class node or None, func node) for module-level functions and
    class-body methods (nested defs excluded: they run on their
    enclosing function's stack and are walked as part of its body)."""
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield None, node
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield node, sub


def _body_calls(fn: ast.AST):
    """Call nodes in a function body, nested defs/lambdas excluded."""

    def walk(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                continue
            if isinstance(child, ast.Call):
                yield child
            yield from walk(child)

    yield from walk(fn)


def build_callgraph(files: Iterable[SourceFile]) -> CallGraph:
    t0 = time.monotonic()
    cg = CallGraph()
    files = [sf for sf in files if _module_name(sf.path)]

    # ---- pass 1: module envs, class/function tables
    for sf in files:
        mod = _module_name(sf.path)
        env = ModuleEnv(mod, sf.path)
        cg.modules[mod] = env
        for node in sf.tree.body:
            if isinstance(node, ast.Import):
                for alias in node.names:
                    env.imports[alias.asname or alias.name] = alias.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for alias in node.names:
                    env.imports[alias.asname or alias.name] = (
                        f"{node.module}.{alias.name}"
                    )
        for cls_node, fn in _iter_defs(sf.tree):
            if cls_node is None:
                q = f"{mod}.{fn.name}"
                env.functions.setdefault(fn.name, q)
                cg.functions[q] = FuncInfo(q, mod, None, sf.path, fn)
            else:
                cq = f"{mod}.{cls_node.name}"
                if cq not in cg.classes:
                    ci = ClassInfo(cq, mod, sf.path, cls_node)
                    cg.classes[cq] = ci
                    env.classes[cls_node.name] = cq
                    for b in cls_node.bases:
                        d = dotted_name(b)
                        if d:
                            ci.bases.append(d)
                ci = cg.classes[cq]
                q = f"{cq}.{fn.name}"
                ci.methods.setdefault(fn.name, q)
                cg.functions[q] = FuncInfo(q, mod, cq, sf.path, fn)

    # ---- pass 2: resolve bases; class attr types + lock attrs;
    #      module-level locks
    for ci in cg.classes.values():
        env = cg.modules[ci.module]
        resolved: List[str] = []
        for raw in ci.bases:
            # same resolution a _Ctx would do, without per-function state
            if raw in env.classes:
                resolved.append(env.classes[raw])
            elif raw in env.imports and env.imports[raw] in cg.classes:
                resolved.append(env.imports[raw])
            else:
                mod_part, _, leaf = raw.rpartition(".")
                tgt = env.imports.get(mod_part)
                tenv = cg.modules.get(tgt) if tgt else None
                if tenv and leaf in tenv.classes:
                    resolved.append(tenv.classes[leaf])
        ci.bases = resolved

    for sf in files:
        mod = _module_name(sf.path)
        env = cg.modules[mod]
        for node in sf.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 and isinstance(
                node.targets[0], ast.Name
            ) and isinstance(node.value, ast.Call):
                kind = _lock_ctor_kind(node.value, env)
                if kind:
                    env.module_locks[node.targets[0].id] = kind
                    cg.locks[f"{mod}.{node.targets[0].id}"] = kind

    # attr types need _Ctx (param annotations), so run them with a
    # throwaway context per method; lock attrs are plain ctor matches
    for ci in cg.classes.values():
        env = cg.modules[ci.module]
        for mname, mq in ci.methods.items():
            fi = cg.functions[mq]
            ctx = None
            for stmt in ast.walk(fi.node):
                if not (
                    isinstance(stmt, ast.Assign)
                    and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Attribute)
                    and isinstance(stmt.targets[0].value, ast.Name)
                    and stmt.targets[0].value.id == "self"
                ):
                    continue
                attr = stmt.targets[0].attr
                if isinstance(stmt.value, ast.Call):
                    hit = _lock_ctor_info(stmt.value, env)
                    if hit:
                        kind, named = hit
                        ci.lock_attrs.setdefault(attr, kind)
                        if named:
                            ci.named_locks.add(attr)
                        cg.locks.setdefault(f"{ci.qname}.{attr}", kind)
                        continue
                if ctx is None:
                    ctx = _Ctx(cg, fi)
                types = ctx.expr_types(stmt.value)
                if types:
                    ci.attr_types.setdefault(attr, set()).update(types)

    # ---- pass 3: call edges + acquisition sites
    for q, fi in cg.functions.items():
        ctx = cg.ctx(q)
        edges = cg.edges.setdefault(q, set())
        sites = cg.edge_sites.setdefault(q, [])
        for call in _body_calls(fi.node):
            callees, _ = ctx.resolve_call(call)
            for c in callees:
                if c != q:
                    if c not in edges:
                        sites.append((c, call.lineno))
                    edges.add(c)
        acqs: List[Acquisition] = []
        for node in ast.walk(fi.node):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    hit = ctx.resolve_lock(item.context_expr)
                    if hit:
                        acqs.append(
                            Acquisition(
                                hit[0], hit[1], node.lineno,
                                getattr(node, "end_lineno", node.lineno), node,
                            )
                        )
            elif (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "acquire"
            ):
                hit = ctx.resolve_lock(node.func.value)
                if hit:
                    # a raw acquire holds (conservatively) to the end of
                    # the function — an __enter__ that leaves the
                    # release to __exit__ holds past it
                    acqs.append(
                        Acquisition(
                            hit[0], hit[1], node.lineno,
                            getattr(fi.node, "end_lineno", node.lineno), node,
                        )
                    )
        if acqs:
            cg.acquisitions[q] = acqs

    cg.build_seconds = time.monotonic() - t0
    return cg


def _graph_digest(files: List[SourceFile]) -> str:
    """Content identity of a walked file set: CACHE_VERSION + each
    file's path and source hash.  Any rule-semantics change bumps
    CACHE_VERSION (kwok_tpu/analysis/driver.py), any edit changes a
    source hash — either invalidates the persisted graph."""
    from kwok_tpu.analysis.driver import CACHE_VERSION

    h = hashlib.sha256()
    h.update(f"callgraph-v{CACHE_VERSION}".encode())
    for sf in sorted(files, key=lambda s: s.path):
        h.update(sf.path.encode())
        h.update(hashlib.sha256(sf.source.encode()).digest())
    return h.hexdigest()


def _node_bearers(cg: CallGraph):
    """Every (object, path) whose ``node`` attribute holds an AST node
    — the part of the graph that must not be pickled (AST unpickling
    costs nearly as much as a rebuild; a walk-index locator into the
    freshly parsed trees is tiny and reattaches in milliseconds)."""
    for fi in cg.functions.values():
        yield fi, fi.path
    for ci in cg.classes.values():
        yield ci, ci.path
    for q, accs in cg.acquisitions.items():
        path = cg.functions[q].path
        for a in accs:
            yield a, path


def _load_graph(
    path: str, digest: str, files: List[SourceFile]
) -> Optional[CallGraph]:
    try:
        with open(path, "rb") as f:
            payload = pickle.load(f)
    except Exception:  # corrupt/stale/foreign file: rebuild
        return None
    if not isinstance(payload, dict) or payload.get("digest") != digest:
        return None
    cg = payload.get("graph")
    if not isinstance(cg, CallGraph):
        return None
    cg._ctx_cache = {}
    # reattach AST nodes: a digest match means byte-identical sources,
    # so each tree's ast.walk order matches the one recorded at save
    by_path = {sf.path: sf for sf in files}
    walks: Dict[str, List[ast.AST]] = {}
    try:
        for obj, p in _node_bearers(cg):
            nodes = walks.get(p)
            if nodes is None:
                nodes = walks[p] = list(ast.walk(by_path[p].tree))
            obj.node = nodes[obj.node]
    except (KeyError, IndexError, TypeError):
        return None  # locator drift: treat as a miss
    return cg


def _save_graph(
    path: str, digest: str, cg: CallGraph, files: List[SourceFile]
) -> None:
    indexes: Dict[str, Dict[int, int]] = {}
    for sf in files:
        indexes[sf.path] = {
            id(n): i for i, n in enumerate(ast.walk(sf.tree))
        }
    saved = []
    for obj, p in _node_bearers(cg):
        idx = indexes.get(p, {}).get(id(obj.node))
        if idx is None:
            # node not from these trees — restore and don't persist
            for prev, node in saved:
                prev.node = node
            return
        saved.append((obj, obj.node))
        obj.node = idx
    ctxs = cg._ctx_cache
    cg._ctx_cache = {}  # per-run resolution contexts don't persist
    try:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            pickle.dump({"digest": digest, "graph": cg}, f)
        os.replace(tmp, path)
    except (OSError, pickle.PicklingError):
        pass  # cache is best-effort; next run just rebuilds
    finally:
        cg._ctx_cache = ctxs
        for obj, node in saved:
            obj.node = node


def get_callgraph(files: List[SourceFile], config) -> CallGraph:
    """Build-once accessor: memoized on the Config object (one driver
    run = one Config = one shared graph across analyzers).  Keyed on
    (path, source length) so each analyzer's own filtered COPY of the
    walked list still hits the cache — identity of the list object is
    an accident of the call site, the file set is not.

    When the Config carries a ``graph_cache_path`` (the CLI derives it
    from ``--cache``), the built graph also persists to disk keyed on
    the walked files' content hashes + the driver CACHE_VERSION —
    across runs the ~second-scale build collapses to an unpickle
    (``callgraph_build_seconds`` + ``callgraph_cache`` in ``--format
    json`` show the hit/miss)."""
    key = tuple((sf.path, len(sf.source)) for sf in files)
    cached = getattr(config, "_callgraph", None)
    if cached is not None and getattr(config, "_callgraph_key", None) == key:
        return cached
    cg = None
    disk = getattr(config, "graph_cache_path", None)
    digest = _graph_digest(files) if disk else ""
    if disk and os.path.exists(disk):
        t0 = time.monotonic()
        cg = _load_graph(disk, digest, files)
        if cg is not None:
            cg.build_seconds = time.monotonic() - t0
            cg.cache_state = "hit"
    if cg is None:
        cg = build_callgraph(files)
        if disk:
            cg.cache_state = "miss"
            _save_graph(disk, digest, cg, files)
    config._callgraph = cg
    config._callgraph_key = key
    return cg
