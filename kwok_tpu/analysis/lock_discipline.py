"""Lock-discipline analyzer: structured acquire/release + no blocking
I/O while holding a lock.

Two rules, both born from real incidents in this repo (CHANGES.md:5
records the spdy ``_wlock``-across-compress+send fix that set the
precedent):

- **raw-acquire**: a bare ``X.acquire()`` call must be immediately
  followed by a ``try:`` whose ``finally`` releases the same lock (or
  be rewritten as ``with X:``).  The one sanctioned exception is a
  lock deliberately held across a context-manager boundary (an
  ``__enter__`` that acquires and leaves the release to ``__exit__``),
  which carries an inline suppression explaining itself.
- **blocking-under-lock**: inside a ``with <lock>:`` block, calls that
  can block on the outside world — ``time.sleep``, ``subprocess.*``,
  socket ``sendall``/``send``/``recv``/``connect``/``accept`` — stall
  every other thread contending for that lock.  The sanctioned
  precedent is the SPDY header path (``utils/spdyproto.py``): the
  zlib header-compressor is stateful, so compress+send MUST happen
  under one continuous ``_wlock`` hold or the peer's shared inflater
  desyncs; those sites carry inline suppressions citing that reason.
  ``<lock>.wait(...)`` (condition-variable wait) releases the lock and
  is always allowed.

Lock receivers are recognized lexically: a ``with`` context expression
whose terminal identifier matches ``lock``/``mutex``/``mut``/``cv``/
``cond`` (``self._wlock``, ``store._mut``, ``self._cv`` ...).  The
blocking-call set closes over the **project-wide call graph**
(:mod:`kwok_tpu.analysis.callgraph`): a function whose body performs
blocking I/O taints every resolvable call chain that reaches it, so a
``with self._mut:`` body calling ``self._client.request`` that bottoms
out in ``sock.sendall`` three modules away fires here — the same
cross-module chains the per-shard lock families of ROADMAP.md:53-82
will multiply.  A same-module lexical fixpoint (the pre-callgraph
behavior) is kept as a fallback for receivers too dynamic to resolve.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from kwok_tpu.analysis import Finding, SourceFile, dotted_name, terminal_name
from kwok_tpu.analysis.callgraph import _body_calls, get_callgraph

RULE = "lock-discipline"

_LOCKISH = re.compile(r"(?:^|_)(?:w?lock|mut(?:ex)?|cv|cond)$")

#: attribute-call names that block on the outside world
_BLOCKING_ATTRS = {"sendall", "send", "recv", "recv_into", "connect", "accept"}
#: ``.write()``/``.flush()``/``.read()`` block too when the receiver is
#: a socket or a socket file wrapper (wfile/rfile/makefile) — plain
#: buffer/StringIO writes are fine, so this keys on the receiver name
_BLOCKING_STREAM_ATTRS = {"write", "flush", "read", "readline"}
_STREAMISH = re.compile(r"(?:^|_)(?:[wr]file|sock(?:et)?|conn(?:ection)?)$")
#: dotted-call prefixes that block
_BLOCKING_DOTTED = (
    "time.sleep",
    "subprocess.",
    "socket.create_connection",
)


def _lockish(node: ast.AST) -> bool:
    return bool(_LOCKISH.search(terminal_name(node).lower()))


def _recv_text(node: ast.AST) -> str:
    """Stable text of an acquire/release receiver for matching."""
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover - unparse is total on 3.9+
        return ""


class _ClassMethods(ast.NodeVisitor):
    """Map method name -> FunctionDef per class plus module-level funcs."""

    def __init__(self) -> None:
        self.methods: Dict[str, List[ast.FunctionDef]] = {}

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self.methods.setdefault(node.name, []).append(node)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]


def _direct_blocking_call(call: ast.Call) -> Optional[str]:
    """The blocking-call description when ``call`` itself blocks."""
    func = call.func
    if isinstance(func, ast.Attribute):
        dotted = dotted_name(func)
        for prefix in _BLOCKING_DOTTED:
            if dotted == prefix or (prefix.endswith(".") and dotted.startswith(prefix)):
                return dotted
        if func.attr in _BLOCKING_ATTRS:
            # `<lock-or-cv>.wait()` is not here (releases the lock);
            # generator `.send(...)` is indistinguishable lexically and
            # rare enough that a suppression is the right escape hatch
            return dotted_name(func) or func.attr
        if func.attr in _BLOCKING_STREAM_ATTRS and _STREAMISH.search(
            terminal_name(func.value).lower()
        ):
            return dotted_name(func) or func.attr
    elif isinstance(func, ast.Name) and func.id == "sleep":
        return "sleep"
    return None


def _blocking_helper_names(tree: ast.Module) -> Set[str]:
    """Function/method names whose bodies block, closed to a fixpoint
    (one module = one closure domain; cross-module helpers are beyond
    a linter's pay grade and get caught at their own definition)."""
    cm = _ClassMethods()
    cm.visit(tree)
    blocking: Set[str] = set()
    changed = True
    while changed:
        changed = False
        for name, defs in cm.methods.items():
            if name in blocking:
                continue
            for fn in defs:
                hit = False
                for node in ast.walk(fn):
                    if not isinstance(node, ast.Call):
                        continue
                    if _direct_blocking_call(node) is not None:
                        hit = True
                        break
                    callee = node.func
                    if (
                        isinstance(callee, ast.Attribute)
                        and isinstance(callee.value, ast.Name)
                        and callee.value.id == "self"
                        and callee.attr in blocking
                    ):
                        hit = True
                        break
                    if isinstance(callee, ast.Name) and callee.id in blocking:
                        hit = True
                        break
                if hit:
                    blocking.add(name)
                    changed = True
                    break
    return blocking


def _check_with_blocks(
    sf: SourceFile, tree: ast.Module, helpers: Set[str]
) -> List[Finding]:
    findings: List[Finding] = []

    def iter_immediate(stmt: ast.AST):
        """Walk a statement without descending into nested function
        defs — code inside a def under a lock runs later, not now."""
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                continue
            yield child
            yield from iter_immediate(child)

    def scan_body(body: List[ast.stmt], lock_text: str) -> None:
        for stmt in body:
            for node in [stmt, *iter_immediate(stmt)]:
                if not isinstance(node, ast.Call):
                    continue
                desc = _direct_blocking_call(node)
                if desc is None:
                    callee = node.func
                    if (
                        isinstance(callee, ast.Attribute)
                        and isinstance(callee.value, ast.Name)
                        and callee.value.id == "self"
                        and callee.attr in helpers
                    ):
                        desc = f"self.{callee.attr}() (blocks transitively)"
                    elif isinstance(callee, ast.Name) and callee.id in helpers:
                        desc = f"{callee.id}() (blocks transitively)"
                if desc is None:
                    continue
                # condition-variable wait on the held lock is the one
                # blocking call that RELEASES it — always fine
                if desc.endswith(".wait"):
                    continue
                findings.append(
                    Finding(
                        rule=RULE,
                        path=sf.path,
                        line=node.lineno,
                        message=(
                            f"blocking call {desc} while holding "
                            f"{lock_text} — move the I/O outside the "
                            "critical section or suppress with the "
                            "reason it must stay"
                        ),
                    )
                )

    for node in ast.walk(tree):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        for item in node.items:
            ctx = item.context_expr
            if isinstance(ctx, ast.Call):
                ctx = ctx.func
            if _lockish(ctx):
                scan_body(node.body, _recv_text(item.context_expr))
                break
    return findings


def _check_raw_acquire(sf: SourceFile, tree: ast.Module) -> List[Finding]:
    findings: List[Finding] = []

    def check_block(body: List[ast.stmt]) -> None:
        for i, stmt in enumerate(body):
            call = None
            if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Call):
                call = stmt.value
            elif isinstance(stmt, ast.Assign) and isinstance(stmt.value, ast.Call):
                call = stmt.value
            if (
                call is None
                or not isinstance(call.func, ast.Attribute)
                or call.func.attr != "acquire"
            ):
                continue
            recv = _recv_text(call.func.value)
            nxt = body[i + 1] if i + 1 < len(body) else None
            if isinstance(nxt, ast.Try) and _releases(nxt.finalbody, recv):
                continue
            findings.append(
                Finding(
                    rule=RULE,
                    path=sf.path,
                    line=stmt.lineno,
                    message=(
                        f"raw {recv}.acquire() without an immediate "
                        "try/finally release — use 'with' or try/finally "
                        "(suppress with a reason when the hold legitimately "
                        "spans a context-manager boundary)"
                    ),
                )
            )

    for node in ast.walk(tree):
        for field in ("body", "orelse", "finalbody"):
            block = getattr(node, field, None)
            if isinstance(block, list) and block and isinstance(block[0], ast.stmt):
                check_block(block)
        for handler in getattr(node, "handlers", []) or []:
            check_block(handler.body)
    return findings


def _releases(body: List[ast.stmt], recv: str) -> bool:
    for stmt in body:
        for node in ast.walk(stmt):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "release"
                and _recv_text(node.func.value) == recv
            ):
                return True
    return False


def _direct_blocking_qnames(cg) -> Set[str]:
    """Project functions whose own bodies perform blocking I/O."""
    out: Set[str] = set()
    for q, fi in cg.functions.items():
        for call in _body_calls(fi.node):
            desc = _direct_blocking_call(call)
            if desc is not None and not desc.endswith(".wait"):
                out.add(q)
                break
    return out


def _check_with_blocks_interproc(
    sf: SourceFile, cg, qnames: List[str], tainted: Set[str],
    direct: Set[str], flagged: Set[Tuple[str, int]],
) -> List[Finding]:
    """The call-graph half of blocking-under-lock: a call under a
    lockish ``with`` whose resolvable callee can reach blocking I/O
    anywhere in the project fires with the witness chain."""
    findings: List[Finding] = []
    for q in qnames:
        fi = cg.functions[q]
        ctx = None
        for node in ast.walk(fi.node):
            if not isinstance(node, (ast.With, ast.AsyncWith)):
                continue
            lock_text = None
            for item in node.items:
                recv = item.context_expr
                if isinstance(recv, ast.Call):
                    recv = recv.func
                if _lockish(recv):
                    lock_text = _recv_text(item.context_expr)
                    break
            if lock_text is None:
                continue
            for call in _body_calls(node):
                if (sf.path, call.lineno) in flagged:
                    continue
                if _direct_blocking_call(call) is not None:
                    continue  # the lexical pass owns direct calls
                if ctx is None:
                    ctx = cg.ctx(q)
                callees, _ = ctx.resolve_call(call)
                hot = sorted(c for c in callees if c in tainted)
                if not hot:
                    continue
                chain = cg.sample_path(hot[0], direct) or [hot[0]]
                short = [c.split(".", 1)[-1] for c in chain]
                findings.append(
                    Finding(
                        rule=RULE,
                        path=sf.path,
                        line=call.lineno,
                        message=(
                            f"call while holding {lock_text} reaches "
                            f"blocking I/O via {' -> '.join(short)} — "
                            "move the I/O outside the critical section "
                            "or suppress with the reason it must stay"
                        ),
                    )
                )
                flagged.add((sf.path, call.lineno))
    return findings


def analyze(files: Iterable[SourceFile], config) -> List[Finding]:
    files = [sf for sf in files if sf.path.startswith("kwok_tpu/")]
    if not files:
        return []
    cg = get_callgraph(files, config)
    direct = _direct_blocking_qnames(cg)
    tainted = cg.closure_reaching(direct)
    by_path: Dict[str, List[str]] = {}
    for q in sorted(cg.functions):
        by_path.setdefault(cg.functions[q].path, []).append(q)
    findings: List[Finding] = []
    for sf in files:
        helpers = _blocking_helper_names(sf.tree)
        findings.extend(_check_raw_acquire(sf, sf.tree))
        lexical = _check_with_blocks(sf, sf.tree, helpers)
        findings.extend(lexical)
        flagged = {(f.path, f.line) for f in lexical}
        findings.extend(
            _check_with_blocks_interproc(
                sf, cg, by_path.get(sf.path, []), tainted, direct, flagged
            )
        )
    return findings
