"""Whole-package concurrency soundness pass (the static half).

Run as ``python -m bigdl_tpu.analysis.concurrency <package> [...]``.
Imports nothing heavy (no jax) — safe as a CI preflight next to the AST
linter; tier-1 runs it over the whole package
(``tests/test_concurrency.py::TestPackageGate``).

The runtime half is :mod:`bigdl_tpu.analysis.lockwitness`; the two share
one vocabulary: a lock's NAME is the string given to
``analysis.make_lock("...")``, so a static inversion report and a
runtime :class:`~bigdl_tpu.analysis.lockwitness.LockOrderViolation` name
the same nodes.

What one pass over the tree computes
====================================

**Inventory** — every thread entry point (direct
``threading.Thread(target=...)`` construction, ``.spawn(name, fn)`` /
``.submit(fn)`` supervised handoffs, and functions declared with a
``# thread-root`` comment for targets handed across modules, e.g. the
fleet supervisor calling ``Fleet._tick``) and every ``Lock`` / ``RLock``
/ ``Condition`` construction, factory-routed or raw.

**Lock-acquisition-order graph** — which locks can be acquired while
others are held.  ``with`` nesting gives the local edges; a per-module
call-graph approximation (``self.m()`` / bare-name / unique-method
calls, with MAY-held sets propagated caller→callee to a fixpoint)
extends them across call boundaries.  Edges from every module land in
one package-wide graph; a cycle is a potential deadlock.  This
generalizes the old ring-handoff-only ``lock-order`` lint rule to the
whole package.

**Guarded-state contract** — an instance attribute mutated from ≥2
distinct thread roots (spawned roots plus "main": anything reachable
from outside the spawned-root closure) must carry a
``# guarded-by: <lockattr>`` comment on an assignment site, and every
mutation outside ``__init__`` must be syntactically under
``with <that lock>`` — directly, or via MUST-held propagation for
private ``*_locked``-style helpers whose every call site holds the
lock.  Attributes bound to thread-safe primitives (queues, events,
locks, threads) are exempt; ``__init__`` (construction happens-before
publication) is exempt.

**Async-abort safety** — every ``_async_raise`` /
``PyThreadState_SetAsyncExc`` call site must sit under a ``with
<lock>`` whose body re-checks completion (an ``if`` containing a
``return``) before injecting — the discipline all four watchdogs
converged on, now codified: an abort that skips the re-check can kill a
thread that already finished its critical section (the PR 18
mid-admission class of bug).

Rules emitted: ``lock-order-inversion``, ``missing-guarded-by``,
``guarded-mutation-outside-lock``, ``async-abort-unguarded`` (plus
``syntax``).  Findings honor the linter's inline
``# lint: allow(<rule>)`` escape and the shared allowlist file, which
stays empty.
"""

from __future__ import annotations

import ast
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from bigdl_tpu.analysis.lint import (
    DEFAULT_ALLOWLIST, Finding, _inline_allows, _iter_sources,
    load_allowlist)

#: every rule this pass can emit — the CLI validates --rule against it
CONCURRENCY_RULES = frozenset({
    "lock-order-inversion", "missing-guarded-by",
    "guarded-mutation-outside-lock", "async-abort-unguarded", "syntax",
})

#: ``self.X.<m>(...)`` calls that mutate the container X in place
MUTATORS = frozenset({
    "append", "appendleft", "extend", "extendleft", "add", "update",
    "remove", "discard", "pop", "popleft", "popitem", "clear", "insert",
    "setdefault",
})

#: constructor names whose result is internally synchronized (or a
#: handle, not shared data): attributes bound to these are exempt from
#: the guarded-state contract
SAFE_CTORS = frozenset({
    "Queue", "SimpleQueue", "LifoQueue", "PriorityQueue", "Event",
    "Semaphore", "BoundedSemaphore", "Barrier", "Thread", "local",
    "Lock", "RLock", "Condition", "make_lock", "make_rlock",
    "make_condition", "WitnessLock",
})

#: lock constructors -> kind (for the inventory / witness-name mapping)
LOCK_CTORS = {
    "Lock": "lock", "RLock": "rlock", "Condition": "condition",
    "make_lock": "lock", "make_rlock": "rlock",
    "make_condition": "condition",
}

#: names that read as a lock in a ``with`` even without a visible decl
_LOCKISH_RE = re.compile(r"(lock|_cv|cond)$", re.IGNORECASE)
_GUARDED_BY_RE = re.compile(r"#\s*guarded-by:\s*([A-Za-z_][A-Za-z0-9_]*)")
_THREAD_ROOT_RE = re.compile(r"#\s*thread-root\b")
_ABORT_NAMES = ("_async_raise", "PyThreadState_SetAsyncExc")

#: a lock key is (base, attr): ("self", "_lock") for instance locks,
#: ("", "_NAME_LOCK") for module globals, ("svc", "_lock") for locks
#: reached through a local variable
Key = Tuple[str, str]
#: a function id is (class name or None, dotted qualname within module)
Fid = Tuple[Optional[str], str]


def _mut_target(t: ast.AST) -> Optional[Key]:
    """(base, attr) for ``self.X`` / ``var.X`` assignment targets,
    looking through subscripts and attribute chains to the attribute
    nearest the base name (``self.X[k] = v`` and ``self.X.Y = v`` both
    mutate the object held by ``X``)."""
    while isinstance(t, ast.Subscript):
        t = t.value
    if not isinstance(t, ast.Attribute):
        return None
    node = t
    while isinstance(node.value, (ast.Attribute, ast.Subscript)):
        inner = node.value
        while isinstance(inner, ast.Subscript):
            inner = inner.value
        if not isinstance(inner, ast.Attribute):
            return None
        node = inner
    if isinstance(node.value, ast.Name):
        return (node.value.id, node.attr)
    return None


def _with_key(expr: ast.AST) -> Optional[Key]:
    if isinstance(expr, ast.Attribute) and isinstance(expr.value, ast.Name):
        return (expr.value.id, expr.attr)
    if isinstance(expr, ast.Name):
        return ("", expr.id)
    return None


def _call_parts(node: ast.Call) -> Tuple[str, Optional[str]]:
    """(base, name) of the callee: ``self.m()`` -> ("self", "m"),
    ``foo()`` -> ("", "foo"), ``obj.m()`` -> ("obj", "m"); anything
    deeper returns (".", None) and is ignored."""
    f = node.func
    if isinstance(f, ast.Name):
        return ("", f.id)
    if isinstance(f, ast.Attribute):
        if isinstance(f.value, ast.Name):
            return (f.value.id, f.attr)
        return (".", f.attr)
    return (".", None)


def _ctor_name(value: ast.AST) -> Optional[str]:
    if isinstance(value, ast.Call):
        f = value.func
        if isinstance(f, ast.Name):
            return f.id
        if isinstance(f, ast.Attribute):
            return f.attr
    return None


@dataclass
class _ModuleScan:
    """Everything one walk of a module's AST collects; the package-level
    analysis in :func:`analyze` stitches the order graph together and
    judges the contracts."""

    rel: str
    stem: str
    lines: List[str]
    funcs: Dict[Fid, int] = field(default_factory=dict)       # fid -> line
    methods: Dict[Optional[str], Set[str]] = field(default_factory=dict)
    calls: List[Tuple[Fid, str, str, Tuple[Key, ...], int]] = \
        field(default_factory=list)     # (caller, base, name, held, line)
    acquires: List[Tuple[Fid, Tuple[Key, ...], Key, int]] = \
        field(default_factory=list)     # (fid, held-before, key, line)
    mutations: List[Tuple[Key, Optional[str], Fid, Tuple[Key, ...], int]] \
        = field(default_factory=list)   # (target, class ctx, fid, held, line)
    decl_locks: Dict[Tuple[Optional[str], str], Tuple[str, str, int]] = \
        field(default_factory=dict)     # (cls, attr) -> (witness, kind, line)
    safe_attrs: Set[Tuple[Optional[str], str]] = field(default_factory=set)
    annotations: Dict[Tuple[Optional[str], str], Tuple[str, int]] = \
        field(default_factory=dict)     # (cls, attr) -> (lock attr, line)
    spawn_targets: List[Tuple[Optional[str], str, str, str, int]] = \
        field(default_factory=list)     # (cls, scope, base, name, line)
    spawn_sites: List[Tuple[int, str]] = field(default_factory=list)
    declared_roots: Set[Fid] = field(default_factory=set)
    findings: List[Finding] = field(default_factory=list)

    # -- collection walk --------------------------------------------------

    def scan(self, tree: ast.Module) -> None:
        for node in tree.body:
            self._stmt(node, None, "", ())

    def _register(self, cls: Optional[str], scope: str,
                  node: ast.AST) -> Fid:
        name = node.name
        qual = f"{scope}.{name}" if scope else name
        fid = (cls, qual)
        self.funcs[fid] = node.lineno
        self.methods.setdefault(cls, set()).add(name.split(".")[-1])
        line = self.lines[node.lineno - 1] if \
            node.lineno - 1 < len(self.lines) else ""
        if _THREAD_ROOT_RE.search(line):
            self.declared_roots.add(fid)
        return fid

    def _stmt(self, node: ast.AST, cls: Optional[str], scope: str,
              held: Tuple[Key, ...], fid: Optional[Fid] = None,
              withs: Optional[list] = None) -> None:
        withs = withs if withs is not None else []
        if isinstance(node, ast.ClassDef):
            for n in node.body:
                self._stmt(n, node.name, "", ())
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            new_fid = self._register(cls, scope, node)
            new_scope = f"{scope}.{node.name}" if scope else node.name
            for n in node.body:
                # a nested function's body runs when CALLED, not where
                # defined: fresh held stack
                self._stmt(n, cls, new_scope, (), new_fid, [])
            return
        if isinstance(node, ast.With):
            inner = held
            for item in node.items:
                self._exprs(item.context_expr, cls, fid, inner, withs)
                key = _with_key(item.context_expr)
                if key is not None and self._lockish(cls, key):
                    if fid is not None:
                        self.acquires.append((fid, inner, key, node.lineno))
                    inner = inner + (key,)
                    withs = withs + [(key, node)]
            for n in node.body:
                self._stmt(n, cls, scope, inner, fid, withs)
            return
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            self._assign(node, cls, scope, held, fid)
            if node.value is not None:
                self._exprs(node.value, cls, fid, held, withs)
            return
        # generic: visit nested statements with the current context,
        # expressions for calls.  except-handlers and match-cases are
        # NOT ast.stmt but carry statement bodies — recurse into them
        # too, or a `with lock:` inside an `except:` loses its held set
        blockish = (ast.stmt, ast.excepthandler) + (
            (ast.match_case,) if hasattr(ast, "match_case") else ())
        for fname, value in ast.iter_fields(node):
            if isinstance(value, list):
                for v in value:
                    if isinstance(v, blockish):
                        self._stmt(v, cls, scope, held, fid, withs)
                    elif isinstance(v, ast.AST):
                        self._exprs(v, cls, fid, held, withs)
            elif isinstance(value, ast.AST):
                if isinstance(value, blockish):
                    self._stmt(value, cls, scope, held, fid, withs)
                else:
                    self._exprs(value, cls, fid, held, withs)

    def _lockish(self, cls: Optional[str], key: Key) -> bool:
        base, attr = key
        if base == "self" and (cls, attr) in self.decl_locks:
            return True
        if base == "" and (None, attr) in self.decl_locks:
            return True
        return bool(_LOCKISH_RE.search(attr))

    def _assign(self, node: ast.AST, cls: Optional[str], scope: str,
                held: Tuple[Key, ...], fid: Optional[Fid]) -> None:
        targets = node.targets if isinstance(node, ast.Assign) \
            else [node.target]
        if isinstance(node, ast.AnnAssign) and node.value is None:
            return                       # a bare annotation binds nothing
        flat: List[ast.AST] = []
        for t in targets:
            flat.extend(t.elts if isinstance(t, ast.Tuple) else [t])
        line_text = self.lines[node.lineno - 1] if \
            node.lineno - 1 < len(self.lines) else ""
        ann = _GUARDED_BY_RE.search(line_text)
        for t in flat:
            key = _mut_target(t)
            if key is None:
                continue
            base, attr = key
            owner: Tuple[Optional[str], str]
            if base == "self" and cls is not None:
                owner = (cls, attr)
            elif base == "" :
                owner = (None, attr)
            else:
                owner = (None, attr)    # resolved per-module later
            if not isinstance(node, ast.AugAssign) and base in ("self", ""):
                ctor = _ctor_name(node.value)
                if ctor in LOCK_CTORS:
                    witness = self._witness_name(node.value, owner, ctor)
                    dkey = (cls, attr) if base == "self" else (None, attr)
                    self.decl_locks[dkey] = (
                        witness, LOCK_CTORS[ctor], node.lineno)
                if ctor in SAFE_CTORS:
                    dkey = (cls, attr) if base == "self" else (None, attr)
                    self.safe_attrs.add(dkey)
            if ann and base in ("self", ""):
                akey = (cls, attr) if base == "self" else (None, attr)
                self.annotations.setdefault(
                    akey, (ann.group(1), node.lineno))
            if fid is not None and base != "":
                self.mutations.append((key, cls, fid, held, node.lineno))

    def _witness_name(self, value: ast.Call, owner, ctor: str) -> str:
        if ctor.startswith("make_") and value.args and \
                isinstance(value.args[0], ast.Constant) and \
                isinstance(value.args[0].value, str):
            return value.args[0].value
        cls, attr = owner
        return f"{self.stem}.{cls + '.' if cls else ''}{attr}"

    # -- expression walk (calls, spawns, aborts, mutator methods) ---------

    def _exprs(self, node: ast.AST, cls: Optional[str],
               fid: Optional[Fid], held: Tuple[Key, ...],
               withs: list) -> None:
        for n in ast.walk(node):
            if not isinstance(n, ast.Call):
                continue
            base, name = _call_parts(n)
            if name is None:
                continue
            if fid is not None and base in ("self", "") and \
                    name not in LOCK_CTORS:
                self.calls.append((fid, base, name, held, n.lineno))
            elif fid is not None and base not in ("self", "", "."):
                # obj.m(): resolved later iff m is a method of exactly
                # one class in this module
                self.calls.append((fid, base, name, held, n.lineno))
            # thread entry points
            if name == "Thread":
                tgt = next((kw.value for kw in n.keywords
                            if kw.arg == "target"), None)
                self._spawn(tgt, cls, fid, n.lineno, "Thread")
            elif name in ("spawn", "submit") and n.args:
                arg = n.args[1] if name == "spawn" and len(n.args) > 1 \
                    else n.args[0]
                self._spawn(arg, cls, fid, n.lineno, name)
            # in-place container mutation through a method
            if name in MUTATORS and isinstance(n.func, ast.Attribute):
                key = _mut_target(n.func.value)
                if key is not None and key[0] != "" and fid is not None:
                    self.mutations.append((key, cls, fid, held, n.lineno))
            # async aborts
            if name in _ABORT_NAMES:
                self._abort(n, fid, withs)

    def _spawn(self, tgt: Optional[ast.AST], cls: Optional[str],
               fid: Optional[Fid], line: int, how: str) -> None:
        if isinstance(tgt, ast.Lambda) and isinstance(tgt.body, ast.Call):
            tgt = tgt.body.func
        if isinstance(tgt, ast.Attribute) and \
                isinstance(tgt.value, ast.Name):
            base, name = tgt.value.id, tgt.attr
        elif isinstance(tgt, ast.Name):
            base, name = "", tgt.id
        else:
            if how == "Thread":         # dynamic target: inventory only
                self.spawn_sites.append((line, f"{how}(<dynamic>)"))
            return
        scope = fid[1] if fid is not None else ""
        self.spawn_targets.append((cls, scope, base, name, line))
        self.spawn_sites.append((line, f"{how}({base + '.' if base else ''}"
                                       f"{name})"))

    def _abort(self, call: ast.Call, fid: Optional[Fid],
               withs: list) -> None:
        if fid is not None and fid[1].split(".")[-1] == "_async_raise":
            return          # the injector's own internals
        ok = False
        if withs:
            _, with_node = withs[-1]
            for n in ast.walk(with_node):
                if isinstance(n, ast.If) and n.lineno <= call.lineno and \
                        any(isinstance(x, ast.Return) for x in ast.walk(n)):
                    ok = True
                    break
        if not ok:
            self.findings.append(Finding(
                self.rel, call.lineno, "async-abort-unguarded",
                "async abort must re-check completion under the lock the "
                "target sets its done-flag with: wrap the injection in "
                "`with <lock>:` with an `if <done>: return` before it "
                "(see compile_cache/elastic watchdogs), or the abort can "
                "kill a thread that already left its critical section"))

    # -- per-module resolution --------------------------------------------

    def resolve_fn(self, caller: Optional[Fid], base: str,
                   name: str) -> Optional[Fid]:
        if base == "self" and caller is not None:
            fid = (caller[0], name)
            return fid if fid in self.funcs else None
        if base == "":
            if caller is not None:
                # prefer a nested def in the caller's scope chain
                parts = caller[1].split(".")
                for i in range(len(parts), 0, -1):
                    fid = (caller[0], ".".join(parts[:i] + [name]))
                    if fid in self.funcs:
                        return fid
            return (None, name) if (None, name) in self.funcs else None
        # obj.m(): unique-method match across this module's classes
        owners = [c for c, ms in self.methods.items()
                  if c is not None and name in ms and (c, name) in self.funcs]
        if len(owners) == 1:
            return (owners[0], name)
        return None

    def lock_witness(self, cls: Optional[str], key: Key) -> Optional[str]:
        base, attr = key
        if base == "self":
            d = self.decl_locks.get((cls, attr))
            return d[0] if d else None
        if base == "":
            d = self.decl_locks.get((None, attr))
            return d[0] if d else None
        owners = [k for k in self.decl_locks if k[0] is not None and
                  k[1] == attr]
        if len(owners) == 1:
            return self.decl_locks[owners[0]][0]
        return None

    def roots(self) -> Set[Fid]:
        out = set(self.declared_roots)
        for cls, scope, base, name, _line in self.spawn_targets:
            caller = (cls, scope) if scope else None
            fid = self.resolve_fn(caller, base, name)
            if fid is not None:
                out.add(fid)
        return out


def _closure(starts: Iterable[Fid],
             edges: Dict[Fid, Set[Fid]]) -> Set[Fid]:
    seen: Set[Fid] = set(starts)
    work = list(seen)
    while work:
        f = work.pop()
        for g in edges.get(f, ()):
            if g not in seen:
                seen.add(g)
                work.append(g)
    return seen


def _translate(held: Iterable[Key], call_base: str,
               callee_cls: Optional[str]) -> Set[Key]:
    """Map the caller's held keys into the callee's frame: locks reached
    through the call's receiver become the callee's ``self`` locks;
    module-global locks pass through; everything else is dropped (a
    different object's locks mean nothing to the callee)."""
    out: Set[Key] = set()
    for base, attr in held:
        if base == "":
            out.add((base, attr))
        elif base == call_base and callee_cls is not None:
            out.add(("self", attr))
        elif base == "self" and call_base == "self":
            out.add((base, attr))
    return out


def _analyze_module(scan: _ModuleScan,
                    order_edges: List[Tuple[str, str, str, int]]
                    ) -> List[Finding]:
    findings = list(scan.findings)

    # call graph + resolved edges ------------------------------------------
    edges: Dict[Fid, Set[Fid]] = {}
    call_sites: List[Tuple[Fid, Fid, str, Tuple[Key, ...]]] = []
    for caller, base, name, held, _line in scan.calls:
        callee = scan.resolve_fn(caller, base, name)
        if callee is None or callee == caller:
            continue
        edges.setdefault(caller, set()).add(callee)
        call_sites.append((caller, callee, base, held))

    roots = scan.roots()
    spawned = _closure(roots, edges)
    roots_reaching: Dict[Fid, Set[str]] = {}
    for r in roots:
        for f in _closure([r], edges):
            roots_reaching.setdefault(f, set()).add(f"{r[0] or ''}."
                                                    f"{r[1]}".lstrip("."))
    # "main" reaches every function outside the spawned closure, plus
    # anything those call (a public API calling into thread-shared code)
    main_seed = [f for f in scan.funcs if f not in spawned]
    main_reach = _closure(main_seed, edges)

    def _is_private(fid: Fid) -> bool:
        leaf = fid[1].split(".")[-1]
        return leaf.startswith("_") and not leaf.startswith("__")

    # MUST-held at entry (intersection over call sites; fixpoint) ----------
    TOP = None          # "no information yet"
    must: Dict[Fid, Optional[Set[Key]]] = {}
    for f in scan.funcs:
        must[f] = TOP if (_is_private(f) and f not in roots) else set()
    for _ in range(20):
        changed = False
        incoming: Dict[Fid, Optional[Set[Key]]] = {}
        for caller, callee, base, held in call_sites:
            if not (_is_private(callee) and callee not in roots):
                continue
            up = must.get(caller)
            if up is TOP:
                contrib: Optional[Set[Key]] = TOP
            else:
                contrib = _translate(set(held) | up, base, callee[0])
            cur = incoming.get(callee, "unset")
            if cur == "unset":
                incoming[callee] = contrib
            elif contrib is not TOP:
                incoming[callee] = contrib if cur is TOP \
                    else (cur & contrib)
        for f, val in incoming.items():
            if val is not TOP and must[f] != val:
                must[f] = val
                changed = True
        if not changed:
            break
    for f, v in must.items():
        if v is TOP:
            must[f] = set()

    # MAY-held at entry in witness-name space (union; fixpoint) ------------
    may: Dict[Fid, Set[str]] = {f: set() for f in scan.funcs}

    def _names(cls: Optional[str], held: Iterable[Key]) -> Set[str]:
        out = set()
        for k in held:
            w = scan.lock_witness(cls, k)
            if w is not None:
                out.add(w)
        return out

    for _ in range(20):
        changed = False
        for caller, callee, _base, held in call_sites:
            add = may[caller] | _names(caller[0], held)
            if not add <= may[callee]:
                may[callee] |= add
                changed = True
        if not changed:
            break

    # order edges into the package-wide graph ------------------------------
    for fid, held, key, line in scan.acquires:
        inner = scan.lock_witness(fid[0], key)
        if inner is None:
            continue
        outers = _names(fid[0], held) | may[fid]
        for outer in outers:
            if outer != inner:
                order_edges.append((outer, inner, scan.rel, line))

    # guarded-state contract -----------------------------------------------
    # attribute universe: (owner class or None, attr) -> mutation sites
    per_attr: Dict[Tuple[Optional[str], str],
                   List[Tuple[Fid, Tuple[Key, ...], int, str]]] = {}
    class_attrs: Dict[str, Set[Optional[str]]] = {}
    for (base, attr), cls, fid, held, line in scan.mutations:
        if base == "self" and cls is not None:
            class_attrs.setdefault(attr, set()).add(cls)
    for (base, attr), cls, fid, held, line in scan.mutations:
        if base == "self" and cls is not None:
            owner: Tuple[Optional[str], str] = (cls, attr)
        else:
            # var.attr: attributed iff exactly one class in this module
            # owns the attr
            owners = class_attrs.get(attr, set())
            if len(owners) != 1:
                continue
            owner = (next(iter(owners)), attr)
        if owner in scan.safe_attrs or (None, attr) in scan.safe_attrs:
            continue
        if owner in scan.decl_locks:
            continue
        per_attr.setdefault(owner, []).append((fid, held, line, base))

    for owner, sites in sorted(per_attr.items(),
                               key=lambda kv: (kv[0][0] or "", kv[0][1])):
        cls, attr = owner
        ann = scan.annotations.get(owner)
        live = [s for s in sites
                if s[0][1].split(".")[-1] != "__init__"]
        attr_roots: Set[str] = set()
        for fid, _held, _line, _base in live:
            attr_roots |= roots_reaching.get(fid, set())
            if fid in main_reach:
                attr_roots.add("main")
        label = f"{cls}.{attr}" if cls else attr
        if ann is None:
            if len(attr_roots) >= 2:
                first = min(s[2] for s in live)
                findings.append(Finding(
                    scan.rel, first, "missing-guarded-by",
                    f"{label} is mutated from {len(attr_roots)} thread "
                    f"roots ({', '.join(sorted(attr_roots))}) with no "
                    f"`# guarded-by: <lock>` annotation — name the lock "
                    f"on its __init__ assignment and take it at every "
                    f"mutation site"))
            continue
        lock_attr, ann_line = ann
        known = (cls, lock_attr) in scan.decl_locks or \
            (None, lock_attr) in scan.decl_locks
        if not known:
            findings.append(Finding(
                scan.rel, ann_line, "missing-guarded-by",
                f"{label} names guard {lock_attr!r} but no such lock is "
                f"declared in {cls or 'module scope'}"))
            continue
        for fid, held, line, base in live:
            want_base = "self" if base == "self" else base
            effective = set(held)
            if base == "self":
                effective |= must.get(fid, set())
            if (want_base, lock_attr) not in effective and \
                    ("", lock_attr) not in effective:
                findings.append(Finding(
                    scan.rel, line, "guarded-mutation-outside-lock",
                    f"{label} is guarded-by {lock_attr!r} but this "
                    f"mutation is not under `with "
                    f"{base + '.' if base else ''}{lock_attr}` (directly "
                    f"or on every call path)"))
    return findings


def _order_findings(order_edges: List[Tuple[str, str, str, int]]
                    ) -> List[Finding]:
    """Package-wide cycle detection over the static acquisition-order
    graph: 2-cycles are reported pairwise with both witnessing sites;
    longer cycles (rare) report the full chain once."""
    graph: Dict[str, Set[str]] = {}
    sites: Dict[Tuple[str, str], Tuple[str, int]] = {}
    for outer, inner, rel, line in order_edges:
        graph.setdefault(outer, set()).add(inner)
        sites.setdefault((outer, inner), (rel, line))
    out: List[Finding] = []
    reported: Set[frozenset] = set()
    for a in sorted(graph):
        for b in sorted(graph[a]):
            if a < b and a in graph.get(b, ()):  # 2-cycle, report once
                pair = frozenset((a, b))
                if pair in reported:
                    continue
                reported.add(pair)
                r1, l1 = sites[(a, b)]
                r2, l2 = sites[(b, a)]
                out.append(Finding(
                    r1, l1, "lock-order-inversion",
                    f"{b!r} can be acquired while holding {a!r} here, "
                    f"but {r2}:{l2} acquires {a!r} while holding {b!r} "
                    f"— two threads on these paths can deadlock; pick "
                    f"one order"))
    # longer cycles: DFS from each node not already in a reported pair
    def _cycle_from(start: str) -> Optional[List[str]]:
        stack = [(start, [start])]
        while stack:
            node, path = stack.pop()
            for nxt in sorted(graph.get(node, ())):
                if nxt == start and len(path) > 2:
                    return path + [nxt]
                if nxt not in path:
                    stack.append((nxt, path + [nxt]))
        return None

    for node in sorted(graph):
        cyc = _cycle_from(node)
        if cyc and not any(frozenset((cyc[i], cyc[i + 1])) in reported
                           for i in range(len(cyc) - 1)):
            key = frozenset(cyc)
            if key in reported:
                continue
            reported.add(key)
            rel, line = sites[(cyc[0], cyc[1])]
            out.append(Finding(
                rel, line, "lock-order-inversion",
                f"acquisition-order cycle {' -> '.join(cyc)} — the "
                f"locks on this chain can be taken in a loop across "
                f"threads; break one edge"))
    return out


# ---------------------------------------------------------------------------
# package API
# ---------------------------------------------------------------------------

def analyze(targets: Sequence[str],
            allowlist: Optional[Set[str]] = None) -> List[Finding]:
    allowlist = allowlist or set()
    findings: List[Finding] = []
    order_edges: List[Tuple[str, str, str, int]] = []
    allows_by_rel: Dict[str, Dict[int, Set[str]]] = {}
    for path, rel in _iter_sources(targets):
        with open(path, encoding="utf-8") as f:
            source = f.read()
        try:
            tree = ast.parse(source, filename=path)
        except SyntaxError as e:
            findings.append(Finding(rel, e.lineno or 0, "syntax",
                                    f"unparseable: {e.msg}"))
            continue
        allows_by_rel[rel] = _inline_allows(source)
        stem = os.path.splitext(os.path.basename(rel))[0]
        scan = _ModuleScan(rel=rel, stem=stem, lines=source.splitlines())
        scan.scan(tree)
        findings.extend(_analyze_module(scan, order_edges))
    findings.extend(_order_findings(order_edges))
    kept = []
    for f in findings:
        if f.rule in allows_by_rel.get(f.path, {}).get(f.line, ()):
            continue
        if f"{f.path}:{f.rule}" in allowlist:
            continue
        kept.append(f)
    return sorted(kept, key=lambda f: (f.path, f.line, f.rule))


def thread_inventory(targets: Sequence[str]) -> dict:
    """The package's concurrency surface: every thread entry point and
    every lock construction site, plus which modules are threaded (the
    ``raw-lock-in-threaded-module`` lint rule's ground truth)."""
    threads: List[dict] = []
    locks: List[dict] = []
    threaded: Set[str] = set()
    for path, rel in _iter_sources(targets):
        with open(path, encoding="utf-8") as f:
            source = f.read()
        try:
            tree = ast.parse(source, filename=path)
        except SyntaxError:
            continue
        stem = os.path.splitext(os.path.basename(rel))[0]
        scan = _ModuleScan(rel=rel, stem=stem, lines=source.splitlines())
        scan.scan(tree)
        for line, descr in sorted(scan.spawn_sites):
            threads.append({"file": rel, "line": line, "target": descr})
            threaded.add(rel)
        for fid in sorted(scan.declared_roots,
                          key=lambda f: scan.funcs[f]):
            threads.append({"file": rel, "line": scan.funcs[fid],
                            "target": f"thread-root "
                                      f"{(fid[0] or '') + '.'}{fid[1]}"
                            .lstrip(".")})
            threaded.add(rel)
        for (cls, attr), (witness, kind, line) in sorted(
                scan.decl_locks.items(),
                key=lambda kv: kv[1][2]):
            locks.append({"file": rel, "line": line, "kind": kind,
                          "attr": f"{cls + '.' if cls else ''}{attr}",
                          "name": witness})
    return {"threads": threads, "locks": locks,
            "threaded_modules": sorted(threaded)}


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m bigdl_tpu.analysis.concurrency",
        description="whole-package lock-order / guarded-state / "
                    "async-abort analysis")
    ap.add_argument("targets", nargs="+",
                    help="package directories or .py files")
    ap.add_argument("--allowlist", default=DEFAULT_ALLOWLIST,
                    help="grandfathered '<relpath>:<rule>' entries "
                         "(default: the in-repo allowlist, kept empty)")
    ap.add_argument("--rule", action="append", default=None,
                    metavar="NAME",
                    help="report only this rule (repeatable); unknown "
                         "names are an error")
    ap.add_argument("--inventory", action="store_true",
                    help="print the thread/lock inventory and exit 0")
    args = ap.parse_args(argv)
    if args.rule:
        unknown = sorted(set(args.rule) - CONCURRENCY_RULES)
        if unknown:
            print(f"unknown rule(s): {', '.join(unknown)}\n"
                  f"known rules: {', '.join(sorted(CONCURRENCY_RULES))}",
                  file=sys.stderr)
            return 2
    if args.inventory:
        inv = thread_inventory(args.targets)
        for t in inv["threads"]:
            print(f"thread  {t['file']}:{t['line']}  {t['target']}")
        for l in inv["locks"]:
            print(f"lock    {l['file']}:{l['line']}  {l['kind']:9s} "
                  f"{l['attr']}  ->  {l['name']!r}")
        print(f"\n{len(inv['threads'])} thread entry point(s), "
              f"{len(inv['locks'])} lock(s), "
              f"{len(inv['threaded_modules'])} threaded module(s)",
              file=sys.stderr)
        return 0
    findings = analyze(args.targets, load_allowlist(args.allowlist))
    if args.rule:
        findings = [f for f in findings if f.rule in set(args.rule)]
    for f in findings:
        print(f)
    if findings:
        print(f"\n{len(findings)} finding(s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
