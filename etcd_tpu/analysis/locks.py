"""lock-discipline: acquisition-order cycles + unguarded writes.

The threaded tier (store + servers) uses plain ``threading`` locks
acquired with ``with self.<lock>:``.  This checker derives, per
class:

- the set of lock attributes (``self.x = threading.Lock()/RLock()``),
- for every method, which locks are held at each point — lexically
  (enclosing ``with``) plus at-entry (the **intersection** of locks
  held at every intra-class call site, the "call with lock held"
  convention made mechanical),
- the **lock-acquisition graph**: an edge ``A → B`` whenever ``B`` is
  acquired (directly or via a call, including calls through typed
  attributes like ``self.store`` → ``Store``) while ``A`` is held.

Findings:

- ``lock-cycle``: a cycle in the acquisition graph — two threads
  entering it from different ends deadlock.
- ``unguarded-write``: an attribute written under a lock somewhere
  but also written with **no** lock held outside construction
  (``__init__`` and helpers reachable only from it are exempt —
  single-threaded by construction).
"""

from __future__ import annotations

import ast
import os

from .engine import LOCK_CTORS, Checker, Finding, dotted_name


def _is_lock_ctor(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    return dotted_name(node.func).split(".")[-1] in LOCK_CTORS


def _self_attr(node: ast.AST) -> str | None:
    """'attr' for ``self.attr`` nodes, else None."""
    if isinstance(node, ast.Attribute) \
            and isinstance(node.value, ast.Name) \
            and node.value.id == "self":
        return node.attr
    return None


class _ClassInfo:
    def __init__(self, relpath: str, name: str, node: ast.ClassDef):
        self.relpath = relpath
        self.name = name
        self.node = node
        self.locks: set[str] = set()
        # attr -> class name (self.attr = ClassName(...) in __init__)
        self.attr_types: dict[str, str] = {}
        self.methods: dict[str, ast.FunctionDef] = {}
        # method -> list[(callee_method, held_set, line)]
        self.calls: dict[str, list] = {}
        # method -> list[(attr_name, callee_method, held_set, line)]
        self.attr_calls: dict[str, list] = {}
        # method -> list[(lock, held_set, line)]  (with-acquisitions)
        self.acquires: dict[str, list] = {}
        # method -> list[(attr, held_set, line)]  (self.attr writes)
        self.writes: dict[str, list] = {}
        # computed later
        self.entry_held: dict[str, frozenset] = {}
        self.excluded: set[str] = set()


class _MethodScan(ast.NodeVisitor):
    """One pass over a method body tracking the lexical held set."""

    def __init__(self, ci: _ClassInfo, mname: str):
        self.ci = ci
        self.m = mname
        self.held: tuple[str, ...] = ()
        ci.calls.setdefault(mname, [])
        ci.attr_calls.setdefault(mname, [])
        ci.acquires.setdefault(mname, [])
        ci.writes.setdefault(mname, [])

    def visit_With(self, node: ast.With) -> None:
        acquired = []
        for item in node.items:
            attr = _self_attr(item.context_expr)
            if attr and attr in self.ci.locks:
                self.ci.acquires[self.m].append(
                    (attr, frozenset(self.held), node.lineno))
                acquired.append(attr)
        prev = self.held
        self.held = prev + tuple(a for a in acquired
                                 if a not in prev)
        for stmt in node.body:
            self.visit(stmt)
        self.held = prev

    def _record_write(self, target: ast.AST, line: int) -> None:
        # self.attr = / self.attr[...] = / self.attr.sub = (outer
        # attr is the shared name a lock would guard)
        node = target
        while isinstance(node, (ast.Subscript, ast.Attribute)):
            attr = _self_attr(node)
            if attr is not None:
                if attr not in self.ci.locks:
                    self.ci.writes[self.m].append(
                        (attr, frozenset(self.held), line))
                return
            node = node.value

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            if isinstance(t, (ast.Tuple, ast.List)):
                for el in t.elts:
                    self._record_write(el, node.lineno)
            else:
                self._record_write(t, node.lineno)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._record_write(node.target, node.lineno)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        f = node.func
        if isinstance(f, ast.Attribute):
            recv_attr = _self_attr(f.value)
            if isinstance(f.value, ast.Name) \
                    and f.value.id == "self":
                self.ci.calls[self.m].append(
                    (f.attr, frozenset(self.held), node.lineno))
            elif recv_attr is not None:
                # self.<attr>.<method>() — cross-class via attr type
                self.ci.attr_calls[self.m].append(
                    (recv_attr, f.attr, frozenset(self.held),
                     node.lineno))
        self.generic_visit(node)

    # nested defs inherit the held set of their definition site (the
    # common closure-callback pattern: defined and called under the
    # same lock); conservative but right for this tree
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        for stmt in node.body:
            self.visit(stmt)

    visit_AsyncFunctionDef = visit_FunctionDef


def _scan_class(relpath: str, node: ast.ClassDef) -> _ClassInfo:
    ci = _ClassInfo(relpath, node.name, node)
    for item in node.body:
        if isinstance(item, ast.FunctionDef):
            ci.methods[item.name] = item
    # pass 1: lock attrs + typed attrs from any method (usually
    # __init__)
    for mname, fn in ci.methods.items():
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Assign) and len(sub.targets) == 1:
                attr = _self_attr(sub.targets[0])
                if attr is None:
                    continue
                if _is_lock_ctor(sub.value):
                    ci.locks.add(attr)
                elif isinstance(sub.value, ast.Call):
                    cname = dotted_name(sub.value.func)
                    if cname and cname[:1].isupper():
                        ci.attr_types[attr] = cname.split(".")[-1]
    # pass 2: per-method scan
    for mname, fn in ci.methods.items():
        _MethodScan(ci, mname).visit(fn)
    return ci


def _compute_entry_and_exclusions(ci: _ClassInfo) -> None:
    # construction-only methods: __init__ + methods whose every
    # intra-class call site lives in an already-excluded method
    excluded = {"__init__"}
    changed = True
    while changed:
        changed = False
        for m in ci.methods:
            if m in excluded:
                continue
            sites = [caller for caller, calls in ci.calls.items()
                     for (callee, _h, _l) in calls if callee == m]
            if sites and all(s in excluded for s in sites):
                excluded.add(m)
                changed = True
    ci.excluded = excluded

    # entry-held fixpoint over non-construction call sites
    all_locks = frozenset(ci.locks)
    entry = {m: (all_locks if any(
        callee == m and caller not in excluded
        for caller, calls in ci.calls.items()
        for (callee, _h, _l) in calls) else frozenset())
        for m in ci.methods}
    for _ in range(len(ci.methods) + 2):
        changed = False
        nxt = dict(entry)
        for m in ci.methods:
            sites = []
            for caller, calls in ci.calls.items():
                if caller in excluded:
                    continue
                for (callee, held, _l) in calls:
                    if callee == m:
                        sites.append(held | entry[caller])
            if sites:
                v = frozenset.intersection(*map(frozenset, sites))
                if v != entry[m]:
                    nxt[m] = v
                    changed = True
        entry = nxt
        if not changed:
            break
    ci.entry_held = entry


def _transitive_acquires(classes: dict[str, _ClassInfo]
                         ) -> dict[tuple[str, str], frozenset]:
    """(class, method) → every lock (``Class.attr``) the call may
    acquire, through intra-class calls and typed-attribute calls."""
    acq: dict[tuple[str, str], frozenset] = {}
    for cname, ci in classes.items():
        for m in ci.methods:
            acq[(cname, m)] = frozenset(
                f"{cname}.{lock}" for (lock, _h, _l)
                in ci.acquires.get(m, ()))
    for _ in range(8):
        changed = False
        for cname, ci in classes.items():
            for m in ci.methods:
                cur = acq[(cname, m)]
                add = frozenset()
                for (callee, _h, _l) in ci.calls.get(m, ()):
                    add |= acq.get((cname, callee), frozenset())
                for (attr, callee, _h, _l) in \
                        ci.attr_calls.get(m, ()):
                    tcls = ci.attr_types.get(attr)
                    if tcls in classes:
                        add |= acq.get((tcls, callee), frozenset())
                if not add <= cur:
                    acq[(cname, m)] = cur | add
                    changed = True
        if not changed:
            break
    return acq


class LockDisciplineChecker(Checker):
    name = "lock-discipline"
    targets = (
        "etcd_tpu/store/store.py",
        "etcd_tpu/store/ttl_heap.py",
        "etcd_tpu/server/server.py",
        "etcd_tpu/server/multigroup.py",
        "etcd_tpu/server/distserver.py",
    )

    def __init__(self):
        self._cache: dict[str, dict[str, list[Finding]]] = {}

    def check(self, relpath, tree, source, root=None, ctx=None):
        root = root or os.getcwd()
        if root not in self._cache:
            self._cache[root] = self._analyze(root)
        return self._cache[root].get(relpath, [])

    # -- whole-target-set analysis ---------------------------------------

    def _analyze(self, root: str) -> dict[str, list[Finding]]:
        classes: dict[str, _ClassInfo] = {}
        for rel in self.targets:
            path = os.path.join(root, rel)
            if not os.path.exists(path):
                continue
            with open(path) as f:
                tree = ast.parse(f.read(), filename=rel)
            for node in tree.body:
                if isinstance(node, ast.ClassDef):
                    ci = _scan_class(rel, node)
                    _compute_entry_and_exclusions(ci)
                    classes[node.name] = ci

        by_file: dict[str, list[Finding]] = {}

        def emit(f: Finding) -> None:
            by_file.setdefault(f.path, []).append(f)

        # -- acquisition graph + cycles
        acq = _transitive_acquires(classes)
        edges: dict[str, set[str]] = {}
        edge_sites: dict[tuple[str, str], tuple[str, int, str]] = {}

        def add_edge(a: str, b: str, rel: str, line: int,
                     scope: str) -> None:
            if a == b:
                return  # RLock re-entry, not an ordering edge
            edges.setdefault(a, set()).add(b)
            edge_sites.setdefault((a, b), (rel, line, scope))

        for cname, ci in classes.items():
            for m in ci.methods:
                if m in ci.excluded:
                    continue
                base = ci.entry_held.get(m, frozenset())
                for (lock, held, line) in ci.acquires.get(m, ()):
                    for h in held | base:
                        add_edge(f"{cname}.{h}", f"{cname}.{lock}",
                                 ci.relpath, line, f"{cname}.{m}")
                for (callee, held, line) in ci.calls.get(m, ()):
                    tgt = acq.get((cname, callee), frozenset())
                    for h in held | base:
                        for t in tgt:
                            add_edge(f"{cname}.{h}", t,
                                     ci.relpath, line,
                                     f"{cname}.{m}")
                for (attr, callee, held, line) in \
                        ci.attr_calls.get(m, ()):
                    tcls = ci.attr_types.get(attr)
                    if tcls not in classes:
                        continue
                    tgt = acq.get((tcls, callee), frozenset())
                    for h in held | base:
                        for t in tgt:
                            add_edge(f"{cname}.{h}", t,
                                     ci.relpath, line,
                                     f"{cname}.{m}")

        for cyc in self._cycles(edges):
            a, b = cyc[0], cyc[1 % len(cyc)]
            rel, line, scope = edge_sites.get(
                (a, b), (next(iter(classes.values())).relpath, 1, a))
            emit(Finding(
                checker=self.name, path=rel, line=line,
                rule="lock-cycle", scope=scope,
                message=("lock acquisition cycle: "
                         + " -> ".join(cyc + [cyc[0]])
                         + " — two threads entering from different "
                           "ends deadlock"),
                detail="->".join(sorted(cyc))))

        # -- unguarded writes
        for cname, ci in classes.items():
            if not ci.locks:
                continue
            sites: dict[str, list] = {}
            for m in ci.methods:
                if m in ci.excluded:
                    continue
                base = ci.entry_held.get(m, frozenset())
                for (attr, held, line) in ci.writes.get(m, ()):
                    sites.setdefault(attr, []).append(
                        (m, held | base, line))
            for attr, ws in sites.items():
                locked = [w for w in ws if w[1]]
                bare = [w for w in ws if not w[1]]
                if locked and bare:
                    for (m, _h, line) in bare:
                        emit(Finding(
                            checker=self.name, path=ci.relpath,
                            line=line, rule="unguarded-write",
                            scope=f"{cname}.{m}",
                            message=(
                                f"`self.{attr}` is written under a "
                                f"lock in {len(locked)} other "
                                f"site(s) but written here with no "
                                f"lock held"),
                            detail=attr))
        return by_file

    @staticmethod
    def _cycles(edges: dict[str, set[str]]) -> list[list[str]]:
        """Small-graph cycle enumeration (unique by node set)."""
        out: list[list[str]] = []
        seen_sets: set[frozenset] = set()

        def dfs(start, node, path, visiting):
            for nxt in sorted(edges.get(node, ())):
                if nxt == start and len(path) > 1:
                    key = frozenset(path)
                    if key not in seen_sets:
                        seen_sets.add(key)
                        out.append(list(path))
                elif nxt not in visiting and len(path) < 6:
                    visiting.add(nxt)
                    dfs(start, nxt, path + [nxt], visiting)
                    visiting.discard(nxt)

        for start in sorted(edges):
            dfs(start, start, [start], {start})
        return out
