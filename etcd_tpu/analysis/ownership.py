"""Thread-ownership checker + domain registry (PR 16 tentpole,
part 3).

Several pieces of state are single-writer by *convention*: the
frontdoor loop thread is the sole owner of per-conn state, the
distpipe per-channel bookkeeping mutates only under the owning
server's lock.  Those conventions live here as checkable facts:

- **Annotations** (in server code): ``# owner: <domain>`` trailing
  an ``self.attr = ...`` assignment declares the attribute a member
  of the domain; the same marker on a ``def`` line declares an
  owner-only method (call sites from non-owner threads are flagged).
- **Registry** (this module): ``DOMAINS`` maps each domain name to
  the thread/process roots allowed to write it — ``(relpath,
  scope)`` function keys, typically thread targets discovered by
  the call graph (``threading.Thread(target=...)``) or serve
  entry points listed in ``EXTRA_ROOTS``.

The checker walks forward from every root through the resolved
call-edge map (spawn boundaries cut the walk: a spawned target is a
new root, not a callee) and flags any write to a domain member from
a function reachable from a root outside the domain's owner set.
``__init__`` writes are exempt — construction happens before the
object is shared.

Suppress with ``# lint: ok(thread-ownership)`` on the write line,
or baseline with a justification.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass

from .concmodel import concurrency_model
from .engine import AnalysisContext, Checker, Finding

_OWNER_RE = re.compile(r"#\s*owner:\s*([A-Za-z0-9_-]+)")


@dataclass(frozen=True)
class Domain:
    """One ownership domain: the roots allowed to write it, plus an
    optional lock-guard escape.  ``guard`` names a lock id
    (``Class.attr``); when set, a NON-owner root may access the
    domain as long as that lock is held at the site (lexically or
    must-held at entry to the containing function) — the shape of
    the distpipe contract, where peerlink reader threads absorb
    acks into pipeline state but only ever under the server lock.
    Without a guard the domain is thread-exclusive (frontdoor
    per-conn state)."""

    owners: tuple[tuple[str, str], ...]  # (relpath, scope) roots
    doc: str = ""
    guard: str | None = None


#: The real tree's domains.  Owner scopes are thread-entry
#: functions (Thread targets / serve entry points); a domain member
#: written from any OTHER root is a finding.
DOMAINS: dict[str, Domain] = {
    "frontdoor-loop": Domain(
        owners=(
            ("etcd_tpu/server/frontdoor.py", "FrontDoor._run"),
        ),
        doc=("per-connection state (_Conn fields, conn/timer "
             "tables): written only by the frontdoor event-loop "
             "thread; workers hand results back via the _post "
             "mailbox")),
    "distpipe-state": Domain(
        owners=(
            ("etcd_tpu/server/distserver.py", "DistServer.run"),
            ("etcd_tpu/server/distserver.py",
             "_make_peer_handler.Handler.do_POST"),
        ),
        doc=("append-pipeline per-peer bookkeeping: mutated from "
             "the run loop, the frame handler, AND the peerlink "
             "channel threads' ack/fail callbacks — every touch "
             "under the owning server's lock (the distpipe module "
             "docstring's contract, now checked)"),
        guard="DistServer.lock"),
}

#: Process/serve entry points the Thread(target=...) scan cannot
#: see: the threaded peer-HTTP handler.
EXTRA_ROOTS: tuple[tuple[str, str], ...] = (
    ("etcd_tpu/server/distserver.py",
     "_make_peer_handler.Handler.do_POST"),
)


def _iter_class_body(node: ast.ClassDef):
    """Walk a class body without descending into nested classes
    (they are their own ClassModels)."""
    stack = list(node.body)
    while stack:
        n = stack.pop()
        if isinstance(n, ast.ClassDef):
            continue
        yield n
        stack.extend(ast.iter_child_nodes(n))


class OwnershipChecker(Checker):
    name = "thread-ownership"
    targets = ("etcd_tpu/",)

    def __init__(self, domains: dict[str, Domain] | None = None,
                 extra_roots: tuple | None = None):
        self.domains = DOMAINS if domains is None else domains
        self.extra_roots = EXTRA_ROOTS if extra_roots is None \
            else extra_roots
        self._cache: dict[str, dict[str, list[Finding]]] = {}

    def check(self, relpath: str, tree: ast.AST, source: str,
              root: str | None = None,
              ctx: AnalysisContext | None = None) -> list[Finding]:
        if root is None or ctx is None:
            return []
        by_file = self._cache.get(root)
        if by_file is None:
            by_file = self._analyze(root, ctx)
            self._cache[root] = by_file
        return list(by_file.get(relpath, ()))

    # ------------------------------------------------------------------

    def _collect_annotations(self, model, ctx):
        """(class, attr) -> (domain, relpath, line) for attribute
        members; (class, method) -> same for owner-only defs;
        plus a list of unknown-domain findings."""
        attrs: dict[tuple[str, str], tuple] = {}
        methods: dict[tuple[str, str], tuple] = {}
        bad: list[Finding] = []

        def domain_on(rel: str, line: int) -> str | None:
            lines = ctx.lines(rel)
            if 0 < line <= len(lines):
                m = _OWNER_RE.search(lines[line - 1])
                if m:
                    return m.group(1)
            return None

        for cm in model.classes.values():
            for n in _iter_class_body(cm.node):
                if isinstance(n, (ast.FunctionDef,
                                  ast.AsyncFunctionDef)):
                    d = domain_on(cm.relpath, n.lineno)
                    if d is None:
                        continue
                    key = (cm.name, n.name)
                    sink, scope = methods, \
                        f"{cm.scope}.{n.name}"
                elif isinstance(n, (ast.Assign, ast.AnnAssign)):
                    tgt = n.targets[0] if isinstance(
                        n, ast.Assign) else n.target
                    if not (isinstance(tgt, ast.Attribute)
                            and isinstance(tgt.value, ast.Name)
                            and tgt.value.id == "self"):
                        continue
                    d = domain_on(cm.relpath, n.lineno)
                    if d is None:
                        continue
                    key = (cm.name, tgt.attr)
                    sink, scope = attrs, cm.scope
                else:
                    continue
                if d not in self.domains:
                    bad.append(Finding(
                        checker=self.name, path=cm.relpath,
                        line=n.lineno, rule="unknown-domain",
                        scope=scope, detail=d,
                        message=(f"annotation names domain "
                                 f"{d!r} not in the ownership "
                                 f"registry (analysis/"
                                 f"ownership.py DOMAINS)")))
                    continue
                sink[key] = (d, cm.relpath, n.lineno)
        return attrs, methods, bad

    def _roots(self, model) -> set[tuple[str, str]]:
        roots: set[tuple[str, str]] = set()
        for fi in model.functions.values():
            for tkey, _name, _line in fi.spawns:
                roots.add(tkey)
        for key in getattr(model.cg, "thread_entry_points",
                           lambda: ())():
            if key in model.functions:
                roots.add(key)
        for key in self.extra_roots:
            if key in model.functions:
                roots.add(key)
        return roots

    def _analyze(self, root: str,
                 ctx: AnalysisContext) -> dict[str, list[Finding]]:
        model = concurrency_model(root, ctx)
        attrs, methods, bad = self._collect_annotations(model, ctx)
        by_file: dict[str, list[Finding]] = {}
        for f in bad:
            by_file.setdefault(f.path, []).append(f)
        if not attrs and not methods:
            return by_file

        roots = self._roots(model)
        # func key -> roots that reach it (forward BFS per root;
        # spawn boundaries were already cut in the edge map)
        reached_by: dict[tuple, set[tuple]] = {}
        for r in roots:
            seen = {r}
            frontier = [r]
            while frontier:
                k = frontier.pop()
                reached_by.setdefault(k, set()).add(r)
                for callee, _h, _l in model.functions[k].edges:
                    if callee not in seen:
                        seen.add(callee)
                        frontier.append(callee)

        # must-held-at-entry: the lock-guard escape accepts a guard
        # the caller is merely KNOWN to hold, not only lexical holds
        entry = model.entry_held_intersection()

        def flag(fi, line, held, rule, domain, what):
            dom = self.domains[domain]
            reaching = reached_by.get((fi.relpath, fi.scope), set())
            bad_roots = sorted(
                f"{r[1]}" for r in reaching
                if r not in dom.owners)
            if not bad_roots:
                return
            if dom.guard is not None:
                held_all = frozenset(held) | entry.get(
                    (fi.relpath, fi.scope), frozenset())
                if dom.guard in held_all:
                    return
                why = (f"without its guard lock {dom.guard} "
                       f"held, from non-owner thread root(s) "
                       f"{', '.join(bad_roots[:3])}")
            else:
                why = (f"from non-owner thread root(s) "
                       f"{', '.join(bad_roots[:3])}")
            by_file.setdefault(fi.relpath, []).append(Finding(
                checker=self.name, path=fi.relpath, line=line,
                rule=rule, scope=fi.scope,
                detail=f"{domain}|{what}",
                message=(f"{what} is owned by domain "
                         f"{domain!r} but reached {why}")))

        for key, fi in model.functions.items():
            if fi.scope.split(".")[-1] == "__init__":
                continue
            for cname, attr, held, line in fi.writes:
                hit = attrs.get((cname, attr))
                if hit is None:
                    continue
                flag(fi, line, held, "non-owner-write", hit[0],
                     f"{cname}.{attr}")
            for callee, held, line in fi.edges:
                cfi = model.functions[callee]
                if not cfi.class_name:
                    continue
                m = cfi.scope.rsplit(".", 1)[-1]
                hit = methods.get((cfi.class_name, m))
                if hit is None:
                    continue
                flag(fi, line, held, "non-owner-call", hit[0],
                     f"{cfi.class_name}.{m}()")
        return by_file
