"""Global lock-order checker (PR 16 tentpole, part 1).

The class-local lock-discipline checker (locks.py) orders locks
*within* one class; the deadlocks the serving tier can actually
manufacture are cross-module: peerlink stripe conds vs. the
DistServer lock, the store world lock vs. the hub mutex, the
frontdoor loop lock vs. worker-side state.  This checker builds the
ONE global lock-acquisition graph:

- nodes are lock identities — ``Class.attr`` for instance locks,
  ``path.py:var`` for module-level locks (from the shared
  concurrency model);
- an edge A → B means "somewhere, B is acquired while A is held",
  where "held" combines the lexical ``with`` nesting, the
  must-held-at-entry set propagated across call edges (the
  cross-module form of the "call with lock held" convention), and
  the transitive acquisitions of every callee reached under A;
- a cycle is a potential deadlock: two threads walking the cycle
  from different entry edges can block each other forever.

Re-entrant self-edges (RLock re-acquisition) are not edges.
Suppress a deliberate ordering with ``# lint: ok(lock-order)`` on
the acquisition (or call) line that closes the cycle, or via the
baseline with a written justification.
"""

from __future__ import annotations

import ast

from .concmodel import concurrency_model
from .engine import AnalysisContext, Checker, Finding


class LockOrderChecker(Checker):
    name = "lock-order"
    targets = ("etcd_tpu/",)

    def __init__(self):
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

    def _analyze(self, root: str,
                 ctx: AnalysisContext) -> dict[str, list[Finding]]:
        model = concurrency_model(root, ctx)
        entry = model.entry_held_intersection()
        acq = model.transitive_acquires()

        # edge (a, b) -> representative site (path, scope, line, why)
        edges: dict[tuple[str, str], tuple] = {}

        def add_edge(a: str, b: str, fi, line: int,
                     why: str) -> None:
            if a == b:
                return  # RLock re-entry
            edges.setdefault(
                (a, b), (fi.relpath, fi.scope, line, why))

        for key, fi in model.functions.items():
            if fi.scope.split(".")[-1] == "__init__":
                continue  # construction is single-threaded
            base = entry.get(key, frozenset())
            for lock, held, line in fi.acquires:
                for h in frozenset(held) | base:
                    add_edge(h, lock, fi, line,
                             f"acquires {lock}")
            for callee, held, line in fi.edges:
                outer = frozenset(held) | base
                if not outer:
                    continue
                for t in acq.get(callee, ()):
                    cs = callee[1]
                    for h in outer:
                        add_edge(h, t, fi, line,
                                 f"call into {cs} acquires {t}")

        graph: dict[str, set[str]] = {}
        for a, b in edges:
            graph.setdefault(a, set()).add(b)
            graph.setdefault(b, set())

        by_file: dict[str, list[Finding]] = {}
        for cycle in self._cycles(graph):
            # anchor the finding at the first edge's site; the
            # detail is the rotated lock chain, so the fingerprint
            # survives edits anywhere along the cycle
            pairs = list(zip(cycle, cycle[1:] + cycle[:1]))
            path, scope, line, why = edges[pairs[0]]
            chain = " -> ".join(cycle + [cycle[0]])
            sites = "; ".join(
                f"{edges[p][0]}:{edges[p][2]} ({edges[p][3]})"
                for p in pairs)
            by_file.setdefault(path, []).append(Finding(
                checker=self.name, path=path, line=line,
                rule="lock-cycle", scope=scope, detail=chain,
                message=(f"potential deadlock: lock-order cycle "
                         f"{chain} [{sites}]")))
        return by_file

    @staticmethod
    def _cycles(graph: dict[str, set[str]]) -> list[list[str]]:
        """Enumerate unique simple cycles (each reported once, from
        its lexicographically-least node; path length capped)."""
        out: list[list[str]] = []
        seen: set[frozenset] = set()

        def dfs(start: str, node: str,
                path: list[str]) -> None:
            if len(path) > 6:
                return
            for nxt in sorted(graph.get(node, ())):
                if nxt == start:
                    key = frozenset(path)
                    if key not in seen and path[0] == min(path):
                        seen.add(key)
                        out.append(list(path))
                elif nxt not in path and nxt > start:
                    dfs(start, nxt, path + [nxt])

        for start in sorted(graph):
            dfs(start, start, [start])
        return out
