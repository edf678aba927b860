"""Blocking-under-lock checker (PR 16 tentpole, part 2).

The static form of the bug class PR 6 found dynamically (snapshot
serialization stalling every handler under the server lock) and
PR 12 re-found in the frontdoor (one wedged loop thread starves all
tenants): a *blocking* operation executed while a hot-path lock is
held turns one slow syscall into a cluster-wide convoy.

Blocking categories (from the shared concurrency model):

- ``fsio``    — ``os.fsync`` / ``os.fdatasync`` / ``.fsync()``
- ``socket``  — ``.sendall`` / ``.recv`` / ``.accept`` /
  ``.connect`` / ``socket.create_connection``
- ``sleep``   — ``time.sleep``
- ``queue``   — blocking ``queue.get`` (and ``put`` on a *bounded*
  queue; puts to unbounded queues never block)
- ``subprocess`` — any ``subprocess.*`` spawn
- ``jit-dispatch`` — a call that reaches a ``@jax.jit`` root (the
  purity walk's dispatch roots): first-call tracing can take
  seconds

An operation is flagged when a HOT lock is lexically held at the
site **or** may be held at entry to the containing function (union
propagation over call edges — the callee form "helper does the
fsync, caller holds the lock" is the common shape).  Only the hot
set below is enforced; cold, short-critical-section locks (metrics
counters, backoff state) may guard whatever they like.

Suppress a deliberate case with ``# lint: ok(blocking-under-lock)``
on the flagged line, or baseline it with a justification (e.g. the
WAL fsync under the DistServer lock *is* the persist-before-ack
durability contract).
"""

from __future__ import annotations

import ast

from .concmodel import concurrency_model
from .engine import AnalysisContext, Checker, Finding

#: locks whose critical sections sit on serving hot paths
HOT_LOCKS = frozenset({
    "Store.world_lock",    # every read/write/watch touches it
    "WatcherHub.mutex",    # watcher tables + history scans
    "DistServer.lock",     # raft state; all peer + client traffic
    "FrontDoor._lock",     # loop<->worker mailbox; loop liveness
    "_Stripe.cond",        # peerlink channel stripes
    "KeepAlivePool._lock",  # shared conn pool on the send path
})

#: (lock, category) pairs that are the DESIGN, not a bug — allowed
#: in code rather than via N identical baseline entries.  Today:
#: every raft step (tick/append/vote/commit) IS a jit dispatch
#: executed under the server lock — the lock exists precisely to
#: serialize those device-state transitions, and steady-state
#: dispatch is a warmed cache hit, not a trace.  fsio under the
#: same lock is NOT allowed here: the WAL-fsync sites are
#: individually baselined so a *new* fsync-under-lock still fails
#: the gate.
ALLOWED_PAIRS = frozenset({
    ("DistServer.lock", "jit-dispatch"),
})


class BlockingUnderLockChecker(Checker):
    name = "blocking-under-lock"
    targets = ("etcd_tpu/",)

    def __init__(self, hot_locks: frozenset = HOT_LOCKS,
                 allowed_pairs: frozenset = ALLOWED_PAIRS):
        self.hot_locks = hot_locks
        self.allowed_pairs = allowed_pairs
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
        entry = model.entry_held_union(self.hot_locks)

        by_file: dict[str, list[Finding]] = {}
        seen: set[tuple] = set()
        for key, fi in model.functions.items():
            if fi.scope.split(".")[-1] == "__init__":
                continue
            inherited = entry.get(key, frozenset())
            for cat, op, held, line in fi.blocking:
                lexical = frozenset(held) & self.hot_locks
                for lock in sorted(lexical | inherited):
                    if (lock, cat) in self.allowed_pairs:
                        continue
                    detail = f"{lock}|{op}"
                    dedup = (fi.relpath, fi.scope, detail)
                    if dedup in seen:
                        continue
                    seen.add(dedup)
                    how = ("under" if lock in lexical
                           else "reachable with")
                    by_file.setdefault(fi.relpath, []).append(
                        Finding(
                            checker=self.name, path=fi.relpath,
                            line=line, rule=f"blocking-{cat}",
                            scope=fi.scope, detail=detail,
                            message=(f"blocking op {op} ({cat}) "
                                     f"{how} hot lock {lock} "
                                     f"held")))
        return by_file
