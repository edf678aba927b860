"""Visitor engine + finding/baseline plumbing shared by the checkers.

Design points:

- One parsed AST per file per run (checkers share the cache).
- Findings carry a **stable fingerprint** (checker, file, enclosing
  scope, rule, detail — never the line number) so routine edits above
  a legacy finding don't churn the baseline.
- The baseline is a committed JSON file mapping fingerprint →
  metadata + a one-line human justification.  ``scripts/lint
  --baseline`` refreshes it; a finding whose fingerprint is absent
  fails the gate.
- ``# lint: ok(<checker>)`` on the flagged line is an inline
  suppression for cases where a comment at the site beats a baseline
  entry.
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field


@dataclass
class Finding:
    checker: str          # checker name ("tracer-purity", ...)
    path: str             # repo-relative posix path
    line: int             # 1-based line (display only, not identity)
    rule: str             # short rule id ("host-cast", "lock-cycle")
    scope: str            # enclosing Class.function ("" = module)
    message: str          # human sentence
    detail: str = ""      # small stable token (attr/call name)

    @property
    def fingerprint(self) -> str:
        key = "|".join((self.checker, self.path, self.scope,
                        self.rule, self.detail))
        return hashlib.sha1(key.encode()).hexdigest()[:16]

    def render(self) -> str:
        return (f"{self.path}:{self.line}: [{self.checker}/"
                f"{self.rule}] {self.message}"
                f"  (fingerprint {self.fingerprint})")


class AnalysisContext:
    """Shared per-run state: ONE parsed AST per file (checkers and
    the call graph read the same cache), plus the lazily-built
    whole-program :class:`~.callgraph.CallGraph`.  It dies with the
    run, so stale-root leaks between fixture trees are
    impossible."""

    def __init__(self, root: str):
        self.root = root
        self._cache: dict[str, tuple[ast.AST, str]] = {}
        self._lines: dict[str, list[str]] = {}
        self._cg = None
        self._parse_lock = threading.Lock()

    def parse(self, relpath: str) -> tuple[ast.AST, str]:
        # lock-free on the hot path; checkers run on a thread pool
        # and may miss concurrently (whole-tree checkers parse files
        # outside the run's selection), so misses serialize
        hit = self._cache.get(relpath)
        if hit is None:
            with self._parse_lock:
                hit = self._cache.get(relpath)
                if hit is None:
                    path = os.path.join(self.root, relpath)
                    with open(path) as fh:
                        source = fh.read()
                    hit = (ast.parse(source, filename=relpath),
                           source)
                    self._cache[relpath] = hit
        return hit

    def lines(self, relpath: str) -> list[str]:
        hit = self._lines.get(relpath)
        if hit is None:
            try:
                hit = self.parse(relpath)[1].splitlines()
            except (OSError, SyntaxError):
                hit = []
            self._lines[relpath] = hit
        return hit

    @property
    def callgraph(self):
        if self._cg is None:
            from .callgraph import CallGraph

            self._cg = CallGraph(self.root, self.parse)
        return self._cg


class Checker:
    """One registered analysis.  Subclasses set ``name`` and
    ``targets`` (repo-relative paths or ``dir/`` prefixes) and
    implement ``check``.  ``ctx`` is the run's
    :class:`AnalysisContext`; cross-module checkers query
    ``ctx.callgraph``."""

    name = "base"
    targets: tuple[str, ...] = ()

    def wants(self, relpath: str) -> bool:
        for t in self.targets:
            if relpath == t or (t.endswith("/")
                                and relpath.startswith(t)):
                return True
        return False

    def check(self, relpath: str, tree: ast.AST, source: str,
              root: str | None = None, ctx: AnalysisContext | None
              = None) -> list[Finding]:  # pragma: no cover
        raise NotImplementedError


@dataclass
class Baseline:
    """Accepted legacy findings: fingerprint → entry with a one-line
    ``justification`` (required — the gate rejects a baseline entry
    without one)."""

    entries: dict[str, dict] = field(default_factory=dict)

    def accepts(self, f: Finding) -> bool:
        return f.fingerprint in self.entries

    def unjustified(self) -> list[str]:
        return [fp for fp, e in sorted(self.entries.items())
                if not str(e.get("justification", "")).strip()
                or str(e.get("justification", "")).startswith("TODO")]


def load_baseline(path: str) -> Baseline:
    if not os.path.exists(path):
        return Baseline()
    with open(path) as f:
        doc = json.load(f)
    return Baseline(entries=doc.get("entries", {}))


def save_baseline(path: str, findings: list[Finding],
                  prior: Baseline) -> Baseline:
    """Write the current findings as the accepted baseline, keeping
    prior justifications for fingerprints that still fire; new
    entries get a TODO the author must replace (the gate and the
    tier-1 test both reject TODO justifications)."""
    entries: dict[str, dict] = {}
    for f in findings:
        old = prior.entries.get(f.fingerprint, {})
        entries[f.fingerprint] = {
            "checker": f.checker,
            "path": f.path,
            "rule": f.rule,
            "scope": f.scope,
            "detail": f.detail,
            "message": f.message,
            "justification": old.get("justification",
                                     "TODO: justify or fix"),
        }
    doc = {"version": 1, "entries": dict(sorted(entries.items()))}
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=2, sort_keys=False)
        fh.write("\n")
    return Baseline(entries=entries)


def prune_baseline(path: str, findings: list[Finding],
                   prior: Baseline) -> list[str]:
    """Drop baseline entries whose fingerprints no longer fire
    (keeping live entries' justifications verbatim) and rewrite the
    file.  Returns the pruned fingerprints, sorted."""
    live = {f.fingerprint for f in findings}
    stale = sorted(set(prior.entries) - live)
    if not stale:
        return []
    entries = {fp: e for fp, e in prior.entries.items()
               if fp in live}
    doc = {"version": 1, "entries": dict(sorted(entries.items()))}
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=2, sort_keys=False)
        fh.write("\n")
    prior.entries = entries
    return stale


def _suppressed(source_lines: list[str], f: Finding) -> bool:
    if not (1 <= f.line <= len(source_lines)):
        return False
    return f"lint: ok({f.checker})" in source_lines[f.line - 1]


def target_files(root: str, checkers) -> dict[str, list]:
    """relpath -> [checkers wanting it], expanded from each
    checker's ``targets`` (``dir/`` prefixes walked)."""
    wanted: dict[str, list] = {}
    for c in checkers:
        for t in c.targets:
            if t.endswith("/"):
                base = os.path.join(root, t)
                for dirpath, dirs, files in os.walk(base):
                    dirs[:] = [d for d in dirs
                               if d != "__pycache__"]
                    for fn in files:
                        if not fn.endswith(".py"):
                            continue
                        rel = os.path.relpath(
                            os.path.join(dirpath, fn), root)
                        rel = rel.replace(os.sep, "/")
                        wanted.setdefault(rel, []).append(c)
            else:
                if os.path.exists(os.path.join(root, t)):
                    wanted.setdefault(t, []).append(c)
    return wanted


def _record_run_metrics(checkers, findings: list[Finding],
                        seconds: float,
                        timings: dict[str, float] | None = None
                        ) -> None:
    """Publish the run summary through the obs registry (CATALOG
    families ``etcd_lint_findings{checker}`` /
    ``etcd_lint_run_seconds{checker}``) — best-effort; analysis must
    keep working even if the obs package is mid-refactor.  Wall time
    is labeled per checker (fan-out means they overlap; the
    ``_total`` child is the run's actual elapsed time, not the
    sum)."""
    try:
        from ..obs.metrics import registry
    except Exception:  # pragma: no cover - bootstrap order
        return
    per: dict[str, int] = {}
    for f in findings:
        per[f.checker] = per.get(f.checker, 0) + 1
    for c in checkers:
        registry.gauge("etcd_lint_findings", checker=c.name).set(
            per.get(c.name, 0))
    for name, secs in (timings or {}).items():
        registry.gauge("etcd_lint_run_seconds",
                       checker=name).set(secs)
    registry.gauge("etcd_lint_run_seconds",
                   checker="_total").set(seconds)


def run_checkers(root: str, checkers,
                 paths: list[str] | None = None,
                 ctx: AnalysisContext | None = None,
                 jobs: int | None = None) -> list[Finding]:
    """Run every checker over its target files under ``root``.
    ``paths`` restricts the run (repo-relative; ``./``-prefixes are
    normalized, and a path that selects no target file raises — a
    silent zero-findings pass on a typo'd path would read as
    clean).  Returns findings sorted by (path, line), inline
    suppressions already dropped; the run summary lands in the obs
    registry (``etcd_lint_findings``/``etcd_lint_run_seconds``).

    Checkers fan out over a thread pool (``jobs`` caps the width;
    default one thread per checker up to the CPU count).  They share
    ONE context: the AST cache is pre-filled serially below, and the
    call graph / concurrency model guard their lazy builds with
    their own locks, so the per-checker work is read-mostly."""
    t0 = time.monotonic()
    if paths is not None:
        paths = [os.path.normpath(p).replace(os.sep, "/")
                 for p in paths]
    ctx = ctx if ctx is not None else AnalysisContext(root)
    wanted = target_files(root, checkers)

    if paths is not None:
        unknown = [p for p in paths if p not in wanted]
        if unknown:
            raise ValueError(
                f"path(s) select no analysis target: {unknown} "
                f"(targets are repo-relative, e.g. "
                f"etcd_tpu/wal/wal.py)")

    selected = [rel for rel in sorted(wanted)
                if paths is None or rel in paths]
    for rel in selected:
        ctx.parse(rel)

    def run_one(c) -> tuple[list[Finding], float]:
        ct0 = time.monotonic()
        out: list[Finding] = []
        for rel in selected:
            if c not in wanted[rel]:
                continue
            tree, source = ctx.parse(rel)
            out.extend(c.check(rel, tree, source, root=root,
                               ctx=ctx))
        return out, time.monotonic() - ct0

    width = max(1, min(len(checkers), jobs if jobs is not None
                       else (os.cpu_count() or 4)))
    timings: dict[str, float] = {}
    findings: list[Finding] = []
    seen: set[tuple[str, int]] = set()
    with ThreadPoolExecutor(max_workers=width) as pool:
        # ex.map keeps registration order, so the dedup pass below
        # is deterministic regardless of completion order
        for c, (out, secs) in zip(checkers,
                                  pool.map(run_one, checkers)):
            timings[c.name] = secs
            for f in out:
                # cross-module checkers may flag a file other than
                # the one being checked — suppression comments are
                # honored at the FLAGGED site, and a finding reached
                # via two different entry files counts once
                lines = ctx.lines(f.path)
                key = (f.fingerprint, f.line)
                if key not in seen and not _suppressed(lines, f):
                    seen.add(key)
                    findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    _record_run_metrics(checkers, findings,
                        time.monotonic() - t0, timings)
    return findings


# -- small shared AST helpers -------------------------------------------------

#: the constructors whose result a checker treats as a lock (the last
#: name of the call): the standard library's, and ``utils/trace.py``'s
#: ``TimedRLock``, an ``RLock`` that times its waits and holds
LOCK_CTORS = frozenset({"Lock", "RLock", "Condition", "Semaphore",
                        "BoundedSemaphore", "TimedRLock"})


def dotted_name(node: ast.AST) -> str:
    """``a.b.c`` for Name/Attribute chains, "" otherwise."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def scope_map(tree: ast.AST) -> dict[ast.AST, str]:
    """node -> enclosing ``Class.function`` scope ("" = module) for
    every node in the module (deepest function wins)."""
    owner: dict[ast.AST, str] = {}

    def walk(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef,
                                  ast.AsyncFunctionDef)):
                scope = f"{prefix}.{child.name}" if prefix \
                    else child.name
                # plain assignment: inner functions are walked after
                # their enclosing one, so the DEEPEST scope wins —
                # scope feeds the finding fingerprint, so this must
                # match the pre-consolidation per-checker behavior
                for n in ast.walk(child):
                    owner[n] = scope
                walk(child, scope)
            elif isinstance(child, ast.ClassDef):
                name = f"{prefix}.{child.name}" if prefix \
                    else child.name
                walk(child, name)
            else:
                walk(child, prefix)

    walk(tree, "")
    return owner


def iter_functions(tree: ast.AST):
    """Yield (scope, node) for every function/method in the module;
    scope is ``Class.name`` or ``name`` (nested: ``outer.inner``)."""

    def walk(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef,
                                  ast.AsyncFunctionDef)):
                scope = f"{prefix}.{child.name}" if prefix \
                    else child.name
                yield scope, child
                yield from walk(child, scope)
            elif isinstance(child, ast.ClassDef):
                name = f"{prefix}.{child.name}" if prefix \
                    else child.name
                yield from walk(child, name)
            else:
                yield from walk(child, prefix)

    yield from walk(tree, "")
