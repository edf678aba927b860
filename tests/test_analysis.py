"""The static-analysis gate as a tier-1 test.

Two halves:

1. **Real tree**: running every checker over the repository yields no
   finding outside ``analysis_baseline.json``, and every baseline
   entry both carries a real justification and still fires (no stale
   entries silently shadowing future regressions).
2. **Seeded violations**: each checker fires on a minimal fixture
   snippet containing the hazard it exists for, and stays quiet on
   the corrected form — so a refactor that lobotomizes a checker
   fails here, not months later in production.
"""

from __future__ import annotations

import os
import textwrap

import pytest

from etcd_tpu.analysis import (
    ALL_CHECKERS,
    AnalysisContext,
    DeviceBoundaryChecker,
    DurabilityOrderingChecker,
    ErrorVocabularyChecker,
    LockDisciplineChecker,
    SeqContiguityChecker,
    StaticShapeChecker,
    TimeoutBandChecker,
    TracerPurityChecker,
    load_baseline,
    prune_baseline,
    run_checkers,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "analysis_baseline.json")

_REAL_TREE: list = []


def _real_tree_findings():
    """One shared full-tree pass for the real-tree tests (the walk
    parses ~25 files; no need to repeat it per test)."""
    if not _REAL_TREE:
        _REAL_TREE.append(run_checkers(REPO, ALL_CHECKERS))
    return _REAL_TREE[0]


def _fixture_root(tmp_path, relpath: str, body: str) -> str:
    p = tmp_path / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(body))
    return str(tmp_path)


def _rules(findings) -> set[str]:
    return {f.rule for f in findings}


# -- 1. the real tree ---------------------------------------------------------


def test_real_tree_has_no_new_findings():
    baseline = load_baseline(BASELINE)
    findings = _real_tree_findings()
    fresh = [f for f in findings if not baseline.accepts(f)]
    assert not fresh, "new static-analysis findings:\n" + "\n".join(
        f.render() for f in fresh)


def test_baseline_entries_are_justified_and_live():
    baseline = load_baseline(BASELINE)
    assert baseline.entries, "baseline unexpectedly empty"
    assert not baseline.unjustified(), (
        "baseline entries without a one-line justification: "
        f"{baseline.unjustified()}")
    findings = _real_tree_findings()
    live = {f.fingerprint for f in findings}
    stale = set(baseline.entries) - live
    assert not stale, (
        f"stale baseline entries (fixed findings still accepted — "
        f"prune with scripts/lint --baseline): {sorted(stale)}")


def test_both_lock_models_see_the_dist_tier_s_timed_lock():
    """``DistServer.lock`` is a ``TimedRLock`` (PR 40): the one set of
    lock constructors makes it a lock to the lock-discipline checker
    and to the concurrency model, with each of its ``with`` sites."""
    import ast

    from etcd_tpu.analysis.concmodel import concurrency_model
    from etcd_tpu.analysis.engine import LOCK_CTORS, AnalysisContext
    from etcd_tpu.analysis.locks import _scan_class

    assert {"RLock", "TimedRLock"} <= LOCK_CTORS
    rel = "etcd_tpu/server/distserver.py"
    with open(os.path.join(REPO, rel)) as f:
        src = f.read()
    node = next(n for n in ast.parse(src).body
                if isinstance(n, ast.ClassDef) and n.name == "DistServer")
    ci = _scan_class(rel, node)
    assert "lock" in ci.locks and "lock" not in ci.attr_types
    sites = sum(1 for acq in ci.acquires.values()
                for lock, _, _ in acq if lock == "lock")
    assert sites == src.count("with self.lock:") == 29
    model = concurrency_model(REPO, AnalysisContext(REPO))
    assert "lock" in model.classes["DistServer"].locks


# -- 2. tracer-purity fires on seeded violations ------------------------------


_PURITY_BAD = """
    import time
    import numpy as np
    import jax
    import jax.numpy as jnp

    @jax.jit
    def bad(x, n):
        if x > 0:                      # traced-branch
            x = x + 1
        k = int(x)                     # host-cast
        v = x.sum().item()             # host-sync
        h = np.asarray(x)              # host-sync (np on traced)
        t = time.time()                # impure-call
        for _ in range(n):             # traced-range
            x = x * 2
        return x + k + v + h.size + t
"""

_PURITY_GOOD = """
    import functools
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("flag", "n"))
    def good(x, flag, n):
        if flag:                       # static arg: fine
            x = x + 1
        if x is None:                  # identity check: fine
            return x
        w = x.shape[0]                 # shape access: fine
        for _ in range(n):             # static bound: fine
            x = x * 2
        return jnp.where(x > 0, x, -x) + w
"""


def test_purity_fires_on_each_seeded_hazard(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/ops/bad.py",
                         _PURITY_BAD)
    findings = run_checkers(root, [TracerPurityChecker()])
    assert {"traced-branch", "host-cast", "host-sync",
            "impure-call", "traced-range"} <= _rules(findings)


def test_purity_quiet_on_clean_jit(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/ops/good.py",
                         _PURITY_GOOD)
    assert run_checkers(root, [TracerPurityChecker()]) == []


def test_purity_follows_callee_with_tainted_args(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/ops/callee.py", """
        import jax

        def helper(y):
            return float(y)            # host-cast, via call taint

        @jax.jit
        def root_fn(x):
            return helper(x)
    """)
    findings = run_checkers(root, [TracerPurityChecker()])
    assert any(f.rule == "host-cast" and f.scope == "helper"
               for f in findings)


# -- 2b. cross-module purity taint (PR 4 tentpole) ----------------------------


_XMOD_HELPER = """
    def helper(y):
        return float(y)            # host-cast when y is traced
"""

_XMOD_ROOT = """
    import jax
    from etcd_tpu.wal.util import helper

    @jax.jit
    def root_fn(x):
        return helper(x)
"""


def test_purity_taint_crosses_module_boundaries(tmp_path):
    """The acceptance fixture: the per-module walk (cross_module=
    False, the pre-PR-4 behavior) provably misses a hazard the
    whole-program walk reports in the file that owns it."""
    _fixture_root(tmp_path, "etcd_tpu/wal/util.py", _XMOD_HELPER)
    root = _fixture_root(tmp_path, "etcd_tpu/ops/a.py", _XMOD_ROOT)
    old = run_checkers(
        root, [TracerPurityChecker(cross_module=False)])
    assert old == [], "per-module walk should NOT see the hazard"
    findings = run_checkers(root, [TracerPurityChecker()])
    assert any(f.rule == "host-cast"
               and f.path == "etcd_tpu/wal/util.py"
               and f.scope == "helper" for f in findings), findings


def test_purity_cross_module_follows_relative_and_alias(tmp_path):
    _fixture_root(tmp_path, "etcd_tpu/wal/util.py", """
        import numpy as np

        def helper(y):
            return np.asarray(y)   # host-sync when y is traced
    """)
    root = _fixture_root(tmp_path, "etcd_tpu/ops/a.py", """
        import jax
        from ..wal.util import helper as h

        @jax.jit
        def root_fn(x):
            return h(x)
    """)
    findings = run_checkers(root, [TracerPurityChecker()])
    assert any(f.rule == "host-sync"
               and f.path == "etcd_tpu/wal/util.py"
               for f in findings), findings


def test_purity_cross_module_suppression_at_flagged_site(tmp_path):
    """`# lint: ok(...)` is honored in the FILE THAT OWNS the
    hazard, not the entry module."""
    _fixture_root(tmp_path, "etcd_tpu/wal/util.py", """
        def helper(y):
            return float(y)  # lint: ok(tracer-purity)
    """)
    root = _fixture_root(tmp_path, "etcd_tpu/ops/a.py", _XMOD_ROOT)
    assert run_checkers(root, [TracerPurityChecker()]) == []


def test_purity_untainted_keyword_does_not_taint_callee(tmp_path):
    """A constant keyword argument must not taint the callee's
    parameter (the multiraft->batched `write_mode` false-positive
    class)."""
    _fixture_root(tmp_path, "etcd_tpu/wal/util.py", """
        def helper(y, mode="dense"):
            if mode == "scatter":  # mode is host data: fine
                return y * 2
            return y
    """)
    root = _fixture_root(tmp_path, "etcd_tpu/ops/a.py", """
        import jax
        from etcd_tpu.wal.util import helper

        @jax.jit
        def root_fn(x):
            return helper(x, mode="scatter")
    """)
    assert run_checkers(root, [TracerPurityChecker()]) == []


# -- 2c. the call graph itself ------------------------------------------------


def _callgraph_fixture(tmp_path) -> AnalysisContext:
    _fixture_root(tmp_path, "etcd_tpu/wal/util.py", """
        def helper(y):
            return y
    """)
    _fixture_root(tmp_path, "etcd_tpu/wal/__init__.py", """
        from .util import helper
    """)
    root = _fixture_root(tmp_path, "etcd_tpu/ops/a.py", """
        import etcd_tpu.wal.util
        import etcd_tpu.wal.util as wu
        from ..wal import helper as rel_reexp
        from etcd_tpu.wal import helper as abs_reexp
        from etcd_tpu.wal.util import helper as direct

        def drive(x):
            return (direct(x), rel_reexp(x), abs_reexp(x),
                    wu.helper(x), etcd_tpu.wal.util.helper(x))
    """)
    return AnalysisContext(root)


def test_callgraph_resolves_every_import_spelling(tmp_path):
    ctx = _callgraph_fixture(tmp_path)
    cg = ctx.callgraph
    for spelling in ("direct", "rel_reexp", "abs_reexp",
                     "wu.helper", "etcd_tpu.wal.util.helper"):
        res = cg.resolve_call("etcd_tpu/ops/a.py", spelling)
        assert [(r[0], r[1]) for r in res] == [
            ("etcd_tpu/wal/util.py", "helper")], (spelling, res)


def test_callgraph_call_sites_invert_resolution(tmp_path):
    ctx = _callgraph_fixture(tmp_path)
    sites = ctx.callgraph.call_sites_of(
        "etcd_tpu/wal/util.py", "helper")
    # all five spellings in drive() resolve back to the one def
    assert len(sites) == 5
    assert {(rel, scope) for rel, scope, _call in sites} == {
        ("etcd_tpu/ops/a.py", "drive")}


def test_callgraph_reverse_dependents_close_transitively(tmp_path):
    _fixture_root(tmp_path, "etcd_tpu/wal/util.py", "X = 1\n")
    _fixture_root(tmp_path, "etcd_tpu/wal/mid.py",
                  "from .util import X\n")
    root = _fixture_root(tmp_path, "etcd_tpu/ops/a.py",
                         "from ..wal.mid import X\n")
    ctx = AnalysisContext(root)
    deps = ctx.callgraph.reverse_dependents(
        {"etcd_tpu/wal/util.py"})
    assert deps == {"etcd_tpu/wal/mid.py", "etcd_tpu/ops/a.py"}
    # forward direction (a changed caller can create findings in
    # the modules it imports — the --changed scope needs both)
    fwd = ctx.callgraph.import_closure({"etcd_tpu/ops/a.py"})
    assert fwd == {"etcd_tpu/wal/mid.py", "etcd_tpu/wal/util.py"}


def test_scope_map_deepest_function_wins():
    """Finding.scope feeds the fingerprint: nodes inside nested
    functions must be owned by the DEEPEST enclosing scope, matching
    the pre-consolidation per-checker maps."""
    import ast as _ast

    from etcd_tpu.analysis.engine import scope_map

    tree = _ast.parse(
        "def outer():\n    def inner():\n        x = 1\n")
    sm = scope_map(tree)
    assign = next(n for n in _ast.walk(tree)
                  if isinstance(n, _ast.Assign))
    assert sm[assign] == "outer.inner"


# -- 3. lock-discipline fires on seeded violations ----------------------------


_LOCKS_BAD = """
    import threading

    class S:
        def __init__(self):
            self.a = threading.Lock()
            self.b = threading.Lock()
            self.n = 0

        def fwd(self):
            with self.a:
                with self.b:           # a -> b
                    self.n += 1

        def rev(self):
            with self.b:
                with self.a:           # b -> a: cycle
                    self.n += 1

        def bare(self):
            self.n = 5                 # unguarded-write
"""


def test_locks_fire_on_cycle_and_unguarded_write(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/store/store.py",
                         _LOCKS_BAD)
    findings = run_checkers(root, [LockDisciplineChecker()])
    assert "lock-cycle" in _rules(findings)
    assert any(f.rule == "unguarded-write" and f.detail == "n"
               for f in findings)


def test_locks_respect_call_with_lock_held_convention(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/store/store.py", """
        import threading

        class S:
            def __init__(self):
                self.lock = threading.Lock()
                self.n = 0

            def public(self):
                with self.lock:
                    self._locked_helper()

            def other(self):
                with self.lock:
                    self._locked_helper()

            def _locked_helper(self):
                self.n += 1            # held at every call site
    """)
    assert run_checkers(root, [LockDisciplineChecker()]) == []


def test_locks_cross_class_cycle_via_typed_attr(tmp_path):
    _fixture_root(tmp_path, "etcd_tpu/store/store.py", """
        import threading

        class Store:
            def __init__(self):
                self.world_lock = threading.Lock()
                self.srv = None

            def query(self):
                with self.world_lock:
                    self.srv.status()  # untyped: no edge back
    """)
    root = _fixture_root(
        tmp_path, "etcd_tpu/server/server.py", """
        import threading
        from etcd_tpu.store.store import Store

        class Server:
            def __init__(self):
                self.lock = threading.Lock()
                self.store = Store()

            def snapshot(self):
                with self.lock:
                    self.store.save()
    """)
    # add the reverse edge inside Store to complete the cycle
    (tmp_path / "etcd_tpu/store/store.py").write_text(
        textwrap.dedent("""
        import threading

        class Store:
            def __init__(self):
                self.world_lock = threading.Lock()
                self.srv = Server()

            def save(self):
                with self.world_lock:
                    return 1

            def query(self):
                with self.world_lock:
                    self.srv.snapshot()

        class Server:
            def __init__(self):
                self.lock = threading.Lock()
                self.store = Store()

            def snapshot(self):
                with self.lock:
                    self.store.save()
        """))
    findings = run_checkers(root, [LockDisciplineChecker()])
    assert "lock-cycle" in _rules(findings)


# -- 4. durability-ordering fires on seeded violations ------------------------


def test_durability_fires_on_unsynced_write(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/wal/wal.py", """
        import os

        class W:
            def bad_save(self, data):
                self.f.write(data)     # returns without fsync
                return True

            def bad_rename(self, a, b):
                os.rename(a, b)        # dir entry never synced
    """)
    findings = run_checkers(root, [DurabilityOrderingChecker()])
    scopes = {f.scope for f in findings
              if f.rule == "unsynced-return"}
    assert {"W.bad_save", "W.bad_rename"} <= scopes


def test_durability_quiet_when_paths_sync(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/wal/wal.py", """
        import os

        def fsync_dir(d):
            fd = os.open(d, os.O_RDONLY)
            os.fsync(fd)
            os.close(fd)

        class W:
            def sync(self):
                self.f.flush()
                os.fsync(self.f.fileno())

            def good_save(self, data):
                self.f.write(data)
                self.sync()
                return True

            def good_rename(self, a, b, d):
                os.rename(a, b)
                fsync_dir(d)

            def error_path_ok(self, data):
                self.f.write(data)
                raise RuntimeError("no ack here")

            def buffered(self, data):
                self.f.write(data)     # the one accepted pattern...

            def boundary(self, data):
                self.buffered(data)    # ...is dirty for CALLERS
                self.sync()
                return True
    """)
    findings = run_checkers(root, [DurabilityOrderingChecker()])
    scopes = {f.scope for f in findings}
    # buffered() itself is flagged (baseline-able); every synced or
    # raising path is clean, and the caller that syncs is clean
    assert scopes == {"W.buffered"}


def test_durability_delete_before_superseding_fsync_fires(tmp_path):
    """PR 6 deletion-ordering rule: an os.remove/unlink while an
    unsynced write is pending (the superseding artifact not yet
    durable) is the crash window that loses BOTH artifacts."""
    root = _fixture_root(tmp_path, "etcd_tpu/snap/snapshotter.py", """
        import os

        class S:
            def bad_purge(self, new, old, d):
                with open(new, "wb") as f:
                    f.write(b"snapshot")   # successor not fsynced...
                os.remove(old)             # ...old one already gone
                fd = os.open(d, os.O_RDONLY)
                os.fsync(fd)
                os.close(fd)

            def bad_gc_rename(self, a, b, old):
                os.rename(a, b)            # rename unsynced...
                os.unlink(old)             # ...delete races it
                os.fsync(self.dfd)
    """)
    findings = run_checkers(root, [DurabilityOrderingChecker()])
    deletes = [f for f in findings if f.rule == "unsynced-delete"]
    assert {f.scope for f in deletes} == {"S.bad_purge",
                                          "S.bad_gc_rename"}


def test_durability_delete_after_fsync_quiet(tmp_path):
    """The correct orderings stay quiet: fsync of the superseding
    artifact before every remove; a purge loop of independent
    deletes with one trailing dir fsync; per-remove dir fsync in a
    GC loop."""
    root = _fixture_root(tmp_path, "etcd_tpu/wal/wal.py", """
        import os

        def fsync_dir(d):
            fd = os.open(d, os.O_RDONLY)
            os.fsync(fd)
            os.close(fd)

        class W:
            def good_supersede(self, new, old, d):
                with open(new, "wb") as f:
                    f.write(b"x")
                    f.flush()
                    os.fsync(f.fileno())
                fsync_dir(d)
                os.remove(old)
                fsync_dir(d)

            def good_purge_loop(self, doomed, d):
                # snapshots are independent files: N removes + ONE
                # trailing dir fsync is a valid ordering (a delete
                # must not arm the delete rule for later deletes)
                for p in doomed:
                    os.remove(p)
                fsync_dir(d)

            def good_gc_loop(self, names, d):
                dfd = os.open(d, os.O_RDONLY)
                for name in names:
                    os.remove(name)
                    os.fsync(dfd)
                os.close(dfd)
    """)
    findings = run_checkers(root, [DurabilityOrderingChecker()])
    assert not [f for f in findings if f.rule == "unsynced-delete"], \
        [f.message for f in findings]
    # and the exit-synced rule still holds on these fixtures too
    assert not [f for f in findings if f.rule == "unsynced-return"], \
        [f.message for f in findings]


def test_durability_delete_dirty_from_callee_fires(tmp_path):
    """Cross-function propagation: a call to a function that exits
    with unsynced bytes counts as the pending write at a later
    delete site."""
    root = _fixture_root(tmp_path, "etcd_tpu/wal/wal.py", """
        import os

        class W:
            def buffered(self, data):
                self.f.write(data)        # exits dirty (baselined)

            def bad_caller(self, data, old):
                self.buffered(data)
                os.remove(old)            # delete under callee dirt
                os.fsync(self.f.fileno())
    """)
    findings = run_checkers(root, [DurabilityOrderingChecker()])
    assert "W.bad_caller" in {f.scope for f in findings
                              if f.rule == "unsynced-delete"}


# -- 4b. device-boundary fires on seeded violations ---------------------------


_BOUNDARY_BAD = """
    import numpy as np
    import jax

    @jax.jit
    def step(x):
        return x + 1

    def drive(x, n):
        for _ in range(n):
            x = step(x)
            h = np.asarray(x)            # per-round fetch (name)
            y = np.array(step(x))        # per-round fetch (direct)
        return h, y
"""

_BOUNDARY_GOOD = """
    import numpy as np
    import jax

    @jax.jit
    def step(x):
        return x + 1

    def drive(x, n):
        for _ in range(n):
            x = step(x)                  # device-resident across
        return np.asarray(x)             # rounds; ONE fetch at the end
"""


def test_boundary_fires_on_per_round_fetch(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/server/loop.py",
                         _BOUNDARY_BAD)
    findings = run_checkers(root, [DeviceBoundaryChecker()])
    assert len(findings) == 2
    assert _rules(findings) == {"per-round-fetch"}
    assert {f.detail for f in findings} == {"x", "step"}


def test_boundary_quiet_on_hoisted_fetch(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/server/loop.py",
                         _BOUNDARY_GOOD)
    assert run_checkers(root, [DeviceBoundaryChecker()]) == []


def test_boundary_resolves_imported_jit_roots(tmp_path):
    """The common split — kernels in ops/, the loop elsewhere — must
    still be seen through the ``from X import y`` edge."""
    _fixture_root(tmp_path, "etcd_tpu/ops/kern.py", """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("k",))
        def fused(x, k):
            return x * k
    """)
    root = _fixture_root(tmp_path, "etcd_tpu/server/loop.py", """
        import numpy as np
        from ..ops.kern import fused

        def drive(x, n):
            while n:
                n -= 1
                out = np.asarray(fused(x, 2))   # cross-module fetch
            return out
    """)
    findings = run_checkers(root, [DeviceBoundaryChecker()])
    assert [f.detail for f in findings] == ["fused"]


# -- 4c. static-shapes fires on seeded violations -----------------------------


_SHAPES_KERNEL = """
    import jax

    @jax.jit
    def kern(x):
        if x.shape[0] > 4:          # shape-dependent Python branch
            return x * 2
        return x
"""


def test_shapes_fire_on_divergent_call_sites(tmp_path):
    _fixture_root(tmp_path, "etcd_tpu/ops/kern.py", _SHAPES_KERNEL)
    root = _fixture_root(tmp_path, "etcd_tpu/server/loop.py", """
        import jax.numpy as jnp
        from ..ops.kern import kern

        def drive():
            a = kern(jnp.zeros((4,)))    # two statically different
            b = kern(jnp.zeros((8, 2)))  # shapes -> re-jit churn
            return a, b
    """)
    findings = run_checkers(root, [StaticShapeChecker()])
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "shape-branch"
    assert f.path == "etcd_tpu/ops/kern.py"
    assert f.detail == "kern.x"


def test_shapes_quiet_on_single_shape_and_unknown(tmp_path):
    _fixture_root(tmp_path, "etcd_tpu/ops/kern.py", _SHAPES_KERNEL)
    root = _fixture_root(tmp_path, "etcd_tpu/server/loop.py", """
        import jax.numpy as jnp
        from ..ops.kern import kern

        def drive(runtime_arr):
            a = kern(jnp.zeros((4,)))    # one proven shape
            b = kern(jnp.zeros((4,)))    # ... repeated
            c = kern(runtime_arr)        # unknown: not evidence
            return a, b, c
    """)
    assert run_checkers(root, [StaticShapeChecker()]) == []


def test_shapes_quiet_on_static_argnames_branch(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/ops/kern.py", """
        import functools
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("pad",))
        def kern(x, pad):
            if pad.shape and False:  # never: pad is declared static
                return x
            return x

        def drive():
            return kern(jnp.zeros((4,)), 1), kern(jnp.zeros((8,)), 2)
    """)
    assert run_checkers(root, [StaticShapeChecker()]) == []


# -- 4d. seq-contiguity fires on seeded violations ----------------------------


_SEQ_BAD = """
    class S:
        def alloc_then_yield(self):
            self.seq += 1
            yield "parked"                 # seq-gap: yield
            self.wal.append(self.seq)

        def alloc_outside_lock(self, rec):
            self.seq += 1
            with self.lock:                # seq-gap: lock-acquire
                self.wal.append(rec, self.seq)

        def orphan(self):
            self.seq += 1                  # seq-orphan: never read
"""

_SEQ_GOOD = """
    class S:
        def persist(self, ents):
            with self.lock:
                self.seq += 1
                ents.append(("rec", self.seq))
                self.wal.save(self.seq, ents)

        def batch(self, items):
            with self.lock:
                out = []
                for p in items:
                    self.seq += 1
                    out.append(("rec", self.seq, p))
                self.wal.save(self.seq, out)
"""


def test_seqcontig_fires_on_each_gap_class(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/server/distserver.py",
                         _SEQ_BAD)
    findings = run_checkers(root, [SeqContiguityChecker()])
    by_scope = {f.scope: f for f in findings}
    assert by_scope["S.alloc_then_yield"].detail == "yield"
    assert by_scope["S.alloc_outside_lock"].detail == "lock-acquire"
    assert by_scope["S.orphan"].rule == "seq-orphan"
    assert len(findings) == 3


def test_seqcontig_quiet_on_adjacent_allocation(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/server/distserver.py",
                         _SEQ_GOOD)
    assert run_checkers(root, [SeqContiguityChecker()]) == []


def test_seqcontig_fires_on_async_with_and_masked_read(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/server/distserver.py",
                         """
        class S:
            async def async_gap(self, rec):
                self.seq += 1
                async with self.lock:        # suspends AND acquires
                    self.wal.append(rec, self.seq)

            def masked_read(self):
                self.seq += 1
                self.log(self.seq)           # incidental early read
                with self.lock:              # still a gap before...
                    self.wal.append(self.seq)  # ...the REAL consume
    """)
    findings = run_checkers(root, [SeqContiguityChecker()])
    by_scope = {}
    for f in findings:
        by_scope.setdefault(f.scope, []).append(f)
    assert [f.detail for f in by_scope["S.async_gap"]] \
        == ["lock-acquire"]
    assert [f.detail for f in by_scope["S.masked_read"]] \
        == ["lock-acquire"]


# -- 4e. timeout-bands fires on seeded violations -----------------------------


def test_timeouts_fire_on_election_and_heartbeat_bands(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/server/boot.py", """
        from etcd_tpu.raft.core import Raft
        from etcd_tpu.raft.distmember import DistMember

        def build():
            mm = DistMember(8, 12, 0, 16, election=4)  # 4 < m=12
            rr = Raft(1, [2, 3], 5, 7)                 # hb 7 >= 5
            return mm, rr
    """)
    findings = run_checkers(root, [TimeoutBandChecker()])
    rules = _rules(findings)
    assert {"election-band", "heartbeat-band"} == rules
    assert any(f.detail == "DistMember:m>4" for f in findings)


def test_timeouts_fire_on_distserver_literal_peer_list(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/server/boot.py", """
        from etcd_tpu.server.distserver import DistServer

        def build(d):
            return DistServer(
                d, slot=0,
                peer_urls=["u0", "u1", "u2", "u3", "u4"],
                election=3)                # 3 < len(peer_urls)=5
    """)
    findings = run_checkers(root, [TimeoutBandChecker()])
    assert [f.rule for f in findings] == ["election-band"]


def test_timeouts_fire_on_argparse_defaults(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/server/boot.py", """
        import argparse

        def build_parser():
            p = argparse.ArgumentParser()
            p.add_argument("--dist-election-ticks", type=int,
                           default=2)
            p.add_argument("--cohosted-members", type=int,
                           default=5)
            return p
    """)
    findings = run_checkers(root, [TimeoutBandChecker()])
    assert [f.rule for f in findings] == ["cli-band"]
    assert "--dist-election-ticks" in findings[0].message


def test_timeouts_tables_match_real_signatures():
    """The checker's positional tables are copies of the real
    constructor signatures; this pins them so a signature change
    (param inserted before `election`, default bumped) fails HERE
    instead of silently muting every call-site check."""
    import ast as _ast

    from etcd_tpu.analysis.timeouts import (
        _ELECTION_CTORS,
        _HEARTBEAT_CTORS,
    )

    def params_defaults(relpath, name, method="__init__"):
        tree = _ast.parse(
            open(os.path.join(REPO, relpath)).read())
        for node in _ast.walk(tree):
            if isinstance(node, _ast.ClassDef) and node.name == name:
                node = next(n for n in node.body
                            if isinstance(n, _ast.FunctionDef)
                            and n.name == method)
            elif not (isinstance(node, _ast.FunctionDef)
                      and node.name == name):
                continue
            args = node.args
            names = [a.arg for a in args.args if a.arg != "self"]
            defaults = dict(zip(names[len(names)
                                      - len(args.defaults):],
                                args.defaults))
            kwdefs = {a.arg: d for a, d in
                      zip(args.kwonlyargs, args.kw_defaults)
                      if d is not None}
            return names, {**defaults, **kwdefs}
        raise AssertionError(f"{name} not found in {relpath}")

    sigs = {
        "DistMember": params_defaults(
            "etcd_tpu/raft/distmember.py", "DistMember"),
        "MultiRaft": params_defaults(
            "etcd_tpu/raft/multiraft.py", "MultiRaft"),
        "init_groups": params_defaults(
            "etcd_tpu/raft/batched.py", "init_groups"),
    }
    for leaf, (m_pos, e_pos, e_default) in _ELECTION_CTORS.items():
        names, defaults = sigs[leaf]
        assert names[m_pos] == "m", (leaf, names)
        assert names[e_pos] == "election", (leaf, names)
        d = defaults["election"]
        assert isinstance(d, _ast.Constant) and d.value == e_default

    hb_sigs = {
        "Raft": params_defaults("etcd_tpu/raft/core.py", "Raft"),
        "start_node": params_defaults(
            "etcd_tpu/raft/node.py", "start_node"),
        "restart_node": params_defaults(
            "etcd_tpu/raft/node.py", "restart_node"),
    }
    for leaf, (e_pos, h_pos) in _HEARTBEAT_CTORS.items():
        names, _d = hb_sigs[leaf]
        assert names[e_pos] == "election", (leaf, names)
        assert names[h_pos] == "heartbeat", (leaf, names)

    # DistServer: election is keyword-only with the default the
    # checker assumes (10), peer_urls keyword-only too
    names, defaults = params_defaults(
        "etcd_tpu/server/distserver.py", "DistServer")
    d = defaults["election"]
    assert isinstance(d, _ast.Constant) and d.value == 10


def test_timeouts_lease_band_fires_on_call_site(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/server/boot.py", """
        from etcd_tpu.server.distserver import DistServer

        def build(d):
            return DistServer(
                d, slot=0,
                peer_urls=["u0", "u1", "u2"],
                election=10, lease_ticks=9)   # 9 >= 10 - 1
    """)
    findings = run_checkers(root, [TimeoutBandChecker()])
    assert [f.rule for f in findings] == ["lease-band"]
    assert "lease_ticks=9" in findings[0].message


def test_timeouts_lease_band_fires_on_argparse_defaults(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/server/boot.py", """
        import argparse

        def build_parser():
            p = argparse.ArgumentParser()
            p.add_argument("--dist-election-ticks", type=int,
                           default=60)
            p.add_argument("--dist-lease-ticks", type=int,
                           default=58)     # 58 >= 60 - 6
            return p
    """)
    findings = run_checkers(root, [TimeoutBandChecker()])
    assert [f.rule for f in findings] == ["lease-band"]
    assert "--dist-lease-ticks" in findings[0].message


def test_timeouts_lease_band_quiet_on_banded_and_dynamic(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/server/boot.py", """
        import argparse

        from etcd_tpu.server.distserver import DistServer

        def build(d, lease_dyn):
            a = DistServer(d, slot=0,
                           peer_urls=["u0", "u1", "u2"],
                           election=10, lease_ticks=5)  # in band
            b = DistServer(d, slot=0,
                           peer_urls=["u0", "u1", "u2"],
                           election=10, lease_ticks=0)  # disabled
            c = DistServer(d, slot=0,
                           peer_urls=["u0", "u1", "u2"],
                           election=10,
                           lease_ticks=lease_dyn)       # dynamic
            # the constructor clamps election up to len(peer_urls):
            # lease 9 clears the CLAMPED band [12 - 1)
            e = DistServer(d, slot=0,
                           peer_urls=["u0", "u1", "u2", "u3", "u4",
                                      "u5", "u6", "u7", "u8", "u9",
                                      "ua", "ub"],
                           election=12, lease_ticks=9)
            return a, b, c, e

        def build_parser():
            p = argparse.ArgumentParser()
            p.add_argument("--dist-election-ticks", type=int,
                           default=60)
            p.add_argument("--dist-lease-ticks", type=int,
                           default=30)     # 30 < 60 - 6
            p.add_argument("--lease-off", type=int, default=0)
            return p
    """)
    assert run_checkers(root, [TimeoutBandChecker()]) == []


def test_timeouts_lease_drift_matches_runtime():
    """Drift-guard: the checker's stdlib-only copy of the drift
    formula must equal the runtime's (server/readindex.py) — the
    static band and the constructor validation may never diverge."""
    from etcd_tpu.analysis.timeouts import _lease_drift
    from etcd_tpu.server.readindex import lease_drift_ticks

    for e in (1, 2, 5, 9, 10, 11, 59, 60, 61, 100, 1000):
        assert _lease_drift(e) == lease_drift_ticks(e), e


def test_timeouts_quiet_on_banded_configs(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/server/boot.py", """
        import argparse

        from etcd_tpu.raft.core import Raft
        from etcd_tpu.raft.distmember import DistMember

        def build(m_dyn):
            a = DistMember(8, 12, 0, 16, election=16)
            b = DistMember(8, m_dyn, 0, 16, election=4)  # dynamic m
            c = Raft(1, [2, 3], 10, 1)
            return a, b, c

        def build_parser():
            p = argparse.ArgumentParser()
            p.add_argument("--dist-election-ticks", type=int,
                           default=60)
            p.add_argument("--cohosted-members", type=int,
                           default=3)
            return p
    """)
    assert run_checkers(root, [TimeoutBandChecker()]) == []


# -- 5. error-vocabulary fires on seeded violations ---------------------------


_VOCAB_FIXTURE_ERRORS = """
    ECODE_KEY_NOT_FOUND = 100
    ECODE_TEST_FAILED = 101

    class EtcdError(Exception):
        def __init__(self, code, cause=""):
            self.error_code = code
"""


def test_errorvocab_fires_on_seeded_violations(tmp_path):
    _fixture_root(tmp_path, "etcd_tpu/utils/errors.py",
                  _VOCAB_FIXTURE_ERRORS)
    root = _fixture_root(tmp_path, "etcd_tpu/store/store.py", """
        from etcd_tpu.utils.errors import EtcdError

        def a():
            raise Exception("opaque")          # generic

        def b():
            raise EtcdError(999, "no such code")

        def c():
            raise EtcdError(ECODE_NOT_A_CODE, "undefined name")

        class MadeUpError(Exception):
            pass

        def d():
            raise MadeUpError("not allow-listed")
    """)
    findings = run_checkers(root, [ErrorVocabularyChecker()])
    details = {f.detail for f in findings}
    assert {"Exception", "999", "ECODE_NOT_A_CODE",
            "MadeUpError"} <= details


def test_errorvocab_quiet_on_vocabulary_and_allowlist(tmp_path):
    _fixture_root(tmp_path, "etcd_tpu/utils/errors.py",
                  _VOCAB_FIXTURE_ERRORS)
    root = _fixture_root(tmp_path, "etcd_tpu/store/store.py", """
        from etcd_tpu.utils.errors import EtcdError

        def a(code):
            raise EtcdError(ECODE_KEY_NOT_FOUND, "x")

        def b():
            raise EtcdError(101, "literal in vocab")

        def c(code):
            raise EtcdError(code, "runtime-resolved")

        def d():
            raise ValueError("allow-listed stdlib")

        def e(resp):
            raise resp.err

        def f():
            try:
                raise ValueError()
            except ValueError:
                raise
    """)
    assert run_checkers(root, [ErrorVocabularyChecker()]) == []


# -- 5b. fault-vocabulary (PR 10) ---------------------------------------------


def test_faultvocab_fires_on_seeded_violations(tmp_path):
    from etcd_tpu.analysis import FaultVocabularyChecker

    root = _fixture_root(tmp_path, "etcd_tpu/wal/bad.py", """
        from ..utils import faults as _faults

        def a():
            _faults.hit("wal.fsnyc")        # typo'd point

        def b(point):
            _faults.hit(point)              # dynamic name

        def c():
            _faults.FAULTS.hit("not.in.catalog")
    """)
    findings = run_checkers(root, [FaultVocabularyChecker()])
    rules = _rules(findings)
    assert rules == {"unregistered-fault", "dynamic-fault-name"}
    details = {f.detail for f in findings}
    assert {"wal.fsnyc", "not.in.catalog", "_faults.hit"} <= details


def test_faultvocab_quiet_on_catalog_points(tmp_path):
    from etcd_tpu.analysis import FaultVocabularyChecker

    root = _fixture_root(tmp_path, "etcd_tpu/wal/good.py", """
        from ..utils import faults as _faults

        def a():
            _faults.hit("wal.fsync")

        def b():
            _faults.FAULTS.hit("peerlink.send", src="s0", dst="s1")

        def c(obj):
            obj.hit("whatever")             # not a faults receiver

        def d(d):
            d.hit()                         # no args, not faults-ish
    """)
    assert run_checkers(root, [FaultVocabularyChecker()]) == []


def test_faultvocab_skips_the_catalog_module(tmp_path):
    from etcd_tpu.analysis import FaultVocabularyChecker

    root = _fixture_root(tmp_path, "etcd_tpu/utils/faults.py", """
        FAULTS = None

        def hit(point):
            return FAULTS.hit(point)        # dynamic, but in-module
    """)
    assert run_checkers(root, [FaultVocabularyChecker()]) == []


# -- 6. engine plumbing -------------------------------------------------------


def test_inline_suppression_drops_finding(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/wal/wal.py", """
        class W:
            def bad(self, data):
                self.f.write(data)  # lint: ok(durability-ordering)
    """)
    assert run_checkers(root, [DurabilityOrderingChecker()]) == []


@pytest.mark.parametrize("tail", [
    "",                 # falls off the end
    "        return 1\n",  # explicit return site
])
def test_fingerprints_survive_line_shifts(tmp_path, tail):
    body = textwrap.dedent("""
        class W:
            def bad(self, data):
                self.f.write(data)
    """) + tail
    (tmp_path / "etcd_tpu/wal").mkdir(parents=True, exist_ok=True)
    (tmp_path / "etcd_tpu/wal/wal.py").write_text(body)
    root = str(tmp_path)
    (f1,) = run_checkers(root, [DurabilityOrderingChecker()])
    shifted = "# moved\n# down\n# by comments\n" + body
    (tmp_path / "etcd_tpu/wal/wal.py").write_text(shifted)
    (f2,) = run_checkers(root, [DurabilityOrderingChecker()])
    assert f1.fingerprint == f2.fingerprint
    assert f1.line != f2.line
    # the detail discriminates by mutating op, so a DIFFERENT future
    # mutation in the same function is NOT masked by this baseline
    assert "self.f.write" in f1.detail


def test_scripts_lint_exits_zero_on_real_tree():
    import subprocess
    import sys

    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint")],
        capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr


def test_lint_run_summary_lands_on_metrics(tmp_path):
    """The PR-4 obs satellite: a lint run publishes per-checker
    finding counts and wall time through the registry, visible in
    the GET /metrics exposition."""
    from etcd_tpu.obs.exporter import render_prometheus

    root = _fixture_root(tmp_path, "etcd_tpu/wal/wal.py", """
        class W:
            def bad_a(self, data):
                self.f.write(data)
                return 1

            def bad_b(self, data):
                self.f.write(data)
                return 2
    """)
    run_checkers(root, [DurabilityOrderingChecker()])
    text = render_prometheus().decode()
    assert ('etcd_lint_findings{checker="durability-ordering"} 2'
            in text), text
    line = next(ln for ln in text.splitlines()
                if ln.startswith("etcd_lint_run_seconds"))
    assert float(line.split()[-1]) > 0.0


def test_prune_baseline_drops_only_dead_entries(tmp_path):
    import json

    root = _fixture_root(tmp_path, "etcd_tpu/wal/wal.py", """
        class W:
            def bad(self, data):
                self.f.write(data)
                return 1
    """)
    findings = run_checkers(root, [DurabilityOrderingChecker()])
    (live,) = findings
    bl_path = str(tmp_path / "analysis_baseline.json")
    with open(bl_path, "w") as fh:
        json.dump({"version": 1, "entries": {
            live.fingerprint: {"checker": live.checker,
                               "path": live.path,
                               "justification": "still real"},
            "deadbeefdeadbeef": {"checker": "durability-ordering",
                                 "path": "gone.py",
                                 "justification": "fixed long ago"},
        }}, fh)
    prior = load_baseline(bl_path)
    removed = prune_baseline(bl_path, findings, prior)
    assert removed == ["deadbeefdeadbeef"]
    after = load_baseline(bl_path)
    assert set(after.entries) == {live.fingerprint}
    assert after.entries[live.fingerprint]["justification"] \
        == "still real"
    # idempotent: nothing left to prune
    assert prune_baseline(bl_path, findings, after) == []


# -- bounded-queue fires on seeded violations ---------------------------------


_BOUNDEDQ_BAD = """
    import queue
    from collections import deque

    class Hub:
        def __init__(self):
            self.jobs = queue.Queue()               # no bound
            self.infinite = queue.Queue(maxsize=0)  # stdlib "infinite"
            self.simple = queue.SimpleQueue()       # unbounded by design
            self.items = deque()                    # no maxlen
"""

_BOUNDEDQ_GOOD = """
    import queue
    from collections import deque

    class Hub:
        def __init__(self, depth):
            self.jobs = queue.Queue(maxsize=1024)
            self.window = queue.Queue(depth)    # policy exists in code
            self.items = deque(maxlen=4096)
            self.seeded = deque([1, 2], maxlen=8)
"""


def test_boundedq_fires_on_seeded_violations(tmp_path):
    from etcd_tpu.analysis import BoundedQueueChecker

    root = _fixture_root(tmp_path, "etcd_tpu/server/bad.py",
                         _BOUNDEDQ_BAD)
    findings = run_checkers(root, [BoundedQueueChecker()])
    assert len(findings) == 4
    assert _rules(findings) == {"unbounded-queue"}
    assert sorted(f.detail for f in findings) \
        == ["Queue", "Queue", "SimpleQueue", "deque"]


def test_boundedq_quiet_on_bounded_forms(tmp_path):
    from etcd_tpu.analysis import BoundedQueueChecker

    root = _fixture_root(tmp_path, "etcd_tpu/store/good.py",
                         _BOUNDEDQ_GOOD)
    assert run_checkers(root, [BoundedQueueChecker()]) == []


def test_boundedq_ignores_off_hot_path_dirs(tmp_path):
    from etcd_tpu.analysis import BoundedQueueChecker

    root = _fixture_root(tmp_path, "etcd_tpu/utils/bad.py",
                         _BOUNDEDQ_BAD)
    assert run_checkers(root, [BoundedQueueChecker()]) == []


def test_scripts_lint_changed_smoke():
    """`--changed` restricts to git-diff files + their call-graph
    closure and exits like the full gate (0 on a clean-or-baselined
    tree)."""
    import subprocess
    import sys

    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint"),
         "--changed"],
        capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "lint --changed:" in r.stdout


# -- concurrency suite: lock-order / blocking-under-lock / ownership ----------


_DEADLOCK = """
    import threading

    class Pair:
        def __init__(self):
            self.a = threading.Lock()
            self.b = threading.Lock()

        def forward(self):
            with self.a:
                with self.b:
                    return 1

        def backward(self):
            with self.b:
                with self.a:
                    return 2
"""

_DEADLOCK_OK = """
    import threading

    class Pair:
        def __init__(self):
            self.a = threading.Lock()
            self.b = threading.Lock()

        def forward(self):
            with self.a:
                with self.b:
                    return 1

        def also_forward(self):
            with self.a:
                with self.b:
                    return 2
"""


def test_lockorder_fires_on_seeded_cycle(tmp_path):
    from etcd_tpu.analysis import LockOrderChecker

    root = _fixture_root(tmp_path, "etcd_tpu/server/pair.py",
                         _DEADLOCK)
    findings = run_checkers(root, [LockOrderChecker()])
    assert _rules(findings) == {"lock-cycle"}
    (f,) = findings
    assert "Pair.a" in f.detail and "Pair.b" in f.detail


def test_lockorder_quiet_on_consistent_order(tmp_path):
    from etcd_tpu.analysis import LockOrderChecker

    root = _fixture_root(tmp_path, "etcd_tpu/server/pair.py",
                         _DEADLOCK_OK)
    assert run_checkers(root, [LockOrderChecker()]) == []


def test_lockorder_fires_on_cross_module_cycle(tmp_path):
    """The cycle the class-local lock-discipline checker CANNOT see:
    each module's nesting is clean, the inversion only appears when
    call edges carry held locks across files."""
    from etcd_tpu.analysis import LockOrderChecker

    _fixture_root(tmp_path, "etcd_tpu/server/xmod.py", """
        import threading
        from etcd_tpu.server.ymod import Helper

        class Front:
            def __init__(self):
                self.lk = threading.Lock()
                self.h = Helper()

            def ping(self):
                with self.lk:
                    return 1

            def forward(self):
                with self.lk:
                    self.h.grab()
    """)
    root = _fixture_root(tmp_path, "etcd_tpu/server/ymod.py", """
        import threading

        class Helper:
            def __init__(self):
                self.lk = threading.Lock()

            def grab(self):
                with self.lk:
                    return 1

            def backward(self, front: "Front"):
                with self.lk:
                    front.ping()
    """)
    findings = run_checkers(root, [LockOrderChecker()])
    assert _rules(findings) == {"lock-cycle"}
    (f,) = findings
    assert "Front.lk" in f.detail and "Helper.lk" in f.detail


def test_lockorder_suppression_on_closing_edge(tmp_path):
    from etcd_tpu.analysis import LockOrderChecker

    root = _fixture_root(tmp_path, "etcd_tpu/server/pair.py", """
        import threading

        class Pair:
            def __init__(self):
                self.a = threading.Lock()
                self.b = threading.Lock()

            def forward(self):
                with self.a:
                    with self.b:  # lint: ok(lock-order)
                        return 1

            def backward(self):
                with self.b:
                    with self.a:
                        return 2
    """)
    assert run_checkers(root, [LockOrderChecker()]) == []


_HOT = frozenset({"Srv.lk"})


def test_blocking_fires_in_callee_under_hot_lock(tmp_path):
    """The op lives in a CALLEE; only entry-held propagation across
    the call edge connects it to the lock."""
    from etcd_tpu.analysis import BlockingUnderLockChecker

    root = _fixture_root(tmp_path, "etcd_tpu/server/srv.py", """
        import os
        import threading

        class Srv:
            def __init__(self):
                self.lk = threading.Lock()

            def serve(self):
                with self.lk:
                    self._flush(3)

            def _flush(self, fd):
                os.fsync(fd)
    """)
    findings = run_checkers(
        root, [BlockingUnderLockChecker(hot_locks=_HOT)])
    assert _rules(findings) == {"blocking-fsio"}
    (f,) = findings
    assert f.scope == "Srv._flush"
    assert "Srv.lk" in f.detail


def test_blocking_quiet_outside_lock_and_on_cold_locks(tmp_path):
    from etcd_tpu.analysis import BlockingUnderLockChecker

    root = _fixture_root(tmp_path, "etcd_tpu/server/srv.py", """
        import os
        import threading

        class Srv:
            def __init__(self):
                self.lk = threading.Lock()
                self.cold = threading.Lock()

            def serve(self):
                with self.lk:
                    n = 1
                self._flush(3)

            def chilled(self):
                with self.cold:
                    os.fsync(3)

            def _flush(self, fd):
                os.fsync(fd)
    """)
    assert run_checkers(
        root, [BlockingUnderLockChecker(hot_locks=_HOT)]) == []


def test_blocking_allowed_pairs_and_suppression(tmp_path):
    from etcd_tpu.analysis import BlockingUnderLockChecker

    body = """
        import time
        import threading

        class Srv:
            def __init__(self):
                self.lk = threading.Lock()

            def serve(self):
                with self.lk:
                    time.sleep(0.1)%s
    """
    root = _fixture_root(tmp_path, "etcd_tpu/server/srv.py",
                         body % "")
    checker = BlockingUnderLockChecker(
        hot_locks=_HOT, allowed_pairs=frozenset({("Srv.lk",
                                                  "sleep")}))
    assert run_checkers(root, [checker]) == []
    root = _fixture_root(tmp_path, "etcd_tpu/server/srv2.py",
                         body % "  # lint: ok(blocking-under-lock)")
    assert run_checkers(
        root, [BlockingUnderLockChecker(hot_locks=_HOT)]) == []


def _ownership_fixture(tmp_path, suppress: str = ""):
    return _fixture_root(tmp_path, "etcd_tpu/server/zmod.py", f"""
        import threading

        class State:
            def __init__(self):
                self.cursor = 0  # owner: loop

        class Owner:
            def __init__(self, st: "State"):
                self.st = st

            def run(self):
                self.st.cursor = 1

        class Intruder:
            def __init__(self, st: "State"):
                self.st = st

            def poke(self):
                self.st.cursor = 2{suppress}

        def main():
            st = State()
            threading.Thread(target=Owner(st).run).start()
            threading.Thread(target=Intruder(st).poke).start()
    """)


def _loop_domain():
    from etcd_tpu.analysis import Domain

    return {"loop": Domain(
        owners=(("etcd_tpu/server/zmod.py", "Owner.run"),),
        doc="seeded fixture domain")}


def test_ownership_fires_on_non_owner_thread_write(tmp_path):
    from etcd_tpu.analysis import OwnershipChecker

    root = _ownership_fixture(tmp_path)
    findings = run_checkers(root, [OwnershipChecker(
        domains=_loop_domain(), extra_roots=())])
    assert _rules(findings) == {"non-owner-write"}
    (f,) = findings
    assert f.scope == "Intruder.poke"
    assert "Intruder.poke" in f.message
    # the owner's write from its own thread root is NOT among them
    assert all(x.scope != "Owner.run" for x in findings)


def test_ownership_suppression_and_unknown_domain(tmp_path):
    from etcd_tpu.analysis import OwnershipChecker

    root = _ownership_fixture(
        tmp_path, "  # lint: ok(thread-ownership)")
    assert run_checkers(root, [OwnershipChecker(
        domains=_loop_domain(), extra_roots=())]) == []

    root = _fixture_root(tmp_path, "etcd_tpu/server/qmod.py", """
        class Q:
            def __init__(self):
                self.x = 0  # owner: not-registered
    """)
    findings = run_checkers(root, [OwnershipChecker(
        domains=_loop_domain(), extra_roots=())])
    assert _rules(findings) == {"unknown-domain"}


def test_ownership_guard_lock_escape(tmp_path):
    """A guarded domain admits non-owner roots that hold the guard
    lock at the access site (the distpipe contract); dropping the
    lock re-arms the finding."""
    from etcd_tpu.analysis import Domain, OwnershipChecker

    body = """
        import threading

        class State:
            def __init__(self):
                self.lk = threading.Lock()
                self.cursor = 0  # owner: loop

        class Owner:
            def __init__(self, st: "State"):
                self.st = st

            def run(self):
                self.st.cursor = 1

        class Intruder:
            def __init__(self, st: "State"):
                self.st = st

            def poke(self):
                %s
                    self.st.cursor = 2

        def main():
            st = State()
            threading.Thread(target=Owner(st).run).start()
            threading.Thread(target=Intruder(st).poke).start()
    """
    domains = {"loop": Domain(
        owners=(("etcd_tpu/server/zmod.py", "Owner.run"),),
        doc="guarded fixture domain", guard="State.lk")}

    root = _fixture_root(tmp_path, "etcd_tpu/server/zmod.py",
                         body % "with self.st.lk:")
    assert run_checkers(root, [OwnershipChecker(
        domains=domains, extra_roots=())]) == []

    root = _fixture_root(tmp_path, "etcd_tpu/server/zmod.py",
                         body % "if True:")
    findings = run_checkers(root, [OwnershipChecker(
        domains=domains, extra_roots=())])
    assert _rules(findings) == {"non-owner-write"}
    assert "without its guard lock State.lk" in findings[0].message


@pytest.mark.parametrize("domain,rel,members", [
    ("frontdoor-loop", "etcd_tpu/server/frontdoor.py",
     {"mode", "rbuf", "out", "watchers", "deadline_at"}),
    ("distpipe-state", "etcd_tpu/server/distpipe.py",
     {"register", "ack", "bump_epoch"}),
])
def test_ownership_annotations_pin_real_server_state(domain, rel,
                                                     members):
    """Drift guard: the in-tree ``# owner:`` annotations must keep
    naming the attributes/methods the ownership story is about —
    silently dropping one would hollow out the checker without
    failing any fixture."""
    import re

    owner_re = re.compile(
        r"(?:self\.(\w+)\s*[:=]|def\s+(\w+)\().*#\s*owner:\s*(\S+)")
    tagged: dict[str, set[str]] = {}
    with open(os.path.join(REPO, rel)) as fh:
        for ln in fh:
            m = owner_re.search(ln)
            if m:
                tagged.setdefault(m.group(3), set()).add(
                    m.group(1) or m.group(2))
    assert members <= tagged.get(domain, set())
    # and every tagged domain is registered (checker enforces it on
    # the tree; this keeps the registry and annotations honest even
    # if the checker is ever detuned)
    from etcd_tpu.analysis import DOMAINS

    assert set(tagged) <= set(DOMAINS)


def test_run_checkers_parallel_matches_serial(tmp_path):
    """The thread-pool fan-out must be invisible: same findings, same
    order, as a jobs=1 run over the same tree."""
    from etcd_tpu.analysis import (
        BoundedQueueChecker,
        DurabilityOrderingChecker,
        LockOrderChecker,
    )

    _fixture_root(tmp_path, "etcd_tpu/server/pair.py", _DEADLOCK)
    _fixture_root(tmp_path, "etcd_tpu/server/mailbox.py", """
        import queue

        class M:
            def __init__(self):
                self.q = queue.Queue()
    """)
    root = _fixture_root(tmp_path, "etcd_tpu/wal/wal.py", """
        class W:
            def bad(self, data):
                self.f.write(data)
                return 1
    """)
    checkers = [DurabilityOrderingChecker(), BoundedQueueChecker(),
                LockOrderChecker()]
    par = run_checkers(root, checkers)
    ser = run_checkers(root, [DurabilityOrderingChecker(),
                              BoundedQueueChecker(),
                              LockOrderChecker()], jobs=1)
    assert [(f.fingerprint, f.line) for f in par] == \
        [(f.fingerprint, f.line) for f in ser]
    assert len(par) == 3


def test_lint_per_checker_timings_on_metrics(tmp_path):
    from etcd_tpu.obs.exporter import render_prometheus

    root = _fixture_root(tmp_path, "etcd_tpu/wal/wal.py", """
        class W:
            def ok(self):
                return 1
    """)
    run_checkers(root, [DurabilityOrderingChecker()])
    text = render_prometheus().decode()
    assert ('etcd_lint_run_seconds{checker='
            '"durability-ordering"}' in text), text
    total = next(
        ln for ln in text.splitlines()
        if ln.startswith('etcd_lint_run_seconds{checker="_total"}'))
    assert float(total.split()[-1]) > 0.0


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))


# -- 18. wire-bounds fires on unchecked wire-derived counts (PR 19) -----------


def test_wirebounds_fires_on_unchecked_count_sinks(tmp_path):
    from etcd_tpu.analysis import WireBoundsChecker

    root = _fixture_root(tmp_path, "etcd_tpu/wire/peermsg.py", """
        import struct
        import numpy as np

        def unpack_table(data):
            (n,) = struct.unpack_from("<I", data, 0)
            out = bytearray(n)
            for i in range(n):
                pass
            arr = np.frombuffer(data, "<i4", count=n, offset=4)
            pad = b"\\x00" * n
            return out, arr, pad
        """)
    findings = run_checkers(root, [WireBoundsChecker()])
    assert _rules(findings) == {"unchecked-wire-count"}
    sinks = {f.detail.split(":")[0] for f in findings}
    assert sinks == {"allocation", "range", "frombuffer-count",
                     "sequence-repeat"}


def test_wirebounds_quiet_on_guarded_counts(tmp_path):
    from etcd_tpu.analysis import WireBoundsChecker

    root = _fixture_root(tmp_path, "etcd_tpu/wire/peermsg.py", """
        import struct
        from .schema import FrameError, check_bound

        def unpack_table(data):
            (n,) = struct.unpack_from("<I", data, 0)
            if 4 + 4 * n > len(data):
                raise FrameError("truncated table")
            out = bytearray(n)
            for i in range(n):
                pass
            return out

        def unpack_capped(data):
            (n,) = struct.unpack_from("<I", data, 0)
            check_bound("dgb2.groups", n)
            return bytearray(n)
        """)
    assert not run_checkers(root, [WireBoundsChecker()])


def test_wirebounds_closes_the_bound_vocabulary(tmp_path):
    from etcd_tpu.analysis import WireBoundsChecker

    root = _fixture_root(tmp_path, "etcd_tpu/wire/peermsg.py", """
        from .schema import check_bound

        def unpack_thing(data, which):
            n = len(data)
            check_bound(which, n)
            check_bound("peer.bogus_count", n)
        """)
    findings = run_checkers(root, [WireBoundsChecker()])
    assert _rules(findings) == {"dynamic-bound-name",
                                "unregistered-bound"}


def test_wirebounds_fires_on_missing_plausibility_cap(tmp_path):
    from etcd_tpu.analysis import WireBoundsChecker

    # a partial distmsg at the real relpath is held to the REAL DGB2
    # schema: dgb2.groups and dgb2.ents_per_lane must be capped in
    # parse_header
    root = _fixture_root(tmp_path, "etcd_tpu/wire/distmsg.py", """
        import struct
        from .schema import FrameError

        def parse_header(data):
            if len(data) < 24:
                raise FrameError("short frame")
            g, e = struct.unpack_from("<II", data, 8)
            return g, e
        """)
    findings = run_checkers(root, [WireBoundsChecker()])
    assert _rules(findings) == {"missing-plausibility-cap"}
    assert {f.detail for f in findings} == {"dgb2.groups",
                                            "dgb2.ents_per_lane"}


def test_wirebounds_quiet_when_caps_enforced(tmp_path):
    from etcd_tpu.analysis import WireBoundsChecker

    root = _fixture_root(tmp_path, "etcd_tpu/wire/distmsg.py", """
        import struct
        from .schema import FrameError, check_bound

        def parse_header(data):
            if len(data) < 24:
                raise FrameError("short frame")
            g, e = struct.unpack_from("<II", data, 8)
            check_bound("dgb2.groups", g)
            check_bound("dgb2.ents_per_lane", e)
            return g, e
        """)
    assert not run_checkers(root, [WireBoundsChecker()])


# -- 19. frame-totality fires on untyped parse escapes (PR 19) ----------------


def test_frametotality_fires_on_untyped_decode_and_unpack(tmp_path):
    from etcd_tpu.analysis import FrameTotalityChecker

    root = _fixture_root(tmp_path, "etcd_tpu/wire/peermsg.py", """
        import json
        import struct

        def parse_head(data):
            (n,) = struct.unpack_from("<I", data, 0)
            return n

        def unpack_name(data):
            return data[4:].decode()

        def unpack_meta(data):
            return json.loads(data)
        """)
    findings = run_checkers(root, [FrameTotalityChecker()])
    assert _rules(findings) == {"unguarded-unpack", "untyped-decode"}
    assert {f.detail for f in findings} == {"struct.unpack_from",
                                            "decode", "json.loads"}


def test_frametotality_quiet_on_typed_parse(tmp_path):
    from etcd_tpu.analysis import FrameTotalityChecker

    root = _fixture_root(tmp_path, "etcd_tpu/wire/peermsg.py", """
        import json
        import struct
        from .schema import FrameError

        def parse_head(data):
            if len(data) < 4:
                raise FrameError("short frame")
            (n,) = struct.unpack_from("<I", data, 0)
            return n

        def unpack_name(data):
            try:
                return data[4:].decode()
            except UnicodeDecodeError:
                raise FrameError("name not utf-8") from None

        def unpack_meta(data):
            try:
                return json.loads(data)
            except (ValueError, KeyError, TypeError):
                raise FrameError("bad meta json") from None
        """)
    assert not run_checkers(root, [FrameTotalityChecker()])


def test_frametotality_fires_on_dropped_kind_checks(tmp_path):
    from etcd_tpu.analysis import FrameTotalityChecker

    # a partial clientmsg at the real relpath is held to the REAL
    # DCB1 schema: the unmarshal scope exists but never rejects its
    # kind, and nothing rejects an unknown kind typed
    root = _fixture_root(tmp_path, "etcd_tpu/wire/clientmsg.py", """
        import struct
        from .schema import FrameError

        KIND_GET_REQ = 0

        def _parse_header(data):
            if len(data) < 12:
                raise FrameError("short client frame")
            hdr = struct.unpack_from("<4sBBHI", data)
            return hdr[1], hdr[4]

        def unpack_get_request(data):
            kind, count = _parse_header(data)
            return count
        """)
    findings = run_checkers(root, [FrameTotalityChecker()])
    assert _rules(findings) == {"unhandled-kind",
                                "missing-unknown-kind-rejection"}


def test_frametotality_fires_on_unhandled_flag(tmp_path):
    from etcd_tpu.analysis import FrameTotalityChecker

    # DGB2 declares FLAG_TRACE and FLAG_PACKED with parse scope
    # AppendBatch.unmarshal; testing only one of them is a finding
    # for the other (its trailing section would be misparsed)
    root = _fixture_root(tmp_path, "etcd_tpu/wire/distmsg.py", """
        from .schema import FrameError

        KIND_APPEND = 0
        FLAG_TRACE = 0x0001
        FLAG_PACKED = 0x0002

        class AppendBatch:
            @classmethod
            def unmarshal(cls, data):
                kind = data[4]
                if kind != KIND_APPEND:
                    raise FrameError("kind")
                flags = data[6]
                trace = None
                if flags & FLAG_TRACE:
                    trace = []
                return cls()
        """)
    findings = run_checkers(root, [FrameTotalityChecker()])
    assert _rules(findings) == {"unhandled-flag"}
    assert {f.detail for f in findings} == {"FLAG_PACKED"}


# -- 20. schema-drift fires on layout divergence (PR 19) ----------------------


def test_schemadrift_fires_on_local_layout_literals(tmp_path):
    from etcd_tpu.analysis import SchemaDriftChecker

    root = _fixture_root(tmp_path, "etcd_tpu/wire/peermsg.py", """
        import struct

        _HDR = struct.Struct("<4sBBHIIII")
        _MAGIC = b"DGB2"
        """)
    findings = run_checkers(root, [SchemaDriftChecker()])
    assert _rules(findings) == {"local-struct-literal",
                                "local-magic-literal"}


def test_schemadrift_quiet_on_schema_imports(tmp_path):
    from etcd_tpu.analysis import SchemaDriftChecker

    root = _fixture_root(tmp_path, "etcd_tpu/wire/peermsg.py", """
        from .schema import DGB2

        _MAGIC = DGB2.magic
        _HDR = DGB2.header_struct()
        """)
    assert not run_checkers(root, [SchemaDriftChecker()])


def test_schemadrift_fires_on_reordered_sections(tmp_path):
    from etcd_tpu.analysis import SchemaDriftChecker

    # the REAL DGB2 schema declares AppendResp sections as
    # term/acked/hint/ok/active — a marshal writing acked first is
    # the silent-corruption drift this rule exists for
    root = _fixture_root(tmp_path, "etcd_tpu/wire/distmsg.py", """
        class AppendResp:
            def marshal(self):
                out = bytearray(64)
                pos = 0
                pos = _w_i32(out, pos, self.acked)
                pos = _w_i32(out, pos, self.term)
                pos = _w_i32(out, pos, self.hint)
                pos = _w_u8(out, pos, self.ok)
                pos = _w_u8(out, pos, self.active)
                return out
        """)
    findings = run_checkers(root, [SchemaDriftChecker()])
    assert _rules(findings) == {"section-drift"}
    assert {f.detail for f in findings} == {"KIND_APPEND_RESP:marshal"}


def test_schemadrift_quiet_on_declared_section_order(tmp_path):
    from etcd_tpu.analysis import SchemaDriftChecker

    root = _fixture_root(tmp_path, "etcd_tpu/wire/distmsg.py", """
        class AppendResp:
            def marshal(self):
                out = bytearray(64)
                pos = 0
                pos = _w_i32(out, pos, self.term)
                pos = _w_i32(out, pos, self.acked)
                pos = _w_i32(out, pos, self.hint)
                pos = _w_u8(out, pos, self.ok)
                pos = _w_u8(out, pos, self.active)
                return out
        """)
    assert not run_checkers(root, [SchemaDriftChecker()])


def test_schemadrift_fires_on_proto_field_divergence(tmp_path):
    from etcd_tpu.analysis import SchemaDriftChecker

    # GPB1 declares HardState field 3 (commit) as wire type 0; tag
    # 0x19 = (3 << 3) | 1 writes it as fixed64 — field-drift
    root = _fixture_root(tmp_path, "etcd_tpu/wire/proto.py", """
        class HardState:
            def marshal(self):
                buf = bytearray()
                _tagged_varint(buf, 0x08, self.term)
                _tagged_varint(buf, 0x10, self.vote)
                _tagged_varint(buf, 0x19, self.commit)
                return bytes(buf)
        """)
    findings = run_checkers(root, [SchemaDriftChecker()])
    assert _rules(findings) == {"field-drift"}
    assert {f.detail for f in findings} == {"HardState.f3:marshal"}


# -- 21. the schemas pin the real modules (PR 19) -----------------------------


@pytest.mark.parametrize("name", ["DGB2", "DCB1", "GPB1"])
def test_wire_schema_matches_real_modules(name):
    """Drift guard in the OTHER direction: the declarative schemas
    (wire/schema.py) must describe the code that actually ships —
    struct formats, magics, kind values, flag bits, and
    section/field names that exist on the real dataclasses."""
    import dataclasses
    import struct as pystruct

    from etcd_tpu.wire import clientmsg, distmsg, proto, schema

    (sch,) = [f for f in schema.FORMATS if f.name == name]
    mod = {"DGB2": distmsg, "DCB1": clientmsg, "GPB1": proto}[name]
    assert sch.module == "etcd_tpu/wire/%s.py" \
        % mod.__name__.rpartition(".")[2]

    if sch.header:
        # header format and magic are what the module actually uses
        assert mod._HDR.format == sch.header
        assert mod._MAGIC == sch.magic
        # header_offsets() tiles the whole packed header exactly
        offs = sch.header_offsets()
        assert set(offs) == set(sch.header_fields)
        assert sum(w for _o, w, _s in offs.values()) \
            == pystruct.calcsize(sch.header)
        for cf in sch.count_fields:
            assert cf in offs, cf
    # kind values and flag bits equal the module constants
    for kind in sch.kinds:
        assert getattr(mod, kind.name) == kind.value, kind.name
    for flag in sch.flags:
        assert getattr(mod, flag.name) == flag.bit, flag.name
    # the struct catalog round-trips through the module
    for const, fmt in sch.structs.items():
        assert getattr(mod, const).format == fmt, const

    # section names name real dataclass fields ("lens" is the
    # derived payload length table, the one non-attribute section)
    for kind in sch.kinds:
        if not kind.cls:
            continue
        fields = {f.name for f in dataclasses.fields(
            getattr(mod, kind.cls))}
        for s in kind.sections:
            assert s.name in fields | {"lens"}, \
                f"{kind.cls}.{s.name}"

    # message field names are real attributes of the real messages
    for msg in sch.messages:
        cls = getattr(mod, msg.cls)
        names = {f.name for f in dataclasses.fields(cls)} \
            if dataclasses.is_dataclass(cls) else set(cls.__slots__)
        for f in msg.fields:
            assert f.name in names, f"{msg.cls}.{f.name}"

    # every declared bound cap is positive and every flag scope /
    # bound scope appears in parse_scopes
    assert sch.bounds
    for b in sch.bounds:
        assert b.cap > 0
        assert b.scope in sch.parse_scopes, b.name
    for fl in sch.flags:
        assert fl.scope in sch.parse_scopes, fl.name
