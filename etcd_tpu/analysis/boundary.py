"""device-boundary: per-round host materialization of jitted results.

The 24x TPU restart-replay regression (round-5 VERDICT) was a
transfer-per-round tax: a host fetch (``np.asarray``) of a value a
jitted call had just produced, sitting inside a per-round Python loop
— every iteration pays a full dispatch + D2H round trip that batching
(or keeping the value device-resident across rounds) would amortize.
``obs/devledger.py`` makes the tax *readable* at runtime on the
instrumented seams; this checker catches the pattern statically on
the un-instrumented ones (the ROADMAP open idea).

Flagged (rule ``per-round-fetch``): inside any ``for``/``while``
body, ``np.asarray(...)`` / ``np.array(...)`` whose argument is a
call to a jit-rooted function — or a name assigned from one inside
the same loop.  Jit roots are resolved in the module itself
(``@jax.jit`` / ``functools.partial(jax.jit, ...)`` decorators,
``f = jax.jit(g)`` bindings) and across ``from X import y`` edges
when X lives in this repo, so the common split (kernels in ``ops/``,
loops in ``server/``/``scripts/``) is covered.  Method calls on
engine objects (``mr.propose(...)``) are NOT resolved — that tier is
instrumented by the devledger at runtime instead.

Fix patterns: hoist the fetch out of the loop, fuse the rounds into
one dispatch (``propose_rounds``-style trains), or — when the
per-round fetch is genuinely required — route it through
``obs.devledger.ledger.fetch`` so the tax is at least accounted, and
baseline the finding with that justification.
"""

from __future__ import annotations

import ast
import os

from .engine import Checker, Finding, dotted_name, iter_functions

_NP_FETCH = {"asarray", "array"}
_NP_NAMES = {"np", "numpy"}


def _is_jit_expr(node: ast.AST) -> bool:
    """True for ``jax.jit``, ``jax.jit(...)``, or
    ``functools.partial(jax.jit, ...)`` expressions."""
    if isinstance(node, ast.Call):
        leaf = dotted_name(node.func).split(".")[-1]
        if leaf == "jit":
            return True
        if leaf == "partial":
            return any(
                dotted_name(a).split(".")[-1] == "jit"
                for a in node.args)
        return False
    return dotted_name(node).split(".")[-1] == "jit"


def _jit_roots_of(tree: ast.AST) -> set[str]:
    """Names bound to jitted callables in one module."""
    roots: set[str] = set()
    for _scope, fn in iter_functions(tree):
        if any(_is_jit_expr(dec) for dec in fn.decorator_list):
            roots.add(fn.name)
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) \
                and isinstance(node.value, ast.Call) \
                and _is_jit_expr(node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    roots.add(t.id)
    return roots


class DeviceBoundaryChecker(Checker):
    name = "device-boundary"
    targets = ("etcd_tpu/", "scripts/")

    def __init__(self):
        self._module_roots: dict[str, set[str]] = {}

    # -- cross-module jit-root resolution ---------------------------------

    def _roots_of_path(self, path: str) -> set[str]:
        cached = self._module_roots.get(path)
        if cached is not None:
            return cached
        try:
            with open(path) as fh:
                tree = ast.parse(fh.read(), filename=path)
            roots = _jit_roots_of(tree)
        except (OSError, SyntaxError):
            roots = set()
        self._module_roots[path] = roots
        return roots

    def _imported_jit_roots(self, tree: ast.AST, relpath: str,
                            root: str | None) -> set[str]:
        if root is None:
            return set()
        pkg = relpath.split("/")[:-1]  # package dirs of this module
        out: set[str] = set()
        for node in ast.walk(tree):
            if not isinstance(node, ast.ImportFrom):
                continue
            if node.level:
                base = pkg[:len(pkg) - (node.level - 1)]
                if node.level - 1 > len(pkg):
                    continue
            else:
                base = []
            parts = base + (node.module.split(".")
                            if node.module else [])
            for cand in (os.path.join(root, *parts) + ".py",
                         os.path.join(root, *parts, "__init__.py")):
                if os.path.exists(cand):
                    mod_roots = self._roots_of_path(cand)
                    for alias in node.names:
                        if alias.name in mod_roots:
                            out.add(alias.asname or alias.name)
                    break
        return out

    # -- the check --------------------------------------------------------

    def check(self, relpath, tree, source, root=None, ctx=None):
        jit_roots = _jit_roots_of(tree) \
            | self._imported_jit_roots(tree, relpath, root)
        if not jit_roots:
            return []
        findings: list[Finding] = []
        seen: set[int] = set()
        for scope, fn in iter_functions(tree):
            for loop in ast.walk(fn):
                if not isinstance(loop, (ast.For, ast.While)):
                    continue
                self._check_loop(relpath, scope, loop, jit_roots,
                                 findings, seen)
        return findings

    def _check_loop(self, relpath, scope, loop, jit_roots,
                    findings, seen) -> None:
        def is_root_call(node) -> bool:
            return (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in jit_roots)

        assigned: set[str] = set()
        for node in ast.walk(loop):
            if isinstance(node, ast.Assign) \
                    and is_root_call(node.value):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        assigned.add(t.id)
        for node in ast.walk(loop):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _NP_FETCH
                    and dotted_name(node.func.value) in _NP_NAMES
                    and node.args):
                continue
            arg = node.args[0]
            detail = None
            if is_root_call(arg):
                detail = arg.func.id
            elif isinstance(arg, ast.Name) and arg.id in assigned:
                detail = arg.id
            if detail is None or id(node) in seen:
                continue
            seen.add(id(node))
            findings.append(Finding(
                checker=self.name, path=relpath, line=node.lineno,
                rule="per-round-fetch", scope=scope,
                message=f"np.{node.func.attr}({detail}...) inside a "
                        f"per-round loop materializes a jitted "
                        f"result every iteration — batch the rounds "
                        f"or hoist the fetch (devledger.fetch if the "
                        f"per-round fetch is load-bearing)",
                detail=detail))
