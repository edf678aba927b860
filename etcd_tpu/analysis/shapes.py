"""static-shapes: shape-dependent branching under divergent callers.

``@jax.jit`` caches one executable per *static signature*: argument
shapes are baked into the trace.  A Python branch on ``x.shape`` (or
``x.ndim``/``x.size``/``len(x)``) inside a jit root is therefore
legal — the purity checker de-taints those reads — but it turns every
NEW caller shape into a full re-trace + re-compile.  With tens of
thousands of co-hosted groups batched through a handful of kernels,
one shape-churning call site is a compile storm (the re-jit-churn
class).

This checker joins both halves statically, which needs the
whole-program call graph:

- **roots**: functions under a jit decoration (``@jax.jit``,
  ``functools.partial(jax.jit, ...)``) containing a Python
  ``if``/``while`` whose test reads the shape of a *non-static*
  parameter;
- **call sites**: every project call expression resolving to that
  root (``callgraph.call_sites_of`` — same module, ``from X import
  y`` edges, re-exports).  The argument feeding the shape-branched
  parameter is reduced to a static **shape token** when the call
  passes a literal-shaped constructor (``jnp.zeros((4, 8))``,
  ``np.ones(n_CONST)``, ``jnp.arange(16)``, ``jnp.array([...])``).

Rule ``shape-branch`` fires when two call sites prove **different**
tokens: the branch re-specializes per caller.  A single observed
shape, or call sites whose shapes the checker cannot prove, stay
quiet — runtime-shaped args are the norm and flagging them would be
noise.  Fix patterns: pad to one shape at the boundary, split the
root per shape family, or hoist the varying dimension into
``static_argnames`` so the specialization is at least declared.
"""

from __future__ import annotations

import ast

from .engine import Checker, Finding, dotted_name, iter_functions
from .purity import _decorator_root

#: shape reads that are static at trace time but specialize the jit
#: cache per caller shape
_SHAPE_ATTRS = {"shape", "ndim", "size"}

#: array constructors whose first argument IS the shape
_SHAPE_CTORS = {"zeros", "ones", "empty", "full"}


def _const_tuple(node: ast.AST) -> tuple | None:
    """Constant int / tuple-of-constant-ints -> shape tuple."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if not (isinstance(el, ast.Constant)
                    and isinstance(el.value, int)):
                return None
            out.append(el.value)
        return tuple(out)
    return None


def shape_token(node: ast.AST) -> str | None:
    """A stable token for the static shape of an argument
    expression, or None when it cannot be proven."""
    if not isinstance(node, ast.Call):
        return None
    leaf = dotted_name(node.func).split(".")[-1]
    if leaf in _SHAPE_CTORS:
        shp = None
        if node.args:
            shp = _const_tuple(node.args[0])
        for kw in node.keywords:
            if kw.arg == "shape":
                shp = _const_tuple(kw.value)
        return str(shp) if shp is not None else None
    if leaf == "arange":
        if len(node.args) == 1:
            shp = _const_tuple(node.args[0])
            return str(shp) if shp is not None else None
        return None
    if leaf in ("array", "asarray") and node.args:
        arg = node.args[0]
        if isinstance(arg, (ast.List, ast.Tuple)):
            shp = _const_tuple(arg)
            if shp is not None:  # flat literal vector
                return str((len(arg.elts),))
        return None
    return None


def _param_names(fn) -> list[str]:
    args = fn.args
    return [a.arg for a in (args.posonlyargs + args.args)]


def _shape_branch_params(fn, statics) -> list[tuple[str, ast.AST]]:
    """(param, test-node) for every if/while test reading the shape
    of a non-static parameter of ``fn``."""
    params = {p for p in _param_names(fn)
              if p not in statics and p not in ("self", "cls")}
    out = []
    for node in ast.walk(fn):
        if not isinstance(node, (ast.If, ast.While)):
            continue
        hit = None
        for sub in ast.walk(node.test):
            if isinstance(sub, ast.Attribute) \
                    and sub.attr in _SHAPE_ATTRS \
                    and isinstance(sub.value, ast.Name) \
                    and sub.value.id in params:
                hit = sub.value.id
            elif isinstance(sub, ast.Call) \
                    and dotted_name(sub.func) == "len" \
                    and sub.args \
                    and isinstance(sub.args[0], ast.Name) \
                    and sub.args[0].id in params:
                hit = sub.args[0].id
        if hit is not None:
            out.append((hit, node))
    return out


class StaticShapeChecker(Checker):
    name = "static-shapes"
    targets = ("etcd_tpu/",)

    def check(self, relpath, tree, source, root=None, ctx=None):
        if ctx is None:
            return []
        findings: list[Finding] = []
        for scope, fn in iter_functions(tree):
            statics: tuple[str, ...] | None = None
            for dec in fn.decorator_list:
                is_root, st = _decorator_root(dec)
                if is_root:
                    statics = st
                    break
            if statics is None:
                continue
            branches = _shape_branch_params(fn, statics)
            if not branches:
                continue
            sites = ctx.callgraph.call_sites_of(relpath, scope)
            tokens = self._site_tokens(fn, sites)
            for param, test in branches:
                toks = tokens.get(param, set())
                if len(toks) >= 2:
                    findings.append(Finding(
                        checker=self.name, path=relpath,
                        line=test.lineno, rule="shape-branch",
                        scope=scope,
                        message=(
                            f"Python branch on `{param}.shape` "
                            f"inside jit root `{fn.name}` whose "
                            f"call sites pass differently-shaped "
                            f"arrays ({', '.join(sorted(toks))}) — "
                            f"every new shape re-traces and "
                            f"re-compiles; pad to one shape or "
                            f"declare the split via "
                            f"static_argnames"),
                        detail=f"{fn.name}.{param}"))
        return findings

    @staticmethod
    def _site_tokens(fn, sites) -> dict[str, set[str]]:
        """param -> set of proven shape tokens across call sites."""
        params = _param_names(fn)
        out: dict[str, set[str]] = {}
        for _rel, _scope, call in sites:
            for i, arg in enumerate(call.args):
                if i >= len(params):
                    break
                tok = shape_token(arg)
                if tok is not None:
                    out.setdefault(params[i], set()).add(tok)
            for kw in call.keywords:
                if kw.arg in params:
                    tok = shape_token(kw.value)
                    if tok is not None:
                        out.setdefault(kw.arg, set()).add(tok)
        return out
