"""frame-totality: parse paths fail typed, and the schema's frame
vocabulary is handled totally.

The wire contract (every parser module's docstring, fuzz-enforced by
scripts/wire_fuzz.py) is that a malformed frame surfaces as the
format's ONE typed error — ``FrameError`` for the frame formats,
``ProtoError`` for the codec — never as a raw ``struct.error``,
``IndexError``, ``UnicodeDecodeError``, or ``ValueError`` escaping
into a serving loop that only catches the typed family.  This checker
is the static half of that contract:

  * ``unguarded-unpack`` — a ``struct`` unpack in a parse scope with
    no dominating raising length check and no enclosing
    ``struct.error`` handler that re-raises typed.
  * ``untyped-decode`` — ``.decode()`` / ``str(b, "utf-8")`` /
    ``json.loads`` in a parse scope outside a try/except that
    catches the decoding failure and re-raises typed.
  * ``unhandled-kind`` — a schema frame kind whose unmarshal scope
    exists but never references its ``KIND_`` constant (the
    ``kind != KIND_X`` rejection was dropped in a refactor).
  * ``missing-unknown-kind-rejection`` — a module dispatching on
    schema kinds with no typed rejection of the unknown case.
  * ``unhandled-flag`` — a schema flag bit with a declared parse
    scope that never tests it (its gated trailing section would be
    silently misparsed as another section's bytes).
"""

from __future__ import annotations

import ast

from .engine import Checker, Finding, dotted_name, iter_functions
from .wiremodel import (SCHEMA_RELPATH, WIRE_TARGETS, module_schema,
                        parse_scopes, typed_error)

#: exception names acceptable as the typed re-raise family
_TYPED = {"FrameError", "ProtoError"}

#: what an enclosing handler must catch for each untyped decoder
_DECODE_CATCHES = {
    "decode": {"UnicodeDecodeError", "ValueError", "Exception"},
    "str": {"UnicodeDecodeError", "ValueError", "Exception"},
    "json.loads": {"ValueError", "KeyError", "TypeError",
                   "Exception"},
}
_UNPACK_CATCHES = {"error", "struct.error", "Exception"}


def _handler_names(handler: ast.ExceptHandler) -> set[str]:
    t = handler.type
    if t is None:
        return {"Exception"}
    elts = t.elts if isinstance(t, ast.Tuple) else [t]
    out = set()
    for e in elts:
        d = dotted_name(e)
        if d:
            out.add(d)
            out.add(d.rsplit(".", 1)[-1])
    return out


def _raises_typed(body: list[ast.stmt], typed: str) -> bool:
    for stmt in body:
        for n in ast.walk(stmt):
            if isinstance(n, ast.Raise) and n.exc is not None:
                exc = n.exc
                if isinstance(exc, ast.Call):
                    exc = exc.func
                name = dotted_name(exc).rsplit(".", 1)[-1]
                if name == typed or name in _TYPED:
                    return True
    return False


def _decoder_kind(node: ast.Call) -> str | None:
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr == "decode":
        return "decode"
    d = dotted_name(f)
    if d.rsplit(".", 1)[-1] == "loads":
        return "json.loads"
    if isinstance(f, ast.Name) and f.id == "str" \
            and len(node.args) >= 2:
        return "str"
    return None


def _is_unpack(node: ast.Call) -> bool:
    return (isinstance(node.func, ast.Attribute)
            and node.func.attr in ("unpack_from", "unpack"))


class FrameTotalityChecker(Checker):
    name = "frame-totality"
    targets = WIRE_TARGETS

    def check(self, relpath: str, tree: ast.AST, source: str,
              root: str | None = None, ctx=None) -> list[Finding]:
        if relpath == SCHEMA_RELPATH:
            return []
        out: list[Finding] = []
        typed = typed_error(relpath)
        scopes = parse_scopes(relpath, tree, ctx)
        for scope, fn in scopes.items():
            self._check_scope(relpath, scope, fn, typed, out)
        self._check_vocabulary(relpath, tree, scopes, typed, out)
        return out

    # -- per-scope: untyped escape routes -------------------------------

    def _check_scope(self, relpath: str, scope: str, fn: ast.AST,
                     typed: str, out: list[Finding]) -> None:
        guard_lines = [
            n.lineno for n in ast.walk(fn)
            if isinstance(n, ast.If)
            and any(isinstance(c, ast.Call)
                    and isinstance(c.func, ast.Name)
                    and c.func.id == "len"
                    for c in ast.walk(n.test))
            and any(isinstance(b, (ast.Raise, ast.Return))
                    for s in n.body for b in ast.walk(s))]

        def walk(node: ast.AST, catches: frozenset[str]) -> None:
            if isinstance(node, ast.Try):
                inner = catches
                good = frozenset(
                    name for h in node.handlers
                    if _raises_typed(h.body, typed)
                    for name in _handler_names(h))
                if good:
                    inner = catches | good
                for s in node.body:
                    walk(s, inner)
                for h in node.handlers:
                    for s in h.body:
                        walk(s, catches)
                for s in node.orelse + node.finalbody:
                    walk(s, catches)
                return
            if isinstance(node, ast.Call):
                kind = _decoder_kind(node)
                if kind is not None \
                        and not (catches & _DECODE_CATCHES[kind]):
                    out.append(Finding(
                        checker=self.name, path=relpath,
                        line=node.lineno, rule="untyped-decode",
                        scope=scope,
                        message=f"{kind} on wire bytes can escape "
                                f"untyped — wrap in try/except and "
                                f"re-raise {typed}",
                        detail=kind))
                elif _is_unpack(node) \
                        and not (catches & _UNPACK_CATCHES) \
                        and not any(ln < node.lineno
                                    for ln in guard_lines):
                    out.append(Finding(
                        checker=self.name, path=relpath,
                        line=node.lineno, rule="unguarded-unpack",
                        scope=scope,
                        message=f"struct unpack with no dominating "
                                f"raising len() check and no "
                                f"struct.error handler — truncation "
                                f"escapes as struct.error, not "
                                f"{typed}",
                        detail=dotted_name(node.func)
                        or "unpack"))
            for child in ast.iter_child_nodes(node):
                walk(child, catches)

        for stmt in fn.body:
            walk(stmt, frozenset())

    # -- whole-module: total handling of the declared vocabulary --------

    def _check_vocabulary(self, relpath: str, tree: ast.AST,
                          scopes: dict[str, ast.AST], typed: str,
                          out: list[Finding]) -> None:
        sch = module_schema(relpath)
        if sch is None:
            return
        funcs = dict(iter_functions(tree))
        refs_kind = False
        for kind in sch.kinds:
            if not kind.unmarshal:
                continue
            fn = funcs.get(kind.unmarshal)
            if fn is None:
                continue
            refs_kind = True
            if not any(isinstance(n, ast.Name) and n.id == kind.name
                       for n in ast.walk(fn)):
                out.append(Finding(
                    checker=self.name, path=relpath, line=fn.lineno,
                    rule="unhandled-kind", scope=kind.unmarshal,
                    message=f"{kind.unmarshal} never checks "
                            f"{kind.name} — a frame of another kind "
                            f"would be parsed as this one's "
                            f"sections",
                    detail=kind.name))
        if refs_kind and scopes \
                and not self._rejects_unknown_kind(tree, typed):
            out.append(Finding(
                checker=self.name, path=relpath, line=1,
                rule="missing-unknown-kind-rejection", scope="",
                message=f"module dispatches on {sch.name} frame "
                        f"kinds but never rejects an unknown kind "
                        f"with {typed}",
                detail=sch.name))
        for flag in sch.flags:
            fn = funcs.get(flag.scope)
            if fn is None:
                continue
            if not any(isinstance(n, ast.Name)
                       and n.id == flag.name
                       for n in ast.walk(fn)):
                out.append(Finding(
                    checker=self.name, path=relpath, line=fn.lineno,
                    rule="unhandled-flag", scope=flag.scope,
                    message=f"{flag.scope} never tests {flag.name} "
                            f"— its gated trailing section would be "
                            f"misparsed or silently dropped",
                    detail=flag.name))

    @staticmethod
    def _rejects_unknown_kind(tree: ast.AST, typed: str) -> bool:
        for n in ast.walk(tree):
            if isinstance(n, ast.ExceptHandler) \
                    and "KeyError" in _handler_names(n) \
                    and _raises_typed(n.body, typed):
                return True
            if isinstance(n, ast.If) \
                    and any(isinstance(t, ast.Name)
                            and "kind" in t.id.lower()
                            for t in ast.walk(n.test)) \
                    and _raises_typed(n.body, typed):
                return True
        return False
