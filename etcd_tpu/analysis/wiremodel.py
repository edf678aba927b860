"""Shared plumbing for the wire checkers (PR 19): which files are
wire targets, which functions are parse scopes, and the per-module
schema/typed-error lookup.

A *parse scope* is a function that turns attacker-controllable bytes
into values: the schema (wire/schema.py) pins the real modules' entry
points exactly via ``parse_scopes``, the ``PARSE_NAME_RE`` name
convention covers fixture trees and newly added helpers, and the call
graph (PR 4) closes over helpers a declared entry calls inside the
wire targets — so a parse path can't dodge the checkers by moving its
body into an oddly named local function.
"""

from __future__ import annotations

import ast

from .engine import iter_functions
from ..wire import schema

#: the formats' home — every wire checker targets exactly this
WIRE_TARGETS = ("etcd_tpu/wire/",)

#: the schema module itself is the one wire file that legitimately
#: declares layout literals
SCHEMA_RELPATH = "etcd_tpu/wire/schema.py"


def module_schema(relpath: str) -> schema.FrameSchema | None:
    return schema.MODULE_SCHEMAS.get(relpath)


def typed_error(relpath: str) -> str:
    sch = module_schema(relpath)
    return sch.error if sch else "FrameError"


def parse_scopes(relpath: str, tree: ast.AST,
                 ctx=None) -> dict[str, ast.AST]:
    """{scope: function node} for every parse scope in the file."""
    sch = module_schema(relpath)
    declared = set(sch.parse_scopes) if sch else set()
    funcs = dict(iter_functions(tree))
    out: dict[str, ast.AST] = {}
    for scope, fn in funcs.items():
        base = scope.rsplit(".", 1)[-1]
        if scope in declared or schema.PARSE_NAME_RE.match(base):
            out[scope] = fn
    if ctx is None or not declared:
        return out
    # call-graph closure: helpers a declared entry scope calls, when
    # they live in a wire target file (same-file helpers surface as
    # scopes here; cross-file ones are checked in their own file's
    # pass since the lint run visits every wire target)
    frontier = list(out.items())
    while frontier:
        _scope, fn = frontier.pop()
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call) \
                    or not isinstance(node.func, ast.Name):
                continue
            try:
                defs = ctx.callgraph.resolve_call(relpath,
                                                  node.func.id)
            except Exception:  # pragma: no cover - defensive
                continue
            for dpath, dscope, _dnode in defs:
                if dpath == relpath and dscope in funcs \
                        and dscope not in out:
                    out[dscope] = funcs[dscope]
                    frontier.append((dscope, funcs[dscope]))
    return out
