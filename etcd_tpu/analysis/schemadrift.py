"""schema-drift: the declared layout, the marshaler, and the
unmarshaler must agree — and the schema is the only place layout
literals live.

wire/schema.py is the single source of truth for every frame layout
(PR 19).  Drift between it and the parser modules is the silent-
corruption failure class: a field written at one offset and read at
another, a section reordered on one side only, a struct format
re-declared locally and edited out of sync.  Three rules:

  * ``local-struct-literal`` / ``local-magic-literal`` — a wire
    module other than the schema declares a ``struct.Struct("...")``
    format string or a frame magic literal.  Layout constants must be
    imported from the schema so there is exactly one copy to edit.
  * ``section-drift`` — for every DGB2-style frame kind, the ordered
    ``_w_i32``/``_w_u8`` writes in ``marshal`` and the ordered
    ``_view_i32``/``_view_u8`` reads in ``unmarshal`` are extracted
    and compared against the schema's declared sections.  A section
    written but not read, read at a different position, or read with
    a different element width fails lint.
  * ``field-drift`` — for every gogoproto message, the tag bytes
    emitted by ``marshal`` and the ``fnum ==``/``_expect_wt`` dispatch
    arms in ``unmarshal`` are compared against the schema's declared
    (field number, wire type) pairs, both directions.
"""

from __future__ import annotations

import ast

from .engine import Checker, Finding, dotted_name, iter_functions
from .wiremodel import SCHEMA_RELPATH, WIRE_TARGETS, module_schema
from ..wire import schema as _schema

#: section element -> (writer helper, reader helper)
_ELEM_CALLS = {"i32": ("_w_i32", "_view_i32"),
               "u8": ("_w_u8", "_view_u8")}
_WRITERS = {w: e for e, (w, _r) in _ELEM_CALLS.items()}
_READERS = {r: e for e, (_w, r) in _ELEM_CALLS.items()}


def _arg_name(node: ast.AST) -> str:
    """Best-effort payload name of a section write argument:
    ``self.term`` -> term, ``n_ents`` -> n_ents,
    ``np.asarray(lens, ...)`` -> lens."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Call):
        for a in node.args:
            got = _arg_name(a)
            if got:
                return got
    return ""


def _top_level_calls(fn: ast.AST):
    """(statement, call) for each unconditional top-level statement
    of ``fn`` whose value is a helper call, in source order.  Only
    top-level statements count: the schema's ordered sections are
    mandatory, while flag-gated trailing sections (FLAG_PACKED's
    table) legitimately marshal under an ``if``."""
    for s in fn.body:
        value = getattr(s, "value", None)
        if isinstance(s, (ast.Assign, ast.AnnAssign, ast.Expr)) \
                and isinstance(value, ast.Call):
            yield s, value


def _ordered_calls(fn: ast.AST,
                   table: dict[str, str]) -> list[tuple[str, str]]:
    """[(elem, payload name)] for every unconditional helper call
    from ``table`` in ``fn``, in source order."""
    out = []
    for _s, n in _top_level_calls(fn):
        f = n.func
        last = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else "")
        if last in table and len(n.args) >= 3:
            out.append((table[last], _arg_name(n.args[2])))
    return out


def _ordered_reads(fn: ast.AST) -> list[tuple[str, str]]:
    """[(elem, bound local name)] for every unconditional
    ``name, pos = _view_*(...)`` in ``fn``, in source order."""
    out = []
    for s, n in _top_level_calls(fn):
        if not isinstance(s, ast.Assign):
            continue
        f = n.func
        last = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else "")
        if last not in _READERS:
            continue
        tgt = s.targets[0]
        if isinstance(tgt, ast.Tuple) and tgt.elts \
                and isinstance(tgt.elts[0], ast.Name):
            out.append((_READERS[last], tgt.elts[0].id))
    return out


class SchemaDriftChecker(Checker):
    name = "schema-drift"
    targets = WIRE_TARGETS

    def check(self, relpath: str, tree: ast.AST, source: str,
              root: str | None = None, ctx=None) -> list[Finding]:
        if relpath == SCHEMA_RELPATH:
            return []
        out: list[Finding] = []
        self._check_literals(relpath, tree, out)
        sch = module_schema(relpath)
        if sch is None:
            return out
        funcs = dict(iter_functions(tree))
        for kind in sch.kinds:
            self._check_sections(relpath, kind, funcs, out)
        for msg in sch.messages:
            self._check_fields(relpath, msg, funcs, out)
        return out

    # -- layout literals belong in the schema ---------------------------

    def _check_literals(self, relpath: str, tree: ast.AST,
                        out: list[Finding]) -> None:
        magics = {f.magic for f in _schema.FORMATS if f.magic}
        for n in ast.walk(tree):
            if isinstance(n, ast.Call) \
                    and dotted_name(n.func).rsplit(".", 1)[-1] \
                    == "Struct" \
                    and n.args \
                    and isinstance(n.args[0], ast.Constant) \
                    and isinstance(n.args[0].value, str):
                out.append(Finding(
                    checker=self.name, path=relpath,
                    line=n.lineno, rule="local-struct-literal",
                    scope="",
                    message=f"struct format "
                            f"{n.args[0].value!r} declared locally "
                            f"— import it from wire/schema.py "
                            f"(structs / header_struct) so there "
                            f"is one copy to edit",
                    detail=n.args[0].value))
            elif isinstance(n, ast.Constant) \
                    and isinstance(n.value, bytes) \
                    and n.value in magics:
                out.append(Finding(
                    checker=self.name, path=relpath,
                    line=n.lineno, rule="local-magic-literal",
                    scope="",
                    message=f"frame magic {n.value!r} declared "
                            f"locally — import it from "
                            f"wire/schema.py",
                    detail=repr(n.value)))

    # -- DGB2-style ordered sections ------------------------------------

    def _check_sections(self, relpath: str, kind, funcs,
                        out: list[Finding]) -> None:
        expected = [(s.elem, s.name) for s in kind.sections
                    if s.elem in _ELEM_CALLS]
        if not expected:
            return
        wfn = funcs.get(kind.marshal) if kind.marshal else None
        rfn = funcs.get(kind.unmarshal) if kind.unmarshal else None
        if wfn is not None:
            writes = _ordered_calls(wfn, _WRITERS)
            if writes and writes != expected:
                out.append(Finding(
                    checker=self.name, path=relpath,
                    line=wfn.lineno, rule="section-drift",
                    scope=kind.marshal,
                    message=f"{kind.marshal} writes {writes} but "
                            f"the schema declares {expected} for "
                            f"{kind.name} — reorder/fix one side "
                            f"or update the schema",
                    detail=f"{kind.name}:marshal"))
        if rfn is not None:
            exp_r = [(s.elem, s.read_name) for s in kind.sections
                     if s.elem in _ELEM_CALLS]
            reads = _ordered_reads(rfn)
            if reads and reads != exp_r:
                out.append(Finding(
                    checker=self.name, path=relpath,
                    line=rfn.lineno, rule="section-drift",
                    scope=kind.unmarshal,
                    message=f"{kind.unmarshal} reads {reads} but "
                            f"the schema declares {exp_r} for "
                            f"{kind.name} — a reordered read "
                            f"silently swaps sections",
                    detail=f"{kind.name}:unmarshal"))

    # -- gogoproto field tags -------------------------------------------

    def _check_fields(self, relpath: str, msg, funcs,
                      out: list[Finding]) -> None:
        declared = {f.fnum: f.wt for f in msg.fields}
        wfn = funcs.get(f"{msg.cls}.marshal")
        rfn = funcs.get(f"{msg.cls}.unmarshal")
        if wfn is not None:
            written: dict[int, int] = {}
            for n in ast.walk(wfn):
                if isinstance(n, ast.Call) \
                        and isinstance(n.func, ast.Name) \
                        and n.func.id in ("_tagged_varint",
                                          "_tagged_bytes") \
                        and len(n.args) >= 2 \
                        and isinstance(n.args[1], ast.Constant) \
                        and isinstance(n.args[1].value, int):
                    tag = n.args[1].value
                    written[tag >> 3] = tag & 7
            if written:
                self._diff(relpath, msg, wfn, "marshal", written,
                           declared, out)
        if rfn is not None:
            read: dict[int, int] = {}
            for n in ast.walk(rfn):
                if not isinstance(n, ast.If):
                    continue
                t = n.test
                if not (isinstance(t, ast.Compare)
                        and len(t.ops) == 1
                        and isinstance(t.ops[0], ast.Eq)):
                    continue
                sides = [t.left, t.comparators[0]]
                fnum = next((s.value for s in sides
                             if isinstance(s, ast.Constant)
                             and isinstance(s.value, int)), None)
                if fnum is None or not any(
                        isinstance(s, ast.Name)
                        and "num" in s.id for s in sides):
                    continue
                wt = next(
                    (c.args[2].value for s in n.body
                     for c in ast.walk(s)
                     if isinstance(c, ast.Call)
                     and isinstance(c.func, ast.Name)
                     and c.func.id == "_expect_wt"
                     and len(c.args) >= 3
                     and isinstance(c.args[2], ast.Constant)),
                    -1)
                read[fnum] = wt
            if read:
                self._diff(relpath, msg, rfn, "unmarshal", read,
                           declared, out)

    def _diff(self, relpath: str, msg, fn, side: str,
              actual: dict[int, int], declared: dict[int, int],
              out: list[Finding]) -> None:
        verb = "writes" if side == "marshal" else "reads"
        for fnum in sorted(actual.keys() | declared.keys()):
            if fnum not in declared:
                why = (f"{msg.cls}.{side} {verb} field {fnum} "
                       f"(wt {actual[fnum]}) not declared in the "
                       f"schema")
            elif fnum not in actual:
                why = (f"{msg.cls}.{side} never {verb} declared "
                       f"field {fnum} — "
                       f"{'silent data loss' if side == 'marshal' else 'the field is written but never read'}")
            elif actual[fnum] != declared[fnum]:
                why = (f"{msg.cls}.{side} {verb} field {fnum} as "
                       f"wire type {actual[fnum]}, schema declares "
                       f"{declared[fnum]}")
            else:
                continue
            out.append(Finding(
                checker=self.name, path=relpath, line=fn.lineno,
                rule="field-drift", scope=f"{msg.cls}.{side}",
                message=why, detail=f"{msg.cls}.f{fnum}:{side}"))
