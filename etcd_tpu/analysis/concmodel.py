"""Whole-program concurrency model shared by the lock-order,
blocking-under-lock and thread-ownership checkers (PR 16).

The class-local lock-discipline checker (locks.py) sees one class at
a time; the locking story spans modules
(store world lock <- server lock <- peerlink channel state), so the
three concurrency checkers need one *global* view:

- every lock object in the project (``self.x = threading.Lock()``
  class attributes AND module-level ``_lock = threading.Lock()``),
- per function: the lexically-held lock set at every acquisition,
  call, blocking operation and attribute write,
- a function-level call-edge map that crosses modules (resolved
  through the import/call graph), classes (typed ``self.attr`` and
  annotated parameters/locals) and closures (nested defs inherit
  their definition site as a call edge),
- thread-spawn sites (``threading.Thread(target=...)``) — spawn
  targets are roots of NEW threads, so call edges into them are
  dropped: a spawner's held locks are not held in the child.

Built once per :class:`~.engine.AnalysisContext` (cached on the
context, lock-guarded — the parallel checker fan-out in
``run_checkers`` may ask from several threads at once).
"""

from __future__ import annotations

import ast
import re
import threading

from .engine import LOCK_CTORS, dotted_name
from .purity import _decorator_root

#: blocking calls by dotted-name (module function form)
_BLOCKING_DOTTED = {
    "time.sleep": ("sleep", "time.sleep"),
    "sleep": ("sleep", "time.sleep"),
    "os.fsync": ("fsio", "os.fsync"),
    "os.fdatasync": ("fsio", "os.fdatasync"),
    "fsync": ("fsio", "os.fsync"),
    "socket.create_connection": ("socket",
                                 "socket.create_connection"),
    "create_connection": ("socket", "socket.create_connection"),
}

#: blocking calls by method name (``<recv>.sendall(...)`` form)
_BLOCKING_METHODS = {
    "sendall": "socket",
    "recv": "socket",
    "recvfrom": "socket",
    "accept": "socket",
    "connect": "socket",
    "fsync": "fsio",
}

_TYPE_TOKEN = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def _self_attr(node: ast.AST) -> str | None:
    if isinstance(node, ast.Attribute) \
            and isinstance(node.value, ast.Name) \
            and node.value.id == "self":
        return node.attr
    return None


def _annotation_class(node: ast.AST | None) -> str | None:
    """Bare class name out of a parameter/attribute annotation:
    ``Foo``, ``"Foo"``, ``mod.Foo``, ``Foo | None``,
    ``"Foo | None"``, ``Optional[Foo]``."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        m = _TYPE_TOKEN.search(node.value)
        return m.group(0) if m else None
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.BinOp):  # X | None
        return _annotation_class(node.left)
    if isinstance(node, ast.Subscript):  # Optional[X] / list[X]
        base = dotted_name(node.value).split(".")[-1]
        if base == "Optional":
            return _annotation_class(node.slice)
        return None
    return None


class ClassModel:
    """One project class: its lock/queue/typed attributes."""

    __slots__ = ("name", "relpath", "scope", "locks", "attr_types",
                 "queues", "methods", "node", "init_params",
                 "param_attrs")

    def __init__(self, name: str, relpath: str, scope: str,
                 node: ast.ClassDef):
        self.name = name
        self.relpath = relpath
        self.scope = scope          # class path inside the module
        self.node = node
        self.locks: set[str] = set()
        self.attr_types: dict[str, str] = {}
        #: queue attrs: attr -> bounded? (maxsize given and nonzero)
        self.queues: dict[str, bool] = {}
        self.methods: set[str] = set()
        #: __init__ positional parameter order (self excluded)
        self.init_params: list[str] = []
        #: __init__ param name -> the self.attr it is stored to
        #: (``self._on_resp = on_resp or default`` included) — the
        #: callback-binding half of ctor-callback edge resolution
        self.param_attrs: dict[str, str] = {}


class FuncInfo:
    """One function's concurrency-relevant events, with the
    lexically-held lock set at each."""

    __slots__ = ("relpath", "scope", "node", "class_name",
                 "acquires", "raw_calls", "edges", "blocking",
                 "writes", "spawns", "is_spawn_target", "var_types",
                 "var_elem_types", "local_queues",
                 "ctor_callbacks")

    def __init__(self, relpath: str, scope: str, node):
        self.relpath = relpath
        self.scope = scope
        self.node = node
        self.class_name = ""       # bare enclosing class name or ""
        #: [(lock_id, held_tuple, line)]
        self.acquires: list[tuple] = []
        #: [(kind, data, held_tuple, line)]  (resolved into edges)
        self.raw_calls: list[tuple] = []
        #: [((relpath, scope), held_tuple, line)]
        self.edges: list[tuple] = []
        #: [(category, op, held_tuple, line)]
        self.blocking: list[tuple] = []
        #: [(class_name, attr, held_tuple, line)]
        self.writes: list[tuple] = []
        #: [((relpath, scope), thread_name, line)]
        self.spawns: list[tuple] = []
        self.is_spawn_target = False
        self.var_types: dict[str, str] = {}
        #: list-valued locals -> their element class
        self.var_elem_types: dict[str, str] = {}
        self.local_queues: dict[str, bool] = {}
        #: ctor sites passing callables: (class_name, param_name,
        #: target_spec, line) where target_spec is ("self", m) |
        #: ("name", n)
        self.ctor_callbacks: list[tuple] = []


def _is_thread_ctor(name: str) -> bool:
    last = name.split(".")[-1]
    return last in ("Thread", "Process") and (
        "." not in name or name.split(".")[0] in
        ("threading", "multiprocessing", "mp"))


def _queue_ctor_bound(node: ast.Call) -> bool | None:
    """None if not a queue ctor; else True when bounded."""
    last = dotted_name(node.func).split(".")[-1]
    if last not in ("Queue", "LifoQueue", "PriorityQueue",
                    "SimpleQueue"):
        return None
    bounded = False
    for a in node.args[:1]:
        if not (isinstance(a, ast.Constant) and a.value in (0, None)):
            bounded = True
    for kw in node.keywords:
        if kw.arg == "maxsize" and not (
                isinstance(kw.value, ast.Constant)
                and kw.value.value in (0, None)):
            bounded = True
    return bounded


class _FuncScan(ast.NodeVisitor):
    """One pass over a single function body (nested defs excluded —
    they are scanned as their own functions, linked by a def-site
    call edge)."""

    def __init__(self, model: "ConcurrencyModel", fi: FuncInfo,
                 cls: ClassModel | None):
        self.model = model
        self.fi = fi
        self.cls = cls
        self.held: tuple[str, ...] = ()

    # -- typing helpers ---------------------------------------------------

    def _var_class(self, name: str) -> ClassModel | None:
        t = self.fi.var_types.get(name)
        return self.model.classes.get(t) if t else None

    def _lock_id_of(self, node: ast.AST) -> str | None:
        """lock id for a ``with``/``.acquire()`` receiver
        expression, or None when it isn't a known lock."""
        attr = _self_attr(node)
        if attr is not None:
            if self.cls is not None and attr in self.cls.locks:
                return f"{self.cls.name}.{attr}"
            return None
        if isinstance(node, ast.Name):
            key = (self.fi.relpath, node.id)
            if key in self.model.module_locks:
                return f"{self.fi.relpath}:{node.id}"
            c = self._var_class(node.id)
            return None if c is None else None
        if isinstance(node, ast.Attribute):
            base = node.value
            # self.a.b — typed attribute's lock
            a = _self_attr(base)
            if a is not None and self.cls is not None:
                t = self.model.classes.get(
                    self.cls.attr_types.get(a, ""))
                if t is not None and node.attr in t.locks:
                    return f"{t.name}.{node.attr}"
                return None
            if isinstance(base, ast.Name):
                c = self._var_class(base.id)
                if c is not None and node.attr in c.locks:
                    return f"{c.name}.{node.attr}"
        return None

    # -- lexical lock tracking --------------------------------------------

    def visit_With(self, node: ast.With) -> None:
        acquired = []
        for item in node.items:
            lid = self._lock_id_of(item.context_expr)
            if lid is not None:
                self.fi.acquires.append(
                    (lid, self.held, node.lineno))
                acquired.append(lid)
            else:
                self.visit(item.context_expr)
        prev = self.held
        self.held = prev + tuple(a for a in acquired
                                 if a not in prev)
        for stmt in node.body:
            self.visit(stmt)
        self.held = prev

    visit_AsyncWith = visit_With

    # -- writes ------------------------------------------------------------

    def _record_write(self, target: ast.AST, line: int) -> None:
        node = target
        while isinstance(node, (ast.Subscript, ast.Attribute)):
            attr = _self_attr(node)
            if attr is not None:
                if self.cls is not None \
                        and attr not in self.cls.locks:
                    self.fi.writes.append(
                        (self.cls.name, attr, self.held, line))
                return
            if isinstance(node, ast.Attribute) \
                    and isinstance(node.value, ast.Name):
                c = self._var_class(node.value.id)
                if c is not None and node.attr not in c.locks:
                    self.fi.writes.append(
                        (c.name, node.attr, self.held, line))
                return
            if isinstance(node, ast.Attribute) \
                    and self.cls is not None:
                # self.member.attr = ...: the write lands on the
                # MEMBER's class (when its type is known), not ours
                a2 = _self_attr(node.value)
                if a2 is not None:
                    c = self.model.classes.get(
                        self.cls.attr_types.get(a2, ""))
                    if c is not None:
                        if node.attr not in c.locks:
                            self.fi.writes.append(
                                (c.name, node.attr, self.held,
                                 line))
                        return
            node = node.value

    def _call_result_class(self, call: ast.Call) -> str | None:
        """Class name a call expression produces: direct ctor,
        classmethod ctor, or an annotated project return type."""
        parts = dotted_name(call.func).split(".")
        if parts[-1] in self.model.classes:
            return parts[-1]
        if parts[0] in self.model.classes:  # WAL.create(...)
            return parts[0]
        for _r, _s, d in self.model.resolve_name(
                self.fi.relpath, dotted_name(call.func)):
            t = _annotation_class(getattr(d, "returns", None))
            if t in self.model.classes:
                return t
        return None

    def _infer_local(self, name: str, value: ast.AST) -> None:
        if isinstance(value, ast.Call):
            qb = _queue_ctor_bound(value)
            if qb is not None:
                self.fi.local_queues[name] = qb
                return
            t = self._call_result_class(value)
            if t is not None:
                self.fi.var_types[name] = t
                return
        if isinstance(value, (ast.ListComp, ast.List)):
            elts = ([value.elt] if isinstance(value, ast.ListComp)
                    else value.elts[:1])
            for el in elts:
                if isinstance(el, ast.Call):
                    t = self._call_result_class(el)
                    if t is not None:
                        self.fi.var_elem_types[name] = t
            return
        attr = _self_attr(value)
        if attr is not None and self.cls is not None:
            t = self.cls.attr_types.get(attr)
            if t in self.model.classes:
                self.fi.var_types[name] = t
            if attr in self.cls.queues:
                self.fi.local_queues[name] = self.cls.queues[attr]

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            if isinstance(t, (ast.Tuple, ast.List)):
                for el in t.elts:
                    self._record_write(el, node.lineno)
            else:
                self._record_write(t, node.lineno)
        if len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            self._infer_local(node.targets[0].id, node.value)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        self._record_write(node.target, node.lineno)
        if isinstance(node.target, ast.Name):
            t = _annotation_class(node.annotation)
            if t in self.model.classes:
                self.fi.var_types[node.target.id] = t
            if node.value is not None:
                self._infer_local(node.target.id, node.value)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._record_write(node.target, node.lineno)
        self.generic_visit(node)

    def visit_For(self, node: ast.For) -> None:
        # ``for x in ...`` clobbers any prior local typing of x
        for n in ast.walk(node.target):
            if isinstance(n, ast.Name):
                self.fi.var_types.pop(n.id, None)
                self.fi.local_queues.pop(n.id, None)
        # ... unless the iterable's element class is known:
        # ``for ring in rings`` / ``for i, ring in enumerate(rings)``
        it, tgt = node.iter, node.target
        if isinstance(it, ast.Call) \
                and dotted_name(it.func) == "enumerate" \
                and it.args:
            it = it.args[0]
            if isinstance(tgt, ast.Tuple) and len(tgt.elts) == 2:
                tgt = tgt.elts[1]
        if isinstance(it, ast.Name) and isinstance(tgt, ast.Name):
            t = self.fi.var_elem_types.get(it.id)
            if t is not None:
                self.fi.var_types[tgt.id] = t
        self.generic_visit(node)

    # -- calls, blocking ops, spawns ---------------------------------------

    def _queue_recv_bounded(self, recv: ast.AST) -> bool | None:
        """None when the receiver is not a known queue; else its
        boundedness."""
        attr = _self_attr(recv)
        if attr is not None and self.cls is not None:
            return self.cls.queues.get(attr)
        if isinstance(recv, ast.Name):
            return self.fi.local_queues.get(recv.id)
        if isinstance(recv, ast.Attribute) \
                and isinstance(recv.value, ast.Name):
            c = self._var_class(recv.value.id)
            if c is not None:
                return c.queues.get(recv.attr)
            a = _self_attr(recv)
        a = _self_attr(recv)
        if a is not None and self.cls is not None:
            t = self.model.classes.get(self.cls.attr_types.get(a, ""))
            if t is not None:
                return t.queues.get(recv.attr)
        return None

    def visit_Call(self, node: ast.Call) -> None:
        f = node.func
        line = node.lineno
        name = dotted_name(f)

        # thread spawn: threading.Thread(target=...)
        if name and _is_thread_ctor(name):
            self._record_spawn(node)
            self.generic_visit(node)
            return

        # project-class construction passing callables: record the
        # (class, param) -> callback target bindings so calls
        # through the stored attr (``self._on_resp(...)`` inside
        # PipeChannel, wired at a DistServer ctor site) resolve to
        # real edges — these fire on the CONSTRUCTED object's
        # threads, which is exactly where ownership and lock-order
        # need them
        cls_name = ""
        if name:
            parts = name.split(".")
            if parts[-1] in self.model.classes:
                cls_name = parts[-1]
        if cls_name:
            target_cm = self.model.classes[cls_name]
            for i, a in enumerate(node.args):
                spec = self._callable_spec(a)
                if spec and i < len(target_cm.init_params):
                    self.fi.ctor_callbacks.append(
                        (cls_name, target_cm.init_params[i],
                         spec, line))
            for kw in node.keywords:
                spec = self._callable_spec(kw.value)
                if spec and kw.arg:
                    self.fi.ctor_callbacks.append(
                        (cls_name, kw.arg, spec, line))

        # module-function blocking ops first (``time.sleep(...)``,
        # ``os.fsync(fd)``, ``subprocess.run(...)`` — Attribute or
        # bare-Name func nodes alike)
        dotted_blocked = False
        if name:
            if name.split(".")[0] == "subprocess":
                self.fi.blocking.append(
                    ("subprocess", name, self.held, line))
                dotted_blocked = True
            elif name in _BLOCKING_DOTTED:
                cat, op = _BLOCKING_DOTTED[name]
                self.fi.blocking.append((cat, op, self.held, line))
                dotted_blocked = True

        if isinstance(f, ast.Attribute):
            m = f.attr
            # lock.acquire(): an acquisition event (held set edge
            # source), conservatively not extending the held span
            if m == "acquire":
                lid = self._lock_id_of(f.value)
                if lid is not None:
                    self.fi.acquires.append((lid, self.held, line))
            # blocking queue get/put
            if m in ("get", "put"):
                qb = self._queue_recv_bounded(f.value)
                if qb is not None:
                    nonblock = any(
                        kw.arg == "block" and isinstance(
                            kw.value, ast.Constant)
                        and kw.value.value is False
                        for kw in node.keywords) or (
                        node.args and isinstance(
                            node.args[0], ast.Constant)
                        and node.args[0].value is False
                        and m == "get")
                    if not nonblock and (m == "get" or qb):
                        self.fi.blocking.append(
                            ("queue", f"queue.{m}", self.held,
                             line))
            elif m in _BLOCKING_METHODS and not dotted_blocked:
                self.fi.blocking.append(
                    (_BLOCKING_METHODS[m], f".{m}", self.held,
                     line))

            # call edges by receiver
            recv = f.value
            if isinstance(recv, ast.Name) and recv.id == "self":
                self.fi.raw_calls.append(
                    (("self", m), self.held, line))
            else:
                a = _self_attr(recv)
                if a is not None:
                    self.fi.raw_calls.append(
                        (("attr", a, m), self.held, line))
                elif isinstance(recv, ast.Name):
                    # typed local first; falls back to a dotted
                    # (module-receiver) lookup at resolve time
                    self.fi.raw_calls.append(
                        (("var", recv.id, m, name), self.held,
                         line))
                elif name:
                    self.fi.raw_calls.append(
                        (("dotted", name), self.held, line))
        elif name:
            self.fi.raw_calls.append((("dotted", name), self.held,
                                      line))
        self.generic_visit(node)

    def _callable_spec(self, value: ast.AST):
        """("self", m) / ("name", f) when the argument is a bound
        method, a bare function, or a lambda wrapping one."""
        attr = _self_attr(value)
        if attr is not None:
            return ("self", attr)
        if isinstance(value, ast.Lambda):
            for sub in ast.walk(value.body):
                if isinstance(sub, ast.Call):
                    a = _self_attr(sub.func)
                    if a is not None:
                        return ("self", a)
                    n = dotted_name(sub.func)
                    if n and "." not in n:
                        return ("name", n)
            return None
        if isinstance(value, ast.Name):
            return ("name", value.id)
        return None

    def _record_spawn(self, node: ast.Call) -> None:
        target = None
        tname = ""
        for kw in node.keywords:
            if kw.arg == "target":
                target = kw.value
            elif kw.arg == "name":
                if isinstance(kw.value, ast.Constant) \
                        and isinstance(kw.value.value, str):
                    tname = kw.value.value
                elif isinstance(kw.value, ast.JoinedStr):
                    tname = "".join(
                        v.value if isinstance(v, ast.Constant)
                        else "*" for v in kw.value.values)
        if target is None:
            return
        key = self.model._resolve_target(self.fi, self.cls, target)
        if key is not None:
            self.fi.spawns.append((key, tname, node.lineno))

    # nested defs/lambdas are separate functions; the model links
    # them with a def-site call edge instead of inlining their body
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        nested = f"{self.fi.scope}.{node.name}"
        key = (self.fi.relpath, nested)
        if key in self.model.functions:
            self.fi.raw_calls.append(
                (("def-site", key), self.held, node.lineno))

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node: ast.Lambda) -> None:
        pass

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        pass  # nested class bodies are scanned via their methods


class ConcurrencyModel:
    """See module docstring.  Build with :func:`concurrency_model`
    (cached per AnalysisContext)."""

    def __init__(self, root: str, ctx):
        self.root = root
        self.ctx = ctx
        cg = ctx.callgraph
        self.cg = cg
        #: bare class name -> ClassModel (ambiguous names dropped)
        self.classes: dict[str, ClassModel] = {}
        #: (relpath, var) -> lock ctor name, for module-level locks
        self.module_locks: dict[tuple[str, str], str] = {}
        #: (relpath, scope) -> FuncInfo
        self.functions: dict[tuple[str, str], FuncInfo] = {}
        #: jit-root function keys (purity-walk dispatch roots)
        self.jit_roots: set[tuple[str, str]] = set()

        self._collect_classes_and_locks()
        self._scan_functions()
        self._resolve_edges()

    # -- pass 1: classes, class attrs, module locks ------------------------

    def _collect_classes_and_locks(self) -> None:
        ambiguous: set[str] = set()
        for rel in self.cg.files:
            mi = self.cg.module(rel)
            if mi is None:
                continue
            for node in mi.tree.body:
                if isinstance(node, ast.Assign) \
                        and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name) \
                        and isinstance(node.value, ast.Call):
                    ctor = dotted_name(
                        node.value.func).split(".")[-1]
                    if ctor in LOCK_CTORS:
                        self.module_locks[
                            (rel, node.targets[0].id)] = ctor
            for scope, cnode in self._iter_classes(mi.tree, ""):
                cm = ClassModel(cnode.name, rel, scope, cnode)
                if cnode.name in self.classes \
                        or cnode.name in ambiguous:
                    ambiguous.add(cnode.name)
                    self.classes.pop(cnode.name, None)
                    continue
                self.classes[cnode.name] = cm
        for cm in self.classes.values():
            self._scan_class_attrs(cm)

    @staticmethod
    def _iter_classes(tree: ast.AST, prefix: str):
        for child in ast.iter_child_nodes(tree):
            if isinstance(child, ast.ClassDef):
                scope = f"{prefix}.{child.name}" if prefix \
                    else child.name
                yield scope, child
                yield from ConcurrencyModel._iter_classes(
                    child, scope)
            elif isinstance(child, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                scope = f"{prefix}.{child.name}" if prefix \
                    else child.name
                yield from ConcurrencyModel._iter_classes(
                    child, scope)

    def _scan_class_attrs(self, cm: ClassModel) -> None:
        for item in cm.node.body:
            if isinstance(item, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                cm.methods.add(item.name)
                ann = {a.arg: _annotation_class(a.annotation)
                       for a in item.args.args}
                if item.name == "__init__":
                    cm.init_params = [
                        a.arg for a in item.args.args[1:]]
                    for sub in ast.walk(item):
                        if not (isinstance(sub, ast.Assign)
                                and len(sub.targets) == 1):
                            continue
                        attr = _self_attr(sub.targets[0])
                        if attr is None:
                            continue
                        v = sub.value
                        if isinstance(v, ast.BoolOp) and v.values:
                            v = v.values[0]
                        if isinstance(v, ast.Name):
                            cm.param_attrs.setdefault(
                                v.id, attr)
                for sub in ast.walk(item):
                    attr = None
                    value = None
                    if isinstance(sub, ast.Assign) \
                            and len(sub.targets) == 1:
                        attr = _self_attr(sub.targets[0])
                        value = sub.value
                    elif isinstance(sub, ast.AnnAssign):
                        attr = _self_attr(sub.target)
                        value = sub.value
                        t = _annotation_class(sub.annotation)
                        if attr and t:
                            cm.attr_types.setdefault(attr, t)
                    if attr is None or value is None:
                        continue
                    if isinstance(value, ast.Call):
                        ctor = dotted_name(
                            value.func).split(".")[-1]
                        if ctor in LOCK_CTORS:
                            cm.locks.add(attr)
                            continue
                        qb = _queue_ctor_bound(value)
                        if qb is not None:
                            cm.queues[attr] = qb
                            continue
                        cname = dotted_name(value.func)
                        if cname and cname.split(".")[-1][:1] \
                                .isupper():
                            cm.attr_types.setdefault(
                                attr, cname.split(".")[-1])
                        elif cname and cname.split(".")[0][:1] \
                                .isupper():
                            # classmethod ctor: WAL.create(...)
                            cm.attr_types.setdefault(
                                attr, cname.split(".")[0])
                    elif isinstance(value, ast.Name) \
                            and ann.get(value.id):
                        # self.x = param  (annotated parameter)
                        cm.attr_types.setdefault(
                            attr, ann[value.id])

    # -- pass 2: per-function scans ----------------------------------------

    def _scan_functions(self) -> None:
        # create FuncInfo shells first (def-site edges need lookup)
        metas = []
        for rel in self.cg.files:
            mi = self.cg.module(rel)
            if mi is None:
                continue
            for scope, node in mi.functions.items():
                fi = FuncInfo(rel, scope, node)
                cls = self._enclosing_class(scope)
                if cls is not None:
                    fi.class_name = cls.name
                if any(_decorator_root(d)[0] for d in
                       getattr(node, "decorator_list", ())):
                    self.jit_roots.add((rel, scope))
                self.functions[(rel, scope)] = fi
                metas.append((fi, cls))
        for fi, cls in metas:
            self._type_params(fi, cls)
        # closure var-type inheritance: outer scopes scan first
        for fi, cls in sorted(metas,
                              key=lambda m: m[0].scope.count(".")):
            parent = fi.scope.rsplit(".", 1)[0] \
                if "." in fi.scope else None
            while parent:
                pfi = self.functions.get((fi.relpath, parent))
                if pfi is not None:
                    for k, v in pfi.var_types.items():
                        fi.var_types.setdefault(k, v)
                    for k, v in pfi.var_elem_types.items():
                        fi.var_elem_types.setdefault(k, v)
                    for k, v in pfi.local_queues.items():
                        fi.local_queues.setdefault(k, v)
                parent = parent.rsplit(".", 1)[0] \
                    if "." in parent else None
            scan = _FuncScan(self, fi, cls)
            for stmt in fi.node.body:
                scan.visit(stmt)

    def _enclosing_class(self, scope: str) -> ClassModel | None:
        if "." not in scope:
            return None
        cls_scope = scope.rsplit(".", 1)[0]
        bare = cls_scope.rsplit(".", 1)[-1]
        cm = self.classes.get(bare)
        if cm is not None and cm.scope == cls_scope:
            return cm
        return None

    def _type_params(self, fi: FuncInfo, cls) -> None:
        args = fi.node.args
        for a in (list(args.args) + list(args.kwonlyargs)
                  + list(getattr(args, "posonlyargs", []))):
            t = _annotation_class(a.annotation)
            if t in self.classes:
                fi.var_types[a.arg] = t

    # -- pass 3: resolve raw calls into function-key edges -----------------

    def resolve_name(self, relpath: str, name: str) -> list:
        """Project definitions a dotted call can reach (thin wrapper
        over the call graph, list of (rel, scope, node))."""
        return self.cg.resolve_call(relpath, name)

    def _method_key(self, cls: ClassModel | None, m: str):
        if cls is None or m not in cls.methods:
            return None
        key = (cls.relpath, f"{cls.scope}.{m}")
        return key if key in self.functions else None

    def _resolve_target(self, fi: FuncInfo, cls, target: ast.AST):
        """Thread-target expression -> function key, or None."""
        if isinstance(target, ast.Name):
            for rel, scope, _n in self.resolve_name(
                    fi.relpath, target.id):
                if (rel, scope) in self.functions:
                    return (rel, scope)
            # nested def in an enclosing scope
            probe = fi.scope
            while True:
                key = (fi.relpath, f"{probe}.{target.id}")
                if key in self.functions:
                    return key
                if "." not in probe:
                    break
                probe = probe.rsplit(".", 1)[0]
            return None
        attr = _self_attr(target)
        if attr is not None:
            return self._method_key(
                self._enclosing_class(fi.scope), attr)
        if isinstance(target, ast.Attribute) \
                and isinstance(target.value, ast.Name):
            t = self.classes.get(
                fi.var_types.get(target.value.id, ""))
            if t is not None:
                return self._method_key(t, target.attr)
        if isinstance(target, ast.Attribute) \
                and isinstance(target.value, ast.Call):
            # Thread(target=Worker(...).run): bound method of a
            # freshly constructed instance
            parts = dotted_name(target.value.func).split(".")
            for cname in (parts[-1], parts[0]):
                if cname in self.classes:
                    return self._method_key(
                        self.classes[cname], target.attr)
        return None

    def _resolve_edges(self) -> None:
        spawn_targets = set()
        for fi in self.functions.values():
            for key, _n, _l in fi.spawns:
                spawn_targets.add(key)
        for key in spawn_targets:
            self.functions[key].is_spawn_target = True

        # (callee class, stored attr) -> {function keys} from ctor
        # callback-passing sites anywhere in the project
        callbacks: dict[tuple[str, str], set] = {}
        for fi in self.functions.values():
            cls = self.classes.get(fi.class_name)
            for cname, param, spec, _line in fi.ctor_callbacks:
                cm = self.classes[cname]
                attr = cm.param_attrs.get(param, param)
                tkeys = []
                if spec[0] == "self":
                    k = self._method_key(cls, spec[1])
                    if k:
                        tkeys.append(k)
                else:
                    for rel, scope, _n in self.resolve_name(
                            fi.relpath, spec[1]):
                        if (rel, scope) in self.functions:
                            tkeys.append((rel, scope))
                for k in tkeys:
                    callbacks.setdefault(
                        (cname, attr), set()).add(k)

        for fi in self.functions.values():
            cls = self.classes.get(fi.class_name)
            for raw, held, line in fi.raw_calls:
                kind = raw[0]
                keys = []
                if kind == "self":
                    k = self._method_key(cls, raw[1])
                    if k:
                        keys.append(k)
                    elif cls is not None:
                        # stored-callback invocation
                        keys.extend(callbacks.get(
                            (cls.name, raw[1]), ()))
                elif kind == "attr":
                    t = self.classes.get(
                        (cls.attr_types.get(raw[1], "")
                         if cls else ""))
                    k = self._method_key(t, raw[2])
                    if k:
                        keys.append(k)
                elif kind == "var":
                    t = self.classes.get(
                        fi.var_types.get(raw[1], ""))
                    k = self._method_key(t, raw[2])
                    if k:
                        keys.append(k)
                    elif t is None and len(raw) > 3 and raw[3]:
                        # module-receiver call (``clientmsg.pack(...)``)
                        for rel, scope, _n in self.resolve_name(
                                fi.relpath, raw[3]):
                            if (rel, scope) in self.functions:
                                keys.append((rel, scope))
                elif kind == "def-site":
                    keys.append(raw[1])
                else:  # dotted
                    for rel, scope, _n in self.resolve_name(
                            fi.relpath, raw[1]):
                        if (rel, scope) in self.functions:
                            keys.append((rel, scope))
                for k in keys:
                    if self.functions[k].is_spawn_target:
                        continue  # spawn boundary: no held carry
                    if k in self.jit_roots:
                        fi.blocking.append(
                            ("jit-dispatch",
                             f"{k[1]} (jit root)", held, line))
                    fi.edges.append((k, held, line))

    # -- derived: entry-held sets and transitive acquires ------------------

    def call_sites(self) -> dict:
        """callee key -> [(caller key, held_tuple, line)], callers
        inside ``__init__`` scopes excluded (single-threaded by
        construction)."""
        sites: dict[tuple, list] = {}
        for key, fi in self.functions.items():
            if fi.scope.split(".")[-1] == "__init__":
                continue
            for callee, held, line in fi.edges:
                sites.setdefault(callee, []).append(
                    (key, held, line))
        return sites

    def entry_held_intersection(self) -> dict:
        """Must-held-at-entry per function: the intersection over
        its non-construction call sites of (lexical held at the site
        + the caller's own entry set) — the cross-module form of the
        locks.py "call with lock held" convention."""
        sites = self.call_sites()
        universe = frozenset(self.all_lock_ids())
        entry = {key: (universe if key in sites else frozenset())
                 for key in self.functions}
        for _ in range(len(self.functions)):
            changed = False
            for key, slist in sites.items():
                v = None
                for caller, held, _line in slist:
                    s = frozenset(held) | entry[caller]
                    v = s if v is None else (v & s)
                v = v if v is not None else frozenset()
                if v != entry[key]:
                    entry[key] = v
                    changed = True
            if not changed:
                break
        return entry

    def entry_held_union(self, restrict: frozenset) -> dict:
        """May-held-at-entry per function, restricted to the given
        lock set (blocking-under-lock wants "reachable while held",
        a union over call sites)."""
        sites = self.call_sites()
        entry = {key: frozenset() for key in self.functions}
        for _ in range(len(self.functions)):
            changed = False
            for key, slist in sites.items():
                v = entry[key]
                for caller, held, _line in slist:
                    v = v | ((frozenset(held) | entry[caller])
                             & restrict)
                if v != entry[key]:
                    entry[key] = v
                    changed = True
            if not changed:
                break
        return entry

    def transitive_acquires(self) -> dict:
        """function key -> every lock id the call may acquire,
        through the resolved call edges."""
        acq = {key: frozenset(a for a, _h, _l in fi.acquires)
               for key, fi in self.functions.items()}
        for _ in range(32):
            changed = False
            for key, fi in self.functions.items():
                add = acq[key]
                for callee, _h, _l in fi.edges:
                    add = add | acq[callee]
                if add != acq[key]:
                    acq[key] = add
                    changed = True
            if not changed:
                break
        return acq

    def all_lock_ids(self) -> set[str]:
        out = {f"{rel}:{var}" for (rel, var) in self.module_locks}
        for cm in self.classes.values():
            out |= {f"{cm.name}.{a}" for a in cm.locks}
        return out


_model_lock = threading.Lock()


def concurrency_model(root: str, ctx) -> ConcurrencyModel:
    """The per-run model, built once and cached on the context
    (thread-safe: the parallel checker fan-out shares it)."""
    with _model_lock:
        m = getattr(ctx, "_concurrency_model", None)
        if m is None or m.root != root:
            m = ConcurrencyModel(root, ctx)
            ctx._concurrency_model = m
        return m
