"""Whole-program layer: project module index, import edges, and the
call graph the cross-module checkers query.

One instance per analysis run (it lives on the engine's
:class:`~.engine.AnalysisContext`, sharing its AST cache), built
lazily the first time a checker asks a cross-module question:

- **import resolution** — ``import a.b.c [as z]``, ``from X import y
  [as z]`` (absolute and relative), ``from X import *``, and
  re-exported names (``__init__.py`` doing ``from .wal import WAL``)
  all resolve to the defining module file under the repo root.
- **function resolution** — :meth:`CallGraph.resolve_call` maps a
  dotted call name in a module's context to the ``(relpath, scope,
  ast-node)`` definitions it can reach, following re-export chains.
- **call sites** — :meth:`CallGraph.call_sites_of` inverts that: for
  one definition, every project call expression that resolves to it
  (the static-shapes checker reads argument shapes off these).
- **reverse dependents** — :meth:`CallGraph.reverse_dependents`
  closes a changed-file set over reverse import edges, so a
  restricted ``scripts/lint --changed`` run still sees every module
  whose cross-module findings could move.

Only project files participate (``etcd_tpu/``, ``scripts/*.py``,
top-level ``*.py``); stdlib/third-party names simply fail to resolve,
which every caller treats as "not ours".
"""

from __future__ import annotations

import ast
import os
import threading

from .engine import dotted_name, iter_functions, scope_map

#: directories (and top-level files) that form the project for
#: whole-program purposes
_PROJECT_DIRS = ("etcd_tpu", "scripts")


def project_files(root: str) -> list[str]:
    """Repo-relative posix paths of every project ``*.py`` file."""
    out: list[str] = []
    for d in _PROJECT_DIRS:
        base = os.path.join(root, d)
        for dirpath, dirs, files in os.walk(base):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for fn in files:
                if fn.endswith(".py"):
                    rel = os.path.relpath(
                        os.path.join(dirpath, fn), root)
                    out.append(rel.replace(os.sep, "/"))
    try:
        for fn in os.listdir(root):
            if fn.endswith(".py") \
                    and os.path.isfile(os.path.join(root, fn)):
                out.append(fn)
    except OSError:
        pass
    return sorted(set(out))


class ModuleInfo:
    """One parsed project module: its functions plus raw import
    records (resolved lazily by the owning :class:`CallGraph`)."""

    def __init__(self, relpath: str, tree: ast.AST):
        self.relpath = relpath
        self.tree = tree
        #: scope ("Class.method" / "fn") -> def node
        self.functions: dict[str, ast.AST] = {}
        #: bare def name -> [(scope, node)]
        self.by_name: dict[str, list] = {}
        for scope, node in iter_functions(tree):
            self.functions[scope] = node
            self.by_name.setdefault(node.name, []).append(
                (scope, node))
        #: ("from", level, module-or-None, [(name, asname)]) |
        #: ("import", "a.b.c", asname-or-None)
        self.import_records: list[tuple] = []
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                self.import_records.append(
                    ("from", node.level, node.module,
                     [(a.name, a.asname) for a in node.names]))
            elif isinstance(node, ast.Import):
                for a in node.names:
                    self.import_records.append(
                        ("import", a.name, a.asname))
        # filled by CallGraph._bind():
        #: local name -> (module relpath, remote name | None);
        #: remote None = the name IS a module alias
        self.imports: dict[str, tuple[str, str | None]] = {}
        #: dotted prefix ("a.b.c") -> module relpath, for plain
        #: ``import a.b.c`` attribute-chain calls
        self.dotted_imports: dict[str, str] = {}
        #: modules star-imported into this namespace
        self.star_imports: list[str] = []
        #: every project module this one imports (reverse-dep edges)
        self.imported_modules: set[str] = set()


class CallGraph:
    """Project-wide import/function index (see module docstring)."""

    def __init__(self, root: str, parse):
        """``parse(relpath) -> (tree, source)`` is the engine's cached
        AST accessor — the graph never re-reads a file the run already
        parsed."""
        self.root = root
        self._parse = parse
        self.files = project_files(root)
        self._fileset = set(self.files)
        self._modules: dict[str, ModuleInfo | None] = {}
        self._sites: dict[tuple[str, str], list] | None = None
        self._rev: dict[str, set[str]] | None = None
        self._entry_points: list[tuple[str, str]] | None = None
        # the checker fan-out in run_checkers shares one graph
        # across worker threads; lazy index builds are guarded
        self._build_lock = threading.Lock()

    # -- module access ----------------------------------------------------

    def module(self, relpath: str) -> ModuleInfo | None:
        mi = self._modules.get(relpath, False)
        if mi is not False:
            return mi
        try:
            tree, _source = self._parse(relpath)
            mi = ModuleInfo(relpath, tree)
            self._bind(mi)
        except (OSError, SyntaxError):
            mi = None
        self._modules[relpath] = mi
        return mi

    def resolve_module(self, parts: list[str]) -> str | None:
        """Module-name parts -> project relpath (file or package
        ``__init__.py``), None when it isn't ours."""
        if not parts:
            return None
        for cand in ("/".join(parts) + ".py",
                     "/".join(parts) + "/__init__.py"):
            if cand in self._fileset:
                return cand
        return None

    def _bind(self, mi: ModuleInfo) -> None:
        pkg = mi.relpath.split("/")[:-1]
        for rec in mi.import_records:
            if rec[0] == "import":
                _kind, dotted, asname = rec
                key = self.resolve_module(dotted.split("."))
                if key is None:
                    continue
                mi.imported_modules.add(key)
                if asname:
                    mi.imports[asname] = (key, None)
                else:
                    mi.dotted_imports[dotted] = key
                continue
            _kind, level, module, names = rec
            if level:
                # relative: level 1 = this package, 2 = parent, ...
                if level - 1 > len(pkg):
                    continue
                base = pkg[:len(pkg) - (level - 1)]
            else:
                base = []
            base = base + (module.split(".") if module else [])
            key = self.resolve_module(base)
            if key is None:
                continue
            mi.imported_modules.add(key)
            for name, asname in names:
                if name == "*":
                    mi.star_imports.append(key)
                    continue
                local = asname or name
                subkey = self.resolve_module(base + [name])
                if subkey is not None:
                    # ``from pkg import submodule [as z]``
                    mi.imported_modules.add(subkey)
                    mi.imports[local] = (subkey, None)
                else:
                    mi.imports[local] = (key, name)

    # -- function resolution ----------------------------------------------

    def resolve_function(self, modkey: str, fname: str,
                         _seen: set | None = None) -> list:
        """``(relpath, scope, node)`` definitions of ``fname`` in
        module ``modkey``, following re-export chains (``__init__.py``
        doing ``from .wal import f``) and star imports."""
        seen = _seen if _seen is not None else set()
        if (modkey, fname) in seen:
            return []
        seen.add((modkey, fname))
        mi = self.module(modkey)
        if mi is None:
            return []
        if fname in mi.by_name:
            return [(modkey, scope, node)
                    for scope, node in mi.by_name[fname]]
        hop = mi.imports.get(fname)
        if hop is not None:
            key, remote = hop
            if remote is not None:
                return self.resolve_function(key, remote, seen)
            return []  # a module alias is not a function
        out: list = []
        for key in mi.star_imports:
            out.extend(self.resolve_function(key, fname, seen))
        return out

    def resolve_call(self, relpath: str, name: str) -> list:
        """Definitions a call spelled ``name`` inside ``relpath`` can
        reach: local defs, ``from X import y as z`` names, module
        aliases (``import a.b as m; m.f()``), dotted module imports
        (``import a.b; a.b.f()``), star imports."""
        mi = self.module(relpath)
        if mi is None or not name:
            return []
        parts = name.split(".")
        if parts[0] in ("self", "cls"):
            return []
        if len(parts) == 1:
            return self.resolve_function(relpath, name)
        # module-alias attribute: ``m.f()``
        hop = mi.imports.get(parts[0])
        if hop is not None and hop[1] is None and len(parts) == 2:
            return self.resolve_function(hop[0], parts[1])
        # plain ``import a.b.c`` + ``a.b.c.f()``: everything before
        # the final attribute must be the imported module path
        key = mi.dotted_imports.get(".".join(parts[:-1]))
        if key is not None:
            return self.resolve_function(key, parts[-1])
        return []

    # -- call sites --------------------------------------------------------

    def call_sites_of(self, relpath: str, scope: str) -> list:
        """Every project call expression resolving to the definition
        at ``(relpath, scope)``: ``[(caller_relpath, caller_scope,
        ast.Call)]``."""
        with self._build_lock:
            if self._sites is None:
                self._build_sites()
        return self._sites.get((relpath, scope), [])

    def _build_sites(self) -> None:
        self._sites = {}
        for rel in self.files:
            mi = self.module(rel)
            if mi is None:
                continue
            owner = scope_map(mi.tree)
            for node in ast.walk(mi.tree):
                if not isinstance(node, ast.Call):
                    continue
                fname = dotted_name(node.func)
                if not fname:
                    continue
                for tkey, tscope, _tnode in \
                        self.resolve_call(rel, fname):
                    self._sites.setdefault(
                        (tkey, tscope), []).append(
                        (rel, owner.get(node, ""), node))

    # -- thread entry points ----------------------------------------------

    def thread_entry_points(self) -> list[tuple[str, str]]:
        """Unique ``(relpath, scope)`` definitions used as thread or
        process targets anywhere in the project —
        ``threading.Thread(target=f)``,
        ``multiprocessing.Process(target=self._run)``, bare
        ``Thread(target=...)``.  Each is the root of a NEW execution
        context: the ownership checker walks the call graph from
        these (plus the registered serve entry points), and held-lock
        propagation must NOT cross into them."""
        with self._build_lock:
            if self._entry_points is None:
                self._entry_points = self._find_entry_points()
        return list(self._entry_points)

    def _find_entry_points(self) -> list[tuple[str, str]]:
        out: set[tuple[str, str]] = set()
        for rel in self.files:
            mi = self.module(rel)
            if mi is None:
                continue
            owner = scope_map(mi.tree)
            for node in ast.walk(mi.tree):
                if not isinstance(node, ast.Call):
                    continue
                fname = dotted_name(node.func)
                last = fname.split(".")[-1] if fname else ""
                if last not in ("Thread", "Process"):
                    continue
                head = fname.split(".")[0]
                if "." in fname and head not in (
                        "threading", "multiprocessing", "mp"):
                    continue
                target = next(
                    (kw.value for kw in node.keywords
                     if kw.arg == "target"), None)
                if target is None:
                    continue
                key = self._resolve_spawn_target(
                    mi, owner.get(node, ""), target)
                if key is not None:
                    out.add(key)
        return sorted(out)

    def _resolve_spawn_target(self, mi: ModuleInfo,
                              spawn_scope: str,
                              target: ast.AST
                              ) -> tuple[str, str] | None:
        tname = dotted_name(target)
        if not tname:
            return None
        parts = tname.split(".")
        if parts[0] in ("self", "cls") and len(parts) == 2:
            # method target: nearest enclosing scope prefix owning
            # a def of that name ("FrontDoor.start" -> "FrontDoor._run")
            probe = spawn_scope
            while "." in probe:
                probe = probe.rsplit(".", 1)[0]
                cand = f"{probe}.{parts[1]}"
                if cand in mi.functions:
                    return (mi.relpath, cand)
            return None
        # plain function / imported name
        for rel, scope, _node in self.resolve_call(
                mi.relpath, tname):
            return (rel, scope)
        # nested def in an enclosing function scope
        probe = spawn_scope
        while probe:
            cand = f"{probe}.{tname}"
            if cand in mi.functions:
                return (mi.relpath, cand)
            probe = probe.rsplit(".", 1)[0] if "." in probe else ""
        if tname in mi.functions:
            return (mi.relpath, tname)
        return None

    # -- import closures ---------------------------------------------------

    def import_closure(self, relpaths: set[str]) -> set[str]:
        """Transitive closure of "is imported by one of ``relpaths``"
        (the inputs themselves excluded).  ``--changed`` needs this
        FORWARD direction too: a new call site in a changed caller
        can create a finding in the jit-root module it imports
        (static-shapes flags the callee's file)."""
        out: set[str] = set()
        frontier = list(relpaths)
        while frontier:
            mi = self.module(frontier.pop())
            if mi is None:
                continue
            for dep in mi.imported_modules:
                if dep not in out and dep not in relpaths:
                    out.add(dep)
                    frontier.append(dep)
        return out

    # -- reverse import dependents ----------------------------------------

    def reverse_dependents(self, relpaths: set[str]) -> set[str]:
        """Transitive closure of "imports one of ``relpaths``" over
        the project (the changed files themselves excluded)."""
        with self._build_lock:
            if self._rev is None:
                rev: dict[str, set[str]] = {}
                for rel in self.files:
                    mi = self.module(rel)
                    if mi is None:
                        continue
                    for dep in mi.imported_modules:
                        rev.setdefault(dep, set()).add(rel)
                self._rev = rev
        out: set[str] = set()
        frontier = list(relpaths)
        while frontier:
            cur = frontier.pop()
            for importer in self._rev.get(cur, ()):
                if importer not in out and importer not in relpaths:
                    out.add(importer)
                    frontier.append(importer)
        return out
