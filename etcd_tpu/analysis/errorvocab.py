"""error-vocabulary: raises on the client-visible tier must resolve
to the numeric vocabulary (utils/errors.py) or an allow-listed
internal type.

The reference maps every client-visible failure to a numeric code
(error/error.go); this tree keeps that vocabulary in
``utils/errors.py``.  In ``api/``, ``server/``, ``store/``:

- ``raise EtcdError(<code>, ...)`` (or the ``bad(<code>, ...)``
  helper): ``<code>`` must be an ``ECODE_*`` name defined in
  utils/errors.py or an integer literal in the vocabulary — an
  unknown code would serialize as "unknown error" to clients.
- ``raise <InternalType>(...)``: the type must be allow-listed
  (typed control-flow exceptions the HTTP layer translates, plus
  stdlib programming-error types).  ``raise Exception(...)`` or an
  unknown type is a finding — it reaches clients as an opaque 500.
- Bare ``raise`` and re-raising a captured variable are always fine.
"""

from __future__ import annotations

import ast
import os

from .engine import Checker, Finding, dotted_name, iter_functions

#: constructors that take a numeric vocabulary code as first arg
_VOCAB_CTORS = {"EtcdError", "bad"}

#: exception types allowed outside the numeric vocabulary: typed
#: internal control flow the API layer translates, plus stdlib
#: programming-error types that indicate caller bugs, not etcd state
_ALLOWED = {
    # repo-internal typed exceptions
    "UnknownMethodError", "ServerStoppedError", "ClientError",
    "StoppedError", "RaftPanicError", "WALError", "TornTailError",
    "FileNotFoundError_", "SnapError", "NoSnapshotError",
    "ProtoError", "FrameError", "DiscoveryError", "ClusterFullError",
    # PR 10: EtcdNoSpace carries ECODE_NO_SPACE (an EtcdError
    # subclass — listed for the bare-raise form); FrameDropped is
    # the injected-loss control exception the peer handler turns
    # into a closed connection
    "EtcdNoSpace", "FrameDropped",
    # PR 15: EtcdOverCapacity carries ECODE_OVER_CAPACITY (same
    # vocabulary-subclass pattern as EtcdNoSpace) — the front
    # door raises it when admission sheds
    "EtcdOverCapacity",
    # stdlib
    "ValueError", "TypeError", "KeyError", "IndexError",
    "AttributeError", "RuntimeError", "TimeoutError",
    "AssertionError", "NotImplementedError", "OSError",
    "FileExistsError", "FileNotFoundError", "InterruptedError",
    "StopIteration", "ConnectionError",
}

_VOCAB_RELPATH = "etcd_tpu/utils/errors.py"


def _load_vocab(root: str) -> tuple[set[str], set[int]]:
    """(ECODE_* names, numeric values) from utils/errors.py."""
    names: set[str] = set()
    values: set[int] = set()
    path = os.path.join(root or ".", _VOCAB_RELPATH)
    try:
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
    except OSError:
        return names, values
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id.startswith("ECODE_") \
                and isinstance(node.value, ast.Constant) \
                and isinstance(node.value.value, int):
            names.add(node.targets[0].id)
            values.add(node.value.value)
    return names, values


class ErrorVocabularyChecker(Checker):
    name = "error-vocabulary"
    targets = (
        "etcd_tpu/api/",
        "etcd_tpu/server/",
        "etcd_tpu/store/",
    )

    def __init__(self):
        self._vocab_cache: dict[str, tuple[set[str], set[int]]] = {}

    def check(self, relpath, tree, source, root=None, ctx=None):
        root = root or os.getcwd()
        if root not in self._vocab_cache:
            self._vocab_cache[root] = _load_vocab(root)
        names, values = self._vocab_cache[root]

        scope_of: dict[int, str] = {}
        for scope, fn in iter_functions(tree):
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Raise):
                    scope_of.setdefault(id(sub), scope)

        findings: list[Finding] = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Raise):
                continue
            scope = scope_of.get(id(node), "")
            exc = node.exc
            if exc is None:
                continue  # bare re-raise
            if not isinstance(exc, ast.Call):
                # `raise resp.err` / `raise e` — variable re-raise;
                # but a bare TYPE (`raise ValueError`) checks like a
                # zero-arg construction
                leaf = dotted_name(exc).split(".")[-1]
                if leaf and leaf[:1].isupper() \
                        and (leaf.endswith("Error")
                             or leaf.endswith("Exception")) \
                        and leaf not in _ALLOWED:
                    findings.append(self._finding(
                        relpath, node, scope, leaf,
                        f"`raise {leaf}` is outside the error "
                        f"vocabulary and the internal allow-list"))
                continue
            leaf = dotted_name(exc.func).split(".")[-1]
            if not leaf:
                continue  # computed constructor — can't resolve
            if leaf in _VOCAB_CTORS:
                findings.extend(self._check_code(
                    relpath, node, scope, exc, names, values))
                continue
            if leaf in _ALLOWED:
                continue
            if leaf in ("Exception", "BaseException"):
                findings.append(self._finding(
                    relpath, node, scope, leaf,
                    "generic `Exception` raised on the "
                    "client-visible tier — use EtcdError or a typed "
                    "internal exception"))
                continue
            findings.append(self._finding(
                relpath, node, scope, leaf,
                f"`{leaf}` is not in the numeric error vocabulary "
                f"or the internal allow-list"))
        return findings

    def _check_code(self, relpath, node, scope, call, names,
                    values) -> list[Finding]:
        if not call.args:
            return [self._finding(
                relpath, node, scope, "missing-code",
                "vocabulary constructor called without an error "
                "code")]
        code = call.args[0]
        if isinstance(code, ast.Name):
            if code.id.startswith("ECODE_") and names \
                    and code.id not in names:
                return [self._finding(
                    relpath, node, scope, code.id,
                    f"`{code.id}` is not defined in "
                    f"utils/errors.py")]
            return []  # a variable code — resolved at runtime
        if isinstance(code, ast.Constant) \
                and isinstance(code.value, int):
            if values and code.value not in values:
                return [self._finding(
                    relpath, node, scope, str(code.value),
                    f"numeric code {code.value} is not in the "
                    f"vocabulary (utils/errors.py)")]
            return []
        if isinstance(code, (ast.Attribute, ast.Call,
                             ast.Subscript, ast.IfExp, ast.BinOp)):
            return []  # runtime-resolved code (e.g. e.error_code,
            #            d.get("errorCode", 300))
        return [self._finding(
            relpath, node, scope, "opaque-code",
            "error code expression cannot be resolved to the "
            "vocabulary")]

    def _finding(self, relpath, node, scope, detail,
                 message) -> Finding:
        return Finding(
            checker=self.name, path=relpath, line=node.lineno,
            rule="unknown-exception", scope=scope, message=message,
            detail=detail)
