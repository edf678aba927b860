"""fault-vocabulary: failpoint names must be in the closed catalog.

The fault registry already raises ``FaultSpecError`` at configure
time for a spec naming an unknown failpoint — but a SEAM calling
``_faults.hit("wal.fsnyc")`` (typo) would silently never fire,
because nothing validates the call-site side at runtime (an unknown
point simply matches no rules).  This checker moves that to lint
time, mirroring metrics-vocabulary: every ``<faults-ish>.hit("...")``
call with a string-literal name must name a catalog entry
(utils/faults.py ``FAULT_CATALOG``), and a *dynamic* name is flagged
too — it defeats both this check and the README's failpoint table.

"Faults-ish" receivers: the final attribute/name segment is one of
``faults`` / ``_faults`` / ``FAULTS`` (the repo's binding
conventions: ``from ..utils import faults as _faults`` at seams,
``FAULTS.hit`` on the registry object).
"""

from __future__ import annotations

import ast

from .engine import Checker, Finding, dotted_name, scope_map

_RECEIVERS = {"faults", "_faults", "FAULTS"}


class FaultVocabularyChecker(Checker):
    name = "fault-vocabulary"
    targets = ("etcd_tpu/", "scripts/")

    def _catalog(self) -> set[str] | None:
        try:
            from ..utils.faults import FAULT_CATALOG

            return set(FAULT_CATALOG)
        except Exception:  # pragma: no cover - bootstrap order
            return None

    def check(self, relpath: str, tree: ast.AST, source: str,
              root: str | None = None, ctx=None) -> list[Finding]:
        if relpath == "etcd_tpu/utils/faults.py":
            return []  # the catalog itself
        catalog = self._catalog()
        if catalog is None:  # pragma: no cover
            return []
        owner = scope_map(tree)
        out: list[Finding] = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if not isinstance(func, ast.Attribute) \
                    or func.attr != "hit":
                continue
            recv = dotted_name(func.value)
            recv_last = recv.rsplit(".", 1)[-1] if recv else ""
            if recv_last not in _RECEIVERS:
                continue
            scope = owner.get(node, "")
            literal = None
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                literal = node.args[0].value
            if literal is None:
                out.append(Finding(
                    checker=self.name, path=relpath,
                    line=node.lineno, rule="dynamic-fault-name",
                    scope=scope,
                    message=f"{recv}.hit(<non-literal>) — failpoint "
                            f"names must be string literals from "
                            f"utils/faults.py's FAULT_CATALOG",
                    detail=f"{recv_last}.hit"))
            elif literal not in catalog:
                out.append(Finding(
                    checker=self.name, path=relpath,
                    line=node.lineno, rule="unregistered-fault",
                    scope=scope,
                    message=f"failpoint {literal!r} is not "
                            f"registered in utils/faults.py's "
                            f"FAULT_CATALOG — it would silently "
                            f"never fire",
                    detail=literal))
        return out
