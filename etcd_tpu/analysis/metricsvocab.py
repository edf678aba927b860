"""metrics-vocabulary: registry accessor names must be in the catalog.

The obs registry already raises ``KeyError`` at runtime for a name
missing from ``obs/metrics.py``'s CATALOG — but only when the code
path executes.  This checker moves that to lint time: every
``<registry-ish>.counter("...")`` / ``.gauge("...")`` /
``.histogram("...")`` call with a string-literal name must name a
registered family, and a *dynamic* (non-literal) name on a
registry-ish receiver is flagged too, because it defeats both this
check and the README's metric inventory.

"Registry-ish" receivers: the final attribute/name segment is one of
``registry`` / ``obs_registry`` / ``reg`` / ``_reg`` (the repo's
binding conventions), or the name literal itself starts with
``etcd_`` (the catalog's namespace) — so an accessor call on any
receiver that *tries* to mint an ``etcd_*`` metric is checked.
"""

from __future__ import annotations

import ast

from .engine import Checker, Finding, dotted_name, scope_map

_ACCESSORS = {"counter", "gauge", "histogram"}
_RECEIVERS = {"registry", "obs_registry", "reg", "_reg", "_obs"}


class MetricsVocabularyChecker(Checker):
    name = "metrics-vocabulary"
    targets = ("etcd_tpu/", "scripts/")

    def _catalog(self) -> set[str] | None:
        try:
            from ..obs.metrics import CATALOG

            return set(CATALOG)
        except Exception:  # pragma: no cover - bootstrap order
            return None

    def check(self, relpath: str, tree: ast.AST, source: str,
              root: str | None = None, ctx=None) -> list[Finding]:
        if relpath == "etcd_tpu/obs/metrics.py":
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
                    or func.attr not in _ACCESSORS:
                continue
            recv = dotted_name(func.value)
            recv_last = recv.rsplit(".", 1)[-1] if recv else ""
            literal = None
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                literal = node.args[0].value
            registryish = recv_last in _RECEIVERS or (
                literal is not None and literal.startswith("etcd_"))
            if not registryish:
                continue
            scope = owner.get(node, "")
            if literal is None:
                out.append(Finding(
                    checker=self.name, path=relpath,
                    line=node.lineno, rule="dynamic-metric-name",
                    scope=scope,
                    message=f"{recv}.{func.attr}(<non-literal>) — "
                            f"metric names must be string literals "
                            f"from obs/metrics.py's CATALOG",
                    detail=f"{recv_last}.{func.attr}"))
            elif literal not in catalog:
                out.append(Finding(
                    checker=self.name, path=relpath,
                    line=node.lineno, rule="unregistered-metric",
                    scope=scope,
                    message=f"metric {literal!r} is not registered "
                            f"in obs/metrics.py's CATALOG",
                    detail=literal))
        return out
