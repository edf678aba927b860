"""Process-wide observability subsystem (PR 2 tentpole).

Two pillars:

- :mod:`.metrics` — typed catalog-checked registry (counters,
  gauges, log-bucketed histograms with exact snapshot-time
  percentiles); the ``metrics-vocabulary`` lint checker enforces the
  catalog.
- :mod:`.exporter` + :mod:`.devledger` — Prometheus text exposition
  for ``GET /metrics`` and the per-stage device/host transfer
  ledger wrapping the jitted-dispatch seams.

``utils.trace.Tracer`` is a thin facade over the span histogram
family, keeping the ``/v2/stats/spans`` contract byte-stable.
"""

from .metrics import CATALOG, Registry, registry

__all__ = ["CATALOG", "Registry", "registry"]
