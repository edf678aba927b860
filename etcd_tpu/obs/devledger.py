"""Device/host transfer ledger: per-stage accounting of the jitted
dispatch seams.

The 24× restart-replay regression on TPU sessions (round-5 VERDICT)
went unnoticed because nothing counted what each stage shipped across
the host↔device boundary.  This ledger makes the transfer-per-round
tax (ROADMAP's device-host-boundary checker idea, partially served at
runtime here) readable off any run: each instrumented seam records

- **dispatches** and wall seconds inside the seam,
- **block seconds** — time spent waiting on device results
  (``block_until_ready`` or host materialization via ``np.asarray``),
- **H2D / D2H bytes** — what actually crossed the boundary.

Stages are coarse, named strings ("multiraft.round",
"replay.verify", "dist.propose", ...) feeding the labeled
``etcd_devledger_*`` counter families, so the ledger shows up in
``GET /metrics``, ``/mraft/obs`` and the soak artifact for free.

The record path is a couple of counter adds — safe inside serving
loops.  NOTHING here may run inside a traced function (the
tracer-purity checker's domain): callers wrap the *dispatch call
site*, never the traced body.

Stage attribution (PR 8): when a ledger seam runs inside an active
``tracer.stage(...)`` context, its host seconds blocked at the seam
are charged ONCE to the innermost stage's
``etcd_stage_seconds{kind="device"}`` column (via
utils/trace.note_device_seconds), and from there into each enclosing
stage's, as wall and cpu are.  They are seconds the HOST spent at the
seam, not seconds the device worked: every read-back of a round goes
through ``fetch`` so that the wait for the device is billed here and
not lost between two seams.  A ``dispatch`` seam charges
its whole window at exit; ``block``/``fetch`` charge only when no
dispatch seam is active on the thread — a block inside a dispatch is
already inside the dispatch's window, and charging both would
double-count the very seconds this split exists to make honest.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager

from .metrics import Registry, registry as default_registry

_tls = threading.local()  # per-thread active-dispatch depth

# utils/trace imports obs.metrics; importing it lazily here keeps
# obs importable before utils and avoids a cycle at package init
_note_device = None


def _charge_stage(dt: float) -> None:
    global _note_device
    if _note_device is None:
        from ..utils.trace import note_device_seconds

        _note_device = note_device_seconds
    _note_device(dt)


def nbytes_of(x) -> int:
    """Best-effort byte size of one array-ish value."""
    n = getattr(x, "nbytes", None)
    if n is not None:
        return int(n)
    if isinstance(x, (bytes, bytearray, memoryview)):
        return len(x)
    return 0


class _Stage:
    __slots__ = ("dispatches", "dispatch_seconds", "block_seconds",
                 "h2d_bytes", "d2h_bytes")

    def __init__(self, reg: Registry, stage: str):
        self.dispatches = reg.counter(
            "etcd_devledger_dispatches_total", stage=stage)
        self.dispatch_seconds = reg.counter(
            "etcd_devledger_dispatch_seconds_total", stage=stage)
        self.block_seconds = reg.counter(
            "etcd_devledger_block_seconds_total", stage=stage)
        self.h2d_bytes = reg.counter(
            "etcd_devledger_h2d_bytes_total", stage=stage)
        self.d2h_bytes = reg.counter(
            "etcd_devledger_d2h_bytes_total", stage=stage)


class DeviceLedger:
    def __init__(self, reg: Registry | None = None):
        self._reg = reg if reg is not None else default_registry
        self._lock = threading.Lock()
        self._stages: dict[str, _Stage] = {}

    def _stage(self, stage: str) -> _Stage:
        s = self._stages.get(stage)
        if s is None:
            with self._lock:
                s = self._stages.get(stage)
                if s is None:
                    s = _Stage(self._reg, stage)
                    self._stages[stage] = s
        return s

    @contextmanager
    def dispatch(self, stage: str):
        """Time one pass through a jitted-dispatch seam.  The
        window is charged to the enclosing stage()'s device column
        at exit (module docstring)."""
        s = self._stage(stage)
        depth = getattr(_tls, "dispatch_depth", 0)
        _tls.dispatch_depth = depth + 1
        t0 = time.perf_counter()
        try:
            yield s
        finally:
            dt = time.perf_counter() - t0
            _tls.dispatch_depth = depth
            s.dispatches.inc()
            s.dispatch_seconds.inc(dt)
            if depth == 0:
                # outermost seam only: a nested dispatch's window is
                # inside ours already
                _charge_stage(dt)

    def h2d(self, stage: str, *values) -> None:
        n = sum(nbytes_of(v) for v in values)
        if n:
            self._stage(stage).h2d_bytes.inc(n)

    def d2h(self, stage: str, *values) -> None:
        n = sum(nbytes_of(v) for v in values)
        if n:
            self._stage(stage).d2h_bytes.inc(n)

    def block(self, stage: str, value):
        """``jax.block_until_ready`` with the wait billed to the
        stage; returns the (now ready) value."""
        import jax

        s = self._stage(stage)
        t0 = time.perf_counter()
        out = jax.block_until_ready(value)
        dt = time.perf_counter() - t0
        s.block_seconds.inc(dt)
        if not getattr(_tls, "dispatch_depth", 0):
            _charge_stage(dt)
        return out

    def fetch(self, stage: str, value):
        """Materialize a device value to a host numpy array, billing
        the wait as block time and the result's bytes as D2H."""
        import numpy as np

        s = self._stage(stage)
        t0 = time.perf_counter()
        out = np.asarray(value)
        dt = time.perf_counter() - t0
        s.block_seconds.inc(dt)
        s.d2h_bytes.inc(out.nbytes)
        if not getattr(_tls, "dispatch_depth", 0):
            _charge_stage(dt)
        return out

    def snapshot(self) -> dict:
        """Per-stage totals (a convenience view of the same counters
        the exporter renders)."""
        out = {}
        with self._lock:
            stages = dict(self._stages)
        for name, s in stages.items():
            out[name] = {
                "dispatches": s.dispatches.get(),
                "dispatch_seconds": round(s.dispatch_seconds.get(),
                                          6),
                "block_seconds": round(s.block_seconds.get(), 6),
                "h2d_bytes": s.h2d_bytes.get(),
                "d2h_bytes": s.d2h_bytes.get(),
            }
        return out


#: process-wide default ledger, recording into the default registry
ledger = DeviceLedger()

__all__ = ["DeviceLedger", "ledger", "nbytes_of"]
