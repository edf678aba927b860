"""First-class host tracing + optional JAX profiler capture.

New work mandated by SURVEY §5.1: the reference has nothing beyond
``log.Printf`` at state transitions (raft/node.go:208) and no pprof
endpoint in this snapshot.  Every hot seam (WAL persist, replay,
consensus round, apply, snapshot) runs under a named span; aggregated
latency stats (count/mean/p50/p99/max over a sliding window) are
exported via ``/v2/stats/spans`` and a JAX device-profile capture can
be armed with ``ETCD_TRACE_DIR=/path`` (written via
``jax.profiler.start_trace`` for xprof/tensorboard).

Since PR 2 the Tracer is a thin FACADE over the obs metrics registry:
``record`` lands in the ``etcd_span_seconds`` histogram family
(window 256, the same ring the old deque implementation kept), so
spans also appear in ``GET /metrics`` bucket form for free.  The
``/v2/stats/spans`` output is byte-stable against the pre-facade
implementation — same keys, same percentile index rule
(``sorted[min(n-1, int(n*q))]``), same rounding.

``tracer.stage()`` is the one timing mechanism the benchmark reads
(``etcd_stage_seconds{stage,kind}``).  Since PR 27 every stage is also
a ``jax.profiler.TraceAnnotation`` of the same name, so a profiler
session shows the program's stages as host events on the clock of the
device's operations; ``tracer.record_wait`` is the light record: one
wall sample under the same family, for a request's wait measured from
stamps the request carries and for work done once per request.  Since
PR 40 a ``TimedRLock`` files its own waits, holds and hand-overs that
way, by the role (``lock_role``) the taking thread's entry point names.
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import sys
import threading
import time

from ..obs import metrics as _metrics

log = logging.getLogger(__name__)

_SPAN_FAMILY = "etcd_span_seconds"  # catalog family backing spans

#: sliding window per span — governed by the catalog entry, surfaced
#: here for readers of the old constant
_WINDOW = _metrics.CATALOG[_SPAN_FAMILY].window

#: thread-local stack of active _StageCtx instances: devledger
#: charges device block/dispatch seconds to the INNERMOST stage so
#: the wall/cpu/device columns of etcd_stage_seconds sum honestly
#: (PR 8 — without this, a ledger-wrapped call inside a traced stage
#: shows its window in both the span wall and the ledger counters
#: with no way to separate them)
_stage_tls = threading.local()

#: thread ident -> innermost active stage name, published by
#: _StageCtx enter/exit for the sampling profiler (obs/profiler.py)
#: — a cross-thread-readable mirror of the thread-local stack (one
#: GIL-atomic dict store per stage pass; the profiler must never
#: touch another thread's TLS)
_active_stages: dict[int, str] = {}


#: ``jax.profiler.TraceAnnotation`` once JAX is up, else None.  This
#: module never imports JAX (the launcher parents that must not hold
#: a chip import it): the class is looked up only when some other
#: module has already put ``jax`` into ``sys.modules``.
_annotation_cls = None


def _annotation(name: str):
    """An entered ``TraceAnnotation(name)``, or None while JAX is not
    loaded.  With it every stage is a host event on the profiler's
    clock, in the same ``.xplane.pb`` as the device's ``XLA Ops``;
    without a profiler session a TraceMe is one flag test."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        try:
            cls = _annotation_cls = jax.profiler.TraceAnnotation
        except AttributeError:  # jax still half-imported
            return None
    ann = cls(name)
    ann.__enter__()
    return ann


def active_stages() -> dict[int, str]:
    """Snapshot of {thread ident: innermost active stage name}."""
    return dict(_active_stages)


def note_device_seconds(dt: float) -> None:
    """Charge ``dt`` seconds of device dispatch/block time to the
    innermost active stage() on this thread (no-op outside one).
    Called by obs/devledger.py at its seam exits."""
    stack = getattr(_stage_tls, "stack", None)
    if stack:
        stack[-1].device_s += dt


class _Span:
    __slots__ = ("tracer", "name", "t0")

    def __init__(self, tracer: "Tracer", name: str):
        self.tracer = tracer
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.tracer.record(self.name, time.perf_counter() - self.t0)
        return False


class _StageCtx:
    """One pass through a labeled stage: wall + thread-CPU + device
    attribution.  Also records the plain span (the ``/v2/stats/
    spans`` surface keeps its coverage — byte-stable format, same
    names).

    ``cpu=False`` leaves the thread-CPU column out: the two
    ``thread_time`` calls are most of a stage's cost where they are
    system calls, so the children that tile a hot pass take wall (and
    device) only and the pass itself carries the cpu.

    ``name`` may be set again before the exit: the pass is filed under
    the name it has then, for a pass that learns what it is on its way
    (the annotation keeps the name it was opened with)."""

    __slots__ = ("tracer", "name", "cpu", "t0", "c0", "device_s", "ann")

    def __init__(self, tracer: "Tracer", name: str, cpu: bool = True):
        self.tracer = tracer
        self.name = name
        self.cpu = cpu

    def __enter__(self):
        self.device_s = 0.0
        stack = getattr(_stage_tls, "stack", None)
        if stack is None:
            stack = _stage_tls.stack = []
        stack.append(self)
        _active_stages[threading.get_ident()] = self.name
        self.ann = _annotation(self.name)
        self.t0 = time.perf_counter()
        if self.cpu:
            self.c0 = time.thread_time()
        return self

    def __exit__(self, *exc):
        cpu = time.thread_time() - self.c0 if self.cpu else None
        wall = time.perf_counter() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)
        stack = _stage_tls.stack
        stack.pop()
        tid = threading.get_ident()
        if stack:
            # all three columns are inclusive: a parent's wall and
            # cpu hold its children's, so its device does too
            stack[-1].device_s += self.device_s
            _active_stages[tid] = stack[-1].name
        else:
            _active_stages.pop(tid, None)
        self.tracer.record(self.name, wall)
        self.tracer.record_stage(self.name, wall, cpu, self.device_s)
        return False


class Tracer:
    """Span recorder over a metrics registry's span family.

    A bare ``Tracer()`` owns a private registry (test isolation);
    the module-level :data:`tracer` records into the process-wide
    default registry so spans ride ``/metrics`` too.
    """

    def __init__(self, registry: _metrics.Registry | None = None):
        self._reg = (registry if registry is not None
                     else _metrics.Registry())
        # per-name child cache: the record path stays one dict get +
        # the histogram lock (catalog/label validation only on first
        # use) — the old deque implementation's cost profile
        self._hists: dict[str, _metrics.Histogram] = {}
        # (stage, kind) handle cache of etcd_stage_seconds, made on a
        # kind's first sample — record_stage runs per serving-loop
        # pass, record_wait per request
        self._stages: dict[tuple[str, str], _metrics.Histogram] = {}

    def span(self, name: str) -> _Span:
        return _Span(self, name)

    def stage(self, name: str, cpu: bool = True) -> _StageCtx:
        """Like :meth:`span`, plus per-stage CPU/device attribution:
        the pass lands in ``etcd_stage_seconds{stage,kind}`` (wall |
        cpu | device; the wall child's ``count`` is the number of
        passes; no cpu sample with ``cpu=False``) and, once JAX is
        loaded, is a ``TraceAnnotation`` of the same name on the
        profiler's clock.  The plain span family still gets the wall
        sample, so ``/v2/stats/spans`` output is unchanged."""
        return _StageCtx(self, name, cpu)

    def _stage_hist(self, name: str, kind: str) -> _metrics.Histogram:
        h = self._stages.get((name, kind))
        if h is None:
            h = self._stages[name, kind] = self._reg.histogram(
                "etcd_stage_seconds", stage=name, kind=kind)
        return h

    def record_wait(self, name: str, seconds: float) -> None:
        """The light record: one sample in
        ``etcd_stage_seconds{stage=name,kind="wall"}`` and nothing
        else.  For a wait some request sat through, measured by the
        caller from stamps that ride on the request, and for a short
        piece of work done once for every request (``fd.parse``).  No
        span, no cpu, no annotation: the waits of many requests
        overlap, and an annotation each would cover, and so name,
        every idle gap of a trace; and a whole stage costs 19 us on
        the v5e's host against 0.8 us for this."""
        self._stage_hist(name, "wall").observe(seconds)

    def record(self, name: str, dt: float) -> None:
        h = self._hists.get(name)
        if h is None:
            h = self._hists[name] = self._reg.histogram(
                "etcd_span_seconds", span=name)
        h.observe(dt)

    def record_stage(self, name: str, wall: float,
                     cpu: float | None = None,
                     device: float = 0.0) -> None:
        self._stage_hist(name, "wall").observe(wall)
        if cpu is not None:
            self._stage_hist(name, "cpu").observe(cpu)
        if device > 0.0:
            # device samples only when the stage actually crossed a
            # ledger seam — an all-zero series would drown the sums'
            # signal in sample count without adding information
            self._stage_hist(name, "device").observe(device)

    def snapshot(self) -> dict:
        out = {}
        for (name,), hist in self._reg.family(
                _SPAN_FAMILY).children():
            count, total, mx, ring = hist.ring_stats()
            if not ring:
                continue
            p50 = ring[len(ring) // 2]
            p99 = ring[min(len(ring) - 1, int(len(ring) * 0.99))]
            out[name] = {
                "count": count,
                "total_ms": round(total * 1e3, 3),
                "mean_ms": round(total / count * 1e3, 3),
                "p50_ms": round(p50 * 1e3, 3),
                "p99_ms": round(p99 * 1e3, 3),
                "max_ms": round(mx * 1e3, 3),
            }
        return out

    def snapshot_json(self) -> bytes:
        return (json.dumps(self.snapshot(), sort_keys=True) +
                "\n").encode()

    def reset(self) -> None:
        # the caches must drop with the families' children: a cached
        # handle to a cleared child would record into an orphan the
        # snapshot path no longer sees
        self._hists = {}
        self._stages = {}
        self._reg.family(_SPAN_FAMILY).clear()
        try:
            self._reg.family("etcd_stage_seconds").clear()
        except KeyError:  # pragma: no cover - custom catalogs
            pass


#: process-wide default tracer — servers and replay paths record here
#: (into the default obs registry, so spans surface on /metrics too)
tracer = Tracer(_metrics.registry)


#: this thread's role for a TimedRLock: ``(role, since, outer)``, the
#: role an entry point set, the stamp its wait counts from (None: the
#: moment before the acquire) and the role it shadows
_role_tls = threading.local()


class lock_role(contextlib.ContextDecorator):
    """What this thread takes a :class:`TimedRLock` for, for the length
    of a ``with`` block or of a decorated call.  ``since`` is a
    ``time.monotonic()`` stamp the wait counts from (the moment a
    response was read), else the moment before the acquire.  The
    instance keeps no state, so one may decorate a method that many
    threads run at once."""

    def __init__(self, role: str | None, since: float | None = None):
        self.role = role
        self.since = since

    def __enter__(self):
        _role_tls.cur = (self.role, self.since,
                         getattr(_role_tls, "cur", None))
        return self

    def __exit__(self, *exc):
        _role_tls.cur = _role_tls.cur[2]
        return False


class TimedRLock:
    """A ``threading.RLock`` whose outermost acquisition and release by
    a thread are timed under the thread's :class:`lock_role` and filed
    with :meth:`Tracer.record_wait`; re-entry files nothing, nor does a
    thread whose role is in none of ``wait``, ``handoff``, ``annotate``:

    - ``<name>.lock_wait.<role>``, roles in ``wait``: ``since`` (or the
      moment before the acquire) to the lock held, every acquisition;
    - ``<name>.lock_hold.<role>``, every role: held to the release;
    - ``<name>.lock_handoff``, roles in ``handoff``, contended only:
      the previous holder's release to this acquire's return, what a
      hand-over costs beyond the work it waited for.

    A contended acquisition by a role in ``annotate`` is a
    ``TraceAnnotation`` of its wait's name around the blocking acquire:
    the thread that dispatches the device's work names the idle gaps
    its waits leave (other roles' waits overlap, as ``record_wait``'s
    do).  Uncontended: one ``acquire(False)`` and two clock reads."""

    def __init__(self, name: str, wait: tuple[str, ...] = (),
                 handoff: tuple[str, ...] = (),
                 annotate: tuple[str, ...] = (),
                 recorder: Tracer | None = None):
        self._lock = threading.RLock()
        self._tracer = recorder if recorder is not None else tracer
        # role -> (wait stage, hold stage, hand-over stage, annotation)
        self._specs = {
            r: (f"{name}.lock_wait.{r}" if r in wait else None,
                f"{name}.lock_hold.{r}",
                f"{name}.lock_handoff" if r in handoff else None,
                f"{name}.lock_wait.{r}" if r in annotate else None)
            for r in {*wait, *handoff, *annotate}}
        # written by the owner alone, under the lock
        self._owner: int | None = None
        self._depth = 0
        self._hold: str | None = None
        self._t_held = 0.0
        self._released = time.monotonic()

    def acquire(self) -> bool:
        me = threading.get_ident()
        if self._owner == me:
            self._depth += 1
            return True
        cur = getattr(_role_tls, "cur", None)
        spec = self._specs.get(cur[0]) if cur is not None else None
        if spec is None:
            self._lock.acquire()
            self._owner, self._depth, self._hold = me, 1, None
            return True
        wait, hold, handoff, ann_name = spec
        t0 = time.monotonic() if cur[1] is None else cur[1]
        if self._lock.acquire(False):
            t = time.monotonic()
        else:
            ann = _annotation(ann_name) if ann_name else None
            self._lock.acquire()
            t = time.monotonic()
            if ann is not None:
                ann.__exit__(None, None, None)
            if handoff:
                self._tracer.record_wait(handoff, t - self._released)
        self._owner, self._depth, self._hold, self._t_held = (
            me, 1, hold, t)
        if wait:
            self._tracer.record_wait(wait, t - t0)
        return True

    def release(self) -> None:
        if self._owner != threading.get_ident():
            raise RuntimeError("cannot release un-acquired lock")
        if self._depth > 1:
            self._depth -= 1
            return
        hold, t_held = self._hold, self._t_held
        t = self._released = time.monotonic()
        self._owner, self._depth = None, 0
        self._lock.release()
        if hold is not None:
            self._tracer.record_wait(hold, t - t_held)

    __enter__ = acquire

    def __exit__(self, *exc) -> None:
        self.release()


_profiling = False


def maybe_start_jax_profile() -> bool:
    """Arm a device-level trace when ETCD_TRACE_DIR is set (xprof
    format; inspect with tensorboard).  Idempotent; returns whether a
    capture is running."""
    global _profiling
    d = os.environ.get("ETCD_TRACE_DIR")
    if not d or _profiling:
        return _profiling
    try:
        import jax

        jax.profiler.start_trace(d)
        _profiling = True
        log.info("trace: JAX profiler capturing to %s", d)
    except Exception as e:  # pragma: no cover - device/env specific
        log.warning("trace: could not start JAX profiler: %s", e)
    return _profiling


def stop_jax_profile() -> None:
    global _profiling
    if _profiling:
        import jax

        jax.profiler.stop_trace()
        _profiling = False
