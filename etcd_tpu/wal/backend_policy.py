"""Measured per-stage backend router for the replay data plane.

The replay path once picked its backend statically and lost on two
fronts the reference never loses on: with no accelerator the e2e
replay still shipped every record through the JAX-CPU bit-matmul,
and with a slow link to the device the restart replay ran far below
the identical stage on the host path.  This module generalizes the one measured auto-choice the repo already had
(ops/crc_kernel's snapshot-hash race, "config3 auto") into a reusable
router for every replay-shaped stage (restart replay, bulk replay,
the bench e2e row):

- **probe**: a cheap startup measurement of the three pipeline legs —
  host fused scan (native scan_verify over a small synthetic stream),
  H2D shipping, and the device CRC verify — cached in-process and,
  when ``cache_path`` is given, on disk so restarts reuse it.
- **route**: ``host`` (fused single-pass native scan), ``device``
  (monolithic batched device verify), or ``stream`` (the chunked
  double-buffered overlap pipeline, wal/replay_device.py).  The
  device lanes are chosen ONLY when the probed pipeline floor —
  min(host_scan, h2d, device_verify), what the overlap pipeline can
  sustain — beats the probed host throughput, so a present-but-slow
  accelerator can never regress replay below the host path.
- **override**: ``ETCD_REPLAY_BACKEND=host|device|stream`` wins over
  the probe unconditionally (operator escape hatch; read per
  decision, so tests and long-lived processes can flip it).

Every decision lands in the obs registry (``etcd_replay_backend_route``
per stage, ``etcd_replay_probe_bytes_per_sec`` per leg) and in
``snapshot()`` — the form chip_smoke.py reads after a restart so a
reviewer can attribute a result to routing vs kernel.

Import-light by design: jax only loads inside the device probe, so
the CPU-pinned server path can route without initializing a backend.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time

import numpy as np

from .. import native
from ..obs import metrics as _obs

log = logging.getLogger(__name__)

#: the operator override (host | device | stream; aliases accepted)
ENV_KNOB = "ETCD_REPLAY_BACKEND"

ROUTES = ("host", "device", "stream")

_ALIASES = {
    "native-host": "host", "native_host": "host", "cpu": "host",
    "streaming-device": "stream", "streaming": "stream",
    "tpu": "device",
}

#: default streaming chunk size — the scripts/replay_bench.py sweep
#: showed host-path throughput flat from 4 MiB up (1 MiB pays ~2-3%
#: more per-chunk overhead), and 4 MiB keeps at most ~12 MiB of scan
#: arrays in flight at double-buffer depth 2
DEFAULT_CHUNK_BYTES = 4 << 20

#: below this stream size the device lanes can't amortize their jit
#: compile (seconds), so the router answers "host" WITHOUT probing —
#: a tiny-WAL restart must not initialize a jax backend just to be
#: told what the size already says (server.py's historical threshold)
DEVICE_MIN_BYTES = 8 << 20

#: on-disk probe cache lifetime — a stale measurement pinning the
#: route would recreate the static-choice failure mode this module
#: exists to kill
DEFAULT_CACHE_TTL_S = 24 * 3600

# probe shapes: small enough to be a startup blip (~1 MiB host blob,
# one [2048, 384] device batch), large enough to amortize call setup
_PROBE_ENTRIES = 4096
_PROBE_PAYLOAD = 256
_PROBE_ROWS = 2048
_PROBE_WIDTH = 384

_PROBE_LEGS = ("host_scan", "host_frame", "h2d", "device_verify")


def _probe_host_default() -> dict | None:
    """Host-leg throughputs (bytes/s) over a synthetic stream:
    ``host_scan_bps`` is the FUSED pass (frame + parse + CRC — what
    the host route runs), ``host_frame_bps`` the frame/parse-only
    sweep (the streaming pipeline's host stage; the CRC rides the
    device there).  None when the native toolchain is absent."""
    if not native.available():
        return None
    blob = native.wal_gen(_PROBE_ENTRIES, _PROBE_PAYLOAD,
                          start_index=1, seed=0)

    def best_of2(fn):
        best = float("inf")
        for _ in range(2):  # best-of-2: first pass pays page faults
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return blob.nbytes / max(best, 1e-9)

    return {"host_scan_bps":
            best_of2(lambda: native.scan_verify(blob, seed=0)),
            "host_frame_bps":
            best_of2(lambda: native.wal_scan(blob))}


def _probe_device_default() -> dict | None:
    """H2D and device-verify throughput (bytes/s), or None when the
    default backend is the host CPU (no accelerator to route to).
    Raises on a broken device — the caller maps that to the host
    route."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    from ..ops.crc_device import raw_crc_batch

    rows = np.zeros((_PROBE_ROWS, _PROBE_WIDTH), np.uint8)
    jax.block_until_ready(jax.device_put(rows))  # warm the transfer
    t0 = time.perf_counter()
    shipped = jax.block_until_ready(jax.device_put(rows))
    h2d = rows.nbytes / max(time.perf_counter() - t0, 1e-9)
    jax.block_until_ready(raw_crc_batch(shipped))  # compile warmup
    t0 = time.perf_counter()
    jax.block_until_ready(raw_crc_batch(shipped))
    verify = rows.nbytes / max(time.perf_counter() - t0, 1e-9)
    return {"h2d_bps": h2d, "device_verify_bps": verify}


class BackendPolicy:
    """One process's replay-routing state: probe results + decisions.

    ``probe_host`` / ``probe_device`` are injectable for tests (a
    simulated slow or broken device must provably select the host
    route without hardware in the loop).
    """

    def __init__(self, cache_path: str | None = None,
                 probe_host=None, probe_device=None,
                 chunk_bytes: int = DEFAULT_CHUNK_BYTES,
                 device_min_bytes: int = DEVICE_MIN_BYTES,
                 cache_ttl_s: float = DEFAULT_CACHE_TTL_S):
        self._lock = threading.Lock()
        self.cache_path = cache_path
        self.chunk_bytes = int(chunk_bytes)
        self.device_min_bytes = int(device_min_bytes)
        self.cache_ttl_s = float(cache_ttl_s)
        self._probe_host_fn = probe_host or _probe_host_default
        self._probe_device_fn = probe_device or _probe_device_default
        self._probe: dict | None = None
        self.decisions: dict[str, dict] = {}
        _obs.registry.gauge("etcd_replay_stream_chunk_bytes").set(
            self.chunk_bytes)

    # -- probe ------------------------------------------------------------

    def probe(self) -> dict:
        """Measure (or recall) the per-leg throughputs.  One probe per
        process; ``cache_path`` extends the reuse across restarts."""
        with self._lock:
            if self._probe is not None:
                return self._probe
            p = self._load_cache()
            if p is None:
                p = self._measure()
                self._save_cache(p)
            else:
                p["source"] = "cache"
            self._probe = p
        for leg in _PROBE_LEGS:
            _obs.registry.gauge(
                "etcd_replay_probe_bytes_per_sec", leg=leg).set(
                p.get(f"{leg}_bps") or 0.0)
        return p

    def _measure(self) -> dict:
        p: dict = {"source": "probe",
                   "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                       time.gmtime()),
                   "ts_epoch": time.time()}
        try:
            ph = self._probe_host_fn()
        except Exception as e:  # no native tier: the device lanes
            log.warning("backend_policy: host probe failed: %r", e)
            ph = None  # may still carry the replay
            p["host_error"] = repr(e)[:200]
        if isinstance(ph, dict):
            p["host_scan_bps"] = ph.get("host_scan_bps")
            p["host_frame_bps"] = ph.get("host_frame_bps",
                                         ph.get("host_scan_bps"))
        else:  # injected scalar probes: one number for both legs
            p["host_scan_bps"] = ph
            p["host_frame_bps"] = ph
        try:
            dev = self._probe_device_fn()
        except Exception as e:
            # a broken/unreachable accelerator must degrade to the
            # host path, never crash a restart
            log.warning("backend_policy: device probe failed: %r", e)
            dev = None
            p["device_error"] = repr(e)[:200]
        p["h2d_bps"] = (dev or {}).get("h2d_bps")
        p["device_verify_bps"] = (dev or {}).get("device_verify_bps")
        return p

    def _load_cache(self) -> dict | None:
        if not self.cache_path:
            return None
        try:
            with open(self.cache_path) as fh:
                doc = json.load(fh)
            if doc.get("version") != 1:
                return None
            p = dict(doc["probe"])
            age = time.time() - float(p.get("ts_epoch", 0))
            if not 0 <= age <= self.cache_ttl_s:
                log.info("backend_policy: probe cache is %.0fh old; "
                         "re-probing", age / 3600)
                return None
            return p
        except (OSError, ValueError, KeyError, TypeError):
            return None

    def _save_cache(self, p: dict) -> None:
        if not self.cache_path:
            return
        if "device_error" in p or "host_error" in p:
            # a probe taken during an outage must not pin the route
            # for every later restart — errors stay process-local
            return
        try:
            tmp = self.cache_path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump({"version": 1, "probe": p}, fh)
            os.replace(tmp, self.cache_path)
        except OSError as e:  # cache is an optimization, never fatal
            log.warning("backend_policy: cache write failed: %r", e)

    # -- routing ----------------------------------------------------------

    def route(self, stage: str, size_bytes: int | None = None,
              strict_device: bool = False) -> str:
        """Pick host | device | stream for one replay-shaped stage.

        Precedence: env override > (strict_device: the operator's
        --storage-backend=tpu promise) > size gate > probe comparison
        > host.  The decision is recorded per ``stage`` in the
        registry and in :attr:`decisions`.
        """
        route, why = self._env_route()
        if route is None and not strict_device \
                and size_bytes is not None \
                and size_bytes < self.device_min_bytes:
            # tiny streams: the device lanes can't amortize their jit
            # compile, and the device probe would initialize a jax
            # backend on the restart path — answer without either
            route, why = "host", (
                f"size {int(size_bytes)} B < device threshold "
                f"{self.device_min_bytes} B")
        if route is None:
            probe = self.probe()
            if strict_device:
                route, why = "stream", "strict_device"
            elif probe.get("device_verify_bps") is None:
                route, why = "host", (
                    "no usable accelerator"
                    if "device_error" not in probe
                    else f"device probe failed: {probe['device_error']}")
            else:
                # host route sustains the FUSED pass; the pipeline
                # sustains min over its legs — frame-only host scan
                # (CRC rides the device), H2D, device verify
                host = probe.get("host_scan_bps") or 0.0
                floor = min(x for x in (
                    probe.get("host_frame_bps") or float("inf"),
                    probe["h2d_bps"],
                    probe["device_verify_bps"]))
                if floor > host:
                    route, why = "stream", (
                        f"pipeline floor {floor:.3g} B/s > host "
                        f"{host:.3g} B/s")
                else:
                    route, why = "host", (
                        f"pipeline floor {floor:.3g} B/s <= host "
                        f"{host:.3g} B/s")
        return self.note(stage, route, why, size_bytes=size_bytes)

    def note(self, stage: str, route: str, why: str,
             size_bytes: int | None = None) -> str:
        """Record — or CORRECT — a stage's decision (registry gauges
        + :attr:`decisions`).  Callers that end up on a different
        lane than the one routed (a failed fast lane falling back to
        the repair path, a bench remap) must call this so the
        recorded route is always the lane that actually ran — the
        whole point of the decision artifact is attribution."""
        decision = {"route": route, "why": why, "stage": stage}
        if size_bytes is not None:
            decision["size_bytes"] = int(size_bytes)
        elif stage in self.decisions \
                and "size_bytes" in self.decisions[stage]:
            decision["size_bytes"] = \
                self.decisions[stage]["size_bytes"]
        self.decisions[stage] = decision
        for r in ROUTES:
            _obs.registry.gauge("etcd_replay_backend_route",
                                stage=stage, route=r).set(
                1.0 if r == route else 0.0)
        return route

    def _env_route(self) -> tuple[str | None, str | None]:
        raw = os.environ.get(ENV_KNOB, "").strip().lower()
        if not raw:
            return None, None
        route = _ALIASES.get(raw, raw)
        if route not in ROUTES:
            log.warning("backend_policy: ignoring %s=%r (want one of "
                        "%s)", ENV_KNOB, raw, "/".join(ROUTES))
            return None, None
        return route, f"env {ENV_KNOB}={raw}"

    def snapshot(self) -> dict:
        """Probe numbers + per-stage decisions, JSON-ready (what
        chip_smoke.py checks for ``device_error`` and a ``host``
        route after a restart)."""
        out = {"chunk_bytes": self.chunk_bytes,
               "decisions": dict(self.decisions)}
        if self._probe is not None:
            out["probe"] = dict(self._probe)
        return out


# -- process-wide singleton ---------------------------------------------------

_policy: BackendPolicy | None = None
_policy_lock = threading.Lock()


def get_policy() -> BackendPolicy:
    """The process's router (probe runs once, on first routed call).
    ``ETCD_REPLAY_PROBE_CACHE`` names an optional on-disk cache file
    so short-lived processes (restart loops) skip re-probing."""
    global _policy
    with _policy_lock:
        if _policy is None:
            _policy = BackendPolicy(
                cache_path=os.environ.get("ETCD_REPLAY_PROBE_CACHE")
                or None)
        return _policy


def set_policy(p: BackendPolicy | None) -> None:
    """Swap (or, with None, reset) the process router — tests."""
    global _policy
    with _policy_lock:
        _policy = p


__all__ = [
    "BackendPolicy", "DEFAULT_CHUNK_BYTES", "ENV_KNOB", "ROUTES",
    "get_policy", "set_policy",
]
