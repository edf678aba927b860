"""wal/backend_policy: the measured per-stage replay router (PR 3).

The contract under test: env override wins; a probe failure or a
probed-slow accelerator can never route replay off the host path; the
probe is cached (in-process and, with a cache file, across restarts);
decisions are visible in ``GET /metrics``; and the server restart
seam actually consults the router.
"""

import json
import os

import numpy as np
import pytest

from etcd_tpu.obs import metrics as _obs
from etcd_tpu.wal import backend_policy
from etcd_tpu.wal.backend_policy import (
    ENV_KNOB,
    BackendPolicy,
    get_policy,
    set_policy,
)


def _fast_device():
    return {"h2d_bps": 1e12, "device_verify_bps": 1e12}


def _slow_device():
    return {"h2d_bps": 1e6, "device_verify_bps": 1e6}


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    monkeypatch.delenv(ENV_KNOB, raising=False)
    yield
    set_policy(None)  # never leak a test policy into other tests


# -- routing decisions --------------------------------------------------------


def test_fast_device_routes_stream():
    # frame-only host scan (the pipeline's leg) is faster than the
    # fused pass, and the device legs are faster still: streaming
    # sustains min(4e9, 1e12, 1e12) > the fused 1e9 -> stream
    p = BackendPolicy(probe_host=lambda: {"host_scan_bps": 1e9,
                                          "host_frame_bps": 4e9},
                      probe_device=_fast_device)
    assert p.route("replay") == "stream"
    assert p.decisions["replay"]["route"] == "stream"


def test_slow_device_probe_selects_host_route():
    """A PRESENT but slow accelerator must never regress replay
    below the host path."""
    p = BackendPolicy(probe_host=lambda: 1e9,
                      probe_device=_slow_device)
    assert p.route("restart") == "host"
    assert "<= host" in p.decisions["restart"]["why"]


def test_probe_failure_falls_back_to_host():
    def broken():
        raise RuntimeError("device unreachable")

    p = BackendPolicy(probe_host=lambda: 1e9, probe_device=broken)
    assert p.route("replay") == "host"
    assert "device unreachable" in p.probe()["device_error"]


def test_no_accelerator_routes_host():
    p = BackendPolicy(probe_host=lambda: 1e9,
                      probe_device=lambda: None)
    assert p.route("replay") == "host"
    assert p.decisions["replay"]["why"] == "no usable accelerator"


def test_env_override_wins(monkeypatch):
    """The operator knob beats the probe in BOTH directions."""
    monkeypatch.setenv(ENV_KNOB, "stream")
    slow = BackendPolicy(probe_host=lambda: 1e9,
                         probe_device=_slow_device)
    assert slow.route("replay") == "stream"  # probe said host
    monkeypatch.setenv(ENV_KNOB, "host")
    fast = BackendPolicy(probe_host=lambda: 1e9,
                         probe_device=_fast_device)
    assert fast.route("replay") == "host"    # probe said stream
    # aliases and junk
    monkeypatch.setenv(ENV_KNOB, "streaming-device")
    assert BackendPolicy(probe_host=lambda: 1e9,
                         probe_device=_slow_device) \
        .route("replay") == "stream"
    monkeypatch.setenv(ENV_KNOB, "warp-drive")
    assert BackendPolicy(probe_host=lambda: 1e9,
                         probe_device=_slow_device) \
        .route("replay") == "host"  # unknown value ignored, probed


def test_strict_device_forces_stream():
    p = BackendPolicy(probe_host=lambda: 1e9,
                      probe_device=_slow_device)
    assert p.route("restart", strict_device=True) == "stream"


# -- probe caching ------------------------------------------------------------


def test_probe_runs_once_in_process():
    calls = {"n": 0}

    def host():
        calls["n"] += 1
        return 1e9

    p = BackendPolicy(probe_host=host, probe_device=lambda: None)
    p.route("replay")
    p.route("restart")
    p.route("e2e")
    assert calls["n"] == 1


def test_probe_cache_reused_across_restarts(tmp_path):
    cache = str(tmp_path / "probe.json")
    calls = {"n": 0}

    def host():
        calls["n"] += 1
        return 123456789.0

    first = BackendPolicy(cache_path=cache, probe_host=host,
                          probe_device=lambda: None)
    first.route("restart")
    assert calls["n"] == 1 and os.path.exists(cache)
    # "restart": a fresh policy (new process) with the same cache
    second = BackendPolicy(cache_path=cache, probe_host=host,
                           probe_device=lambda: None)
    assert second.route("restart") == "host"
    assert calls["n"] == 1  # no re-probe
    assert second.probe()["source"] == "cache"
    assert second.probe()["host_scan_bps"] == 123456789.0


def test_corrupt_cache_reprobes(tmp_path):
    cache = tmp_path / "probe.json"
    cache.write_text("{not json")
    p = BackendPolicy(cache_path=str(cache),
                      probe_host=lambda: 1e9,
                      probe_device=lambda: None)
    assert p.route("replay") == "host"
    assert p.probe()["source"] == "probe"
    assert json.loads(cache.read_text())["probe"]["host_scan_bps"] \
        == 1e9


# -- observability ------------------------------------------------------------


def test_decision_visible_in_metrics_exposition():
    from etcd_tpu.obs.exporter import render_prometheus

    p = BackendPolicy(probe_host=lambda: 2e9,
                      probe_device=_slow_device)
    p.route("restart")
    text = render_prometheus().decode()
    assert ('etcd_replay_backend_route'
            '{route="host",stage="restart"} 1') in text \
        or ('etcd_replay_backend_route'
            '{stage="restart",route="host"} 1') in text
    assert 'etcd_replay_probe_bytes_per_sec{leg="host_scan"} ' in text
    gauge = _obs.registry.gauge("etcd_replay_backend_route",
                                stage="restart", route="stream")
    assert gauge.get() == 0.0


def test_snapshot_carries_probe_and_decisions():
    p = BackendPolicy(probe_host=lambda: 1e9,
                      probe_device=_fast_device, chunk_bytes=1 << 20)
    p.route("e2e", size_bytes=345 << 20)
    snap = p.snapshot()
    assert snap["chunk_bytes"] == 1 << 20
    assert snap["decisions"]["e2e"]["size_bytes"] == 345 << 20
    assert snap["probe"]["device_verify_bps"] == 1e12


def test_small_stream_routes_host_without_probing():
    """A tiny WAL restart must not initialize a jax backend (or pay
    any probe) just to learn what its size already says."""
    calls = {"n": 0}

    def dev():
        calls["n"] += 1
        return _fast_device()

    p = BackendPolicy(probe_host=lambda: {"host_scan_bps": 1e9,
                                          "host_frame_bps": 4e9},
                      probe_device=dev)
    assert p.route("restart", size_bytes=1 << 20) == "host"
    assert calls["n"] == 0
    assert "device threshold" in p.decisions["restart"]["why"]
    # a large stream DOES probe (and here, streams)
    assert p.route("restart", size_bytes=1 << 30) == "stream"
    assert calls["n"] == 1


def test_errored_probe_never_persisted(tmp_path):
    """A probe taken during a device outage must not pin the host
    route for every later restart via the cache file."""
    cache = str(tmp_path / "p.json")

    def broken():
        raise RuntimeError("device down")

    p = BackendPolicy(cache_path=cache, probe_host=lambda: 1e9,
                      probe_device=broken)
    assert p.route("replay") == "host"
    assert not os.path.exists(cache)


def test_stale_cache_reprobes(tmp_path):
    import time as _time

    cache = tmp_path / "p.json"
    cache.write_text(json.dumps({"version": 1, "probe": {
        "source": "probe", "ts_epoch": _time.time() - 48 * 3600,
        "host_scan_bps": 1.0, "host_frame_bps": 1.0,
        "h2d_bps": None, "device_verify_bps": None}}))
    calls = {"n": 0}

    def host():
        calls["n"] += 1
        return 1e9

    p = BackendPolicy(cache_path=str(cache), probe_host=host,
                      probe_device=lambda: None)
    p.route("replay")
    assert calls["n"] == 1  # expired cache ignored, re-probed
    assert p.probe()["source"] == "probe"


def test_note_corrects_decision_and_gauges():
    """A caller that lands on a different lane than routed (failed
    fast lane -> repair path) corrects the artifact."""
    p = BackendPolicy(probe_host=lambda: {"host_scan_bps": 1e9,
                                          "host_frame_bps": 4e9},
                      probe_device=_fast_device)
    assert p.route("restart", size_bytes=1 << 30) == "stream"
    p.note("restart", "host", "stream lane failed; host repair path")
    assert p.decisions["restart"]["route"] == "host"
    assert p.decisions["restart"]["size_bytes"] == 1 << 30  # kept
    assert _obs.registry.gauge("etcd_replay_backend_route",
                               stage="restart",
                               route="stream").get() == 0.0
    assert _obs.registry.gauge("etcd_replay_backend_route",
                               stage="restart",
                               route="host").get() == 1.0


# -- the restart seam ---------------------------------------------------------


def test_replay_wal_raw_routes_through_policy(tmp_path):
    """The server restart seam consults the router (stage "restart")
    and honors its host-route answer with the fused native lane."""
    from etcd_tpu import native
    from etcd_tpu.server.server import _replay_wal_raw
    from etcd_tpu.wal import WAL
    from etcd_tpu.wal.replay_device import EntryBlock
    from etcd_tpu.wire import Entry, HardState

    if not native.available():
        pytest.skip("native library unavailable")
    d = str(tmp_path / "wal")
    w = WAL.create(d, b"id-meta")
    w.save(HardState(term=1, vote=1, commit=3),
           [Entry(term=1, index=i, data=b"x" * 24) for i in range(4)])
    w.close()

    probe = BackendPolicy(probe_host=lambda: 1e9,
                          probe_device=_slow_device)
    set_policy(probe)
    w2, md, hs, out = _replay_wal_raw(d, 0, "auto")
    w2.close()
    assert md == b"id-meta"
    assert isinstance(out, EntryBlock)  # fused fast lane, not python
    dec = probe.decisions["restart"]
    assert dec["route"] == "host"
    assert dec["size_bytes"] > 0


def test_get_policy_is_a_singleton():
    set_policy(None)
    assert get_policy() is get_policy()


def test_default_probe_runs_on_this_host():
    """The real probe (no injection): native host leg measured, no
    device on the CPU-pinned test backend, host route chosen."""
    from etcd_tpu import native

    p = BackendPolicy()
    route = p.route("replay", size_bytes=1 << 20)
    assert route == "host"
    probe = p.probe()
    if native.available():
        assert probe["host_scan_bps"] > 0
    assert probe["device_verify_bps"] is None


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
