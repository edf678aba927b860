"""Observability subsystem (PR 2): registry/histogram exactness,
Prometheus exposition conformance, devledger accounting,
Tracer-facade backward compatibility, and the metrics-vocabulary lint
checker."""

import json
import re
import textwrap
import urllib.request
from collections import deque

import numpy as np
import pytest

from etcd_tpu.analysis import MetricsVocabularyChecker, run_checkers
from etcd_tpu.obs import exporter
from etcd_tpu.obs.devledger import DeviceLedger
from etcd_tpu.obs.metrics import (
    CATALOG,
    Registry,
    merge_histograms,
    percentile_from_buckets,
)

# -- 1. histogram bucket / percentile exactness ------------------------------


def test_histogram_percentiles_match_numpy_reference():
    reg = Registry()
    h = reg.histogram("etcd_wal_fsync_seconds")
    rng = np.random.default_rng(7)
    vals = rng.exponential(0.01, size=900)  # < window (1024): exact
    for v in vals:
        h.observe(float(v))
    ref = np.sort(vals)
    n = len(ref)
    for q in (0.5, 0.9, 0.99, 0.999):
        want = float(ref[min(n - 1, int(n * q))])
        assert h.percentile(q) == pytest.approx(want, rel=0, abs=0)
    snap = h.snapshot()
    assert snap["count"] == n
    assert snap["sum"] == pytest.approx(float(vals.sum()))
    assert snap["max"] == pytest.approx(float(vals.max()))
    assert snap["p50"] == h.percentile(0.5)


def test_histogram_buckets_match_numpy_histogram():
    reg = Registry()
    h = reg.histogram("etcd_wal_fsync_seconds")
    bounds = list(h.bounds)
    rng = np.random.default_rng(3)
    vals = rng.uniform(0, 12.0, size=2000)
    for v in vals:
        h.observe(float(v))
    # le semantics: bucket i counts bounds[i-1] < v <= bounds[i]
    edges = [-np.inf] + bounds + [np.inf]
    want, _ = np.histogram(vals, bins=edges)
    # np.histogram bins are half-open [lo, hi); flip to (lo, hi] by
    # counting exact-boundary hits (measure zero for uniform floats,
    # so the distributions agree)
    assert h.snapshot()["buckets"] == want.tolist()
    assert sum(h.snapshot()["buckets"]) == 2000


def test_catalog_rejects_unknown_names_and_label_mismatch():
    reg = Registry()
    with pytest.raises(KeyError):
        reg.counter("etcd_not_a_metric_total")
    with pytest.raises(TypeError):
        reg.counter("etcd_wal_fsync_seconds")  # histogram, not ctr
    with pytest.raises(TypeError):
        reg.histogram("etcd_span_seconds")  # missing span label


def test_bucket_percentile_merge_across_processes():
    reg = Registry()
    a = reg.histogram("etcd_ack_rtt_seconds")
    b = Registry().histogram("etcd_ack_rtt_seconds")
    for v in (0.002,) * 50:
        a.observe(v)
    for v in (0.2,) * 50:
        b.observe(v)
    merged = merge_histograms([a.snapshot(), b.snapshot()])
    assert merged["count"] == 100
    p50 = percentile_from_buckets(merged["bounds"],
                                  merged["buckets"], 0.5)
    p99 = percentile_from_buckets(merged["bounds"],
                                  merged["buckets"], 0.99)
    assert p50 <= 0.0025  # the le bound holding 0.002
    assert 0.2 <= p99 <= 0.25


# -- 2. /metrics exposition-format conformance -------------------------------

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")


def test_exposition_covers_catalog_and_is_well_formed():
    reg = Registry()
    reg.counter("etcd_wal_append_entries_total").inc(3)
    reg.histogram("etcd_wal_fsync_seconds").observe(0.004)
    text = exporter.render_prometheus(reg).decode()
    types = dict(re.findall(r"# TYPE (\S+) (\S+)", text))
    # every catalog family is announced, even sampleless ones
    assert set(types) == set(CATALOG)
    assert len(types) >= 10
    for name, kind in types.items():
        assert _NAME_RE.match(name)
        assert kind in ("counter", "gauge", "histogram")
    # the acceptance span: wal, apply, election, peer-send, ack-RTT,
    # devledger are all families
    for needle in ("etcd_wal_fsync_seconds", "etcd_apply_seconds",
                   "etcd_election_campaigns_total",
                   "etcd_peer_send_seconds", "etcd_ack_rtt_seconds",
                   "etcd_devledger_dispatches_total"):
        assert needle in types
    # histogram structure: cumulative buckets, +Inf terminal, sum,
    # count
    assert 'etcd_wal_fsync_seconds_bucket{le="0.005"} 1' in text
    assert 'etcd_wal_fsync_seconds_bucket{le="+Inf"} 1' in text
    assert "etcd_wal_fsync_seconds_count 1" in text
    assert "etcd_wal_append_entries_total 3" in text
    cums = [int(m) for m in re.findall(
        r'etcd_wal_fsync_seconds_bucket\{le="[^"]+"\} (\d+)', text)]
    assert cums == sorted(cums)  # cumulative by definition


def test_exposition_escaping():
    reg = Registry()
    evil = 'sp"an\\with\nnewline'
    reg.histogram("etcd_span_seconds", span=evil).observe(0.001)
    text = exporter.render_prometheus(reg).decode()
    assert 'span="sp\\"an\\\\with\\nnewline"' in text
    # every line is a comment or a sample — a raw newline inside a
    # label value would break this shape
    sample_re = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? \S+$")
    for line in text.splitlines():
        assert line.startswith("#") or sample_re.match(line), line
    # HELP escaping helper contract
    assert exporter.escape_help("a\\b\nc") == "a\\\\b\\nc"
    assert exporter.escape_label_value('a"b') == 'a\\"b'


def test_metrics_endpoint_on_client_api(tmp_path):
    from etcd_tpu.api.http import make_client_handler, serve
    from etcd_tpu.server.multigroup import MultiGroupServer
    from etcd_tpu.wire.requests import Request

    s = MultiGroupServer(str(tmp_path / "d"), g=4, m=3, cap=32,
                         tick_interval=0.02)
    s.start()
    httpd = serve(make_client_handler(s), "127.0.0.1", 0)
    try:
        s.do(Request(id=77, method="PUT", path="/t/k", val="v"),
             timeout=90)
        port = httpd.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics",
                timeout=30) as resp:
            assert resp.status == 200
            ctype = resp.headers["Content-Type"]
            text = resp.read().decode()
        assert ctype.startswith("text/plain")
        assert "version=0.0.4" in ctype
        types = dict(re.findall(r"# TYPE (\S+) (\S+)", text))
        assert len(types) >= 10
        # a real serving round has recorded wal + apply samples
        m = re.search(r"etcd_wal_fsync_seconds_count (\d+)", text)
        assert m and int(m.group(1)) >= 1
        m = re.search(r"etcd_apply_batch_entries_count (\d+)", text)
        assert m and int(m.group(1)) >= 1
        # spans ride /metrics too (Tracer facade)
        assert 'etcd_span_seconds_bucket{span="mg.persist"' in text
    finally:
        httpd.shutdown()
        s.stop()


# -- 4. devledger on a fake-dispatch fixture ----------------------------------


def test_devledger_counts_fake_dispatches():
    reg = Registry()
    led = DeviceLedger(reg)
    rows = np.zeros((128, 64), np.uint8)
    out = np.ones(128, bool)
    for _ in range(3):
        led.h2d("fake.stage", rows)
        with led.dispatch("fake.stage"):
            pass  # the "jitted call"
        got = led.fetch("fake.stage", out)
        assert isinstance(got, np.ndarray)
    snap = led.snapshot()["fake.stage"]
    assert snap["dispatches"] == 3
    assert snap["h2d_bytes"] == 3 * rows.nbytes
    assert snap["d2h_bytes"] == 3 * out.nbytes
    assert snap["dispatch_seconds"] >= 0
    assert snap["block_seconds"] >= 0
    # the same numbers ride the registry's exporter families
    text = exporter.render_prometheus(reg).decode()
    assert ('etcd_devledger_dispatches_total{stage="fake.stage"} 3'
            in text)
    assert (f'etcd_devledger_h2d_bytes_total{{stage="fake.stage"}} '
            f"{3 * rows.nbytes}" in text)


def test_devledger_instruments_multiraft_round():
    from etcd_tpu.obs.devledger import ledger
    from etcd_tpu.raft.multiraft import MultiRaft

    before = ledger.snapshot().get("multiraft.round",
                                   {}).get("dispatches", 0)
    mr = MultiRaft(g=4, m=3, cap=16)
    mr.campaign(0)
    mr.propose(np.ones(4, np.int32))
    after = ledger.snapshot()["multiraft.round"]
    assert after["dispatches"] > before
    assert after["d2h_bytes"] > 0


def test_devledger_instruments_replay_verify(tmp_path):
    from etcd_tpu.obs.devledger import ledger
    from etcd_tpu.wal import WAL
    from etcd_tpu.wal.replay_device import read_all_device
    from etcd_tpu.wire import Entry, HardState
    from etcd_tpu.wire.requests import Info

    w = WAL.create(str(tmp_path / "wal"), Info(id=1).marshal())
    w.save(HardState(term=1, vote=0, commit=1),
           [Entry(index=0, term=1, data=b"x" * 100),
            Entry(index=1, term=1, data=b"y" * 100)])
    w.close()
    before = ledger.snapshot().get("replay.verify", {})
    _md, _st, block = read_all_device(str(tmp_path / "wal"))
    assert len(block) == 2
    after = ledger.snapshot().get("replay.verify", {})
    # on the CPU backend the native sequential lane may serve the
    # verify (no device dispatch); when the batched lane ran, the
    # ledger must have seen it
    if after:
        assert after.get("dispatches", 0) >= before.get(
            "dispatches", 0)


# -- 5. Tracer facade: /v2/stats/spans backward compatibility -----------------


def test_tracer_snapshot_byte_stable_vs_legacy_impl():
    """The facade must reproduce the pre-PR-2 deque implementation
    byte for byte (same window, index rule, rounding, key set)."""
    from etcd_tpu.utils.trace import Tracer

    rng = np.random.default_rng(11)
    vals = rng.exponential(0.003, size=700)  # > window: ring wraps
    t = Tracer()
    legacy_ring: deque = deque(maxlen=256)
    cnt, tot, mx = 0, 0.0, 0.0
    for v in vals:
        v = float(v)
        t.record("seam", v)
        cnt += 1
        tot += v
        mx = max(mx, v)
        legacy_ring.append(v)
    ring = sorted(legacy_ring)
    legacy = {"seam": {
        "count": cnt,
        "total_ms": round(tot * 1e3, 3),
        "mean_ms": round(tot / cnt * 1e3, 3),
        "p50_ms": round(ring[len(ring) // 2] * 1e3, 3),
        "p99_ms": round(
            ring[min(len(ring) - 1, int(len(ring) * 0.99))] * 1e3,
            3),
        "max_ms": round(mx * 1e3, 3),
    }}
    assert t.snapshot() == legacy
    assert t.snapshot_json() == (
        json.dumps(legacy, sort_keys=True) + "\n").encode()
    t.reset()
    assert t.snapshot() == {}


def test_tracer_spans_land_in_metrics_registry():
    from etcd_tpu.obs.metrics import registry
    from etcd_tpu.utils.trace import tracer

    tracer.record("obs.test.span", 0.002)
    hist = registry.histogram("etcd_span_seconds",
                              span="obs.test.span")
    assert hist.count >= 1


# -- 6. metrics-vocabulary lint checker ---------------------------------------


def _fixture_root(tmp_path, relpath, body):
    p = tmp_path / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(body))
    return str(tmp_path)


def test_metricsvocab_fires_on_unregistered_and_dynamic(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/x.py", """
        from etcd_tpu.obs.metrics import registry

        def f(name):
            registry.counter("etcd_bogus_total").inc()
            registry.histogram(name).observe(1)
    """)
    findings = run_checkers(root, [MetricsVocabularyChecker()])
    rules = {f.rule for f in findings}
    assert rules == {"unregistered-metric", "dynamic-metric-name"}
    assert any(f.detail == "etcd_bogus_total" for f in findings)


def test_metricsvocab_quiet_on_catalog_names(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/x.py", """
        from etcd_tpu.obs.metrics import registry

        def f():
            registry.counter("etcd_wal_append_entries_total").inc()
            registry.histogram("etcd_span_seconds",
                               span="a").observe(1)
    """)
    assert run_checkers(root, [MetricsVocabularyChecker()]) == []


def test_metricsvocab_ignores_unrelated_receivers(tmp_path):
    root = _fixture_root(tmp_path, "etcd_tpu/x.py", """
        def f(obj):
            obj.counter("whatever")      # not registry-ish
            obj.histogram(3)             # not a metric call
    """)
    assert run_checkers(root, [MetricsVocabularyChecker()]) == []
