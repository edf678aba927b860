"""Cluster observability plane (PR 17): time-series ring deltas and
windowed queries, SLO burn-rate math, sampling-profiler
attribution."""

import threading
import time

import pytest

from etcd_tpu.obs import profiler, slo, timeseries
from etcd_tpu.obs.metrics import CATALOG, Registry

# -- 1. time-series ring: deltas, retention, restart, queries ---------------


def test_timeseries_counter_deltas_and_rate():
    reg = Registry()
    c = reg.counter("etcd_wal_append_entries_total")
    c.inc(10)
    ts = timeseries.TimeSeries(reg, step=1.0)
    ts.step_once()
    snap = ts.snapshot()
    assert len(snap["steps"]) == 1
    fam, labels, d = snap["steps"][0]["counters"][0]
    assert (fam, labels, d) == ("etcd_wal_append_entries_total",
                                {}, 10.0)
    # exactly one step in the ring -> span == its dt == step_s, so
    # the windowed rate is exact
    assert ts.rate("etcd_wal_append_entries_total",
                   window_s=10.0) == pytest.approx(10.0 / 1.0)
    c.inc(7)
    ts.step_once()
    steps = ts.snapshot()["steps"]
    assert steps[1]["counters"][0][2] == 7.0  # delta, not total


def test_timeseries_restart_resets_to_fresh_delta():
    vals = iter([100.0, 40.0])  # cumulative moves BACKWARD: respawn

    def source():
        return {"etcd_wal_append_entries_total": {
            "kind": "counter",
            "samples": [{"labels": {}, "value": next(vals)}]}}

    ts = timeseries.TimeSeries(source)
    ts.step_once()
    ts.step_once()
    steps = ts.snapshot()["steps"]
    assert steps[0]["counters"][0][2] == 100.0
    # the new incarnation's value IS the delta — never negative
    assert steps[1]["counters"][0][2] == 40.0


def test_timeseries_retention_drops_oldest():
    reg = Registry()
    c = reg.counter("etcd_wal_append_entries_total")
    ts = timeseries.TimeSeries(reg, retention=3)
    for i in range(5):
        c.inc(i + 1)
        ts.step_once()
    steps = ts.snapshot()["steps"]
    assert len(steps) == 3
    # steps 1 and 2 (deltas 2, 3) were dropped; 3..5 remain
    assert [st["counters"][0][2] for st in steps] == [3.0, 4.0, 5.0]


def test_timeseries_rejects_unknown_family():
    ts = timeseries.TimeSeries(Registry())
    with pytest.raises(KeyError):
        ts.rate("etcd_not_a_metric_total")


def test_timeseries_windowed_percentile_is_bucket_upper_bound():
    reg = Registry()
    h = reg.histogram("etcd_ack_rtt_seconds")
    for _ in range(100):
        h.observe(0.004)
    ts = timeseries.TimeSeries(reg)
    ts.step_once()
    bounds = list(CATALOG["etcd_ack_rtt_seconds"].buckets)
    want = min(b for b in bounds if b >= 0.004)
    assert ts.percentile("etcd_ack_rtt_seconds",
                         0.99) == pytest.approx(want)
    hist = ts.windowed_hist("etcd_ack_rtt_seconds")
    assert hist["count"] == 100
    assert hist["sum"] == pytest.approx(0.4)


def _mk_snap(steps):
    """Hand-built ring snapshot: deterministic dt for exact rate
    math in the pure cross-node helpers."""
    return {"step_s": 1.0, "retention": 120, "now": 0.0,
            "steps": steps}


def test_snap_rate_and_windowed_summary_cross_node():
    bounds = list(CATALOG["etcd_ack_rtt_seconds"].buckets)
    db = [0] * (len(bounds) + 1)
    db[0] = 10  # 10 acks in the fastest bucket per step
    steps = [{"t": 0.0, "dt": 2.0, "counters": [], "gauges": [],
              "hists": [["etcd_ack_rtt_seconds", {}, 10, 0.01, db]]}
             for _ in range(5)]
    snap = _mk_snap(steps)
    # 5 steps x dt=2.0 cover the 10 s window exactly: 50 acks / 10 s
    assert timeseries.snap_rate(
        [snap], "etcd_ack_rtt_seconds",
        10.0) == pytest.approx(5.0)
    # two nodes: rates SUM, span does not double
    assert timeseries.snap_rate(
        [snap, snap], "etcd_ack_rtt_seconds",
        10.0) == pytest.approx(10.0)
    w = timeseries.windowed_summary([snap])
    assert w["acked_per_s_10s"] == pytest.approx(5.0)
    assert w["ack_rtt_p99_ms_60s"] == pytest.approx(bounds[0] * 1e3)
    assert w["estimator"] == "bucket-le-upper-bound"


# -- 2. SLO burn rates ------------------------------------------------------


def _latency_snap(family, bucket_counts):
    bounds = list(CATALOG[family].buckets)
    db = [0] * (len(bounds) + 1)
    for i, n in bucket_counts.items():
        db[i] = n
    return _mk_snap([{
        "t": 0.0, "dt": 1.0, "counters": [], "gauges": [],
        "hists": [[family, {}, sum(db), 0.0, db]]}])


def test_slo_latency_burning_and_ok():
    # all 100 acks in the overflow bucket: every one above the
    # 500 ms target, bad fraction 1.0, allowed 1 - q = 0.01
    bounds = list(CATALOG["etcd_ack_rtt_seconds"].buckets)
    snap = _latency_snap("etcd_ack_rtt_seconds",
                         {len(bounds): 100})
    v = slo.evaluate([snap])
    o = v["objectives"]["write_ack_p99"]
    assert o["burn_rate"] == pytest.approx(100.0)
    assert not o["ok"]
    assert v["verdict"] == "burning"
    assert v["worst"] == "write_ack_p99"
    # all acks in the fastest bucket: zero bad, burn 0, verdict ok
    snap = _latency_snap("etcd_ack_rtt_seconds", {0: 100})
    v = slo.evaluate([snap])
    assert v["objectives"]["write_ack_p99"]["burn_rate"] == 0.0
    assert v["objectives"]["write_ack_p99"]["ok"]
    assert v["verdict"] == "ok"  # sampled, nothing burning


def test_slo_ratio_burn_math():
    # 90 admits / 10 sheds over one 1 s step: bad fraction 0.1
    # against the 5% budget -> burn 2.0
    snap = _mk_snap([{
        "t": 0.0, "dt": 1.0, "hists": [], "gauges": [],
        "counters": [
            ["etcd_admission_total", {"outcome": "admit"}, 90.0],
            ["etcd_admission_total", {"outcome": "shed"}, 10.0]]}])
    v = slo.evaluate([snap])
    o = v["objectives"]["shed_rate"]
    assert o["bad_fraction"] == pytest.approx(0.1)
    assert o["burn_rate"] == pytest.approx(2.0)
    assert not o["ok"]


def test_slo_no_data_verdict_and_gauge_export():
    reg = Registry()
    v = slo.evaluate([_mk_snap([])], registry=reg)
    assert v["verdict"] == "no_data"
    # an idle objective is vacuously met, and the gauges exported
    snap = reg.snapshot()
    objs = {s["labels"]["objective"]: s["value"]
            for s in snap["etcd_slo_ok"]["samples"]}
    assert objs["write_ack_p99"] == 1.0
    assert "write_ack_p99" in {
        s["labels"]["objective"]
        for s in snap["etcd_slo_burn_rate"]["samples"]}


def test_slo_merge_verdicts_worst_of():
    ok = {"verdict": "ok", "objectives": {
        "write_ack_p99": {"burn_rate": 0.1, "ok": True}}}
    burn = {"verdict": "burning", "objectives": {
        "write_ack_p99": {"burn_rate": 7.0, "ok": False}}}
    m = slo.merge_verdicts([ok, burn])
    assert m["verdict"] == "burning"
    assert m["worst"] == "write_ack_p99"
    assert m["objectives"]["write_ack_p99"]["burn_rate"] == 7.0


# -- 3. sampling profiler ---------------------------------------------------


def test_profiler_attributes_stage_and_domain():
    from etcd_tpu.utils.trace import tracer

    reg = Registry()
    p = profiler.Profiler(registry=reg)
    hold = threading.Event()
    inside = threading.Event()

    def worker():
        with tracer.stage("replay.verify"):
            inside.set()
            hold.wait(5)

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    assert inside.wait(5)
    try:
        n = p.sample_once()
        assert n >= 1
    finally:
        hold.set()
        t.join()
    stages = {s["labels"]["stage"]
              for s in reg.snapshot()[
                  "etcd_profile_samples_total"]["samples"]}
    assert "replay.verify" in stages


def test_profiler_domain_roots_speak_ownership_vocabulary():
    from etcd_tpu.analysis.ownership import DOMAINS

    roots = profiler._domain_roots()
    assert roots, "ownership registry produced no roots"
    assert set(roots.values()) <= set(DOMAINS)
    # a known owner root resolves to its domain
    assert roots[("frontdoor.py", "_run")] == "frontdoor-loop"
