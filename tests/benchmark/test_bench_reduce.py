"""The yardstick: trace reduction on a small recorded trace, the work
functions against hand-worked numbers, the peaks and the readers."""

import json
import os

import pytest

import bench_fixtures  # noqa: F401 - puts benchmark/ on sys.path

import bench_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


def test_trace_reduction_busy_idle_named_operation_and_gaps(recorded):
    """``recorded_trace.json``: one chip, 1 ms in all (host events span
    0 .. 1 000 000 ns).  Device: gather 100k-300k, gather 250k-400k
    (overlaps: the union counts 100k-400k once), crc 600k-700k.  Busy
    400 us, idle 60 %.  Gaps: 400k-600k (host: np.asarray covers it),
    700k-1000k (host: PjitFunction covers two thirds), 0-100k (nothing
    but the long run-loop event)."""
    r = bench_reduce.reduce_events(
        recorded, {"crc": "crc", "gathers": r"^fusion\.\d+ gather",
                   "absent": "nothing-like-this"})
    assert r["busy_s"] == pytest.approx(400e-6)
    assert r["window_s"] == pytest.approx(1000e-6)
    assert r["ops"]["crc"] == {"seconds": pytest.approx(100e-6), "count": 1}
    assert r["ops"]["gathers"] == {"seconds": pytest.approx(350e-6),
                                   "count": 2}
    assert "absent" not in r["ops"]
    assert r["device_ops"][0] == ["fusion.1 gather s32[320000]",
                                  pytest.approx(200e-6)]
    assert [[n, round(g * 1e6)] for n, g in r["idle_gaps"]] == [
        ["PjitFunction(_fused_round_hot)", 300],
        ["np.asarray(jax.Array)", 200],
        ["round_loop", 100]]
    spec = {"kind": "trace", "stat": "idle_share"}
    assert bench_reduce.read_metric(spec, {"trace": r}) == pytest.approx(60.0)


def test_trace_without_device_operations_names_nothing():
    r = bench_reduce.reduce_events(
        {"device": [], "host": [["x", 0, 10]]}, {})
    assert r["busy_s"] is None
    for stat in ("idle_share", "ms_per", "roofline"):
        assert bench_reduce.read_metric(
            {"kind": "trace", "stat": stat}, {"trace": r}) is None
    assert bench_reduce.read_metric(
        {"kind": "trace", "stat": "idle_share"}, {"trace": None}) is None


def test_two_chips_average(recorded):
    two = {"host": recorded["host"],
           "device": recorded["device"]
           + [["/device:TPU:1", "fusion.9", 0, 200000]]}
    r = bench_reduce.reduce_events(two, {})
    assert r["busy_s"] == pytest.approx((400e-6 + 200e-6) / 2)


def test_engine_round_work_by_hand():
    # 10000 groups x 6 slots x (9 + 2) int32, read and written once:
    # 2 x 2 640 000 B; 16 entries x 6 slots x 4 B of terms
    w = bench_reduce.engine_round_work(groups=10000, slots=6, entries=16)
    assert w == {"bytes": 5_280_000 + 384, "int8_ops": 0.0}
    least, bound = bench_reduce.least_seconds(
        w, bench_reduce.peaks_of("TPU v5 lite"))
    assert bound == "hbm" and least == pytest.approx(5_280_384 / 819e9)


def test_crc_verify_work_by_hand():
    w = bench_reduce.crc_verify_work(16_000_000)
    assert w == {"bytes": 16e6, "int8_ops": 128e6}
    least, bound = bench_reduce.least_seconds(
        w, bench_reduce.peaks_of("TPU v5 lite"))
    # 16 MB / 819 GB/s = 19.5 us against 128 Mop / 393 Top/s = 0.33 us
    assert bound == "hbm" and least == pytest.approx(16e6 / 819e9)


def test_roofline_share_over_100_raises_and_is_never_clipped():
    assert bench_reduce.roofline_share(1e-6, 4e-6) == pytest.approx(25.0)
    with pytest.raises(ValueError, match="over 100"):
        bench_reduce.roofline_share(4.1e-6, 4e-6)


def test_unknown_device_is_an_error():
    assert bench_reduce.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        bench_reduce.peaks_of("cpu")


SNAP0 = {
    "etcd_stage_seconds": {"samples": [
        {"labels": {"stage": "mg.consensus_round", "kind": "wall"},
         "count": 10, "sum": 1.0},
        {"labels": {"stage": "mg.persist", "kind": "wall"},
         "count": 10, "sum": 0.01}]},
    "etcd_apply_batch_entries": {"samples": [
        {"labels": {}, "count": 10, "sum": 100.0}]},
    "etcd_admission_total": {"samples": [
        {"labels": {"outcome": "admit", "reason": "ok"}, "value": 100.0}]},
}
SNAP1 = {
    "etcd_stage_seconds": {"samples": [
        {"labels": {"stage": "mg.consensus_round", "kind": "wall"},
         "count": 30, "sum": 3.4},
        {"labels": {"stage": "mg.persist", "kind": "wall"},
         "count": 30, "sum": 0.03}]},
    "etcd_apply_batch_entries": {"samples": [
        {"labels": {}, "count": 30, "sum": 420.0}]},
    "etcd_admission_total": {"samples": [
        {"labels": {"outcome": "admit", "reason": "ok"}, "value": 500.0},
        {"labels": {"outcome": "shed_write", "reason": "queue_depth"},
         "value": 3.0}]},
}


def _spec(name):
    with open(os.path.join(HERE, "..", "..", "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return dict(json.load(f), name=name)


@pytest.mark.parametrize("name,want", [
    ("engine_round_ms", 120.0),        # (3.4 - 1.0) s / 20 rounds
    ("persist_ms", 1.0),
    ("entries_per_round", 16.0),       # 320 entries / 20 rounds
    ("frontdoor_sheds", 3.0),
])
def test_registry_readers_take_the_window_s_delta(name, want):
    ctx = {"registry": {"window": (SNAP0, SNAP1)}}
    assert bench_reduce.read_metric(_spec(name), ctx) == pytest.approx(want)


def test_registry_reader_without_a_registry_returns_nothing():
    ctx = {"registry": {"window": (None, None)}}
    assert bench_reduce.read_metric(_spec("engine_round_ms"), ctx) is None
    # a stage that never ran: no rounds to divide by
    ctx = {"registry": {"window": (SNAP1, SNAP1)}}
    assert bench_reduce.read_metric(_spec("engine_round_ms"), ctx) is None
    assert bench_reduce.read_metric(_spec("frontdoor_sheds"), ctx) == 0.0


def test_engine_roofline_and_busy_per_round_from_a_trace():
    trace = {"busy_s": 2.0, "window_s": 3.0, "ops": {}}
    ctx = {"trace": trace, "registry": {"trace": (SNAP0, SNAP1)},
           "facts": {"groups": 10000, "slots": 6},
           "device_kind": "TPU v5 lite"}
    assert bench_reduce.read_metric(
        _spec("device_busy_ms_per_round"), ctx) == pytest.approx(100.0)
    want = 100 * (5_280_384 / 819e9) / 0.1
    assert bench_reduce.read_metric(
        _spec("engine_roofline"), ctx) == pytest.approx(want)
    ctx["device_kind"] = "TPU v9"
    with pytest.raises(KeyError):
        bench_reduce.read_metric(_spec("engine_roofline"), ctx)


def test_crc_roofline_reads_bytes_from_the_log_and_time_from_the_kernel():
    log = ("2026-01-01 00:00:00,000 x etcdserver: stream-route replay of "
           "3190 entries (16000000 bytes, 2 segments)")
    trace = {"busy_s": 1.0, "window_s": 30.0,
             "ops": {"crc_roofline": {"seconds": 0.002, "count": 4}}}
    ctx = {"trace": trace, "registry": {}, "facts": {}, "log_text": log,
           "device_kind": "TPU v5 lite"}
    want = 100 * (16e6 / 819e9) / 0.002
    assert bench_reduce.read_metric(
        _spec("crc_roofline"), ctx) == pytest.approx(want)
    trace["ops"] = {}                   # the kernel is not in the trace
    assert bench_reduce.read_metric(_spec("crc_roofline"), ctx) is None


def test_log_readers():
    log = ("2026-09-30 07:00:10,500 etcd_tpu.cli: Listening for client "
           "requests on http://127.0.0.1:1 (10000 co-hosted groups x 5 "
           "members)\n2026-09-30 07:00:05,000 etcd_tpu.server.server: "
           "etcdserver: stream-route replay of 10 entries (5 bytes)\n")
    import datetime
    t = datetime.datetime(2026, 9, 30, 7, 0, 0).timestamp()
    ctx = {"log_text": log, "t_signal_wall": t}
    assert bench_reduce.read_metric(
        _spec("restart_listen_s"), ctx) == pytest.approx(10.5)
    assert bench_reduce.read_metric(_spec("replay_route_stream"), ctx) == 1.0
    ctx["log_text"] = "host-route replay"
    assert bench_reduce.read_metric(_spec("replay_route_stream"), ctx) == 0.0
    assert bench_reduce.read_metric(_spec("restart_listen_s"), ctx) is None


def test_reduction_of_a_trace_recorded_on_the_chip_against_brute_force():
    """``recorded_tpu_trace.json``: 240 ms of a real v5e trace.  Busy
    and the longest gap are worked out again on a 1 us raster."""
    import numpy as np

    with open(os.path.join(HERE, "recorded_tpu_trace.json")) as f:
        ev = json.load(f)
    r = bench_reduce.reduce_events(ev, {"gathers": r"s32\[320000\]"})
    ends = [s + d for _, _, s, d in ev["device"]] \
        + [s + d for _, s, d in ev["host"]]
    starts = [s for _, _, s, _ in ev["device"]] + [s for _, s, _ in ev["host"]]
    t0, t1 = min(starts), max(ends)
    raster = np.zeros((t1 - t0) // 1000 + 1, bool)
    for _, _, s, d in ev["device"]:
        raster[(s - t0) // 1000:(s + d - t0) // 1000 + 1] = True
    assert r["window_s"] == pytest.approx((t1 - t0) / 1e9)
    assert r["busy_s"] == pytest.approx(raster.sum() / 1e6, rel=0.01)
    idle = np.flatnonzero(np.diff(np.concatenate(
        [[True], raster, [True]]).astype(int)))
    longest = max(b - a for a, b in zip(idle[::2], idle[1::2]))
    assert r["idle_gaps"][0][1] == pytest.approx(longest / 1e6, rel=0.01)
    # what the host did in the four gaps over 10 ms: fetching the round's
    # result, dispatching the next round (twice), completing callbacks
    assert sorted(n for n, g in r["idle_gaps"] if g > 0.01) == [
        "CompleteCallbacks", "PjitFunction(_fused_round_hot)",
        "PjitFunction(_fused_round_hot)", "np.asarray(jax.Array)"]
    gathers = [d for _, n, _, d in ev["device"] if "s32[320000]" in n]
    assert r["ops"]["gathers"]["count"] == len(gathers) > 100
    assert r["ops"]["gathers"]["seconds"] == pytest.approx(sum(gathers) / 1e9)
    assert 70 < 100 * r["busy_s"] / r["window_s"] < 75
