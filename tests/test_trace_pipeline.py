"""End-to-end proposal tracing, stage attribution and the flight
recorder (PR 8): ring semantics, wire round-trip, head/tail
sampling through a real 3-host cluster, cross-node stitching, and
the SIGTERM crash dump."""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import bootstrap_dist_leader, make_dist_cluster
from etcd_tpu.obs.flight import FlightRecorder, install_crash_dump
from etcd_tpu.obs.metrics import Registry
from etcd_tpu.wire.requests import Request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

import trace_stitch  # noqa: E402

_NEXT_ID = [1 << 20]


def rid() -> int:
    _NEXT_ID[0] += 1
    return _NEXT_ID[0]


# -- ring semantics ---------------------------------------------------------


def test_ring_overflow_drops_oldest_with_accounting():
    reg = Registry()
    f = FlightRecorder(node="t", slot=0, capacity=8, sample=0,
                       registry=reg)
    for i in range(20):
        f.record("span", n=i)
    ev = f.events()
    # oldest dropped, newest kept, allocation order preserved
    assert [e["n"] for e in ev] == list(range(12, 20))
    assert f.dropped() == 12
    assert reg.counter("etcd_trace_drop_total",
                       reason="ring_overflow").get() == 12
    assert reg.counter("etcd_flight_events_total",
                       **{"class": "span"}).get() == 20


def test_head_sampling_rate_and_disable():
    reg = Registry()
    f = FlightRecorder(capacity=16, sample=4, registry=reg)
    ids = [f.sample_trace() for _ in range(16)]
    assert sum(1 for t in ids if t is not None) == 4
    off = FlightRecorder(capacity=16, sample=0, registry=reg)
    assert all(off.sample_trace() is None for _ in range(8))


def test_dump_is_json_roundtrippable():
    reg = Registry()
    f = FlightRecorder(node="n0", slot=0, capacity=8, sample=2,
                       registry=reg)
    f.record("election", fired=3, won=2)
    d = json.loads(f.dump_json())
    assert d["node"] == "n0" and d["slot"] == 0
    assert d["events"][0]["c"] == "election"
    assert "mono_anchor" in d and "wall_anchor" in d


# -- wire: the versioned DGB2 trace block -----------------------------------


def _frame(g=3, trace=None):
    from etcd_tpu.wire.distmsg import AppendBatch

    return AppendBatch(
        sender=1, term=np.ones(g, np.int32),
        prev_idx=np.zeros(g, np.int32),
        prev_term=np.zeros(g, np.int32),
        n_ents=np.asarray([2, 0, 1], np.int32),
        commit=np.zeros(g, np.int32),
        active=np.ones(g, bool), need_snap=np.zeros(g, bool),
        ent_terms=np.ones((g, 2), np.int32),
        payloads=[[b"aa", b"bb"], [], [b"c"]],
        seq=7, epoch=3, trace=trace)


def test_trace_block_roundtrips_through_dgb2():
    from etcd_tpu.wire.distmsg import FLAG_TRACE, unmarshal_any

    tr = [(0, 1, 0xDEADBEEF, 2), (2, 1, 7, 0)]
    wire = bytes(_frame(trace=tr).marshal())
    assert int.from_bytes(wire[6:8], "little") & FLAG_TRACE
    back = unmarshal_any(wire)
    assert back.trace == tr
    assert back.payloads[0] == [b"aa", b"bb"]
    assert bytes(back.marshal()) == wire  # re-encode byte-stable


def test_untraced_frame_is_byte_identical_to_pretrace_layout():
    """flags=0 and NO trailing block: old peers parse a new sender's
    untraced frames bit-for-bit as before, and a traced frame's
    trailing block is invisible to a parser that stops at the
    payload table (structural versioning)."""
    from etcd_tpu.wire.distmsg import _TRACE_ENT, unmarshal_any

    plain = bytes(_frame(trace=None).marshal())
    assert plain[6:8] == b"\x00\x00"
    traced = bytes(_frame(trace=[(0, 1, 5, 1)]).marshal())
    # same prefix; the trace block is purely additive at the tail
    assert traced[8:] [:len(plain) - 8] == plain[8:]
    assert len(traced) == len(plain) + 4 + _TRACE_ENT.size
    # absence parses as today
    assert unmarshal_any(plain).trace is None


def test_flipped_trace_flag_fails_typed():
    """A bit flip that sets FLAG_TRACE on an untraced frame must
    surface as FrameError (decoder totality), not IndexError."""
    from etcd_tpu.wire.distmsg import FrameError, unmarshal_any

    wire = bytearray(_frame(trace=None).marshal())
    wire[6] |= 0x01
    with pytest.raises(FrameError):
        unmarshal_any(bytes(wire))


def test_truncated_trace_block_fails_typed():
    from etcd_tpu.wire.distmsg import FrameError, unmarshal_any

    wire = bytes(_frame(trace=[(0, 1, 5, 1), (2, 1, 6, 1)])
                 .marshal())
    for cut in (1, 5, 17):
        with pytest.raises(FrameError):
            unmarshal_any(wire[:-cut])


# -- stage facade + device attribution --------------------------------------


def test_stage_records_wall_cpu_and_device():
    from etcd_tpu.utils.trace import Tracer, note_device_seconds

    reg = Registry()
    t = Tracer(reg)
    with t.stage("s1"):
        x = 0
        for i in range(200000):
            x += i  # real CPU so thread_time moves
        note_device_seconds(0.125)
    wall = reg.histogram("etcd_stage_seconds", stage="s1",
                         kind="wall")
    cpu = reg.histogram("etcd_stage_seconds", stage="s1",
                        kind="cpu")
    dev = reg.histogram("etcd_stage_seconds", stage="s1",
                        kind="device")
    assert wall.count == 1 and cpu.count == 1
    assert dev.count == 1 and abs(dev.sum - 0.125) < 1e-9
    assert cpu.sum > 0
    # the wall child's count IS the number of passes
    with t.stage("s1"):
        pass
    assert wall.count == 2 and dev.count == 1
    # the wall sample also landed in the span family: the
    # /v2/stats/spans surface keeps its coverage
    assert "s1" in t.snapshot()


def stage_counts(reg, stage: str) -> dict[str, int]:
    fam = reg.snapshot(light=True)["etcd_stage_seconds"]["samples"]
    return {c["labels"]["kind"]: c["count"] for c in fam
            if c["labels"]["stage"] == stage}


@pytest.mark.parametrize("cpu", [True, False])
def test_stage_takes_thread_time_only_where_asked(cpu, monkeypatch):
    """A child that tiles a hot pass is opened with ``cpu=False``: it
    makes no ``thread_time`` call and files no cpu sample, and its
    device seconds still pass into the stage around it."""
    from etcd_tpu.utils import trace as trace_mod

    calls = []
    real = time.thread_time
    monkeypatch.setattr(trace_mod.time, "thread_time",
                        lambda: calls.append(1) or real())
    reg = Registry()
    t = trace_mod.Tracer(reg)
    with t.stage("outer"):
        n_outer = len(calls)
        with t.stage("inner", cpu=cpu):
            trace_mod.note_device_seconds(0.25)
        n_inner = len(calls) - n_outer
    assert n_inner == (2 if cpu else 0)
    assert stage_counts(reg, "inner") == (
        {"wall": 1, "cpu": 1, "device": 1} if cpu
        else {"wall": 1, "device": 1})
    assert stage_counts(reg, "outer") == {"wall": 1, "cpu": 1,
                                          "device": 1}


def test_stage_is_filed_under_the_name_it_has_at_its_exit():
    """The engine loop opens an iteration as mg.pass and names it
    mg.heartbeat once its pack has found nothing to propose."""
    from etcd_tpu.utils.trace import Tracer

    reg = Registry()
    t = Tracer(reg)
    with t.stage("first") as ctx:
        ctx.name = "second"
    assert stage_counts(reg, "first") == {}
    assert stage_counts(reg, "second") == {"wall": 1, "cpu": 1}
    assert "second" in t.snapshot() and "first" not in t.snapshot()


def test_light_record_is_one_wall_sample_in_the_stage_family():
    from etcd_tpu.utils.trace import Tracer

    reg = Registry()
    t = Tracer(reg)
    t.record_wait("w", 0.5)
    t.record_wait("w", 0.25)
    assert stage_counts(reg, "w") == {"wall": 2}
    assert t.snapshot() == {}          # no span
    t.reset()
    t.record_wait("w", 0.5)            # the cache dropped with reset
    assert stage_counts(reg, "w") == {"wall": 1}


def test_devledger_charges_device_once_inside_stage():
    """The double-count fix: a ledger dispatch inside a traced stage
    charges its window to kind="device" exactly once — a block
    inside the dispatch does NOT add again."""
    from etcd_tpu.obs.devledger import DeviceLedger
    from etcd_tpu.utils import trace as trace_mod

    reg = Registry()
    led = DeviceLedger(reg)
    t = trace_mod.Tracer(reg)
    with t.stage("seam"):
        with led.dispatch("seam"):
            time.sleep(0.01)
            led.block("seam", 42)  # nested: must not double-charge
    dev = reg.histogram("etcd_stage_seconds", stage="seam",
                        kind="device")
    wall = reg.histogram("etcd_stage_seconds", stage="seam",
                         kind="wall")
    assert dev.count == 1
    assert dev.sum >= 0.01
    # device <= wall: the columns sum honestly instead of the old
    # span-wall + ledger-dispatch double count
    assert dev.sum <= wall.sum + 1e-6
    # outside any stage: no device sample minted
    with led.dispatch("seam"):
        pass
    assert dev.count == 1


# -- end-to-end through a real 3-host cluster -------------------------------


@pytest.fixture
def traced_cluster(tmp_path, monkeypatch):
    monkeypatch.setenv("ETCD_TRACE_SAMPLE", "1")   # trace everything
    monkeypatch.setenv("ETCD_TRACE_SLOW_MS", "0")  # tail everything
    servers, ports = make_dist_cluster(tmp_path)
    bootstrap_dist_leader(servers)
    yield servers
    for s in servers:
        try:
            s.stop()
        except Exception:
            pass


def test_trace_spans_flow_end_to_end(traced_cluster):
    servers = traced_cluster
    for i in range(4):
        servers[0].do(Request(method="PUT", id=rid(),
                              path=f"/tp/k{i}", val="v"),
                      timeout=30)
    lead = servers[0].flight.events()
    spans = [e for e in lead if e["c"] == "span"]
    stages = {e["stage"] for e in spans}
    assert {"ingest", "append", "leader_fsync", "commit", "apply",
            "client_ack"} <= stages
    # one trace id walks every origin stage.  Chosen from the far end:
    # with every ingest sampled, the first id belongs to a proposal of
    # the cluster's own start (the member's registration), and where
    # its lane lost leadership in the bootstrap's timer races its
    # trace context was dropped with the lane (distserver purges
    # _trace_live of deposed lanes) and rightly ends at leader_fsync
    tid = next(e["trace"] for e in spans if e["stage"] == "client_ack")
    mine = {e["stage"] for e in spans if e["trace"] == tid}
    assert {"ingest", "append", "leader_fsync", "commit", "apply",
            "client_ack"} <= mine
    # followers recorded the frame hop + their fsync for that trace
    for s in servers[1:]:
        ev = s.flight.events()
        assert any(e["c"] == "frame" and e["dir"] == "recv"
                   for e in ev)
        assert any(e["c"] == "span"
                   and e["stage"] == "follower_fsync" for e in ev)


def test_tail_capture_catches_slow_proposal(tmp_path, monkeypatch):
    """Head sampling OFF (ETCD_TRACE_SAMPLE=0) and the slow
    threshold at 0 ms: every acked proposal is 'slow', so the ring
    must still capture it as a tail event — the outliers never
    depend on the head sample."""
    monkeypatch.setenv("ETCD_TRACE_SAMPLE", "0")
    monkeypatch.setenv("ETCD_TRACE_SLOW_MS", "0")
    servers, _ = make_dist_cluster(tmp_path)
    try:
        bootstrap_dist_leader(servers)
        servers[0].do(Request(method="PUT", id=rid(),
                              path="/tail/k", val="v"), timeout=30)
        tails = [e for e in servers[0].flight.events()
                 if e["c"] == "tail"
                 and e["kind"] == "slow_proposal"]
        assert tails, "slow proposal not tail-captured"
        assert tails[0]["rtt_ms"] >= 0
        assert tails[0]["trace"] is None  # head sampling was off
        # and NO span events: tracing was disabled
        assert not any(e["c"] == "span"
                       for e in servers[0].flight.events())
    finally:
        for s in servers:
            try:
                s.stop()
            except Exception:
                pass


def test_flight_endpoint_serves_dump(traced_cluster):
    import urllib.request

    servers = traced_cluster
    servers[0].do(Request(method="PUT", id=rid(), path="/fe/k",
                          val="v"), timeout=30)
    port = servers[1].peer_urls[1].rsplit(":", 1)[1]
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/mraft/obs/flight",
            timeout=10) as r:
        d = json.loads(r.read())
    assert d["slot"] == 1
    assert isinstance(d["events"], list)
    assert "stages" in d and "mono_anchor" in d


def test_read_fail_closed_lands_in_flight_ring(traced_cluster):
    """A fail-closed linearizable read leaves its CAUSE in the
    serving host's ring: kill the leader, read from a follower —
    the read must reject (leader unreachable) and the follower's
    black box must say why."""
    servers = traced_cluster
    servers[0].stop()  # the bootstrap leader of every lane
    with pytest.raises(Exception):
        servers[1].do(Request(method="GET", id=rid(),
                              path="/rf/k"), timeout=8.0)
    ev = servers[1].flight.events()
    fails = [e for e in ev if e["c"] == "read_fail"]
    assert fails, ev
    assert fails[0]["outcome"] in ("no_leader", "not_leader",
                                   "timeout")


# -- stitcher ---------------------------------------------------------------


def test_stitcher_reconstructs_known_3node_timeline(tmp_path):
    trace_stitch.make_fixture(str(tmp_path))
    rep = trace_stitch.stitch_dir(str(tmp_path))
    assert rep["complete"] == 3 and rep["partial"] == 0
    off = {int(k): v for k, v in rep["offsets_s"].items()}
    # the fixture's known clock skews (+5 s / -3 s) recovered from
    # the symmetric frame quads alone
    assert abs(off[1] - 5.0) < 1e-3
    assert abs(off[2] + 3.0) < 1e-3
    bd = rep["stage_breakdown_ms"]
    assert abs(bd["queue_wait"]["p50_ms"] - 1.0) < 0.01
    assert abs(bd["net_out"]["p50_ms"] - 2.0) < 0.01
    assert abs(bd["follower_fsync"]["p50_ms"] - 2.0) < 0.01
    assert abs(bd["total"]["p50_ms"] - 12.0) < 0.01
    # the CPU budget table aggregates the dumps' stage sums
    assert rep["cpu_budget"]["dist.propose"]["passes"] == 30


def test_stitcher_incomplete_without_follower_hop(tmp_path):
    """A trace missing the follower hop counts partial, not
    complete — 'complete' means every stage ingest->client-ack AND
    a stitched network leg."""
    trace_stitch.make_fixture(str(tmp_path))
    # drop the follower dumps: only node0 remains
    for f in os.listdir(tmp_path):
        if "fix0" not in f:
            os.unlink(os.path.join(tmp_path, f))
    rep = trace_stitch.stitch_dir(str(tmp_path))
    assert rep["complete"] == 0
    assert rep["partial"] == 3


def test_stitcher_drops_stale_incarnation(tmp_path):
    """A killed-and-restarted node leaves TWO dumps for one slot
    (crash dump + restarted ring) whose seqs/trace ids/clock bases
    all restart — the stitcher must keep only the newest
    incarnation instead of merging unrelated proposals."""
    trace_stitch.make_fixture(str(tmp_path))
    # forge an OLD incarnation of slot 1: same slot, different pid,
    # older wall anchor, colliding seq/trace keys on a wild clock
    with open(os.path.join(tmp_path, "flight_fix1.json")) as f:
        live = json.load(f)
    stale = dict(live)
    stale["pid"] = 9999
    stale["wall_anchor"] = live["wall_anchor"] - 3600.0
    stale["events"] = [dict(e, t=e["t"] + 7777.0)
                       for e in live["events"]]
    with open(os.path.join(tmp_path, "flight_fix1_old.json"),
              "w") as f:
        json.dump(stale, f)
    rep = trace_stitch.stitch_dir(str(tmp_path))
    # identical result to the clean fixture set: the stale
    # incarnation's wild-clock events never entered the quads
    assert rep["complete"] == 3
    off = {int(k): v for k, v in rep["offsets_s"].items()}
    assert abs(off[1] - 5.0) < 1e-3


def test_stitched_cluster_run(traced_cluster, tmp_path):
    """Real cluster -> harvested dumps -> stitched timelines."""
    servers = traced_cluster
    for i in range(10):
        servers[0].do(Request(method="PUT", id=rid(),
                              path=f"/st/k{i}", val="v"),
                      timeout=30)
    time.sleep(0.5)
    d = str(tmp_path / "dumps")
    os.makedirs(d)
    for s in servers:
        with open(os.path.join(d, f"flight_s{s.slot}.json"),
                  "wb") as f:
            f.write(s.flight.dump_json())
    rep = trace_stitch.stitch_dir(d)
    assert rep["complete"] >= 8, rep
    assert rep["stage_breakdown_ms"]["total"]["n"] >= 8
    # all three nodes aligned (offsets exist for every slot)
    assert sorted(rep["nodes"]) == [0, 1, 2]


# -- SIGTERM crash dump -----------------------------------------------------

_SIGTERM_CHILD = r"""
import os, signal, sys, time
sys.path.insert(0, {repo!r})
from etcd_tpu.obs.flight import FlightRecorder, install_crash_dump
from etcd_tpu.obs.metrics import Registry

rec = FlightRecorder(node="child", slot=7, capacity=64, sample=1,
                     registry=Registry())
for i in range(10):
    rec.record("span", trace=i, origin=7, stage="ingest", n=i)
rec.record("election", fired=4, won=4)
install_crash_dump(rec, {dump_dir!r})
print("ARMED", flush=True)
time.sleep(30)
"""


def test_sigterm_dump_is_complete_and_parseable(tmp_path):
    dump_dir = str(tmp_path / "art")
    child = subprocess.Popen(
        [sys.executable, "-c",
         _SIGTERM_CHILD.format(repo=REPO, dump_dir=dump_dir)],
        stdout=subprocess.PIPE, text=True)
    try:
        assert child.stdout.readline().strip() == "ARMED"
        child.send_signal(signal.SIGTERM)
        child.wait(timeout=15)
    finally:
        if child.poll() is None:
            child.kill()
    # the process died OF SIGTERM (the handler re-raises after the
    # dump; exit semantics are unchanged)
    assert child.returncode == -signal.SIGTERM
    files = os.listdir(dump_dir)
    assert len(files) == 1 and "sigterm" in files[0]
    with open(os.path.join(dump_dir, files[0])) as f:
        d = json.load(f)
    assert d["node"] == "child" and d["slot"] == 7
    assert len(d["events"]) == 11
    assert d["events"][-1]["c"] == "election"
    assert all(e["stage"] == "ingest" for e in d["events"][:10])


def test_crash_dump_on_unhandled_exception(tmp_path):
    dump_dir = str(tmp_path / "art")
    code = _SIGTERM_CHILD.format(repo=REPO, dump_dir=dump_dir) \
        .replace("time.sleep(30)", "raise RuntimeError('boom')")
    child = subprocess.Popen([sys.executable, "-c", code],
                             stdout=subprocess.PIPE,
                             stderr=subprocess.DEVNULL, text=True)
    child.wait(timeout=15)
    assert child.returncode == 1
    files = os.listdir(dump_dir)
    assert len(files) == 1 and "crash" in files[0]
    with open(os.path.join(dump_dir, files[0])) as f:
        d = json.load(f)
    assert len(d["events"]) == 11


def test_crash_dump_on_daemon_thread_exception(tmp_path):
    """sys.excepthook never fires for non-main threads — and the
    server's round loop and handler threads are where crashes
    actually happen.  threading.excepthook must dump too."""
    dump_dir = str(tmp_path / "art")
    code = _SIGTERM_CHILD.format(repo=REPO, dump_dir=dump_dir) \
        .replace(
            "time.sleep(30)",
            "import threading\n"
            "t = threading.Thread("
            "target=lambda: (_ for _ in ()).throw("
            "RuntimeError('thread boom')))\n"
            "t.start(); t.join(); time.sleep(0.2)")
    child = subprocess.Popen([sys.executable, "-c", code],
                             stdout=subprocess.PIPE,
                             stderr=subprocess.DEVNULL, text=True)
    child.wait(timeout=15)
    files = os.listdir(dump_dir)
    assert len(files) == 1 and "crash" in files[0]
    with open(os.path.join(dump_dir, files[0])) as f:
        d = json.load(f)
    assert len(d["events"]) == 11
