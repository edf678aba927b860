"""The engine thread's pass, the request's waits and the restart are
covered by ``tracer.stage`` / ``tracer.record_wait`` (PR 27): every
name the benchmark's per-layer metrics read has a sample after real
traffic through a front door at 64 groups, the counts tie up, the
stages are host events of a profiler session, and the devledger bills
the wait for the device to the round's seam."""

from __future__ import annotations

import http.client
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from etcd_tpu.obs import metrics
from etcd_tpu.utils.trace import tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_PUTS = 24
PASS_CHILDREN = ("mg.pack", "mg.consensus_round", "mg.frontier_fetch",
                 "mg.assign", "mg.persist", "mg.apply", "mg.mark_applied")
ROUND_PARTS = ("mg.round.dispatch", "mg.round.wait", "mg.round.fetch")
#: the stages of a pass whose bodies PR 34 changed (one packed
#: read-back, ``applied`` riding the next round): the names stay
PACKED_PASS = (*ROUND_PARTS, "mg.frontier_fetch", "mg.mark_applied",
               "mg.consensus_round", "mg.pass")
SERVING = ("mg.pass", "mg.drain_wait", *PASS_CHILDREN, *ROUND_PARTS,
           "mg.readback", "mg.queue_wait", "mg.commit_wait", "fd.parse",
           "fd.worker_wait", "fd.do.put", "fd.do.get",
           "fd.read_inline", "fd.respond_wait")
RESTART = ("restart.snapshot_load", "replay.device", "replay.matrix",
           "restart.apply", "restart.seed", "mg.bootstrap_election")


def wall() -> dict[str, tuple[int, float]]:
    """``{stage: (count, sum)}`` of ``etcd_stage_seconds{kind=wall}``."""
    fam = metrics.registry.snapshot(light=True).get(
        "etcd_stage_seconds", {"samples": []})
    return {c["labels"]["stage"]: (c["count"], c["sum"])
            for c in fam["samples"] if c["labels"]["kind"] == "wall"}


def grown(before: dict, after: dict) -> dict[str, tuple[int, float]]:
    return {k: (n - before.get(k, (0, 0.0))[0],
                s - before.get(k, (0, 0.0))[1])
            for k, (n, s) in after.items()}


def new_server(data_dir: str):
    from etcd_tpu.server.multigroup import MultiGroupServer

    # no tick inside the test: a tick's idle heartbeat is a round of
    # MultiRaft outside mg.consensus_round, and the counts below are
    # compared exactly
    return MultiGroupServer(data_dir, g=64, m=5, cap=64,
                            storage_backend="tpu", tick_interval=30.0,
                            sync_interval=30.0)


def request(conn, method: str, path: str, body: str | None = None):
    conn.request(method, path, body=body, headers={
        "Content-Type": "application/x-www-form-urlencoded"})
    r = conn.getresponse()
    return r.status, r.read()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """N_PUTS acknowledged PUTs and as many GETs through a front door
    over 64 co-hosted groups; yields what the stages grew by, and the
    data directory of the stopped server."""
    from etcd_tpu.server.frontdoor import FrontDoor

    data_dir = str(tmp_path_factory.mktemp("stagecov") / "d")
    start = wall()

    def settled() -> dict:
        """The registry once the engine thread has closed its last
        pass (a write is acknowledged inside mg.apply, before mg.pass
        ends) and the loop thread has filed every fd.respond_wait
        (after _reply returned, which the client need not wait for;
        one for each request that went to a worker)."""
        deadline = time.monotonic() + 5.0
        while True:
            now = wall()
            g = grown(start, now)
            if (g.get("mg.pass", (0,))[0]
                    == g.get("mg.consensus_round", (0,))[0]
                    and g.get("fd.respond_wait", (0,))[0]
                    == g.get("fd.worker_wait", (0,))[0]) \
                    or time.monotonic() > deadline:
                return now
            time.sleep(0.01)

    s = new_server(data_dir)
    s.start()
    fd = FrontDoor(s, "127.0.0.1", 0, server_timeout=60.0).start()
    conn = http.client.HTTPConnection(*fd.server_address, timeout=90)
    try:
        # the first write compiles the round: outside the counted part
        assert request(conn, "PUT", "/v2/keys/warm/k",
                       "value=w")[0] in (200, 201)
        before = settled()
        for i in range(N_PUTS):
            status, _ = request(conn, "PUT", f"/v2/keys/t{i % 7}/k{i}",
                                f"value=v{i}")
            assert status in (200, 201)
            status, body = request(conn, "GET",
                                   f"/v2/keys/t{i % 7}/k{i}")
            assert status == 200 and f"v{i}".encode() in body
        after = settled()
    finally:
        conn.close()
        fd.shutdown()
        s.stop()
    yield {"grew": grown(before, after), "data_dir": data_dir}


@pytest.mark.parametrize("stage", SERVING)
def test_serving_stage_or_wait_has_a_sample(served, stage):
    count, total = served["grew"].get(stage, (0, 0.0))
    assert count >= 1 and total > 0.0, served["grew"]


@pytest.mark.parametrize("part", ROUND_PARTS)
def test_round_parts_are_counted_with_the_round(served, part):
    g = served["grew"]
    assert g[part][0] == g["mg.consensus_round"][0] >= 1


@pytest.mark.parametrize("stage", PACKED_PASS)
def test_packed_pass_records_each_stage_once_a_pass(served, stage):
    """Every write of the fixture is a pass of its own that commits:
    each of the seven names has one sample a pass, and the pass made
    one read-back (``mg.readback``), the round's."""
    g = served["grew"]
    assert g[stage][0] == g["mg.pass"][0] >= N_PUTS, (stage, g)
    assert g[stage][1] > 0.0
    assert g["mg.readback"][0] == g["mg.round.wait"][0]


def test_round_parts_tile_the_round(served):
    g = served["grew"]
    parts = sum(g[p][1] for p in ROUND_PARTS)
    whole = g["mg.consensus_round"][1]
    assert parts <= whole
    assert parts >= 0.8 * whole, (parts, whole)


@pytest.mark.parametrize("wait", ["mg.queue_wait", "mg.commit_wait",
                                  "fd.do.put", "fd.do.get",
                                  "fd.read_inline"])
def test_each_request_files_its_wait_once(served, wait):
    assert served["grew"][wait][0] == N_PUTS


def test_front_door_waits_cover_every_request(served):
    # every request is parsed; a PUT goes to a worker and comes back
    # through the mailbox, a plain GET is answered where it was parsed
    g = served["grew"]
    assert g["fd.parse"][0] == 2 * N_PUTS, g["fd.parse"]
    for name in ("fd.worker_wait", "fd.respond_wait"):
        assert g[name][0] == N_PUTS, (name, g[name])


def test_pass_holds_its_children_and_counts_rounds_only(served):
    g = served["grew"]
    assert g["mg.pass"][0] == g["mg.consensus_round"][0] \
        == g["mg.pack"][0] == g["mg.assign"][0]
    children = sum(g[c][1] for c in PASS_CHILDREN)
    assert g["mg.pass"][1] >= children
    # a write waits for its round at least: commit_wait holds a round
    assert g["mg.commit_wait"][1] / N_PUTS >= \
        0.5 * g["mg.round.wait"][1] / g["mg.round.wait"][0]


@pytest.mark.parametrize("stage", RESTART)
def test_restart_records_its_stage(served, stage):
    before = wall()
    s = new_server(served["data_dir"])
    try:
        s.start()
        grew = grown(before, wall())
        assert int(s.applied.sum()) >= N_PUTS
    finally:
        s.stop()
    if stage == "restart.snapshot_load":
        # no snapshot yet at this size: the stage ran and found none
        assert grew[stage][0] == 1
    else:
        assert grew[stage][0] >= 1 and grew[stage][1] > 0.0, grew


def test_stages_are_host_events_on_the_profiler_s_clock(tmp_path):
    """Under a profiler session every stage is a TraceAnnotation: the
    benchmark's own loader finds them by name in the ``.xplane.pb``."""
    import jax

    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import bench_reduce
    from etcd_tpu.wire.requests import Request

    s = new_server(str(tmp_path / "d"))
    s.start()
    try:
        s.do(Request(id=7001, method="PUT", path="/p/a", val="1"),
             timeout=90)
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            for i in range(3):
                s.do(Request(id=7002 + i, method="PUT", path=f"/p/k{i}",
                             val="2"), timeout=90)
        finally:
            jax.profiler.stop_trace()
    finally:
        s.stop()
    events = bench_reduce.load_events(str(tmp_path / "trace"))
    names = {name for name, _start, _dur in events["host"]}
    assert {"mg.pass", "mg.pack", "mg.round.wait",
            "mg.drain_wait"} <= names
    assert not any(n.startswith(("mg.queue_wait", "fd."))
                   for n in names)    # light records are never annotated
    passes = [(st, st + d) for n, st, d in events["host"]
              if n == "mg.pass"]
    packs = [(st, st + d) for n, st, d in events["host"]
             if n == "mg.pack"]
    # (the session may end inside the last pass, after its pack)
    assert sum(any(p0 <= s0 and s1 <= p1 for p0, p1 in passes)
               for s0, s1 in packs) >= 2


def test_trace_module_never_imports_jax():
    code = ("import sys; import etcd_tpu.utils.trace as t; "
            "assert 'jax' not in sys.modules; "
            "x = t.tracer.stage('a'); x.__enter__(); x.__exit__(); "
            "t.tracer.record_wait('w', 0.1); "
            "assert 'jax' not in sys.modules, 'stage imported jax'")
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True)


class _SlowArray:
    """A device value whose read-back blocks, as a round's first one
    does while the device still runs."""

    def __init__(self, value, seconds):
        self.value, self.seconds = value, seconds

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.seconds)
        return np.asarray(self.value, dtype)


def test_devledger_bills_the_wait_for_valid_to_the_round(monkeypatch):
    """The pack (``valid`` its first row) is the round's one
    read-back: its wait is block time of ``multiraft.round``, the wall
    of ``mg.round.wait`` and host-blocked ``device`` seconds of it,
    and one sample of ``mg.readback``."""
    from etcd_tpu.raft import multiraft

    mr = multiraft.MultiRaft(8, 3, 32)
    mr.campaign(0)
    real = multiraft._fused_round_hot

    def slow_round(*a, **kw):
        states, pack = real(*a, **kw)
        return states, _SlowArray(pack, 0.05)

    monkeypatch.setattr(multiraft, "_fused_round_hot", slow_round)
    block = metrics.registry.counter(
        "etcd_devledger_block_seconds_total", stage="multiraft.round")
    dev = metrics.registry.histogram(
        "etcd_stage_seconds", stage="mg.round.wait", kind="device")
    b0, d0, w0 = block.get(), dev.sum, wall().get("mg.round.wait",
                                                  (0, 0.0))
    r0 = wall().get("mg.readback", (0, 0.0))
    with tracer.stage("outer.round"):
        mr.propose(np.ones(8, np.int32))
    assert mr.last_valid.all()
    assert block.get() - b0 >= 0.05
    assert dev.sum - d0 >= 0.05
    w1 = wall()["mg.round.wait"]
    assert w1[0] == w0[0] + 1 and w1[1] - w0[1] >= 0.05
    r1 = wall()["mg.readback"]
    assert r1[0] == r0[0] + 1 and r1[1] - r0[1] >= 0.05
    # and the enclosing stage's device column holds its children's
    outer = metrics.registry.histogram(
        "etcd_stage_seconds", stage="outer.round", kind="device")
    assert outer.sum >= 0.05


def test_idle_heartbeat_is_its_own_stage_not_a_pass(tmp_path):
    """With nothing queued the loop runs a heartbeat round each tick.
    The iteration packs first, as a pass does, and is named after it
    has found nothing to propose: mg.heartbeat with its pack as
    mg.heartbeat.pack, never mg.pass or mg.pack, and no child of a pass
    but the round's three parts (MultiRaft.replicate is a round)."""
    from etcd_tpu.server.multigroup import MultiGroupServer

    s = MultiGroupServer(str(tmp_path / "d"), g=8, m=3, cap=32,
                         tick_interval=0.02)
    before = wall()
    s.start()
    try:
        deadline = time.monotonic() + 30.0
        while grown(before, wall()).get("mg.heartbeat", (0,))[0] < 3 \
                and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        s.stop()
    g = grown(before, wall())
    assert g["mg.heartbeat"][0] >= 3 and g["mg.heartbeat"][1] > 0.0
    assert g["mg.round.wait"][0] >= g["mg.heartbeat"][0]
    assert g["mg.heartbeat.pack"][0] == g["mg.heartbeat"][0]
    assert g["mg.heartbeat.pack"][1] <= g["mg.heartbeat"][1]
    for name in ("mg.pass", "mg.pack", "mg.consensus_round"):
        assert g.get(name, (0, 0.0))[0] == 0, (name, g)
