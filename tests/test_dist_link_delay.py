"""``--dist-local-link-delay-ms``: the members of a local cluster a
stated distance apart (PR 38).  Three members at 64 groups on the
CPU with the benchmark configuration's own delays (slots 0 and 1 a
20 ms round trip apart, slot 2 200 ms from both), built and started
by the functions the CLI builds and starts them with: a write is
acknowledged in under the far link's round trip (the near follower
closes the quorum), a read with the lease off likewise, and once the
writers stop all three stores are equal.  Then the flag's refusals,
and, over the deterministic fake transport of
``test_dist_pipeline.py``, the one fault of progress the long link
found: a thin entry frame is held back no longer than a heartbeat
interval."""

import statistics
import time

import numpy as np
import pytest

from test_dist_pipeline import (  # noqa: F401 - ``cluster`` is a fixture
    _elapse_hb, cluster, elect, pend, settle)
from test_local_cluster import wait_for

from etcd_tpu import cli
from etcd_tpu.obs import metrics as _obs
from etcd_tpu.wire.distmsg import unmarshal_any
from etcd_tpu.wire.requests import Request

SPEC = "0-1:10,0-2:100,1-2:100"     # benchmark/configs/cluster3-geo.json
NEAR_RTT_S, FAR_RTT_S = 0.020, 0.200
G = 64


def counter(family: str, peer: int) -> float:
    return _obs.registry.counter(family, peer=str(peer)).get()


def stage(name: str) -> tuple[float, float]:
    h = _obs.registry.histogram("etcd_stage_seconds", stage=name,
                                kind="wall")
    count, total, _mx, _ring = h.ring_stats()
    return count, total


def test_parse_link_delays_and_each_member_s_row():
    pairs = cli.parse_link_delays(SPEC, 3)
    assert pairs == {(0, 1): 0.010, (0, 2): 0.100, (1, 2): 0.100}
    # order of a pair and of the list does not matter; 0 is no delay
    assert cli.parse_link_delays(" 2-1:100 ,1-0:10,2-0:100,", 3) == pairs
    assert cli.parse_link_delays("0-1:0", 2) == {(0, 1): 0.0}
    assert cli.parse_link_delays("", 3) == {}


@pytest.mark.parametrize("lease_ticks", [30, 0],
                         ids=["lease", "lease-off"])
def test_the_near_follower_closes_the_quorum_and_the_far_one_follows(
        tmp_path, lease_ticks):
    from etcd_tpu.server.server import gen_id

    servers = cli.local_dist_members(
        str(tmp_path), 3, name="geo", g=G, cap=64, election=60,
        lease_ticks=lease_ticks, storage_backend="tpu",
        link_delays=cli.parse_link_delays(SPEC, 3))
    # each member got its own row of the three delays
    assert [s._link_delay for s in servers] == [
        {1: 0.010, 2: 0.100}, {0: 0.010, 2: 0.100},
        {0: 0.100, 1: 0.100}]
    leader = servers[0]

    def put(i: int) -> float:
        t = time.monotonic()
        r = leader.do(Request(method="PUT", id=gen_id(),
                              path=f"/t{i}/cfg", val=f"v{i}"),
                      timeout=5.0)
        assert r.event.node.value == f"v{i}"
        return time.monotonic() - t

    def get(i: int) -> float:
        t = time.monotonic()
        r = leader.do(Request(method="GET", id=gen_id(),
                              path=f"/t{i}/cfg"), timeout=5.0)
        assert r.event.node.value == f"v{i}"
        return time.monotonic() - t

    def holds(s, n: int) -> bool:
        try:
            return all(s.store.get(f"/t{i}/cfg", False, False)
                       .node.value == f"v{i}" for i in range(n))
        except Exception:
            return False

    try:
        cli.start_dist_members(servers)
        wait_for(lambda: cli.dist_groups_led(servers) == G, 60.0,
                 "every group led")
        wait_for(lambda: np.asarray(leader.mr.is_leader()).all(), 30.0,
                 "slot 0 leads every group")
        for i in range(8):              # compilations, first frames
            put(i)
            get(i)
        closed = [counter("etcd_dist_commit_advance_acks_total", p)
                  for p in (1, 2)]
        near0, far0 = stage("dist.peer_rtt.s1"), stage("dist.peer_rtt.s2")
        n = 32
        puts = [put(i) for i in range(8, n)]
        gets = [get(i) for i in range(8, n)]
        # the quorum is the leader and the follower 20 ms away: a
        # write crossed that link both ways and did not wait for the
        # member 200 ms away
        assert NEAR_RTT_S <= statistics.median(puts) < FAR_RTT_S, puts
        if lease_ticks:
            # the lease answers with no message at all
            assert statistics.median(gets) < NEAR_RTT_S, gets
        else:
            # one confirmation round: the near follower's answer
            assert NEAR_RTT_S <= statistics.median(gets) < FAR_RTT_S, gets
        closed = [counter("etcd_dist_commit_advance_acks_total", p) - c
                  for p, c in zip((1, 2), closed)]
        assert closed[0] >= n - 8 and closed[1] <= closed[0] / 10, closed
        # ... and the far member is not skipped: all three stores are
        # equal soon after the writers stop (a link's delay, a
        # heartbeat interval and a pass; before the repair in
        # _pump_peer this took the 8 s of the expire sweep, every
        # other run)
        for s in servers:
            wait_for(lambda: holds(s, n), 3.0,
                     f"slot {s.slot} holds every record")
        # a round trip under each peer's own name: the stated delay
        # both ways, and the follower's append in between
        near1, far1 = stage("dist.peer_rtt.s1"), stage("dist.peer_rtt.s2")
        near = (near1[1] - near0[1]) / (near1[0] - near0[0])
        far = (far1[1] - far0[1]) / (far1[0] - far0[0])
        assert NEAR_RTT_S <= near < FAR_RTT_S <= far, (near, far)
    finally:
        for s in servers:
            s.stop()


@pytest.mark.parametrize("argv,why", [
    (["--dist-slot", "0", "--dist-peers",
      "http://127.0.0.1:1,http://127.0.0.1:2,http://127.0.0.1:3",
      "--dist-local-link-delay-ms", SPEC], "--dist-local-cluster"),
    (["--dist-local-cluster", "3", "--dist-local-link-delay-ms",
      "0-3:10"], "names no pair of the slots 0..2"),
    (["--dist-local-cluster", "3", "--dist-local-link-delay-ms",
      "1-1:10"], "names no pair"),
    (["--dist-local-cluster", "3", "--dist-local-link-delay-ms",
      "0-1:-5"], "a delay is >= 0 ms"),
    (["--dist-local-cluster", "3", "--dist-local-link-delay-ms",
      "0-1:10,1-0:20"], "twice"),
    (["--dist-local-cluster", "3", "--dist-local-link-delay-ms",
      "0-1=10"], "is not A-B:MS"),
], ids=["not-local", "no-such-slot", "one-slot-twice", "negative",
        "pair-twice", "garbage"])
def test_the_flag_refuses_what_it_cannot_place(argv, why, caplog,
                                               tmp_path):
    import os

    with caplog.at_level("ERROR", logger="etcd_tpu.cli"):
        assert cli.main(argv + ["--data-dir", str(tmp_path / "d")]) == 1
    assert "--dist-local-link-delay-ms" in caplog.text
    assert why in caplog.text
    assert not os.path.exists(tmp_path / "d")


def test_the_member_refuses_a_row_that_names_no_peer(tmp_path):
    from etcd_tpu.server.distserver import DistServer

    urls = [f"http://127.0.0.1:{p}" for p in (1, 2, 3)]
    for row in ({0: 0.01}, {3: 0.01}, {1: -0.01}):
        with pytest.raises(ValueError, match="link_delay_s"):
            DistServer(str(tmp_path / "d"), slot=0, peer_urls=urls,
                       g=4, link_delay_s=row)
    assert not (tmp_path / "d").exists()


# -- the thin-frame hold, frame by frame -------------------------------------


def entries_in(frame) -> int:
    return int(np.asarray(unmarshal_any(frame["payload"]).n_ents).sum())


def test_a_thin_entry_frame_is_held_no_longer_than_a_heartbeat_interval(
        cluster):
    """Over a link longer than the heartbeat interval some frame is
    always in flight, an empty one if no other.  The
    anti-fragmentation hold waits for the window's next free moment,
    which then never came: every acknowledgement's re-pump found
    another frame in flight, and the far peer got no entry until the
    expire sweep.  The hold now ends a heartbeat interval after it
    began, and the entry frame joins the frames in flight."""
    servers, net = cluster
    leader = servers[0]
    elect(leader)
    settle(leader, net)
    leader._min_frame_ents = 1024       # the CLI's default: 2 x 512
    net.auto_peers = {1}                # peer 2 is the far one: by hand
    held0 = counter("etcd_dist_thin_frame_holds_total", 2)
    depth = _obs.registry.histogram("etcd_dist_inflight_at_send",
                                    peer="2")
    _elapse_hb(leader)
    leader._leader_round([])            # a heartbeat leaves for peer 2
    hb = net.sent_to(2)[-1]
    assert entries_in(hb) == 0 and leader.pipe.inflight(2) == 1
    n0 = len(net.sent_to(2))
    # a thin entry frame waits while the window is busy, as ever ...
    leader._leader_round([pend(0, "a")])
    assert len(net.sent_to(2)) == n0
    assert counter("etcd_dist_thin_frame_holds_total", 2) == held0 + 1
    # ... and every re-pump that finds it busy holds it again
    with leader.lock:
        leader._pump_peer(2)
    assert len(net.sent_to(2)) == n0
    assert counter("etcd_dist_thin_frame_holds_total", 2) == held0 + 2
    # a heartbeat interval after the hold began it ends, busy or not
    # (the interval is 10 s here: rewind the stamp, as _elapse_hb does)
    (key, since), = leader._thin_since.items()
    assert key == (2, 0)
    leader._thin_since[key] = since - leader._hb_interval
    seen, depth_sum = depth.ring_stats()[:2]
    with leader.lock:
        leader._pump_peer(2)
    first = net.sent_to(2)[n0:]
    assert [entries_in(f) for f in first] == [1]
    assert leader.pipe.inflight(2) == 2     # beside the heartbeat
    # ... and its depth at that moment was filed: one frame ahead
    assert depth.ring_stats()[:2] == (seen + 1, depth_sum + 1)
    assert not leader._thin_since
    # the next thin frame starts a hold of its own
    leader._leader_round([pend(0, "b")])
    assert len(net.sent_to(2)) == n0 + 1
    assert counter("etcd_dist_thin_frame_holds_total", 2) == held0 + 3
    # ... which the window's next free moment ends, as it always did
    for fr in (hb, first[0]):
        i = net.frames.index(fr)
        net.process(i)
        net.respond(i)
    after = net.sent_to(2)[n0 + 1:]
    assert [entries_in(f) for f in after if entries_in(f)] == [1]
    assert not leader._thin_since
