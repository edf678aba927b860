"""Five members across three regions, 2 + 2 + 1 (the configuration
``cluster5-geo``): a quorum is three, so a write waits for the SECOND
follower.  On the CPU at 64 groups, with the configuration's layout
and its delays scaled down, built and started by the functions the
CLI builds and starts them with: acknowledged writes are in at least
three WALs at the moment of the acknowledgement and read back equal
to a register at all five members; with two followers stopped writes
are still acknowledged, with three a write times out.  Then the
order statistic itself at five members against the scalar reference,
and the two waits that time it (``dist.first_ack``,
``dist.quorum_ack``) frame by frame over the deterministic fake
transport of ``test_dist_pipeline.py``.  Every test runs under a time
limit of its own."""

import contextlib
import os
import signal
import time

import numpy as np
import pytest

from test_dist_pipeline import FakeNet, pend
from test_local_cluster import wait_for

from etcd_tpu import cli
from etcd_tpu.obs import metrics as _obs
from etcd_tpu.server.distserver import ACK_ROUNDS_KEPT, DistServer
from etcd_tpu.server.server import gen_id
from etcd_tpu.wire.distmsg import unmarshal_any
from etcd_tpu.wire.requests import Request

from conftest import free_ports

#: benchmark/configs/cluster5-geo.json's layout, its delays scaled
#: down (region A 1 ms, A-B 5 ms, to region C 50 ms, one way)
SPEC = ("0-1:1,2-3:1,0-2:5,0-3:5,1-2:5,1-3:5,"
        "0-4:50,1-4:50,2-4:50,3-4:50")
G = 64


@contextlib.contextmanager
def time_limit(seconds: float):
    """Raise in the test's own thread once ``seconds`` have passed."""
    def expire(*_):
        raise TimeoutError(f"over the test's limit of {seconds:.0f} s")

    old = signal.signal(signal.SIGALRM, expire)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


def stage(name: str) -> tuple[int, float]:
    h = _obs.registry.histogram("etcd_stage_seconds", stage=name,
                                kind="wall")
    count, total, _mx, _ring = h.ring_stats()
    return count, total


def start_five(root: str) -> list:
    servers = cli.local_dist_members(
        root, 5, name="geo5", g=G, cap=64, election=60,
        storage_backend="tpu", link_delays=cli.parse_link_delays(SPEC, 5))
    cli.start_dist_members(servers)
    wait_for(lambda: cli.dist_groups_led(servers) == G, 60.0,
             "every group led")
    wait_for(lambda: np.asarray(servers[0].mr.is_leader()).all(), 30.0,
             "slot 0 leads every group")
    return servers


def settled(leader) -> tuple:
    """Both waits' (count, sum) at a moment when no round of the
    leader awaits its quorum (the 2/s SYNC rounds come and go)."""
    def grab():
        with leader.lock:
            if not leader._ack_rounds:
                return stage("dist.first_ack"), stage("dist.quorum_ack")
    return wait_for(grab, 5.0, "no round awaiting its quorum")


def put(leader, key: str, val: str, timeout: float = 5.0) -> None:
    r = leader.do(Request(method="PUT", id=gen_id(), path=key, val=val),
                  timeout=timeout)
    assert r.event.node.value == val


def stop(servers, slots) -> None:
    for i in slots:
        assert servers[i].stop()


def value_at(s, key: str):
    try:
        return s.store.get(key, False, False).node.value
    except Exception:
        return None


def test_each_member_gets_its_row_of_the_ten_links():
    pairs = cli.parse_link_delays(SPEC, 5)
    assert len(pairs) == 10 and pairs[0, 1] == pairs[2, 3] == 0.001
    assert {pairs[a, b] for a in (0, 1) for b in (2, 3)} == {0.005}
    assert {pairs[a, 4] for a in range(4)} == {0.050}


def test_acknowledged_writes_are_in_three_wals_and_read_back_at_all_five(
        tmp_path):
    with time_limit(90):
        servers = start_five(str(tmp_path))
        try:
            assert [s._link_delay for s in servers][4] == {
                0: 0.050, 1: 0.050, 2: 0.050, 3: 0.050}
            leader = servers[0]
            # every record each member's WAL has fsynced: save() returns
            # once the write is durable
            durable: list[list[bytes]] = [[] for _ in servers]
            for s, seen in zip(servers, durable):
                def save(hs, ents, _save=s.wal.save, _seen=seen):
                    out = _save(hs, ents)
                    _seen.extend(bytes(e.data) for e in ents)
                    return out
                s.wal.save = save
            for i in range(4):               # compilations, first frames
                put(leader, f"/warm{i}", f"w{i}")
            first0, quorum0 = settled(leader)
            ref: dict[str, str] = {}
            copies = []
            for i in range(24):
                key, val = f"/t{i % 8}/cfg", f"five-{i:03d}-{gen_id()}"
                put(leader, key, val)
                ref[key] = val
                # at the acknowledgement: the leader and two followers
                mark = val.encode()
                copies.append(sum(any(mark in d for d in list(seen))
                                  for seen in durable))
            assert min(copies) >= 3, copies
            # ... and all five once the writers stop: a register per key
            for s in servers:
                wait_for(lambda: all(value_at(s, k) == v
                                     for k, v in ref.items()), 5.0,
                         f"slot {s.slot} holds every record")
            # every noted round has met its quorum by now, and for each
            # the quorum-closing answer came no earlier than the first
            first, quorum = settled(leader)
            n_first, n_quorum = first[0] - first0[0], quorum[0] - quorum0[0]
            assert n_first == n_quorum >= 24
            assert quorum[1] - quorum0[1] >= first[1] - first0[1] > 0
        finally:
            for s in servers:
                s.stop()


def test_two_followers_down_still_commit_three_down_do_not(tmp_path):
    with time_limit(90):
        servers = start_five(str(tmp_path))
        stopped: list[int] = []
        try:
            leader = servers[0]
            put(leader, "/before", "five")
            # region B's second member and region C lost: the leader,
            # slot 1 and slot 2 are a quorum of three
            stop(servers, (3, 4))
            stopped += [3, 4]
            for i in range(6):
                put(leader, f"/two-down/{i}", f"v{i}")
            for s in servers[:3]:
                wait_for(lambda: value_at(s, "/two-down/5") == "v5", 5.0,
                         f"slot {s.slot} holds the last write")
            # a third follower lost: two of five are no quorum, and the
            # write is never acknowledged (fail-closed)
            stop(servers, (2,))
            stopped.append(2)
            with pytest.raises(TimeoutError):
                put(leader, "/three-down", "never", timeout=1.5)
            time.sleep(0.3)
            assert value_at(leader, "/three-down") is None
            assert value_at(servers[1], "/three-down") is None
        finally:
            for s in servers:
                if s.slot not in stopped:
                    s.stop()


# -- the order statistic at five members, against the scalar reference ------


def test_commit_index_and_quorum_basis_at_five_match_the_scalar_reference():
    import jax.numpy as jnp

    from etcd_tpu.ops.quorum import (commit_index_batch, maybe_commit_batch,
                                     quorum_basis)
    from etcd_tpu.raft.core import Raft
    from etcd_tpu.wire import Entry

    rng = np.random.default_rng(2_200_000_042)
    g, m, cap = 48, 5, 16
    nmem = rng.integers(1, m + 1, size=g)
    match = np.where(np.arange(m) < nmem[:, None],
                     rng.integers(0, cap, size=(g, m)), 0)
    terms = rng.integers(1, 4, size=(g, cap))
    terms.sort(axis=1)                       # a log's terms never fall
    committed = rng.integers(0, 4, size=g)
    term = rng.integers(1, 4, size=g)
    mci = np.asarray(commit_index_batch(jnp.asarray(match, jnp.int32),
                                        jnp.asarray(nmem, jnp.int32)))
    new = np.asarray(maybe_commit_batch(
        jnp.asarray(match, jnp.int32), jnp.asarray(nmem, jnp.int32),
        jnp.asarray(committed, jnp.int32), jnp.asarray(term, jnp.int32),
        jnp.asarray(terms, jnp.int32), jnp.zeros(g, jnp.int32)))
    now = 100.0
    ack_t0 = rng.uniform(0.0, now, size=(m, g))
    members = np.arange(m)[None, :] < nmem[:, None]
    basis = quorum_basis(ack_t0, members, nmem, 0, now)
    for gi in range(g):
        n = int(nmem[gi])
        r = Raft(1, list(range(1, n + 1)), election=10, heartbeat=1)
        r.raft_log.append(0, [Entry(index=i, term=int(terms[gi, i]))
                              for i in range(1, cap)])
        r.raft_log.committed = int(committed[gi])
        r.term = int(term[gi])
        for j, pr in enumerate(r.prs.values()):
            pr.match = int(match[gi, j])
        srt = sorted((pr.match for pr in r.prs.values()), reverse=True)
        assert mci[gi] == srt[r.q() - 1], gi
        r.maybe_commit()
        assert new[gi] == r.raft_log.committed, gi
        # the same order statistic over time: this member counts now
        times = sorted([now] + ack_t0[1:n, gi].tolist(), reverse=True)
        assert basis[gi] == times[r.q() - 1], gi
    assert (nmem == 5).sum() >= 5            # the five-member case ran


# -- one stripe past two peers ---------------------------------------------------


@pytest.mark.parametrize("m,stripes", [(2, 2), (3, 2), (4, 1), (5, 1)])
def test_two_stripes_a_peer_up_to_two_peers_and_one_past(
        tmp_path, monkeypatch, m, stripes):
    """A stripe is a frame the leader builds, a response it absorbs
    and a heartbeat it keeps, a peer.  Five members on two stripes
    sent 20 frames a round (13 of them empty) against 5 on one, and
    the leader's lock collapsed the cell into re-sends: past two
    peers the lanes ride one connection a peer."""
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    urls = [f"http://127.0.0.1:{p}" for p in free_ports(m)]
    s = DistServer(str(tmp_path / "d"), slot=0, peer_urls=urls, g=4,
                   pipeline_depth=8)
    try:
        assert s._n_stripes == stripes
        assert len(s._stripe_masks) == stripes
    finally:
        s.stop()


def test_at_five_a_round_is_one_frame_a_peer(tmp_path, monkeypatch):
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    with time_limit(60):
        servers, net = make_cluster(tmp_path, 5, depth=8)
        try:
            leader = servers[0]
            n0 = len(net.frames)
            # lanes of both parities: two stripes would send two frames
            leader._leader_round([pend(g, f"v{g}") for g in range(4)])
            sent = net.frames[n0:]
            assert sorted(f["dst"] for f in sent) == [1, 2, 3, 4]
            for fr in sent:
                assert np.asarray(unmarshal_any(fr["payload"]).n_ents
                                  ).tolist() == [1, 1, 1, 1]
        finally:
            for s in servers:
                s.done.set()
                s.wal.close()


# -- the two waits, frame by frame ----------------------------------------------


def make_cluster(tmp_path, m: int, depth: int = 4):
    """``m`` real DistServers over the fake transport, no listeners or
    round loops: the test moves every frame and response itself."""
    urls = [f"http://127.0.0.1:{p}" for p in free_ports(m)]
    servers = [
        DistServer(str(tmp_path / f"d{s}"), slot=s, peer_urls=urls,
                   g=4, cap=64, tick_interval=10.0, election=60,
                   pipeline_depth=depth, coalesce_ents=1)
        for s in range(m)]
    net = FakeNet(servers)
    for s in servers:
        s._min_frame_ents = 1
        s._channel = (lambda peer, _s=s: net.chan(_s, peer))

        def _exchange(frames, track=False, _net=net):
            return [unmarshal_any(_net.servers[p].handle_frame(
                bytes(payload))) for p, payload in frames]
        s._exchange = _exchange
    leader = servers[0]
    leader._campaign(np.ones(4, bool))
    assert leader.mr.is_leader().all()
    net.auto_peers = set(range(1, m))
    for _ in range(8):                       # the election's entries
        leader._leader_round([])
        if not any(leader.pipe.inflight(p) for p in range(1, m)):
            break
    net.auto_peers = set()
    return servers, net


@pytest.fixture
def fake5(tmp_path):
    servers, net = make_cluster(tmp_path, 5)
    yield servers, net
    for s in servers:
        s.done.set()
        s.wal.close()


def answer(net, peer: int) -> None:
    """Every frame sent to ``peer`` and not yet answered: processed
    and answered, in order."""
    for i, fr in enumerate(net.frames):
        if fr["dst"] == peer and fr["resp"] is None:
            net.process(i)
            net.respond(i)


def test_at_five_the_quorum_ack_is_the_second_follower_s(fake5):
    with time_limit(60):
        servers, net = fake5
        leader = servers[0]
        assert leader._quorum_followers == 2
        first0, quorum0 = stage("dist.first_ack"), stage("dist.quorum_ack")
        leader._leader_round([pend(0, "a"), pend(1, "b")])
        (rnd,) = leader._ack_rounds
        assert rnd.lanes.tolist() == [0, 1]
        answer(net, 1)                       # region A's follower
        first, quorum = stage("dist.first_ack"), stage("dist.quorum_ack")
        assert first[0] == first0[0] + 1 and quorum == quorum0
        assert leader._ack_rounds[0] is rnd
        time.sleep(0.02)
        answer(net, 2)                       # region B's first
        quorum = stage("dist.quorum_ack")
        assert quorum[0] == quorum0[0] + 1 and not leader._ack_rounds
        assert quorum[1] - quorum0[1] >= (first[1] - first0[1]) + 0.02
        answer(net, 3)                       # after the commit: nothing
        answer(net, 4)
        assert stage("dist.first_ack") == first
        assert stage("dist.quorum_ack") == quorum


def test_a_response_that_misses_an_appended_lane_covers_nothing(fake5):
    with time_limit(60):
        servers, net = fake5
        leader = servers[0]
        leader._leader_round([pend(0, "a"), pend(2, "b")])
        (rnd,) = leader._ack_rounds
        frame = net.sent_to(1)[-1]
        i = net.frames.index(frame)
        net.process(i)
        resp = unmarshal_any(frame["resp"])
        # a response that did not take lane 2 up to the round's last
        resp.acked = np.where(np.arange(4) == 2, 0, resp.acked)
        ok = np.asarray(resp.active) & np.asarray(resp.ok)
        assert not rnd.cover(1, ok, np.asarray(resp.acked))
        assert rnd.left[1][0].tolist() == [2]
        # ... the lane's own answer then completes that follower
        assert rnd.cover(1, ok, np.full(4, 10**6))
        assert not rnd.cover(1, ok, np.full(4, 10**6))   # only once


def test_at_three_the_two_waits_are_one_and_equal(tmp_path):
    with time_limit(60):
        servers, net = make_cluster(tmp_path, 3)
        try:
            leader = servers[0]
            assert leader._quorum_followers == 1
            first0, quorum0 = stage("dist.first_ack"), stage(
                "dist.quorum_ack")
            leader._leader_round([pend(3, "c")])
            answer(net, 2)
            first, quorum = stage("dist.first_ack"), stage(
                "dist.quorum_ack")
            assert first[0] - first0[0] == quorum[0] - quorum0[0] == 1
            assert first[1] - first0[1] == quorum[1] - quorum0[1] > 0
            assert not leader._ack_rounds
            answer(net, 1)
            assert stage("dist.first_ack") == first
        finally:
            for s in servers:
                s.done.set()
                s.wal.close()


def test_pending_rounds_are_bounded_and_forgotten_on_a_step_down(fake5):
    with time_limit(60):
        servers, net = fake5
        leader = servers[0]
        for k in range(ACK_ROUNDS_KEPT + 12):    # nobody answers
            leader._leader_round([pend(k % 4, f"x{k}")])
        assert len(leader._ack_rounds) == ACK_ROUNDS_KEPT
        assert leader._ack_rounds.maxlen == ACK_ROUNDS_KEPT
        leader.mr.step_down(np.ones(4, bool))
        leader._leader_round([])
        assert not leader._ack_rounds
