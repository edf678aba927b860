"""The leader absorbs its queued acknowledgements in one batch.

Engine half: ``DistMember.handle_append_resps`` leaves the state bit
for bit where the same responses absorbed one by one through
``handle_append_resp`` leave it, and returns the same commit vectors.

Server half, over the deterministic fake transport of
``test_dist_pipeline.py``: the acknowledgements that queue while the
lock is held are absorbed by ONE take, with one re-pump a peer; a queued
acknowledgement lands before a failure of its peer; the first response
of a batch whose step closed a quorum is the one credited; and a batch
confirms no read registered after its frames left."""

import threading
import time

import numpy as np
import pytest

from test_dist_pipeline import (  # noqa: F401 - ``cluster`` is a fixture
    cluster, elect, pend, settle)
from test_dist_read_pump import closed, deliver, lease_off, register

from etcd_tpu.obs import metrics as _obs
from etcd_tpu.raft import distmember
from etcd_tpu.raft.distmember import DistMember
from etcd_tpu.wire.distmsg import AppendResp, unmarshal_any

G, M, CAP, K = 64, 3, 64, 16


def leader_member() -> DistMember:
    """Slot 0 leads every lane of G with a few rounds of entries
    appended (self-acked on some lanes) and none acknowledged."""
    ms = [DistMember(G, M, s, CAP, ack_rows=K) for s in range(M)]
    req = unmarshal_any(ms[0].begin_campaign(np.ones(G, bool)).marshal())
    votes = [unmarshal_any(ms[p].handle_vote(req).marshal())
             for p in (1, 2)]
    assert ms[0].tally(req.active, votes).all()
    lead = ms[0]
    rng = np.random.default_rng(7)
    for _ in range(3):
        lead.propose(rng.integers(0, 4, G).astype(np.int32),
                     self_ack=False)
    last = np.asarray(lead.state.last)
    lead.ack_self(np.where(rng.random(G) < 0.5, last, 0))
    return lead


def resp(peer, term, ok, acked, hint, active) -> AppendResp:
    return AppendResp(sender=peer, term=np.asarray(term, np.int32),
                      ok=np.asarray(ok, bool),
                      acked=np.asarray(acked, np.int32),
                      hint=np.asarray(hint, np.int32),
                      active=np.asarray(active, bool))


def random_resps(lead, rng, n, p_ok=0.7):
    term = np.asarray(lead.state.term)
    last = np.asarray(lead.state.last)
    return [resp(int(rng.integers(1, M)), term, rng.random(G) < p_ok,
                 rng.integers(0, last + 1), rng.integers(0, last + 1),
                 rng.random(G) < 0.8) for _ in range(n)]


def case_ok_and_rejected(lead, rng):
    return random_resps(lead, rng, int(rng.integers(1, K + 1)))


def case_need_snap(lead, rng):
    # a need_snap lane acks positively at the follower's commit
    commit = np.asarray(lead.state.commit)
    term = np.asarray(lead.state.term)
    need = rng.random(G) < 0.5
    at = rng.integers(0, commit + 1)
    snap = resp(2, term, need, at, at, need)
    return random_resps(lead, rng, 2) + [snap] \
        + random_resps(lead, rng, 1)


def case_out_of_order(lead, rng):
    # one peer, one stripe: the later frame's answer comes first
    last = np.asarray(lead.state.last)
    term = np.asarray(lead.state.term)
    lanes = np.arange(G) % 2 == 0
    lo = np.maximum(last - 2, 0)
    return [resp(1, term, lanes, last, last, lanes),
            resp(1, term, lanes, lo, lo, lanes)] \
        + random_resps(lead, rng, 1)


def case_stale_higher_term(lead, rng):
    # an older reign's answer, neutered to the lanes of a higher term
    term = np.asarray(lead.state.term)
    higher = rng.random(G) < 0.3
    stale = resp(2, np.where(higher, term + 1, term), np.zeros(G, bool),
                 np.asarray(lead.state.last), np.zeros(G), higher)
    return random_resps(lead, rng, 2) + [stale] \
        + random_resps(lead, rng, 3)


def case_padding(lead, rng):
    # a response whose lanes are all inactive changes nothing
    term = np.asarray(lead.state.term)
    last = np.asarray(lead.state.last)
    return random_resps(lead, rng, 1) + [
        resp(1, term, np.ones(G, bool), last, last, np.zeros(G, bool))]


def case_longer_than_k(lead, rng):
    return random_resps(lead, rng, K + 5)


CASES = [case_ok_and_rejected, case_need_snap, case_out_of_order,
         case_stale_higher_term, case_padding, case_longer_than_k]


def same_state(a, b) -> bool:
    return all(np.asarray(x).dtype == np.asarray(y).dtype
               and np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


@pytest.mark.parametrize("build", CASES, ids=lambda f: f.__name__[5:])
def test_batch_absorbs_as_one_by_one(build):
    rng = np.random.default_rng(41)
    base = leader_member()
    for _ in range(3):
        resps = build(base, rng)
        one = DistMember(G, M, 0, CAP, ack_rows=K)
        one.state = base.state
        before = np.asarray(one.state.commit)
        singles = [one.handle_append_resp(r) for r in resps]
        got = base.handle_append_resps(resps)
        assert same_state(base.state, one.state)
        assert got.shape == (len(resps) + 1, G)
        assert np.array_equal(got[0], before)
        for row, single in zip(got[1:], singles):
            assert np.array_equal(row, single)
    # the run moved something: a test of nothing proves nothing
    assert (np.asarray(base.state.match)[:, 1:] > 0).any()


def test_padding_rows_and_the_warm_up_leave_the_state_alone():
    lead = leader_member()
    lead.handle_append_resps(random_resps(lead, np.random.default_rng(3),
                                          2))
    st = lead.state
    lead.prepare_absorb()
    assert same_state(lead.state, st)
    pad = distmember._absorb_resps(st, lead._put(lead._resp_rows([])))
    assert same_state(pad[0], st)
    assert (np.asarray(pad[1]) == np.asarray(st.commit)).all()
    # and the one-response step itself on an all-inactive response
    z = np.zeros(G, np.int32)
    again = distmember._absorb_resp(st, 1, np.asarray(st.term), z == 0,
                                    np.asarray(st.last), z, z != 0)
    assert same_state(again, st)


# -- the server -----------------------------------------------------------


def in_flight(leader, net, rounds=1):
    """Write rounds with the transport held: their frames to both peers
    processed by the followers, the answers not yet delivered."""
    elect(leader)
    settle(leader, net)
    net.auto_peers = set()
    n0 = len(net.frames)
    for r in range(rounds):
        leader._leader_round([pend(gi, f"w{r}") for gi in range(2)])
    frames = [i for i in range(n0, len(net.frames))]
    assert {net.frames[i]["dst"] for i in frames} == {1, 2}
    for i in frames:
        net.process(i)
    return frames


def queue_ack(leader, net, i) -> None:
    """What ``_on_pipe_resp`` does before it takes the lock."""
    fr = net.frames[i]
    leader._acks.append((fr["dst"], unmarshal_any(fr["resp"]),
                         time.monotonic()))


def test_acks_queued_behind_the_lock_are_absorbed_by_one_take(cluster):
    servers, net = cluster
    leader = servers[0]
    frames = in_flight(leader, net, rounds=2)   # two frames a peer
    assert len(frames) == 4
    hist = _obs.registry.histogram("etcd_dist_acks_per_absorb")
    n0, s0 = hist.count, hist.sum
    pumps, absorbs = [], []
    pump, absorb = leader._pump_peer, leader.mr.handle_append_resps

    def pump_watched(peer):
        pumps.append(peer)
        pump(peer)

    def absorb_watched(resps):
        absorbs.append(len(resps))
        return absorb(resps)

    leader._pump_peer = pump_watched
    leader.mr.handle_append_resps = absorb_watched
    readers = [threading.Thread(target=net.respond, args=(i,))
               for i in frames]
    with leader.lock:
        for t in readers:
            t.start()
        deadline = time.monotonic() + 10.0
        while len(leader._acks) < len(frames):
            assert time.monotonic() < deadline, "readers never queued"
            time.sleep(0.005)
    for t in readers:
        t.join(10.0)
    assert absorbs == [len(frames)]
    assert (hist.count - n0, hist.sum - s0) == (1, len(frames))
    assert sorted(pumps) == [1, 2]
    assert not leader._acks
    last = np.asarray(leader.mr.state.last)
    assert (leader.mr.commit_index() == last).all()
    assert (np.asarray(leader.mr.state.match)[:, 1:]
            == last[:, None]).all()


def test_a_queued_ack_lands_before_a_failure_of_its_peer(cluster):
    servers, net = cluster
    leader = servers[0]
    first = in_flight(leader, net)
    leader._leader_round([pend(0, "x")])
    to_1 = [i for i in range(first[-1] + 1, len(net.frames))
            if net.frames[i]["dst"] == 1]
    acked = next(i for i in first if net.frames[i]["dst"] == 1)
    order = []
    absorb, probe = leader.mr.handle_append_resps, leader.mr.probe_reset
    leader.mr.handle_append_resps = \
        lambda r: order.append("absorb") or absorb(r)
    leader.mr.probe_reset = \
        lambda p: order.append("probe_reset") or probe(p)
    queue_ack(leader, net, acked)
    net.fail(to_1[0])
    assert order == ["absorb", "probe_reset"]
    want = unmarshal_any(net.frames[acked]["resp"]).acked
    assert (np.asarray(leader.mr.state.match)[:, 1] >= want).all()


@pytest.mark.parametrize("first", [1, 2])
def test_the_first_response_that_closed_a_quorum_is_credited(cluster,
                                                             first):
    servers, net = cluster
    leader = servers[0]
    frames = in_flight(leader, net)
    by_peer = {net.frames[i]["dst"]: i for i in frames}

    def credits():
        return {p: _obs.registry.counter(
            "etcd_dist_commit_advance_acks_total", peer=str(p)).get()
            for p in (1, 2)}

    c0 = credits()
    for peer in (first, 3 - first):
        queue_ack(leader, net, by_peer[peer])
    with leader.lock:
        leader._drain_acks()
    c1 = credits()
    assert {p: c1[p] - c0[p] for p in c1} == {first: 1, 3 - first: 0}


def test_a_batch_confirms_no_read_registered_after_its_frames(cluster):
    servers, net = cluster
    leader = lease_off(servers, net)
    warm = register(leader, 0)         # puts a frame a peer in flight
    old = [i for i in range(len(net.frames) - 2, len(net.frames))]
    ch = register(leader, 0)           # registered behind them
    for i in old:
        net.process(i)
        queue_ack(leader, net, i)
    n = len(net.frames)
    with leader.lock:
        leader._drain_acks()           # both acknowledgements, one take
    assert closed(warm) is not None
    assert closed(ch) is None and leader._reads.pending == 1
    # the batch's re-pump sent the read its own frame, a peer
    again = net.frames[n:]
    assert sorted(f["dst"] for f in again) == [1, 2]
    deliver(net, again[0])
    assert closed(ch) is not None
