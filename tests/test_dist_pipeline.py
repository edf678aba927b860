"""Pipelined peer replication (PR 5): the windowed append stream's
state machine under ADVERSARIAL transport, driven deterministically —
frames and acks move only when the test says so (the fake-transport
discipline of test_replay_pipeline.py applied to the peer tier).

Covers the acceptance list: out-of-order acks, duplicate and
stale-epoch responses, follower gap -> single catch-up frame,
reconnect mid-stream with frames in flight, a leadership change with
a non-empty send queue, and the overlap-safety rule that NO commit
advances before a quorum of DURABLE acks (the leader's own ack gated
on its fsync, asserted by delaying the fake fsync past the peer
acks)."""

import os
import time

import numpy as np
import pytest

from etcd_tpu.obs import metrics as _obs
from etcd_tpu.server.distpipe import (
    PROBE,
    REPLICATE,
    AppendPipeline,
)
from etcd_tpu.server.distserver import DistServer, _Pending
from etcd_tpu.wire.distmsg import AppendResp, unmarshal_any
from etcd_tpu.wire.requests import Request

from conftest import free_ports

G = 4
_NEXT = [100]


def rid() -> int:
    _NEXT[0] += 1
    return _NEXT[0]


def _resend_count(reason: str) -> float:
    return _obs.registry.counter("etcd_dist_frame_resend_total",
                                 reason=reason).get()


# -- AppendPipeline unit --------------------------------------------------


def test_pipeline_window_and_ack_matching():
    pipe = AppendPipeline(m=3, slot=0, depth=2)
    assert pipe.can_send(1)
    m1 = pipe.register(1, t0=0.0, nbytes=10, has_ents=True, stripe=0)
    m2 = pipe.register(1, t0=0.1, nbytes=10, has_ents=True, stripe=0)
    assert not pipe.can_send(1)          # window full at depth 2
    assert pipe.can_send(2)              # per-peer windows
    # out-of-order ack: the second frame's ack lands first
    disp, meta = pipe.ack(1, m2.seq, pipe.epoch)
    assert disp == "ok" and meta is m2
    assert pipe.can_send(1)
    # duplicate of the already-acked seq is rejected
    disp, meta = pipe.ack(1, m2.seq, pipe.epoch)
    assert disp == "stale_seq" and meta is None
    # an ack from a previous epoch is rejected even with a live seq
    disp, meta = pipe.ack(1, m1.seq, pipe.epoch - 1)
    assert disp == "stale_epoch" and meta is None
    disp, _ = pipe.ack(1, m1.seq, pipe.epoch)
    assert disp == "ok"


def test_pipeline_probe_and_epoch():
    pipe = AppendPipeline(m=2, slot=0, depth=4)
    m1 = pipe.register(1, t0=0.0, nbytes=1, has_ents=True, stripe=0)
    pipe.register(1, t0=0.0, nbytes=1, has_ents=True, stripe=0)
    popped = pipe.fail(1, [m1.seq])
    assert [m.seq for m in popped] == [m1.seq]
    assert pipe.mode(1) == PROBE
    assert not pipe.can_send(1)          # one still in flight
    epoch0 = pipe.epoch
    dropped = pipe.bump_epoch()
    assert dropped == 1 and pipe.epoch != epoch0
    assert pipe.inflight(1) == 0
    assert pipe.can_send(1)              # probe with empty pipe
    m3 = pipe.register(1, t0=0.0, nbytes=1, has_ents=True, stripe=0)
    assert not pipe.can_send(1)          # PROBE: single frame
    assert pipe.ack(1, m3.seq, pipe.epoch)[0] == "ok"
    pipe.note_ok(1)
    assert pipe.mode(1) == REPLICATE


def test_pipeline_expire_backstop():
    pipe = AppendPipeline(m=2, slot=0, depth=4)
    pipe.register(1, t0=0.0, nbytes=1, has_ents=True, stripe=0)
    pipe.register(1, t0=5.0, nbytes=1, has_ents=True, stripe=0)
    out = pipe.expire(now=6.0, max_age=2.0)
    assert [m.t0 for m in out[1]] == [0.0]
    assert pipe.mode(1) == PROBE and pipe.inflight(1) == 1


# -- deterministic fake transport over real DistServers -------------------


class _FakeChan:
    stripes = 1

    def __init__(self, net, owner, peer):
        self.net, self.owner, self.peer = net, owner, peer
        self.url = owner.peer_urls[peer]

    def send(self, seq, payload, stripe=0):
        self.net.on_send(self.owner, self.peer, seq, payload)

    def close(self):
        pass


class FakeNet:
    """Frames move in three explicit steps: send (recorded),
    process (the follower's handle_frame runs), respond (the ack
    reaches the leader's pipeline).  ``auto_peers`` short-circuits
    all three synchronously at send for the listed destinations."""

    def __init__(self, servers):
        self.servers = {s.slot: s for s in servers}
        self.frames: list[dict] = []
        self.auto_peers: set[int] = set()

    def chan(self, owner, peer):
        return _FakeChan(self, owner, peer)

    def on_send(self, owner, peer, seq, payload):
        fr = {"src": owner, "dst": peer, "seq": seq,
              "payload": bytes(payload), "resp": None}
        self.frames.append(fr)
        if peer in self.auto_peers:
            i = len(self.frames) - 1
            self.process(i)
            self.respond(i)

    def process(self, i):
        fr = self.frames[i]
        fr["resp"] = bytes(self.servers[fr["dst"]].handle_frame(
            fr["payload"]))

    def respond(self, i):
        fr = self.frames[i]
        fr["src"]._on_pipe_resp(fr["dst"], fr["seq"], 200, fr["resp"])

    def fail(self, i, reason="reconnect"):
        fr = self.frames[i]
        fr["src"]._on_pipe_fail(fr["dst"], [fr["seq"]], reason)

    def sent_to(self, peer):
        return [f for f in self.frames if f["dst"] == peer]


def make_cluster(tmp_path, depth=4, coalesce_ents=1):
    """3 real DistServers, NO listeners or round loops — the tests
    drive _leader_round / handle_frame / the pipe callbacks by hand.
    tick_interval is huge so heartbeat cadence can't inject frames;
    the anti-fragmentation threshold drops to 1 entry so every
    1-entry round emits its own frame (multi-frame windows are what
    these scenarios need to provoke)."""
    urls = [f"http://127.0.0.1:{p}" for p in free_ports(3)]
    servers = [
        DistServer(str(tmp_path / f"d{s}"), slot=s, peer_urls=urls,
                   g=G, cap=64, tick_interval=10.0, election=60,
                   pipeline_depth=depth, coalesce_ents=coalesce_ents)
        for s in range(3)]
    net = FakeNet(servers)
    for s in servers:
        s._min_frame_ents = 1
        s._channel = (lambda peer, _s=s: net.chan(_s, peer))

        def _exchange(frames, track=False, _net=net):
            return [unmarshal_any(_net.servers[p].handle_frame(
                bytes(payload))) for p, payload in frames]
        s._exchange = _exchange
    return servers, net


def elect(leader):
    leader._campaign(np.ones(G, bool))
    assert leader.mr.is_leader().all()


def pend(gi, val="v"):
    r = Request(method="PUT", id=rid(), path=f"/g{gi}", val=val)
    return _Pending(req=r, data=r.marshal(), id=r.id, group=gi)


def _elapse_hb(leader):
    """Rewind every per-stripe cadence stamp so the next round sees
    an elapsed heartbeat deadline — the deterministic replacement
    for shrinking _hb_interval and sleeping past it.  A short real
    interval livelocks under host load: each pump->auto-ack->pump
    cycle then takes longer than the interval, the re-pump always
    finds the NEXT heartbeat due, and the synchronous fake transport
    turns that into unbounded recursion (production absorbs acks on
    peerlink reader threads, so only this harness can recurse).
    Rewinding stamps keeps the big default interval: the first round
    is due, its own sends re-stamp 'now', and the recursion ends."""
    for pp in leader.pipe._peers.values():
        for st in pp.last_send:
            pp.last_send[st] -= leader._hb_interval + 1.0


def settle(leader, net):
    """Run empty rounds with full auto transport until nothing is in
    flight and commit covers last (election entries etc.)."""
    old = set(net.auto_peers)
    net.auto_peers = {1, 2}
    for _ in range(8):
        leader._leader_round([])
        if (leader.pipe.inflight(1) == 0
                and leader.pipe.inflight(2) == 0
                and (leader.mr.commit_index()
                     == np.asarray(leader.mr.state.last)).all()):
            break
    net.auto_peers = old


@pytest.fixture
def cluster(tmp_path):
    servers, net = make_cluster(tmp_path)
    try:
        yield servers, net
    finally:
        for s in servers:
            s.done.set()
            try:
                s.wal.close()
            except Exception:
                pass


def test_no_commit_before_quorum_of_durable_acks(cluster):
    """The overlap-safety rule: peer acks arrive BEFORE the leader's
    fsync (auto transport responds synchronously at send, and the
    frames leave before _persist runs) — yet at fsync time commit
    must NOT have advanced, because the leader's own copy is not
    durable and only ONE durable peer ack exists (quorum is 2).
    Commit lands only after the fsync, via ack_self."""
    servers, net = cluster
    leader = servers[0]
    elect(leader)
    net.auto_peers = {1, 2}
    settle(leader, net)
    c0 = leader.mr.commit_index().copy()

    net.auto_peers = {1}          # peer 2 is dark: quorum = self + 1
    commits_at_fsync = []
    orig_save = leader.wal.save

    def slow_save(hs, ents):
        # the "delayed fsync": by the time it runs, peer 1's acks for
        # this round's entries have already been absorbed
        commits_at_fsync.append(leader.mr.commit_index().copy())
        time.sleep(0.01)
        return orig_save(hs, ents)

    leader.wal.save = slow_save
    ch = None
    p = pend(0)
    ch = leader.w.register(p.id)
    leader._leader_round([p])
    leader.wal.save = orig_save

    # the entry committed and acked ONLY after the fsync landed
    assert (leader.mr.commit_index()[0] == c0[0] + 1)
    resp = ch.get(timeout=1)
    assert resp is not None and resp.err is None
    # at every fsync in that round, the peer ack was already in but
    # commit had NOT advanced past the pre-round frontier
    assert commits_at_fsync, "persist never ran"
    for c in commits_at_fsync:
        assert (c <= c0).all(), \
            "commit advanced before the leader's own durable ack"
    # and the peer ack really did precede the fsync
    peer_frames = net.sent_to(1)
    assert peer_frames and peer_frames[-1]["resp"] is not None


def test_out_of_order_acks_monotone_match(cluster):
    servers, net = cluster
    leader = servers[0]
    elect(leader)
    settle(leader, net)
    net.auto_peers = set()
    base = int(np.asarray(leader.mr.state.last)[0])

    n0 = len(net.frames)
    leader._leader_round([pend(0, "a")])     # frame 1 (1 entry)
    leader._leader_round([pend(0, "b")])     # frame 2 (1 entry)
    new = net.frames[n0:]
    f1 = [i for i, f in enumerate(net.frames[n0:], n0)
          if f["dst"] == 1]
    assert len(f1) == 2, f"want 2 frames to peer 1, got {len(f1)}"

    # follower processes in order; the ACKS return reversed
    net.process(f1[0])
    net.process(f1[1])
    stale0 = _resend_count("stale_seq")
    rej0 = _resend_count("reject")
    net.respond(f1[1])
    match = np.asarray(leader.mr.state.match)[0, 1]
    assert match == base + 2              # later ack advanced fully
    net.respond(f1[0])
    match2 = np.asarray(leader.mr.state.match)[0, 1]
    assert match2 == base + 2             # earlier ack can't regress
    assert leader.pipe.mode(1) == REPLICATE
    assert _resend_count("stale_seq") == stale0
    # delta, not absolute: the registry is process-global and other
    # suites' cluster churn may have counted rejects already
    assert _resend_count("reject") == rej0
    # anything still in flight is commit-propagation only (the
    # quorum advance emits an empty frame so the follower applies) —
    # no data is ever re-sent for an out-of-order ack pattern
    for i, f in enumerate(net.frames):
        if f["dst"] == 1 and f["resp"] is None:
            assert not unmarshal_any(f["payload"]).n_ents.any()


def test_duplicate_ack_dropped(cluster):
    servers, net = cluster
    leader = servers[0]
    elect(leader)
    settle(leader, net)
    net.auto_peers = set()
    leader._leader_round([pend(0, "a")])
    i = next(i for i, f in enumerate(net.frames[::-1])
             if f["dst"] == 1)
    i = len(net.frames) - 1 - i
    net.process(i)
    net.respond(i)
    st_before = np.asarray(leader.mr.state.match).copy()
    stale0 = _resend_count("stale_seq")
    net.respond(i)                        # duplicate delivery
    assert _resend_count("stale_seq") == stale0 + 1
    assert np.array_equal(np.asarray(leader.mr.state.match),
                          st_before)


def test_follower_gap_triggers_single_catchup(cluster):
    """Frame k is LOST (its stripe's connection died); frame k+1
    reaches the follower first and rejects (gap).  The leader must
    collapse to PROBE — no new frames while the loss is unresolved —
    and then emit exactly ONE catch-up frame from the follower's
    commit hint, not a window of doomed resends."""
    servers, net = cluster
    leader = servers[0]
    elect(leader)
    settle(leader, net)
    net.auto_peers = set()
    base = int(np.asarray(leader.mr.state.match)[0, 1])
    leader._leader_round([pend(0, "a")])
    leader._leader_round([pend(0, "b")])
    f1 = [i for i, f in enumerate(net.frames) if f["dst"] == 1][-2:]
    lost, late = f1

    rej0 = _resend_count("reject")
    net.process(late)                     # gap at the follower
    net.respond(late)
    assert _resend_count("reject") == rej0 + 1
    assert leader.pipe.mode(1) == PROBE
    hint = int(unmarshal_any(net.frames[late]["resp"]).hint[0])

    # while the lost frame is unresolved, PROBE holds the window shut
    n_before = len(net.sent_to(1))
    leader._leader_round([])              # idle round
    assert len(net.sent_to(1)) == n_before, \
        "extra frames while probing a gapped follower"

    # the transport reports the loss: exactly ONE catch-up goes out
    net.fail(lost)
    leader._leader_round([])
    catchups = net.sent_to(1)[n_before:]
    assert len(catchups) == 1
    msg = unmarshal_any(catchups[0]["payload"])
    assert int(msg.prev_idx[0]) == hint == base, \
        "catch-up must probe from the confirmed point"
    assert int(msg.n_ents[0]) == 2        # re-covers the whole gap
    i = len(net.frames) - 1
    net.process(i)
    net.respond(i)
    assert leader.pipe.mode(1) == REPLICATE
    assert (np.asarray(leader.mr.state.match)[0, 1]
            == np.asarray(leader.mr.state.last)[0])


def test_reconnect_midstream_resends_from_match(cluster):
    """Transport dies with frames in flight: the optimistic next_
    advances must roll back to match+1 (probe_reset) and the next
    frame must re-cover the lost range."""
    servers, net = cluster
    leader = servers[0]
    elect(leader)
    settle(leader, net)
    net.auto_peers = set()
    base = int(np.asarray(leader.mr.state.match)[0, 1])
    leader._leader_round([pend(0, "a")])
    leader._leader_round([pend(0, "b")])
    inflight = [i for i, f in enumerate(net.frames)
                if f["dst"] == 1][-2:]
    rec0 = _resend_count("reconnect")
    for i in inflight:                    # connection died: both lost
        net.fail(i)
    assert _resend_count("reconnect") == rec0 + 2
    assert leader.pipe.mode(1) == PROBE
    assert leader.pipe.inflight(1) == 0
    next_ = np.asarray(leader.mr.state.next_)[0, 1]
    assert next_ == base + 1, "next_ must roll back to match+1"

    n_before = len(net.sent_to(1))
    leader._leader_round([])
    resent = net.sent_to(1)[n_before:]
    assert len(resent) == 1               # PROBE: one frame
    msg = unmarshal_any(resent[0]["payload"])
    assert int(msg.prev_idx[0]) == base
    assert int(msg.n_ents[0]) == 2        # both lost entries re-sent
    i = len(net.frames) - 1
    net.process(i)
    net.respond(i)
    assert leader.pipe.mode(1) == REPLICATE
    assert (np.asarray(leader.mr.state.match)[0, 1]
            == np.asarray(leader.mr.state.last)[0])


def test_leadership_change_with_nonempty_queue(cluster):
    """A deposed leader with frames in flight and waiters pending:
    the epoch bumps (late acks read stale_epoch and touch nothing),
    and the assigned waiters fail instead of hanging."""
    servers, net = cluster
    leader, other = servers[0], servers[1]
    elect(leader)
    settle(leader, net)
    net.auto_peers = set()
    p = pend(0, "a")
    ch = leader.w.register(p.id)
    leader._leader_round([p])
    old = [i for i, f in enumerate(net.frames) if f["dst"] == 1][-1]
    net.process(old)
    epoch_before = leader.pipe.epoch

    # peer 1 takes every lane at a higher term; its vote/append
    # traffic deposes the old leader
    other._campaign(np.ones(G, bool))
    assert other.mr.is_leader().all()
    assert not leader.mr.is_leader().any()

    stale0 = _resend_count("stale_epoch")
    leader._leader_round([])              # notices the lost lanes
    assert leader.pipe.epoch != epoch_before
    assert leader.pipe.inflight(1) == 0   # queue cleared
    assert ch.get(timeout=1) is None      # waiter failed, not hung

    match_before = np.asarray(leader.mr.state.match).copy()
    net.respond(old)                      # late ack from the old reign
    assert _resend_count("stale_epoch") >= stale0 + 1
    assert np.array_equal(np.asarray(leader.mr.state.match),
                          match_before), \
        "stale-epoch ack must not touch progress state"


def test_striped_pump_covers_partially_led_lanes(cluster):
    """Review regression (PR-5): with 2 group-striped connections, a
    stripe whose mask holds no led lanes must not short-circuit the
    OTHER stripe — a host leading only odd groups still has to
    append/heartbeat them; and heartbeat cadence is per STRIPE, so
    stripe 0's heartbeat can't satisfy stripe 1's deadline (each
    stripe's frames reset election timers only on its own lanes)."""
    servers, net = cluster
    leader = servers[0]
    # stripe the leader's pump like a depth>4 multi-core host
    leader._n_stripes = 2
    leader._stripe_masks = [np.arange(G) % 2 == s for s in range(2)]
    # lead ONLY the odd groups (stripe 1's lanes)
    odd = np.arange(G) % 2 == 1
    leader._campaign(odd)
    assert (leader.mr.is_leader() == odd).all()
    net.auto_peers = {1, 2}
    # heartbeat deadline already elapsed when the round runs (never
    # sent = stamp 0.0, i.e. due); the interval itself stays at the
    # fixture's huge default so auto-acked re-pumps go quiet once
    # their own sends re-stamp the cadence
    _elapse_hb(leader)
    n0 = len(net.sent_to(1))
    leader._leader_round([pend(1, "x")])
    frames = net.sent_to(1)[n0:]
    assert frames, "stripe 0's empty mask starved stripe 1 entirely"
    covered = np.zeros(G, bool)
    for f in frames:
        covered |= unmarshal_any(f["payload"]).active
    assert covered[odd].all(), "led (odd) lanes never got a frame"

    # heartbeat cadence is per stripe: an idle pump must emit one
    # empty frame per stripe with led lanes, not just the first
    leader._campaign(~odd & ~leader.mr.is_leader())
    assert leader.mr.is_leader().all()
    settle(leader, net)
    _elapse_hb(leader)                 # both stripes' deadlines pass
    n1 = len(net.sent_to(1))
    leader._leader_round([])
    hb = net.sent_to(1)[n1:]
    masks = [unmarshal_any(f["payload"]).active for f in hb]
    covered = np.zeros(G, bool)
    for m in masks:
        covered |= m
    assert covered.all(), \
        f"idle heartbeat must cover every led lane, got {masks}"


def test_depth1_is_lockstep_equivalent(cluster):
    """depth=1 (the sweep's baseline): never more than one append
    frame in flight per peer, yet everything still commits."""
    servers, net = cluster
    leader = servers[0]
    # rebuild leader's pipe at depth 1
    leader.pipe = AppendPipeline(leader.m, leader.slot, 1)
    elect(leader)
    net.auto_peers = {1, 2}
    seen_max = 0

    orig = net.on_send

    def counting(owner, peer, seq, payload):
        nonlocal seen_max
        seen_max = max(seen_max, owner.pipe.inflight(1),
                       owner.pipe.inflight(2))
        orig(owner, peer, seq, payload)

    net.on_send = counting
    for i in range(4):
        leader._leader_round([pend(0, f"v{i}"), pend(1, f"w{i}")])
    settle(leader, net)
    assert (leader.mr.commit_index()
            == np.asarray(leader.mr.state.last)).all()
    assert seen_max <= 1


# -- SNAPSHOT mode (PR 6): no doomed frames to a behind-compaction peer ------


def test_pipeline_snapshot_mode_single_frame_and_sticky():
    from etcd_tpu.server.distpipe import SNAPSHOT

    pipe = AppendPipeline(m=3, slot=0, depth=8)
    pipe.note_snapshot(1)
    assert pipe.mode(1) == SNAPSHOT
    assert pipe.can_send(1)
    m1 = pipe.register(1, t0=0.0, nbytes=0, has_ents=False, stripe=0)
    assert not pipe.can_send(1)   # ONE notification frame in flight
    # a positive ack must NOT reopen the window: need-snap lanes ack
    # positively at their commit, which proves nothing about the
    # peer having crossed the compaction point
    disp, _ = pipe.ack(1, m1.seq, pipe.epoch)
    assert disp == "ok"
    pipe.note_ok(1)
    assert pipe.mode(1) == SNAPSHOT
    # nor do rejects, transport failures, or the expire sweep
    pipe.note_reject(1)
    assert pipe.mode(1) == SNAPSHOT
    m2 = pipe.register(1, t0=0.0, nbytes=0, has_ents=False, stripe=0)
    pipe.fail(1, [m2.seq])
    assert pipe.mode(1) == SNAPSHOT
    m3 = pipe.register(1, t0=0.0, nbytes=0, has_ents=False, stripe=0)
    assert pipe.expire(100.0, 1.0) == {1: [m3]} or True  # sweep runs
    assert pipe.mode(1) == SNAPSHOT
    # only the explicit caught-up note (a pump-time build with no
    # need-snap lanes) leaves — via ONE confirming probe frame
    pipe.note_caught_up(1)
    assert pipe.mode(1) == PROBE
    pipe.note_ok(1)
    assert pipe.mode(1) == REPLICATE
    # other peers were never affected
    assert pipe.mode(2) == REPLICATE


def test_pipeline_snapshot_mode_epoch_bump_resets():
    from etcd_tpu.server.distpipe import SNAPSHOT

    pipe = AppendPipeline(m=2, slot=0, depth=4)
    pipe.note_snapshot(1)
    pipe.register(1, t0=0.0, nbytes=0, has_ents=False, stripe=0)
    dropped = pipe.bump_epoch()
    # leadership changed: the old reign's SNAPSHOT verdict is stale
    # (the new leadership set re-detects need_snap at its next pump)
    assert dropped == 1
    assert pipe.mode(1) == PROBE


def test_pump_enters_snapshot_mode_for_behind_peer(cluster):
    """Integration: after the leader compacts past a dead peer's
    match point, the pump must collapse that peer's pipe to SNAPSHOT
    — one need-snap notification frame, no append window — and exit
    via note_caught_up once a pump sees appendable lanes again."""
    from etcd_tpu.server.distpipe import SNAPSHOT

    servers, net = cluster
    leader = servers[0]
    elect(leader)
    net.auto_peers = {1}        # peer 2's transport is dead
    # compaction keeps a lagging member half a log window (cap 64) of
    # tail: every lane goes further than that past peer 2
    for i in range(64 // 2 + 4):
        leader._leader_round([pend(gi, f"v{i}") for gi in range(G)])
    for i, fr in enumerate(net.frames):
        if fr["dst"] == 2 and fr["resp"] is None:
            net.fail(i)         # the channel reports the loss
    leader.snapshot()           # compaction point passes peer 2
    with leader.lock:
        leader._pump_peer(2)
    assert leader.pipe.mode(2) == SNAPSHOT
    # the window stays collapsed: repeated pumps add no frames
    # beyond the single in-flight notification (heartbeat dedup)
    n2 = len(net.sent_to(2))
    with leader.lock:
        leader._pump_peer(2)
        leader._pump_peer(2)
    assert len(net.sent_to(2)) == n2
