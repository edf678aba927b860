"""A confirmed read's way through the leader with the lease off (PR
35), driven over the deterministic fake transport of
``test_dist_pipeline.py``: no thread, no clock.  A read that
registers in the ReadIndex queue sends its confirmation frame at once
where none of its stripe is in flight, rides the next ack's re-pump
where one is, costs the round thread no wake, and is released by the
sweep that comes BEFORE the engine absorbs the acknowledgement.  None
of it may serve a read early: no channel closes before a quorum
acknowledged a frame sent after the read registered."""

import queue
import time

import numpy as np
import pytest

from test_dist_pipeline import (  # noqa: F401 - ``cluster`` is a fixture
    G, cluster, elect, pend, settle)

from etcd_tpu.utils.wait import Chan
from etcd_tpu.wire.distmsg import unmarshal_any


def lease_off(servers, net):
    leader = servers[0]
    leader._n_stripes = 2
    leader._stripe_masks = [np.arange(G) % 2 == s for s in range(2)]
    elect(leader)
    settle(leader, net)
    leader._lease_s = 0.0              # --dist-lease-ticks 0
    net.auto_peers = set()             # every step by hand
    return leader


def register(leader, gi: int) -> Chan:
    """What ``_linz_read`` does under the lock for a led lane."""
    ch = Chan()
    with leader.lock:
        t0 = time.monotonic()
        assert not leader._lease_fast_ok(gi, t0)
        leader._reads.register(gi, t0, int(leader.applied[gi]), ch)
        leader._nudge_reads(t0, (gi,))
    return ch


def closed(ch: Chan):
    try:
        return ch.get(timeout=0)
    except queue.Empty:
        return None


def deliver(net, frame) -> None:
    i = net.frames.index(frame)
    net.process(i)
    net.respond(i)


def lanes_of(frame) -> list[bool]:
    return np.asarray(unmarshal_any(frame["payload"]).active).tolist()


def test_a_read_sends_its_confirmation_at_once_and_wakes_nobody(cluster):
    servers, net = cluster
    leader = lease_off(servers, net)
    n0 = len(net.frames)
    ch = register(leader, 2)           # an even lane: stripe 0
    sent = net.frames[n0:]
    # one empty frame a peer, of the read's stripe alone, from the
    # caller's thread; nothing in the round thread's queue
    assert [f["dst"] for f in sent] == [1, 2]
    for f in sent:
        msg = unmarshal_any(f["payload"])
        assert not np.asarray(msg.n_ents).any()
        assert lanes_of(f) == [True, False, True, False]
    assert leader._queue.empty()
    assert closed(ch) is None          # sent is not confirmed
    deliver(net, sent[0])              # one of two peers: a quorum
    assert closed(ch) == ("read_index",
                          max(int(leader.applied[2]),
                              int(leader._read_floor[2])))


def test_reads_behind_a_frame_in_flight_ride_one_re_pump(cluster):
    servers, net = cluster
    leader = lease_off(servers, net)
    first = register(leader, 0)
    n1 = len(net.frames)
    # three more reads of the stripe while its frame is in flight:
    # no frame each (they used to fill the window, one a nudge)
    later = [register(leader, gi) for gi in (0, 2, 2)]
    assert len(net.frames) == n1
    # a read of the OTHER stripe has nothing in flight: it sends
    odd = register(leader, 1)
    assert [lanes_of(f) for f in net.frames[n1:]] == [
        [False, True, False, True]] * 2
    to_peer1 = [f for f in net.sent_to(1)[-2:]]
    n2 = len(net.sent_to(1))
    deliver(net, to_peer1[0])          # stripe 0's first frame
    assert closed(first) is not None
    # the frame was sent BEFORE the later reads registered: they are
    # not confirmed by it
    assert [closed(ch) for ch in later] == [None] * 3
    assert closed(odd) is None
    # ... and the ack's re-pump sent ONE frame for all three
    again = net.sent_to(1)[n2:]
    assert [lanes_of(f) for f in again] == [[True, False, True, False]]
    deliver(net, again[0])
    assert all(closed(ch) is not None for ch in later)
    assert closed(odd) is None         # its own stripe's ack is due
    deliver(net, to_peer1[1])
    assert closed(odd) is not None
    assert leader._reads.pending == 0


def test_the_release_sweep_comes_before_the_engine_s_absorb(cluster):
    servers, net = cluster
    leader = lease_off(servers, net)
    ch = register(leader, 0)
    frame = net.sent_to(1)[-1]
    seen = []
    absorb = leader.mr.handle_append_resps

    def watched(resps):
        seen.append(leader._reads.pending)
        return absorb(resps)

    leader.mr.handle_append_resps = watched
    deliver(net, frame)
    assert seen == [0]                 # released, then absorbed
    assert closed(ch) is not None


def test_an_acknowledgement_of_an_older_frame_confirms_nothing(cluster):
    """The guarantee the pacing must not touch: a frame that left
    before the read registered proves nothing about it."""
    servers, net = cluster
    leader = lease_off(servers, net)
    warm = register(leader, 0)         # puts a frame in flight
    old = [f for f in net.frames[-2:]]
    ch = register(leader, 0)           # registered behind it
    for f in old:                      # BOTH peers acknowledge it
        deliver(net, f)
    assert closed(warm) is not None
    assert closed(ch) is None
    with leader.lock:
        leader._read_release()
    assert closed(ch) is None and leader._reads.pending == 1


def test_writes_keep_their_place_in_the_round_thread_s_queue(cluster):
    """Readers queue nothing in front of a write: the next drain
    returns the write, however many reads registered before it."""
    servers, net = cluster
    leader = lease_off(servers, net)
    for gi in (0, 1, 2, 3, 0, 1):
        register(leader, gi)
    leader._queue.put(pend(3, "w"))
    batch = leader._drain(timeout=0.0)
    assert [p.req.val for p in batch] == ["w"]


@pytest.mark.parametrize("stripe,want", [(0, 2), (1, 1), (2, 0)])
def test_pipeline_counts_a_stripe_s_frames_in_flight(stripe, want):
    from etcd_tpu.server.distpipe import AppendPipeline

    pipe = AppendPipeline(3, 0, depth=8)
    metas = [pipe.register(1, t0=float(i), nbytes=0, has_ents=False,
                           stripe=s) for i, s in enumerate((0, 1, 0))]
    pipe.register(2, t0=9.0, nbytes=0, has_ents=True, stripe=2)
    assert pipe.inflight_stripe(1, stripe) == want
    pipe.ack(1, metas[0].seq, pipe.epoch)
    assert pipe.inflight_stripe(1, 0) == 1
