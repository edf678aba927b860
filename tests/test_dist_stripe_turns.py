"""The striped pump gives its stripes turns (PR 30).  A busy pipe
holds thin entry frames back (``_min_frame_ents``), and the pump used
to try stripe 0 first at every re-pump: under steady load stripe 0
always had something to send when its ack came back, so the odd lanes
waited for a lull — a write there took eight frame round trips, not
two, and met the server's 0.5 s timeout.  Driven over the
deterministic fake transport of ``test_dist_pipeline.py``: no thread,
no clock."""

import numpy as np

from test_dist_pipeline import (  # noqa: F401 - ``cluster`` is a fixture
    G, cluster, elect, pend, settle)

from etcd_tpu.wire.distmsg import unmarshal_any


def ents_of(frame) -> np.ndarray:
    return np.asarray(unmarshal_any(frame["payload"]).n_ents)


def test_a_held_stripe_goes_first_at_the_next_pump(cluster):
    servers, net = cluster
    leader = servers[0]
    leader._n_stripes = 2
    leader._stripe_masks = [np.arange(G) % 2 == s for s in range(2)]
    elect(leader)
    settle(leader, net)
    # the program's own threshold: a busy pipe holds every thin frame
    leader._min_frame_ents = 1024
    net.auto_peers = set()             # every step by hand
    leader._stripe_turn = {1: 0, 2: 0}  # whoever went last in settle
    n0 = len(net.sent_to(1))

    # writes on an even and an odd lane: stripe 0 goes, stripe 1 is
    # held behind it
    leader._leader_round([pend(0, "a"), pend(1, "b")])
    sent = net.sent_to(1)[n0:]
    assert len(sent) == 1
    assert ents_of(sent[0]).tolist() == [1, 0, 0, 0]
    # more writes while that frame is in flight: both stripes held
    leader._leader_round([pend(2, "c")])
    assert len(net.sent_to(1)) == n0 + 1

    def deliver(frame) -> list:
        i, before = net.frames.index(frame), len(net.sent_to(1))
        net.process(i)
        net.respond(i)                 # the ack re-pumps the peer
        return [f for f in net.sent_to(1)[before:] if ents_of(f).any()]

    # the ack: the held stripe goes first, though stripe 0 has new
    # entries too (which is what used to starve it)
    nxt = deliver(sent[0])
    assert len(nxt) == 1 and ents_of(nxt[0]).tolist() == [0, 1, 0, 0]
    # ... and then it is stripe 0's turn again
    nxt = deliver(nxt[0])
    assert len(nxt) == 1 and ents_of(nxt[0]).tolist() == [0, 0, 1, 0]
    deliver(nxt[0])
    # everything committed on the leader's and this follower's word
    assert (leader.mr.commit_index()
            == np.asarray(leader.mr.state.last)).all()
    assert leader._stripe_turn[1] in (0, 1)
