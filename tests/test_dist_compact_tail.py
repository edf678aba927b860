"""A leader's compaction keeps the log tail its slowest member has not
confirmed (PR 30).  Commit needs a quorum only, so under steady load
one follower of three is a frame behind on some lane at any moment; a
cut at ``applied`` put that follower's next entry behind the offset,
and a lag of a few entries cost a pull of the whole store, which on
the v5e did not end while the cluster served.  Driven over the
deterministic fake transport of ``test_dist_pipeline.py``: no thread,
no clock."""

import numpy as np
import pytest

from test_dist_pipeline import (  # noqa: F401 - ``cluster`` is a fixture
    cluster, elect, pend, settle)

from etcd_tpu.snap.stream import ChunkPuller, ChunkVerifier

CAP = 64  # make_cluster's log window


def write(leader, n, lane=0):
    for i in range(n):
        leader._leader_round([pend(lane, f"v{i}")])


def lose_frames_to(net, leader, peer):
    """Every unanswered frame to ``peer`` fails: the leader falls back
    to the index that peer last confirmed."""
    for i, fr in enumerate(net.frames):
        if fr["dst"] == peer and fr["resp"] is None:
            net.fail(i)
    assert leader.pipe.inflight(peer) == 0


def test_compaction_stops_at_the_slowest_followers_confirmed_index(
        cluster):
    servers, net = cluster
    leader = servers[0]
    elect(leader)
    settle(leader, net)
    net.auto_peers = {1}            # follower 2 hears nothing
    confirmed = int(np.asarray(leader.mr.state.match)[0, 2])
    write(leader, 6)
    # committed and applied on the leader's and follower 1's word
    last = int(np.asarray(leader.mr.state.last)[0])
    assert last == confirmed + 6 and leader.applied[0] == last
    lose_frames_to(net, leader, 2)

    leader.mr.compact()
    offset = np.asarray(leader.mr.state.offset)
    assert offset[0] == confirmed   # not ``applied``
    assert (offset[1:] == leader.applied[1:]).all()
    # follower 2 catches up from the log: no lane asks for a snapshot
    b = leader.mr.build_append(2)
    assert not np.asarray(b.need_snap).any()
    assert int(np.asarray(b.n_ents)[0]) == 6
    net.auto_peers = {1, 2}
    settle(leader, net)
    assert not servers[2]._need_pull
    assert servers[2].mr.commit_index()[0] == last
    # once it has confirmed them the tail goes
    leader.mr.compact()
    assert np.asarray(leader.mr.state.offset)[0] == last


def test_a_dark_member_holds_back_half_a_window_and_no_more(cluster):
    servers, net = cluster
    leader = servers[0]
    elect(leader)
    settle(leader, net)
    net.auto_peers = {1}
    write(leader, CAP // 2 + 8)
    lose_frames_to(net, leader, 2)
    leader.mr.compact()
    st = leader.mr.state
    assert (np.asarray(st.offset)[0]
            == np.asarray(st.applied)[0] - CAP // 2)
    # past that bound the member installs a snapshot, as before
    assert np.asarray(leader.mr.build_append(2).need_snap)[0]


def test_a_follower_lane_cuts_at_its_applied_index(cluster):
    servers, net = cluster
    leader = servers[0]
    elect(leader)
    net.auto_peers = {1, 2}
    settle(leader, net)
    write(leader, 5)
    settle(leader, net)
    follower = servers[1]
    follower.mr.compact()
    assert (np.asarray(follower.mr.state.offset)
            == follower.applied).all()


def test_the_snapshot_stream_verifies_on_the_host_by_default():
    """The device form uploads ``contribution_matrix(chunk + 4)``
    (64 MiB for a 256 KiB chunk) on every call and compiles a program
    a width, on the serving interpreter, for a digest the host takes
    in 0.06 ms (module docstring of ``snap/stream.py``): a caller has
    to name it."""
    assert ChunkVerifier().route == "host"
    meta = {"n_chunks": 0, "size": 0, "chunk_bytes": 4, "crcs": [],
            "id": "x"}
    puller = ChunkPuller("http://127.0.0.1:9", meta)
    try:
        assert puller.verifier.route == "host"
    finally:
        puller.close()
    assert ChunkVerifier(route="device").route == "device"
    with pytest.raises(ValueError):
        ChunkVerifier(route="auto")


def test_an_apply_during_the_snapshot_asks_for_no_second_one(cluster):
    """``_apply_committed`` raises ``_want_snap`` while ``raft_index``
    is past ``_snapi + snap_count``, and ``_snapi`` moves only when
    the snapshot is done: on the v5e every member's snapshot was
    followed by a second one 0.25 s later (PR 30)."""
    servers, net = cluster
    leader = servers[0]
    elect(leader)
    net.auto_peers = {1, 2}
    settle(leader, net)
    leader.snap_count = 5
    write(leader, 8)
    assert leader._want_snap
    leader._want_snap = False       # the round loop takes the flag ...
    save = leader.ss.save_snap

    def save_while_writes_go_on(snap):
        write(leader, 1)            # ... and an apply lands meanwhile
        assert leader._want_snap
        return save(snap)

    leader.ss.save_snap = save_while_writes_go_on
    leader.snapshot()
    assert not leader._want_snap
    assert leader._snapi == leader.raft_index
    write(leader, 5)                # snap_count entries later: not yet
    assert not leader._want_snap
    write(leader, 1)
    assert leader._want_snap
