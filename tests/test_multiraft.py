"""Co-hosted multi-raft runtime: batched cluster behavior.

The batched analog of the reference's in-process cluster tests
(server_test.go:370-447 TestClusterOf1/Of3) and the fake-network
election matrix (raft_test.go:27-240) — G groups live through
elections, replication, leader loss, and divergent-log repair at once.
"""

import functools

import numpy as np
import pytest

from etcd_tpu.raft.batched import LEADER, term_at
from etcd_tpu.raft.multiraft import MultiRaft


def _logs_equal_live(mr, g, upto, live):
    """Live members agree on terms of entries [1, upto] of group g."""
    ref = None
    for slot in live:
        st = mr.states[slot]
        lt = np.asarray(term_at(st.log_term, st.offset, st.last,
                                np.tile(np.arange(1, upto + 1,
                                                  dtype=np.int32),
                                        (mr.g, 1))))[g]
        if ref is None:
            ref = lt
        elif not np.array_equal(ref, lt):
            return False
    return True


def _logs_equal(mr, g, upto):
    """All members agree on terms of entries [1, upto] of group g."""
    return _logs_equal_live(mr, g, upto, live=range(mr.m))


def test_campaign_elects_all_groups():
    mr = MultiRaft(g=16, m=3, cap=32)
    won = mr.campaign(0)
    assert won.all()
    assert (mr.leader == 0).all()
    assert (np.asarray(mr.states[0].role) == LEADER).all()
    # the empty becoming-leader entry replicates and commits
    np.testing.assert_array_equal(mr.commit_index(), 1)


def test_propose_commits_across_groups():
    mr = MultiRaft(g=16, m=5, cap=64)
    mr.campaign(0)
    n = np.full(16, 3, np.int32)
    newly = mr.propose(n)
    np.testing.assert_array_equal(newly, 3)
    np.testing.assert_array_equal(mr.commit_index(), 4)  # 1 empty + 3
    for g in range(16):
        assert _logs_equal(mr, g, 4)


def test_payload_store_roundtrip():
    mr = MultiRaft(g=4, m=3, cap=32)
    mr.campaign(0)
    data = [[f"g{g}-v{j}".encode() for j in range(2)] for g in range(4)]
    mr.propose(np.full(4, 2, np.int32), data=data)
    assert mr.committed_payload(2, 2) == b"g2-v0"
    assert mr.committed_payload(2, 3) == b"g2-v1"


def test_leader_change_and_log_repair():
    """Member 1 takes over some groups at a higher term; its log wins
    and followers converge (the dueling-logs repair path)."""
    mr = MultiRaft(g=8, m=3, cap=64)
    mr.campaign(0)
    mr.propose(np.full(8, 2, np.int32))
    # member 1 campaigns for half the groups
    mask = np.zeros(8, bool)
    mask[::2] = True
    won = mr.campaign(1, mask)
    assert won[::2].all() and not won[1::2].any()
    assert (mr.leader[::2] == 1).all()
    assert (mr.leader[1::2] == 0).all()
    # both leaders keep committing their groups
    mr.propose(np.full(8, 1, np.int32))
    for _ in range(4):
        mr.replicate()
    commits = mr.commit_index()
    assert (commits >= 4).all()
    for g in range(8):
        assert _logs_equal(mr, g, int(commits[g])), g


def test_tick_triggers_election():
    mr = MultiRaft(g=8, m=3, cap=32, election=4)
    for _ in range(10):
        mr.tick()
        if (mr.leader >= 0).all():
            break
    assert (mr.leader >= 0).all()
    mr.propose(np.full(8, 1, np.int32))
    for _ in range(3):
        mr.replicate()
    assert (mr.commit_index() >= 1).all()


def test_backlog_replicates_in_windows():
    """A backlog larger than the per-round window drains over
    successive replicate() rounds."""
    mr = MultiRaft(g=4, m=3, cap=128, max_batch_ents=4)
    mr.campaign(0)
    mr.propose(np.full(4, 20, np.int32))
    for _ in range(8):
        mr.replicate()
    np.testing.assert_array_equal(mr.commit_index(), 21)
    for g in range(4):
        assert _logs_equal(mr, g, 21)


def test_minority_cannot_commit():
    """With only 1 of 5 members reachable... the quorum math refuses:
    simulate by campaigning with a doctored nmembers view."""
    mr = MultiRaft(g=4, m=5, cap=32)
    mr.campaign(0)
    base = mr.commit_index().copy()
    # cut members 2..4 out of replication by marking them leaders of
    # nothing with huge terms (stale-leader guard drops the sends)
    import jax.numpy as jnp
    for peer in (2, 3, 4):
        st = mr.states[peer]
        mr.states[peer] = st._replace(
            term=st.term + 100)
    mr.propose(np.full(4, 1, np.int32))
    for _ in range(3):
        mr.replicate()
    # only member 1 acked: 2 of 5 < quorum(3) -> no commit advance
    np.testing.assert_array_equal(mr.commit_index(), base)


def test_steady_state_no_churn():
    """Healthy-leader heartbeats (replicate rounds) reset follower
    timers: no spurious elections, no term inflation."""
    mr = MultiRaft(g=8, m=3, cap=32, election=3)
    for _ in range(10):
        mr.tick()
        if (mr.leader >= 0).all():
            break
    lead0 = mr.leader.copy()
    term0 = np.max(np.stack([np.asarray(s.term) for s in mr.states]),
                   axis=0)
    for _ in range(12):  # 4x the election timeout
        mr.tick()
        mr.replicate()
    np.testing.assert_array_equal(mr.leader, lead0)
    term1 = np.max(np.stack([np.asarray(s.term) for s in mr.states]),
                   axis=0)
    np.testing.assert_array_equal(term1, term0)


def test_deposed_leader_propose_stores_nothing():
    """propose() against a member that was deposed (role no longer
    LEADER) must not deposit payloads or append."""
    import jax.numpy as jnp
    from etcd_tpu.raft.batched import FOLLOWER
    mr = MultiRaft(g=4, m=3, cap=32)
    mr.campaign(0)
    # depose member 0 everywhere without updating mr.leader
    st = mr.states[0]
    mr.states[0] = st._replace(
        role=jnp.full((4,), FOLLOWER, jnp.int32))
    before = {k: dict(v) for k, v in enumerate(mr.payloads)}
    mr.propose(np.full(4, 1, np.int32),
               data=[[b"stale"] for _ in range(4)])
    for gi in range(4):
        assert mr.payloads[gi] == before[gi]


def test_drop_mask_delays_but_converges():
    """Per-edge message drops (the lossy-network matrix): a dropped
    follower lags, quorum still commits, healing catches it up."""
    mr = MultiRaft(g=8, m=3, cap=64)
    mr.campaign(0)
    drop = {(0, 2): np.ones(8, bool)}  # isolate member 2 inbound
    mr.propose(np.full(8, 3, np.int32), drop=drop)
    for _ in range(3):
        mr.replicate(drop=drop)
    np.testing.assert_array_equal(mr.commit_index(), 4)  # 2-of-3 quorum
    lag = np.asarray(mr.states[2].last)
    assert (lag < 4).all()
    for _ in range(3):  # heal
        mr.replicate()
    assert (np.asarray(mr.states[2].last) == 4).all()
    assert (np.asarray(mr.states[2].commit) == 4).all()


def test_drop_both_followers_blocks_commit():
    mr = MultiRaft(g=4, m=3, cap=64)
    mr.campaign(0)
    base = mr.commit_index().copy()
    drop = {(0, 1): np.ones(4, bool), (0, 2): np.ones(4, bool)}
    mr.propose(np.full(4, 2, np.int32), drop=drop)
    for _ in range(3):
        mr.replicate(drop=drop)
    np.testing.assert_array_equal(mr.commit_index(), base)
    mr.replicate()  # heal: commit catches up
    np.testing.assert_array_equal(mr.commit_index(), base + 2)


def test_lost_ack_resends_idempotently():
    """Follower receives appends but its acks are dropped: leader
    retries the same window; duplicate appends are idempotent."""
    mr = MultiRaft(g=4, m=3, cap=64)
    mr.campaign(0)
    drop = {(1, 0): np.ones(4, bool)}  # member 1's responses lost
    mr.propose(np.full(4, 2, np.int32), drop=drop)
    for _ in range(2):
        mr.replicate(drop=drop)
    # member 1 HAS the entries but leader's match for it is stale;
    # member 2 alone still forms a 2/3 quorum with the leader
    np.testing.assert_array_equal(mr.commit_index(), 3)
    assert (np.asarray(mr.states[1].last) == 3).all()
    mr.replicate()  # acks flow again; no duplication, logs intact
    np.testing.assert_array_equal(mr.commit_index(), 3)
    for g in range(4):
        assert _logs_equal(mr, g, 3)


def test_truncated_payload_invalidated():
    """A deposed leader's uncommitted payload must not survive the
    election that truncates its entry (review repro)."""
    mr = MultiRaft(g=4, m=3, cap=64)
    mr.campaign(0)
    drop = {(0, 1): np.ones(4, bool), (0, 2): np.ones(4, bool)}
    mr.propose(np.full(4, 1, np.int32),
               data=[[b"STALE"] for _ in range(4)], drop=drop)
    assert mr.committed_payload(0, 2) == b"STALE"  # stored, uncommitted
    mr.campaign(1)  # winner's log lacks index 2; empty entry lands there
    for _ in range(3):
        mr.replicate()
    assert (mr.commit_index() >= 2).all()
    assert mr.committed_payload(0, 2) is None


def test_compact_and_snapshot_catchup():
    """Leader compaction strands a lagging follower behind the log
    window; the msgSnap path restores it and replication resumes
    (raft.go:207-209, needSnapshot)."""
    mr = MultiRaft(g=4, m=3, cap=64)
    mr.campaign(0)
    drop = {(0, 2): np.ones(4, bool)}  # member 2 isolated
    mr.propose(np.full(4, 6, np.int32), drop=drop)
    for _ in range(3):
        mr.replicate(drop=drop)
    np.testing.assert_array_equal(mr.commit_index(), 7)
    assert (np.asarray(mr.states[2].last) < 7).all()
    mr.mark_applied(mr.commit_index())
    mr.compact()  # leader log now starts at commit=7
    assert (np.asarray(mr.states[0].offset) == 7).all()
    for _ in range(3):  # heal: snapshot then normal appends
        mr.replicate()
    assert (np.asarray(mr.states[2].offset) == 7).all()
    assert (np.asarray(mr.states[2].commit) == 7).all()
    # replication continues past the snapshot
    mr.propose(np.full(4, 2, np.int32))
    for _ in range(2):
        mr.replicate()
    np.testing.assert_array_equal(mr.commit_index(), 9)
    assert (np.asarray(mr.states[2].last) == 9).all()


def test_per_group_overflow_isolated():
    """One group at log capacity stalls ALONE: its overflow lane
    raises per-group, every other group keeps committing (no
    batch-wide exception)."""
    mr = MultiRaft(g=4, m=3, cap=8)
    mr.campaign(0)  # commit=1 everywhere (becoming-leader entry)
    n = np.array([7, 1, 1, 1], np.int32)  # group 0: 1+7=8 >= cap
    newly = mr.propose(n, data=[[b"p%d" % j for j in range(7)],
                                [b"x"], [b"y"], [b"z"]])
    assert mr.errors["overflow"][0]
    assert not mr.errors["overflow"][1:].any()
    assert not mr.errors["conflict"].any()
    # group 0 stalled (append refused), others advanced
    assert newly[0] == 0
    np.testing.assert_array_equal(newly[1:], 1)
    assert int(np.asarray(mr.states[0].last)[0]) == 1
    # the refused group's payloads were NOT recorded (no garbage at
    # indices its log never reached); accepted groups' were
    assert 2 not in mr.payloads[0]
    assert mr.payloads[1][2] == b"x"
    # compaction frees the stalled group; it then catches up
    mr.mark_applied(mr.commit_index())
    mr.compact()
    newly = mr.propose(np.array([5, 0, 0, 0], np.int32))
    assert not mr.errors["overflow"].any()
    assert newly[0] == 5


def test_split_vote_then_retry_converges():
    """Votes are RECORDED at peers even when the response edge drops:
    a second candidate at the same term is refused (split vote), and
    only a fresh term wins — the dueling-candidates table
    (raft_test.go:204) at the batched level."""
    mr = MultiRaft(g=4, m=5, cap=32)
    ones = np.ones(4, bool)
    # member 0 campaigns: requests to peers 3,4 dropped, responses
    # from peers 1,2 dropped -> visible votes = self alone
    drop = {(0, 3): ones, (0, 4): ones, (1, 0): ones, (2, 0): ones}
    won = mr.campaign(0, drop=drop)
    assert not won.any()
    # ...but peers 1,2 DID vote for member 0 at term 1
    for peer in (1, 2):
        assert (np.asarray(mr.states[peer].vote) == 0).all()
    # member 4 (never contacted, still term 0) campaigns -> term 1:
    # peers 1,2 and the rival candidate refuse (votes burned at this
    # term); only peer 3 grants: 2 < 3 — the split vote
    won4 = mr.campaign(4)
    assert not won4.any()
    assert (mr.leader == -1).all()
    # member 0 retries at a higher term: peers adopt, votes reset, win
    won = mr.campaign(0)
    assert won.all()
    np.testing.assert_array_equal(mr.commit_index(), 1)


def test_partitioned_candidate_cannot_win():
    """A candidate cut off from every peer keeps losing while the
    majority side elects a leader and commits; healing demotes it."""
    from etcd_tpu.raft.batched import LEADER as L
    mr = MultiRaft(g=4, m=3, cap=64)
    ones = np.ones(4, bool)
    # full bidirectional isolation of member 0
    part = {(0, 1): ones, (0, 2): ones, (1, 0): ones, (2, 0): ones}
    won = mr.campaign(0, drop=part)
    assert not won.any()
    # majority side elects member 1 (its requests reach member 2)
    won = mr.campaign(1, drop=part)
    assert won.all()
    mr.propose(np.full(4, 2, np.int32), drop=part)
    for _ in range(3):
        mr.replicate(drop=part)
    assert (mr.commit_index() == 3).all()  # empty entry + 2 proposals
    # the isolated ex-candidate learned nothing
    assert (np.asarray(mr.states[0].last) == 0).all()
    # heal: next rounds demote member 0 and catch it up
    for _ in range(4):
        mr.replicate()
    assert (np.asarray(mr.states[0].role) != L).all()
    assert (np.asarray(mr.states[0].commit) == 3).all()
    for g in range(4):
        assert _logs_equal(mr, g, 3)


def test_vote_request_drop_vs_response_drop():
    """Request-edge and response-edge drops are distinct phases: a
    dropped request leaves the peer's vote free, a dropped response
    burns it."""
    mr = MultiRaft(g=2, m=3, cap=32)
    ones = np.ones(2, bool)
    # request to peer 1 dropped; response from peer 2 dropped
    drop = {(0, 1): ones, (2, 0): ones}
    won = mr.campaign(0, drop=drop)
    assert not won.any()  # only own vote visible
    assert (np.asarray(mr.states[1].vote) == -1).all()  # never asked
    assert (np.asarray(mr.states[2].vote) == 0).all()   # voted, lost
    # member 1 (never contacted, term 0) campaigns at term 1: its own
    # vote is free but peer 2's is burned and the rival refuses —
    # split vote at term 1
    won1 = mr.campaign(1)
    assert not won1.any()
    # its RETRY reaches term 2 > everyone: adopt, reset, clean win
    won1 = mr.campaign(1)
    assert won1.all()


def test_shrink_5_to_3_under_load():
    """Remove two members while proposals keep flowing: quorums track
    the live size, commits never stall, logs stay consistent
    (raft.go:376-387 batched)."""
    mr = MultiRaft(g=8, m=5, cap=128)
    mr.campaign(0)
    mr.propose(np.full(8, 2, np.int32))
    assert (mr.commit_index() == 3).all()
    mr.apply_conf_change(add=False, slot=4)
    mr.propose(np.full(8, 2, np.int32))   # 4 live: quorum 3
    assert (mr.commit_index() == 5).all()
    assert (np.asarray(mr.states[0].nmembers) == 4).all()
    mr.apply_conf_change(add=False, slot=3)
    # 3 live: quorum 2 — tolerate one dropped follower
    drop = {(0, 2): np.ones(8, bool)}
    mr.propose(np.full(8, 2, np.int32), drop=drop)
    assert (mr.commit_index() == 7).all()
    # removed members received nothing new
    assert (np.asarray(mr.states[4].last) <= 3).all()
    for g in range(8):
        assert _logs_equal_live(mr, g, 7, live=(0, 1))


def test_grow_3_to_5_under_load():
    """Add two member slots to a live cluster: each starts empty, is
    caught up by normal replication, and joins the quorum."""
    mr = MultiRaft(g=8, m=5, cap=128, live=3)
    assert (np.asarray(mr.states[0].nmembers) == 3).all()
    mr.campaign(0)
    mr.propose(np.full(8, 2, np.int32))
    assert (mr.commit_index() == 3).all()
    mr.apply_conf_change(add=True, slot=3)
    assert (np.asarray(mr.states[0].nmembers) == 4).all()
    mr.propose(np.full(8, 1, np.int32))   # quorum now 3 of 4
    for _ in range(3):
        mr.replicate()
    assert (mr.commit_index() == 4).all()
    assert (np.asarray(mr.states[3].last) == 4).all()  # caught up
    mr.apply_conf_change(add=True, slot=4)
    mr.propose(np.full(8, 1, np.int32))   # quorum 3 of 5
    for _ in range(6):   # fresh member: next walks back 1/reject round
        mr.replicate()
    assert (mr.commit_index() == 5).all()
    for g in range(8):
        assert _logs_equal(mr, g, 5)


def test_removed_leader_group_reelects():
    """Removing the leader slot deposes it; a remaining member wins
    the next election and commits resume."""
    from etcd_tpu.raft.batched import LEADER as L
    mr = MultiRaft(g=4, m=3, cap=64)
    mr.campaign(0)
    mr.propose(np.full(4, 1, np.int32))
    mr.apply_conf_change(add=False, slot=0)
    assert (mr.leader == -1).all()
    assert (np.asarray(mr.states[0].role) != L).all()  # stepped down
    won = mr.campaign(1)
    assert won.all()
    mr.propose(np.full(4, 1, np.int32))
    for _ in range(2):
        mr.replicate()
    # commit advances under the new 2-member... still-3 slot view:
    # nmembers=2, quorum=2 (leader + member 2)
    assert (mr.commit_index() >= 4).all()


def test_removed_member_cannot_campaign_or_vote():
    mr = MultiRaft(g=4, m=3, cap=32)
    mr.apply_conf_change(add=False, slot=2)
    won = mr.campaign(2)      # a non-member cannot campaign
    assert not won.any()
    won = mr.campaign(0)      # quorum of nmembers=2 is 2: self + m1
    assert won.all()
    # the removed slot was never asked to vote
    assert (np.asarray(mr.states[2].vote) == -1).all()


def test_snapshot_carries_membership():
    """A follower restored via the snapshot path adopts the leader's
    membership view (raft.go:535-554 rebuilds prs from s.Nodes)."""
    import jax.numpy as jnp
    mr = MultiRaft(g=4, m=5, cap=32)
    mr.campaign(0)
    drop = {(0, 2): np.ones(4, bool)}  # member 2 isolated
    mr.propose(np.full(4, 5, np.int32), drop=drop)
    for _ in range(2):
        mr.replicate(drop=drop)
    # shrink while member 2 is cut off; then hand-roll divergence:
    # member 2 missed the conf change (co-hosted apply is atomic, so
    # simulate the lag by reverting its membership row)
    mr.apply_conf_change(add=False, slot=4)
    full_row = jnp.ones((4, 5), bool)
    st2 = mr.states[2]
    mr.states[2] = st2._replace(members=full_row,
                                nmembers=jnp.full((4,), 5, jnp.int32))
    mr.mark_applied(mr.commit_index())
    mr.compact()  # leader log now starts past member 2's next
    for _ in range(3):
        mr.replicate()  # snapshot path restores member 2
    assert (np.asarray(mr.states[2].offset) > 0).all()
    # membership arrived with the snapshot
    assert not np.asarray(mr.states[2].members)[:, 4].any()
    assert (np.asarray(mr.states[2].nmembers) == 4).all()


def test_compact_prunes_payloads():
    mr = MultiRaft(g=2, m=3, cap=64)
    mr.campaign(0)
    mr.propose(np.full(2, 3, np.int32),
               data=[[b"a", b"b", b"c"], [b"x", b"y", b"z"]])
    assert mr.committed_payload(0, 2) == b"a"
    mr.replicate()  # propagate the commit frontier to followers
    mr.mark_applied(mr.commit_index())
    mr.compact()
    assert mr.committed_payload(0, 2) is None  # pruned below offset


def test_propose_rounds_matches_serial():
    """The fused K-round train commits exactly what K serial rounds
    commit (same engine, one dispatch)."""
    from etcd_tpu.raft.multiraft import MultiRaft

    a = MultiRaft(g=4, m=3, cap=64)
    b = MultiRaft(g=4, m=3, cap=64)
    a.campaign(0)
    b.campaign(0)
    one = np.ones(4, np.int32)
    serial = np.zeros(4, np.int64)
    for _ in range(5):
        serial += a.propose(one)
    fused = b.propose_rounds(one, 5)
    assert np.array_equal(serial, fused)
    assert np.array_equal(a.commit_index(), b.commit_index())
    # overflow lanes surface identically
    for _ in range(40):
        a.propose(one)
    c = MultiRaft(g=4, m=3, cap=64)
    c.campaign(0)
    c.propose_rounds(one, 40)
    assert np.array_equal(a.errors["overflow"], c.errors["overflow"])


# -- propose(data=...): a list of G lists or a mapping of touched groups -----

_PAYLOAD_N_NEW = np.array([2, 0, 1, 0, 1, 0], np.int32)
_PAYLOAD_ROWS = {0: [b"a0", b"a1"], 2: [b"c0"], 4: [b"stale"]}
_PAYLOAD_FORMS = {
    "list_of_g_lists": lambda: [list(_PAYLOAD_ROWS.get(gi, []))
                                for gi in range(6)],
    "mapping_of_touched_groups": lambda: dict(_PAYLOAD_ROWS),
    # a row longer than n_new is cut to n_new in either form
    "list_with_spare_blobs": lambda: [
        list(_PAYLOAD_ROWS.get(gi, [])) + [b"spare"] for gi in range(6)],
}


def _propose_with_deposed_group(data):
    """Six groups led by member 0, group 4's leader deposed without
    ``mr.leader`` learning of it; one propose() of ``_PAYLOAD_N_NEW``
    with ``data``.  Returns everything the caller may key on."""
    import jax.numpy as jnp
    from etcd_tpu.raft.batched import FOLLOWER
    mr = MultiRaft(g=6, m=3, cap=32)
    mr.campaign(0)
    st = mr.states[0]
    mr.states[0] = st._replace(
        role=jnp.asarray(st.role).at[4].set(FOLLOWER))
    newly = mr.propose(_PAYLOAD_N_NEW, data=data)
    return (mr.payloads, np.asarray(mr.last_valid),
            np.asarray(mr.last_base), np.asarray(newly))


@functools.cache
def _list_form_reference():
    return _propose_with_deposed_group(
        _PAYLOAD_FORMS["list_of_g_lists"]())


@pytest.mark.parametrize("form", sorted(_PAYLOAD_FORMS))
def test_propose_records_payloads_of_touched_groups(form):
    """``data`` is indexed by group and only where ``n_new > 0`` and
    the addressed member is leader: a mapping that holds the touched
    groups alone and a list of G lists give the same bookkeeping."""
    payloads, valid, base, newly = _propose_with_deposed_group(
        _PAYLOAD_FORMS[form]())
    np.testing.assert_array_equal(
        valid, [True, True, True, True, False, True])
    # the becoming-leader entry is index 1, so proposals start at 2
    assert payloads[0] == {2: b"a0", 3: b"a1"}
    assert payloads[2] == {2: b"c0"}
    # n_new > 0 but not valid: nothing recorded; untouched: nothing
    assert all(payloads[gi] == {} for gi in (1, 3, 4, 5))
    np.testing.assert_array_equal(base[valid], 1)
    np.testing.assert_array_equal(newly, [2, 0, 1, 0, 0, 0])
    ref = _list_form_reference()
    assert payloads == ref[0]
    for got, want in zip((valid, base, newly), ref[1:]):
        np.testing.assert_array_equal(got, want)
