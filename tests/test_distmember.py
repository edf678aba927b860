"""DistMember engine: batched cross-host consensus rounds exchanged
as wire frames between in-process members (the fake-network pattern,
raft_test.go:1203-1263, at the frame level)."""

import numpy as np
import pytest

from etcd_tpu.raft.distmember import DistMember
from etcd_tpu.wire.distmsg import (
    AppendBatch,
    AppendResp,
    VoteReq,
    VoteResp,
    unmarshal_any,
)

G, M, CAP = 8, 3, 64


def make_cluster(g=G, m=M, cap=CAP):
    return [DistMember(g, m, s, cap) for s in range(m)]


def elect(ms, slot=0, mask=None):
    """One full campaign round-trip for member ``slot``."""
    mask = np.ones(ms[slot].g, bool) if mask is None else mask
    req_frame = ms[slot].begin_campaign(mask).marshal()
    req = unmarshal_any(req_frame)
    votes = []
    for peer in range(len(ms)):
        if peer == slot:
            continue
        votes.append(unmarshal_any(
            ms[peer].handle_vote(req).marshal()))
    return ms[slot].tally(req.active, votes)


def replicate(ms, lead=0, drop=()):
    """One append round-trip from ``lead`` to every peer; ``drop`` is
    a set of peer slots whose frames vanish (either direction)."""
    for peer in range(len(ms)):
        if peer == lead or peer in drop:
            continue
        b = ms[lead].build_append(peer)
        if b is None:
            continue
        resp = ms[peer].handle_append(
            unmarshal_any(b.marshal()))
        ms[lead].handle_append_resp(unmarshal_any(resp.marshal()))


def test_frame_roundtrip():
    b = AppendBatch(
        sender=1, term=np.arange(4, dtype=np.int32),
        prev_idx=np.arange(4, dtype=np.int32),
        prev_term=np.zeros(4, np.int32),
        n_ents=np.asarray([2, 0, 1, 0], np.int32),
        commit=np.zeros(4, np.int32),
        active=np.asarray([1, 1, 0, 0], bool),
        need_snap=np.zeros(4, bool),
        ent_terms=np.ones((4, 2), np.int32),
        payloads=[[b"aa", b"b"], [], [b"ccc"], []])
    got = unmarshal_any(b.marshal())
    assert isinstance(got, AppendBatch) and got.sender == 1
    assert got.payloads[0] == [b"aa", b"b"]
    assert got.payloads[2] == [b"ccc"]
    assert np.array_equal(got.n_ents, b.n_ents)

    r = AppendResp(sender=2, term=np.ones(4, np.int32),
                   ok=np.asarray([1, 0, 1, 0], bool),
                   acked=np.arange(4, dtype=np.int32),
                   hint=np.zeros(4, np.int32),
                   active=np.ones(4, bool))
    got = unmarshal_any(r.marshal())
    assert isinstance(got, AppendResp)
    assert np.array_equal(got.ok, r.ok)

    v = VoteReq(sender=0, term=np.ones(4, np.int32),
                last=np.zeros(4, np.int32),
                lterm=np.zeros(4, np.int32),
                active=np.ones(4, bool))
    assert isinstance(unmarshal_any(v.marshal()), VoteReq)
    vr = VoteResp(sender=1, term=np.ones(4, np.int32),
                  granted=np.ones(4, bool), active=np.ones(4, bool))
    assert isinstance(unmarshal_any(vr.marshal()), VoteResp)


def test_election_and_commit():
    ms = make_cluster()
    won = elect(ms, 0)
    assert won.all()
    assert ms[0].is_leader().all()
    # becoming-leader empty entry + a real proposal
    ms[0].propose(np.ones(G, np.int32),
                  data=[[b""] for _ in range(G)])
    valid, base = ms[0].propose(
        np.ones(G, np.int32), data=[[b"x"] for _ in range(G)])
    assert valid.all() and (base == 1).all()
    replicate(ms, 0)
    assert (ms[0].commit_index() == 2).all()
    # commit propagates to followers on the NEXT round
    replicate(ms, 0)
    assert (ms[1].commit_index() == 2).all()
    assert ms[1].committed_payload(0, 2) == b"x"


def test_split_vote_lockstep_breaks_via_timeout_redraw():
    """VERDICT r3 #6 regression (the ~12s leaderless window): two
    survivors of a leader kill whose lanes drew EQUAL election
    timeouts fire in lockstep — both campaign the same term, each
    votes for itself, neither grants.  With init-only randomization
    that split repeats forever; begin_campaign must re-draw the fired
    lanes' timeouts (raft.go:608-617) so consecutive retries
    decorrelate and every lane elects within a few timeouts."""
    import jax.numpy as jnp

    g, m, cap = 8, 3, 16
    a = DistMember(g, m, 1, cap, election=5, seed=11)
    b = DistMember(g, m, 2, cap, election=5, seed=22)
    # adversarial worst case: identical timeouts, identical phase
    same = jnp.asarray(np.full(g, 7, np.int32))
    a.state = a.state._replace(timeout=same)
    b.state = b.state._replace(timeout=same)

    def campaign_pair(fired_a, fired_b):
        """Simultaneous campaigns crossing in flight (slot 0 dead)."""
        reqs = {}
        if fired_a.any():
            reqs["a"] = unmarshal_any(
                a.begin_campaign(fired_a).marshal())
        if fired_b.any():
            reqs["b"] = unmarshal_any(
                b.begin_campaign(fired_b).marshal())
        votes_a = [unmarshal_any(b.handle_vote(reqs["a"]).marshal())] \
            if "a" in reqs else []
        votes_b = [unmarshal_any(a.handle_vote(reqs["b"]).marshal())] \
            if "b" in reqs else []
        if "a" in reqs:
            a.tally(reqs["a"].active, votes_a)
        if "b" in reqs:
            b.tally(reqs["b"].active, votes_b)

    led_at = np.full(g, -1)
    for t in range(200):
        fa, fb = a.tick(), b.tick()
        if fa.any() or fb.any():
            campaign_pair(fa, fb)
        led = a.is_leader() | b.is_leader()
        led_at[(led_at < 0) & led] = t
        if led.all():
            break
    assert (led_at >= 0).all(), \
        f"lanes never elected: {np.nonzero(led_at < 0)[0]}"
    # the first fire is at tick 7; a handful of re-drawn retries must
    # suffice (bound: 10 election timeouts — way under the drill's
    # observed 12s ~ 240 ticks)
    assert led_at.max() <= 50, f"slow convergence: {led_at}"


def test_reject_repair_jumps_forward_past_compacted_probe():
    """Chaos-drill regression (round 4): response loss can leave the
    leader's next_[f] BELOW the follower's commit+1 while the
    follower has lane-compacted to its commit (offset == commit ==
    last).  The probe's prev then sits below the follower's offset —
    unverifiable, rejected every round — and a min()-clamped repair
    pinned next_ there FOREVER (a permanent one-lane replication
    wedge that survived restarts of every host).  The repair must SET
    next_ = hint+1, jumping forward."""
    ms = make_cluster()
    elect(ms, 0)
    ms[0].propose(np.ones(G, np.int32), data=[[b""] for _ in range(G)])
    for i in range(6):
        ms[0].propose(np.ones(G, np.int32),
                      data=[[bytes([i])] for _ in range(G)])
        replicate(ms, 0)
    replicate(ms, 0)  # commits propagate
    lead, fol = ms[0], ms[1]
    assert (fol.commit_index() >= 6).all()
    # follower lane-compacts everything it applied (offset == commit)
    fol.mark_applied(fol.commit_index())
    fol.compact()
    st = fol.state
    assert (np.asarray(st.offset) == np.asarray(st.commit)).all()
    # manufacture the stale leader view: next_[fol] one BELOW the
    # follower's commit+1 (as left by a lost response under overload)
    import jax.numpy as jnp

    stale = jnp.asarray(np.asarray(fol.state.commit))  # = commit
    lst = lead.state
    next_ = np.asarray(lst.next_).copy()
    next_[:, 1] = np.asarray(stale)
    lead.state = lst._replace(next_=jnp.asarray(next_))
    # new entries the follower must eventually receive
    lead.propose(np.ones(G, np.int32), data=[[b"new"] for _ in range(G)])

    before = fol.commit_index().copy()
    for _ in range(4):  # reject -> forward repair -> append -> commit
        replicate(ms, 0, drop={2})  # only the wedged pair exchanges
    assert (fol.commit_index() > before).all(), \
        (before, fol.commit_index())
    assert fol.committed_payload(0, int(fol.commit_index()[0])) \
        in (b"new", b"")


def test_quorum_commits_with_one_peer_down():
    ms = make_cluster()
    elect(ms, 0)
    ms[0].propose(np.ones(G, np.int32),
                  data=[[b""] for _ in range(G)])
    replicate(ms, 0)
    before = ms[0].commit_index().copy()
    ms[0].propose(np.ones(G, np.int32),
                  data=[[b"y"] for _ in range(G)])
    replicate(ms, 0, drop={2})       # only peer 1 answers
    assert (ms[0].commit_index() == before + 1).all()


def test_reject_repairs_next_from_hint():
    ms = make_cluster()
    elect(ms, 0)
    ms[0].propose(np.ones(G, np.int32),
                  data=[[b""] for _ in range(G)])
    # peer 2 misses 3 rounds
    for i in range(3):
        ms[0].propose(np.ones(G, np.int32),
                      data=[[bytes([i])] for _ in range(G)])
        replicate(ms, 0, drop={2})
    # peer 2 now gets a frame whose prev it lacks -> reject+hint,
    # leader repairs next_, second round delivers the backlog
    replicate(ms, 0)
    replicate(ms, 0)
    assert (ms[2].commit_index() >= 3).all()


def test_higher_term_deposes_leader():
    ms = make_cluster()
    elect(ms, 0)
    ms[0].propose(np.ones(G, np.int32),
                  data=[[b""] for _ in range(G)])
    replicate(ms, 0)
    # member 1 campaigns at a higher term and wins
    won = elect(ms, 1)
    assert won.all()
    # the old leader learns the new term from the next response
    b = ms[0].build_append(1)
    if b is not None:
        resp = ms[1].handle_append(unmarshal_any(b.marshal()))
        ms[0].handle_append_resp(unmarshal_any(resp.marshal()))
    assert not ms[0].is_leader().any()


def test_vote_durability_shape():
    """begin_campaign bumps terms before any frame ships (the caller
    persists the ballot between these two steps)."""
    ms = make_cluster()
    t0 = ms[0].terms().copy()
    req = ms[0].begin_campaign(np.ones(G, bool))
    assert (ms[0].terms() == t0 + 1).all()
    assert (req.term == t0 + 1).all()


def test_need_snap_flag_past_compaction():
    ms = make_cluster(cap=16)
    elect(ms, 0)
    ms[0].propose(np.ones(G, np.int32),
                  data=[[b""] for _ in range(G)])
    # past the tail compaction keeps for a lagging member (cap // 2)
    for i in range(10):
        ms[0].propose(np.ones(G, np.int32),
                      data=[[bytes([i])] for _ in range(G)])
        replicate(ms, 0, drop={2})
    ms[0].mark_applied(ms[0].commit_index())
    ms[0].compact()
    b = ms[0].build_append(2)
    assert b is not None and b.need_snap.all()
    # follower pulls + installs the snapshot, then appends resume
    frontier = ms[0].commit_index()
    terms = ms[0].commit_terms()
    inst = ms[2].install_snapshot(frontier, terms)
    assert inst.all()
    # ONE response repairs the leader: the need_snap lane acks
    # positively at its commit (raft.go:418-424's handleSnapshot
    # reply), advancing match/next past the compaction point —
    # merely re-reaching the frontier would also hold for an
    # install LOOP, so assert the flag clears and real appends
    # resume (chaos-drill regression)
    replicate(ms, 0)
    assert (np.asarray(ms[0].state.match)[:, 2]
            >= np.asarray(frontier)).all()
    b = ms[0].build_append(2)
    assert b is None or not b.need_snap.any()
    ms[0].propose(np.ones(G, np.int32),
                  data=[[b"post"] for _ in range(G)])
    replicate(ms, 0)
    replicate(ms, 0)
    assert (ms[2].commit_index() > frontier).all()
    assert ms[2].committed_payload(0, int(frontier[0]) + 1) == b"post"


def test_partial_mask_campaign():
    ms = make_cluster()
    mask = np.zeros(G, bool)
    mask[:3] = True
    won = elect(ms, 1, mask)
    assert won[:3].all() and not won[3:].any()
    assert ms[1].is_leader()[:3].all()
    assert not ms[1].is_leader()[3:].any()


def test_dist_frames_match_fused_multiraft():
    """Property pin: the SAME proposal schedule driven through (a)
    the fused in-process MultiRaft and (b) three DistMembers
    exchanging wire frames must land identical commit vectors and
    identical per-entry log terms — the frame layer is transport,
    not semantics."""
    from etcd_tpu.raft.multiraft import MultiRaft

    rng = np.random.default_rng(42)
    g, m, cap, rounds = 6, 3, 64, 12

    fused = MultiRaft(g=g, m=m, cap=cap)
    fused.campaign(0)
    dist = make_cluster(g=g, m=m, cap=cap)
    elect(dist, 0)
    # becoming-leader empty entry on both engines
    dist_n0 = np.ones(g, np.int32)
    dist[0].propose(dist_n0, data=[[b""] for _ in range(g)])
    replicate(dist, 0)

    for r in range(rounds):
        n_new = rng.integers(0, 3, size=g).astype(np.int32)
        payloads = [[bytes([r, j]) for j in range(int(n_new[gi]))]
                    for gi in range(g)]
        fused.propose(n_new, data=payloads)
        dist[0].propose(n_new, data=payloads)
        replicate(dist, 0)

    # one extra fused round with no new input lets commit catch up on
    # both sides (the dist loop already did its exchange per round)
    fused.replicate()
    replicate(dist, 0)

    assert np.array_equal(fused.commit_index(), dist[0].commit_index())
    # per-entry terms agree over the committed window
    from etcd_tpu.raft.batched import term_at
    import jax.numpy as jnp

    for gi in range(g):
        hi = int(fused.commit_index()[gi])
        for idx in range(1, hi + 1):
            ft = int(np.asarray(term_at(
                fused.states[0].log_term, fused.states[0].offset,
                fused.states[0].last,
                jnp.asarray(np.full(g, idx, np.int32))))[gi])
            dt = int(dist[0].terms_at(np.full(g, idx))[gi])
            assert ft == dt, (gi, idx, ft, dt)
            # committed payloads agree too
            assert (fused.committed_payload(gi, idx) or b"") == \
                (dist[0].committed_payload(gi, idx) or b"")


@pytest.mark.parametrize("seed,m,steps", [(1234, 3, 120),
                                          (777, 5, 150)])
def test_randomized_lossy_exchange_log_matching(seed, m, steps):
    """Fuzz the frame layer the way the reference fuzzes its fake
    network (raft_test.go lossy topologies): random proposals,
    per-edge drops, competing campaigns, compactions — then assert
    the Log Matching safety property: every pair of members agrees
    on term AND payload for every index at or below both commits
    (above both offsets).  The 5-member case exercises larger
    quorums and more drop patterns."""
    rng = np.random.default_rng(seed)
    g, cap = 4, 96
    ms = make_cluster(g=g, m=m, cap=cap)
    elect(ms, 0)
    ms[0].propose(np.ones(g, np.int32), data=[[b""]] * g)

    def rand_drop():
        if rng.random() < 0.5:
            return set()
        return set(rng.choice(m, size=rng.integers(1, m),
                              replace=False).tolist())

    leader = 0
    for step in range(steps):
        act = rng.random()
        if act < 0.55:
            n = rng.integers(0, 3, size=g).astype(np.int32)
            data = [[bytes([step % 256, j]) for j in range(int(n[gi]))]
                    for gi in range(g)]
            ms[leader].propose(n, data=data)
            replicate(ms, leader, drop=rand_drop() - {leader})
        elif act < 0.75:
            replicate(ms, leader, drop=rand_drop() - {leader})
        elif act < 0.9:
            # competing campaign from a random member; on a win it
            # proposes its becoming-leader entry
            cand = int(rng.integers(0, m))
            won = elect(ms, cand)
            if won.any():
                leader = cand
                ms[cand].propose(
                    won.astype(np.int32),
                    data=[[b"L"] if won[gi] else []
                          for gi in range(g)])
        else:
            slot = int(rng.integers(0, m))
            ms[slot].mark_applied(ms[slot].commit_index())
            ms[slot].compact()

    # settle: several clean rounds so commits converge
    for _ in range(6):
        replicate(ms, leader)

    for a in range(m):
        for b in range(a + 1, m):
            ca, cb = ms[a].commit_index(), ms[b].commit_index()
            oa = np.asarray(ms[a].state.offset)
            ob = np.asarray(ms[b].state.offset)
            for gi in range(g):
                lo = int(max(oa[gi], ob[gi])) + 1
                hi = int(min(ca[gi], cb[gi]))
                for idx in range(lo, hi + 1):
                    v = np.full(g, idx)
                    ta = int(ms[a].terms_at(v)[gi])
                    tb = int(ms[b].terms_at(v)[gi])
                    assert ta == tb, (
                        f"term divergence g{gi}@{idx}: "
                        f"m{a}={ta} m{b}={tb}")
                    pa = ms[a].committed_payload(gi, idx)
                    pb = ms[b].committed_payload(gi, idx)
                    if pa is not None and pb is not None:
                        assert pa == pb, (gi, idx, pa, pb)


@pytest.mark.parametrize("election,m", [
    (10, 3),    # the drill's config
    (3, 8),     # small election / large m: clamps to election=8
    (5, 5),     # boundary: exactly one tick of band per slot
    (16, 4),    # wide bands
])
def test_timeout_bands_are_disjoint_across_slots(election, m):
    """Stratified election timeouts (distmember._draw_timeouts):
    every draw a slot can make lives in a per-slot tick band that is
    DISJOINT from every other slot's band, so two live hosts' timers
    can never fire in the same band — the structural fix for the
    drill's multi-round election tail (split votes between
    survivors).  ``election < m`` cannot produce m disjoint bands in
    [election, 2*election); DistMember clamps election up to m at
    construction, so the documented <= 2*election worst case holds
    on every config (the clamped election is the effective bound)."""
    g, cap = 64, 16
    eff = max(election, m)  # DistMember's construction clamp
    ranges = []
    for s in range(m):
        mm = DistMember(g, m, s, cap, election=election, seed=s)
        assert mm.election == eff
        draws = np.concatenate(
            [mm._draw_timeouts() for _ in range(50)])
        assert (draws >= eff).all()
        assert (draws < 2 * eff).all(), \
            f"slot {s} draws beyond 2*election: {draws.max()}"
        ranges.append((int(draws.min()), int(draws.max())))
    for i in range(m):
        for j in range(i + 1, m):
            lo_i, hi_i = ranges[i]
            lo_j, hi_j = ranges[j]
            assert hi_i < lo_j or hi_j < lo_i, \
                f"bands overlap: slot {i} {ranges[i]} vs " \
                f"slot {j} {ranges[j]}"


def test_lost_campaign_backs_off_beyond_band():
    """Loser backoff (distmember.tally): a lane that campaigns and
    LOSES must wait strictly longer than its normal band before
    re-firing — an immediately re-firing refused candidate pre-empts
    the better peer's campaign under slow frame delivery."""
    g, m, cap, election = 8, 3, 16, 10
    a = DistMember(g, m, 1, cap, election=election, seed=7)
    mask = np.ones(g, bool)
    a.begin_campaign(mask)
    band_hi = election + 2 * max(1, election // m)  # slot 1 band end
    # no responses at all -> every lane lost
    won = a.tally(mask, [])
    assert not won.any()
    t = np.asarray(a.state.timeout)
    assert (t >= band_hi).all(), \
        f"lost lanes did not back off: timeouts {t}"
    assert (t > election).all()
    # a lane that WINS keeps its normal band on the next campaign
    b = DistMember(g, 1, 0, cap, election=election, seed=8, live=1)
    b.begin_campaign(np.ones(g, bool))
    wonb = b.tally(np.ones(g, bool), [])  # single-member: self quorum
    assert wonb.all()
    tb = np.asarray(b.state.timeout)
    w0 = max(1, election // 1)
    assert (tb >= election).all() and (tb < election + w0).all()
