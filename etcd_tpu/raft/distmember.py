"""One member slot of G co-hosted raft groups, for cross-host
replication (SURVEY §5.8's two-tier design composed).

`MultiRaft` (multiraft.py) fuses all M members of every group into
one process — maximal device batching, but the whole cluster shares
process fate (VERDICT r2 missing #2).  This module is the other half:
each HOST owns ONE member slot of all G groups, rounds exchange
batched [G] message frames (wire/distmsg.py) over the host DCN tier,
and every device transition reuses the same batched engine ops
(raft/batched.py) the fused runtime uses — `maybe_append`,
`leader_append`, `progress_update`, `maybe_commit`, `grant_vote`,
`restore_snapshot` — applied to a single slot's GroupState.

Protocol parity: the exchange IS the reference's message protocol
(msgApp/msgAppResp/msgVote/msgVoteResp/msgSnap semantics,
raft/raft.go:372-520) with the group axis batched; drop tolerance is
the reference's fire-and-forget contract (server.go:202-206) — any
frame may vanish, progress resumes on a later round.

Durability is the CALLER's job (the server layer persists entries,
ballots and frontiers to its WAL before acks/responses leave the
host — the Ready contract, node.go:41-60); this class is pure
consensus state.
"""

from __future__ import annotations

import os
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ..wire.distmsg import (
    AppendBatch,
    AppendResp,
    PackedPayloads,
    VoteReq,
    VoteResp,
    flat_entry_table,
)
from .batched import (
    FOLLOWER,
    LEADER,
    CANDIDATE,
    GroupState,
    apply_conf_change as conf_change_batch,
    compact as compact_batch,
    grant_vote,
    init_groups,
    leader_append,
    maybe_append,
    maybe_commit,
    progress_optimistic,
    progress_probe,
    progress_repair,
    progress_update,
    restore_snapshot,
    term_at,
    term_window,
    tick as tick_batch,
)


@jax.jit
def _adopt_term(state: GroupState, msg_term, lead, active):
    """Higher-term message handling (raft.go:388-396): adopt the term,
    become follower, forget the vote; ``lead`` [G] i32 is the new
    leader slot to record (-1 for vote traffic)."""
    higher = active & (msg_term > state.term)
    return state._replace(
        term=jnp.where(higher, msg_term, state.term),
        vote=jnp.where(higher, -1, state.vote),
        role=jnp.where(higher, FOLLOWER, state.role),
        lead=jnp.where(higher, lead, state.lead))


def _absorb_step(state: GroupState, peer_v, term, ok, acked, hint,
                 active):
    """The leader's step for one response, ``peer_v`` [G] the sender's
    slot on every lane (``_absorb_resp`` has the why)."""
    state = _adopt_term(state, term, jnp.full_like(term, -1), active)
    state = progress_update(state, peer_v, acked, active=active & ok)
    state = progress_repair(state, peer_v, hint, active=active & ~ok)
    return maybe_commit(state)


@jax.jit
def _absorb_resp(state: GroupState, peer, term, ok, acked, hint,
                 active):
    """Leader absorbing one peer's batched msgAppResp: step down on
    higher terms, progress-update ok lanes, repair next_ from the
    commit hint on rejects, then quorum-commit.

    The repair SETS next_ = hint + 1 in both directions.  The hint is
    the follower's commit, so prev = hint is always verifiable there
    (offset <= commit, and the compaction slot carries the offset
    entry's term) and everything <= hint is immutable.  Clamping with
    min(next_, hint+1) — the earlier form — deadlocks the lane when
    response loss leaves the leader's next_ BELOW the follower's
    commit+1 while the follower has lane-compacted to its commit: the
    probe's prev sits below the follower's offset (term unknowable →
    reject forever) and the min pins next_ there.  Found by the chaos
    drill as a one-lane permanent replication wedge that survived
    restarts of every host."""
    g, _m = state.match.shape
    return _absorb_step(state, jnp.full((g,), peer, jnp.int32), term,
                        ok, acked, hint, active)


@jax.jit
def _absorb_resps(state: GroupState, rows):
    """``_absorb_step`` for each of K responses in order, ONE dispatch:
    ``rows`` [G, K, 6] i32 holds a response a column of the K axis
    (sender | term | ok | acked | hint | active, as in ``AppendResp``).
    A row whose sender is -1 is padding, and the scan passes the state
    through it untouched.  Returns the state and the [K + 1, G] commit
    vectors: before the first row, then after each."""
    def one(st, row):
        st = jax.lax.cond(
            row[0, 0] >= 0,
            lambda s: _absorb_step(s, row[:, 0], row[:, 1],
                                   row[:, 2] != 0, row[:, 3], row[:, 4],
                                   row[:, 5] != 0),
            lambda s: s, st)
        return st, st.commit

    out, commits = jax.lax.scan(one, state, jnp.swapaxes(rows, 0, 1))
    return out, jnp.concatenate([state.commit[None], commits])


@jax.jit
def _handle_append_fused(state: GroupState, sender_v, term, prev_idx,
                         prev_term, ent_terms, n_ents, commit, active,
                         need_snap):
    """The WHOLE follower-side msgApp step as ONE device dispatch:
    higher-term adoption, leadership + election-timer reset,
    maybe_append, and the response arrays packed into a single [G, 7]
    i32 block (ok | cur | conflict | overflow | acked | term |
    commit) so the host does one fetch instead of seven.

    The unfused chain (PR 2's shape) cost ~8 eager dispatches per
    frame — at the pipeline's frame rates that fixed per-frame tax
    was the follower's single largest CPU line."""
    st = _adopt_term(state, term, sender_v, active)
    cur = active & (term == st.term)
    st = st._replace(
        role=jnp.where(cur, FOLLOWER, st.role),
        lead=jnp.where(cur, sender_v, st.lead),
        elapsed=jnp.where(cur, 0, st.elapsed))
    do = cur & ~need_snap
    st, ok, e_conf, e_over = maybe_append(
        st, prev_idx, prev_term, ent_terms, n_ents, commit, do)
    need = need_snap & cur
    commit_i = st.commit.astype(jnp.int32)
    acked = jnp.where(need, commit_i,
                      prev_idx + n_ents).astype(jnp.int32)
    packed = jnp.stack([
        ok.astype(jnp.int32), cur.astype(jnp.int32),
        e_conf.astype(jnp.int32), e_over.astype(jnp.int32),
        acked, st.term, commit_i], axis=1)
    return st, packed


@jax.jit
def _ack_self_fused(state: GroupState, self_slot, upto):
    """Durable self-ack + quorum commit in one dispatch."""
    return maybe_commit(progress_update(state, self_slot, upto))


@partial(jax.jit, static_argnames=("peer", "e"))
def _build_append_fused(state: GroupState, lane_mask, peer, e):
    """The msgApp window computation as ONE dispatch returning one
    packed [G, 6 + e + 1] i32 block: active | need_snap | prev_idx |
    n_ents | term | commit | terms2[e+1] — the host slices columns
    out of a single fetch (the unfused form did five separate
    device->host reads plus a term_at dispatch per peer per pump)."""
    lead = state.role == LEADER
    member = state.members[:, peer]
    active = lead & member & lane_mask
    nxt = state.next_[:, peer]
    offset = state.offset
    need_snap = active & (nxt <= offset) & (offset > 0)
    sendable = active & ~need_snap
    prev_idx = jnp.where(sendable, nxt - 1, 0).astype(jnp.int32)
    n_ents = jnp.where(
        sendable, jnp.clip(state.last - prev_idx, 0, e),
        0).astype(jnp.int32)
    terms2 = term_window(state.log_term, state.offset, state.last,
                         prev_idx, e + 1)
    return jnp.concatenate([
        jnp.stack([active.astype(jnp.int32),
                   need_snap.astype(jnp.int32),
                   prev_idx, n_ents, state.term, state.commit],
                  axis=1),
        terms2], axis=1)


@partial(jax.jit, static_argnames=("slot", "keep"))
def _compact_cut(state: GroupState, slot, keep):
    """[G] index ``compact`` cuts at.  A follower lane cuts at its
    applied index.  A LEADER lane keeps the tail its slowest other
    member has not confirmed (``match``): commit needs only a quorum,
    so under steady load one follower is a frame behind on some lane
    at any moment, and a cut at ``applied`` puts its next entry
    behind the offset — ``need_snap``, a whole-store snapshot pull
    for a lag of a few entries (the reference keeps its log for the
    same reason, server.go's snapshot leaves the catch-up entries).
    At most ``keep`` entries are kept, so a dead member cannot fill
    the window: past that it installs a snapshot, as before."""
    others = state.members & (
        jnp.arange(state.match.shape[1]) != slot)[None, :]
    slowest = jnp.min(jnp.where(others, state.match,
                                jnp.iinfo(jnp.int32).max), axis=1)
    cut = jnp.where(state.role == LEADER,
                    jnp.minimum(state.applied, slowest),
                    state.applied)
    return jnp.maximum(jnp.maximum(cut, state.applied - keep),
                       state.offset)


@partial(jax.jit, static_argnames=("slot",))
def _begin_campaign(state: GroupState, mask, slot):
    """term+1, vote self, CANDIDATE (raft.go:358-362 batched)."""
    mask = mask & state.members[:, slot]
    lterm = term_at(state.log_term, state.offset, state.last,
                    state.last)
    return state._replace(
        term=state.term + mask.astype(jnp.int32),
        role=jnp.where(mask, CANDIDATE, state.role),
        vote=jnp.where(mask, slot, state.vote),
        elapsed=jnp.where(mask, 0, state.elapsed)), mask, lterm


@jax.jit
def _step_down(state: GroupState, mask):
    """Check-quorum abdication (PR 10): masked LEADER lanes become
    followers with no known leader and a reset election timer.  The
    term is untouched (the reference's checkQuorum stepDown —
    raft.go becomeFollower(r.Term, None)): the deposed leader's
    peers will elect at term+1 on their own timers."""
    down = mask & (state.role == LEADER)
    return state._replace(
        role=jnp.where(down, FOLLOWER, state.role),
        lead=jnp.where(down, -1, state.lead),
        elapsed=jnp.where(down, 0, state.elapsed))


@partial(jax.jit, static_argnames=("slot",))
def _become_leader(state: GroupState, won, slot):
    """Winner lanes become leader (raft.go:329-348 batched); the
    becoming-leader empty entry is appended by the caller via
    propose()."""
    m = state.match.shape[1]
    return state._replace(
        role=jnp.where(won, LEADER, state.role),
        lead=jnp.where(won, slot, state.lead),
        match=jnp.where(won[:, None], 0, state.match),
        next_=jnp.where(won[:, None], state.last[:, None] + 1,
                        state.next_))


class DistMember:
    """Member ``slot`` of G co-hosted groups; peers live on other
    hosts and exchange wire/distmsg.py frames."""

    def __init__(self, g: int, m: int, slot: int, cap: int,
                 election: int = 10, max_batch_ents: int = 8,
                 seed: int | None = None, live: int | None = None,
                 ack_rows: int | None = None):
        # (election is in ticks; the server layer's tick_interval
        # scales it to wall time — raft.go:611-617 randomization;
        # ``live`` < m leaves spare member slots for runtime
        # AddMember, batched state being static-shaped; ``ack_rows``
        # is how many responses one absorb dispatch takes, the most
        # that can be in flight: the pipelined server's window depth
        # times its peers)
        self.g, self.m, self.slot, self.cap = g, m, slot, cap
        self.e = max_batch_ents
        self.k = ack_rows or max(1, m - 1)
        # the stratified election bands (_draw_timeouts) carve m
        # disjoint width->=1 bands out of [election, 2*election);
        # with election < m that is impossible — w clamps to 1 and
        # high slots' bands spill past 2*election, silently breaking
        # the drill-calibrated worst case.  Clamp up so the
        # documented ``<= 2*election`` recovery bound holds on every
        # config (an election of at least m ticks is also the only
        # sane operating point: fewer ticks than hosts cannot
        # stagger anything).
        self.election = max(election, m)
        # kept: the timeout is re-drawn per campaign (see
        # begin_campaign), not fixed at init
        self._rng = np.random.default_rng(
            slot if seed is None else seed)
        st = init_groups(g, m, cap, election=self.election,
                         live=live)
        st = st._replace(timeout=jnp.asarray(
            self._draw_timeouts(), jnp.int32))
        self.state = st
        # host-side payload ring: per-group {index: bytes}; a follower
        # keeps payloads too — it applies them at commit
        self.payloads: list[dict[int, bytes]] = [dict()
                                                 for _ in range(g)]
        self.errors = {"overflow": np.zeros(g, bool),
                       "conflict": np.zeros(g, bool)}
        self._placer = None  # set by shard(): parallel.mesh placer
        # PR 14: ship the FLAG_PACKED flat entry table on outgoing
        # append frames (receivers consume entries in one flat pass).
        # ETCD_DIST_PACKED=0 reverts to plain DGB2 frames — the
        # mixed-version lever the compat tests drive.
        self.packed_wire = \
            os.environ.get("ETCD_DIST_PACKED", "1") != "0"

    # -- intra-host scale-out ---------------------------------------------

    def shard(self, mesh) -> None:
        """Shard every [G]-leading state array over the mesh's ``g``
        axis (SURVEY §5.8's intra-slice tier composed under the
        cross-host tier): groups are independent, so the batched
        engine ops run SPMD across the mesh's devices with no
        cross-device collectives, while the frame exchange above is
        unchanged.  Callers re-invoke after wholesale state
        replacement (restart seeding)."""
        from ..parallel.mesh import (
            check_group_divisible,
            leading_placer,
            shard_leading,
        )

        check_group_divisible(mesh, self.g)
        self.state = type(self.state)(
            *(shard_leading(mesh, x) for x in self.state))
        # per-frame [G]/[G, E] host inputs must be PLACED with the
        # same g-sharding before each dispatch (leading_placer's
        # docstring has the why)
        self._placer = leading_placer(mesh)

    def _put(self, arr, dtype=None):
        """Host array → device, g-sharded when the state is."""
        if self._placer is not None:
            return self._placer(arr, dtype)
        return jnp.asarray(np.asarray(arr, dtype))

    def _full(self, value, dtype=jnp.int32):
        """[G] constant vector, placed like every other [G] input (an
        eagerly-created jnp.full lands on the default device and
        reintroduces the per-dispatch reshard _put exists to avoid)."""
        return self._put(np.full(self.g, value), dtype)

    # -- views ------------------------------------------------------------

    def is_leader(self) -> np.ndarray:
        return np.asarray(self.state.role) == LEADER

    def leader_hint(self) -> np.ndarray:
        """[G] member slot believed to lead each group (-1 none)."""
        return np.asarray(self.state.lead)

    def commit_index(self) -> np.ndarray:
        return np.asarray(self.state.commit)

    def terms(self) -> np.ndarray:
        return np.asarray(self.state.term)

    def commit_terms(self) -> np.ndarray:
        """[G] term of the entry AT each commit index — what frontier
        markers and snapshots must record: a restarted/installed
        follower seeds its log slot 0 with this value, and the
        leader's append match at prev=frontier compares against the
        ENTRY's term, not the group's current term."""
        st = self.state
        return np.asarray(term_at(st.log_term, st.offset, st.last,
                                  st.commit))

    def terms_at(self, idx: np.ndarray) -> np.ndarray:
        """[G] term of the entry at ``idx`` per group (0 outside the
        retained window)."""
        st = self.state
        return np.asarray(term_at(st.log_term, st.offset, st.last,
                                  self._put(idx, np.int32)))

    def committed_payload(self, group: int, index: int):
        return self.payloads[group].get(index)

    # -- leader path ------------------------------------------------------

    def propose(self, n_new: np.ndarray,
                data: list[list[bytes]] | None = None,
                self_ack: bool = True):
        """Append ``n_new[g]`` entries on lanes where this slot leads.
        Returns (valid, base): which lanes accepted, and each lane's
        pre-append last index (keys the caller's bookkeeping).

        ``self_ack=False`` (the pipelined server): the append does NOT
        advance this slot's own match — the caller counts its own ack
        via :meth:`ack_self` only after the WAL fsync covering these
        entries has landed, so commit can never form a quorum out of
        a non-durable local copy."""
        st = self.state
        base = np.asarray(st.last)
        lead = self.is_leader()
        st, err = leader_append(
            st, self._put(n_new, np.int32),
            self._full(self.slot), self_ack=self_ack)
        self.state = st
        overflow = np.asarray(err)
        self.errors["overflow"] = overflow
        valid = lead & (np.asarray(n_new) > 0) & ~overflow
        if data is not None:
            pay = self.payloads
            for gi in np.nonzero(valid)[0].tolist():
                row, b0 = pay[gi], int(base[gi])
                for j, blob in enumerate(data[gi][:int(n_new[gi])]):
                    row[b0 + 1 + j] = blob
        return valid, base

    def build_append(self, peer: int,
                     lane_mask: np.ndarray | None = None
                     ) -> AppendBatch | None:
        """The batched msgApp frame for ``peer``: every lane this slot
        leads sends its window [next_[peer], min(next+E-1, last)] (or
        a need_snap flag past compaction, raft.go:207-209).

        ``lane_mask`` restricts the frame to a subset of groups — the
        pipelined server stripes groups across parallel connections,
        and each stripe's frames must cover only ITS lanes so one
        lane's appends always ride one ordered connection."""
        mask = (np.ones(self.g, bool) if lane_mask is None
                else np.asarray(lane_mask, bool))
        p = np.asarray(_build_append_fused(
            self.state, self._put(mask), peer=peer, e=self.e))
        active = p[:, 0].astype(bool)
        if not active.any():
            return None
        need_snap = p[:, 1].astype(bool)
        prev_idx = p[:, 2]
        n_ents = p[:, 3]
        terms2 = p[:, 6:]
        # flat fetch: one (group, gindex) table drives one pass over
        # the payload ring — no per-group inner loop; the same table
        # ships on the wire (FLAG_PACKED) so the follower stores flat
        groups, gindex = flat_entry_table(prev_idx, n_ents)
        pay = self.payloads
        flat = [pay[gi].get(ix, b"")
                for gi, ix in zip(groups.tolist(), gindex.tolist())]
        return AppendBatch(
            sender=self.slot, term=p[:, 4],
            prev_idx=prev_idx, prev_term=terms2[:, 0],
            n_ents=n_ents, commit=p[:, 5],
            active=active, need_snap=need_snap,
            ent_terms=terms2[:, 1:],
            payloads=PackedPayloads.from_counts(flat, n_ents),
            ent_group=groups if self.packed_wire else None,
            ent_gindex=gindex if self.packed_wire else None)

    def ack_self(self, upto: np.ndarray) -> None:
        """Count this host's own DURABLE ack (pipelined mode):
        advance own match to ``upto`` (monotone max) once the WAL
        fsync covering entries ``<= upto`` has landed, then
        quorum-commit — one fused dispatch."""
        self.state = _ack_self_fused(self.state,
                                     self._full(self.slot),
                                     self._put(upto, np.int32))

    def optimistic_advance(self, peer: int, b: AppendBatch) -> None:
        """Advance ``next_[:, peer]`` past the window just SENT in
        frame ``b`` (etcd raft OptimisticUpdate) so the next
        build_append ships the following entries without waiting for
        the ack.  match is untouched — only real acks move quorum."""
        sent = (np.asarray(b.prev_idx)
                + np.asarray(b.n_ents)).astype(np.int32)
        active = np.asarray(b.active) & ~np.asarray(b.need_snap)
        self.state = progress_optimistic(
            self.state, self._full(peer),
            self._put(sent, np.int32), active=self._put(active))

    def probe_reset(self, peer: int) -> None:
        """Roll ``next_[:, peer]`` back to ``match + 1`` after a
        transport failure dropped in-flight frames (etcd raft
        becomeProbe): resend from the last CONFIRMED point."""
        self.state = progress_probe(self.state, self._full(peer))

    def step_down(self, mask: np.ndarray) -> None:
        """Abdicate the masked leader lanes (check-quorum, PR 10):
        a leader whose outbound frames still deliver but whose
        inbound acks are lost keeps the followers' election timers
        reset FOREVER while never committing anything — the
        asymmetric-partition wedge.  The server calls this when a
        lane's quorum ack basis (the lease clock) has gone stale for
        longer than the full worst-case election window: stop
        heartbeating so the followers can elect a reachable
        leader."""
        self.state = _step_down(
            self.state, self._put(np.asarray(mask, bool)))

    def handle_append_resp(self, r: AppendResp) -> np.ndarray:
        """Absorb a peer's batched response; returns the [G] commit
        vector after quorum advance."""
        self.state = _absorb_resp(
            self.state, r.sender, self._put(r.term),
            self._put(r.ok), self._put(r.acked),
            self._put(r.hint), self._put(r.active))
        return np.asarray(self.state.commit)

    def handle_append_resps(self, resps: list[AppendResp]) -> np.ndarray:
        """Absorb responses in order, as :meth:`handle_append_resp`
        absorbs each: ONE put, dispatch and read-back for every ``k``
        of them (a shorter run is padded).  Returns the [n + 1, G]
        commit vectors: row 0 before the first response, row i after
        response i."""
        out = []
        for c in range(0, len(resps), self.k):
            chunk = resps[c:c + self.k]
            rows = self._resp_rows(chunk)
            self.state, commits = _absorb_resps(self.state,
                                                self._put(rows))
            out.append(np.asarray(commits)[(1 if out else 0):
                                           len(chunk) + 1])
        return np.concatenate(out)

    def _resp_rows(self, resps: list[AppendResp]) -> np.ndarray:
        """``_absorb_resps``' [G, k, 6] input: the responses, then
        padding rows (sender -1)."""
        rows = np.zeros((self.g, self.k, 6), np.int32)
        rows[:, len(resps):, 0] = -1
        for i, r in enumerate(resps):
            rows[:, i] = np.stack([
                np.full(self.g, r.sender), r.term, r.ok, r.acked,
                r.hint, r.active], axis=1)
        return rows

    def prepare_absorb(self) -> None:
        """Compile the batched absorb ahead of the first response: an
        all-padding run, whose result is the state as it is."""
        _absorb_resps(self.state, self._put(self._resp_rows([])))

    # -- follower path ----------------------------------------------------

    def handle_append(self, b: AppendBatch) -> AppendResp:
        """Batched msgApp receipt (stepFollower, raft.go:496-504):
        adopt higher terms, maybe_append current-term lanes, store
        payloads, reply with match/hint arrays — ONE fused device
        dispatch + ONE packed fetch per frame (the pipeline's frame
        rates made the unfused chain's ~8 dispatches the follower's
        top CPU line).  The CALLER persists the accepted entries
        BEFORE shipping the response."""
        st, packed = _handle_append_fused(
            self.state, self._full(b.sender), self._put(b.term),
            self._put(b.prev_idx), self._put(b.prev_term),
            self._put(b.ent_terms), self._put(b.n_ents),
            self._put(b.commit), self._put(b.active),
            self._put(b.need_snap))
        self.state = st
        p = np.asarray(packed)
        ok_np = p[:, 0].astype(bool)
        cur = p[:, 1].astype(bool)
        self.errors["conflict"] = p[:, 2].astype(bool)
        self.errors["overflow"] = (self.errors["overflow"]
                                   | p[:, 3].astype(bool))
        pay = self.payloads
        if (b.ent_group is not None
                and isinstance(b.payloads, PackedPayloads)):
            # packed frame: the validated flat table routes every
            # blob in ONE pass — mask by the accepting lanes, no
            # per-group dict hop
            groups = np.asarray(b.ent_group)
            gl, il = groups.tolist(), \
                np.asarray(b.ent_gindex).tolist()
            flat = b.payloads.flat
            for k in np.nonzero(ok_np[groups])[0].tolist():
                pay[gl[k]][il[k]] = flat[k]
        else:
            for gi in np.nonzero(ok_np)[0].tolist():
                row, b0 = pay[gi], int(b.prev_idx[gi])
                blobs = b.payloads[gi]
                for j in range(int(b.n_ents[gi])):
                    row[b0 + 1 + j] = blobs[j]
        # A need_snap lane acks POSITIVELY at its commit (the
        # reference's handleSnapshot reply, raft.go:418-424): the
        # follower durably holds everything at or below its commit,
        # and after a snapshot install this is what advances the
        # leader's match/next past its compaction point.  (A reject's
        # hint repair — _absorb_resp sets next_ = hint+1 — repairs
        # next_, but a need_snap lane sends no append to reject, so
        # without this positive ack the leader re-flags need_snap
        # forever and the follower loops snapshot pulls — found by
        # the chaos drill.)  The fused op already folded the need
        # lanes into acked (= commit there); ok/active fold here.
        need_mask = np.asarray(b.need_snap)
        need = need_mask & cur
        return AppendResp(
            sender=self.slot, term=p[:, 5],
            ok=ok_np | need,
            acked=p[:, 4],
            hint=p[:, 6],
            active=cur | (need_mask & np.asarray(b.active)),
            appended=ok_np)

    def install_snapshot(self, frontier: np.ndarray,
                         terms: np.ndarray,
                         members: np.ndarray | None = None
                         ) -> np.ndarray:
        """Collapse lanes to a pulled snapshot's frontier
        (raft.go:535-554 batched); returns installed lanes."""
        st, installed = restore_snapshot(
            self.state, self._put(frontier, np.int32),
            self._put(terms, np.int32),
            members=None if members is None else self._put(members))
        self.state = st
        inst = np.asarray(installed)
        for gi in np.nonzero(inst)[0]:
            cut = int(frontier[gi])
            p = self.payloads[gi]
            if p and min(p) <= cut:
                self.payloads[gi] = {k: v for k, v in p.items()
                                     if k > cut}
        return inst

    # -- elections --------------------------------------------------------

    def _draw_timeouts(self) -> np.ndarray:
        """[G] election timeouts from this slot's stratified band.

        The draw is randomized WITHIN ``[election + slot*w,
        election + (slot+1)*w)`` where ``w = election // m`` — bands
        are disjoint across slots, so two live hosts' timers cannot
        fire in the same tick band at all.  Plain uniform
        ``[election, 2*election)`` draws (raft.go:608-617) let two
        survivors collide with probability ~1/election per round;
        at p99 over hundreds of drill lanes that shows up as 2-3
        failed election rounds (~5.5s recoveries measured by the
        kill->writable decomposition).  The per-campaign redraw is
        kept for decorrelation within a band; worst case stays
        <= 2*election for slot < m."""
        w = max(1, self.election // max(1, self.m))
        lo = self.election + self.slot * w
        return self._rng.integers(lo, lo + w, size=self.g)

    def begin_campaign(self, mask: np.ndarray) -> VoteReq:
        """Start campaigns on the masked lanes; the returned frame
        goes to every peer.  Caller persists the ballot (term+vote)
        BEFORE shipping (vote durability, wal.go:35-39's state
        record).

        Each campaign RE-DRAWS the fired lanes' election timeouts
        from the slot's stratified band (see _draw_timeouts).  A
        fixed per-lane timeout lets two hosts that drew equal values
        fire in lockstep forever: both campaign the same term, each
        votes for itself, neither grants — a split that repeats
        every timeout (the chaos drill's ~12s leaderless windows,
        VERDICT r3 #6)."""
        mask_d = self._put(np.asarray(mask, bool))
        st, mj, lterm = _begin_campaign(
            self.state, mask_d, slot=self.slot)
        fresh = self._draw_timeouts()
        st = st._replace(timeout=jnp.where(
            mask_d, self._put(fresh, np.int32), st.timeout))
        self.state = st
        return VoteReq(sender=self.slot, term=np.asarray(st.term),
                       last=np.asarray(st.last),
                       lterm=np.asarray(lterm),
                       active=np.asarray(mj))

    def handle_vote(self, v: VoteReq) -> VoteResp:
        """Batched msgVote receipt (raft.go:511-518): adopt higher
        terms, grant where log-up-to-date and not already voted.
        Caller persists the ballot before shipping the response."""
        st = self.state
        active = self._put(v.active)
        term = self._put(v.term)
        st = _adopt_term(st, term,
                         self._full(-1), active)
        st, granted = grant_vote(
            st, self._put(v.last), self._put(v.lterm), term,
            self._full(v.sender), active=active)
        st = st._replace(elapsed=jnp.where(granted, 0, st.elapsed))
        self.state = st
        return VoteResp(sender=self.slot, term=np.asarray(st.term),
                        granted=np.asarray(granted),
                        active=np.asarray(active))

    def tally(self, mask: np.ndarray,
              resps: list[VoteResp]) -> np.ndarray:
        """Count votes (self + granted responses) for the campaign
        lanes; quorum from live member counts.  Returns won lanes
        (already promoted to leader)."""
        votes = np.asarray(mask, np.int32).copy()  # own vote
        st = self.state
        for r in resps:
            st = _adopt_term(st, self._put(r.term),
                             self._full(-1),
                             self._put(r.active))
            votes += (r.granted & r.active).astype(np.int32)
        quorum = np.asarray(st.nmembers) // 2 + 1
        still_cand = np.asarray(st.role) == CANDIDATE
        won = np.asarray(mask, bool) & still_cand & (votes >= quorum)
        self.state = _become_leader(st, self._put(won),
                                    slot=self.slot)
        lost = np.asarray(mask, bool) & ~won
        if lost.any():
            # Loser backoff: a refused campaign usually means a
            # better-qualified peer exists (our log is behind, or the
            # peer is mid-candidacy) — re-firing on the normal band
            # just churns terms and, under slow frame delivery, can
            # pre-empt that peer's own campaign for several rounds
            # (measured by the chaos drill as 5s+ multi-round
            # elections).  Waiting one extra election period before
            # retrying gives every other slot's band a clear shot
            # while still guaranteeing progress if we are the only
            # candidate left.
            extra = self._draw_timeouts() + self.election
            stl = self.state
            self.state = stl._replace(timeout=jnp.where(
                self._put(lost), self._put(extra, np.int32),
                stl.timeout))
        if won.any():
            # Raft safety: uncommitted tail payloads beyond our last
            # may be overwritten by the new term — drop stale keys
            last = np.asarray(self.state.last)
            for gi in np.nonzero(won)[0]:
                p = self.payloads[gi]
                if p and max(p) > int(last[gi]):
                    self.payloads[gi] = {
                        k: v for k, v in p.items()
                        if k <= int(last[gi])}
        return won

    # -- timers / maintenance --------------------------------------------

    def tick(self) -> np.ndarray:
        """Advance timers; returns lanes whose election timer fired
        (caller runs the campaign round-trip)."""
        st, elect, _beat = tick_batch(self.state)
        self.state = st
        return np.asarray(elect)

    def mark_applied(self, upto: np.ndarray) -> None:
        st = self.state
        upto = self._put(upto, np.int32)
        self.state = st._replace(applied=jnp.maximum(
            st.applied, jnp.minimum(upto, st.commit)))

    def compact(self) -> None:
        """Slide every lane's window up to its applied index; a lane
        this slot leads stops at the slowest member's confirmed index
        (see ``_compact_cut``)."""
        st = self.state
        st, _err = compact_batch(
            st, _compact_cut(st, slot=self.slot, keep=self.cap // 2))
        self.state = st
        cut = np.asarray(st.offset)
        for gi in range(self.g):
            p = self.payloads[gi]
            c = int(cut[gi])
            if p and min(p) < c:
                self.payloads[gi] = {k: v for k, v in p.items()
                                     if k >= c}

    def apply_conf_change(self, add: bool, slot: int,
                          mask: np.ndarray | None = None) -> None:
        """Adopt a COMMITTED membership change (server layer proposes
        it through the log first, server.go:542-559)."""
        mask = np.ones(self.g, bool) if mask is None \
            else np.asarray(mask, bool)
        self.state = conf_change_batch(
            self.state, self._full(bool(add), jnp.bool_),
            self._full(slot),
            self._full(self.slot),
            active=self._put(mask))
