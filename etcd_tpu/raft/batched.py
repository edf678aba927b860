"""Group-batched Raft engine: [G, ...] state arrays, masked XLA ops.

The reference runs ONE raft group per process and its hot loops are
scalar (`maybeCommit`'s sort, `log.append`/`findConflict` walks —
raft/raft.go:248-258, raft/log.go:49-84).  Here tens of thousands of
co-hosted groups step at once: state lives as leading-axis-``G``
arrays in HBM and every hot-path transition is a masked, branchless
batch op (BASELINE config 4).

Design split (the TPU-first shape of the protocol):

- **Device (this module)**: the *replication hot path* — follower
  ``maybe_append`` (term match, conflict scan, truncating append,
  commit advance), leader append + progress update + quorum commit,
  election timers, vote up-to-dateness checks, log compaction.  All
  pure functions of ``GroupState``; all jit/vmap/pjit-compatible
  (shard the ``G`` axis with parallel/mesh.py).
- **Host**: rare, branchy transitions — campaigns, config change,
  message routing between members (DCN) — driven by the scalar core
  (core.py), which doubles as the executable specification these ops
  are property-tested against.

Capacity model: each group's log is a CAP-slot window; slot ``s``
holds the term of entry ``offset + s`` (slot 0 = the dummy/compacted
entry, mirroring ``ents[0]`` in log.py).  Overflow and
conflict-below-commit (a panic in the reference, raft/log.go:57)
surface as per-group error lanes in the returned flags.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.quorum import commit_index_batch


FOLLOWER, CANDIDATE, LEADER = 0, 1, 2


class GroupState(NamedTuple):
    """Per-group consensus state, leading axis G (a jax pytree)."""

    term: jnp.ndarray       # [G] i32 current term
    vote: jnp.ndarray       # [G] i32 voted-for member slot (-1 none)
    role: jnp.ndarray       # [G] i32 FOLLOWER/CANDIDATE/LEADER
    lead: jnp.ndarray       # [G] i32 leader member slot (-1 none)
    commit: jnp.ndarray     # [G] i32 commit index
    applied: jnp.ndarray    # [G] i32 applied index
    log_term: jnp.ndarray   # [G, CAP] i32 terms; slot s = idx offset+s
    offset: jnp.ndarray     # [G] i32 compaction offset
    last: jnp.ndarray       # [G] i32 last log index
    match: jnp.ndarray      # [G, M] i32 leader view of peer match
    next_: jnp.ndarray      # [G, M] i32 leader view of peer next
    nmembers: jnp.ndarray   # [G] i32 live member count
    elapsed: jnp.ndarray    # [G] i32 ticks since last reset
    timeout: jnp.ndarray    # [G] i32 randomized election timeout
    members: jnp.ndarray    # [G, M] bool live-membership mask (a
                            # non-member slot is either removed or not
                            # yet added — both are masked edges; the
                            # reference's msgDenied self-stop,
                            # raft.go:376-387, has no message to deny
                            # in the shared-state co-hosted runtime)

    @property
    def cap(self) -> int:
        return self.log_term.shape[1]


def init_groups(g: int, m: int, cap: int, election: int = 10,
                live: int | None = None) -> GroupState:
    """Fresh follower groups at term 0 with empty logs.

    ``live``: how many of the ``m`` member slots start as cluster
    members (default all) — the rest are addable later via
    :func:`apply_conf_change` (grow-the-cluster bootstrap).
    """
    live = m if live is None else live
    members = jnp.tile(jnp.arange(m) < live, (g, 1))

    def full(v):
        # every field its own buffer: a program that donates the
        # state can donate a buffer once a call, never in two fields
        return jnp.full((g,), v, jnp.int32)

    return GroupState(
        term=full(0), vote=full(-1), role=full(FOLLOWER), lead=full(-1),
        commit=full(0), applied=full(0),
        log_term=jnp.zeros((g, cap), jnp.int32), offset=full(0),
        last=full(0),
        match=jnp.zeros((g, m), jnp.int32),
        next_=jnp.ones((g, m), jnp.int32),
        nmembers=full(live), elapsed=full(0), timeout=full(election),
        members=members,
    )


# ---------------------------------------------------------------------------
# log primitives (batched forms of log.py / reference raft/log.go)
# ---------------------------------------------------------------------------


def term_at(log_term, offset, last, idx):
    """Term of entry ``idx`` per group; 0 outside [offset, last].

    ``idx`` may be [G] or [G, K] (absolute entry indices).
    Batched ``RaftLog.term`` (log.go:117-124 via at()).
    """
    squeeze = idx.ndim == 1
    if squeeze:
        idx = idx[:, None]
    cap = log_term.shape[1]
    # one stable name for the gather over the [G, cap] window, which
    # is most of a round's device time, wherever it is called from
    with jax.named_scope("term_at"):
        slot = idx - offset[:, None]
        valid = (idx >= offset[:, None]) & (idx <= last[:, None]) & \
            (slot < cap)
        t = jnp.take_along_axis(log_term, jnp.clip(slot, 0, cap - 1),
                                axis=1)
        t = jnp.where(valid, t, 0)
    return t[:, 0] if squeeze else t


def _block_width(cap: int, e: int) -> int | None:
    """The narrowest blocks that tile a row of ``cap`` slots and hold
    a run of ``e``; None where ``e > cap``."""
    return next((w for w in range(e, cap + 1) if cap % w == 0), None)


def term_window(log_term, offset, last, start, e: int):
    """Terms of entries ``start .. start+e-1`` per group, [G, e]:
    :func:`term_at` of ``start[:, None] + arange(e)``, bit for bit,
    for the callers whose indices are such a run (the windows of a
    round).  ``term_at``'s gather of ``e`` elements a group was most
    of the fused round's device time at 10k groups x cap 1024
    (ledger, PR 30: ten ``s32[320000]`` fusions a round), and a
    ``dynamic_slice`` a group is a gather to the TPU too, as slow
    (chip run, PR 32).  So the run is read with selects alone: the
    row is cut into blocks at least ``e`` wide, the block the run
    starts in and the next one are picked by a one-hot over the
    blocks (one dense pass over the log), and the run is picked out
    of the pair the same way.

    A block outside the row is zeros, so a run that begins below
    ``offset`` or runs past slot ``cap`` reads 0 there as in
    ``term_at``; above ``last`` the mask says so.
    """
    g, cap = log_term.shape
    w = _block_width(cap, e)
    if w is None:
        # e > cap: the caller's shape is no window of this log
        return term_at(log_term, offset, last,
                       start[:, None] + jnp.arange(e, dtype=jnp.int32))
    with jax.named_scope("term_window"):
        slot0 = start - offset
        b = slot0 // w                  # floor: "block -1" below slot 0
        blocks = log_term.reshape(g, cap // w, w)
        k = jnp.arange(cap // w, dtype=jnp.int32)[None, :, None]
        pair = jnp.concatenate([
            jnp.sum(jnp.where(k == (b + i)[:, None, None], blocks, 0),
                    axis=1) for i in (0, 1)], axis=1)
        # pair[:, c] holds slot b * w + c; entry j of the run wants
        # slot slot0 + j
        j = jnp.arange(e, dtype=jnp.int32)
        c = jnp.arange(2 * w, dtype=jnp.int32)
        want = (slot0 - b * w)[:, None] + j
        t = jnp.sum(jnp.where(c[None, None, :] == want[:, :, None],
                              pair[:, None, :], 0), axis=2)
        return jnp.where(start[:, None] + j <= last[:, None], t, 0)


def append_window(log_term, offset, start, ent_terms, write):
    """``log_term`` [G, cap] with the slot of entry ``start + j`` set
    to ``ent_terms[:, j]`` wherever ``write[:, j]``: the write twin of
    :func:`term_window`, with selects alone.  A scatter of the E
    entries a group is, on the TPU, a sort of the G x E indices and a
    fusion over the whole log, once an exchange (9.5 of the 14.2 ms
    the device worked a round at 10k groups x cap 1024: ledger, PR
    38), and a gather of the row from the window as slow (85 ms an
    exchange: chip run, PR 21).  So the run is shifted into a pair of
    blocks by its offset into the first one, a few static shifts, and
    each block of the row selects the pair's first half, its second
    half or itself: one dense pass over the log.  A select a block
    took 9.1 ms for the bare hot round where one ``where`` over the
    ``[G, cap/w, w]`` view took 9.5 and the scatter 16.8 (10k x 5,
    cap 1024, E 32; chip runs, PR 39): XLA materialises the view's
    broadcast halves.

    A slot below 0 or at ``cap`` and past it lies in no block of the
    row and is dropped, as the scatter's ``mode="drop"`` dropped it.
    """
    g, cap = log_term.shape
    e = ent_terms.shape[1]
    w = _block_width(cap, e)
    slot0 = start - offset
    if w is None:
        # e > cap: the caller's shape is no window of this log; slot s
        # takes entry s - slot0
        r = jnp.arange(cap, dtype=jnp.int32)[None, :] - slot0[:, None]
        rc = jnp.clip(r, 0, e - 1)
        put = (r >= 0) & (r < e) & jnp.take_along_axis(write, rc, axis=1)
        return jnp.where(put, jnp.take_along_axis(ent_terms, rc, axis=1),
                         log_term)
    with jax.named_scope("append_window"):
        b = slot0 // w                  # floor: "block -1" below slot 0
        d = slot0 - b * w               # where the run starts in it
        # pair[:, c] is slot b * w + c: the run at columns d .. d+e-1,
        # shifted there bit by bit of d (d < w, and d + e <= 2w)
        pair = jnp.pad(ent_terms, ((0, 0), (0, 2 * w - e)))
        put = jnp.pad(write, ((0, 0), (0, 2 * w - e)))
        for bit in range((w - 1).bit_length()):
            s = 1 << bit
            on = ((d & s) != 0)[:, None]
            pair = jnp.where(on, jnp.pad(pair, ((0, 0), (s, 0)))[:, :2 * w],
                             pair)
            put = jnp.where(on, jnp.pad(put, ((0, 0), (s, 0)))[:, :2 * w],
                            put)
        first, second = (slice(0, w), slice(w, 2 * w))
        return jnp.concatenate([
            jnp.where((b == k)[:, None] & put[:, first], pair[:, first],
                      jnp.where((b == k - 1)[:, None] & put[:, second],
                                pair[:, second],
                                log_term[:, k * w:(k + 1) * w]))
            for k in range(cap // w)], axis=1)


def match_term(log_term, offset, last, idx, term):
    """Batched ``RaftLog.match_term`` — NB a term-0 entry at a valid
    index cannot be distinguished from absence, exactly like the
    reference where the dummy entry has term 0 (log.go:14-18)."""
    in_range = (idx >= offset) & (idx <= last)
    return in_range & (term_at(log_term, offset, last, idx) == term)


def is_up_to_date(log_term, offset, last, cand_idx, cand_term):
    """Batched ``RaftLog.is_up_to_date`` (log.go:136-139): vote grant
    condition on candidate's (last index, last term)."""
    lt = term_at(log_term, offset, last, last)
    return (cand_term > lt) | ((cand_term == lt) & (cand_idx >= last))


@jax.jit
def maybe_append(state: GroupState, prev_idx, prev_term, ent_terms,
                 n_ents, leader_commit, active=None):
    """Follower replication step, batched ``RaftLog.maybe_append``
    (log.go:49-69): term-match at prev, conflict scan, truncating
    append, commit advance.

    ``ent_terms`` [G, E] terms of incoming entries (entry j has index
    prev_idx + 1 + j), ``n_ents`` [G] how many are real, ``active``
    [G] bool mask of groups actually receiving an append (inactive
    groups pass through unchanged).

    Returns ``(state', ok, err_conflict, err_overflow)``:
    ``ok`` = the append was accepted (msgAppResp success);
    ``err_conflict`` = conflict below commit, a reference-panic
    condition (log.go:57); ``err_overflow`` = log-capacity overflow
    (compact and retry).  Error lanes leave the group's state
    untouched and respond with a reject — one hot or corrupted group
    never poisons the batch.
    """
    g, cap = state.log_term.shape
    e = ent_terms.shape[1]
    if active is None:
        active = jnp.ones((g,), bool)

    ok = active & match_term(state.log_term, state.offset, state.last,
                             prev_idx, prev_term)

    # conflict scan (log.go:77-84) over the incoming window
    e_idx = prev_idx[:, None] + 1 + jnp.arange(e, dtype=jnp.int32)
    existing = term_window(state.log_term, state.offset, state.last,
                           prev_idx + 1, e)
    valid_e = jnp.arange(e) < n_ents[:, None]
    mismatch = valid_e & ((e_idx > state.last[:, None]) |
                          (existing != ent_terms))
    conflict = mismatch.any(axis=1)
    ci_rel = jnp.argmax(mismatch, axis=1)  # first mismatch position
    ci = prev_idx + 1 + ci_rel
    lastnewi = prev_idx + n_ents

    err_conflict = ok & conflict & (ci <= state.commit)
    err_overflow = ok & (lastnewi - state.offset >= cap)
    ok = ok & ~(err_conflict | err_overflow)

    # truncating append: slots in [prev_idx+1, lastnewi] take the
    # incoming terms (identical values where already matching, new
    # values from the conflict point on)
    log_term = append_window(state.log_term, state.offset, prev_idx + 1,
                             ent_terms, ok[:, None] & valid_e)
    last = jnp.where(ok & conflict, lastnewi, state.last)
    tocommit = jnp.minimum(leader_commit, lastnewi)
    commit = jnp.where(ok & (tocommit > state.commit), tocommit,
                       state.commit)
    return state._replace(log_term=log_term, last=last,
                          commit=commit), ok, err_conflict, err_overflow


@partial(jax.jit, static_argnames=("self_ack",))
def leader_append(state: GroupState, n_new, self_slot, active=None,
                  self_ack: bool = True):
    """Leader-side ``append_entry`` (raft.go:279-286): append n_new
    entries of the leader's term, update own progress.

    Returns ``(state', err)`` with err = capacity overflow lanes.
    Overflow lanes are left untouched (no partial window write, no
    ``last`` advance): the group stalls until compaction frees space
    while the rest of the batch proceeds.

    ``self_ack=False`` (the pipelined dist tier) appends WITHOUT
    advancing the leader's own ``match`` — the entries exist in the
    engine log but do not yet count toward quorum.  The caller runs
    :func:`progress_update` for its own slot (DistMember.ack_self)
    once its WAL fsync covering them lands,
    so a quorum can only ever be formed from DURABLE copies (Raft's
    overlap rule: send may precede local durability, counting may
    not).
    """
    g, cap = state.log_term.shape
    if active is None:
        active = jnp.ones((g,), bool)
    self_live = jnp.take_along_axis(
        state.members, self_slot[:, None], axis=1)[:, 0]
    active = active & (state.role == LEADER) & self_live

    lastnew = state.last + n_new
    err = active & (lastnew - state.offset >= cap)
    do = active & ~err

    cap_idx = state.offset[:, None] + jnp.arange(cap, dtype=jnp.int32)
    write = do[:, None] & (cap_idx > state.last[:, None]) & \
        (cap_idx <= lastnew[:, None])
    log_term = jnp.where(write, state.term[:, None], state.log_term)

    m = state.match.shape[1]
    onehot = jax.nn.one_hot(self_slot, m, dtype=bool)
    match = state.match
    if self_ack:
        match = jnp.where(do[:, None] & onehot, lastnew[:, None],
                          match)
    next_ = jnp.where(do[:, None] & onehot, lastnew[:, None] + 1,
                      state.next_)
    last = jnp.where(do, lastnew, state.last)
    return state._replace(log_term=log_term, last=last, match=match,
                          next_=next_), err


@jax.jit
def progress_update(state: GroupState, from_slot, idx, active=None):
    """Leader handling a successful msgAppResp (raft.go:456-463):
    ``prs[from].update(idx)`` batched as a one-hot scatter."""
    g, m = state.match.shape
    if active is None:
        active = jnp.ones((g,), bool)
    active = active & (state.role == LEADER)
    onehot = jax.nn.one_hot(from_slot, m, dtype=bool) & active[:, None]
    match = jnp.where(onehot, jnp.maximum(state.match, idx[:, None]),
                      state.match)
    next_ = jnp.where(onehot, jnp.maximum(state.next_, idx[:, None] + 1),
                      state.next_)
    return state._replace(match=match, next_=next_)


@jax.jit
def progress_optimistic(state: GroupState, from_slot, idx,
                        active=None):
    """Pipelined leader: advance ``next_[from]`` past a just-SENT
    window (etcd raft ``Progress.OptimisticUpdate``) so the next
    frame carries the following entries without waiting for the ack.
    ``match`` is untouched — only real acks may move quorum input."""
    g, m = state.match.shape
    if active is None:
        active = jnp.ones((g,), bool)
    active = active & (state.role == LEADER)
    onehot = jax.nn.one_hot(from_slot, m, dtype=bool) & active[:, None]
    next_ = jnp.where(onehot,
                      jnp.maximum(state.next_, idx[:, None] + 1),
                      state.next_)
    return state._replace(next_=next_)


@jax.jit
def progress_probe(state: GroupState, from_slot, active=None):
    """Pipelined leader on TRANSPORT failure to a peer: optimistic
    ``next_`` advances for frames the peer never received must be
    rolled back to the last confirmed point, ``match + 1`` (etcd raft
    ``Progress.becomeProbe``).  Safe unconditionally: match only ever
    reflects real acks, so resending from there is at worst a
    duplicate prefix the follower's append check ignores."""
    g, m = state.match.shape
    if active is None:
        active = jnp.ones((g,), bool)
    active = active & (state.role == LEADER)
    onehot = jax.nn.one_hot(from_slot, m, dtype=bool) & active[:, None]
    return state._replace(next_=jnp.where(
        onehot, jnp.maximum(state.match + 1, 1), state.next_))


def progress_repair(state: GroupState, from_slot, hint,
                    active) -> GroupState:
    """Leader handling a REJECTED msgAppResp: SET
    ``next_[from] = hint + 1`` where ``hint`` is the follower's
    commit — one-round repair instead of the reference's
    decrement-by-one probe (raft.go:464-470).

    Safe in BOTH directions: the committed prefix is immutable and
    ``prev = hint`` is always verifiable at the follower (compaction
    never outruns applied ≤ commit, and the compaction slot carries
    the offset entry's term).  The SET matters — a min()-clamped
    variant deadlocked a lane permanently when the leader's next_ was
    stale-low against a follower that had compacted to its commit
    (round-4 chaos-drill wedge; see distmember._absorb_resp)."""
    g, m = state.match.shape
    active = active & (state.role == LEADER)
    onehot = jax.nn.one_hot(from_slot, m, dtype=bool) & active[:, None]
    repaired = jnp.maximum(hint + 1, 1)
    return state._replace(next_=jnp.where(
        onehot, repaired[:, None], state.next_))


@jax.jit
def maybe_commit(state: GroupState) -> GroupState:
    """Quorum commit advance (raft.go:248-258 + log.go:88-95) for all
    leader groups: q-th largest LIVE match, gated on current-term
    entry (a removed member's stale match must not form quorums)."""
    mci = commit_index_batch(
        jnp.where(state.members, state.match, 0), state.nmembers)
    t_at = term_at(state.log_term, state.offset, state.last, mci)
    ok = (state.role == LEADER) & (mci > state.commit) & \
        (t_at == state.term)
    return state._replace(commit=jnp.where(ok, mci, state.commit))


def shift_left(x, shift):
    """Row ``g`` of ``x`` moved ``shift[g]`` slots to the left with
    zeros shifted in: ``out[g, j] = x[g, j + shift[g]]`` where that is
    inside the row, else 0 (``shift`` clamped to ``[0, cap]``).  A
    barrel shifter: one static shift and one select a bit of the
    shift, so the pass streams the window log2(cap) + 1 times; a
    per-element gather of the ``[G, cap]`` window took 2.8 s a member
    at 100 000 groups on the v5e.  Written in ``lax`` so that tracing
    it starts no nested trace a step."""
    g, cap = x.shape
    zero = np.zeros((), x.dtype)
    shift = lax.clamp(np.int32(0), shift.astype(np.int32), np.int32(cap))
    for b in range(cap.bit_length()):
        step = 1 << b
        moved = lax.full_like(x, 0) if step >= cap else lax.pad(
            lax.slice_in_dim(x, step, cap, axis=1), zero,
            ((0, 0, 0), (0, step, 0)))
        on = lax.ne(lax.bitwise_and(shift, np.int32(step)), np.int32(0))
        x = lax.select(lax.broadcast_in_dim(on, (g, cap), (0,)), moved, x)
    return x


@jax.jit
def compact(state: GroupState, idx, active=None):
    """Batched ``RaftLog.compact`` (log.go:161-169): slide the window
    so slot 0 holds entry ``idx`` (which keeps its term for future
    match checks).  err lanes where idx ∉ [offset, applied]."""
    g = state.log_term.shape[0]
    if active is None:
        active = jnp.ones((g,), bool)
    err = active & ((idx < state.offset) | (idx > state.applied))
    do = active & ~err
    rolled = shift_left(state.log_term, idx - state.offset)
    return state._replace(
        log_term=jnp.where(do[:, None], rolled, state.log_term),
        offset=jnp.where(do, idx, state.offset)), err


@jax.jit
def restore_snapshot(state: GroupState, idx, term, commit=None,
                     active=None, members=None):
    """Install a snapshot into the masked groups (raft.go:535-554 +
    log.go:185-191 batched): the log collapses to a single dummy slot
    at ``idx`` carrying ``term`` (for future match checks), and
    commit/applied jump to ``idx``.  The state-machine payload itself
    is the host's concern (SURVEY §7: opaque blobs stay host-side).

    ``members``: optional [G, M] snapshot-carried membership
    (raft.go:535-554 rebuilds prs from s.Nodes) — installed lanes
    adopt it, with nmembers recounted.

    Guard (raft.go:536-538): lanes whose commit already reaches
    ``idx`` REJECT the snapshot — commit/applied never regress and
    already-committed suffixes are not truncated.  Returns
    ``(state', installed)``; rejected-but-active lanes are the
    follower's "reply with my commit" case (raft.go:419-424).
    """
    g, cap = state.log_term.shape
    if active is None:
        active = jnp.ones((g,), bool)
    if commit is None:
        commit = idx
    installed = active & (idx > state.commit)
    slot0 = jnp.concatenate(
        [term[:, None], jnp.zeros((g, cap - 1), jnp.int32)], axis=1)
    new_members = state.members
    nmembers = state.nmembers
    if members is not None:
        new_members = jnp.where(installed[:, None], members,
                                state.members)
        nmembers = new_members.sum(axis=1).astype(jnp.int32)
    return state._replace(
        log_term=jnp.where(installed[:, None], slot0, state.log_term),
        offset=jnp.where(installed, idx, state.offset),
        last=jnp.where(installed, idx, state.last),
        commit=jnp.where(installed, commit, state.commit),
        applied=jnp.where(installed, commit, state.applied),
        members=new_members, nmembers=nmembers), installed


@jax.jit
def apply_conf_change(state: GroupState, add, slot, self_slot,
                      active=None):
    """Batched ConfChange apply (raft.go:376-387,431-435 semantics).

    ``add`` [G] bool (True = AddNode, False = RemoveNode), ``slot``
    [G] i32 the member slot being changed, ``self_slot`` [G] i32 the
    slot THIS state belongs to (a member removing itself steps down
    to follower — the reference's ShouldStop self-stop,
    raft.go:158-161).  A newly added member starts with match 0 and
    next = last+1 (raft.go:349-351 set_progress); nmembers recounts,
    so quorums and vote counts track the live size.
    """
    g, m = state.match.shape
    if active is None:
        active = jnp.ones((g,), bool)
    onehot = jax.nn.one_hot(slot, m, dtype=bool) & active[:, None]
    members = jnp.where(onehot, add[:, None], state.members)
    newly = onehot & add[:, None] & ~state.members
    match = jnp.where(newly, 0, state.match)
    next_ = jnp.where(newly, state.last[:, None] + 1, state.next_)
    nmembers = members.sum(axis=1).astype(jnp.int32)
    self_removed = active & ~add & (slot == self_slot)
    role = jnp.where(self_removed, FOLLOWER, state.role)
    # a group whose leader was removed has no leader until the next
    # election
    lead = jnp.where(active & ~add & (slot == state.lead), -1,
                     state.lead)
    return state._replace(members=members, match=match, next_=next_,
                          nmembers=nmembers, role=role, lead=lead)


@jax.jit
def tick(state: GroupState, heartbeat: int = 1):
    """Batched tick (raft.go:288-301): advance timers, report which
    groups fire an election timeout (followers/candidates) or a
    heartbeat (leaders).  The host drains the fire masks and runs the
    (rare) campaign logic through the scalar core."""
    elapsed = state.elapsed + 1
    elect = (state.role != LEADER) & (elapsed >= state.timeout)
    beat = (state.role == LEADER) & (elapsed >= heartbeat)
    elapsed = jnp.where(elect | beat, 0, elapsed)
    return state._replace(elapsed=elapsed), elect, beat


@jax.jit
def grant_vote(state: GroupState, cand_idx, cand_term, msg_term,
               cand_slot, active=None):
    """Vote grant decision batched (raft.go:511-518): term check,
    not-voted-or-same check, log up-to-dateness."""
    g = state.term.shape[0]
    if active is None:
        active = jnp.ones((g,), bool)
    utd = is_up_to_date(state.log_term, state.offset, state.last,
                        cand_idx, cand_term)
    free = (state.vote == -1) | (state.vote == cand_slot)
    grant = active & (msg_term >= state.term) & free & utd
    vote = jnp.where(grant, cand_slot, state.vote)
    return state._replace(vote=vote), grant


@jax.jit
def replication_round(state: GroupState, n_new, self_slot,
                      resp_slots, resp_idx, resp_mask):
    """One fused leader-side pipeline step (the flagship batch op):

    1. append ``n_new`` proposals per leader group (raft.go:279),
    2. absorb a [G, R] batch of msgAppResp progress updates
       (raft.go:456-463) — R responses per group, masked,
    3. advance quorum commit (raft.go:248).

    Returns ``(state', err, n_committed)`` where n_committed is the
    per-group count of newly committed entries this round.
    """
    before = state.commit
    state, err = leader_append(state, n_new, self_slot)
    r = resp_slots.shape[1]
    for k in range(r):
        state = progress_update(state, resp_slots[:, k], resp_idx[:, k],
                                active=resp_mask[:, k])
    state = maybe_commit(state)
    return state, err, state.commit - before
