"""Co-hosted multi-raft runtime: G groups × M members, batched.

The reference hosts ONE raft group per process and tests multi-node
behavior with an in-process fake network pump (raft_test.go:1203-1263).
This runtime is the batched generalization: member ``m`` of *every*
group lives in one ``GroupState`` batch (arrays [G]), so a full
M-member cluster of G co-hosted groups is M pytrees, and "message
delivery" between co-hosted members is array exchange — no
serialization, no sockets (SURVEY §5.8: intra-slice communication is
sharded-array collectives; inter-member DCN transport stays at the
server layer for cross-host peers).

The hot path (propose → replicate → respond → commit) is ONE fused
jit call per round (`_fused_round`): all M² member-pair exchanges and
the quorum commit run on device; the host syncs once for the returned
commit delta.  Elections are batched and fused too, decomposed into
droppable vote-request / vote-response phases sharing the same
per-edge fault mask machinery as replication (the batched analog of
the reference's lossy fake network, raft_test.go:1258-1287).

Error lanes are per-group: an overflowing or conflicted group stalls
alone (its lanes surface in :attr:`MultiRaft.errors`) while the rest
of the batch keeps committing — no batch-wide exceptions.

Payload bytes stay host-side (a per-group ring keyed by log index —
the wrong shape for HBM), mirroring the split in SURVEY §7: the
device owns index/term/commit math, the host owns opaque blobs.
"""

from __future__ import annotations

import threading
import time
from collections.abc import Mapping, Sequence
from functools import partial, wraps

import numpy as np

import jax
import jax.numpy as jnp

from ..obs import metrics as _obs
from ..obs.devledger import ledger as _ledger
from ..utils.trace import tracer
from .batched import (
    CANDIDATE,
    FOLLOWER,
    LEADER,
    GroupState,
    apply_conf_change as conf_change_batch,
    grant_vote,
    init_groups,
    leader_append,
    compact as compact_batch,
    maybe_append,
    maybe_commit,
    progress_repair,
    progress_update,
    restore_snapshot,
    term_at,
    term_window,
    tick as tick_batch,
)


#: served rounds whose input states the program consumed in place
#: (donated: the old tuple's log is deleted once the call returns)
_M_DONATED = _obs.registry.counter("etcd_round_donated_total")
#: host<->device bytes of each served round: its input put, a mixed
#: routing's leader vector, its pack read back
_M_TRANSFER = _obs.registry.histogram("etcd_round_transfer_bytes")

#: ``_payload_low`` of a group that holds no payload
_NO_PAYLOAD = np.iinfo(np.int64).max


def _drop_dense(drop, m: int, g: int) -> np.ndarray:
    """Per-edge fault dict {(a, b): [G] bool} → dense [M, M, G]."""
    dense = np.zeros((m, m, g), bool)
    for (a, b), mask in (drop or {}).items():
        dense[a, b] |= np.asarray(mask, bool)
    return dense


#: rows of the round's packed result, an ``int32[len(PACK), G]``
PACK = ("valid", "base", "newly", "overflow", "conflict", "terms",
        "commit")


def readback(stage: str, value) -> np.ndarray:
    """A device value as a host array: the ledger's ``fetch`` (bytes
    and block time billed to ``stage``), its wait filed as one sample
    of ``mg.readback``.  Every device→host materialisation of the
    engine thread goes through here, so the count of the wait over
    the count of rounds says how many crossings a round cost."""
    t0 = time.perf_counter()
    out = _ledger.fetch(stage, value)
    tracer.record_wait("mg.readback", time.perf_counter() - t0)
    return out


def _max_over(states, field: str):
    out = getattr(states[0], field)
    for st in states[1:]:
        out = jnp.maximum(out, getattr(st, field))
    return out


def _take_applied(states, upto):
    """Every member's ``applied`` raised to ``min(upto, commit)``:
    what the host declared applied since the last round, absorbed
    before the round reads anything else (all zeros: a no-op)."""
    return tuple(st._replace(applied=jnp.maximum(
        st.applied, jnp.minimum(upto, st.commit))) for st in states)


def _round_core(states, sels, n_new, drop, e, slots):
    """The propose→replicate→respond→commit round body, parametric in
    which member slots participate as leaders.

    Returns ``(states', rows)``: ``rows`` maps each name of
    :data:`PACK` to a [G] array, the round's whole answer to the
    host — ``valid`` / ``base`` key the host payload store (which
    groups had a real leader, and its pre-append last index),
    ``newly`` is the commit delta, ``overflow`` / ``conflict`` the
    per-group error lanes, ``terms`` / ``commit`` the maxima over the
    members at the round's end.  The programs hand it over as ONE
    array (:func:`_pack`).

    ``sels[i]``: [G] bool router mask for ``slots[i]`` (which groups
    address that slot as leader).  The general round passes every
    slot; the hot-slot specialization passes exactly one — compiling
    1/M of the append work and (M-1) of the M(M-1) pair exchanges,
    which is exactly equivalent whenever the router addresses a
    single slot (a slot with an all-False ``sel`` contributes nothing
    to the general program: every send/append/response in its pair
    iterations is masked by ``sel``, and ``maybe_commit`` on a
    non-addressed state is a fixed point — its match vectors cannot
    advance without sends).
    """
    states = list(states)
    m = len(states)
    g = n_new.shape[0]

    commits0 = _max_over(states, "commit")

    valid = jnp.zeros((g,), bool)
    base = jnp.zeros((g,), jnp.int32)
    overflow = jnp.zeros((g,), bool)
    conflict = jnp.zeros((g,), bool)

    # -- leader appends (raft.go:279-286), masked per slot -------------
    for sel, slot in zip(sels, slots):
        st = states[slot]
        is_lead = sel & (st.role == LEADER)
        valid = valid | is_lead
        base = jnp.where(is_lead, st.last, base)
        with jax.named_scope("round.append"):
            st, err = leader_append(
                st, jnp.where(sel, n_new, 0),
                jnp.full((g,), slot, jnp.int32), active=sel)
        overflow |= err
        states[slot] = st
    # groups whose append was refused (overflow) must not key host
    # payloads: their log never advanced past base
    valid = valid & ~overflow

    # -- replication: leaders send, followers respond, quorum commits --
    for sel, slot in zip(sels, slots):
        lst = states[slot]
        with jax.named_scope("round.exchange"):
            for peer in range(m):
                if peer == slot:
                    continue
                pst = states[peer]
                # window: follower's next.. min(next+E-1, leader last)
                nxt = jnp.take_along_axis(
                    lst.next_, jnp.full((g, 1), peer, jnp.int32),
                    axis=1)[:, 0]
                # followers at a lower term adopt the leader's
                # (raft.go:388-396); stale leaders don't send; removed /
                # not-yet-added slots are masked edges on both ends
                send = sel & (lst.term >= pst.term) & \
                    (lst.role == LEADER) & ~drop[slot, peer] & \
                    lst.members[:, slot] & lst.members[:, peer]
                adopt = send & (lst.term > pst.term)
                pst = pst._replace(
                    term=jnp.where(adopt, lst.term, pst.term),
                    vote=jnp.where(adopt, -1, pst.vote),
                    role=jnp.where(send, FOLLOWER, pst.role),
                    lead=jnp.where(send, slot, pst.lead))
                # slow follower fell behind the leader's compaction
                # point: send a snapshot instead (raft.go:207-209,
                # needSnapshot :556); the follower's log collapses to
                # the leader's offset entry and normal appends resume.
                # The whole install path runs under lax.cond — in the
                # serving steady state no lane ever needs a snapshot, and
                # the masked [G, cap] log-collapse write was ~1/3 of each
                # exchange's memory traffic (round-5 profile: the
                # per-follower exchange is the serving round's cost)
                needs_snap = send & (nxt <= lst.offset) & (lst.offset > 0)
                peer_v = jnp.full((g,), peer, jnp.int32)

                def with_snap(operand, lst=lst, needs_snap=needs_snap,
                              peer_v=peer_v, peer=peer, slot=slot):
                    pst, nxt = operand
                    snap_term = term_at(lst.log_term, lst.offset,
                                        lst.last, lst.offset)
                    follower_commit = pst.commit
                    pst, installed = restore_snapshot(
                        pst, lst.offset, snap_term,
                        commit=jnp.minimum(lst.commit, lst.offset),
                        active=needs_snap, members=lst.members)
                    # installed lanes ack the snapshot index; lanes that
                    # rejected (commit already past it) reply with their
                    # commit, repairing the leader's stale next_ without
                    # any truncation (raft.go:419-424).  Both acks ride
                    # the response edge — droppable like any msgAppResp.
                    snap_ack = ~drop[peer, slot]
                    upd = progress_update(lst, peer_v, lst.offset,
                                          active=installed & snap_ack)
                    rejected = needs_snap & ~installed
                    upd = progress_update(upd, peer_v, follower_commit,
                                          active=rejected & snap_ack)
                    nxt = jnp.where(
                        installed & snap_ack, lst.offset + 1,
                        jnp.where(rejected & snap_ack,
                                  follower_commit + 1, nxt))
                    return (pst, nxt), (upd.next_, upd.match)

                def no_snap(operand, lst=lst):
                    return operand, (lst.next_, lst.match)

                (pst, nxt), (l_next, l_match) = jax.lax.cond(
                    needs_snap.any(), with_snap, no_snap, (pst, nxt))
                lst = lst._replace(next_=l_next, match=l_match)

                prev_idx = nxt - 1
                prev_term = term_at(lst.log_term, lst.offset, lst.last,
                                    prev_idx)
                n_send = jnp.clip(lst.last - prev_idx, 0, e)
                ent_terms = term_window(lst.log_term, lst.offset,
                                        lst.last, prev_idx + 1, e)
                pst, ok, e_conf, e_over = maybe_append(
                    pst, prev_idx, prev_term, ent_terms, n_send,
                    lst.commit, active=send)
                conflict |= e_conf
                overflow |= e_over
                # any append from the legitimate leader resets the
                # follower's election timer (otherwise every follower
                # would depose a healthy leader each `timeout` ticks)
                pst = pst._replace(elapsed=jnp.where(send, 0, pst.elapsed))
                states[peer] = pst
                # msgAppResp: success → progress update; reject →
                # progress_repair jumps next_ to the follower's commit+1
                # (one round instead of the reference's decrement-by-one
                # probe — see the helper's docstring for the safety
                # argument and the wedge the SET semantics prevent)
                resp_ok = send & ~drop[peer, slot]
                acked = prev_idx + n_send
                lst = progress_update(lst, peer_v, acked,
                                      active=resp_ok & ok)
                lst = progress_repair(lst, peer_v, pst.commit,
                                      active=resp_ok & ~ok)
        with jax.named_scope("round.commit"):
            lst = maybe_commit(lst)
        states[slot] = lst

    commits1 = _max_over(states, "commit")
    return tuple(states), dict(
        valid=valid, base=base, newly=commits1 - commits0,
        overflow=overflow, conflict=conflict,
        terms=_max_over(states, "term"), commit=commits1)


def _pack(rows):
    """A round's rows as ONE ``int32[len(PACK), G]`` array: one
    output buffer and one transfer, the ``g`` axis last (placed like
    the fault mask where the state is sharded)."""
    return jnp.stack([rows[k].astype(jnp.int32) for k in PACK])


@partial(jax.jit, static_argnames=("e",), donate_argnums=0)
def _fused_round(states, leader, inp, drop, e):
    """One full propose→replicate→respond→commit round, on device.

    ``states``: tuple of M GroupState pytrees; ``leader``: [G] i32
    member slot per group (-1 none); ``inp``: [2, G] i32, row 0 the
    proposals to append at each group's leader, row 1 what the host
    has applied since the last round (:func:`_take_applied`);
    ``drop``: [M, M, G] bool per-edge fault mask (drop[a, b, g] kills
    a→b messages of group g).

    Returns ``(states', pack)`` (:func:`_pack`).  ``states`` is
    donated, here and in every program that returns a whole new tuple
    (the hot and train forms, the campaign): each field's output takes
    its input's buffer, so a round allocates the pack and nothing else,
    and the tuple passed in is deleted.  Each leaf has to be a buffer
    of its own (:meth:`MultiRaft.seed`, :func:`~.batched.init_groups`).
    """
    m = len(states)
    sels = [leader == s for s in range(m)]
    states, rows = _round_core(_take_applied(states, inp[1]), sels,
                               inp[0], drop, e, tuple(range(m)))
    return states, _pack(rows)


@partial(jax.jit, static_argnames=("e", "slot"), donate_argnums=0)
def _fused_round_hot(states, sel, inp, drop, e, slot):
    """The single-addressed-slot round (serving steady state: every
    group routes to one member slot — the bootstrap shape and the
    common shape between elections).  Compiles 1/M of the append work
    and 1/M of the pair exchanges; exactly equivalent to
    :func:`_fused_round` under that routing (see _round_core)."""
    states, rows = _round_core(_take_applied(states, inp[1]), [sel],
                               inp[0], drop, e, (slot,))
    return states, _pack(rows)


def _round_train(states, sels, inp, drop, e, k, slots):
    """``k`` rounds of ``inp[0]`` proposals each, the host's applied
    vector absorbed before the first.  The pack holds the train's
    total of ``newly``, its error lanes ORed and the frontier at its
    end; ``valid`` / ``base`` are a single round's keying and read 0
    here."""
    def body(_, carry):
        states, total, overflow, conflict = carry
        states, rows = _round_core(states, sels, inp[0], drop, e,
                                   slots)
        return (states, total + rows["newly"],
                overflow | rows["overflow"],
                conflict | rows["conflict"])

    g = inp.shape[1]
    none = jnp.zeros((g,), bool)
    zero = jnp.zeros((g,), jnp.int32)
    states, total, overflow, conflict = jax.lax.fori_loop(
        0, k, body, (_take_applied(states, inp[1]), zero, none, none))
    return states, _pack(dict(
        valid=none, base=zero, newly=total, overflow=overflow,
        conflict=conflict, terms=_max_over(states, "term"),
        commit=_max_over(states, "commit")))


@partial(jax.jit, static_argnames=("e", "k", "slot"), donate_argnums=0)
def _fused_multi_round_hot(states, sel, inp, drop, e, k, slot):
    """``k`` hot-slot rounds in one dispatch (propose_rounds')."""
    return _round_train(states, [sel], inp, drop, e, k, (slot,))


@partial(jax.jit, static_argnames=("e", "k"), donate_argnums=0)
def _fused_multi_round(states, leader, inp, drop, e, k):
    """``k`` consecutive fused rounds in ONE device dispatch.

    The per-round host sync in :meth:`MultiRaft.propose` is a fixed
    cost per dispatch that is transport, not consensus.  Payload-less
    callers (benchmarks, idle heartbeat trains, catch-up replication
    bursts) don't need the per-round keying arrays, so the whole train
    runs device-side with a single read-back.

    Returns ``(states', pack)`` (:func:`_round_train`).
    """
    m = len(states)
    sels = [leader == s for s in range(m)]
    return _round_train(states, sels, inp, drop, e, k,
                        tuple(range(m)))


@partial(jax.jit, static_argnames=("slot",), donate_argnums=0)
def _fused_campaign(states, mask, drop, slot):
    """Batched campaign for member ``slot`` (raft.go:358-370), fused.

    Vote requests and vote responses are separate droppable phases:
    ``drop[slot, peer]`` kills the request (peer never votes),
    ``drop[peer, slot]`` kills the response (peer's vote is RECORDED
    but the candidate never learns of it — the asymmetry real lossy
    networks produce, raft_test.go:204 dueling-candidates territory).

    Returns ``(states', won)``; quorum uses each group's live member
    count (nmembers), not the static member-slot count.
    """
    states = list(states)
    m = len(states)
    g = mask.shape[0]
    mj = mask

    cand = states[slot]
    mj = mj & cand.members[:, slot]  # a non-member cannot campaign
    new_term = cand.term + mj.astype(jnp.int32)
    cand = cand._replace(
        term=new_term,
        role=jnp.where(mj, CANDIDATE, cand.role),
        vote=jnp.where(mj, slot, cand.vote))

    votes = mj.astype(jnp.int32)  # own vote
    cand_last = cand.last
    cand_lterm = term_at(cand.log_term, cand.offset, cand.last,
                         cand.last)
    for peer in range(m):
        if peer == slot:
            continue
        st = states[peer]
        req = mj & ~drop[slot, peer] & cand.members[:, peer]
        # msgVote carries the candidate term; peers at a lower term
        # adopt it and forget the deposed leader (becomeFollower with
        # lead=None, raft.go:388-396 batched)
        adopt = req & (cand.term > st.term)
        st = st._replace(
            term=jnp.where(adopt, cand.term, st.term),
            vote=jnp.where(adopt, -1, st.vote),
            role=jnp.where(adopt, FOLLOWER, st.role),
            lead=jnp.where(adopt, -1, st.lead))
        st, granted = grant_vote(
            st, cand_last, cand_lterm, cand.term,
            jnp.full((g,), slot, jnp.int32), active=req)
        # granting a vote resets the election timer (the reference
        # resets on any message from a legitimate candidate)
        st = st._replace(elapsed=jnp.where(granted, 0, st.elapsed))
        states[peer] = st
        resp = granted & ~drop[peer, slot]
        votes += resp.astype(jnp.int32)

    quorum = cand.nmembers // 2 + 1
    won = mj & (votes >= quorum)
    # winners become leader; note the reference appends an empty
    # entry on becoming leader (raft.go:329-348) so the new term has
    # a committable entry — replicated via the normal path
    cand = cand._replace(
        role=jnp.where(won, LEADER, cand.role),
        lead=jnp.where(won, slot, cand.lead),
        match=jnp.where(won[:, None], 0, cand.match),
        next_=jnp.where(won[:, None], cand.last[:, None] + 1,
                        cand.next_))
    states[slot] = cand
    return tuple(states), won


def _holding_states(method):
    """Run ``method`` holding the engine's lock.  The round programs
    donate the member states, so whatever reads or replaces them does
    so under the lock: a caller on another thread (a snapshot taken
    off the engine thread) waits for the round instead of meeting an
    array the round has deleted."""
    @wraps(method)
    def run(self, *args, **kwargs):
        with self._lock:
            return method(self, *args, **kwargs)
    return run


class MultiRaft:
    """G co-hosted groups, M members each, batched across groups.

    :attr:`errors` holds the per-group error lanes of the most recent
    round: ``{"overflow": [G] bool, "conflict": [G] bool}``.
    Overflowing groups stall (compact to resume) without blocking the
    batch; conflict lanes mark the reference's panic condition
    (append conflict below commit, log.go:57).
    """

    def __init__(self, g: int, m: int, cap: int, election: int = 10,
                 max_batch_ents: int = 8, seed: int = 0,
                 live: int | None = None):
        self.g, self.m, self.cap = g, m, cap
        self._lock = threading.RLock()   # see _holding_states
        self.e = max_batch_ents
        rng = np.random.default_rng(seed)
        self.states: list[GroupState] = []
        for slot in range(m):
            st = init_groups(g, m, cap, election=election, live=live)
            # randomized election timeouts (raft.go:611-617): each
            # member draws [election, 2*election) per group
            st = st._replace(timeout=jnp.asarray(
                rng.integers(election, 2 * election, size=g), jnp.int32))
            self.states.append(st)
        self.leader = np.full(g, -1, np.int32)  # member slot per group
        # [G, M] membership, which every member holds alike, kept on
        # the host too: members_mask() answers from it, so no reader
        # on another thread holds an array the next round donates
        live = m if live is None else live
        self._members = np.tile(np.arange(m) < live, (g, 1))
        # cached single-addressed-slot routing (None = mixed): keyed
        # off self.leader, recomputed only where the routing changes
        # (campaign wins, conf-change removals) — the round dispatch
        # picks the 1/M-work hot-slot program when it is set
        self._route_hot: int | None = None
        self._hot_sel = None  # cached device router mask (see
        # _hot_sel_dev)
        # host-side payload store: per-group dict index -> bytes, and
        # per group the lowest index it holds (_NO_PAYLOAD when none),
        # so that a compaction visits only the groups it prunes
        self.payloads: list[dict[int, bytes]] = [dict() for _ in range(g)]
        self._payload_low = np.full(g, _NO_PAYLOAD, np.int64)
        self.errors = {"overflow": np.zeros(g, bool),
                       "conflict": np.zeros(g, bool),
                       "compact_oob": np.zeros(g, bool)}
        # fault-free rounds reuse one device-resident all-False mask
        # instead of re-uploading an [M, M, G] array per call
        self._no_drop = jnp.zeros((m, m, g), bool)
        self._placer = None   # set by shard(): parallel.mesh placer
        self._sh_drop = None  # set by shard(): for [M, M, G] masks
        self._sh_rows = None  # set by shard(): for [K, G] rows
        # what the host declared applied since the last round: rides
        # the next round's input (mark_applied), None when nothing is
        self._applied_due: np.ndarray | None = None
        # the last round's pack as host arrays (_take_pack), and the
        # (term, commit) arrays of the states that round returned:
        # while they are still the engine's, last_terms / last_commit
        # are the device's view (_pack_stands)
        self.last_valid = np.zeros(g, bool)
        self.last_base = np.zeros(g, np.int32)
        self.last_terms = np.zeros(g, np.int32)
        self.last_commit = np.zeros(g, np.int32)
        self._pack_of: tuple = ()

    # -- intra-slice scale-out --------------------------------------------

    def shard(self, mesh) -> None:
        """Shard every member slot's [G]-leading state over the
        mesh's ``g`` axis (BASELINE config 5 in serving shape):
        groups are independent, so the fused rounds run SPMD across
        the mesh with no cross-device collectives.  Callers re-invoke
        after wholesale state replacement (restart seeding)."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import (
            check_group_divisible,
            leading_placer,
            shard_leading,
        )

        check_group_divisible(mesh, self.g)
        self.states = [
            type(st)(*(shard_leading(mesh, x) for x in st))
            for st in self.states]
        self._no_drop = jax.device_put(
            self._no_drop, NamedSharding(mesh, P(None, None, "g")))
        # Per-call [G] host inputs (leader routing, proposal counts,
        # campaign masks) must be PLACED with the same g-sharding
        # before each dispatch (parallel.mesh.leading_placer's
        # docstring has the measured why); the [M, M, G] fault masks
        # shard their TRAILING axis and keep their own sharding.
        self._placer = leading_placer(mesh)
        self._hot_sel = None  # placement changed: rebuild the mask
        self._sh_drop = NamedSharding(mesh, P(None, None, "g"))
        self._sh_rows = NamedSharding(mesh, P(None, "g"))

    def seed(self, frontier, terms, members=None) -> None:
        """Re-seed every member with a committed log in compacted form
        (a restart): ``offset = last = commit = applied = frontier``,
        slot 0 of the log carries ``terms`` for match checks, and
        ``members`` ([G, M] bool, where given) is every member's
        membership mask.  Each field of each member is a put of its
        own (``jnp.array`` copies: ``asarray`` may alias one host
        array for several): the round programs donate every leaf of
        the states, and one buffer can be donated once a call."""
        fr = np.asarray(frontier, np.int32)
        tm = np.asarray(terms, np.int32)
        for slot, st in enumerate(self.states):
            term = jnp.array(tm)
            st = st._replace(
                term=term, offset=jnp.array(fr), last=jnp.array(fr),
                commit=jnp.array(fr), applied=jnp.array(fr),
                log_term=jnp.zeros((self.g, self.cap), jnp.int32)
                .at[:, 0].set(term))
            if members is not None:
                st = st._replace(
                    members=jnp.array(members, bool),
                    nmembers=jnp.array(np.sum(members, axis=1),
                                       jnp.int32))
            self.states[slot] = st
        if members is not None:
            self._members = np.array(members, bool)

    def _put_g(self, arr, dtype=None):
        """[G] host array → device, g-sharded when the state is."""
        if self._placer is not None:
            return self._placer(arr, dtype)
        return jnp.asarray(np.asarray(arr, dtype))

    def _put_drop(self, dense: np.ndarray):
        """[M, M, G] fault mask → device, g-sharded like _no_drop."""
        if self._sh_drop is not None:
            return jax.device_put(dense, self._sh_drop)
        return jnp.asarray(dense)

    def _round_input(self, n_new: np.ndarray):
        """The round's ONE put: ``[2, G]`` i32, the proposal counts
        over the applied vector that was due (zeros when none is),
        g-sharded on its trailing axis when the state is."""
        inp = np.zeros((2, self.g), np.int32)
        inp[0] = n_new
        if self._applied_due is not None:
            inp[1] = self._applied_due
            self._applied_due = None
        return jax.device_put(inp, self._sh_rows)

    def _take_pack(self, stage: str, states, pack) -> np.ndarray:
        """Adopt a round program's result: the states, and the pack
        read back in ONE transfer and split into host arrays.
        Returns the round's newly committed counts."""
        self.states = list(states)
        self._pack_of = tuple((st.term, st.commit) for st in states)
        rows = dict(zip(PACK, readback(stage, pack)))
        self.last_valid = rows["valid"].astype(bool)
        self.last_base = rows["base"]
        self.last_terms = rows["terms"]
        self.last_commit = rows["commit"]
        self.errors["overflow"] = rows["overflow"].astype(bool)
        self.errors["conflict"] = rows["conflict"].astype(bool)
        return rows["newly"]

    def _pack_stands(self) -> bool:
        """Whether every member's ``term`` and ``commit`` are still
        the arrays the last round returned with its pack (an array
        object is never rewritten: the next round donates it and
        returns new ones, so the same object is the same value; the
        test is by identity alone and holds for a deleted array).
        Only those two are kept, never a whole state: a compaction's
        stale generation of logs must not stay alive for this."""
        return len(self._pack_of) == len(self.states) and all(
            st.term is t and st.commit is c
            for st, (t, c) in zip(self.states, self._pack_of))

    def _recompute_hot(self) -> None:
        mx = int(self.leader.max(initial=-1))
        self._route_hot = mx if mx >= 0 and bool(
            ((self.leader == mx) | (self.leader == -1)).all()) \
            else None
        self._hot_sel = None  # device router mask follows the routing

    def _hot_sel_dev(self, hot: int):
        """Device-resident ``leader == hot`` router mask, cached
        until the routing changes — re-placing a [G] host bool per
        dispatch was measurable serving overhead (round-5 profile)."""
        sel = self._hot_sel
        if sel is None:
            sel = self._hot_sel = self._put_g(self.leader == hot)
        return sel

    # -- elections (batched, fused, droppable) ---------------------------

    @_holding_states
    def campaign(self, slot: int, mask: np.ndarray | None = None,
                 drop=None) -> np.ndarray:
        """Member ``slot`` campaigns for the masked groups: term+1,
        vote self, request votes (droppable edges), count per-group
        quorums.  Returns the [G] bool mask of groups where it won.
        """
        g = self.g
        mask = np.ones(g, bool) if mask is None else np.asarray(mask, bool)
        dense = self._no_drop if not drop else \
            self._put_drop(_drop_dense(drop, self.m, g))
        _ledger.h2d("multiraft.campaign", mask)
        with _ledger.dispatch("multiraft.campaign"):
            states, won = _fused_campaign(
                tuple(self.states), self._put_g(mask), dense,
                slot=slot)
        self.states = list(states)
        won_np = readback("multiraft.campaign", won)
        self.leader = np.where(won_np, slot, self.leader).astype(np.int32)
        self._recompute_hot()
        if won_np.any():
            # Entries beyond the winner's last were never committed
            # (Raft safety: committed entries survive elections), so a
            # deposed leader's payloads at those indices are garbage
            # the new term may overwrite — drop them.
            winner_last = readback("multiraft.campaign",
                                   self.states[slot].last)
            for gi in np.nonzero(won_np)[0]:
                p = self.payloads[gi]
                cut = int(winner_last[gi])
                if p and max(p) > cut:  # skip the common no-op case
                    self._keep_payloads(gi, lambda k: k <= cut)
            # the becoming-leader empty entry (raft.go:329-348)
            self.propose(np.where(won_np, 1, 0).astype(np.int32),
                         drop=drop)
        return won_np

    # -- the replication hot path (one fused device call per round) ------

    @_holding_states
    def propose(self, n_new: np.ndarray,
                data: Sequence[list[bytes]] | Mapping[int, list[bytes]]
                | None = None,
                drop=None) -> np.ndarray:
        """Append ``n_new[g]`` proposals to each group's leader and
        run one full replicate→respond→commit round.  ``data[g]``
        holds the payloads of every group with ``n_new[g] > 0``.
        Returns the per-group count of newly committed entries."""
        g = self.g
        n_new = np.asarray(n_new, np.int32)
        dense = self._no_drop if not drop else \
            self._put_drop(_drop_dense(drop, self.m, g))
        # the round's three parts, each a stage at the ledger's seam
        # (the co-hosted engine's mg.consensus_round tiles into them):
        # dispatch up to the jitted call's return, one put in and one
        # program; wait the round's ONE read-back, which blocks until
        # the device has run the round; fetch the payload bookkeeping,
        # host work alone
        old_log = self.states[0].log_term
        with tracer.stage("mg.round.dispatch", cpu=False), \
                _ledger.dispatch("multiraft.round"):
            inp = self._round_input(n_new)
            _ledger.h2d("multiraft.round", inp)
            moved = inp.nbytes
            if self._route_hot is not None:
                hot = self._route_hot
                states, pack = _fused_round_hot(
                    tuple(self.states), self._hot_sel_dev(hot), inp,
                    dense, e=self.e, slot=hot)
            else:
                leader = self._put_g(self.leader)
                moved += leader.nbytes
                states, pack = _fused_round(
                    tuple(self.states), leader, inp, dense, e=self.e)
            # the pack is int32[len(PACK), G]
            _M_TRANSFER.observe(moved + 4 * len(PACK) * g)
        with tracer.stage("mg.round.wait", cpu=False):
            newly = self._take_pack("multiraft.round", states, pack)
            if old_log.is_deleted():
                _M_DONATED.inc()
        # payloads recorded only for groups whose addressed member
        # really IS leader (a deposed member may linger in
        # self.leader), keyed from its pre-append last index; the
        # assignment arrays are kept for callers that key their own
        # bookkeeping (the multi-group server's wait registry)
        with tracer.stage("mg.round.fetch", cpu=False):
            if data is not None:
                # only the groups that took proposals, so a mapping
                # of those answers as a list of G lists does
                for gi in np.nonzero(self.last_valid & (n_new > 0))[0]:
                    first = int(self.last_base[gi]) + 1
                    for j, blob in enumerate(
                            data[gi][:int(n_new[gi])]):
                        self.payloads[gi][first + j] = blob
                    self._payload_low[gi] = min(
                        self._payload_low[gi], first)
        return newly

    @_holding_states
    def propose_rounds(self, n_new: np.ndarray, rounds: int,
                       drop=None) -> np.ndarray:
        """``rounds`` consecutive payload-less propose→commit rounds
        fused into ONE device dispatch (each round appends
        ``n_new[g]`` entries at the leader and completes a full
        replicate→respond→commit exchange).  Returns the per-group
        TOTAL of newly committed entries.

        For callers that track payloads use :meth:`propose` — this
        path skips the per-round valid/base keying in exchange for
        eliminating the per-round host↔device sync (a
        dispatch-latency saving on any backend)."""
        g = self.g
        dense = self._no_drop if not drop else \
            self._put_drop(_drop_dense(drop, self.m, g))
        with _ledger.dispatch("multiraft.train"):
            inp = self._round_input(np.asarray(n_new, np.int32))
            _ledger.h2d("multiraft.train", inp)
            if self._route_hot is not None:
                hot = self._route_hot
                states, pack = _fused_multi_round_hot(
                    tuple(self.states), self._hot_sel_dev(hot), inp,
                    dense, e=self.e, k=rounds, slot=hot)
            else:
                states, pack = _fused_multi_round(
                    tuple(self.states), self._put_g(self.leader), inp,
                    dense, e=self.e, k=rounds)
        return self._take_pack("multiraft.train", states, pack)

    def replicate(self, drop=None) -> np.ndarray:
        """One replication round for every group: leaders send their
        pending window to every follower member, absorb the responses,
        advance the quorum commit (the batched §3.2 inner loop).

        ``drop``: optional fault-injection mask — ``drop[(a, b)]`` is a
        [G] bool array dropping messages from member a to member b for
        the masked groups, the batched analog of the reference's
        per-edge lossy fake network (raft_test.go:1258-1287).  Dropped
        appends are simply retried on a later round: the protocol's
        fire-and-forget contract (server.go:202-206)."""
        return self.propose(np.zeros(self.g, np.int32), drop=drop)

    # -- membership change (raft.go:376-387,431-435 batched) -------------

    @_holding_states
    def apply_conf_change(self, add: bool, slot: int,
                          mask: np.ndarray | None = None) -> None:
        """Apply a committed ConfChange to the masked groups: every
        co-hosted member adopts the new membership at once (the
        reference applies the committed entry at each member's server
        loop, server.go:542-559; co-hosted members share the host, so
        the fan-out is one batched update per member).

        Grow: the new slot starts empty (match 0, next last+1) and is
        caught up by normal replication — or the snapshot path if the
        leader already compacted.  Shrink: the removed slot's edges
        mask off, its stale match can't form quorums, and a removed
        leader steps down (its groups elect fresh on the next
        timeout).  The CALLER is responsible for proposing the change
        through the log and applying it only once committed (the
        server layer's job, as in the reference)."""
        g = self.g
        mask = np.ones(g, bool) if mask is None else np.asarray(mask, bool)
        mj = self._put_g(mask)
        addv = jnp.full((g,), bool(add))
        slotv = jnp.full((g,), slot, jnp.int32)
        for s in range(self.m):
            self.states[s] = conf_change_batch(
                self.states[s], addv, slotv,
                jnp.full((g,), s, jnp.int32), active=mj)
        members = self._members.copy()   # swapped whole, never torn
        members[mask, slot] = add
        self._members = members
        if not add:
            # deposed-by-removal groups lose their routing entry too
            self.leader = np.where(mask & (self.leader == slot), -1,
                                   self.leader).astype(np.int32)
            self._recompute_hot()

    @_holding_states
    def mark_applied(self, upto: np.ndarray) -> None:
        """The host consumer declares it has applied entries up to
        ``upto[g]`` (clamped to each member's commit).  Compaction
        never slides past this point, so committed-but-unconsumed
        payloads stay retrievable.  Nothing is dispatched: the vector
        rides the next round's input, which absorbs it before it
        reads anything else (no program moves ``commit`` between), and
        :meth:`compact`, which reads ``applied`` before that, puts it
        on the device first."""
        upto = np.array(upto, np.int32)       # the caller's may change
        due = self._applied_due
        self._applied_due = upto if due is None \
            else np.maximum(due, upto)

    def _flush_applied(self) -> None:
        """Put the applied vector that is due on the device now, in
        the eager form, for a reader of ``states[*].applied`` that
        comes before the next round."""
        if self._applied_due is None:
            return
        upto = self._put_g(self._applied_due)
        self._applied_due = None
        self.states = list(_take_applied(self.states, upto))

    @_holding_states
    def compact(self, upto: np.ndarray | None = None) -> None:
        """Compact every member's log at its applied index (the
        reference couples this to the snapshot trigger,
        server.go:313-316 + log.go:161); payloads below the
        compaction point are dropped from the host ring.  Call
        :meth:`mark_applied` first — compaction never outruns what
        the consumer declared applied.  Out-of-bounds lanes skip
        compaction (surfaced per-group in ``errors["compact_oob"]``,
        never batch-fatal)."""
        self._flush_applied()
        if upto is not None:
            upto = self._put_g(upto, np.int32)
        oob = cut = None
        for slot in range(self.m):
            st = self.states[slot]
            idx = st.applied
            if upto is not None:
                idx = jnp.minimum(idx, upto)
            st, err = compact_batch(st, jnp.maximum(idx, st.offset))
            oob = err if oob is None else oob | err
            cut = st.offset if cut is None \
                else jnp.minimum(cut, st.offset)
            self.states[slot] = st
        oob, cut = readback("multiraft.compact", jnp.stack(
            [oob.astype(jnp.int32), cut]))
        self.errors["compact_oob"] = oob.astype(bool)
        for gi in np.nonzero(self._payload_low < cut)[0]:
            c = int(cut[gi])
            self._keep_payloads(gi, lambda k: k >= c)

    def _keep_payloads(self, gi: int, keep) -> None:
        """Keep the payloads of group ``gi`` whose index ``keep``
        accepts, and its lowest held index with them."""
        p = {k: v for k, v in self.payloads[gi].items() if keep(k)}
        self.payloads[gi] = p
        self._payload_low[gi] = min(p, default=_NO_PAYLOAD)

    @_holding_states
    def tick(self, drop=None) -> None:
        """Advance every member's timers; campaign where they fire.
        ``drop`` faults apply to the resulting vote traffic too."""
        for slot in range(self.m):
            st, elect, _beat = tick_batch(self.states[slot])
            self.states[slot] = st
            fire = readback("multiraft.tick", elect)
            if fire.any():
                self.campaign(slot, fire, drop=drop)

    # -- views -----------------------------------------------------------

    @_holding_states
    def _max_view(self, field: str, last: np.ndarray) -> np.ndarray:
        """Max of ``field`` across members per group: the last
        round's own answer while its states still stand, else taken
        on the device and read back."""
        if self._pack_stands():
            return last
        return readback("multiraft.view",
                        _max_over(self.states, field))

    def commit_index(self) -> np.ndarray:
        """Max commit across members per group (any member's commit
        is authoritative once set)."""
        return self._max_view("commit", self.last_commit)

    def term_index(self) -> np.ndarray:
        """Max term across members per group."""
        return self._max_view("term", self.last_terms)

    def members_mask(self) -> np.ndarray:
        """[G, M] live-membership mask (every member holds the same:
        a committed ConfChange flips all of them at once), from the
        host's copy: safe on any thread, and no read-back."""
        return self._members.copy()

    def committed_payload(self, group: int, index: int) -> bytes | None:
        return self.payloads[group].get(index)

    @_holding_states
    def log_terms(self, slot: int) -> np.ndarray:
        return np.asarray(self.states[slot].log_term)
