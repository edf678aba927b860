"""Batched raft engine vs the scalar core (executable specification).

Random per-group operation sequences run through BOTH the scalar
RaftLog/maybe_commit spec (raft/log.py, the host-parity structure) and
the [G, CAP] batched ops; state must match lane-for-lane.  This is the
batched analog of the reference's pure-SM table tests (SURVEY §4).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from etcd_tpu.raft import batched
from etcd_tpu.raft.batched import (
    FOLLOWER,
    LEADER,
    GroupState,
    init_groups,
)
from etcd_tpu.raft.log import LogError, RaftLog
from etcd_tpu.wire import Entry

from test_term_window import WRITES, parent_maybe_append

G, M, CAP, E = 32, 5, 64, 8


def _mk_logs(rng):
    """Random scalar logs + the matching batched state."""
    logs = []
    st = init_groups(G, M, CAP)
    log_term = np.zeros((G, CAP), np.int32)
    last = np.zeros(G, np.int32)
    commit = np.zeros(G, np.int32)
    for g in range(G):
        n = int(rng.integers(0, 20))
        terms = np.sort(rng.integers(1, 5, size=n)).astype(np.int32)
        lg = RaftLog()
        lg.ents = [Entry()] + [Entry(term=int(t), index=i + 1)
                               for i, t in enumerate(terms)]
        lg.committed = int(rng.integers(0, n + 1))
        logs.append(lg)
        log_term[g, 1:n + 1] = terms
        last[g] = n
        commit[g] = lg.committed
    st = st._replace(log_term=jnp.asarray(log_term),
                     last=jnp.asarray(last),
                     commit=jnp.asarray(commit))
    return logs, st


def test_term_at_matches_scalar():
    rng = np.random.default_rng(0)
    logs, st = _mk_logs(rng)
    idx = rng.integers(-2, 25, size=(G, 4)).astype(np.int32)
    t = np.asarray(batched.term_at(st.log_term, st.offset, st.last,
                                   jnp.asarray(idx)))
    for g in range(G):
        for k in range(4):
            assert t[g, k] == logs[g].term(int(idx[g, k])), (g, k)


def test_maybe_append_parity():
    rng = np.random.default_rng(1)
    for trial in range(5):
        logs, st = _mk_logs(rng)
        prev_idx = rng.integers(0, 22, size=G).astype(np.int32)
        prev_term = rng.integers(0, 5, size=G).astype(np.int32)
        n_ents = rng.integers(0, E + 1, size=G).astype(np.int32)
        ent_terms = rng.integers(1, 5, size=(G, E)).astype(np.int32)
        ent_terms = np.sort(ent_terms, axis=1)  # terms non-decreasing
        leader_commit = rng.integers(0, 30, size=G).astype(np.int32)

        st2, ok, errc, erro = batched.maybe_append(
            st, jnp.asarray(prev_idx), jnp.asarray(prev_term),
            jnp.asarray(ent_terms), jnp.asarray(n_ents),
            jnp.asarray(leader_commit))
        ok = np.asarray(ok)
        err = np.asarray(errc) | np.asarray(erro)
        lt2 = np.asarray(st2.log_term)
        last2 = np.asarray(st2.last)
        commit2 = np.asarray(st2.commit)

        for g in range(G):
            lg = logs[g]
            ents = [Entry(term=int(ent_terms[g, j]),
                          index=int(prev_idx[g]) + 1 + j)
                    for j in range(int(n_ents[g]))]
            try:
                want_ok = lg.maybe_append(
                    int(prev_idx[g]), int(prev_term[g]),
                    int(leader_commit[g]), ents)
                want_err = False
            except LogError:
                want_err = True
                want_ok = True  # scalar raises mid-accept
            assert bool(err[g]) == want_err, (trial, g)
            if want_err:
                continue
            assert bool(ok[g]) == want_ok, (trial, g)
            assert last2[g] == lg.last_index(), (trial, g)
            assert commit2[g] == lg.committed, (trial, g)
            for i in range(lg.offset, lg.last_index() + 1):
                assert lt2[g, i - lg.offset] == lg.term(i), (trial, g, i)


def test_leader_append_and_commit_parity():
    rng = np.random.default_rng(2)
    logs, st = _mk_logs(rng)
    term = np.asarray([lg.term(lg.last_index()) + 1 for lg in logs],
                      np.int32)
    st = st._replace(role=jnp.full((G,), LEADER, jnp.int32),
                     term=jnp.asarray(term))
    n_new = rng.integers(0, 5, size=G).astype(np.int32)
    self_slot = np.zeros(G, np.int32)
    st2, err = batched.leader_append(st, jnp.asarray(n_new),
                                     jnp.asarray(self_slot))
    assert not np.asarray(err).any()
    last2 = np.asarray(st2.last)
    match2 = np.asarray(st2.match)
    for g in range(G):
        want = logs[g].last_index() + int(n_new[g])
        assert last2[g] == want
        assert match2[g, 0] == want
        # appended slots carry the leader term
        for i in range(logs[g].last_index() + 1, want + 1):
            assert np.asarray(st2.log_term)[g, i] == term[g]

    # responses from a quorum commit the new entries
    resp_slots = np.tile(np.asarray([1, 2], np.int32), (G, 1))
    resp_idx = np.stack([last2, last2], axis=1).astype(np.int32)
    resp_mask = np.ones((G, 2), bool)
    st3 = st2
    for k in range(2):
        st3 = batched.progress_update(
            st3, jnp.asarray(resp_slots[:, k]),
            jnp.asarray(resp_idx[:, k]),
            active=jnp.asarray(resp_mask[:, k]))
    st3 = batched.maybe_commit(st3)
    commit3 = np.asarray(st3.commit)
    for g in range(G):
        # 3 of 5 members at last2 -> quorum; commit gated on cur term
        want = last2[g] if int(n_new[g]) > 0 else np.asarray(st.commit)[g]
        assert commit3[g] == want, g


def test_replication_round_counts():
    st = init_groups(G, M, CAP)
    st = st._replace(role=jnp.full((G,), LEADER, jnp.int32),
                     term=jnp.ones((G,), jnp.int32))
    n_new = jnp.full((G,), 3, jnp.int32)
    self_slot = jnp.zeros((G,), jnp.int32)
    resp_slots = jnp.tile(jnp.asarray([[1, 2]], jnp.int32), (G, 1))
    resp_idx = jnp.full((G, 2), 3, jnp.int32)
    resp_mask = jnp.ones((G, 2), bool)
    st2, err, ncomm = batched.replication_round(
        st, n_new, self_slot, resp_slots, resp_idx, resp_mask)
    assert not np.asarray(err).any()
    np.testing.assert_array_equal(np.asarray(ncomm), 3)
    np.testing.assert_array_equal(np.asarray(st2.commit), 3)


def test_capacity_overflow_err_lane():
    st = init_groups(4, 3, 8)
    st = st._replace(role=jnp.full((4,), LEADER, jnp.int32),
                     term=jnp.ones((4,), jnp.int32))
    n_new = jnp.asarray([1, 9, 2, 30], jnp.int32)
    st2, err = batched.leader_append(st, n_new, jnp.zeros(4, jnp.int32))
    np.testing.assert_array_equal(np.asarray(err),
                                  [False, True, False, True])


def test_compact_parity():
    rng = np.random.default_rng(3)
    logs, st = _mk_logs(rng)
    st = st._replace(applied=st.commit)
    for lg in logs:
        lg.applied = lg.committed
    idx = np.asarray([min(lg.committed, lg.last_index()) for lg in logs],
                     np.int32)
    st2, err = batched.compact(st, jnp.asarray(idx))
    assert not np.asarray(err).any()
    for g in range(G):
        lg = logs[g]
        if idx[g] > 0:
            lg.compact(int(idx[g]))
        assert np.asarray(st2.offset)[g] == lg.offset
        for i in range(lg.offset, lg.last_index() + 1):
            assert np.asarray(st2.log_term)[g, i - lg.offset] == \
                lg.term(i), (g, i)


def test_compact_err_lanes():
    st = init_groups(3, 3, 16)
    st = st._replace(last=jnp.asarray([5, 5, 5], jnp.int32),
                     applied=jnp.asarray([3, 3, 3], jnp.int32),
                     offset=jnp.asarray([2, 0, 0], jnp.int32))
    _, err = batched.compact(st, jnp.asarray([1, 4, 2], jnp.int32))
    np.testing.assert_array_equal(np.asarray(err), [True, True, False])


def test_tick_fires():
    st = init_groups(4, 3, 8, election=3)
    st = st._replace(role=jnp.asarray(
        [FOLLOWER, FOLLOWER, LEADER, FOLLOWER], jnp.int32))
    elect_total = np.zeros(4, bool)
    beat_count = 0
    for _ in range(3):
        st, elect, beat = batched.tick(st)
        elect_total |= np.asarray(elect)
        beat_count += int(np.asarray(beat)[2])
    np.testing.assert_array_equal(elect_total, [True, True, False, True])
    assert beat_count == 3  # leader beats every tick (heartbeat=1)
    assert int(np.asarray(st.elapsed)[0]) == 0  # reset after firing


def test_grant_vote_up_to_date():
    rng = np.random.default_rng(4)
    logs, st = _mk_logs(rng)
    cand_idx = rng.integers(0, 25, size=G).astype(np.int32)
    cand_term = rng.integers(0, 6, size=G).astype(np.int32)
    st2, grant = batched.grant_vote(
        st, jnp.asarray(cand_idx), jnp.asarray(cand_term),
        st.term, jnp.full((G,), 1, jnp.int32))
    grant = np.asarray(grant)
    for g in range(G):
        want = logs[g].is_up_to_date(int(cand_idx[g]), int(cand_term[g]))
        assert bool(grant[g]) == want, g
    # granted lanes recorded their vote
    np.testing.assert_array_equal(
        np.asarray(st2.vote)[grant], 1)


def test_maybe_append_is_the_scatter_and_gather_forms():
    """The block write leaves the state both of the parent's window
    writes left (the scatter the TPU ran, the gather XLA-CPU ran)."""
    rng = np.random.default_rng(9)
    for trial in range(4):
        _, st = _mk_logs(rng)
        prev_idx = rng.integers(0, 22, size=G).astype(np.int32)
        prev_term = rng.integers(0, 5, size=G).astype(np.int32)
        n_ents = rng.integers(0, E + 1, size=G).astype(np.int32)
        ent_terms = np.sort(
            rng.integers(1, 5, size=(G, E)).astype(np.int32), axis=1)
        leader_commit = rng.integers(0, 30, size=G).astype(np.int32)
        args = (st, jnp.asarray(prev_idx), jnp.asarray(prev_term),
                jnp.asarray(ent_terms), jnp.asarray(n_ents),
                jnp.asarray(leader_commit))
        st2, ok, errc, erro = batched.maybe_append(*args)
        got = (st2.log_term, st2.last, st2.commit, ok, errc, erro)
        # non-vacuity: accepted lanes with real entries exist in
        # every trial
        assert (np.asarray(ok) & (n_ents > 0)).any(), trial
        for reference in WRITES:
            st3, *flags = parent_maybe_append(*args, reference=reference)
            want = (st3.log_term, st3.last, st3.commit, *flags)
            for a, b in zip(got, want, strict=True):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    err_msg=f"{trial} {reference}")
