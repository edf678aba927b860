"""``batched.term_window``: a round's E-entry log window read as one
contiguous run a group is ``term_at`` of the same indices, bit for
bit; ``batched.append_window``, its write twin, is the scatter and
the gather forms it replaced, bit for bit; the rounds built on them
give the scalar core's logs; and no program reads a window of the
``[G, cap]`` log through a gather or writes one through a scatter."""

from __future__ import annotations

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from etcd_tpu.raft import batched, distmember, multiraft
from etcd_tpu.raft.batched import (
    append_window,
    init_groups,
    match_term,
    term_at,
    term_window,
)
from etcd_tpu.raft.core import MSG_HUP, MSG_PROP
from etcd_tpu.raft.multiraft import MultiRaft
from etcd_tpu.wire import Entry

from test_raft_core import Network, msg

CAPS = (32, 64, 1024)
ES = (4, 8, 32)


def gather_window(log_term, offset, last, start, e):
    """The parent's form: ``term_at`` over the run's indices."""
    return term_at(log_term, offset, last,
                   start[:, None] + jnp.arange(e, dtype=jnp.int32))


def scatter_write(log_term, offset, start, ent_terms, write):
    """The parent's TPU form of the window write: ``.at[].set`` of the
    E slots, a slot outside the row dropped."""
    g, cap = log_term.shape
    e = ent_terms.shape[1]
    rel = start[:, None] + jnp.arange(e, dtype=jnp.int32) - \
        offset[:, None]
    cols = jnp.where(write & (rel >= 0) & (rel < cap), rel, cap)
    gidx = jnp.arange(g, dtype=jnp.int32)[:, None]
    return log_term.at[gidx, cols].set(ent_terms, mode="drop")


def gather_write(log_term, offset, start, ent_terms, write):
    """The parent's XLA-CPU form: every slot of the row gathers the
    entry it would hold, and a masked ``where`` keeps it."""
    cap = log_term.shape[1]
    e = ent_terms.shape[1]
    j = offset[:, None] + jnp.arange(cap, dtype=jnp.int32) - \
        start[:, None]
    jc = jnp.clip(j, 0, e - 1)
    put = (j >= 0) & (j < e) & jnp.take_along_axis(write, jc, axis=1)
    return jnp.where(put, jnp.take_along_axis(ent_terms, jc, axis=1),
                     log_term)


WRITES = {"scatter": scatter_write, "gather": gather_write}


@partial(jax.jit, static_argnames=("reference",))
def parent_maybe_append(state, prev_idx, prev_term, ent_terms, n_ents,
                        leader_commit, active=None, *, reference):
    """The parent's ``maybe_append``: the conflict scan over
    ``term_at``'s gather, the window written in the ``reference``
    form (``scatter`` | ``gather``)."""
    g, cap = state.log_term.shape
    e = ent_terms.shape[1]
    if active is None:
        active = jnp.ones((g,), bool)
    ok = active & match_term(state.log_term, state.offset, state.last,
                             prev_idx, prev_term)
    e_idx = prev_idx[:, None] + 1 + jnp.arange(e, dtype=jnp.int32)
    existing = gather_window(state.log_term, state.offset, state.last,
                             prev_idx + 1, e)
    valid_e = jnp.arange(e) < n_ents[:, None]
    mismatch = valid_e & ((e_idx > state.last[:, None]) |
                          (existing != ent_terms))
    conflict = mismatch.any(axis=1)
    ci = prev_idx + 1 + jnp.argmax(mismatch, axis=1)
    lastnewi = prev_idx + n_ents
    err_conflict = ok & conflict & (ci <= state.commit)
    err_overflow = ok & (lastnewi - state.offset >= cap)
    ok = ok & ~(err_conflict | err_overflow)
    log_term = WRITES[reference](state.log_term, state.offset,
                                prev_idx + 1, ent_terms,
                                ok[:, None] & valid_e)
    last = jnp.where(ok & conflict, lastnewi, state.last)
    tocommit = jnp.minimum(leader_commit, lastnewi)
    commit = jnp.where(ok & (tocommit > state.commit), tocommit,
                       state.commit)
    return state._replace(log_term=log_term, last=last,
                          commit=commit), ok, err_conflict, err_overflow


def assert_same(got, want) -> None:
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def random_logs(rng, n: int, cap: int, offset_kind: str):
    """``n`` logs of random fill; ``offset_kind``: zero | some | far."""
    log = rng.integers(1, 1000, (n, cap)).astype(np.int32)
    hi = {"zero": 1, "some": 40, "far": 100_000}[offset_kind]
    offset = rng.integers(0, hi, n).astype(np.int32)
    fill = rng.integers(0, cap, n)
    fill[0], fill[-1] = 0, cap - 1     # an empty and a full window
    return log, offset, (offset + fill).astype(np.int32)


def every_start(log, offset, last, e):
    """The logs repeated once for every start in
    ``[offset - e - 1, last + e + 1]``: one lane a (log, start)."""
    rows, starts = [], []
    for i in range(len(offset)):
        ss = np.arange(offset[i] - e - 1, last[i] + e + 2)
        rows.append(np.full(len(ss), i))
        starts.append(ss)
    rows = np.concatenate(rows)
    return (jnp.asarray(log[rows]), jnp.asarray(offset[rows]),
            jnp.asarray(last[rows]),
            jnp.asarray(np.concatenate(starts).astype(np.int32)))


@pytest.mark.parametrize("offset_kind", ["zero", "some", "far"])
@pytest.mark.parametrize("e", ES)
@pytest.mark.parametrize("cap", CAPS)
def test_window_is_term_at_for_every_start(cap, e, offset_kind):
    rng = np.random.default_rng(cap * 100 + e)
    log, offset, last = random_logs(rng, 6 if cap > 64 else 12, cap,
                                    offset_kind)
    lt, off, la, start = every_start(log, offset, last, e)
    got = np.asarray(jax.jit(term_window, static_argnums=4)(
        lt, off, la, start, e))
    want = np.asarray(gather_window(lt, off, la, start, e))
    np.testing.assert_array_equal(got, want)
    # not vacuous: runs that begin below the offset, runs cut by the
    # end of the row, and runs that read real terms are all there
    slot0 = np.asarray(start) - np.asarray(off)
    assert (slot0 < 0).any() and (slot0 > cap - e).any()
    assert (want != 0).any()


@pytest.mark.parametrize("e", ES)
@pytest.mark.parametrize("cap", CAPS)
def test_window_past_the_row_reads_zero(cap, e):
    """``last`` beyond the window's capacity (a state no append
    leaves, but ``term_at`` answers it): a slot >= cap reads 0."""
    rng = np.random.default_rng(e)
    n = 8
    log = jnp.asarray(rng.integers(1, 9, (n, cap)), jnp.int32)
    offset = jnp.asarray(rng.integers(0, 9, n), jnp.int32)
    last = offset + cap + 5
    for back in range(0, e + 1):
        start = offset + cap - back
        got = np.asarray(term_window(log, offset, last, start, e))
        want = np.asarray(gather_window(log, offset, last, start, e))
        np.testing.assert_array_equal(got, want)
        assert (got[:, back:] == 0).all()
        if back:
            assert (got[:, :back] != 0).all()


@pytest.mark.parametrize("e", [33, 40])
def test_window_longer_than_the_row(e):
    rng = np.random.default_rng(3)
    log, offset, last = random_logs(rng, 5, 32, "some")
    lt, off, la, start = every_start(log, offset, last, 4)
    np.testing.assert_array_equal(
        np.asarray(term_window(lt, off, la, start, e)),
        np.asarray(gather_window(lt, off, la, start, e)))


def random_writes(cap: int, e: int, run: int | None = None):
    """Runs of ``e`` random terms under random write masks, for every
    start in :func:`every_start` of a run of ``run`` (default ``e``):
    ``(log_term, offset, start, ent_terms, write)``."""
    rng = np.random.default_rng(cap * 10 + e)
    log, offset, last = random_logs(rng, 6 if cap > 64 else 12, cap,
                                    "some")
    lt, off, _la, start = every_start(log, offset, last, run or e)
    n = start.shape[0]
    ents = jnp.asarray(rng.integers(1000, 2000, (n, e)), jnp.int32)
    write = jnp.asarray(rng.random((n, e)) < 0.7)
    return lt, off, start, ents, write


@pytest.mark.parametrize("reference", sorted(WRITES))
@pytest.mark.parametrize("e", ES)
@pytest.mark.parametrize("cap", CAPS)
def test_window_write_is_the_parents_for_every_start(cap, e, reference):
    """Every start from below the offset to past the end of the row:
    the block write leaves the row the scatter and the gather forms
    leave, slots outside it dropped."""
    args = random_writes(cap, e)
    got = np.asarray(jax.jit(append_window)(*args))
    want = np.asarray(WRITES[reference](*args))
    np.testing.assert_array_equal(got, want)
    # not vacuous: runs that begin below the offset and runs that end
    # past the row both wrote something
    lt, off, start = (np.asarray(x) for x in args[:3])
    wrote = (want != lt).any(axis=1)
    assert wrote[start < off].any() and wrote[start - off > cap - e].any()
    assert (~wrote).any()


@pytest.mark.parametrize("cap,e,run", [(48, 5, 5), (24, 1, 1), (30, 7, 7),
                                       (32, 33, 4), (32, 40, 4)])
def test_window_write_at_odd_widths(cap, e, run):
    """Blocks whose width is no power of two (6, 1 and 10 wide), and
    windows longer than the row (no block holds them)."""
    args = random_writes(cap, e, run)
    np.testing.assert_array_equal(np.asarray(append_window(*args)),
                                  np.asarray(scatter_write(*args)))


# -- the callers ----------------------------------------------------------


def _edge_logs(rng, g: int, cap: int, e: int):
    """Follower states whose logs end near the end of the row and
    begin at an offset, with appends aimed at both ends."""
    st = init_groups(g, 3, cap)
    offset = rng.integers(0, 50, g).astype(np.int32)
    fill = rng.integers(max(cap - 2 * e, 0), cap, g)
    fill[: g // 4] = rng.integers(0, e, g // 4)
    last = (offset + fill).astype(np.int32)
    log = np.sort(rng.integers(1, 5, (g, cap)), axis=1).astype(np.int32)
    log = np.where(np.arange(cap)[None, :] <= fill[:, None], log, 0)
    st = st._replace(log_term=jnp.asarray(log),
                     offset=jnp.asarray(offset), last=jnp.asarray(last),
                     commit=jnp.asarray(offset))
    prev_idx = (last - rng.integers(0, e + 2, g)).astype(np.int32)
    prev_idx[::5] = offset[::5] - rng.integers(0, 3, len(offset[::5]))
    prev_term = np.asarray(term_at(st.log_term, st.offset, st.last,
                                   jnp.asarray(prev_idx)))
    n_ents = rng.integers(0, e + 1, g).astype(np.int32)
    ent_terms = np.sort(rng.integers(1, 6, (g, e)), axis=1).astype(
        np.int32)
    return st, prev_idx, prev_term, ent_terms, n_ents


@pytest.mark.parametrize("reference", sorted(WRITES))
@pytest.mark.parametrize("cap,e", [(32, 4), (32, 8), (64, 8),
                                   (64, 32), (1024, 32)])
def test_maybe_append_with_window_is_the_gather_form(cap, e, reference):
    """``maybe_append`` at the ends of the row: the state and the flags
    of the parent's program (the window read by a gather, written in
    either of the parent's forms)."""
    rng = np.random.default_rng(cap + e)
    st, prev_idx, prev_term, ent_terms, n_ents = _edge_logs(
        rng, 64, cap, e)
    args = (st, jnp.asarray(prev_idx), jnp.asarray(prev_term),
            jnp.asarray(ent_terms), jnp.asarray(n_ents),
            st.last + 3)
    got = batched.maybe_append(*args)
    assert_same(got, parent_maybe_append(*args, reference=reference))
    ok, _conf, over = (np.asarray(x) for x in got[1:])
    assert ok.any() and (~ok).any() and over.any()


@pytest.mark.parametrize("cap,e", [(32, 4), (64, 8)])
def test_maybe_append_forms_agree_at_the_ends_of_the_row(cap, e):
    rng = np.random.default_rng(7 * cap + e)
    st, prev_idx, prev_term, ent_terms, n_ents = _edge_logs(
        rng, 64, cap, e)
    args = (st, jnp.asarray(prev_idx), jnp.asarray(prev_term),
            jnp.asarray(ent_terms), jnp.asarray(n_ents), st.last + 3)
    got = batched.maybe_append(*args)
    for reference in WRITES:
        assert_same(got, parent_maybe_append(*args, reference=reference))


def _case_lanes(case: str, cap: int, e: int):
    """Eight follower lanes aimed at one edge of the window write:
    ``(state, prev_idx, prev_term, ent_terms, n_ents, leader_commit)``
    and the lanes that must come out ``ok``, in conflict below the
    commit and overflowing."""
    g = 8
    r = np.arange(g)
    offset = np.full(g, 5)
    fill = {"straddle": 2 * e + 3, "below_offset": e,
            "past_cap": cap - 1, "conflict_below_commit": 3 * e,
            "overflow": cap - 2}[case]
    last = offset + fill
    log = np.tile(np.where(np.arange(cap) <= fill, 2, 0), (g, 1))
    log[:, 0] = 1
    commit = offset.copy()
    n_ents = np.full(g, e)
    none, every = np.zeros(g, bool), np.ones(g, bool)
    ok, conf, over = every, none, none
    if case == "straddle":
        # a run that crosses a block edge, at each offset into a block
        prev_idx = last - e + r % e
    elif case == "below_offset":
        # prev at the compaction slot is verifiable, below it is not
        prev_idx = offset - r % 3
        ok = r % 3 == 0
    elif case == "past_cap":
        # runs that end at the last slot of the row, the rest of the
        # window past it
        prev_idx = offset + cap - 2 - r % e
        n_ents = r % e + 1
    elif case == "conflict_below_commit":
        commit = last - 1
        prev_idx = commit - 1 - r % 3
        ok, conf = none, every
    else:                               # overflow: the run passes cap
        prev_idx = last.copy()
        ok, over = none, every
    st = init_groups(g, 3, cap)._replace(
        log_term=jnp.asarray(log, jnp.int32),
        offset=jnp.asarray(offset, jnp.int32),
        last=jnp.asarray(last, jnp.int32),
        commit=jnp.asarray(commit, jnp.int32))
    prev_idx = jnp.asarray(prev_idx, jnp.int32)
    prev_term = term_at(st.log_term, st.offset, st.last, prev_idx)
    args = (st, prev_idx, prev_term, jnp.full((g, e), 3, jnp.int32),
            jnp.asarray(n_ents, jnp.int32), st.last + e)
    return args, (ok, conf, over)


@pytest.mark.parametrize("reference", sorted(WRITES))
@pytest.mark.parametrize("case", ["straddle", "below_offset",
                                  "past_cap", "conflict_below_commit",
                                  "overflow"])
@pytest.mark.parametrize("cap,e", [(64, 8), (1024, 32)])
def test_maybe_append_at_each_edge_is_the_parents(cap, e, case,
                                                  reference):
    args, flags = _case_lanes(case, cap, e)
    got = batched.maybe_append(*args)
    assert_same(got, parent_maybe_append(*args, reference=reference))
    # every lane took the path its case names, and accepted lanes
    # wrote their run
    for name, a, b in zip(("ok", "conflict", "overflow"), got[1:],
                          flags, strict=True):
        np.testing.assert_array_equal(np.asarray(a), b, err_msg=name)
    wrote = (np.asarray(got[0].log_term) == 3).any(axis=1)
    np.testing.assert_array_equal(wrote, flags[0])


@pytest.mark.parametrize("reference", sorted(WRITES))
def test_handle_append_is_the_parents(monkeypatch, reference):
    """``DistMember``'s fused follower step: the packed response and
    the state of the parent's program."""
    rng = np.random.default_rng(11)
    g, cap, e = 64, 64, 8
    st, prev_idx, prev_term, ent_terms, n_ents = _edge_logs(
        rng, g, cap, e)
    term = np.asarray(st.term) + rng.integers(0, 2, g).astype(np.int32)
    args = (st, jnp.full((g,), 1, jnp.int32), jnp.asarray(term),
            jnp.asarray(prev_idx), jnp.asarray(prev_term),
            jnp.asarray(ent_terms), jnp.asarray(n_ents), st.last + 3,
            jnp.asarray(rng.random(g) < 0.9),
            jnp.asarray(rng.random(g) < 0.1))
    got = distmember._handle_append_fused(*args)
    monkeypatch.setattr(distmember, "maybe_append",
                        partial(parent_maybe_append, reference=reference))
    jax.clear_caches()
    want = distmember._handle_append_fused(*args)
    jax.clear_caches()
    assert_same(got, want)
    packed = np.asarray(got[1])
    assert packed[:, 0].any() and (packed[:, 0] == 0).any()


def _drive(mr: MultiRaft, seed: int, rounds: int) -> list:
    """Random proposals, lossy edges, leader changes and compactions;
    returns every round's device state as numpy."""
    rng = np.random.default_rng(seed)
    g, m = mr.g, mr.m
    mr.campaign(0)
    trail = []
    for r in range(rounds):
        drop = None
        if rng.random() < 0.5:
            a, b = rng.choice(m, 2, replace=False)
            drop = {(int(a), int(b)): rng.random(g) < 0.6}
        if r % 7 == 6:
            mr.campaign(int(rng.integers(0, m)),
                        mask=rng.random(g) < 0.4, drop=drop)
        mr.propose(rng.integers(0, mr.cap // 10 + 1, g).astype(np.int32),
                   drop=drop)
        if r % 5 == 4:
            # keep most of a row: the logs live at the end of theirs
            mr.mark_applied(np.maximum(
                mr.commit_index() - 3 * mr.cap // 4, 0))
            mr.compact()
        trail.append([np.asarray(x) for st in mr.states for x in st])
    return trail


SEEDS = range(12)
_TRAILS: dict = {}


def _trails(cap: int, e: int) -> dict:
    """``{seed: (with the window, with the gather)}`` for one shape:
    each form's programs are compiled once for all the seeds."""
    if (cap, e) not in _TRAILS:
        def run():
            return {seed: _drive(
                MultiRaft(16, 3, cap, max_batch_ents=e, seed=seed),
                seed, 20) for seed in SEEDS}

        got = run()
        mods = (batched, multiraft)
        try:
            for mod in mods:
                mod.term_window = gather_window
            jax.clear_caches()
            want = run()
        finally:
            for mod in mods:
                mod.term_window = term_window
            jax.clear_caches()
        _TRAILS[cap, e] = {s: (got[s], want[s]) for s in SEEDS}
    return _TRAILS[cap, e]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cap,e", [(32, 4)])
def test_rounds_with_window_are_the_gather_form(cap, e, seed):
    """Twenty rounds of proposals, dropped edges, campaigns and
    compactions at a cap the logs run up against: every array of
    every member after every round is the parent's (the same rounds
    with ``term_at`` over the window's indices)."""
    got, want = _trails(cap, e)[seed]
    assert len(got) == len(want) == 20
    for r, (a, b) in enumerate(zip(got, want)):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y, err_msg=f"round {r}")
    last = np.stack([a[8] for a in got])      # member 0's ``last``
    offset = np.stack([a[7] for a in got])
    assert (last - offset >= cap - e).any() and (offset > 0).any()


@pytest.mark.parametrize("seed", range(4))
def test_build_append_with_window_is_the_gather_form(monkeypatch,
                                                     seed):
    """``DistMember``'s frame builder reads prev's term and the run
    behind it as ONE window of E + 1 from ``prev_idx``."""
    rng = np.random.default_rng(seed)
    g, cap, e = 48, 32, 4
    st, prev_idx, _pt, _et, _n = _edge_logs(rng, g, cap, e)
    nxt = np.tile((prev_idx + 1)[:, None], (1, 3))
    st = st._replace(role=jnp.full((g,), batched.LEADER, jnp.int32),
                     next_=jnp.asarray(nxt))
    lanes = jnp.asarray(rng.random(g) < 0.8)
    got = np.asarray(distmember._build_append_fused(
        st, lanes, peer=1, e=e))
    monkeypatch.setattr(distmember, "term_window", gather_window)
    jax.clear_caches()
    want = np.asarray(distmember._build_append_fused(
        st, lanes, peer=1, e=e))
    jax.clear_caches()
    np.testing.assert_array_equal(got, want)
    assert got[:, 0].any() and got[:, 1].any() and got[:, 6:].any()


# -- against the scalar core ----------------------------------------------


def _scalar_terms(r) -> list[int]:
    return [en.term for en in r.raft_log.ents[1:]]


def _batched_terms(mr: MultiRaft, slot: int, g: int) -> list[int]:
    st = mr.states[slot]
    last = int(np.asarray(st.last)[g])
    return [int(t) for t in np.asarray(st.log_term)[g, 1:last + 1]]


def _settle(mr: MultiRaft, rounds: int = 12) -> None:
    for _ in range(rounds):
        mr.replicate()


def _agree(nt: Network, mr: MultiRaft, lead: int, g: int = 0) -> None:
    """Every member's log and commit, and the leader's ``next_``, as
    the scalar cluster has them (scalar ids are slots + 1)."""
    m = mr.m
    for slot in range(m):
        r = nt.peers[slot + 1]
        assert _batched_terms(mr, slot, g) == _scalar_terms(r), slot
        assert int(np.asarray(mr.states[slot].commit)[g]) == \
            r.raft_log.committed, slot
    lead_r = nt.peers[lead + 1]
    want_next = [lead_r.prs[s + 1].next for s in range(m)]
    got_next = [int(x) for x in np.asarray(mr.states[lead].next_)[g]]
    assert got_next == want_next


def _propose(nt: Network, lead: int, n: int) -> None:
    for _ in range(n):
        nt.send(msg(from_=lead, to=lead, type=MSG_PROP,
                    entries=[Entry(data=b"x")]))


def _heal(nt: Network, lead: int) -> None:
    """Heartbeats and appends until the scalar cluster is quiet."""
    nt.recover()
    for _ in range(4):
        nt.peers[lead].bcast_append()
        nt.send(*nt.filter(nt.peers[lead].read_messages()))


@pytest.mark.parametrize("cap,e", [(32, 4), (64, 8)])
def test_round_matches_scalar_core_after_a_leader_change(cap, e):
    nt = Network(None, None, None)
    nt.send(msg(from_=1, to=1, type=MSG_HUP))
    _propose(nt, 1, 3)
    nt.send(msg(from_=2, to=2, type=MSG_HUP))
    _propose(nt, 2, 2)
    _heal(nt, 2)

    mr = MultiRaft(4, 3, cap, max_batch_ents=e)
    mr.campaign(0)
    mr.propose(np.full(4, 3, np.int32))
    mr.campaign(1)
    mr.propose(np.full(4, 2, np.int32))
    _settle(mr)
    _agree(nt, mr, lead=1)


@pytest.mark.parametrize("cap,e", [(32, 4), (64, 8)])
def test_round_matches_scalar_core_after_a_conflict(cap, e):
    """A cut-off leader's uncommitted tail is overwritten by the new
    leader's entries at the same indices."""
    nt = Network(None, None, None)
    nt.send(msg(from_=1, to=1, type=MSG_HUP))
    _propose(nt, 1, 2)
    nt.isolate(1)
    _propose(nt, 1, 3)            # term 1 at 4..6, never committed
    nt.send(msg(from_=2, to=2, type=MSG_HUP))
    _propose(nt, 2, 5)            # term 2 at 4.. (its empty entry first)
    _heal(nt, 2)

    g = 4
    cut = np.ones(g, bool)
    alone = {(0, 1): cut, (1, 0): cut, (0, 2): cut, (2, 0): cut}
    mr = MultiRaft(g, 3, cap, max_batch_ents=e)
    mr.campaign(0)
    mr.propose(np.full(g, 2, np.int32))
    mr.propose(np.full(g, 3, np.int32), drop=alone)
    mr.campaign(1, drop=alone)
    mr.propose(np.full(g, 5, np.int32), drop=alone)
    _settle(mr)
    assert _batched_terms(mr, 0, 0)[3:6] == [2, 2, 2]
    _agree(nt, mr, lead=1)


@pytest.mark.parametrize("cap,e", [(32, 4), (64, 8)])
def test_round_matches_scalar_core_for_a_lagging_follower(cap, e):
    """A follower cut off for several windows' worth of entries
    catches up window by window, up to the end of the row (cap 32:
    the last windows start past ``cap - E`` and are clamped)."""
    n = cap - 6
    nt = Network(None, None, None)
    nt.send(msg(from_=1, to=1, type=MSG_HUP))
    nt.isolate(3)
    _propose(nt, 1, n)
    _heal(nt, 1)

    g = 4
    cut = np.ones(g, bool)
    lag = {(0, 2): cut, (2, 0): cut}
    mr = MultiRaft(g, 3, cap, max_batch_ents=e)
    mr.campaign(0, drop=lag)
    for _ in range(n):
        mr.propose(np.full(g, 1, np.int32), drop=lag)
    behind = int(np.asarray(mr.states[2].last)[0])
    assert behind == 0
    _settle(mr, rounds=n // e + 4)
    assert not np.asarray(mr.errors["overflow"]).any()
    _agree(nt, mr, lead=0)


# -- what the TPU's compiler is handed ------------------------------------


def _indexed(jaxpr, out: list) -> list:
    """``(primitive, operand shape, indices shape, slice sizes)`` of
    every gather and scatter in the jaxpr and the jaxprs under it."""
    for eq in jaxpr.eqns:
        name = eq.primitive.name
        if name == "gather" or name.startswith("scatter"):
            out.append((name, eq.invars[0].aval.shape,
                        eq.invars[1].aval.shape,
                        tuple(eq.params.get("slice_sizes", ()))))
        for v in eq.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(sub, "jaxpr"):
                    _indexed(sub.jaxpr, out)
                elif hasattr(sub, "eqns"):
                    _indexed(sub, out)
    return out


def _round_args(g: int, m: int, cap: int):
    st = init_groups(g, m, cap)
    return (tuple(st for _ in range(m)), jnp.zeros((g,), jnp.int32),
            jnp.zeros((2, g), jnp.int32), jnp.zeros((m, m, g), bool))


@pytest.mark.parametrize("program", ["hot", "general", "append",
                                     "build_append", "handle_append"])
def test_no_program_gathers_a_window_element_by_element(program):
    """The only gathers whose operand is the ``[G, cap]`` log are the
    single-index lookups, one element a group; a window is read with
    selects, and no scatter writes into the log at all."""
    g, m, cap, e = 256, 3, 128, 8
    states, leader, inp, drop = _round_args(g, m, cap)
    n_new = inp[0]
    if program == "hot":
        jaxpr = jax.make_jaxpr(
            lambda *a: multiraft._fused_round_hot(*a, e=e, slot=0))(
                states, leader == 0, inp, drop)
    elif program == "general":
        jaxpr = jax.make_jaxpr(
            lambda *a: multiraft._fused_round(*a, e=e))(
                states, leader, inp, drop)
    elif program == "append":
        jaxpr = jax.make_jaxpr(batched.maybe_append)(
            states[0], n_new, n_new, jnp.zeros((g, e), jnp.int32),
            n_new, n_new)
    elif program == "build_append":
        jaxpr = jax.make_jaxpr(
            lambda *a: distmember._build_append_fused(
                *a, peer=1, e=e))(states[0], leader == 0)
    else:
        jaxpr = jax.make_jaxpr(distmember._handle_append_fused)(
            states[0], n_new, n_new, n_new, n_new,
            jnp.zeros((g, e), jnp.int32), n_new, n_new, leader == 0,
            leader == 0)
    indexed = _indexed(jaxpr.jaxpr, [])
    assert not [x for x in indexed
                if x[0] != "gather" and x[1] == (g, cap)], indexed
    gathers = [x[1:] for x in indexed if x[0] == "gather"]
    over_log = [x for x in gathers if x[0] == (g, cap)]
    for _operand, indices, sizes in over_log:
        assert int(np.prod(indices[1:-1])) == 1, over_log
        assert sizes == (1, 1), over_log
    # and no copy of the log is gathered in its place
    for operand, indices, _sizes in gathers:
        assert int(np.prod(operand)) < g * cap or \
            int(np.prod(indices[1:-1])) == 1, gathers
