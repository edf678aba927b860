"""The co-hosted engine's programs donate the member states: the round,
its hot and train forms and the campaign take each field's input
buffer for its output, so a round allocates its pack and nothing
else, and the tuple it was given is deleted.

Held here: the tuple a served round was given is gone after it, and
``etcd_round_donated_total`` counts that round; a sequence of every
donating program reads, pack for pack and state for state, what the
same sequence reads with undonated copies of the programs; every way
an engine's states are built (fresh, sharded, seeded by a restart)
gives each leaf a buffer of its own, which a donating call needs; and
what reads the states between rounds reads live arrays."""

from __future__ import annotations

import sys
import threading

import numpy as np
import pytest

import jax

from etcd_tpu.parallel.mesh import serving_mesh
from etcd_tpu.raft import multiraft
from etcd_tpu.raft.multiraft import MultiRaft

from test_multiraft_hot import _states_equal

G, M, CAP, E = 16, 3, 32, 4

#: the donating programs and their static arguments
PROGRAMS = {
    "_fused_round": ("e",),
    "_fused_round_hot": ("e", "slot"),
    "_fused_multi_round": ("e", "k"),
    "_fused_multi_round_hot": ("e", "k", "slot"),
    "_fused_campaign": ("slot",),
}


def _mk(program: str = "hot", **kw) -> MultiRaft:
    """Member 0 leads every group; ``general`` pins the route cache
    off, so every dispatch takes the M-slot program."""
    mr = MultiRaft(**{"g": G, "m": M, "cap": CAP, "max_batch_ents": E,
                      "seed": 5, **kw})
    if program == "general":
        mr._recompute_hot = lambda: None
        mr._route_hot = None
    mr.campaign(0)
    return mr


def _leaves(mr: MultiRaft) -> list:
    return [x for st in mr.states for x in st]


def _buffers(mr: MultiRaft) -> list[int]:
    """The device buffer of every shard of every leaf."""
    return [s.data.unsafe_buffer_pointer() for x in _leaves(mr)
            for s in x.addressable_shards]


def _donated() -> float:
    return multiraft._M_DONATED.get()


def _undonated(monkeypatch) -> None:
    """The same programs jitted without donation."""
    for name, static in PROGRAMS.items():
        prog = getattr(multiraft, name)
        monkeypatch.setattr(multiraft, name, jax.jit(
            prog.__wrapped__, static_argnames=static))


# -- the round consumes the tuple it was given -------------------------------


@pytest.mark.parametrize("program", ["hot", "general"])
def test_served_round_deletes_the_tuple_it_was_given(program):
    mr = _mk(program)
    before, n0 = _leaves(mr), _donated()
    mr.propose(np.ones(G, np.int32))
    assert all(x.is_deleted() for x in before)
    assert not any(x.is_deleted() for x in _leaves(mr))
    assert _donated() == n0 + 1
    assert (mr.commit_index() == 2).all()


@pytest.mark.parametrize("program", ["hot", "general"])
def test_train_and_campaign_delete_the_tuple_they_were_given(program):
    mr = _mk(program)
    before = _leaves(mr)
    mr.propose_rounds(np.ones(G, np.int32), 3)
    assert all(x.is_deleted() for x in before)
    before = _leaves(mr)
    won = mr.campaign(1, mask=np.arange(G) % 2 == 1)
    assert won[1::2].all() and not won[::2].any()
    assert all(x.is_deleted() for x in before)


def test_counter_says_when_the_round_did_not_donate():
    """A host view of the log alive across the call keeps its buffer
    (the CPU backend then leaves the input alone and allocates): the
    round is right, and the counter does not count it."""
    mr = _mk()
    view = np.asarray(mr.states[0].log_term)
    log, n0 = mr.states[0].log_term, _donated()
    mr.propose(np.ones(G, np.int32))
    assert not log.is_deleted() and _donated() == n0
    assert view[:, 1].tolist() == [1] * G      # the old log, unchanged
    del view, log
    mr.propose(np.ones(G, np.int32))
    assert _donated() == n0 + 1
    assert (mr.commit_index() == 3).all()


# -- the same answers as the programs without donation -----------------------


def _script(mr: MultiRaft) -> list[np.ndarray]:
    """Every donating program in turn: a campaign, hot rounds with and
    without dropped edges, a hot train, a second campaign that splits
    the routing, general rounds and a general train, a compaction and
    a round after it.  Returns each answer and, after each step,
    every state array (host copies: no view outlives its read)."""
    rng = np.random.default_rng(17)
    out: list[np.ndarray] = []

    def step(answer) -> None:
        out.append(np.array(answer))
        out.extend(np.array(v) for v in (
            mr.last_valid, mr.last_base, mr.last_terms, mr.last_commit,
            mr.errors["overflow"], mr.errors["conflict"]))
        out.extend(np.array(x) for x in _leaves(mr))

    def n_new():
        return rng.integers(0, E + 1, G).astype(np.int32)

    def drop():
        return {(0, 1): rng.random(G) < 0.5, (2, 0): rng.random(G) < 0.5}

    step(mr.campaign(0))
    assert mr._route_hot == 0
    for d in (None, drop(), None):
        step(mr.propose(n_new(), drop=d))
    step(mr.propose_rounds(n_new(), 2))
    step(mr.campaign(1, mask=np.arange(G) % 2 == 1))
    assert mr._route_hot is None
    for d in (drop(), None):
        step(mr.propose(n_new(), drop=d))
    step(mr.propose_rounds(n_new(), 2, drop=drop()))
    mr.mark_applied(mr.commit_index())
    mr.compact()
    step(mr.propose(n_new()))
    return out


def test_donated_programs_read_what_undonated_ones_read(monkeypatch):
    with monkeypatch.context() as mp:
        _undonated(mp)
        ref = MultiRaft(g=G, m=M, cap=CAP, max_batch_ents=E, seed=5)
        n0 = _donated()
        want = _script(ref)
        assert _donated() == n0       # nothing donated, nothing counted
    got = MultiRaft(g=G, m=M, cap=CAP, max_batch_ents=E, seed=5)
    n0 = _donated()
    have = _script(got)
    # every served round donated: the five rounds of the script and
    # the becoming-leader round of each of the two campaigns
    assert _donated() == n0 + 8
    assert len(have) == len(want)
    for i, (a, b) in enumerate(zip(have, want)):
        np.testing.assert_array_equal(a, b, err_msg=f"item {i}")
    _states_equal(got, ref)
    assert got.payloads == ref.payloads
    assert (np.array(got.last_commit) > 10).all()


# -- every way of building the states gives each leaf its own buffer ---------


def test_fresh_engine_gives_each_leaf_a_buffer_of_its_own():
    """``init_groups`` once built term, commit, applied, offset, last
    and elapsed from ONE zeros array: the first donated round then
    fails with "donate the same buffer twice"."""
    mr = MultiRaft(g=G, m=M, cap=CAP, max_batch_ents=E)
    ptrs = _buffers(mr)
    assert len(set(ptrs)) == len(ptrs) == M * len(mr.states[0])
    mr.campaign(0)
    mr.propose(np.ones(G, np.int32))
    assert (mr.commit_index() == 2).all()


@pytest.mark.parametrize("devices", [1, 8])
def test_sharded_engine_gives_each_leaf_a_buffer_of_its_own(devices):
    if len(jax.devices()) < devices:
        pytest.skip(f"needs {devices} (virtual) devices")
    mr = MultiRaft(g=G, m=M, cap=CAP, max_batch_ents=E)
    mr.shard(serving_mesh(devices))
    ptrs = _buffers(mr)
    assert len(set(ptrs)) == len(ptrs) == devices * M * len(mr.states[0])
    mr.campaign(0)
    before = _leaves(mr)
    mr.propose(np.ones(G, np.int32))
    assert all(x.is_deleted() for x in before)
    assert (mr.commit_index() == 2).all()
    assert len(mr.states[0].log_term.sharding.device_set) == devices


def test_seeded_engine_gives_each_leaf_a_buffer_of_its_own():
    """The restart's seeding once gave every member the same frontier
    array for offset, last, commit and applied, the same log and the
    same members mask."""
    frontier = np.arange(G, dtype=np.int64) + 3
    terms = np.full(G, 4, np.int64)
    members = np.ones((G, M), bool)
    members[::2, 2] = False
    mr = MultiRaft(g=G, m=M, cap=CAP, max_batch_ents=E)
    mr.seed(frontier, terms, members=members)
    ptrs = _buffers(mr)
    assert len(set(ptrs)) == len(ptrs)
    for st in mr.states:
        for f in ("offset", "last", "commit", "applied"):
            np.testing.assert_array_equal(np.array(getattr(st, f)),
                                          frontier)
        np.testing.assert_array_equal(np.array(st.term), terms)
        np.testing.assert_array_equal(np.array(st.log_term)[:, 0], terms)
        assert not np.array(st.log_term)[:, 1:].any()
        np.testing.assert_array_equal(np.array(st.members), members)
        np.testing.assert_array_equal(np.array(st.nmembers),
                                      members.sum(axis=1))
    np.testing.assert_array_equal(mr.members_mask(), members)
    won = mr.campaign(0)
    assert won.all()
    mr.propose(np.ones(G, np.int32))
    # the becoming-leader entry and the proposal, both committed
    np.testing.assert_array_equal(mr.commit_index(), frontier + 2)


def test_restarted_server_serves_a_donated_round(tmp_path):
    """A server restarted from a snapshot that carries a members mask
    and a WAL tail behind it seeds its engine through
    ``MultiRaft.seed`` and serves, every round donated."""
    from test_multigroup import _get, _mk as _server, _put

    s = _server(tmp_path, spare_member_slots=1)
    s.start()
    try:
        _put(s, "/don/a", "1")
        s.snapshot()
        _put(s, "/don/b", "2")
    finally:
        s.stop()
    s2 = _server(tmp_path, spare_member_slots=1)
    ptrs = _buffers(s2.mr)
    assert len(set(ptrs)) == len(ptrs)
    n0 = _donated()
    s2.start()
    try:
        _put(s2, "/don/c", "3")
        assert _get(s2, "/don/b").event.node.value == "2"
        assert _get(s2, "/don/c").event.node.value == "3"
        assert s2.members_of(0).tolist() == [True] * 3 + [False]
    finally:
        s2.stop()
    assert _donated() > n0


# -- what reads the states between rounds reads live arrays ------------------


@pytest.mark.parametrize("program", ["hot", "general"])
def test_views_after_a_round_read_live_arrays(program):
    mr = _mk(program)
    for _ in range(3):
        mr.propose(np.full(G, 2, np.int32))
    assert (mr.log_terms(0)[:, 1:8] == 1).all()
    np.testing.assert_array_equal(mr.log_terms(1), mr.log_terms(0))
    np.testing.assert_array_equal(mr.commit_index(), np.full(G, 7))
    np.testing.assert_array_equal(mr.term_index(), np.ones(G))
    # a state replaced between rounds: the views go to the device
    mr.states[2] = mr.states[2]._replace(commit=mr.states[0].commit + 1)
    np.testing.assert_array_equal(mr.commit_index(), np.full(G, 8))
    mr.propose(np.ones(G, np.int32))
    mr.mark_applied(mr.commit_index())
    mr.compact()
    assert (np.array(mr.states[0].offset) > 0).all()
    mr.propose(np.ones(G, np.int32))
    assert (mr.commit_index() == 9).all()
    assert not any(x.is_deleted() for x in _leaves(mr))


def test_members_mask_follows_every_member_and_any_thread():
    """The mask is the host's copy: it answers what each member's
    device mask holds after a conf change, and a reader on another
    thread never meets an array a round has donated."""
    mr = _mk(live=2)
    assert mr.members_mask().tolist() == [[True, True, False]] * G
    mr.apply_conf_change(True, 2, mask=np.arange(G) % 2 == 0)
    for st in mr.states:
        np.testing.assert_array_equal(mr.members_mask(),
                                      np.array(st.members))
    mr.members_mask()[:] = False               # the caller's own copy
    assert mr.members_mask()[0].all()
    stop, seen, errors = threading.Event(), [], []

    def reader():
        try:
            while not stop.is_set():
                seen.append(int(mr.members_mask().sum()))
        except Exception as e:                 # pragma: no cover
            errors.append(e)

    t = threading.Thread(target=reader)
    t.start()
    try:
        for _ in range(20):
            mr.propose(np.ones(G, np.int32))
    finally:
        stop.set()
        t.join()
    assert not errors and seen and set(seen) == {2 * G + G // 2}


def test_readers_on_other_threads_wait_for_the_round():
    """One thread runs rounds and marks what it applied, as the
    engine thread does; three others compact, read the views and the
    logs, as a snapshot taken off the engine thread does.  With the
    interpreter switching threads every 10 us, none of them
    meets an array a round has donated, and no applied mark is lost."""
    mr = _mk(cap=64)
    stop, errors, marked = threading.Event(), [], []

    def engine():
        try:
            for _ in range(20):
                mr.propose(np.ones(G, np.int32))
                marked.append(mr.commit_index().copy())
                mr.mark_applied(marked[-1])
        except Exception as e:                 # pragma: no cover
            errors.append(e)
        finally:
            stop.set()

    def other(read):
        try:
            while not stop.wait(0.0005):
                read()
        except Exception as e:                 # pragma: no cover
            errors.append(e)

    reads = (mr.compact, lambda: (mr.commit_index(), mr.term_index()),
             lambda: mr.log_terms(1))
    threads = [threading.Thread(target=engine)] + [
        threading.Thread(target=other, args=(r,)) for r in reads]
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert len(marked) == 20 and (marked[-1] == 21).all()
    mr.propose(np.zeros(G, np.int32))       # absorbs the last mark
    np.testing.assert_array_equal(np.array(mr.states[0].applied), 21)
    assert (np.array(mr.states[0].offset) > 0).all()
