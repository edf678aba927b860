"""One read-back and no eager dispatch a pass (PR 34): the fused round
hands its whole answer to the host as ONE packed array and takes the
host's ``applied`` vector as an input.

What the pack's rows read is held to the parent's values, taken the
parent's way (every row its own output buffer, the members' maxima of
``term`` and ``commit`` on the host) on the edge logs
``test_term_window.py`` uses; the deferred ``mark_applied`` is held to
the eager expression the parent dispatched; and the served loop to
exactly one ``_ledger.fetch`` a pass, with nothing lost under a
snapshot or an overflow compaction."""

from __future__ import annotations

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from etcd_tpu.obs import metrics
from etcd_tpu.raft import multiraft
from etcd_tpu.raft.multiraft import PACK, MultiRaft
from etcd_tpu.wire.requests import Request

from test_multiraft_hot import _states_equal as _assert_same_states
from test_stage_coverage import grown, wall

G, M, CAP, E = 16, 3, 32, 4


def _mk(program: str, cap: int = CAP) -> MultiRaft:
    """Member 0 leads every group; ``general`` pins the route cache
    off, so every dispatch takes the M-slot program."""
    mr = MultiRaft(g=G, m=M, cap=cap, max_batch_ents=E, seed=5)
    if program == "general":
        mr._recompute_hot = lambda: None
        mr._route_hot = None
    mr.campaign(0)
    return mr


def _fill_to(mr: MultiRaft, last: int) -> None:
    """Rounds of proposals until every leader's log ends at ``last``
    (committed: no edge is dropped)."""
    while True:
        have = int(np.asarray(mr.states[0].last).min())
        if have >= last:
            return
        mr.propose(np.full(mr.g, min(mr.e, last - have), np.int32))


def _state_arrays(mr: MultiRaft) -> list[np.ndarray]:
    return [np.asarray(x) for st in mr.states for x in st]


# -- (a) the pack's rows are the parent's values ---------------------------


def _empty(mr, rng):
    """Nobody leads half the groups: their logs hold nothing."""
    fresh = MultiRaft(g=G, m=M, cap=CAP, max_batch_ents=E, seed=5)
    half = np.arange(G) % 2 == 0
    for slot in range(M):
        mr.states[slot] = jax.tree_util.tree_map(
            lambda new, old: jnp.where(
                half.reshape((G,) + (1,) * (new.ndim - 1)), new, old),
            fresh.states[slot], mr.states[slot])
    return rng.integers(0, 3, G).astype(np.int32), None


def _one_short_of_full(mr, rng):
    """Every log one entry short of its row: one proposal fits, two
    overflow, and a follower's window ends at the end of the row."""
    _fill_to(mr, CAP - 2)
    return (np.arange(G) % 4).astype(np.int32), None


def _just_compacted(mr, rng):
    _fill_to(mr, CAP - 6)
    mr.mark_applied(mr.commit_index() - np.arange(G) % 3)
    mr.compact()
    return rng.integers(0, E + 1, G).astype(np.int32), None


def _deposed_leader(mr, rng):
    """Member 1 takes half the groups while member 0 is cut off, and
    ``mr.leader`` goes on naming member 0: the addressed member still
    calls itself leader, at a term its peers have left."""
    _fill_to(mr, 5)
    cut = np.ones(G, bool)
    alone = {(0, 1): cut, (1, 0): cut, (0, 2): cut, (2, 0): cut}
    leader, hot = mr.leader.copy(), mr._route_hot
    won = mr.campaign(1, mask=np.arange(G) % 2 == 1, drop=alone)
    assert won.any()
    mr.leader, mr._route_hot, mr._hot_sel = leader, hot, None
    return np.full(G, 2, np.int32), None


def _dropped_edge(mr, rng):
    _fill_to(mr, 7)
    drop = {(0, 1): rng.random(G) < 0.6, (2, 0): rng.random(G) < 0.6}
    mr.propose(rng.integers(0, E + 1, G).astype(np.int32), drop=drop)
    return rng.integers(0, E + 1, G).astype(np.int32), drop


def _conflict_below_commit(mr, rng):
    """A follower whose committed entries differ from the leader's at
    the same indices (the reference's panic, log.go:57): the conflict
    lane."""
    _fill_to(mr, 8)
    odd = np.arange(G) % 2 == 1
    f = mr.states[1]
    mr.states[1] = f._replace(log_term=jnp.where(
        odd[:, None] & (jnp.arange(CAP)[None, :] >= 4)
        & (jnp.arange(CAP)[None, :] <= 8), 9, f.log_term))
    lead = mr.states[0]
    mr.states[0] = lead._replace(
        next_=lead.next_.at[:, 1].set(4),
        match=lead.match.at[:, 1].set(3))
    return np.ones(G, np.int32), None


SCENARIOS = {
    "empty": _empty, "one_short_of_full": _one_short_of_full,
    "just_compacted": _just_compacted, "deposed_leader": _deposed_leader,
    "dropped_edge": _dropped_edge,
    "conflict_below_commit": _conflict_below_commit,
}
#: what each scenario is there to show, of the parent's values and
#: the addressed member's term before the round (not vacuous)
SHOWS = {
    "empty": lambda w, t0: w["valid"].any() and (~w["valid"]).any(),
    "one_short_of_full": lambda w, t0: (
        w["overflow"].any() and (~w["overflow"]).any()
        and (w["newly"] > 0).any()),
    "just_compacted": lambda w, t0: (w["base"] > CAP - 8).all(),
    # the addressed member calls itself leader at a term its peers
    # have left: nothing of what it appends commits there
    "deposed_leader": lambda w, t0: (
        w["valid"].all() and (w["terms"] > t0).any()
        and (w["newly"][w["terms"] > t0] == 0).all()
        and (w["newly"] > 0).any()),
    "dropped_edge": lambda w, t0: (
        (w["newly"] > 0).any() and (w["newly"] == 0).any()),
    "conflict_below_commit": lambda w, t0: (
        w["conflict"].any() and (~w["conflict"]).any()),
}


def _parent_round(mr: MultiRaft, n_new, drop):
    """The round the parent's way: the same body jitted with every
    row its own output, each read back on its own, and the members'
    maxima of ``term`` and ``commit`` taken on the host from the
    states it returned."""
    dense = jnp.asarray(multiraft._drop_dense(drop, mr.m, mr.g))
    hot = mr._route_hot
    if hot is not None:
        sels, slots = [jnp.asarray(mr.leader == hot)], (hot,)
    else:
        sels = [jnp.asarray(mr.leader == s) for s in range(mr.m)]
        slots = tuple(range(mr.m))
    states, rows = jax.jit(
        lambda st, sels, n, d: multiraft._round_core(
            st, sels, n, d, mr.e, slots))(
                tuple(mr.states), sels, jnp.asarray(n_new), dense)
    want = {k: np.asarray(rows[k]) for k in
            ("valid", "base", "newly", "overflow", "conflict")}
    want["terms"] = np.max(np.stack(
        [np.asarray(st.term) for st in states]), axis=0)
    want["commit"] = np.max(np.stack(
        [np.asarray(st.commit) for st in states]), axis=0)
    return states, want


@pytest.mark.parametrize("program", ["hot", "general"])
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_packed_round_reads_the_parents_values(scenario, program):
    mr = _mk(program)
    assert (mr._route_hot is None) == (program == "general")
    n_new, drop = SCENARIOS[scenario](mr, np.random.default_rng(11))
    assert (mr._route_hot is None) == (program == "general")
    term0 = np.asarray(mr.states[0].term)
    want_states, want = _parent_round(mr, n_new, drop)

    newly = mr.propose(n_new, drop=drop)

    got = {"valid": mr.last_valid, "base": mr.last_base, "newly": newly,
           "overflow": mr.errors["overflow"],
           "conflict": mr.errors["conflict"], "terms": mr.last_terms,
           "commit": mr.last_commit}
    assert set(got) == set(PACK)
    for k in PACK:
        assert isinstance(got[k], np.ndarray), k
        assert got[k].dtype == want[k].dtype, (k, got[k].dtype)
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    for a, b in zip(_state_arrays(mr), (
            np.asarray(x) for st in want_states for x in st)):
        np.testing.assert_array_equal(a, b)
    # the views answer from the pack while its states stand
    np.testing.assert_array_equal(mr.commit_index(), want["commit"])
    np.testing.assert_array_equal(mr.term_index(), want["terms"])
    assert SHOWS[scenario](want, term0), want


@pytest.mark.parametrize("program", ["hot", "general"])
def test_views_go_to_the_device_once_a_program_has_run(program):
    """``commit_index()`` / ``term_index()`` answer from the last
    pack only while no program has replaced a state since."""
    mr = _mk(program)
    mr.propose(np.ones(G, np.int32))
    np.testing.assert_array_equal(mr.commit_index(), mr.last_commit)
    st = mr.states[1]
    mr.states[1] = st._replace(commit=st.commit + 5, term=st.term + 2)
    np.testing.assert_array_equal(
        mr.commit_index(), np.asarray(st.commit) + 5)
    assert (mr.commit_index() > mr.last_commit).all()
    np.testing.assert_array_equal(mr.term_index(), mr.last_terms + 2)
    # the views are read-backs either way: no caller writes to one
    assert not mr.commit_index().flags.writeable
    assert not mr.last_commit.flags.writeable


@pytest.mark.parametrize("program", ["hot", "general"])
def test_no_stale_generation_of_logs_outlives_a_compaction(program):
    """What the views are checked against holds ``term`` and
    ``commit`` alone: once a compaction has replaced the states, the
    round's own ``[G, cap]`` logs are garbage (a third generation on
    the device during the next round otherwise)."""
    import gc
    import weakref

    mr = _mk(program)
    _fill_to(mr, 9)
    logs = [weakref.ref(st.log_term) for st in mr.states]
    mr.mark_applied(mr.commit_index())
    mr.compact()
    gc.collect()
    assert [r() for r in logs] == [None] * M
    np.testing.assert_array_equal(
        mr.commit_index(),
        np.max([np.asarray(st.commit) for st in mr.states], axis=0))


# -- (b) applied rides the next round --------------------------------------


def _eager_mark(mr: MultiRaft, upto) -> None:
    """The parent's ``mark_applied``: a put and, a member,
    ``minimum``, ``maximum`` and a ``_replace``."""
    upto = jnp.asarray(np.asarray(upto, np.int32))
    for slot in range(mr.m):
        st = mr.states[slot]
        mr.states[slot] = st._replace(applied=jnp.maximum(
            st.applied, jnp.minimum(upto, st.commit)))


def _lagging(program: str) -> MultiRaft:
    """Member 2 behind (its commit is not the leader's), so the clamp
    to ``commit`` differs between members."""
    mr = _mk(program)
    cut = np.arange(G) % 2 == 0
    lag = {(0, 2): cut, (2, 0): cut}
    for _ in range(3):
        mr.propose(np.full(G, 2, np.int32), drop=lag)
    assert (np.asarray(mr.states[2].commit)
            < np.asarray(mr.states[0].commit)).any()
    return mr


MARKS = {
    "at_commit": lambda c: [c],
    "past_commit_is_clamped": lambda c: [c + 7],
    "below_applied_is_a_no_op": lambda c: [c - 1, c - 3],
    "several_marks_take_their_maximum": lambda c: [c - 2, c, c - 1],
}


@pytest.mark.parametrize("rounds", [None, 3], ids=["round", "train"])
@pytest.mark.parametrize("program", ["hot", "general"])
@pytest.mark.parametrize("marks", sorted(MARKS))
def test_deferred_mark_then_round_is_the_eager_form(marks, program,
                                                    rounds):
    def step(mr):
        n = (np.arange(G) % 3).astype(np.int32)
        return mr.propose(n) if rounds is None \
            else mr.propose_rounds(n, rounds)

    got, want = _lagging(program), _lagging(program)
    before = tuple(got.states)
    for upto in MARKS[marks](got.commit_index()):
        got.mark_applied(np.maximum(upto, 0))
        _eager_mark(want, np.maximum(upto, 0))
    # nothing was dispatched: no member's state was replaced
    assert all(a is b for a, b in zip(before, got.states))
    np.testing.assert_array_equal(step(got), step(want))
    _assert_same_states(got, want)
    assert (np.asarray(got.states[0].applied) > 0).any()
    # the vector is spent: a second round adds nothing to applied
    applied = [np.asarray(st.applied) for st in got.states]
    got.replicate()
    for a, st in zip(applied, got.states):
        np.testing.assert_array_equal(a, np.asarray(st.applied))


@pytest.mark.parametrize("upto", [None, 3], ids=["applied", "upto"])
@pytest.mark.parametrize("program", ["hot", "general"])
def test_mark_then_compact_with_no_round_between(program, upto):
    got, want = _lagging(program), _lagging(program)
    for mr in (got, want):
        mr.propose(np.full(G, 2, np.int32),
                   data=[[b"a%d" % gi, b"b%d" % gi] for gi in range(G)])
        mr.replicate()
    mark = got.commit_index() - np.arange(G) % 2
    got.mark_applied(mark)
    _eager_mark(want, mark)
    cut = None if upto is None else np.full(G, upto, np.int32)
    got.compact(cut)
    want.compact(cut)
    _assert_same_states(got, want)
    assert got.payloads == want.payloads
    np.testing.assert_array_equal(got.errors["compact_oob"],
                                  want.errors["compact_oob"])
    offset = np.asarray(got.states[0].offset)
    assert (offset > 0).all()
    if upto is None:
        np.testing.assert_array_equal(offset, mark)
    # and the round after it starts from the same place
    np.testing.assert_array_equal(
        got.propose(np.ones(G, np.int32)),
        want.propose(np.ones(G, np.int32)))
    _assert_same_states(got, want)


# -- (c), (d) the served loop ----------------------------------------------


def _server(tmp_path, **kw):
    from etcd_tpu.server.multigroup import MultiGroupServer

    kw.setdefault("tick_interval", 30.0)
    kw.setdefault("sync_interval", 30.0)
    return MultiGroupServer(str(tmp_path / "d"), g=8, m=3,
                            storage_backend="tpu", **kw)


@pytest.mark.parametrize("what", ["snapshot", "overflow"])
def test_compaction_under_writes_loses_no_committed_payload(tmp_path,
                                                            what):
    """Four callers write 60 values each into one tenant (one group's
    log) while the server snapshots every 40 applied entries, or the
    group's 16-entry log fills and is compacted: every write is
    acknowledged with its own value, the last one of each key reads
    back, and the compaction never passed what the host had applied."""
    # (a proposal held over by an overflow goes into the next pass,
    # and with an empty queue that is the next tick's: a short one)
    kw = {"snapshot": dict(cap=256, snap_count=40),
          "overflow": dict(cap=16, snap_count=100_000,
                           tick_interval=0.02)}[what]
    s = _server(tmp_path, **kw)
    seen = {"compact": 0, "past_applied": 0}
    real = s.mr.compact

    def compact(*a, **k):
        real(*a, **k)
        seen["compact"] += 1
        offset = np.min(np.stack(
            [np.asarray(st.offset) for st in s.mr.states]), axis=0)
        seen["past_applied"] += int((offset > s.applied).sum())

    s.mr.compact = compact
    s.start()
    errors: list = []

    def caller(c: int) -> None:
        try:
            for i in range(60):
                r = s.do(Request(id=1 + c * 1000 + i, method="PUT",
                                 path=f"/hot/k{c}", val=f"v{c}.{i}"),
                         timeout=60)
                assert r.event.node.value == f"v{c}.{i}", r.event
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    try:
        threads = [threading.Thread(target=caller, args=(c,))
                   for c in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        for c in range(4):
            r = s.do(Request(id=9000 + c, method="GET",
                             path=f"/hot/k{c}"))
            assert r.event.node.value == f"v{c}.59"
        assert s.raft_index >= 240
    finally:
        s.stop()
    assert seen["compact"] >= 1, seen
    assert seen["past_applied"] == 0, seen
    if what == "snapshot":
        assert s._snapi > 0
    else:
        assert (np.asarray(s.mr.states[0].offset) > 0).any()


def test_one_pass_of_the_served_loop_is_one_fetch(tmp_path, monkeypatch):
    """With the registry on, a pass of the engine thread makes exactly
    one ``_ledger.fetch`` (the round's pack) and one dispatch, and
    ``readbacks_per_round`` reads 1.0 over it."""
    s = _server(tmp_path, cap=64)
    s.start()
    fetches: list[tuple[str, tuple]] = []
    real_fetch = multiraft._ledger.fetch

    def fetch(stage, value):
        out = real_fetch(stage, value)
        if threading.current_thread() is s._thread:
            fetches.append((stage, out.shape))
        return out

    try:
        s.do(Request(id=1, method="PUT", path="/a/warm", val="w"),
             timeout=90)
        monkeypatch.setattr(multiraft._ledger, "fetch", fetch)
        dispatches = metrics.registry.counter(
            "etcd_devledger_dispatches_total", stage="multiraft.round")
        d0, before = dispatches.get(), wall()
        for i in range(12):
            s.do(Request(id=2 + i, method="PUT", path=f"/t{i % 5}/k",
                         val=f"v{i}"), timeout=60)
        s.stop()                  # the last pass has closed
        after = wall()
    finally:
        s.stop()
    grew = {k: n for k, (n, _s) in grown(before, after).items()}
    passes = grew["mg.pass"]
    assert passes >= 12 and grew.get("mg.heartbeat", 0) == 0
    assert fetches == [("multiraft.round", (len(PACK), 8))] * passes
    assert dispatches.get() - d0 == passes
    assert grew["mg.readback"] == grew["mg.round.wait"] == passes
    for stage in ("mg.round.dispatch", "mg.round.fetch",
                  "mg.frontier_fetch", "mg.mark_applied",
                  "mg.consensus_round"):
        assert grew[stage] == passes, (stage, grew)
