"""The co-hosted WAL's commit-frontier record names only the groups
whose commit moved (``gereplay.sparse_frontier``, kind 3).  The dense
record (kind 1: the whole ``[G]`` commit and term-at-commit vectors,
the last one winning) is the oracle: replaying seeded random histories
written as sparse records gives exactly the frontier and terms that
the same histories written densely give, over a snapshot, after the
dense records of an older data directory, and behind the guard
against another ``--cohosted-groups``.  Then the served path at
100 000 groups: acknowledged writes read back, before and after a
restart, and a write costs the WAL under a kilobyte."""

import glob
import hashlib
import os

import numpy as np
import pytest

from etcd_tpu import native
from etcd_tpu.server import gereplay
from etcd_tpu.server.gereplay import (
    K_FRONTIER,
    K_FRONTIER_SPARSE,
    fold_frontier,
    sparse_frontier,
)
from etcd_tpu.server.multigroup import MultiGroupServer, group_of
from etcd_tpu.wal import WAL
from etcd_tpu.wal.replay_device import EntryBlock
from etcd_tpu.wire import Entry, GroupEntry, HardState
from etcd_tpu.wire.requests import Info, Request

G = 48
SEEDS = range(8)


def dense(commit, terms) -> bytes:
    """The dense record's payload, as data directories written before
    the sparse form hold it."""
    return (np.asarray(commit, np.int32).tobytes()
            + np.asarray(terms, np.int32).tobytes())


def history(seed: int, g: int, flushes: int):
    """A seeded random run of flushes: each moves the commit of some
    groups (none, now and then) forward, their term at commit never
    falling.  ``(moved, commit, terms)`` after each flush."""
    rng = np.random.default_rng(seed)
    commit = np.zeros(g, np.int64)
    terms = np.zeros(g, np.int64)
    out = []
    for _ in range(flushes):
        moved = np.sort(rng.choice(g, size=int(rng.integers(0, g // 3)),
                                   replace=False))
        commit[moved] += rng.integers(1, 40, size=moved.size)
        terms[moved] += rng.integers(0, 3, size=moved.size)
        out.append((moved, commit.copy(), terms.copy()))
    return out


def records(hist, g: int, form: str):
    for moved, commit, terms in hist:
        if form == "dense":
            yield K_FRONTIER, dense(commit, terms)
        else:
            yield K_FRONTIER_SPARSE, sparse_frontier(
                g, moved, commit[moved], terms[moved])


def to_block(entries) -> EntryBlock:
    """An Entry list in the device replay's output shape."""
    blob = bytearray()
    off = np.empty(len(entries), np.uint64)
    ln = np.empty(len(entries), np.uint64)
    for i, e in enumerate(entries):
        eb = e.marshal()
        off[i], ln[i] = len(blob), len(eb)
        blob += eb
    n = len(entries)
    return EntryBlock(
        index=np.asarray([e.index for e in entries], np.uint64),
        term=np.ones(n, np.uint64), type=np.zeros(n, np.uint64),
        data_off=off, data_len=ln,
        blob=np.frombuffer(bytes(blob), np.uint8))


def stream_of(recs, path: str):
    """The WAL stream of ``recs``, each frontier record behind a
    group's log entry as a flush writes them, scanned by ``path``."""
    entries = []
    for kind, payload in recs:
        for ge in (GroupEntry(kind=0, group=0, gindex=len(entries) + 1,
                              gterm=1, payload=b"x"),
                   GroupEntry(kind=kind, payload=payload)):
            entries.append(Entry(index=len(entries) + 1, term=1,
                                 data=ge.marshal()))
    if path == "native":
        if not native.available():
            pytest.skip("native toolchain unavailable")
        return gereplay.scan(to_block(entries))
    return gereplay.scan(entries)


def dense_oracle(recs, g: int, frontier=None, terms=None):
    """The parent's selection: the last dense record wins whole."""
    last = [p for k, p in recs if k == K_FRONTIER]
    if not last:
        return (np.zeros(g, np.int64) if frontier is None else frontier,
                np.zeros(g, np.int64) if terms is None else terms)
    v = np.frombuffer(last[-1], np.int32).astype(np.int64)
    return v[:g], v[g:]


ZERO = np.zeros(G, np.int64)


@pytest.mark.parametrize("path", ["python", "native"])
@pytest.mark.parametrize("seed", SEEDS)
def test_sparse_records_fold_to_what_the_dense_records_give(seed, path):
    hist = history(seed, G, 40)
    want = dense_oracle(list(records(hist, G, "dense")), G)
    s = stream_of(records(hist, G, "sparse"), path)
    got = fold_frontier(s, ZERO, ZERO, G)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[0], hist[-1][1])


@pytest.mark.parametrize("seed", SEEDS)
def test_dense_records_first_then_sparse(seed):
    """An older data directory (dense records) restarted and written
    on by this form: the last dense record is the base, the sparse
    records after it fold over it; sparse records BEFORE it are
    superseded."""
    hist = history(seed, G, 30)
    k = 5 + seed
    recs = (list(records(hist[:2], G, "sparse"))
            + list(records(hist[2:k], G, "dense"))
            + list(records(hist[k:], G, "sparse")))
    got = fold_frontier(stream_of(recs, "python"), ZERO, ZERO, G)
    np.testing.assert_array_equal(got[0], hist[-1][1])
    np.testing.assert_array_equal(got[1], hist[-1][2])


@pytest.mark.parametrize("seed", SEEDS)
def test_a_snapshot_in_the_middle(seed):
    """The stream after a snapshot holds the records after it alone:
    the snapshot's vectors are the base."""
    hist = history(seed, G, 30)
    k = 7 + seed
    _, snap_commit, snap_terms = hist[k - 1]
    got = fold_frontier(
        stream_of(records(hist[k:], G, "sparse"), "python"),
        snap_commit, snap_terms, G)
    np.testing.assert_array_equal(got[0], hist[-1][1])
    np.testing.assert_array_equal(got[1], hist[-1][2])
    # a stream with no frontier record leaves the snapshot's as it is
    bare = fold_frontier(stream_of([], "python"), snap_commit,
                         snap_terms, G)
    np.testing.assert_array_equal(bare[0], snap_commit)


def test_a_record_of_another_group_count_is_refused():
    hist = history(1, G, 6)
    for form in ("sparse", "dense"):
        s = stream_of(records(hist, G, form), "python")
        with pytest.raises(RuntimeError, match="cohosted-groups 48, "
                                               "not 96"):
            fold_frontier(s, np.zeros(2 * G), np.zeros(2 * G), 2 * G)
    bad = [(K_FRONTIER_SPARSE, sparse_frontier(G, [G], [3], [1]))]
    with pytest.raises(ValueError, match="outside"):
        fold_frontier(stream_of(bad, "python"), ZERO, ZERO, G)


def test_the_record_holds_the_moved_groups_alone():
    p = sparse_frontier(100_000, [7, 99_999], [12, 3], [2, 1])
    assert len(p) == 4 * (2 + 3 * 2)
    g, groups, commit, terms = gereplay.read_sparse_frontier(p)
    assert g == 100_000 and list(groups) == [7, 99_999]
    assert list(commit) == [12, 3] and list(terms) == [2, 1]
    with pytest.raises(ValueError, match="malformed"):
        gereplay.read_sparse_frontier(p[:-4])


# -- the server on an older data directory ------------------------------------

def server_id(name: str = "multigroup") -> int:
    return int.from_bytes(hashlib.sha1(name.encode()).digest()[:8],
                          "big") & (2**63 - 1)


def write_dense_data_dir(data_dir: str, g: int, writes: dict) -> None:
    """A data directory as the server left it before the sparse
    record: the dense seq-0 marker; one flush of the bootstrap's
    fence for every group with a dense frontier (commit 1, term 1);
    then a flush a write, the write's entry and a dense frontier."""
    os.makedirs(os.path.join(data_dir, "snap"))
    wal = WAL.create(os.path.join(data_dir, "wal"),
                     Info(id=server_id()).marshal())
    wal.save(HardState(), [Entry(index=0, term=0, data=GroupEntry(
        kind=K_FRONTIER, payload=dense(np.zeros(g), np.zeros(g)))
        .marshal())])
    commit = np.ones(g, np.int64)
    terms = np.ones(g, np.int64)
    seq = 0
    ents = []
    for gi in range(g):
        seq += 1
        ents.append(Entry(index=seq, term=1, data=GroupEntry(
            kind=0, group=gi, gindex=1, gterm=1).marshal()))
    seq += 1
    ents.append(Entry(index=seq, term=1, data=GroupEntry(
        kind=K_FRONTIER, payload=dense(commit, terms)).marshal()))
    wal.save(HardState(term=1, commit=seq), ents)
    for i, (path, val) in enumerate(writes.items()):
        gi = group_of(path, g)
        commit[gi] += 1
        r = Request(id=1000 + i, method="PUT", path=path, val=val)
        ents = [Entry(index=seq + 1, term=1, data=GroupEntry(
            kind=0, group=gi, gindex=int(commit[gi]), gterm=1,
            payload=r.marshal()).marshal()),
            Entry(index=seq + 2, term=1, data=GroupEntry(
                kind=K_FRONTIER, payload=dense(commit, terms)).marshal())]
        seq += 2
        wal.save(HardState(term=1, commit=seq), ents)
    wal.close()


def put(s, path: str, val: str):
    return s.do(Request(id=int(np.random.randint(1, 2**62)),
                        method="PUT", path=path, val=val), timeout=60)


def read_back(s, acked: dict) -> dict:
    return {p: s.store.get(p, False, False).node.value for p in acked}


def test_an_older_data_dir_restarts_and_writes_on(tmp_path):
    d = str(tmp_path / "data")
    g = 16
    old = {f"/old{i}/k": f"o{i}" for i in range(12)}
    write_dense_data_dir(d, g, old)
    s = MultiGroupServer(d, g=g, m=3, cap=64, tick_interval=0.02)
    want_applied = np.ones(g, np.int64)
    for p in old:
        want_applied[group_of(p, g)] += 1
    np.testing.assert_array_equal(s.applied, want_applied)
    assert read_back(s, old) == old
    new = {f"/new{i}/k": f"n{i}" for i in range(10)}
    s.start()
    try:
        for p, v in new.items():
            put(s, p, v)
        applied = s.applied.copy()
    finally:
        s.stop()
    s2 = MultiGroupServer(d, g=g, m=3, cap=64, tick_interval=0.02)
    try:
        np.testing.assert_array_equal(s2.applied, applied)
        assert read_back(s2, {**old, **new}) == {**old, **new}
    finally:
        s2.stop()
    with pytest.raises(RuntimeError, match="cohosted-groups 16, not 32"):
        MultiGroupServer(d, g=2 * g, m=3, cap=64)


# -- the served path at 100 000 groups ----------------------------------------

G100K = 100_000
N_PUTS = 40


def wal_bytes(data_dir: str) -> int:
    return sum(os.path.getsize(p) for p in
               glob.glob(os.path.join(data_dir, "wal", "*.wal")))


@pytest.fixture(scope="module")
def served100k(tmp_path_factory):
    """``N_PUTS`` acknowledged PUTs over 25 tenants' records at 100 000
    groups (a log window of 16 a member keeps the state ≈ 40 MB),
    the WAL's growth over them, and a restart on the data dir."""
    d = str(tmp_path_factory.mktemp("t100k") / "data")
    rng = np.random.default_rng(45)
    s = MultiGroupServer(d, g=G100K, m=5, cap=16, storage_backend="tpu",
                         tick_interval=0.1)
    s.start()
    acked, gets = {}, {}
    try:
        before = wal_bytes(d)
        for i in range(N_PUTS):
            path = f"/t{int(rng.integers(25)):05d}/cfg"
            put(s, path, f"v{i}")
            acked[path] = f"v{i}"
        grown = wal_bytes(d) - before
        for p in acked:
            gets[p] = s.do(Request(id=int(rng.integers(1, 2**62)),
                                   method="GET", path=p)).event.node.value
        applied = s.applied.copy()
    finally:
        s.stop()
    s2 = MultiGroupServer(d, g=G100K, m=5, cap=16, storage_backend="tpu")
    try:
        yield {"acked": acked, "gets": gets, "grown": grown,
               "applied": applied, "restarted": s2, "data_dir": d}
    finally:
        s2.stop()


def test_100k_acknowledged_puts_read_back(served100k):
    assert served100k["gets"] == served100k["acked"]
    assert len(served100k["acked"]) > 10


def test_100k_restart_replays_every_acknowledged_write(served100k):
    s2 = served100k["restarted"]
    assert read_back(s2, served100k["acked"]) == served100k["acked"]
    np.testing.assert_array_equal(s2.applied, served100k["applied"])
    # the bootstrap's election passed the snap count: its snapshot
    # is the packed form, not a JSON list of 100 000 numbers
    snaps = glob.glob(os.path.join(served100k["data_dir"], "snap",
                                   "*.snap"))
    assert snaps and max(os.path.getsize(p) for p in snaps) < 2 << 20


def test_100k_a_write_costs_the_wal_under_a_kilobyte(served100k):
    assert 0 < served100k["grown"] / N_PUTS < 1024
