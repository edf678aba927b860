"""Three repairs of the co-hosted snapshot at large G, each held to
what the earlier code gave: the blob carries the frontier, the terms
and the members mask as packed little-endian arrays (a JSON list of
100 000 numbers was the encode's cost), and still reads the JSON
object older data directories hold; a compaction prunes the payloads
of the groups that hold one below the cut, where it visited every
group; and it slides each log window with a barrel shifter, where a
per-element gather took 2.8 s a member at 100 000 groups on the
v5e."""

import copy
import json
import os
import shutil

import numpy as np
import pytest

from etcd_tpu.raft.multiraft import MultiRaft
from etcd_tpu.server.multigroup import (
    MultiGroupServer,
    decode_snapshot,
    encode_snapshot,
)
from etcd_tpu.snap import Snapshotter
from etcd_tpu.wire import Snapshot
from etcd_tpu.wire.requests import Request


def json_blob(store: bytes, frontier, terms, members, seq: int,
              applied_total: int) -> bytes:
    """The blob as data directories written before the packed form
    hold it."""
    return json.dumps({
        "store": store.decode(),
        "frontier": [int(x) for x in frontier],
        "terms": [int(x) for x in terms],
        "seq": seq,
        "applied_total": applied_total,
        "members": np.asarray(members).astype(int).tolist(),
    }).encode()


@pytest.mark.parametrize("g,m", [(1, 1), (7, 4), (1000, 6)])
def test_packed_and_json_blobs_decode_alike(g, m):
    rng = np.random.default_rng(g * 10 + m)
    args = (json.dumps({"root": {"k": "v" * g}}).encode(),
            rng.integers(0, 2**31 - 1, g), rng.integers(0, 9, g),
            rng.random((g, m)) < 0.5, int(rng.integers(1 << 40)),
            int(rng.integers(1 << 40)))
    packed = encode_snapshot(*args)
    assert not packed.startswith(b"{")
    a, b = decode_snapshot(packed), decode_snapshot(json_blob(*args))
    assert a.keys() == b.keys()
    for key in a:
        np.testing.assert_array_equal(a[key], b[key], err_msg=key)
    assert a["frontier"].dtype == b["frontier"].dtype == np.int64
    assert a["members"].dtype == bool and a["members"].shape == (g, m)
    with pytest.raises(RuntimeError, match="header"):
        decode_snapshot(packed[:-1])


def state_of(s: MultiGroupServer, paths) -> dict:
    return {
        "values": {p: s.store.get(p, False, False).node.value
                   for p in paths},
        "applied": s.applied.tolist(),
        "members": s.mr.members_mask().tolist(),
        "commit": s.mr.commit_index().tolist(),
        "raft_index": s.raft_index, "seq": s.seq}


def test_a_json_snapshot_restores_what_the_packed_one_does(tmp_path):
    """One data directory, restarted as written (a packed snapshot)
    and with its newest snapshot rewritten in the older JSON form:
    the two restored states are the same."""
    packed = str(tmp_path / "packed")
    kw = dict(g=8, m=3, cap=64, snap_count=5, tick_interval=0.02)
    s = MultiGroupServer(packed, **kw)
    s.start()
    paths = [f"/snapns{i % 3}/k{i}" for i in range(14)]
    try:
        for i, p in enumerate(paths):
            s.do(Request(id=900 + i, method="PUT", path=p, val=f"x{i}"),
                 timeout=60)
    finally:
        s.stop()
    old = str(tmp_path / "old")
    shutil.copytree(packed, old)
    ss = Snapshotter(os.path.join(old, "snap"))
    snap = ss.load()
    blob = decode_snapshot(snap.data)
    newest = max(n for n in os.listdir(os.path.join(old, "snap"))
                 if n.endswith(".snap"))
    os.remove(os.path.join(old, "snap", newest))
    ss.save_snap(Snapshot(data=json_blob(
        blob["store"], blob["frontier"], blob["terms"], blob["members"],
        blob["seq"], blob["applied_total"]), index=snap.index,
        term=snap.term))
    assert Snapshotter(os.path.join(old, "snap")).load().data \
        .startswith(b"{")
    states = []
    for d in (packed, old):
        r = MultiGroupServer(d, **kw)
        try:
            states.append(state_of(r, paths))
        finally:
            r.stop()
    assert states[0] == states[1]
    assert states[0]["values"] == {p: f"x{i}" for i, p in
                                   enumerate(paths)}


def prune_as_before(payloads, cut):
    """What a compaction left of the payloads before the repair: every
    group visited, those with a payload below the cut pruned."""
    out = []
    for gi, p in enumerate(payloads):
        c = int(cut[gi])
        out.append({k: v for k, v in p.items() if k >= c}
                   if p and min(p) < c else p)
    return out


@pytest.mark.parametrize("seed", range(4))
def test_compaction_prunes_what_the_full_sweep_pruned(seed, monkeypatch):
    rng = np.random.default_rng(seed)
    g = 40
    mr = MultiRaft(g=g, m=3, cap=16, max_batch_ents=8)
    mr.campaign(0)
    for r in range(6):
        n = np.where(rng.random(g) < 0.3, rng.integers(1, 4, g), 0) \
            .astype(np.int32)
        mr.propose(n, data={int(gi): [f"{r}.{gi}.{j}".encode()
                                      for j in range(int(n[gi]))]
                            for gi in np.nonzero(n)[0]})
        if r % 3 == 2:
            # applied short of the commit in some groups: the cut
            # stops there, so part of a group's payloads survives
            applied = mr.commit_index() - rng.integers(0, 2, g)
            mr.mark_applied(np.maximum(applied, 0))
            before = copy.deepcopy(mr.payloads)
            visited = []
            keep = mr._keep_payloads
            monkeypatch.setattr(mr, "_keep_payloads", lambda gi, f: (
                visited.append(int(gi)), keep(gi, f)))
            mr.compact()
            monkeypatch.undo()
            cut = np.min([np.asarray(st.offset) for st in mr.states],
                         axis=0)
            assert mr.payloads == prune_as_before(before, cut)
            assert sorted(visited) == [
                gi for gi, p in enumerate(before)
                if p and min(p) < cut[gi]]
            assert visited               # something was pruned
        low = [min(p) if p else np.iinfo(np.int64).max
               for p in mr.payloads]
        assert mr._payload_low.tolist() == low


@pytest.mark.parametrize("cap", [1, 2, 3, 8, 64, 1024])
def test_the_window_slides_as_the_gather_slid_it(cap):
    """The compaction's window slide (``batched.shift_left``, a
    barrel shifter) against the per-element gather it replaced."""
    import jax

    from etcd_tpu.raft.batched import shift_left

    rng = np.random.default_rng(cap)
    x = rng.integers(-5, 1000, (64, cap)).astype(np.int32)
    shift = rng.integers(-3, cap + 3, 64).astype(np.int32)
    got = np.asarray(jax.jit(shift_left)(x, shift))
    src = np.arange(cap)[None, :] + np.clip(shift, 0, cap)[:, None]
    want = np.where(src < cap, np.take_along_axis(
        x, np.clip(src, 0, cap - 1), axis=1), 0)
    np.testing.assert_array_equal(got, want)
