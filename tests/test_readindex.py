"""Linearizable read path (PR 7): leader-lease reads, batched
ReadIndex, follower commit-index wait-points, and the consistency
knob.

The headline regression here was written FIRST, against the pre-PR-7
behavior: a follower GET during a partition served its local replica
and could return a value the quorum had since overwritten.  With the
linearizable default it must FAIL CLOSED (rejected or forwarded);
the stale serve stays reachable only via the explicit
``serializable`` opt-out.
"""

import time

import numpy as np
import pytest

from etcd_tpu.obs import metrics as _obs
from etcd_tpu.server.distserver import DistServer
from etcd_tpu.server.multigroup import group_of
from etcd_tpu.server.readindex import (
    LeaseClock,
    ReadQueue,
    WaitPoints,
    lease_drift_ticks,
)
from etcd_tpu.utils.errors import EtcdError
from etcd_tpu.utils.wait import Chan
from etcd_tpu.wire.requests import Request

from conftest import bootstrap_dist_leader, free_ports, \
    make_dist_cluster

G = 8
_NEXT_ID = [1 << 20]


def rid() -> int:
    _NEXT_ID[0] += 1
    return _NEXT_ID[0]


def put(srv, key, val, timeout=10.0):
    return srv.do(Request(method="PUT", id=rid(), path=key, val=val),
                  timeout=timeout)


def get(srv, key, timeout=5.0, **kw):
    return srv.do(Request(method="GET", id=rid(), path=key, **kw),
                  timeout=timeout)


def wait_for(pred, timeout=15.0, msg="condition"):
    t0 = time.time()
    while time.time() - t0 < timeout:
        try:
            if pred():
                return
        except (EtcdError, TimeoutError):
            pass
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.fixture
def cluster(tmp_path):
    servers, ports = make_dist_cluster(tmp_path, g=G)
    bootstrap_dist_leader(servers)
    yield servers, ports, tmp_path
    for s in servers:
        try:
            s.stop()
        except Exception:
            pass


_DEAD_URL = "http://127.0.0.1:1"


def _cut(servers, isolated):
    originals = [list(s.peer_urls) for s in servers]
    for i, s in enumerate(servers):
        for j in range(len(s.peer_urls)):
            if i != j and (i == isolated or j == isolated):
                s.peer_urls[j] = _DEAD_URL
    return originals


def _heal(servers, originals):
    for s, urls in zip(servers, originals):
        s.peer_urls[:] = urls


def _ctr(path, outcome):
    return _obs.registry.counter("etcd_read_serve_total",
                                 path=path, outcome=outcome).get()


# -- THE regression: stale follower reads must fail closed -------------------


def test_stale_follower_read_fails_closed_under_partition(cluster):
    """A follower cut off from the quorum holds a value the quorum
    overwrites.  Pre-PR-7, a GET on that follower served the stale
    value; now the linearizable default must reject (its leader is
    unreachable, so neither the forward nor the wait can confirm),
    and ONLY the explicit serializable opt-out reaches the old
    behavior."""
    servers, _, _ = cluster
    put(servers[0], "/stale", "v1")
    wait_for(lambda: get(servers[2], "/stale", serializable=True)
             .event.node.value == "v1",
             msg="v1 replicated to the follower")

    originals = _cut(servers, isolated=2)
    try:
        # the quorum (0, 1) overwrites while 2 is partitioned away
        put(servers[0], "/stale", "v2")
        assert get(servers[0], "/stale").event.node.value == "v2"

        # fail closed: the isolated follower must NOT serve v1 on
        # the default consistency level...
        with pytest.raises((TimeoutError, EtcdError)):
            get(servers[2], "/stale", timeout=2.0)
        # ...and the stale value stays reachable only via the
        # explicit opt-out
        assert get(servers[2], "/stale", serializable=True) \
            .event.node.value == "v1"
    finally:
        _heal(servers, originals)

    # healed: the linearizable read on the old follower converges to
    # the overwrite (never serving v1 again on the default level)
    def healed():
        v = get(servers[2], "/stale", timeout=5.0).event.node.value
        assert v == "v2", f"stale read after heal: {v}"
        return True

    wait_for(healed, timeout=30.0, msg="post-heal linearizable read")


# -- leader serve paths ------------------------------------------------------


def test_leader_lease_read_serves_instantly(cluster):
    servers, _, _ = cluster
    put(servers[0], "/lease", "x")
    # heartbeat acks establish the lease within a round or two
    wait_for(lambda: servers[0]._lease_fast_ok(
        group_of("/lease", G), time.monotonic()),
        msg="lease established")
    before = _ctr("lease", "ok")
    t0 = time.perf_counter()
    ev = get(servers[0], "/lease")
    dt = time.perf_counter() - t0
    assert ev.event.node.value == "x"
    assert _ctr("lease", "ok") >= before + 1
    # a lease serve is quorum-free: no network round trip in it
    assert dt < 1.0


def test_read_index_path_without_lease(tmp_path):
    """lease_ticks=0 disables the lease: every linearizable read
    takes the batched-ReadIndex confirmation piggybacked on the
    heartbeat acks — and still serves correct data."""
    servers, _ = make_dist_cluster(tmp_path, g=G, lease_ticks=0)
    try:
        bootstrap_dist_leader(servers)
        put(servers[0], "/ri", "y")
        before = _ctr("read_index", "ok")
        ev = get(servers[0], "/ri", timeout=10.0)
        assert ev.event.node.value == "y"
        assert _ctr("read_index", "ok") >= before + 1
        # the confirmation sweep recorded a batch
        h = _obs.registry.histogram("etcd_read_index_batch_size")
        assert h.count >= 1
    finally:
        for s in servers:
            s.stop()


def test_follower_read_observes_preceding_acked_write(cluster):
    """The linearizability contract the chaos gate asserts at scale:
    a write acked to THIS client must be visible to its immediately
    following read, even via a follower replica."""
    servers, _, _ = cluster
    for n in range(5):
        put(servers[0], "/seq", f"v{n}")
        ev = get(servers[1], "/seq", timeout=10.0)
        assert ev.event.node.value == f"v{n}", \
            f"follower read went back in time at {n}"
    assert _ctr("follower_wait", "ok") >= 1


def test_read_many_batches_confirmation(cluster):
    servers, _, _ = cluster
    for i in range(6):
        put(servers[0], f"/rm/k{i}", str(i))
    reqs = [Request(method="GET", id=rid(), path=f"/rm/k{i % 6}")
            for i in range(32)]
    h = _obs.registry.histogram("etcd_read_index_batch_size")
    before = h.count
    res = servers[0].read_many(reqs, timeout=10.0)
    vals = [x.event.node.value for x in res]
    assert vals == [str(i % 6) for i in range(32)]
    # one sweep released the whole batch: the amortization evidence
    assert h.count > before
    assert h.max >= 2


def test_read_many_serializable_and_rejects_writes(cluster):
    servers, _, _ = cluster
    put(servers[0], "/rm2", "z")
    reqs = [
        Request(method="GET", id=rid(), path="/rm2",
                serializable=True),
        Request(method="PUT", id=rid(), path="/rm2", val="nope"),
    ]
    res = servers[0].read_many(reqs, timeout=5.0)
    assert res[0].event.node.value == "z"
    assert isinstance(res[1], Exception)


def test_quorum_get_still_goes_through_log(cluster):
    servers, _, _ = cluster
    put(servers[0], "/q", "qq")
    ev = servers[0].do(Request(method="GET", id=rid(), path="/q",
                               quorum=True), timeout=10.0)
    assert ev.event.node.value == "qq"
    assert servers[0].store.stats.reads_by_path["quorum"] >= 1


def test_read_index_rpc_not_leader_refused(cluster):
    servers, _, _ = cluster
    with pytest.raises(TimeoutError):
        servers[1].read_index(0, timeout=1.0)


# -- lease band validation ---------------------------------------------------


def test_lease_band_enforced_at_construction(tmp_path):
    urls = [f"http://127.0.0.1:{p}" for p in free_ports(3)]
    with pytest.raises(ValueError, match="lease"):
        DistServer(str(tmp_path / "d"), slot=0, peer_urls=urls,
                   g=4, election=10, lease_ticks=9)


def test_lease_drift_margin():
    assert lease_drift_ticks(10) == 1
    assert lease_drift_ticks(60) == 6
    assert lease_drift_ticks(5) == 1


# -- bookkeeping units -------------------------------------------------------


def _mk_release_inputs(g, **over):
    kw = dict(
        lead=np.ones(g, bool), read_ok=np.ones(g, bool),
        applied=np.full(g, 10), floor=np.zeros(g, np.int64),
        basis=np.full(g, 5.0), lease_until=np.full(g, -np.inf),
        now=100.0)
    kw.update(over)
    return kw


def test_readqueue_releases_on_basis_past_registration():
    q = ReadQueue(4)
    c1, c2 = Chan(), Chan()
    q.register(1, t0=3.0, required=7, ch=c1)
    q.register(1, t0=6.0, required=8, ch=c2)
    # basis 5.0 covers only the first read (registered at 3.0)
    rel = q.release(**_mk_release_inputs(4))
    assert [(r[0].ch, r[1]) for r in rel] == [(c1, "read_index")]
    assert rel[0][2] == 7  # rd = max(required, floor)
    assert q.pending == 1
    # basis advances past the second registration
    rel = q.release(**_mk_release_inputs(4, basis=np.full(4, 6.5)))
    assert [r[0].ch for r in rel] == [c2]
    assert q.pending == 0


def test_readqueue_lease_releases_everything_and_floor_raises_rd():
    q = ReadQueue(2)
    ch = Chan()
    q.register(0, t0=50.0, required=3, ch=ch)
    rel = q.release(**_mk_release_inputs(
        2, basis=np.full(2, 0.0), lease_until=np.full(2, 200.0),
        floor=np.full(2, 9, np.int64)))
    assert [(r[1], r[2]) for r in rel] == [("lease", 9)]


def test_readqueue_gates_on_lead_read_ok_and_floor():
    q = ReadQueue(2)
    q.register(0, t0=1.0, required=0, ch=Chan())
    base = _mk_release_inputs(2)
    for bad in (dict(lead=np.zeros(2, bool)),
                dict(read_ok=np.zeros(2, bool)),
                dict(applied=np.zeros(2),
                     floor=np.full(2, 5, np.int64))):
        assert q.release(**{**base, **bad}) == []
    assert q.release(**base) != []


def test_readqueue_fail_lanes_and_expire():
    q = ReadQueue(4)
    a, b, c = Chan(), Chan(), Chan()
    q.register(0, t0=1.0, required=0, ch=a)
    q.register(2, t0=2.0, required=0, ch=b)
    q.register(2, t0=90.0, required=0, ch=c)
    lanes = np.zeros(4, bool)
    lanes[0] = True
    failed = q.fail_lanes(lanes)
    assert [p.ch for p in failed] == [a]
    expired = q.expire(now=100.0, max_age=50.0)
    assert [p.ch for p in expired] == [b]
    assert q.pending == 1


def test_waitpoints_release_in_index_order():
    w = WaitPoints(2)
    chans = [Chan() for _ in range(3)]
    w.register(0, 5, chans[0])
    w.register(0, 3, chans[1])
    w.register(1, 4, chans[2])
    out = w.release(np.array([4, 2]))
    assert out == [chans[1]]
    out = w.release(np.array([5, 4]))
    assert set(map(id, out)) == {id(chans[0]), id(chans[2])}
    assert w.pending == 0


def test_waitpoints_expire_drops_stale_waiters_only():
    w = WaitPoints(2)
    old, fresh = Chan(), Chan()
    w.register(0, 50, old, t0=1.0)
    w.register(0, 40, fresh, t0=90.0)
    out = w.expire(now=100.0, max_age=50.0)
    assert out == [old]
    assert w.pending == 1
    # the surviving heap still releases in index order
    assert w.release(np.array([45, 0])) == [fresh]


def test_read_many_value_equal_to_sentinel_text(cluster):
    """A STORED VALUE must never collide with read_many's internal
    result-slot sentinels (regression: the serializable marker was
    the string \"serz\", so a key holding that text crashed the
    batch)."""
    servers, _, _ = cluster
    put(servers[0], "/sentinel", "serz")
    res = servers[0].read_many(
        ["/sentinel",
         Request(method="GET", id=rid(), path="/sentinel",
                 serializable=True)], timeout=10.0)
    assert res[0] == "serz"                 # compact raw value
    assert res[1].event.node.value == "serz"


def test_leaseclock_deposing_ack_extends_nothing():
    lc = LeaseClock(2, 3, 0)
    members = np.ones((2, 3), bool)
    nm = np.full(2, 3)
    # peer 1 endorses lane 0 only (lane 1 answered from a higher
    # term -> inactive); peer 2 endorses both
    lc.note_ack(1, 8.0, np.array([True, False]))
    lc.note_ack(2, 4.0, np.array([True, True]))
    b = lc.basis(members, nm, now=10.0)
    assert list(b) == [8.0, 4.0]
    # a late ack for an OLDER frame cannot regress the evidence
    lc.note_ack(1, 2.0, np.array([True, True]))
    assert list(lc.basis(members, nm, now=10.0)) == [8.0, 4.0]


def test_deposed_need_snap_ack_shape_cannot_renew_lease():
    """The lease mask is ``resp.active & resp.ok`` because bare
    ``active`` is NOT cur-only: a follower at a HIGHER term still
    folds need_snap lanes into active so the step-down propagates
    (distmember.handle_append).  Pin that shape — ok must stay
    False on such lanes, or a deposing ack could extend a lease."""
    from etcd_tpu.raft.distmember import DistMember
    from etcd_tpu.wire.distmsg import AppendBatch, VoteReq

    m = DistMember(2, 2, 1, 8)
    # adopt term 5 (the member voted in a newer election)
    m.handle_vote(VoteReq(
        sender=0, term=np.full(2, 5, np.int32),
        last=np.zeros(2, np.int32), lterm=np.zeros(2, np.int32),
        active=np.ones(2, bool)))
    # a stale term-1 leader's need_snap notification frame
    resp = m.handle_append(AppendBatch(
        sender=0, term=np.ones(2, np.int32),
        prev_idx=np.zeros(2, np.int32),
        prev_term=np.zeros(2, np.int32),
        n_ents=np.zeros(2, np.int32),
        commit=np.zeros(2, np.int32),
        active=np.ones(2, bool),
        need_snap=np.array([True, False]),
        ent_terms=np.zeros((2, m.e), np.int32),
        payloads=[[], []]))
    # active folds the need_snap lane in (step-down must propagate)
    assert bool(resp.active[0])
    # ...but ok stays False: active & ok excludes it from the lease
    assert not bool(resp.ok[0])
    assert not bool((np.asarray(resp.active)
                     & np.asarray(resp.ok)).any())
    # and the response carries the deposing term
    assert int(np.asarray(resp.term)[0]) == 5


def test_stats_reads_by_path_split():
    from etcd_tpu.store.stats import Stats

    s = Stats()
    s.inc_read_path("lease")
    s.inc_read_path("lease", 3)
    s.inc_read_path("follower_wait")
    d = s.to_dict()
    assert d["readsByPath"]["lease"] == 4
    assert d["readsByPath"]["follower_wait"] == 1
    with pytest.raises(KeyError):
        s.inc_read_path("typo_path")
    assert Stats.from_dict(d).reads_by_path["lease"] == 4


# -- the lease off (--dist-lease-ticks 0): every read a quorum round ---------

G_OFF = 64
N_OPS = 120
READ_WAITS = ("dist.read_lock", "dist.read_confirm", "dist.read_release")


def _wall() -> dict[str, int]:
    """``{stage: count}`` of ``etcd_stage_seconds{kind=wall}``
    (``tests/test_stage_coverage.py``'s form)."""
    fam = _obs.registry.snapshot(light=True).get(
        "etcd_stage_seconds", {"samples": []})
    return {c["labels"]["stage"]: c["count"] for c in fam["samples"]
            if c["labels"]["kind"] == "wall"}


def _lease_serves() -> float:
    fam = _obs.registry.snapshot(light=True)["etcd_read_serve_total"]
    return sum(c["value"] for c in fam["samples"]
               if c["labels"]["path"] == "lease")


@pytest.fixture(scope="module")
def lease_off(tmp_path_factory):
    """A 64-group cluster of three with ``lease_ticks=0`` and, once a
    member, a seeded sequence of PUTs through slot 0 and default GETs
    through that member, each answer kept beside what a dict holds."""
    import random

    servers, _ = make_dist_cluster(tmp_path_factory.mktemp("leaseoff"),
                                   g=G_OFF, lease_ticks=0)
    try:
        bootstrap_dist_leader(servers)
        lease0, wall0 = _lease_serves(), _wall()
        seen: dict[int, list] = {}
        gets: dict[int, int] = {}
        for through in (0, 1):
            rng = random.Random(2_200_000_935 + through)
            model: dict[str, str] = {}
            seen[through], gets[through] = [], 0
            for n in range(N_OPS):
                key = f"/t{rng.randrange(24):05d}/cfg{through}"
                if rng.random() < 0.4:
                    model[key] = f"{through}.{n}"
                    put(servers[0], key, model[key])
                    continue
                gets[through] += 1
                try:
                    got = get(servers[through], key,
                              timeout=10.0).event.node.value
                except EtcdError:
                    got = None             # key not found
                seen[through].append((key, got, model.get(key)))
        wall1 = _wall()
        yield {"servers": servers, "seen": seen, "gets": gets,
               "lease_serves": _lease_serves() - lease0,
               "grown": {k: wall1.get(k, 0) - wall0.get(k, 0)
                         for k in READ_WAITS}}
    finally:
        for s in servers:
            s.stop()


@pytest.mark.parametrize("through", [0, 1], ids=["leader", "follower"])
def test_lease_off_reads_agree_with_a_dict(lease_off, through):
    seen = lease_off["seen"][through]
    assert len(seen) > N_OPS // 3
    assert [(k, got) for k, got, _ in seen] == [
        (k, want) for k, _, want in seen]


def test_lease_off_serves_no_read_by_lease(lease_off):
    assert lease_off["lease_serves"] == 0
    assert all(s._lease_s == 0 for s in lease_off["servers"])


@pytest.mark.parametrize("wait", READ_WAITS)
def test_lease_off_records_the_read_s_waits(lease_off, wait):
    """Every default GET files its wait for the member's lock (the
    follower's too); every registered read (the leader's own and the
    follower's read-index call) its wait for the confirmation; every
    sweep that released one its own time.  A wait is one wall sample:
    no cpu column, no span."""
    n = lease_off["grown"][wait]
    if wait == "dist.read_release":
        assert n >= 1
    else:
        assert n == sum(lease_off["gets"].values())
    fam = _obs.registry.snapshot(light=True)["etcd_stage_seconds"]
    assert {c["labels"]["kind"] for c in fam["samples"]
            if c["labels"]["stage"] == wait} == {"wall"}


def test_lease_off_read_fails_closed_with_both_links_cut(lease_off):
    """No quorum answers: the GET times out, is counted ``timeout``
    and is never served — not from the lease (there is none), not from
    local state."""
    from etcd_tpu.utils import faults

    leader = lease_off["servers"][0]
    put(leader, "/t00001/closed", "v1")
    assert get(leader, "/t00001/closed",
               timeout=10.0).event.node.value == "v1"
    ok0 = _ctr("read_index", "ok")
    out0, lease0 = _ctr("read_index", "timeout"), _lease_serves()
    faults.FAULTS.configure("peerlink.send[s0->*]=drop()")
    try:
        with pytest.raises(TimeoutError):
            get(leader, "/t00001/closed", timeout=1.0)
    finally:
        faults.FAULTS.configure("")
    assert _ctr("read_index", "timeout") == out0 + 1
    assert _ctr("read_index", "ok") == ok0
    assert _lease_serves() == lease0
    # healed: the same read confirms again
    wait_for(lambda: get(leader, "/t00001/closed", timeout=5.0)
             .event.node.value == "v1", timeout=30.0,
             msg="a confirmed read after the links heal")
