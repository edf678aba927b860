"""Distributed multi-group server: 3 hosts on localhost HTTP, real
frames over real sockets (the reference's in-process cluster test
upgraded to actual transport, server_test.go:370-447 +
cluster_store.go:106-156 semantics)."""

import os
import time

import numpy as np
import pytest

from etcd_tpu.server.distserver import DistServer
from etcd_tpu.wire.requests import Request

G = 8
_NEXT_ID = [1]


def rid() -> int:
    _NEXT_ID[0] += 1
    return _NEXT_ID[0]


from conftest import bootstrap_dist_leader, free_ports as free_ports_n, \
    make_dist_cluster


def make_cluster(tmp_path, m=3, g=G, ports=None, **kw):
    return make_dist_cluster(tmp_path, m=m, g=g, ports=ports, **kw)


def put(srv, key, val, timeout=10.0):
    return srv.do(Request(method="PUT", id=rid(), path=key, val=val),
                  timeout=timeout)


def get(srv, key):
    # serializable on purpose: this suite's GETs assert what THIS
    # host's replica holds (replication progress, restart catch-up,
    # partition divergence) — the pre-PR-7 local-read semantics,
    # reachable only via the explicit opt-out.  Linearizable-read
    # behavior is covered by tests/test_readindex.py.
    return srv.do(Request(method="GET", id=rid(), path=key,
                          serializable=True))


def wait_for(pred, timeout=15.0, msg="condition"):
    from etcd_tpu.utils.errors import EtcdError

    t0 = time.time()
    while time.time() - t0 < timeout:
        try:
            if pred():
                return
        except EtcdError:
            pass  # e.g. key not replicated yet
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {msg}")


def eventually(call, timeout=120.0, msg="call"):
    """``call()`` until it returns and not a ``TimeoutError``: a
    request's own timeout says how long THAT attempt waited on a
    loaded host, not that the cluster cannot do it.  For calls that
    are safe to make again (a PUT of the same value, a CONFCHANGE:
    an idempotent membership-mask set)."""
    deadline = time.time() + timeout
    while True:
        try:
            return call()
        except TimeoutError:
            if time.time() >= deadline:
                raise AssertionError(f"timed out: {msg}") from None


@pytest.fixture
def cluster(tmp_path):
    servers, ports = make_cluster(tmp_path)
    bootstrap_dist_leader(servers)
    yield servers, ports, tmp_path
    for s in servers:
        try:
            s.stop()
        except Exception:
            pass


def test_write_commits_and_replicates(cluster):
    servers, _, _ = cluster
    ev = put(servers[0], "/foo", "bar")
    assert ev.event.node.value == "bar"
    # replication reaches follower replicas within a few rounds
    wait_for(lambda: all(
        get(s, "/foo").event.node.value == "bar"
        for s in servers[1:]), msg="replication to followers")


def test_follower_forwards_writes(cluster):
    servers, _, _ = cluster
    # follower must learn the leader before it can forward
    wait_for(lambda: (servers[1].mr.leader_hint() == 0).all(),
             msg="leader hint propagation")
    ev = put(servers[1], "/fwd", "v1")
    assert ev.event.node.value == "v1"
    wait_for(lambda: get(servers[0], "/fwd").event.node.value == "v1",
             msg="forwarded write on leader")


def test_survives_one_host_down(cluster):
    servers, _, _ = cluster
    put(servers[0], "/a", "1")
    servers[2].stop()          # hard loss of one member
    # quorum of 2/3 keeps committing
    ev = put(servers[0], "/a", "2", timeout=15.0)
    assert ev.event.node.value == "2"
    wait_for(lambda: get(servers[1], "/a").event.node.value == "2",
             msg="replication with one host down")


def test_restart_catches_up_from_wal(cluster):
    servers, ports, tmp_path = cluster
    for i in range(5):
        put(servers[0], f"/k{i}", f"v{i}")
    servers[1].stop()
    for i in range(5, 10):
        put(servers[0], f"/k{i}", f"v{i}", timeout=15.0)
    # restart host 1 from its own WAL; replication repairs the gap
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    s1 = DistServer(str(tmp_path / "slot1"), slot=1, peer_urls=urls,
                    g=G, cap=64, tick_interval=0.05,
                    post_timeout=2.0)
    # pre-restart state survived (committed prefix is in the store)
    assert get(s1, "/k0").event.node.value == "v0"
    s1.start()
    servers[1] = s1
    wait_for(lambda: all(
        get(s1, f"/k{i}").event.node.value == f"v{i}"
        for i in range(10)), msg="restarted host catch-up")


def test_snapshot_pull_past_compaction(cluster):
    servers, ports, tmp_path = cluster
    put(servers[0], "/base", "x")
    servers[2].stop()
    # drive the leader far past the dead member, then snapshot +
    # compact so its log no longer reaches the laggard
    for i in range(30):
        put(servers[0], f"/s{i}", f"v{i}", timeout=15.0)
    servers[0].snapshot()
    # restart the laggard: appends reject -> need_snap -> pull
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    s2 = DistServer(str(tmp_path / "slot2"), slot=2, peer_urls=urls,
                    g=G, cap=64, tick_interval=0.05,
                    post_timeout=2.0)
    s2.start()
    servers[2] = s2
    wait_for(lambda: all(
        get(s2, f"/s{i}").event.node.value == f"v{i}"
        for i in range(30)), timeout=30.0,
        msg="snapshot pull catch-up")


def test_leader_failover_elects_new_leader(cluster):
    servers, _, _ = cluster
    put(servers[0], "/f", "1")
    wait_for(lambda: all(
        get(s, "/f").event.node.value == "1" for s in servers),
        msg="initial replication")
    servers[0].stop()          # kill the leader of every group
    # a surviving member's election timers fire and win 2/3 quorums
    wait_for(lambda: (servers[1].mr.is_leader()
                      | servers[2].mr.is_leader()).all(),
             timeout=30.0, msg="failover election")
    new_lead = servers[1] if servers[1].mr.is_leader().any() \
        else servers[2]
    ev = put(new_lead, "/f", "2", timeout=20.0)
    assert ev.event.node.value == "2"


def test_v2_http_api_serves_dist_cluster(cluster):
    """The standard /v2 client API mounts on DistServer (same seams
    as EtcdServer): PUT via the leader host's HTTP endpoint, GET from
    a follower's, /v2/machines lists the published member."""
    import json as _json
    import urllib.request

    from etcd_tpu.api.http import make_client_handler, serve

    servers, _, _ = cluster
    # the reference's 500 ms server timeout is too tight for a
    # 3-server single-CPU test box; the mounting is what's under test
    h0 = serve(make_client_handler(servers[0], server_timeout=30.0),
               "127.0.0.1", 0)
    h1 = serve(make_client_handler(servers[1], server_timeout=30.0),
               "127.0.0.1", 0)
    p0 = h0.server_address[1]
    p1 = h1.server_address[1]
    try:
        def put_ok():
            req = urllib.request.Request(
                f"http://127.0.0.1:{p0}/v2/keys/httpapi/k",
                data=b"value=V", method="PUT",
                headers={"Content-Type":
                         "application/x-www-form-urlencoded"})
            try:
                with urllib.request.urlopen(req, timeout=30) as resp:
                    body = _json.loads(resp.read())
            except urllib.error.HTTPError:
                return False  # transient leadership blip: retry
            assert body["action"] == "set"
            assert body["node"]["value"] == "V"
            return True
        wait_for(put_ok, timeout=30.0, msg="HTTP PUT through dist")

        def follower_sees():
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{p1}/v2/keys/httpapi/k",
                        timeout=5) as resp:
                    return _json.loads(
                        resp.read())["node"]["value"] == "V"
            except urllib.error.HTTPError:
                return False
        wait_for(follower_sees, msg="follower HTTP read")

        # the registry publishes through consensus; these servers set
        # no client_urls so the /v2/machines body itself is empty —
        # assert the endpoint serves and the replicated registry holds
        # all three members
        def registry_full():
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{p0}/v2/machines",
                        timeout=5) as resp:
                    assert resp.status == 200
            except urllib.error.HTTPError:
                return False
            return len(servers[0].cluster_store.get()) == 3
        wait_for(registry_full, timeout=30.0,
                 msg="registry publish via consensus")
    finally:
        h0.shutdown()
        h1.shutdown()


def test_dist_runtime_membership_grow(tmp_path):
    """Distributed AddMember: a 4th host (pre-sized slot, live=3)
    joins at runtime — the ConfChange commits under the old 2-of-3
    quorum, the new member catches up by replication, and the new
    4-member quorum (3) is reflected in every host's mask."""
    servers, _ = make_dist_cluster(tmp_path, m=4, g=4, live=3)
    try:
        bootstrap_dist_leader(servers)
        eventually(lambda: put(servers[0], "/dm/a", "1"),
                   msg="a write under the 3-member quorum")
        assert servers[0].members_of(0).sum() == 3

        eventually(lambda: servers[0].add_member(3),
                   msg="the grow commits in every group")
        assert all(servers[0].members_of(gi).sum() == 4
                   for gi in range(4))
        # the joined member replicates (append path now includes it)
        eventually(lambda: put(servers[0], "/dm/b", "2"),
                   msg="a write under the 4-member quorum")
        wait_for(lambda: get(servers[3],
                             "/dm/b").event.node.value == "2",
                 timeout=120.0, msg="new member catches up")
        # every host converges on the 4-member mask via replication
        wait_for(lambda: all(
            s.members_of(0).sum() == 4 for s in servers),
            timeout=120.0, msg="mask convergence")
        # shrink back: quorum returns to 2-of-3
        eventually(lambda: servers[0].remove_member(3),
                   msg="the shrink commits in every group")
        assert all(servers[0].members_of(gi).sum() == 3
                   for gi in range(4))
        eventually(lambda: put(servers[0], "/dm/c", "3"),
                   msg="a write after the shrink")
    finally:
        for s in servers:
            try:
                s.stop()
            except Exception:
                pass


def test_dist_conf_change_with_split_leadership(tmp_path):
    """The review scenario: leadership split across hosts — a
    ConfChange for a group led elsewhere must FORWARD to that
    group's leader (a local-only submission would commit on this
    host's lanes and silently diverge per-group membership)."""
    servers, _ = make_dist_cluster(tmp_path, m=4, g=4, live=3)
    try:
        bootstrap_dist_leader(servers)
        # groups 0-1 led by host 1, groups 2-3 by host 0: each host
        # campaigns for the lanes it is to lead until it has them
        # (an election can flap under load; nothing here trusts how
        # long one takes)
        want = {1: np.array([True, True, False, False]),
                0: np.array([False, False, True, True])}

        def split() -> bool:
            return all((servers[h].mr.is_leader() == m).all()
                       for h, m in want.items())

        def make_split(deadline: float) -> None:
            while not split():
                assert time.time() < deadline, \
                    "leadership never split 2/2 across hosts 0 and 1"
                for h, m in want.items():
                    lanes = m & ~servers[h].mr.is_leader()
                    if lanes.any():
                        servers[h]._campaign(lanes)
                time.sleep(0.3)

        deadline = time.time() + 240.0
        make_split(deadline)
        # host 0 proposes the grow; groups 0-1 forward to host 1.
        # A forward that times out is re-proposed once the split
        # holds again (the CONFCHANGE apply is an idempotent
        # membership-mask set, so a commit that raced the timeout is
        # safe to re-propose); the cross-host forward is exercised on
        # whichever attempt lands.
        while True:
            try:
                servers[0].add_member(3)
                break
            except TimeoutError:
                assert time.time() < deadline, \
                    "the grow never committed in every group"
                make_split(deadline)
        wait_for(lambda: all(
            s.members_of(gi).sum() == 4
            for s in servers for gi in range(4)),
            timeout=120.0, msg="uniform 4-member masks everywhere")
    finally:
        for s in servers:
            try:
                s.stop()
            except Exception:
                pass


def test_ttl_expiry_replicates_to_followers(cluster):
    """TTL expiry rides a replicated SYNC proposal (server.go:438-456
    semantics): the key disappears from FOLLOWER replicas too, not
    just the leader's store."""
    from etcd_tpu.utils.errors import EtcdError

    servers, _, _ = cluster
    # TTL long enough that replication observably lands first (a
    # too-short TTL races the first wait and flakes)
    servers[0].do(Request(
        method="PUT", id=rid(), path="/ttl/a", val="v",
        expiration=int((time.time() + 3.0) * 1e9)), timeout=15)
    wait_for(lambda: get(servers[1], "/ttl/a").event.node.value
             == "v", msg="TTL key replicated")

    def gone_everywhere():
        for s in servers:
            try:
                s.store.get("/ttl/a", False, False)
                return False
            except EtcdError:
                continue
        return True
    wait_for(gone_everywhere, timeout=30.0,
             msg="TTL expiry on all replicas")


def test_idle_sync_traffic_does_not_wedge_group0(tmp_path):
    """Review regression: periodic replicated SYNCs must not fill
    group 0's fixed-cap log lane on an idle cluster — lane-fill
    compaction runs independently of the snap_count trigger."""
    servers, _ = make_dist_cluster(tmp_path, m=3, g=4, cap=16,
                                   sync_interval=0.02)
    try:
        bootstrap_dist_leader(servers)
        # idle until more SYNC entries than the lane holds have gone
        # into group 0: last - offset never exceeds cap, so the lane
        # was compacted on the way, with snap_count far out of reach
        cap = 16

        def lane0() -> tuple[int, int]:
            st = servers[0].mr.state
            return (int(np.asarray(st.last)[0]),
                    int(np.asarray(st.offset)[0]))

        wait_for(lambda: lane0()[0] > cap, timeout=120.0,
                 msg=f"more than cap SYNC entries into group 0 "
                     f"(last, offset = {lane0()})")
        last, offset = lane0()
        assert 0 < offset and last - offset <= cap, (last, offset)
        # and the member still takes a client's write
        ev = put(servers[0], "/idle/k", "v", timeout=60.0)
        assert ev.event.node.value == "v"
    finally:
        for s in servers:
            try:
                s.stop()
            except Exception:
                pass


def test_ballot_survives_restart_no_double_vote(tmp_path):
    """Vote durability (the HardState analog): a host that granted
    its vote for term T must still refuse a competing candidate at
    term T after a crash/restart — the ballot WAL record is the only
    thing standing between this and a split-brain double grant."""
    from etcd_tpu.wire.distmsg import VoteReq, unmarshal_any

    ports = free_ports_n(3)
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    s = DistServer(str(tmp_path / "slot0"), slot=0, peer_urls=urls,
                   g=4, cap=64, election=60)
    term5 = np.full(4, 5, np.int32)
    req_a = VoteReq(sender=1, term=term5,
                    last=np.zeros(4, np.int32),
                    lterm=np.zeros(4, np.int32),
                    active=np.ones(4, bool))
    resp = unmarshal_any(s.handle_frame(req_a.marshal()))
    assert resp.granted.all()
    # a TRUE crash image: snapshot the data dir BEFORE any graceful
    # shutdown flushes could mask a missing ballot fsync in the
    # vote-response path itself
    import shutil

    shutil.copytree(str(tmp_path / "slot0"), str(tmp_path / "crash"))
    s.stop()

    s2 = DistServer(str(tmp_path / "crash"), slot=0, peer_urls=urls,
                    g=4, cap=64, election=60)
    assert (np.asarray(s2.mr.state.term) == 5).all()
    assert (np.asarray(s2.mr.state.vote) == 1).all()
    req_b = VoteReq(sender=2, term=term5,
                    last=np.ones(4, np.int32) * 9,
                    lterm=np.ones(4, np.int32) * 9,
                    active=np.ones(4, bool))
    resp_b = unmarshal_any(s2.handle_frame(req_b.marshal()))
    assert not resp_b.granted.any(), "double vote at the same term!"
    # the SAME candidate re-asking is re-granted (idempotent)
    resp_a2 = unmarshal_any(s2.handle_frame(req_a.marshal()))
    assert resp_a2.granted.all()
    s2.stop()


def test_stats_reflect_distributed_roles(cluster):
    """/v2/stats/self parity: the bootstrap leader reports
    StateLeader with append sends; followers report receives."""
    servers, _, _ = cluster
    put(servers[0], "/stats/k", "v")
    wait_for(lambda: servers[0].server_stats.to_dict()["state"]
             == "StateLeader", msg="leader state in stats")
    d0 = servers[0].server_stats.to_dict()
    assert d0["sendAppendRequestCnt"] > 0
    wait_for(lambda: servers[1].server_stats.to_dict()[
        "recvAppendRequestCnt"] > 0, msg="follower recv count")


def test_stats_deposed_leader_becomes_follower(cluster):
    """Review regression: a deposed leader's /v2/stats/self must drop
    back to StateFollower (the no-leader-lanes early return must not
    freeze the last reported role)."""
    servers, _, _ = cluster
    put(servers[0], "/dep/k", "v")
    wait_for(lambda: servers[0].server_stats.to_dict()["state"]
             == "StateLeader", msg="leader state")
    # host 1 takes every group at a higher term
    deadline = time.time() + 30.0
    while time.time() < deadline:
        if servers[1].mr.is_leader().all():
            break
        servers[1]._campaign(~servers[1].mr.is_leader())
        time.sleep(0.3)
    assert servers[1].mr.is_leader().all()
    wait_for(lambda: servers[0].server_stats.to_dict()["state"]
             == "StateFollower", timeout=30.0,
             msg="deposed host reports follower")


def test_watch_fires_on_follower_replica(cluster):
    """Watches registered on a FOLLOWER's replica fire when
    replication applies the committed write there — the wait=true
    long-poll works against any host."""
    servers, _, _ = cluster
    wc = servers[1].do(Request(id=rid(), method="GET",
                               path="/wf/key", wait=True)).watcher
    put(servers[0], "/wf/key", "fired")
    # watcher events buffer from registration; drain inline
    ev = wc.next_event(timeout=30)
    assert ev is not None and ev.action == "set"
    assert ev.node.value == "fired"


# -- partition / split-brain safety ----------------------------------------


_DEAD_URL = "http://127.0.0.1:1"  # nothing listens: instant refusal


def _cut(servers, isolated):
    """Bidirectional partition at the network layer: every peer URL
    crossing the cut is swapped for a dead address, so ALL HTTP
    paths — round frames, write forwarding, snapshot pulls — fail
    the way a partitioned network fails (connection refused = the
    dropped-message contract)."""
    originals = [list(s.peer_urls) for s in servers]
    for i, s in enumerate(servers):
        for j in range(len(s.peer_urls)):
            if i != j and (i == isolated or j == isolated):
                s.peer_urls[j] = _DEAD_URL
    return originals


def _heal(servers, originals):
    for s, urls in zip(servers, originals):
        s.peer_urls[:] = urls


def test_partition_no_split_brain_then_heal_converges(cluster):
    """An isolated leader must not ack writes (no quorum); the
    majority side elects and serves; after healing, the deposed
    leader converges and the unacked write never surfaces anywhere
    (the system-level form of the raft_test lossy-topology suite)."""
    from etcd_tpu.utils.errors import EtcdError

    servers, _, _ = cluster
    put(servers[0], "/p", "committed")
    wait_for(lambda: all(
        get(s, "/p").event.node.value == "committed"
        for s in servers[1:]), msg="pre-partition replication")

    originals = _cut(servers, isolated=0)
    try:
        # safety: the cut-off leader cannot reach quorum, so the
        # write must NOT be acknowledged
        with pytest.raises((TimeoutError, EtcdError)):
            put(servers[0], "/p", "stale", timeout=3.0)
        assert get(servers[1], "/p").event.node.value == "committed"
        # liveness: the majority elects new leaders and serves
        wait_for(lambda: (servers[1].mr.is_leader()
                          | servers[2].mr.is_leader()).all(),
                 timeout=30.0, msg="majority election")
        new_lead = servers[1] if servers[1].mr.is_leader().any() \
            else servers[2]

        # leader hints on the majority side may lag the election by a
        # round; retry the write like a real client would
        def majority_write():
            try:
                return put(new_lead, "/maj", "2",
                           timeout=5.0).event.node.value == "2"
            except (TimeoutError, EtcdError):
                return False

        wait_for(majority_write, timeout=30.0,
                 msg="majority-side write during partition")
    finally:
        _heal(servers, originals)

    # healed: a write to the same path lands at the old entry's slot,
    # forcing log truncation of the stale uncommitted entry
    def heal_write():
        try:
            return put(new_lead, "/p", "new",
                       timeout=5.0).event.node.value == "new"
        except (TimeoutError, EtcdError):
            return False

    wait_for(heal_write, timeout=30.0, msg="post-heal write")
    wait_for(lambda: all(
        get(s, "/p").event.node.value == "new" for s in servers),
        timeout=30.0, msg="post-heal convergence")
    wait_for(lambda: all(
        get(s, "/maj").event.node.value == "2" for s in servers),
        timeout=30.0, msg="partition-era majority write catch-up")


# -- intra-host mesh sharding (two-tier composition) -----------------------


def test_mesh_sharded_dist_cluster(tmp_path):
    """SURVEY §5.8 composed end to end: each host's [G] group batch
    sharded over the virtual device mesh (intra-slice tier) while
    the cross-host frame exchange replicates between hosts (DCN
    tier).  Groups are mesh-independent, so the engine runs SPMD
    with no cross-device collectives."""
    import jax

    from etcd_tpu.parallel.mesh import group_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device (virtual) mesh")
    mesh = group_mesh()
    if G % mesh.shape["g"]:
        pytest.skip(f"G={G} not divisible by mesh g-axis "
                    f"{mesh.shape['g']} on this device count")
    servers, _ = make_cluster(tmp_path, mesh=mesh)
    try:
        bootstrap_dist_leader(servers)
        # state actually spans the mesh's devices, split on 'g'
        # (replicated over 's', so the set covers the whole mesh)
        sh = servers[0].mr.state.term.sharding
        assert len(sh.device_set) == mesh.size
        assert sh.spec[0] == "g"
        ev = put(servers[0], "/m", "sharded")
        assert ev.event.node.value == "sharded"
        wait_for(lambda: all(
            get(s, "/m").event.node.value == "sharded"
            for s in servers[1:]), msg="replication with sharded state")
        # engine transitions preserve multi-device placement
        assert len(servers[0].mr.state.last.sharding.device_set) > 1
    finally:
        for s in servers:
            try:
                s.stop()
            except Exception:
                pass


def test_append_with_term_change_keeps_wal_contiguous(tmp_path):
    """Chaos-drill regression: a frame carrying BOTH a term change
    and entries (a new leader's first append after failover) must
    write WAL records in seq order — the ballot record is persisted
    immediately inside _persist_ballot, so it must be allocated
    BEFORE the entry records.  Pre-fix the stream went
    [..., ballot(n+k+1), ent(n+1..n+k), ...] and every later restart
    died with 'entry index gap'."""
    from etcd_tpu.wire.distmsg import AppendBatch

    g = 4
    urls = [f"http://127.0.0.1:{p}" for p in free_ports_n(2)]
    s = DistServer(str(tmp_path / "slot0"), slot=0, peer_urls=urls,
                   g=g, cap=64, tick_interval=0.05)
    payload = Request(method="PUT", id=9, path="/x", val="v").marshal()
    term = np.full(g, 5, np.int32)  # far above the fresh server's
    frame = AppendBatch(
        sender=1, term=term,
        prev_idx=np.zeros(g, np.int32),
        prev_term=np.zeros(g, np.int32),
        n_ents=np.ones(g, np.int32),
        commit=np.zeros(g, np.int32),
        active=np.ones(g, bool),
        need_snap=np.zeros(g, bool),
        ent_terms=np.full((g, 1), 5, np.int32),
        payloads=[[payload] for _ in range(g)])
    s.handle_frame(frame.marshal())
    s.wal.close()

    # the on-disk stream must be index-contiguous from 0
    from etcd_tpu.wal import WAL

    w = WAL.open_at_index(str(tmp_path / "slot0" / "wal"), 0)
    _, _, ents = w.read_all()  # raises 'entry index gap' pre-fix
    w.close()
    idxs = [e.index for e in ents]
    assert idxs == list(range(len(idxs)))

    # and a fresh server restarts from the same dir
    s2 = DistServer(str(tmp_path / "slot0"), slot=0, peer_urls=urls,
                    g=g, cap=64, tick_interval=0.05)
    assert (s2.mr.terms() == 5).all()
    s2.wal.close()


def test_do_many_pipelined_batch(cluster):
    """do_many: a whole window of writes in flight at once (pipelined
    acks, VERDICT r3 #5), each committed+applied independently; bad
    lanes report errors in place without failing the batch."""
    servers, _, _ = cluster
    reqs = [Request(method="PUT", id=rid(), path=f"/dm/k{i}",
                    val=f"v{i}") for i in range(40)]
    reqs.append(Request(method="BOGUS", id=rid(), path="/dm/bad"))
    out = servers[0].do_many(reqs, timeout=30.0)
    assert len(out) == 41
    from etcd_tpu.server.server import Response, UnknownMethodError

    assert all(isinstance(x, Response) for x in out[:40])
    assert isinstance(out[40], UnknownMethodError)
    for i in range(40):
        assert get(servers[0], f"/dm/k{i}").event.node.value == f"v{i}"
    # replicated: a follower replica serves the same values
    wait_for(lambda: get(servers[1], "/dm/k39").event.node.value
             == "v39", msg="replication of the batch tail")


def test_propose_many_http_endpoint(cluster):
    """POST /mraft/propose_many (the batch-propose wire form): one
    keep-alive connection ships a window of writes, gets one verdict
    per request, in order."""
    import http.client
    import json as _json

    from etcd_tpu.server.distserver import pack_requests

    servers, ports, _ = cluster
    c = http.client.HTTPConnection("127.0.0.1", ports[0], timeout=30)
    reqs = [Request(method="PUT", id=rid(), path=f"/pm/k{i}", val="x")
            for i in range(16)]
    for _ in range(2):  # two batches on ONE connection (keep-alive)
        c.request("POST", "/mraft/propose_many",
                  body=pack_requests(reqs))
        out = _json.loads(c.getresponse().read().decode())
        assert out["n"] == 16 and out["errs"] == {}
        reqs = [Request(method="PUT", id=rid(), path=f"/pm/k{i}",
                        val="y") for i in range(16)]
    c.close()
    assert get(servers[0], "/pm/k7").event.node.value == "y"


def test_need_snap_lanes_never_persist_phantom_entries(tmp_path):
    """Advisor r3 regression: a need_snap lane acks ok=True (positive
    commit ack, raft.go:418-424 analog) but the engine appends NOTHING
    for it — the persist loop must iterate resp.appended, not resp.ok.
    A (buggy or future) leader shipping entries alongside need_snap
    must not get those entries into this host's WAL: the engine never
    accepted them, and persisting them would diverge WAL from engine
    state on the next restart."""
    from etcd_tpu.wire.distmsg import AppendBatch, unmarshal_any

    g = 4
    urls = [f"http://127.0.0.1:{p}" for p in free_ports_n(2)]
    s = DistServer(str(tmp_path / "slot0"), slot=0, peer_urls=urls,
                   g=g, cap=64, tick_interval=0.05)
    payload = Request(method="PUT", id=9, path="/x", val="v").marshal()
    term = np.full(g, 5, np.int32)
    need = np.array([False, True, False, True])
    frame = AppendBatch(
        sender=1, term=term,
        prev_idx=np.zeros(g, np.int32),
        prev_term=np.zeros(g, np.int32),
        n_ents=np.ones(g, np.int32),  # entries on EVERY lane,
        commit=np.zeros(g, np.int32),  # including need_snap ones
        active=np.ones(g, bool),
        need_snap=need,
        ent_terms=np.full((g, 1), 5, np.int32),
        payloads=[[payload] for _ in range(g)])
    resp = unmarshal_any(s.handle_frame(frame.marshal()))
    # wire-level ok covers the need lanes (positive ack at commit) ...
    assert resp.ok.all()
    s.wal.close()

    # ... but the WAL holds entry records ONLY for the lanes the
    # engine actually appended
    from etcd_tpu.wal import WAL
    from etcd_tpu.wire import GroupEntry

    w = WAL.open_at_index(str(tmp_path / "slot0" / "wal"), 0)
    _, _, ents = w.read_all()
    w.close()
    groups_with_entries = {
        ge.group for ge in (GroupEntry.unmarshal(e.data)
                            for e in ents if e.data)
        if ge.kind == 0 and ge.payload}
    assert groups_with_entries == {0, 2}

    # and the directory restarts cleanly
    s2 = DistServer(str(tmp_path / "slot0"), slot=0, peer_urls=urls,
                    g=g, cap=64, tick_interval=0.05)
    assert (s2.mr.terms() == 5).all()
    s2.wal.close()


def test_leaders_endpoint_traces_elections(cluster):
    """GET /mraft/leaders: the leadership-transition trace the chaos
    drill's kill->writable decomposition reads (VERDICT r4 #3).
    Bootstrap elections and the first post-election apply must be
    stamped with wall times; a host that leads nothing reports its
    (empty) trace without error."""
    import json as _json
    import urllib.request

    servers, ports, _ = cluster
    put(servers[0], "/lt/k", "v")  # ensure a post-election apply

    def fetch(slot):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{ports[slot]}/mraft/leaders",
                timeout=10) as r:
            return _json.loads(r.read())

    # elections can flap under CPU load — poll for the settled view
    # rather than asserting a snapshot (same discipline as the other
    # tests in this file)
    wait_for(lambda: all(fetch(0)["lead"]),
             msg="slot 0 leads every lane")
    d0 = fetch(0)
    assert d0["slot"] == 0
    now = time.time()
    assert all(0 < e <= now for e in d0["elected_at"])
    assert all(t >= 1 for t in d0["elected_term"])
    wait_for(lambda: any(f > 0 for f in fetch(0)["first_apply_at"]),
             msg="first post-election apply stamped")
    d0 = fetch(0)
    for e, f in zip(d0["elected_at"], d0["first_apply_at"]):
        if f:
            assert f >= e, "apply cannot precede the election win"
    # while slot 0 holds every lane, peers lead nothing and say so —
    # guarded on BOTH sides of the peer fetch (a load-induced flap
    # between the guard and the assert must invalidate the check,
    # not fail it)
    lead_before = all(fetch(0)["lead"])
    d1 = fetch(1)
    lead_after = all(fetch(0)["lead"])
    if lead_before and lead_after:
        assert not any(d1["lead"])


# -- PR 6: streamed snapshot install, re-arm, and corruption rejection --------


def test_pull_failure_rearms_need_pull(tmp_path):
    """The satellite wedge fix: an all-donors-fail pull attempt must
    re-arm _need_pull with backoff (and count the attempt), never
    silently drop it."""
    from etcd_tpu.obs.metrics import registry as obs

    ports = free_ports_n(3)
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    srv = DistServer(str(tmp_path / "slot0"), slot=0, peer_urls=urls,
                     g=G, cap=64, tick_interval=0.05,
                     post_timeout=0.3)
    try:
        before = obs.counter("etcd_snap_install_total",
                             outcome="no_donor").get()
        srv._need_pull = True
        import time as _t

        t0 = _t.monotonic()
        srv._pull_snapshot()   # peers were never started: all dead
        assert srv._need_pull          # re-armed, not dropped
        assert srv._pull_not_before > t0
        # the shared Backoff (PR 10) is mid-escalation
        assert srv._pull_backoff.pending
        assert obs.counter("etcd_snap_install_total",
                           outcome="no_donor").get() == before + 1
        # second failure backs off further (exponential: the
        # internal level doubles, jitter only shapes the delay)
        b1 = srv._pull_backoff._cur
        srv._need_pull = False
        srv._pull_snapshot()
        assert srv._pull_backoff._cur == 2 * b1
    finally:
        srv.stop()


def test_streamed_pull_rejects_corrupt_chunk_then_installs(
        tmp_path, monkeypatch):
    """Deep-lag catch-up through the REAL streamed path with an
    injected corrupt chunk: the receiver must reject + refetch the
    chunk (metric proof) and still install + converge — never
    install the corrupted bytes."""
    from etcd_tpu.obs.metrics import registry as obs

    monkeypatch.setenv("ETCD_SNAP_STREAM_CORRUPT_CHUNK", "0")
    monkeypatch.setenv("ETCD_SNAP_CHUNK_BYTES", "2048")
    servers, ports = make_cluster(tmp_path)
    try:
        bootstrap_dist_leader(servers)
        eventually(lambda: put(servers[0], "/base", "x"),
                   msg="a write before the member leaves")
        servers[2].stop()
        for i in range(30):
            eventually(lambda: put(servers[0], f"/s{i}", f"v{i}",
                                   timeout=15.0),
                       msg=f"write {i} under 2 of 3")
        # compact BOTH live peers past every written key: snapshot()
        # compacts to the host's APPLY cursor, so a donor whose apply
        # loop lagged the commit frontier (common under full-suite
        # load) would keep a low offset — and if leadership then
        # flaps to it, it can append-catch-up the rejoined peer from
        # index 1, the install correctly goes `stale`, and the ok>ok0
        # assert below flakes.  Waiting until both applied vectors
        # dominate the write set makes the streamed install the ONLY
        # path the keys can take.
        target = np.maximum(servers[0].applied,
                            servers[1].applied).copy()
        wait_for(lambda: ((servers[0].applied >= target).all()
                          and (servers[1].applied >= target).all()),
                 timeout=120.0, msg="both donors applied the write set")
        servers[0].snapshot()
        servers[1].snapshot()
        rejects0 = obs.counter("etcd_snap_install_total",
                               outcome="chunk_reject").get()
        ok0 = obs.counter("etcd_snap_install_total",
                          outcome="ok").get()
        urls = [f"http://127.0.0.1:{p}" for p in ports]
        # rejoin on a FRESH data dir: a frontier-0 peer sits behind
        # ANY compacted donor's offset on every lane, so the streamed
        # install is the only possible catch-up path.  Rejoining on
        # the old WAL raced plain append catch-up whenever leadership
        # flapped to the donor whose applied lagged at its snapshot()
        # call (lower compaction point) — the ok>ok0 assert then
        # flaked under full-suite load with zero installs recorded.
        # election=60: the rejoining peer must not campaign whenever
        # suite load stalls a heartbeat for a few ticks — its epoch
        # bumps reset the donors' pipes and stack pull attempts into
        # backoff; it has nothing to lead and only needs to vote
        s2 = DistServer(str(tmp_path / "d2b"), slot=2, peer_urls=urls,
                        g=G, cap=64, tick_interval=0.05,
                        post_timeout=5.0, election=60)
        s2.start()
        servers[2] = s2
        # generous window: _arm_pull_retry's backoff base is
        # post_timeout (doubling to a 30s cap), so a few load-induced
        # no_donor attempts (donor probe timeouts) legitimately cost
        # tens of seconds before the install lands
        wait_for(lambda: all(
            get(s2, f"/s{i}").event.node.value == f"v{i}"
            for i in range(30)), timeout=300.0,
            msg="streamed snapshot catch-up past a corrupt chunk")
        outcomes = obs.snapshot()["etcd_snap_install_total"][
            "samples"]
        assert obs.counter("etcd_snap_install_total",
                           outcome="ok").get() > ok0, outcomes
        assert obs.counter("etcd_snap_install_total",
                           outcome="chunk_reject").get() \
            > rejects0, outcomes
    finally:
        for s in servers:
            try:
                s.stop()
            except Exception:
                pass


def test_pull_preprobe_skips_pin_and_meta_failed_counted(tmp_path):
    """Pull-path review hardening: (1) a donor that answers with
    unparseable meta counts the documented meta_failed outcome (it
    is a real failed attempt, not an unreachable donor); (2) the
    cheap frontier pre-probe skips a non-dominating donor WITHOUT
    making it serialize + pin its whole store."""
    from etcd_tpu.obs.metrics import registry as obs

    servers, ports = make_cluster(tmp_path)
    try:
        bootstrap_dist_leader(servers)
        put(servers[0], "/a", "1")

        # (1) garbage meta: pin the probe dominating (a follower's
        # applied can lag the leader's for a moment, which would
        # deterministically-flakily turn this into not_dominating),
        # so the meta parse failure is what's exercised
        import numpy as _np

        mf0 = obs.counter("etcd_snap_install_total",
                          outcome="meta_failed").get()
        for s in (servers[1], servers[2]):
            s.snapshot_stream_meta = lambda: b"}{ not json"
        servers[0]._fetch_snap_frontier = lambda h: _np.full_like(
            servers[0].applied, 2 ** 40)
        servers[0]._pull_snapshot()
        assert obs.counter("etcd_snap_install_total",
                           outcome="meta_failed").get() == mf0 + 2
        # all donors unusable -> no_donor aggregate + backoff re-arm
        assert servers[0]._need_pull

        # (2) non-dominating donors: restore the real meta + probe
        # paths, make the receiver artificially ahead — the real
        # pre-probe must skip every donor with no pin ever created
        # donor-side
        for s in (servers[1], servers[2]):
            del s.snapshot_stream_meta
        del servers[0]._fetch_snap_frontier
        nd0 = obs.counter("etcd_snap_install_total",
                          outcome="not_dominating").get()
        with servers[0].lock:
            servers[0].applied = servers[0].applied + 1_000_000
        servers[0]._need_pull = False
        servers[0]._pull_snapshot()
        assert obs.counter("etcd_snap_install_total",
                           outcome="not_dominating").get() == nd0 + 2
        for s in (servers[1], servers[2]):
            assert not s._snap_sources._pins, "probe must pre-empt pin"
        # snapshot-class miss: NOT re-armed
        assert not servers[0]._need_pull
    finally:
        for s in servers:
            try:
                s.stop()
            except Exception:
                pass


def test_snapshot_bounds_wal_and_snap_dirs(tmp_path):
    """Bounded state: repeated snapshots GC segments and purge old
    snapshots — dirs must not grow with snapshot count."""
    servers, ports, tp = None, None, tmp_path
    servers, ports = make_cluster(tp, snap_keep=2)
    try:
        bootstrap_dist_leader(servers)
        for r in range(4):
            for i in range(6):
                put(servers[0], f"/b{r}/k{i}", f"v{r}.{i}",
                    timeout=15.0)
            servers[0].snapshot()
        waldir = str(tp / "slot0" / "wal")
        snapdir = str(tp / "slot0" / "snap")
        segs = [n for n in os.listdir(waldir) if n.endswith(".wal")]
        snaps = [n for n in os.listdir(snapdir)
                 if n.endswith(".snap")]
        # GC keeps segments back to the OLDEST retained snapshot
        # (~one per kept snapshot + the live post-cut one);
        # retention keeps snap_keep files
        assert len(segs) <= 2 + 2, sorted(segs)
        assert len(snaps) <= 2, sorted(snaps)
        # and the node still restarts cleanly from what survives
        servers[0].stop()
        urls = [f"http://127.0.0.1:{p}" for p in ports]
        s0 = DistServer(str(tp / "slot0"), slot=0, peer_urls=urls,
                        g=G, cap=64, tick_interval=0.05,
                        post_timeout=2.0)
        assert get(s0, "/b3/k5").event.node.value == "v3.5"
        servers[0] = s0
    finally:
        for s in servers:
            try:
                s.stop()
            except Exception:
                pass


def test_crash_between_snapshot_and_gc_restarts_clean(tmp_path):
    """Crash-ordering at the server level: the snapshot saved but
    the process died before gc/cut completed — restart must come up
    from the surviving artifacts (old chain + new snapshot)."""
    servers, ports = make_cluster(tmp_path)
    try:
        bootstrap_dist_leader(servers)
        for i in range(8):
            put(servers[0], f"/c{i}", f"v{i}")
        s0 = servers[0]
        # simulate the crash window: durable snapshot, NO gc/cut
        with s0.lock:
            from etcd_tpu.wire import Snapshot as _Snap

            s0.ss.save_snap(_Snap(data=s0.snapshot_blob(),
                                  index=s0.seq, term=s0.raft_term))
        servers[0].stop()
        urls = [f"http://127.0.0.1:{p}" for p in ports]
        r0 = DistServer(str(tmp_path / "slot0"), slot=0, peer_urls=urls,
                        g=G, cap=64, tick_interval=0.05,
                        post_timeout=2.0)
        for i in range(8):
            assert get(r0, f"/c{i}").event.node.value == f"v{i}"
        servers[0] = r0
    finally:
        for s in servers:
            try:
                s.stop()
            except Exception:
                pass


def test_corrupt_newest_snapshot_still_restarts_after_gc(tmp_path):
    """Review regression (PR 6): segment GC must stop at the OLDEST
    retained snapshot, not the newest — otherwise a corrupt newest
    snapshot leaves load()'s fallback target without WAL coverage
    and the node cannot restart at all despite K-1 good snapshots."""
    servers, ports = make_cluster(tmp_path, snap_keep=3)
    try:
        bootstrap_dist_leader(servers)
        for r in range(3):
            for i in range(5):
                put(servers[0], f"/g{r}/k{i}", f"v{r}.{i}",
                    timeout=15.0)
            servers[0].snapshot()
        servers[0].stop()
        snapdir = str(tmp_path / "slot0" / "snap")
        newest = sorted(n for n in os.listdir(snapdir)
                        if n.endswith(".snap"))[-1]
        fpath = os.path.join(snapdir, newest)
        blob = bytearray(open(fpath, "rb").read())
        blob[-1] ^= 0xFF
        open(fpath, "wb").write(bytes(blob))
        urls = [f"http://127.0.0.1:{p}" for p in ports]
        # restart must fall back to an older kept snapshot AND find
        # the WAL chain covering its index — with newest-index GC
        # this constructor raised 'no wal file covers index'
        r0 = DistServer(str(tmp_path / "slot0"), slot=0, peer_urls=urls,
                        g=G, cap=64, tick_interval=0.05,
                        post_timeout=2.0)
        servers[0] = r0
        # the committed-and-frontier-persisted prefix is readable
        # before start (round 0 predates two snapshots)
        assert get(r0, "/g0/k0").event.node.value == "v0.0"
        # the final write may sit in the acked-but-uncommitted tail
        # (its frontier record can postdate the stop) — it re-commits
        # once the member rejoins its quorum
        r0.start()
        wait_for(lambda: all(
            get(r0, f"/g{r}/k{i}").event.node.value == f"v{r}.{i}"
            for r in range(3) for i in range(5)), timeout=30.0,
            msg="post-fallback rejoin re-commits the tail")
    finally:
        for s in servers:
            try:
                s.stop()
            except Exception:
                pass
