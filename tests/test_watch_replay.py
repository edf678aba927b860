"""The watch side of "nothing is applied twice": after a restart on
the same data directory, ``GET ?wait=true&waitIndex=i`` walked over a
tenant's history answers what the scalar tier (``EtcdServer``, the
plain reference) answers for the same writes and the same restart:
each event once, in index order, with the acknowledged value, or the
reference's typed error where its history no longer holds the index.

The walk starts at index 1 and follows the answers, not at the first
write's own index: the [G] tiers number events in the order a replay
applies them, group by group, so an index taken before the restart
names another event after it (PERF.md section 7)."""

from __future__ import annotations

import time

import pytest

from etcd_tpu.server.server import gen_id
from etcd_tpu.utils.errors import EtcdError
from etcd_tpu.wire.requests import Request

N = 40
SNAP_TAIL = 16        # the restart loads a snapshot, replays a tail
SNAP_NEVER = 10000    # the restart replays every write
G = 64
TENANT = "/t7"


def write_all(server) -> None:
    """N acknowledged writes of one tenant: PUTs, most of them
    overwrites, and a DELETE every tenth."""
    for i in range(N):
        key = f"{TENANT}/k{i % 7}"
        if i % 10 == 9:
            r = Request(id=gen_id(), method="DELETE", path=key)
        else:
            r = Request(id=gen_id(), method="PUT", path=key,
                        val=f"v{i}")
        server.do(r, timeout=90)


def walk(server) -> list[tuple]:
    """What a watcher of the tenant is told from index 1 on, each
    wait asking for the index after the last answer; after the last
    write there must be nothing left to tell."""
    told, since = [], 1
    for _ in range(N):
        try:
            w = server.do(Request(id=gen_id(), method="GET",
                                  path=TENANT, recursive=True,
                                  wait=True, since=since)).watcher
        except EtcdError as e:
            told.append(("error", e.error_code))
            since += 1
            continue
        ev = w.next_event(timeout=30)
        assert ev is not None, f"no event from index {since}"
        w.remove()
        assert ev.index() >= since
        told.append((ev.action, ev.node.key, ev.node.value))
        since = ev.index() + 1
    w = server.do(Request(id=gen_id(), method="GET", path=TENANT,
                          recursive=True, wait=True,
                          since=since)).watcher
    assert w.next_event(timeout=0.3) is None, "an event past the last"
    w.remove()
    return told


def wait_applied(server, timeout: float = 60.0) -> None:
    """The restarted member has applied the last acknowledged write
    (a follower learns the commit index from its leader)."""
    last = N - 2     # the last PUT; N - 1 deletes another key
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            got = server.store.get(f"{TENANT}/k{last % 7}", False,
                                   False).node.value
            if got == f"v{last}":
                return
        except EtcdError:
            pass
        time.sleep(0.05)
    raise AssertionError("the restarted member never caught up")


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    """``reference(snap_count)``: what the scalar tier tells a
    watcher after the writes and a restart under that snapshot
    cadence (run once a cadence)."""
    from etcd_tpu.server.cluster import Cluster
    from etcd_tpu.server.config import ServerConfig
    from etcd_tpu.server.server import new_server

    def start(data, snap_count):
        cluster = Cluster()
        cluster.set_from_string("solo=http://127.0.0.1:7001")
        s = new_server(ServerConfig(
            name="solo", data_dir=data, cluster=cluster,
            snap_count=snap_count,
            client_urls=["http://127.0.0.1:4001"]))
        s.tick_interval = 0.01
        s._start()
        return s

    told: dict[int, list[tuple]] = {}

    def run(snap_count: int) -> list[tuple]:
        if snap_count in told:
            return told[snap_count]
        data = str(tmp_path_factory.mktemp("watch_replay_ref"))
        s = start(data, snap_count)
        try:
            write_all(s)
        finally:
            s.stop()
        s = start(data, snap_count)
        try:
            wait_applied(s)
            got = walk(s)
        finally:
            s.stop()
        # the reference itself tells each write once, in order
        assert [t[2] for t in got if t[0] == "set"] == [
            f"v{i}" for i in range(N) if i % 10 != 9]
        assert [t[0] for t in got].count("delete") == N // 10
        told[snap_count] = got
        return got

    return run


@pytest.mark.parametrize("snap_count", [SNAP_TAIL, SNAP_NEVER])
def test_cohosted_restart_tells_a_watcher_what_the_reference_does(
        tmp_path, reference, snap_count):
    from etcd_tpu.server.multigroup import MultiGroupServer

    def start():
        s = MultiGroupServer(str(tmp_path / "d"), g=G, m=5, cap=64,
                             snap_count=snap_count,
                             storage_backend="tpu")
        s.start()
        return s

    s = start()
    try:
        write_all(s)
    finally:
        s.stop()
    s = start()
    try:
        wait_applied(s)
        assert walk(s) == reference(snap_count)
    finally:
        s.stop()


def test_local_cluster_restart_tells_a_watcher_what_the_reference_does(
        tmp_path, reference):
    """Every member of the three, each from its own WAL.  Without a
    snapshot in the restart: a ``DistServer`` snapshot taken while an
    entry is persisted and not yet applied records a WAL position
    past that entry, and the restart then never applies it (PERF.md
    section 7, ROADMAP R1)."""
    from conftest import bootstrap_dist_leader, make_dist_cluster

    servers, ports = make_dist_cluster(tmp_path, m=3, g=G,
                                       snap_count=SNAP_NEVER)
    try:
        bootstrap_dist_leader(servers)
        write_all(servers[0])
    finally:
        for s in servers:
            assert s.stop()
    servers, _ = make_dist_cluster(tmp_path, m=3, g=G, ports=ports,
                                   snap_count=SNAP_NEVER)
    try:
        bootstrap_dist_leader(servers)
        for s in servers:
            wait_applied(s)
            assert walk(s) == reference(SNAP_NEVER), s.slot
    finally:
        for s in servers:
            s.stop()
