"""The ``dist`` tier's ``self.lock`` in the program's spans (PR 40): a
three-member cluster built and started by the CLI's own functions on
the CPU at 64 groups, each member's lock filing into a tracer of its
own, under concurrent writes and default GETs at the leader.  The
leader files every stage of the lock but a frame's; a follower, whose
round thread leads nothing, files its peer handler's frames alone."""

from __future__ import annotations

import threading
import time

import numpy as np

from etcd_tpu import cli
from etcd_tpu.server.server import gen_id
from etcd_tpu.utils.trace import Tracer
from etcd_tpu.wire.requests import Request

G = 64
JOIN_S = 60.0

LEADER = {"dist.lock_wait.round", "dist.lock_hold.round",
          "dist.lock_wait.ack", "dist.lock_hold.ack",
          "dist.lock_hold.read", "dist.lock_handoff"}
FOLLOWER = {"dist.lock_wait.frame", "dist.lock_hold.frame"}


def wait_for(cond, limit: float, what: str):
    deadline = time.monotonic() + limit
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise AssertionError(f"{what}: not within {limit:.0f}s")


def filed(tr: Tracer) -> dict[str, int]:
    return {stage: h.ring_stats()[0] for (stage, kind), h in
            tr._reg.family("etcd_stage_seconds").children()
            if kind == "wall"}


def test_leader_files_every_lock_stage_and_followers_their_frames(
        tmp_path):
    servers = cli.local_dist_members(
        str(tmp_path), 3, name="lk", g=G, cap=64, election=60,
        storage_backend="tpu")
    tracers = [Tracer() for _ in servers]
    for s, tr in zip(servers, tracers):
        s.lock._tracer = tr           # one member's records apart
    leader = servers[0]
    errors: list[BaseException] = []

    def client(c: int) -> None:
        try:
            for i in range(12):
                path = f"/t{(c * 12 + i) % G}/cfg"
                leader.do(Request(method="PUT", id=gen_id(), path=path,
                                  val=f"v{c}-{i}"), timeout=10.0)
                got = leader.do(Request(method="GET", id=gen_id(),
                                        path=path), timeout=10.0)
                assert got.event.node.value == f"v{c}-{i}"
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    try:
        cli.start_dist_members(servers)
        wait_for(lambda: cli.dist_groups_led(servers) == G, 90.0,
                 "every group led")
        wait_for(lambda: np.asarray(leader.mr.is_leader()).all(), 30.0,
                 "slot 0 leads every group")
        for tr in tracers:
            tr.reset()                # the election's frames are history
        clients = [threading.Thread(target=client, args=(c,))
                   for c in range(6)]
        for t in clients:
            t.start()
        # a holder without a role: the round thread and the readers of
        # acknowledgements that come meanwhile take the lock contended
        time.sleep(0.3)
        with leader.lock:
            time.sleep(0.3)
        for t in clients:
            t.join(JOIN_S)
        assert all(not t.is_alive() for t in clients)
        assert not errors, errors
    finally:
        for s in servers:
            s.stop()
    got = [filed(tr) for tr in tracers]
    assert set(got[0]) == LEADER, set(got[0]) ^ LEADER
    for f in got[1:]:
        assert set(f) == FOLLOWER, set(f) ^ FOLLOWER
        assert f["dist.lock_hold.frame"] > 0
        tied(f, "frame")
    lead = got[0]
    assert lead["dist.lock_hold.read"] >= 6 * 12
    tied(lead, "round")
    tied(lead, "ack")               # every acknowledgement, zeros too


def tied(f: dict[str, int], role: str) -> None:
    """A wait a hold: one apart at most, a hold that was open when
    the tracers were reset."""
    assert abs(f[f"dist.lock_wait.{role}"]
               - f[f"dist.lock_hold.{role}"]) <= 1, f
