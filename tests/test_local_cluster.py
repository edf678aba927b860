"""``--dist-local-cluster M``: every member slot of a distributed
multi-group cluster hosted by ONE ``cli.main`` (three hosts cut to one
process), on the CPU at 64 groups.  The CLI runs as a child, as it is
deployed; what one client URL cannot show — that the quorum is real —
is shown on members built and started by the functions the CLI builds
and starts them with.  Every wait is on a condition with a limit of
its own."""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import bench_load  # noqa: E402 - the benchmark's generator
import bench_ref  # noqa: E402 - and its plain reference

from conftest import free_ports  # noqa: E402
from etcd_tpu import cli  # noqa: E402
from etcd_tpu.wire.requests import Request  # noqa: E402

G = 64
RECORDS = 48
SEED = 2_200_000_777
START_LIMIT_S = 180.0
STOP_LIMIT_S = 60.0
LISTENING = "Listening for client requests on "
SERVES = "serves clients on "


def wait_for(cond, limit: float, what: str):
    deadline = time.monotonic() + limit
    while time.monotonic() < deadline:
        got = cond()
        if got:
            return got
        time.sleep(0.05)
    raise AssertionError(f"{what}: not within {limit:.0f}s")


class Cluster:
    """One ``python -m etcd_tpu.cli --dist-local-cluster 3`` child."""

    def __init__(self, data_dir: str, log_path: str, port: int):
        url = f"http://127.0.0.1:{port}"
        self.log_path = log_path
        self._log = open(log_path, "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "etcd_tpu.cli",
             "--dist-local-cluster", "3", "--cohosted-groups", str(G),
             "--storage-backend", "tpu", "--name", "lc",
             "--data-dir", data_dir, "--listen-client-urls", url,
             "--advertise-client-urls", url],
            cwd=REPO, stderr=self._log, stdout=self._log,
            env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))

    def log_lines(self) -> list[str]:
        with open(self.log_path, errors="replace") as f:
            return f.read().splitlines()

    def wait_listening(self) -> list[int]:
        """The three members' client ports, slot-indexed, once the
        line that says the cluster serves is there."""
        def listening():
            assert self.proc.poll() is None, "\n".join(
                self.log_lines()[-30:])
            return [l for l in self.log_lines() if LISTENING in l]

        line = wait_for(listening, START_LIMIT_S, "Listening line")[-1]
        ports = {0: int(line.split(LISTENING)[1].split()[0]
                        .rsplit(":", 1)[1])}
        for l in self.log_lines():
            if SERVES in l:
                slot = int(l.split("dist slot ")[1].split("/")[0])
                ports[slot] = int(l.rsplit(":", 1)[1])
        assert sorted(ports) == [0, 1, 2], ports
        return [ports[i] for i in range(3)]

    def stop(self) -> int:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=STOP_LIMIT_S)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                raise AssertionError("SIGTERM did not stop the members")
        self._log.close()
        return self.proc.returncode


def wal_puts(slot_dir: str) -> set[tuple[str, str]]:
    """``(path, value)`` of every PUT entry in a member's WAL."""
    from etcd_tpu.server.distserver import K_ENTRY
    from etcd_tpu.wal import WAL
    from etcd_tpu.wire import GroupEntry

    w = WAL.open_at_index(os.path.join(slot_dir, "wal"), 0)
    try:
        _, _, ents = w.read_all()
    finally:
        w.close()
    out = set()
    for e in ents:
        ge = GroupEntry.unmarshal(e.data)
        if ge.kind == K_ENTRY and ge.payload:
            r = Request.unmarshal(ge.payload)
            if r.method == "PUT":
                out.add((r.path, r.val))
    return out


def read_back(plan, hist, port: int, phase: str) -> list:
    return bench_load.run_phase(
        plan, hist, "127.0.0.1", port, phase=phase, clients=4,
        work=[("get", r) for r in range(plan.records)
              if hist.writes[r]])


def test_cli_serves_three_members_with_three_wals_and_restarts(tmp_path):
    with open(os.path.join(BENCH, "traffic", "put-c16.json")) as f:
        traffic = json.load(f)
    traffic.update(clients=4, records=RECORDS)
    plan = bench_load.Plan(traffic, SEED)
    hist = bench_load.History(plan.records)
    data = str(tmp_path / "data")
    port = free_ports(1)[0]
    cluster = Cluster(data, str(tmp_path / "run1.log"), port)
    try:
        ports = cluster.wait_listening()
        assert ports[0] == port
        # the line came after every group had a leader: one PUT to
        # each record, the first the cluster sees, and none fails.
        # (That none waited for an election is the order of the log's
        # lines below: a count of re-sends would also count a first
        # compilation on a loaded machine.)
        first = bench_load.run_phase(
            plan, hist, "127.0.0.1", port, phase="first", clients=4,
            work=[("put", r) for r in range(plan.records)])
        assert [op.outcome for op in first] == ["ack"] * RECORDS
        lines = cluster.log_lines()
        led = next(i for i, l in enumerate(lines)
                   if f"of {G} groups" in l)
        assert led < next(i for i, l in enumerate(lines)
                          if LISTENING in l)
        # 200 seeded PUTs of the generator's values through slot 0
        puts = bench_load.run_phase(plan, hist, "127.0.0.1", port,
                                    phase="warmup", clients=4, ops=200)
        assert len(puts) == 200
        assert all(op.outcome == "ack" for op in puts)
        # ... read back with the default GET from EACH member's URL
        for slot, p in enumerate(ports):
            got = read_back(plan, hist, p, "readback")
            assert got and all(op.outcome == "ack" for op in got), slot
        compared = bench_ref.compare(hist.ops)
        assert bench_ref.is_correct(compared), compared
        assert cluster.stop() == 0
    finally:
        cluster.stop()
    # each member's own directory holds a WAL that carries every
    # acknowledged entry (each member answered the read-backs, so
    # each had applied, and so persisted, them all)
    acked = {("/" + plan.key(op.rank).strip("/"),
              plan.value(op.rank, op.seq))
             for op in hist.ops if op.kind == "put"}
    assert len(acked) == RECORDS + 200
    assert sorted(os.listdir(data)) == ["slot0", "slot1", "slot2"]
    for slot in range(3):
        missing = acked - wal_puts(os.path.join(data, f"slot{slot}"))
        assert not missing, (slot, len(missing), sorted(missing)[:3])
    # the same flags on the same directory: everything reads back
    # from every member, and the cluster takes writes again
    again = Cluster(data, str(tmp_path / "run2.log"), port)
    try:
        ports = again.wait_listening()
        for slot, p in enumerate(ports):
            got = read_back(plan, hist, p, "readback")
            assert got and all(op.outcome == "ack" for op in got), slot
        more = bench_load.run_phase(plan, hist, "127.0.0.1", port,
                                    phase="warmup", clients=4, ops=40)
        assert all(op.outcome == "ack" for op in more)
        compared = bench_ref.compare(hist.ops)
        assert bench_ref.is_correct(compared), compared
        assert again.stop() == 0
    finally:
        again.stop()


@pytest.mark.parametrize("lease_ticks", [30, 0],
                         ids=["lease", "lease-off"])
def test_the_quorum_is_real(tmp_path, lease_ticks):
    """Members built and started by the CLI's own functions: with one
    follower stopped a write is still acknowledged and a default GET
    still answered, with both stopped no write is, whatever the
    leader holds itself — and with the lease off (``--dist-lease-ticks
    0``) no read either: it times out, it is not served."""
    from etcd_tpu.server.server import gen_id

    g = 8
    servers = cli.local_dist_members(
        str(tmp_path), 3, name="q", g=g, cap=64,
        election=60, lease_ticks=lease_ticks, storage_backend="tpu")
    assert [os.path.basename(s.data_dir) for s in servers] == [
        "slot0", "slot1", "slot2"]
    stopped = []

    def put(i: int, timeout: float):
        return servers[0].do(Request(
            method="PUT", id=gen_id(), path=f"/t{i}/cfg", val=f"v{i}"),
            timeout=timeout)

    def get(i: int, timeout: float):
        return servers[0].do(Request(
            method="GET", id=gen_id(), path=f"/t{i}/cfg"),
            timeout=timeout).event.node.value

    try:
        cli.start_dist_members(servers)
        wait_for(lambda: cli.dist_groups_led(servers) == g, 60.0,
                 "every group led")
        wait_for(lambda: np.asarray(servers[0].mr.is_leader()).all(),
                 30.0, "slot 0, which campaigned, leads every group")
        for i in range(g):
            assert put(i, 5.0).event.node.value == f"v{i}"
        assert servers[2].stop()
        stopped.append(servers[2])
        for i in range(g, 2 * g):     # 2 of 3 copies: acknowledged
            assert put(i, 5.0).event.node.value == f"v{i}"
            assert get(i, 5.0) == f"v{i}"   # ... and confirmed
        assert servers[1].stop()
        stopped.append(servers[1])
        if not lease_ticks:
            # no quorum answers and there is no lease: fail closed
            with pytest.raises(TimeoutError):
                get(0, 1.0)
        for i in range(2 * g, 2 * g + 3):
            with pytest.raises(TimeoutError):
                put(i, 0.5)           # the server's request timeout
        # ... and the leader never applied what no quorum holds
        for i in range(2 * g, 2 * g + 3):
            with pytest.raises(Exception):
                servers[0].store.get(f"/t{i}/cfg", False, False)
    finally:
        for s in servers:
            if s not in stopped:
                s.stop()


@pytest.mark.parametrize("argv,why", [
    (["--dist-local-cluster", "3", "--dist-slot", "0"], "--dist-slot"),
    (["--dist-local-cluster", "3", "--dist-peers",
      "http://127.0.0.1:1,http://127.0.0.1:2"], "--dist-peers"),
    (["--dist-local-cluster", "1"], "at least 2"),
    (["--dist-local-cluster", "3", "--dist-election-ticks", "2"],
     "--dist-election-ticks"),
])
def test_flag_refuses_the_mix_and_the_senseless(argv, why, caplog,
                                                tmp_path):
    with caplog.at_level("ERROR", logger="etcd_tpu.cli"):
        assert cli.main(argv + ["--data-dir", str(tmp_path / "d")]) == 1
    assert why in caplog.text
    assert not os.path.exists(tmp_path / "d")


@pytest.mark.parametrize("argv", [
    ["--dist-local-cluster", "3", "--dist-roles", "2"],
    ["--dist-slot", "0", "--dist-peers",
     "http://127.0.0.1:1,http://127.0.0.1:2,http://127.0.0.1:3",
     "--dist-roles", "2"],
], ids=["local-cluster", "one-slot"])
def test_the_parser_knows_no_role_split(argv, capsys, tmp_path):
    """One member is one process: a flag that asks for a process
    tree a slot is refused where every unknown flag is."""
    with pytest.raises(SystemExit) as e:
        cli.main(argv + ["--data-dir", str(tmp_path / "d")])
    assert e.value.code == 2
    assert "--dist-roles" in capsys.readouterr().err
    assert not os.path.exists(tmp_path / "d")


def test_the_one_slot_form_still_parses_as_before():
    p = cli.build_parser()
    old = p.parse_args(["--dist-slot", "1", "--dist-peers",
                        "http://h0:7700,http://h1:7700,http://h2:7700",
                        "--cohosted-groups", "1024"])
    assert (old.dist_slot, old.dist_local_cluster) == (1, 0)
    assert old.dist_peers.count(",") == 2
    new = p.parse_args(["--dist-local-cluster", "3"])
    assert (new.dist_slot, new.dist_peers, new.dist_local_cluster) == (
        -1, "", 3)
    # none of start_dist's defaults moved
    for a in (old, new):
        assert (a.dist_pipeline_depth, a.dist_coalesce_us,
                a.dist_election_ticks, a.dist_lease_ticks) == (
            8, 2000, 60, 30)


def test_the_one_slot_form_takes_the_same_way_down(tmp_path):
    """``--dist-slot`` (one member of three, its peers absent) stops
    as the local cluster does: SIGTERM dumps the member's flight ring
    beside its data, stops the member, exit code 0."""
    ports = free_ports(4)
    peers = ",".join(f"http://127.0.0.1:{p}" for p in ports[:3])
    url = f"http://127.0.0.1:{ports[3]}"
    data = str(tmp_path / "d0")
    log_path = str(tmp_path / "slot.log")
    with open(log_path, "ab") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "etcd_tpu.cli", "--dist-slot", "0",
             "--dist-peers", peers, "--cohosted-groups", "8",
             "--name", "one", "--data-dir", data,
             "--listen-client-urls", url, "--advertise-client-urls", url],
            cwd=REPO, stderr=log, stdout=log,
            env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))
        try:
            def listening():
                assert proc.poll() is None
                with open(log_path, errors="replace") as f:
                    return LISTENING in f.read()

            wait_for(listening, START_LIMIT_S, "Listening line")
            proc.send_signal(signal.SIGTERM)
            assert proc.wait(timeout=STOP_LIMIT_S) == 0
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    dumps = os.listdir(os.path.join(data, "trace_artifacts"))
    assert any("sigterm" in name for name in dumps), dumps


def test_a_cluster_that_elects_no_leader_says_so_and_exits(
        tmp_path, monkeypatch, caplog):
    """The wait for every group's leader has a limit: past it the
    process names the leaderless groups, stops its members and exits
    1; the line that says it serves never comes."""
    monkeypatch.setattr(cli, "LOCAL_LEADERS_LIMIT_S", 0.0)
    monkeypatch.setattr(cli, "dist_groups_led", lambda servers: 5)
    # what start_dist installs for the life of its process
    import threading
    before = signal.getsignal(signal.SIGTERM)
    monkeypatch.setattr(sys, "excepthook", sys.excepthook)
    monkeypatch.setattr(threading, "excepthook", threading.excepthook)
    url = f"http://127.0.0.1:{free_ports(1)[0]}"
    try:
        with caplog.at_level("INFO", logger="etcd_tpu.cli"):
            rc = cli.main(["--dist-local-cluster", "3",
                           "--cohosted-groups", "8", "--name", "nl",
                           "--data-dir", str(tmp_path / "d"),
                           "--listen-client-urls", url])
    finally:
        signal.signal(signal.SIGTERM, before)
    assert rc == 1
    assert "3 of 8 groups have no leader" in caplog.text
    assert LISTENING not in caplog.text
