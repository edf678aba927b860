"""The per-layer metrics that read the program's stages and waits
(PR 27), each read by the benchmark's own reader from the registry
snapshots of a rehearsal on the CPU: the launcher as ``run.py`` starts
it, 64 groups, PUTs and GETs over HTTP, a restart on the same data
directory.  A number from here is never a device metric: the test
only holds that each entry finds its span and gets a number."""

import http.client
import json
import os
import sys
import time

import pytest

from bench_fixtures import run_cell, tiny_copy  # noqa: F401 - fixtures

import bench_reduce
import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    B = json.load(_f)

#: the per-layer metrics the first benchmark (PR 26) brought
ACCEPTED = {
    "client_resends_per_kop", "write_p99_ms", "frontdoor_sheds",
    "entries_per_round", "engine_round_ms", "device_busy_ms_per_round",
    "persist_ms", "wal_bytes_per_write", "apply_ms", "update_p95_ms",
    "restart_to_serving_s", "restart_exit_and_init_s", "restart_listen_s",
    "replay_route_stream", "engine_roofline", "crc_roofline",
    "device_idle_share", "device_idle_share.restart"}
NEW = [m for m in B["per_layer"] if m["name"] not in ACCEPTED]


def spec_of(metric: dict) -> dict:
    with open(os.path.join(BENCH, "layer_metrics",
                           metric["name"] + ".json")) as f:
        spec = json.load(f)
    spec["name"] = metric["name"]
    return spec


def request(conn, method: str, path: str, body: str | None = None):
    conn.request(method, path, body=body, headers={
        "Content-Type": "application/x-www-form-urlencoded"})
    r = conn.getresponse()
    return r.status, r.read()


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """``{"window": (before, after), "trace": (None, restarted)}`` as
    ``run.py`` fills ``ctx["registry"]``: the registry of a serving
    launcher around 40 PUTs and GETs, and of a second launcher on the
    same data directory at its first acknowledgement."""
    work = tmp_path_factory.mktemp("spanmetrics")
    port = bench_run.free_port()
    url = f"http://127.0.0.1:{port}"
    cmd = [sys.executable, os.path.join(BENCH, "bench_launcher.py"),
           "--allow-cpu", "--", "--cohosted-groups", "64",
           "--cohosted-members", "5", "--storage-backend", "tpu",
           "--name", "bench", "--data-dir", str(work / "data"),
           "--listen-client-urls", url, "--advertise-client-urls", url]
    servers = []

    def start() -> bench_run.Server:
        srv = bench_run.Server(cmd, str(work / f"s{len(servers)}.log"))
        servers.append(srv)
        srv.wait_listening()
        return srv

    def put(conn, i: int) -> None:
        status, _ = request(conn, "PUT", f"/v2/keys/t{i % 5}/k{i}",
                            f"value=v{i}")
        assert status in (200, 201), status

    try:
        srv = start()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        put(conn, 0)                   # compiles the round
        before = srv.ask("stats")["registry"]
        for i in range(1, 41):
            put(conn, i)
            assert request(conn, "GET",
                           f"/v2/keys/t{i % 5}/k{i}")[0] == 200
        time.sleep(0.35)               # nothing queued: heartbeats
        after = srv.ask("stats")["registry"]
        conn.close()
        srv.stop()
        srv2 = start()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        put(conn, 99)                  # the first acknowledgement
        conn.close()
        restarted = srv2.ask("stats")["registry"]
        yield {"window": (before, after), "trace": (None, restarted)}
    finally:
        for s in servers:
            s.stop()


def test_this_pr_adds_only_entries_at_the_end():
    names = [m["name"] for m in B["per_layer"]]
    assert set(names[:len(ACCEPTED)]) == ACCEPTED
    assert len(NEW) >= 21


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_new_metric_reads_a_number_from_a_rehearsal(rehearsal, metric):
    spec = spec_of(metric)
    if spec["kind"] == "trace":
        pytest.skip(f"{metric['name']} reads the device trace: no "
                    f"number of it ever comes from a CPU")
    assert metric["source"] == "program_span"
    assert spec["numerator"]["family"] == "etcd_stage_seconds"
    assert spec["numerator"]["labels"]["kind"] == "wall"
    value = bench_reduce.read_metric(spec, {"registry": rehearsal})
    assert isinstance(value, float) and value > 0.0, (spec, value)
    # what the parent, which has no such stage, gives: nothing, never
    # 0 and never an exception
    bare = {"etcd_stage_seconds": {"samples": []}}
    assert bench_reduce.read_metric(
        spec, {"registry": {"window": (bare, bare),
                            "trace": (None, bare)}}) is None
    assert bench_reduce.read_metric(spec, {"registry": {}}) is None


def test_round_parts_sum_to_the_round_in_a_rehearsal(rehearsal):
    ctx = {"registry": rehearsal}
    by_name = {m["name"]: spec_of(m) for m in B["per_layer"]}
    parts = sum(bench_reduce.read_metric(by_name[n], ctx)
                for n in ("round_dispatch_ms", "round_wait_ms",
                          "round_fetch_ms"))
    whole = bench_reduce.read_metric(by_name["engine_round_ms"], ctx)
    # the parts' means count idle heartbeat rounds too (they are taken
    # in MultiRaft.propose), so they may pass the served round's mean;
    # tests/test_stage_coverage.py holds the exact tiling, with no tick
    assert 0.8 * whole <= parts <= 1.25 * whole
    children = sum(bench_reduce.read_metric(by_name[n], ctx)
                   for n in ("pack_ms", "engine_round_ms",
                             "frontier_fetch_ms", "assign_ms",
                             "persist_ms", "apply_ms",
                             "mark_applied_ms"))
    assert children <= bench_reduce.read_metric(by_name["engine_pass_ms"],
                                                ctx)


def test_cpu_rehearsal_names_every_span_metric_of_the_cell(run_cell):
    """A whole run of ``run.py --rehearse-cpu --trace 1``: the last
    line names the cell's per-layer metrics old and new, less every
    one that comes from the device trace.  (Takes the place of
    ``test_cpu_rehearsal_refuses_to_name_a_device_metric``, whose
    exact set dates from before this PR's entries and whose file is
    the benchmark's, not this PR's to edit.)"""
    rc, line, err = run_cell("tiny-put-c4", "--rehearse-cpu", trace=1,
                             seconds=3.0)
    assert rc == 0, err[-3000:]
    out = json.loads(line)
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    assert "busy_s" not in out["device"] and "breakdown" not in out
    want = {m["name"] for m in B["per_layer"]
            if "tenants10k-put-c16" in m["workloads"]
            and spec_of(m)["kind"] != "trace"} | {"fsync_ms"}
    assert want == set(out["metrics"])
    assert {m["name"] for m in NEW
            if "tenants10k-put-c16" in m["workloads"]
            and spec_of(m)["kind"] != "trace"} <= want
    assert all(isinstance(m["value"], float)
               for m in out["metrics"].values())
    assert out["window"]["lowerings"] == 0
