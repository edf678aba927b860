"""The cell ``cluster3-geo-put-c16`` at a size the CPU can hold: a
copy of the benchmark made the way ``test_cluster3_readindex_cells.py``
makes its own, to which a 64-group configuration with
``cluster3-geo``'s own link delays, a 4-caller PUT traffic and a tiny
cell were ADDED, and whole runs of ``run.py --rehearse-cpu`` in it
(the launcher passes the configuration's flags to ``cli.main`` and
nothing else).  Every run is a child with a time limit of its own.  A
number from here is never a device metric: the tests hold that the
cell comes out correct with no failed operation, that every entry of
the layer ``dist links`` gets a number from ITS OWN cell's rehearsal
and says what the mechanism did, that the control comes out not
correct, and that the reader of EVERY per-layer entry the cell is
listed under gives nothing, and raises nothing, on a program without
the span or counter (the parent commit)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_fixtures
from bench_fixtures import BENCH, ROOT

CELL = "cluster3-geo-put-c16"
TWIN = "cluster3-put-c16"
TINY = "tiny-cluster3-geo-put-c4"
RUN_LIMIT_S = 300

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    B = json.load(_f)

#: the entries this PR brought, found by their layer, and every entry
#: the cell is listed under (its own and the accepted ones it joined)
OWN = [m for m in B["per_layer"] if m["layer"] == "dist links"]
LISTED = [m for m in B["per_layer"] if CELL in m.get("workloads", [])]


def spec_of(name: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return dict(json.load(f), name=name)


@pytest.fixture(scope="module")
def geo_copy(tmp_path_factory):
    """``BENCHMARK.json`` and ``benchmark/`` copied, with the
    configuration ``geo64`` (``cluster3-geo.json`` at 64 groups, no
    warm-up override), the traffic ``put-c4`` and the tiny cell added
    as files and entries; the tiny cell reports what the full-size
    cell reports.  No file that was there is edited."""
    dst = str(tmp_path_factory.mktemp("geocopy"))
    shutil.copytree(BENCH, os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = bench_fixtures._hashes(os.path.join(dst, "benchmark"))
    bench = json.loads(json.dumps(B))

    def write(rel: str, obj: dict) -> None:
        with open(os.path.join(dst, "benchmark", rel), "x") as f:
            json.dump(obj, f)

    with open(os.path.join(BENCH, "configs", "cluster3-geo.json")) as f:
        cfg = json.load(f)
    del cfg["setup_overrides"]         # no snapshot is due at this size
    cfg["name"] = "geo64"
    cfg["facts"] = {"groups": 64, "members": 3}
    cfg["flags"] = [x if x != "1024" else "64" for x in cfg["flags"]]
    assert "--dist-local-link-delay-ms" in cfg["flags"]
    write("configs/geo64.json", cfg)
    with open(os.path.join(BENCH, "traffic", "put-c16.json")) as f:
        mix = json.load(f)
    mix.update(clients=4, records=200,
               setup=[{"name": "warmup", "clients": 4, "ops": 100}])
    write("traffic/put-c4.json", mix)
    bench["configs"].append({
        "name": "geo64", "source": "a test's own: geo64",
        "file": "benchmark/configs/geo64.json",
        "reduced": sorted(cfg["reduced"]),
        "why": "64 groups x 3 members a stated distance apart: a size "
               "the CPU can hold"})
    bench["workloads"].append({
        "name": TINY, "config": "geo64", "traffic": "put-c4",
        "chips": 1, "why": "a test's cell"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append(TINY)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = bench_fixtures._hashes(os.path.join(dst, "benchmark"))
    assert {k: after[k] for k in before} == before
    return dst


def run(copy: str, *flags: str, seconds: float = 3.0, trace: int = 0,
        seed: int = 2_200_000_381):
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
               BENCH_RUN="ignored")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", TINY,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace), *flags], cwd=copy, env=env, capture_output=True,
        text=True, timeout=RUN_LIMIT_S)
    lines = p.stdout.strip().splitlines()
    assert p.returncode == 0 and lines, p.stderr[-3000:]
    return json.loads(lines[-1]), p.stderr


@pytest.fixture(scope="module")
def traced(geo_copy) -> dict:
    """The one ``--trace 1`` rehearsal the per-layer tests share."""
    out, err = run(geo_copy, "--rehearse-cpu", trace=1, seconds=5.0)
    assert out["correct"] is True, err[-3000:]
    return out


def test_this_pr_brought_one_configuration_one_cell_seven_entries():
    cell = next(w for w in B["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "cluster3-geo", "put-c16", 1)
    assert B["workloads"][-1] == cell and len(cell["why"]) <= 200
    entry = next(c for c in B["configs"] if c["name"] == "cluster3-geo")
    assert B["configs"][-1] == entry and len(entry["source"]) <= 200
    with open(os.path.join(ROOT, entry["file"])) as f:
        new = json.load(f)
    with open(os.path.join(BENCH, "configs", "cluster3.json")) as f:
        old = json.load(f)
    assert new["source"] == entry["source"]
    # cluster3 plus ONE flag: the three one-way delays
    at = new["flags"].index("--dist-local-link-delay-ms")
    assert new["flags"][at + 1] == "0-1:10,0-2:100,1-2:100"
    assert new["flags"][:at] + new["flags"][at + 2:] == old["flags"]
    assert new["assumed"]["link_one_way_ms"] == {
        "0-1": 10, "0-2": 100, "1-2": 100}
    assert new["facts"] == old["facts"] == {"groups": 1024, "members": 3}
    # cluster3's guarantees word for word, and what is new
    for key in ("write", "read", "server_request_timeout_s",
                "client_deadline_s"):
        assert new["guarantees"][key] == old["guarantees"][key], key
    assert "after the clients stop all three stores are equal" in \
        new["guarantees"]["placement"]
    for key, val in old["assumed"].items():
        assert new["assumed"][key] == val, key
    # upstream's rule for the far link, in seconds, at the defaults
    a = new["assumed"]
    assert a["election_timeout_s"] >= 10 * 2 * 0.100
    assert (a["heartbeat_s"], a["election_timeout_s"], a["lease_s"]) == (
        0.1, 6.0, 3.0)
    assert sorted(new["reduced"]) == sorted(entry["reduced"]) == [
        "hosts_in_one_process", "ycsb_record_fields"]
    assert "delay line" in new["reduced"]["hosts_in_one_process"]
    assert "no jitter, loss or bandwidth limit" in \
        new["reduced"]["hosts_in_one_process"]
    assert new["setup_overrides"] == {"warmup": {"clients": 16,
                                                 "ops": 1000}}
    # the cell reports what its twin without the mechanism reports,
    # under the bounds those metrics have
    reports = {m["name"] for m in B["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    assert reports == {"acked_ops_per_s", "write_p95_ms", "setup_s"}
    twin = {m["name"] for m in B["per_layer"]
            if TWIN in m.get("workloads", [])}
    assert len(twin) == 24 and twin <= {m["name"] for m in LISTED}
    assert [m["name"] for m in OWN] == [
        "link_overshoot_ms", "dist_peer_rtt_near_ms",
        "dist_peer_rtt_far_ms", "dist_inflight_at_send_far",
        "dist_thin_frame_holds_per_pass",
        "dist_commit_closed_by_near_share", "dist_far_lag_entries"]
    assert B["per_layer"][-len(OWN):] == OWN
    for m in OWN:
        assert m["workloads"] == [CELL]
        assert m["moves"] in ("acked_ops_per_s", "write_p95_ms")
        assert spec_of(m["name"])["kind"] == "registry"   # data, no code
    assert len(LISTED) == 24 + len(OWN)


def test_rehearsal_is_correct_with_no_failed_operation(geo_copy):
    out, err = run(geo_copy, "--rehearse-cpu")
    assert out["correct"] is True, err[-3000:]
    assert out["failed"] == 0 and out["attempted"] > 20
    assert all(c["value"] == 0 for c in out["compared"].values())
    assert set(out["metrics"]) == {"acked_ops_per_s", "write_p95_ms",
                                   "setup_s"}
    assert out["device"]["platform"] == "cpu"
    assert out["window"]["outcomes"] == {"deadline": 0, "shed": 0,
                                         "wrong": 0}


def test_traced_rehearsal_names_every_span_and_counter_metric(traced):
    """``--trace 1`` on the CPU: every ``program_span`` and
    ``program_counter`` entry that lists the cell with a number — the
    seven of this PR and the accepted ones the cell joined — and no
    ``device_trace`` one."""
    out = traced
    assert out["failed"] == 0
    assert all(c["value"] == 0 for c in out["compared"].values())
    assert "busy_s" not in out["device"] and "breakdown" not in out
    want = {m["name"] for m in LISTED
            if spec_of(m["name"])["kind"] != "trace"}
    assert want == set(out["metrics"]), want ^ set(out["metrics"])
    values = {k: v["value"] for k, v in out["metrics"].items()}
    assert all(isinstance(v, float) for v in values.values())
    assert values["dist_snapshots_in_window"] == 0.0
    assert values["wal_bytes_per_write"] > 3 * 256
    assert out["window"]["lowerings"] == 0


@pytest.mark.parametrize("metric", OWN, ids=lambda m: m["name"])
def test_new_entry_reads_a_number_from_its_own_cell(traced, metric):
    values = {k: v["value"] for k, v in traced["metrics"].items()}
    v = values[metric["name"]]
    assert isinstance(v, float) and v >= 0.0
    assert traced["metrics"][metric["name"]]["unit"] == metric["unit"]


def test_the_rehearsal_says_what_the_links_did(traced):
    """Counts and the program's own clock, not device numbers: each
    peer's round trip holds its link's stated delay both ways, the
    near follower's acknowledgement is the one that commits, and the
    far member trails by more than a round's own entries."""
    v = {k: m["value"] for k, m in traced["metrics"].items()}
    assert 20.0 <= v["dist_peer_rtt_near_ms"] < 200.0
    assert v["dist_peer_rtt_far_ms"] >= 200.0
    # over both peers, as cluster3-put-c16 reads it: between the two
    assert (v["dist_peer_rtt_near_ms"] < v["dist_peer_rtt_ms"]
            < v["dist_peer_rtt_far_ms"])
    assert v["dist_commit_closed_by_near_share"] >= 90.0
    assert v["dist_far_lag_entries"] > v["dist_entries_per_round"]
    assert 0.0 <= v["link_overshoot_ms"] < 10.0


@pytest.mark.parametrize("metric", LISTED, ids=lambda m: m["name"])
def test_reader_gives_nothing_on_a_program_without_the_span(metric):
    """What the parent commit gives, which has no delay line, none of
    its waits and counters, and cannot run the cell at all: nothing,
    never an exception — for every entry the cell is listed under,
    the eleven that were ``cluster3-put-c16``'s alone among them."""
    import bench_reduce

    spec = spec_of(metric["name"])
    ctx = {"registry": {}, "trace": None, "window_ops": [],
           "t0": 0.0, "t1": 1.0}
    assert bench_reduce.read_metric(spec, ctx) is None
    bare = {"etcd_stage_seconds": {"samples": []},
            "etcd_admission_total": {"samples": []},
            "etcd_dist_proposed_entries": {"samples": []}}
    # a count with no denominator reads 0 of a family that is there
    counts = {"dist_snapshots_in_window", "frontdoor_sheds"}
    nothing = 0.0 if metric["name"] in counts else None
    assert bench_reduce.read_metric(
        spec, dict(ctx, registry={"window": (bare, bare),
                                  "trace": (bare, bare)})) == nothing


def test_control_ack_without_commit_comes_out_not_correct(geo_copy):
    out, err = run(geo_copy, "--stand-in", "ack_without_commit")
    assert out["correct"] is False
    assert out["compared"]["lost_writes"]["value"] > 0
