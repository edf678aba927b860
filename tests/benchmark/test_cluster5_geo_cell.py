"""The cell ``cluster5-geo-put-c16`` at a size the CPU can hold: a
copy of the benchmark made the way ``test_cluster3_geo_cell.py`` makes
its own, to which a 64-group configuration with ``cluster5-geo``'s
own five members and link delays, a 4-caller PUT traffic and a tiny
cell were ADDED, and whole runs of ``run.py --rehearse-cpu`` in it
(the launcher passes the configuration's flags to ``cli.main`` and
nothing else).  Every run is a child with a time limit of its own.  A
number from here is never a device metric: the tests hold that the
cell comes out correct with no failed operation, that every entry of
the layer ``dist quorum`` gets a number from ITS OWN cell's rehearsal,
and that each entry's reader gives nothing, and raises nothing, on a
program without the span or counter (the parent commit).  Entries are
found by their layer and cells by their names, never by position."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_fixtures
from bench_fixtures import BENCH, ROOT

CELL = "cluster5-geo-put-c16"
TWIN = "cluster3-geo-put-c16"
TINY = "tiny-cluster5-geo-put-c4"
RUN_LIMIT_S = 300
SPEC = ("0-1:1,2-3:1,0-2:10,0-3:10,1-2:10,1-3:10,"
        "0-4:100,1-4:100,2-4:100,3-4:100")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    B = json.load(_f)

#: the entries this cell brought, found by their layer, and every entry
#: the cell is listed under (its own and the accepted ones it joined)
OWN = [m for m in B["per_layer"] if m["layer"] == "dist quorum"]
LISTED = [m for m in B["per_layer"] if CELL in m.get("workloads", [])]
#: the twin's entries that name a peer: its peers 1 and 2 are other
#: members here
PEER_NAMED = {"dist_peer_rtt_near_ms", "dist_peer_rtt_far_ms",
              "dist_inflight_at_send_far",
              "dist_commit_closed_by_near_share", "dist_far_lag_entries"}


def spec_of(name: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return dict(json.load(f), name=name)


@pytest.fixture(scope="module")
def geo5_copy(tmp_path_factory):
    """``BENCHMARK.json`` and ``benchmark/`` copied, with the
    configuration ``geo5-64`` (``cluster5-geo.json`` at 64 groups, no
    warm-up override), the traffic ``put-c4`` and the tiny cell added
    as files and entries; the tiny cell reports what the full-size
    cell reports.  No file that was there is edited."""
    dst = str(tmp_path_factory.mktemp("geo5copy"))
    shutil.copytree(BENCH, os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = bench_fixtures._hashes(os.path.join(dst, "benchmark"))
    bench = json.loads(json.dumps(B))

    def write(rel: str, obj: dict) -> None:
        with open(os.path.join(dst, "benchmark", rel), "x") as f:
            json.dump(obj, f)

    with open(os.path.join(BENCH, "configs", "cluster5-geo.json")) as f:
        cfg = json.load(f)
    del cfg["setup_overrides"]         # no snapshot is due at this size
    cfg["name"] = "geo5-64"
    cfg["facts"] = {"groups": 64, "members": 5}
    cfg["flags"] = [x if x != "1024" else "64" for x in cfg["flags"]]
    write("configs/geo5-64.json", cfg)
    with open(os.path.join(BENCH, "traffic", "put-c16.json")) as f:
        mix = json.load(f)
    mix.update(clients=4, records=200,
               setup=[{"name": "warmup", "clients": 4, "ops": 100}])
    write("traffic/put-c4.json", mix)
    bench["configs"].append({
        "name": "geo5-64", "source": "a test's own: geo5-64",
        "file": "benchmark/configs/geo5-64.json",
        "reduced": sorted(cfg["reduced"]),
        "why": "64 groups x 5 members across three regions: a size "
               "the CPU can hold"})
    bench["workloads"].append({
        "name": TINY, "config": "geo5-64", "traffic": "put-c4",
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
        seed: int = 2_200_000_542):
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
def traced(geo5_copy) -> dict:
    """The one ``--trace 1`` rehearsal the per-layer tests share."""
    out, err = run(geo5_copy, "--rehearse-cpu", trace=1, seconds=5.0)
    assert out["correct"] is True, err[-3000:]
    return out


def test_this_cell_brought_one_configuration_one_cell_four_entries():
    cell = next(w for w in B["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "cluster5-geo", "put-c16", 1)
    assert len(cell["why"]) <= 200
    entry = next(c for c in B["configs"] if c["name"] == "cluster5-geo")
    assert len(entry["source"]) <= 200
    with open(os.path.join(ROOT, entry["file"])) as f:
        new = json.load(f)
    with open(os.path.join(BENCH, "configs", "cluster3-geo.json")) as f:
        geo = json.load(f)
    assert new["source"] == entry["source"]
    # cluster3-geo with five members and the 2 + 2 + 1 delays
    assert new["flags"] == ["--dist-local-cluster", "5",
                            "--cohosted-groups", "1024",
                            "--dist-local-link-delay-ms", SPEC,
                            "--storage-backend", "tpu"]
    a = new["assumed"]
    assert a["link_one_way_ms"] == {
        f"{k.split(':')[0]}": int(k.split(":")[1])
        for k in SPEC.split(",")}
    assert new["facts"] == {"groups": 1024, "members": 5}
    assert "3 of 5" in new["guarantees"]["write"]
    assert "at least two followers" in new["guarantees"]["placement"]
    assert "all five stores are equal" in new["guarantees"]["placement"]
    for key in ("read", "server_request_timeout_s", "client_deadline_s"):
        assert new["guarantees"][key] == geo["guarantees"][key], key
    for key, val in geo["assumed"].items():
        if key not in ("link_one_way_ms", "layout", "timing_rule"):
            assert a[key] == val, key
    # upstream's rule for the far link, in seconds, at the defaults,
    # and an election band for each of five members
    assert a["election_timeout_s"] >= 10 * 2 * 0.100
    assert (a["heartbeat_s"], a["election_timeout_s"], a["lease_s"]) == (
        0.1, 6.0, 3.0)
    assert "60 election ticks >= 5 members" in a["timing_rule"]
    assert sorted(new["reduced"]) == sorted(entry["reduced"]) == [
        "hosts_in_one_process", "ycsb_record_fields"]
    assert "five hosts" in new["reduced"]["hosts_in_one_process"]
    assert "no jitter, loss or bandwidth limit" in \
        new["reduced"]["hosts_in_one_process"]
    assert new["setup_overrides"] == geo["setup_overrides"]
    # the write's tail and the set-up: the parent's writes collapse
    # here (about 3 ops/s), and half of acked_ops_per_s's bound as a
    # share of THAT median is a spread no run of the change can keep
    reports = {m["name"] for m in B["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    assert reports == {"write_p95_ms", "setup_s"}
    # it joined every entry of its twin that names no peer and moves
    # what it reports ...
    twin = {m["name"] for m in B["per_layer"]
            if TWIN in m.get("workloads", []) and m["moves"] in reports}
    joined = {m["name"] for m in LISTED} - {m["name"] for m in OWN}
    assert joined == twin - PEER_NAMED and len(joined) == 17
    # ... and brought four of its own, all data
    assert [m["name"] for m in OWN] == [
        "dist_first_ack_ms", "dist_quorum_ack_ms",
        "dist_commit_closed_by_region_a_share", "dist_far_lag_entries_s4"]
    for m in OWN:
        assert m["workloads"] == [CELL]
        assert m["moves"] in reports
        assert spec_of(m["name"])["kind"] == "registry"


def test_rehearsal_is_correct_with_no_failed_operation(geo5_copy):
    out, err = run(geo5_copy, "--rehearse-cpu")
    assert out["correct"] is True, err[-3000:]
    assert out["failed"] == 0 and out["attempted"] > 20
    assert all(c["value"] == 0 for c in out["compared"].values())
    assert set(out["metrics"]) == {
        m["name"] for m in B["end_to_end"]
        if CELL in m.get("workloads", [CELL])}
    assert out["device"]["platform"] == "cpu"
    assert out["window"]["outcomes"] == {"deadline": 0, "shed": 0,
                                         "wrong": 0}


def test_traced_rehearsal_names_every_span_and_counter_metric(traced):
    out = traced
    assert out["failed"] == 0
    assert all(c["value"] == 0 for c in out["compared"].values())
    assert "busy_s" not in out["device"] and "breakdown" not in out
    want = {m["name"] for m in LISTED
            if spec_of(m["name"])["kind"] != "trace"}
    assert want == set(out["metrics"]), want ^ set(out["metrics"])
    assert all(isinstance(v["value"], float)
               for v in out["metrics"].values())
    assert out["window"]["lowerings"] == 0


@pytest.mark.parametrize("metric", OWN, ids=lambda m: m["name"])
def test_new_entry_reads_a_number_from_its_own_cell(traced, metric):
    v = traced["metrics"][metric["name"]]
    assert isinstance(v["value"], float) and v["value"] >= 0.0
    assert v["unit"] == metric["unit"]


def test_the_rehearsal_says_what_the_quorum_did(traced):
    """Counts and the program's own clock, not device numbers: the
    quorum-closing answer comes no earlier than the first, and region
    C's member trails."""
    v = {k: m["value"] for k, m in traced["metrics"].items()}
    assert v["dist_quorum_ack_ms"] >= v["dist_first_ack_ms"] > 0.0
    assert v["dist_far_lag_entries_s4"] > 0.0
    assert 0.0 <= v["dist_commit_closed_by_region_a_share"] <= 100.0


@pytest.mark.parametrize("metric", OWN, ids=lambda m: m["name"])
def test_reader_gives_nothing_on_a_program_without_the_family(metric):
    """What the parent commit gives, which has neither wait: nothing,
    never 0 and never an exception."""
    import bench_reduce

    spec = spec_of(metric["name"])
    ctx = {"registry": {}, "trace": None, "window_ops": [],
           "t0": 0.0, "t1": 1.0}
    assert bench_reduce.read_metric(spec, ctx) is None
    bare = {"etcd_stage_seconds": {"samples": []},
            "etcd_admission_total": {"samples": []}}
    assert bench_reduce.read_metric(
        spec, dict(ctx, registry={"window": (bare, bare),
                                  "trace": (bare, bare)})) is None
