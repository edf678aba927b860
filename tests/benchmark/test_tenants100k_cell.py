"""The cell ``tenants100k-put-c16`` at a size the CPU can hold: a copy
of the benchmark made the way ``test_cluster5_geo_cell.py`` makes its
own, to which a 2000-group copy of ``tenants100k`` (with a snapshot
count of 100, so that snapshots land in a five-second window), a
4-caller PUT traffic and a tiny cell were ADDED, and whole runs of
``run.py --rehearse-cpu`` in it (the launcher passes the
configuration's flags to ``cli.main`` and nothing else).  Every run is
a child with a time limit of its own.  A number from here is never a
device metric: the tests hold that the cell comes out correct with no
failed operation, that every entry the cell is listed under gets a
number from ITS OWN cell's rehearsal, the four this cell brought
among them, and that each new entry's reader gives nothing, and
raises nothing, on a program without the span or counter (the parent
commit).  Cells are found by their names and entries by their names
and ``workloads``, never by position."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_fixtures
from bench_fixtures import BENCH, ROOT

CELL = "tenants100k-put-c16"
TWIN = "tenants10k-put-c16"          # the same cell at G = 10 000
SMALL = "tenants1k-put-c16"
TINY = "tiny-tenants100k-put-c4"
TINY_G = 2000
RUN_LIMIT_S = 300
NEW = ("frontier_groups_per_flush", "round_transfer_kb",
       "snapshot_encode_ms", "snapshot_compact_ms")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    B = json.load(_f)

OWN = [m for m in B["per_layer"] if m["name"] in NEW]
LISTED = [m for m in B["per_layer"] if CELL in m.get("workloads", [])]


def spec_of(name: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return dict(json.load(f), name=name)


def config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny_copy(tmp_path_factory):
    """``BENCHMARK.json`` and ``benchmark/`` copied, with the
    configuration ``tenants100k-cpu`` (``tenants100k.json`` at 2000
    groups, snapshot count 100, no warm-up override), the traffic
    ``put-c4`` and the tiny cell added as files and entries; the tiny
    cell reports what the full-size cell reports.  No file that was
    there is edited."""
    dst = str(tmp_path_factory.mktemp("t100kcopy"))
    shutil.copytree(BENCH, os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = bench_fixtures._hashes(os.path.join(dst, "benchmark"))
    bench = json.loads(json.dumps(B))

    def write(rel: str, obj: dict) -> None:
        with open(os.path.join(dst, "benchmark", rel), "x") as f:
            json.dump(obj, f)

    cfg = config("tenants100k")
    del cfg["setup_overrides"]
    cfg["name"] = "tenants100k-cpu"
    cfg["facts"] = {"groups": TINY_G, "slots": 6}
    cfg["flags"] = [x if x != "100000" else str(TINY_G)
                    for x in cfg["flags"]] + ["--snapshot-count", "100"]
    write("configs/tenants100k-cpu.json", cfg)
    with open(os.path.join(BENCH, "traffic", "put-c16.json")) as f:
        mix = json.load(f)
    mix.update(clients=4, records=200,
               setup=[{"name": "warmup", "clients": 4, "ops": 100}])
    write("traffic/put-c4.json", mix)
    bench["configs"].append({
        "name": "tenants100k-cpu", "source": "a test's own",
        "file": "benchmark/configs/tenants100k-cpu.json",
        "reduced": sorted(cfg["reduced"]),
        "why": "tenants100k at 2000 groups: a size the CPU can hold"})
    bench["workloads"].append({
        "name": TINY, "config": "tenants100k-cpu", "traffic": "put-c4",
        "chips": 1, "why": "a test's cell"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append(TINY)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = bench_fixtures._hashes(os.path.join(dst, "benchmark"))
    assert {k: after[k] for k in before} == before
    return dst


def run(copy: str, *flags: str, seconds: float = 5.0, trace: int = 0,
        seed: int = 2_200_000_545):
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
def traced(tiny_copy) -> dict:
    """The one ``--trace 1`` rehearsal the per-layer tests share."""
    out, err = run(tiny_copy, "--rehearse-cpu", trace=1)
    assert out["correct"] is True, err[-3000:]
    return out


def test_this_cell_brought_one_configuration_one_cell_four_entries():
    cell = next(w for w in B["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tenants100k", "put-c16", 1)
    assert len(cell["why"]) <= 200
    entry = next(c for c in B["configs"] if c["name"] == "tenants100k")
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    new, ten = config("tenants100k"), config("tenants10k")
    assert new["source"] == entry["source"]
    assert new["flags"] == ["--cohosted-groups", "100000",
                            "--cohosted-members", "5",
                            "--storage-backend", "tpu"]
    assert new["facts"] == {"groups": 100000, "slots": 6}
    assert new["guarantees"] == ten["guarantees"]
    for key, val in ten["assumed"].items():
        assert new["assumed"][key] == val, key
    assert sorted(new["reduced"]) == sorted(entry["reduced"]) == [
        "v5e8_to_one_chip", "ycsb_record_fields"]
    assert new["reduced"]["ycsb_record_fields"] == \
        ten["reduced"]["ycsb_record_fields"]
    assert "G is not cut" in new["reduced"]["v5e8_to_one_chip"]
    assert set(new["setup_overrides"]) == {"warmup"}
    assert new["setup_overrides_why"]
    reports = {m["name"] for m in B["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    assert reports == {"acked_ops_per_s", "write_p95_ms", "setup_s"}
    # it joined every entry of the same cell at G = 10 000 ...
    twin = {m["name"] for m in B["per_layer"]
            if TWIN in m.get("workloads", [])}
    assert {m["name"] for m in LISTED} == twin | set(NEW)
    # ... and brought four of its own, all data, all read from the
    # registry; the snapshot's stages are not listed for cell 1 (its
    # CPU twin's short window holds no snapshot)
    assert [m["name"] for m in OWN] == list(NEW)
    for m in OWN:
        assert m["workloads"][-1] == CELL and SMALL in m["workloads"]
        assert m["moves"] in reports
        assert spec_of(m["name"])["kind"] == "registry"
    assert {m["name"] for m in OWN if TWIN in m["workloads"]} == {
        "frontier_groups_per_flush", "round_transfer_kb"}


def test_rehearsal_is_correct_with_no_failed_operation(tiny_copy):
    out, err = run(tiny_copy, "--rehearse-cpu")
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
    assert out["window"]["snapshots"] >= 1


@pytest.mark.parametrize("metric", OWN, ids=lambda m: m["name"])
def test_new_entry_reads_a_number_from_its_own_cell(traced, metric):
    v = traced["metrics"][metric["name"]]
    assert isinstance(v["value"], float) and v["value"] > 0.0
    assert v["unit"] == metric["unit"]


def test_the_rehearsal_says_what_the_round_and_the_record_cost(traced):
    """Counts, not device numbers: a round moves its ``[2, G]`` input
    and its ``int32[7, G]`` pack, 36 bytes a group (the routing is
    single-slot once every group has its leader); a frontier record
    names the groups whose commit moved, no more than a round's
    entries, never G."""
    v = {k: m["value"] for k, m in traced["metrics"].items()}
    assert v["round_transfer_kb"] == pytest.approx(36 * TINY_G / 1000)
    assert 1.0 <= v["frontier_groups_per_flush"] \
        <= 1.05 * v["entries_per_round"] + 0.05
    assert v["frontier_groups_per_flush"] < TINY_G / 100
    assert v["wal_bytes_per_write"] < 1024


@pytest.mark.parametrize("metric", OWN, ids=lambda m: m["name"])
def test_reader_gives_nothing_on_a_program_without_the_family(metric):
    """What the parent commit gives, which has neither the stages nor
    the counters: nothing, never 0 and never an exception."""
    import bench_reduce

    spec = spec_of(metric["name"])
    ctx = {"registry": {}, "trace": None, "window_ops": [],
           "t0": 0.0, "t1": 1.0}
    assert bench_reduce.read_metric(spec, ctx) is None
    bare = {"etcd_stage_seconds": {"samples": [
        {"labels": {"stage": "mg.snapshot", "kind": "wall"},
         "count": 1, "sum": 0.25}]}}
    assert bench_reduce.read_metric(
        spec, dict(ctx, registry={"window": (bare, bare),
                                  "trace": (bare, bare)})) is None
