"""The cell ``cluster3-put-c16`` at a size the CPU can hold: a copy of
the benchmark made the way ``bench_fixtures.tiny_copy`` makes one, to
which a 64-group ``cluster`` configuration (``--dist-local-cluster
3``) and its cell were ADDED, and whole runs of ``run.py
--rehearse-cpu`` in it.  Every run is a child with a time limit of
its own.  A number from here is never a device metric: the tests hold
that the cell comes out correct with no failed operation, that each
per-layer entry this cell brought finds its span or counter and gets
a number, and that the control comes out not correct."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_fixtures
from bench_fixtures import BENCH, ROOT

CELL = "cluster3-put-c16"
TINY = "tiny-cluster3-put-c4"
RUN_LIMIT_S = 300

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    B = json.load(_f)

#: the per-layer entries that exist for this cell alone
OWN = [m for m in B["per_layer"] if m.get("workloads") == [CELL]]


def spec_of(metric: dict) -> dict:
    with open(os.path.join(BENCH, "layer_metrics",
                           metric["name"] + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cluster_copy(tmp_path_factory):
    """``BENCHMARK.json`` and ``benchmark/`` copied, with the
    configuration ``cluster64``, the traffic ``put-c4`` and the cell
    ``tiny-cluster3-put-c4`` added as files and entries; the cell
    reports what ``cluster3-put-c16`` reports.  No file that was
    there is edited."""
    dst = str(tmp_path_factory.mktemp("cluster3copy"))
    shutil.copytree(BENCH, os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = bench_fixtures._hashes(os.path.join(dst, "benchmark"))
    bench = json.loads(json.dumps(B))

    def write(rel: str, obj: dict) -> None:
        with open(os.path.join(dst, "benchmark", rel), "x") as f:
            json.dump(obj, f)

    with open(os.path.join(BENCH, "configs", "cluster3.json")) as f:
        cfg = json.load(f)
    del cfg["setup_overrides"]         # no snapshot is due at this size
    cfg.update(name="cluster64", facts={"groups": 64, "members": 3},
               flags=["--dist-local-cluster", "3", "--cohosted-groups",
                      "64", "--storage-backend", "tpu"])
    write("configs/cluster64.json", cfg)
    with open(os.path.join(BENCH, "traffic", "put-c16.json")) as f:
        mix = json.load(f)
    mix.update(clients=4, records=200,
               setup=[{"name": "warmup", "clients": 4, "ops": 100}])
    write("traffic/put-c4.json", mix)
    bench["configs"].append({
        "name": "cluster64", "source": "a test's own",
        "file": "benchmark/configs/cluster64.json",
        "reduced": sorted(cfg["reduced"]),
        "why": "64 groups x 3 members: a size the CPU can hold"})
    bench["workloads"].append({
        "name": TINY, "config": "cluster64", "traffic": "put-c4",
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
        seed: int = 2_200_000_321):
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


def test_this_cell_brought_its_own_per_layer_entries():
    names = {m["name"] for m in OWN}
    assert len(OWN) >= 14
    assert {"dist_pass_ms", "dist_entries_per_round", "dist_peer_rtt_ms",
            "dist_queue_wait_ms", "dist_commit_wait_ms",
            "dist_snapshots_in_window",
            "device_busy_ms_per_dist_round"} <= names
    # at the end of the list, and each moving a metric the cell reports
    assert B["per_layer"][-len(OWN):] == OWN
    assert {m["moves"] for m in OWN} == {"acked_ops_per_s",
                                         "write_p95_ms"}
    cell = next(w for w in B["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "cluster3", "put-c16", 1)


def test_rehearsal_is_correct_with_no_failed_operation(cluster_copy):
    out, err = run(cluster_copy, "--rehearse-cpu")
    assert out["correct"] is True, err[-3000:]
    assert out["failed"] == 0 and out["attempted"] > 20
    assert all(c["value"] == 0 for c in out["compared"].values())
    assert set(out["metrics"]) == {"acked_ops_per_s", "write_p95_ms",
                                   "setup_s"}
    assert out["device"]["platform"] == "cpu"
    assert out["window"]["outcomes"] == {"deadline": 0, "shed": 0,
                                         "wrong": 0}


def test_traced_rehearsal_names_every_span_and_counter_metric(
        cluster_copy):
    """``--trace 1`` on the CPU: every ``program_span`` and
    ``program_counter`` entry of the cell with a number, those this
    cell brought and the accepted ones it joined; no ``device_trace``
    one (a run on anything but a TPU takes no trace)."""
    out, err = run(cluster_copy, "--rehearse-cpu", trace=1,
                   seconds=4.0)
    assert out["correct"] is True and out["failed"] == 0, err[-3000:]
    assert "busy_s" not in out["device"] and "breakdown" not in out
    mine = [m for m in B["per_layer"] if CELL in m["workloads"]]
    want = {m["name"] for m in mine if spec_of(m)["kind"] != "trace"}
    assert want == set(out["metrics"]), want ^ set(out["metrics"])
    assert {m["name"] for m in mine
            if m["source"] == "device_trace"}.isdisjoint(out["metrics"])
    values = {k: v["value"] for k, v in out["metrics"].items()}
    assert all(isinstance(v, float) for v in values.values())
    for m in OWN:
        if m["source"] == "program_span":
            assert values[m["name"]] > 0.0, m["name"]
    # three members of one registry: the leader's rounds are counted
    # once, and no snapshot is due at this size
    assert 1.0 <= values["dist_entries_per_round"] <= 5.0
    assert values["dist_snapshots_in_window"] == 0.0
    assert values["wal_bytes_per_write"] > 3 * 256
    assert out["window"]["lowerings"] == 0


@pytest.mark.parametrize("metric", OWN, ids=lambda m: m["name"])
def test_reader_gives_nothing_on_a_program_without_the_span(metric):
    """What the parent commit, which has none of these stages and no
    such counter, gives: nothing, never 0 and never an exception."""
    import bench_reduce

    spec = dict(spec_of(metric), name=metric["name"])
    bare = {"etcd_stage_seconds": {"samples": []}}
    assert bench_reduce.read_metric(
        spec, {"registry": {}, "trace": None}) is None
    # a count with no denominator reads 0 of a family that is there
    # (the parent has dist.snapshot; it cannot run the cell at all)
    nothing = 0.0 if metric["name"] == "dist_snapshots_in_window" else None
    assert bench_reduce.read_metric(
        spec, {"registry": {"window": (bare, bare), "trace": (bare, bare)},
               "trace": None}) == nothing


def test_control_ack_without_commit_comes_out_not_correct(cluster_copy):
    out, err = run(cluster_copy, "--stand-in", "ack_without_commit")
    assert out["correct"] is False
    assert out["compared"]["lost_writes"]["value"] > 0
