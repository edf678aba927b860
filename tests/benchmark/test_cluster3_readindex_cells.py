"""The cells ``cluster3-readindex-ycsb-b-c16`` and
``cluster3-ycsb-b-c16`` at a size the CPU can hold: a copy of the
benchmark made the way ``test_cluster3_cell.py`` makes its own, to
which two 64-group ``--dist-local-cluster 3`` configurations (the
lease off, ``--dist-lease-ticks 0``, and the lease on), a 4-caller
YCSB-B traffic and their two cells were ADDED, and whole runs of
``run.py --rehearse-cpu`` in it.  Every run is a child with a time
limit of its own.  A number from here is never a device metric: the
tests hold that both cells come out correct with no failed operation,
that each per-layer entry of the layer ``dist reads`` gets a number
from its OWN cell's rehearsal (and says which mechanism served), and
that the control comes out not correct."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_fixtures
from bench_fixtures import BENCH, ROOT

RI, LEASE = "cluster3-readindex-ycsb-b-c16", "cluster3-ycsb-b-c16"
TINY = {RI: "tiny-cluster3-readindex-ycsb-b-c4",
        LEASE: "tiny-cluster3-ycsb-b-c4"}
RUN_LIMIT_S = 300

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    B = json.load(_f)

#: the per-layer entries of this PR, found by their layer and their
#: cells, not by their place in the list
OWN = [m for m in B["per_layer"] if m["layer"] == "dist reads"]
OWN_IN = [(m["name"], cell) for m in OWN for cell in m["workloads"]]


def spec_of(name: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return dict(json.load(f), name=name)


@pytest.fixture(scope="module")
def cells_copy(tmp_path_factory):
    """``BENCHMARK.json`` and ``benchmark/`` copied, with the
    configurations ``readindex64`` and ``lease64``, the traffic
    ``ycsb-b-c4`` and the two tiny cells added as files and entries;
    each tiny cell reports what its full-size cell reports.  No file
    that was there is edited."""
    dst = str(tmp_path_factory.mktemp("readindexcopy"))
    shutil.copytree(BENCH, os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = bench_fixtures._hashes(os.path.join(dst, "benchmark"))
    bench = json.loads(json.dumps(B))

    def write(rel: str, obj: dict) -> None:
        with open(os.path.join(dst, "benchmark", rel), "x") as f:
            json.dump(obj, f)

    for cell, name, full in ((RI, "readindex64", "cluster3-readindex"),
                             (LEASE, "lease64", "cluster3")):
        with open(os.path.join(BENCH, "configs", full + ".json")) as f:
            cfg = json.load(f)
        del cfg["setup_overrides"]     # no snapshot is due at this size
        cfg["name"] = name
        cfg["facts"] = {"groups": 64, "members": 3}
        cfg["flags"] = [x if x != "1024" else "64" for x in cfg["flags"]]
        assert ("--dist-lease-ticks" in cfg["flags"]) == (cell == RI)
        write(f"configs/{name}.json", cfg)
        bench["configs"].append({
            "name": name, "source": "a test's own: " + name,
            "file": f"benchmark/configs/{name}.json",
            "reduced": sorted(cfg["reduced"]),
            "why": "64 groups x 3 members: a size the CPU can hold"})
        bench["workloads"].append({
            "name": TINY[cell], "config": name, "traffic": "ycsb-b-c4",
            "chips": 1, "why": "a test's cell"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if cell in m.get("workloads", []):
                m["workloads"].append(TINY[cell])
    with open(os.path.join(BENCH, "traffic", "ycsb-b-c16.json")) as f:
        mix = json.load(f)
    mix.update(clients=4, records=200, setup=[
        {"name": "preload", "clients": 8, "each_record_once": True},
        {"name": "warmup", "clients": 4, "ops": 100}])
    write("traffic/ycsb-b-c4.json", mix)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = bench_fixtures._hashes(os.path.join(dst, "benchmark"))
    assert {k: after[k] for k in before} == before
    return dst


def run(copy: str, cell: str, *flags: str, seconds: float = 3.0,
        trace: int = 0, seed: int = 2_200_000_357):
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
               BENCH_RUN="ignored")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", TINY[cell],
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace), *flags], cwd=copy, env=env, capture_output=True,
        text=True, timeout=RUN_LIMIT_S)
    lines = p.stdout.strip().splitlines()
    assert p.returncode == 0 and lines, p.stderr[-3000:]
    return json.loads(lines[-1]), p.stderr


@pytest.fixture(scope="module")
def traced(cells_copy):
    """One ``--trace 1`` rehearsal a cell, made when first asked for."""
    done: dict[str, dict] = {}

    def of(cell: str) -> dict:
        if cell not in done:
            out, err = run(cells_copy, cell, "--rehearse-cpu", trace=1,
                           seconds=4.0)
            assert out["correct"] is True, err[-3000:]
            done[cell] = out
        return done[cell]
    return of


def test_this_pr_brought_one_configuration_two_cells_six_entries():
    cells = {w["name"]: w for w in B["workloads"]}
    assert (cells[RI]["config"], cells[LEASE]["config"]) == (
        "cluster3-readindex", "cluster3")
    for cell in (RI, LEASE):
        assert (cells[cell]["traffic"], cells[cell]["chips"]) == (
            "ycsb-b-c16", 1)
    cfg = next(c for c in B["configs"] if c["name"] == "cluster3-readindex")
    with open(os.path.join(ROOT, cfg["file"])) as f:
        new = json.load(f)
    with open(os.path.join(BENCH, "configs", "cluster3.json")) as f:
        old = json.load(f)
    # the pair differs in one flag, and in the read guarantee it buys
    at = new["flags"].index("--dist-lease-ticks")
    assert new["flags"][at + 1] == "0"
    assert new["flags"][:at] + new["flags"][at + 2:] == old["flags"]
    for key in ("facts", "assumed", "reduced", "setup_overrides"):
        assert new[key] == old[key], key
    assert new["guarantees"]["write"] == old["guarantees"]["write"]
    assert "quorum" in new["guarantees"]["read"]
    assert "no lease" in new["guarantees"]["read"]
    assert {m["name"] for m in OWN} == {
        "read_lease_share", "read_index_batch", "read_rtt_ms",
        "read_lock_wait_ms", "read_confirm_wait_ms", "read_release_ms"}
    for m in OWN:
        assert m["moves"] == "read_p95_ms"
        assert RI in m["workloads"] and set(m["workloads"]) <= {RI, LEASE}
        assert spec_of(m["name"])["kind"] == "registry"   # data, no code
    # the mechanism's proof and a read's own clock are read in both
    for name in ("read_lease_share", "read_rtt_ms", "read_lock_wait_ms"):
        assert (name, LEASE) in OWN_IN
    # the lease-off cell is held to its reads' tail alone: the parent
    # commit starves its writes there (43 ops/s), and no cell of three
    # times that rate can spread by under a tenth of THAT median
    reports = {m["name"]: set(m["workloads"]) for m in B["end_to_end"]
               if "workloads" in m}
    assert {RI, LEASE} <= reports["read_p95_ms"]
    assert LEASE in reports["acked_ops_per_s"]
    assert RI not in reports["acked_ops_per_s"]
    for m in B["per_layer"]:
        if m["moves"] == "acked_ops_per_s":
            assert RI not in m.get("workloads", [])


@pytest.mark.parametrize("cell", [RI, LEASE])
def test_rehearsal_is_correct_with_no_failed_operation(cells_copy, cell):
    out, err = run(cells_copy, cell, "--rehearse-cpu")
    assert out["correct"] is True, err[-3000:]
    assert out["failed"] == 0 and out["attempted"] > 40
    assert all(c["value"] == 0 for c in out["compared"].values())
    assert set(out["metrics"]) == {"read_p95_ms", "setup_s"} | (
        {"acked_ops_per_s"} if cell == LEASE else set())
    assert out["device"]["platform"] == "cpu"
    assert out["window"]["outcomes"] == {"deadline": 0, "shed": 0,
                                         "wrong": 0}


@pytest.mark.parametrize("cell", [RI, LEASE])
def test_traced_rehearsal_names_every_span_and_counter_metric(traced,
                                                              cell):
    """``--trace 1`` on the CPU: every ``program_span`` and
    ``program_counter`` entry that lists the cell with a number — the
    six of this PR and the accepted ones the cell joined — and no
    ``device_trace`` one."""
    out = traced(cell)
    assert out["failed"] == 0
    assert all(c["value"] == 0 for c in out["compared"].values())
    assert "busy_s" not in out["device"] and "breakdown" not in out
    mine = [m for m in B["per_layer"] if cell in m["workloads"]]
    want = {m["name"] for m in mine
            if spec_of(m["name"])["kind"] != "trace"}
    assert want == set(out["metrics"]), want ^ set(out["metrics"])
    values = {k: v["value"] for k, v in out["metrics"].items()}
    assert all(isinstance(v, float) for v in values.values())
    # DistServer has no do_local: no GET is answered on the loop thread
    assert values["inline_read_share"] == 0.0
    assert values["read_serve_ms"] > 0.0
    # what moves ``acked_ops_per_s`` is read where that is reported:
    # with the lease on
    for name in ("dist_snapshots_in_window", "dist_pass_ms",
                 "wal_bytes_per_write", "update_p95_ms"):
        assert (name in values) == (cell == LEASE)
    if cell == LEASE:
        assert values["dist_snapshots_in_window"] == 0.0
    assert out["window"]["lowerings"] == 0


@pytest.mark.parametrize("name,cell", OWN_IN,
                         ids=[f"{n}-{c}" for n, c in OWN_IN])
def test_new_entry_reads_a_number_from_its_own_cell(traced, name, cell):
    values = {k: v["value"] for k, v in traced(cell)["metrics"].items()}
    assert isinstance(values[name], float)
    if name == "read_lease_share":
        # which mechanism served: none by lease with the lease off
        if cell == RI:
            assert values[name] == 0.0
        else:
            assert values[name] >= 90.0
    else:
        assert values[name] > 0.0
    if name == "read_index_batch":
        assert values[name] >= 1.0


def test_lease_off_confirms_every_read_and_the_waits_tie_up(traced):
    values = {k: v["value"] for k, v in traced(RI)["metrics"].items()}
    # a read's clock holds its wait for the lock and its confirmation
    assert values["read_rtt_ms"] >= values["read_confirm_wait_ms"]
    assert values["read_rtt_ms"] >= values["read_lock_wait_ms"]


@pytest.mark.parametrize("metric", OWN, ids=lambda m: m["name"])
def test_reader_gives_nothing_on_a_program_without_the_span(metric):
    """What a program with no such wait or counter gives: nothing,
    never an exception (the three waits on the parent commit)."""
    import bench_reduce

    spec = spec_of(metric["name"])
    assert bench_reduce.read_metric(
        spec, {"registry": {}, "trace": None}) is None
    bare = {"etcd_stage_seconds": {"samples": []},
            "etcd_read_serve_total": {"samples": []},
            "etcd_read_index_batch_size": {"samples": []},
            "etcd_read_rtt_seconds": {"samples": []}}
    assert bench_reduce.read_metric(
        spec, {"registry": {"window": (bare, bare)},
               "trace": None}) is None


@pytest.mark.parametrize("cell", [RI, LEASE])
def test_control_stale_read_comes_out_not_correct(cells_copy, cell):
    out, err = run(cells_copy, cell, "--stand-in", "stale_read")
    assert out["correct"] is False
    assert out["compared"]["stale_reads"]["value"] > 0
