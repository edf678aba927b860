"""The layer ``dist lock`` (PR 40): seven entries over the stages the
``dist`` tier's ``self.lock`` files (``utils/trace.py``
``TimedRLock``), each read by the benchmark's own reader from a CPU
rehearsal of its OWN cell at a size the CPU can hold.  The copy is made
the way ``test_cluster3_geo_cell.py`` makes its own: ``BENCHMARK.json``
and ``benchmark/`` copied, a 64-group configuration for each of the
four ``dist`` cells, two 4-caller traffic mixes and a tiny cell a
full-size one ADDED, each tiny cell on every list its full-size cell is
on; no file that was there is edited.  A number from here is never a
device metric: the tests hold that each entry finds its stage in each
of its cells, that the numbers tie up with the round thread's own
stages, and that on a program without the stages (the parent commit)
each reader gives nothing, or 0 where the denominator is there, and
never raises."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_fixtures
from bench_fixtures import BENCH, ROOT

RUN_LIMIT_S = 300
C5, C6, C7, C8 = ("cluster3-put-c16", "cluster3-readindex-ycsb-b-c16",
                  "cluster3-ycsb-b-c16", "cluster3-geo-put-c16")
#: full-size cell -> (its configuration, the tiny cell's traffic)
CELLS = {C5: ("cluster3", "put-c4"), C6: ("cluster3-readindex", "ycsb-b-c4"),
         C7: ("cluster3", "ycsb-b-c4"), C8: ("cluster3-geo", "put-c4")}
TINY = {cell: "tiny-" + cell.replace("-c16", "-c4") for cell in CELLS}

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    B = json.load(_f)

OWN = [m for m in B["per_layer"] if m["layer"] == "dist lock"]
OWN_IN = [(m["name"], cell) for m in OWN for cell in m["workloads"]]
PER_PASS = {"dist_round_lock_wait_ms", "dist_round_lock_hold_ms"}


def spec_of(name: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return dict(json.load(f), name=name)


@pytest.fixture(scope="module")
def lock_copy(tmp_path_factory):
    dst = str(tmp_path_factory.mktemp("lockcopy"))
    shutil.copytree(BENCH, os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = bench_fixtures._hashes(os.path.join(dst, "benchmark"))
    bench = json.loads(json.dumps(B))

    def write(rel: str, obj: dict) -> None:
        with open(os.path.join(dst, "benchmark", rel), "x") as f:
            json.dump(obj, f)

    for full in sorted({c for c, _ in CELLS.values()}):
        with open(os.path.join(BENCH, "configs", full + ".json")) as f:
            cfg = json.load(f)
        del cfg["setup_overrides"]     # no snapshot is due at this size
        cfg["name"] = full + "-64"
        cfg["facts"] = {"groups": 64, "members": 3}
        cfg["flags"] = [x if x != "1024" else "64" for x in cfg["flags"]]
        write(f"configs/{cfg['name']}.json", cfg)
        bench["configs"].append({
            "name": cfg["name"], "source": "a test's own: " + cfg["name"],
            "file": f"benchmark/configs/{cfg['name']}.json",
            "reduced": sorted(cfg["reduced"]),
            "why": "64 groups x 3 members: a size the CPU can hold"})
    for name, full, extra in (
            ("put-c4", "put-c16",
             [{"name": "warmup", "clients": 4, "ops": 100}]),
            ("ycsb-b-c4", "ycsb-b-c16",
             [{"name": "preload", "clients": 8, "each_record_once": True},
              {"name": "warmup", "clients": 4, "ops": 100}])):
        with open(os.path.join(BENCH, "traffic", full + ".json")) as f:
            mix = json.load(f)
        mix.update(clients=4, records=200, setup=extra)
        write(f"traffic/{name}.json", mix)
    for cell, (config, traffic) in CELLS.items():
        bench["workloads"].append({
            "name": TINY[cell], "config": config + "-64",
            "traffic": traffic, "chips": 1, "why": "a test's cell"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if cell in m.get("workloads", []):
                m["workloads"].append(TINY[cell])
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = bench_fixtures._hashes(os.path.join(dst, "benchmark"))
    assert {k: after[k] for k in before} == before
    return dst


@pytest.fixture(scope="module")
def traced(lock_copy):
    """One ``--trace 1`` rehearsal a cell, made when first asked for."""
    done: dict[str, dict] = {}

    def of(cell: str) -> dict:
        if cell not in done:
            env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
                       BENCH_RUN="ignored")
            p = subprocess.run(
                [sys.executable, "benchmark/run.py", "--workload",
                 TINY[cell], "--seed", "2200000419", "--seconds", "3",
                 "--trace", "1", "--rehearse-cpu"], cwd=lock_copy,
                env=env, capture_output=True, text=True,
                timeout=RUN_LIMIT_S)
            lines = p.stdout.strip().splitlines()
            assert p.returncode == 0 and lines, p.stderr[-3000:]
            out = json.loads(lines[-1])
            assert out["correct"] is True and out["failed"] == 0, \
                p.stderr[-3000:]
            done[cell] = {k: v["value"] for k, v in out["metrics"].items()}
        return done[cell]
    return of


def test_this_pr_brought_seven_entries_of_one_layer_at_the_end():
    assert [m["name"] for m in OWN] == [
        "dist_round_lock_wait_ms", "dist_round_lock_hold_ms",
        "dist_lock_handoff_ms", "dist_ack_lock_wait_ms",
        "dist_ack_lock_hold_ms", "dist_frame_lock_wait_ms",
        "dist_read_lock_hold_ms"]
    assert B["per_layer"][-len(OWN):] == OWN
    lists = {m["name"]: (m["moves"], m["workloads"]) for m in OWN}
    for name in ("dist_round_lock_wait_ms", "dist_round_lock_hold_ms",
                 "dist_lock_handoff_ms"):
        assert lists[name] == ("acked_ops_per_s", [C5, C7, C8])
    for name in ("dist_ack_lock_wait_ms", "dist_ack_lock_hold_ms",
                 "dist_frame_lock_wait_ms"):
        assert lists[name] == ("write_p95_ms", [C5, C8])
    assert lists["dist_read_lock_hold_ms"] == ("read_p95_ms", [C6, C7])
    for m in OWN:
        assert (m["unit"], m["better"], m["source"]) == (
            "ms", "lower", "program_span")
        spec = spec_of(m["name"])              # data, no code
        assert (spec["kind"], spec["over"], spec["scale"]) == (
            "registry", "window", 1000.0)
        for part in (spec["numerator"], spec["denominator"]):
            assert part["family"] == "etcd_stage_seconds"
            assert part["labels"]["kind"] == "wall"
    # cell 6 reports no rate (PERF.md section 2), and stays off its lists
    rate = next(m for m in B["end_to_end"]
                if m["name"] == "acked_ops_per_s")
    assert C6 not in rate["workloads"]


@pytest.mark.parametrize("name,cell", OWN_IN,
                         ids=[f"{n}-{c}" for n, c in OWN_IN])
def test_new_entry_reads_a_number_from_its_own_cell(traced, name, cell):
    v = traced(cell)[name]
    assert isinstance(v, float) and v >= 0.0
    if name != "dist_frame_lock_wait_ms":
        # a frame rarely finds the follower's lock taken at this size
        assert v > 0.0


@pytest.mark.parametrize("cell", [C5, C7, C8])
def test_the_round_thread_s_time_at_the_lock_fits_its_iterations(traced,
                                                                 cell):
    """Its waits and holds fall inside its iterations: both per-pass
    numerators sum over ``dist.pass`` and ``dist.heartbeat``, as
    ``dist_heartbeat_ms`` does, and a hand-over is part of a wait."""
    v = traced(cell)
    at_lock = v["dist_round_lock_wait_ms"] + v["dist_round_lock_hold_ms"]
    # one iteration may straddle the end of the window
    assert at_lock <= 1.05 * (v["dist_pass_ms"] + v["dist_heartbeat_ms"])
    assert v["dist_lock_handoff_ms"] < 1000.0


@pytest.mark.parametrize("metric", OWN, ids=lambda m: m["name"])
def test_reader_gives_nothing_on_a_program_without_the_stages(metric):
    """The parent commit files none of these stages: nothing, never an
    exception; and where its registry has ``dist.pass``, the two
    per-pass entries read 0 and the per-event ones still nothing."""
    import bench_reduce

    spec = spec_of(metric["name"])
    assert bench_reduce.read_metric(
        spec, {"registry": {}, "trace": None}) is None
    bare = {"etcd_stage_seconds": {"samples": []}}
    assert bench_reduce.read_metric(
        spec, {"registry": {"window": (bare, bare)},
               "trace": None}) is None
    passes = {"etcd_stage_seconds": {"samples": [
        {"labels": {"stage": "dist.pass", "kind": "wall"},
         "count": 40, "sum": 6.8}]}}
    got = bench_reduce.read_metric(
        spec, {"registry": {"window": (bare, passes)}, "trace": None})
    assert got == (0.0 if metric["name"] in PER_PASS else None)
