"""``BENCHMARK.json`` and every data file under ``benchmark/``: they
parse, name only what the contract lets a name hold, and find each
other by name."""

import glob
import json
import os
import re

import pytest

import bench_fixtures  # noqa: F401 - puts benchmark/ on sys.path

import bench_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    B = json.load(_f)

DATA_FILES = sorted(
    os.path.relpath(p, BENCH) for kind in ("configs", "traffic",
                                           "end_to_end", "layer_metrics")
    for p in glob.glob(os.path.join(BENCH, kind, "*")))


def test_benchmark_json_has_exactly_the_contract_s_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["benchmark", "tests/benchmark"]
    assert B["command"][:2] == ["python3", "benchmark/run.py"]
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10


@pytest.mark.parametrize("path", DATA_FILES)
def test_data_file_parses_and_is_named_from_name_characters(path):
    stem, ext = os.path.splitext(os.path.basename(path))
    assert ext == ".json" and NAME.match(stem), path
    with open(os.path.join(BENCH, path)) as f:
        assert isinstance(json.load(f), dict)


@pytest.mark.parametrize("metric", B["end_to_end"] + B["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(metric):
    per_layer = metric in B["per_layer"]
    keys = {"name", "unit", "better", "source"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in B["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if per_layer:
        moved = next(m for m in B["end_to_end"]
                     if m["name"] == metric["moves"])
        assert set(metric["workloads"]) <= set(
            moved.get("workloads", cells))
        with open(os.path.join(BENCH, "layer_metrics",
                               metric["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["kind"] in bench_reduce.READERS
        if spec["kind"] == "trace":
            assert metric["source"] == "device_trace"
            if spec["stat"] == "roofline":
                assert spec["work"] in bench_reduce.WORK
                assert metric["name"].endswith("_roofline")
                assert metric["unit"] == "%"
    else:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
        with open(os.path.join(BENCH, "end_to_end",
                               metric["name"] + ".json")) as f:
            # taken by the benchmark itself, never read from the program
            assert json.load(f)["kind"] in ("generator", "clock")


@pytest.mark.parametrize("cell", B["workloads"], ids=lambda w: w["name"])
def test_cell_finds_its_files_and_reports_what_it_must(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])
    assert cell["chips"] == 1 and 1 <= len(cell["why"]) <= 200
    cfg = next(c for c in B["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg["file"])) as f:
        conf = json.load(f)
    assert conf["name"] == cell["config"]
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"])
    assert "--storage-backend" in conf["flags"] and conf["guarantees"]
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["loop"] == "closed" and traffic["clients"] == 16
    assert abs(sum(traffic["mix"].values()) - 1.0) < 1e-9

    def reported(metrics):
        return [m["name"] for m in metrics
                if cell["name"] in m.get("workloads", [cell["name"]])]

    e2e = reported(B["end_to_end"])
    assert "setup_s" in e2e and len(e2e) >= 2
    assert reported(B["per_layer"])


def test_configs_and_names_are_unique_and_within_limits():
    for key in ("configs", "workloads"):
        names = [x["name"] for x in B[key]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(metrics) == len(set(metrics))
    assert len({c["file"] for c in B["configs"]}) == len(B["configs"])
    assert len({c["source"] for c in B["configs"]}) == len(B["configs"])
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in B["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(pairs) == len(set(pairs))
    layers = {m["layer"] for m in B["per_layer"]}
    assert all("\n" not in l and "\t" not in l for l in layers)
