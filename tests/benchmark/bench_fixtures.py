"""What the tests of the benchmark's own yardstick (``benchmark/``)
share: its directory on ``sys.path`` (they import its modules by file
name, never ``jax``) and two fixtures.  Not a ``conftest.py``: the
repo's tests import names from ``tests/conftest.py`` as ``conftest``,
and a second module of that name would shadow it."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def _hashes(top: str) -> dict[str, str]:
    out = {}
    for d, _, files in os.walk(top):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, top)] = hashlib.sha1(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="session")
def tiny_copy(tmp_path_factory):
    """A copy of ``BENCHMARK.json`` and ``benchmark/`` to which a
    configuration (64 groups), a traffic mix, three cells and a
    per-layer metric were ADDED as files and entries; no file that was
    there is edited.  Returns the copy's root."""
    dst = str(tmp_path_factory.mktemp("benchcopy"))
    shutil.copytree(BENCH, os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(os.path.join(dst, "benchmark"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)

    def write(rel: str, obj: dict) -> None:
        with open(os.path.join(dst, "benchmark", rel), "x") as f:
            json.dump(obj, f)

    with open(os.path.join(BENCH, "configs", "tenants1k.json")) as f:
        cfg = json.load(f)
    del cfg["setup_overrides"]         # no snapshot is due at this size
    cfg.update(name="tenants64", facts={"groups": 64, "slots": 6},
               flags=["--cohosted-groups", "64", "--cohosted-members", "5",
                      "--storage-backend", "tpu"])
    write("configs/tenants64.json", cfg)
    with open(os.path.join(BENCH, "traffic", "put-c16.json")) as f:
        mix = json.load(f)
    mix.update(clients=4, records=200,
               setup=[{"name": "warmup", "clients": 4, "ops": 100}])
    write("traffic/put-c4.json", mix)
    write("layer_metrics/fsync_ms.json", {
        "kind": "registry", "over": "window", "scale": 1000.0,
        "numerator": {"family": "etcd_wal_fsync_seconds", "field": "sum"},
        "denominator": {"family": "etcd_wal_fsync_seconds",
                        "field": "count"}})
    bench["configs"].append({
        "name": "tenants64", "source": "a test's own",
        "file": "benchmark/configs/tenants64.json", "reduced": [],
        "why": "64 groups: a size the CPU can hold"})
    new = {"tiny-put-c4": "put-c4", "tiny-ycsb-b-c16": "ycsb-b-c16",
           "tiny-restart": "restart-after-3000"}
    like = {"put-c4": "tenants10k-put-c16",
            "ycsb-b-c16": "tenants10k-ycsb-b-c16",
            "restart-after-3000": "tenants10k-restart"}
    for name, traffic in new.items():
        bench["workloads"].append({
            "name": name, "config": "tenants64", "traffic": traffic,
            "chips": 1, "why": "a test's cell"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like[traffic] in m.get("workloads", []):
                m["workloads"].append(name)
    bench["per_layer"].append({
        "name": "fsync_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "durability",
        "moves": "write_p95_ms", "workloads": ["tiny-put-c4"]})
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = _hashes(os.path.join(dst, "benchmark"))
    assert {k: after[k] for k in before} == before
    return dst


@pytest.fixture(scope="session")
def run_cell(tiny_copy):
    """Run one cell of the copy as the driver would, plus the hidden
    flags given; returns ``(returncode, last stdout line, stderr)``."""
    def run(workload: str, *flags: str, seconds: float = 2.0,
            seed: int = 2_200_000_123, trace: int = 0, cwd=None):
        env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
                   BENCH_RUN="ignored")
        p = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", workload,
             "--seed", str(seed), "--seconds", str(seconds), "--trace",
             str(trace), *flags], cwd=cwd or tiny_copy, env=env,
            capture_output=True, text=True, timeout=300)
        lines = p.stdout.strip().splitlines()
        return p.returncode, (lines[-1] if lines else ""), p.stderr
    return run
