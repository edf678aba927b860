"""Whole runs of the harness on the CPU: the reference in the
program's place with a guarantee broken (the control), the program at
64 groups with the timed path broken underneath, and the rules of a
run's last line.  No device metric is ever named here."""

import json
import os
import subprocess
import sys

import pytest

from bench_fixtures import run_cell, tiny_copy  # noqa: F401 - fixtures

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KEYS = ["correct", "attempted", "failed", "metrics", "device"]
DEVICE_METRICS = {"device_busy_ms_per_round", "engine_roofline",
                  "crc_roofline", "device_idle_share",
                  "device_idle_share.restart"}


def test_parent_process_never_imports_jax():
    code = ("import sys; sys.path.insert(0, 'benchmark'); import run; "
            "import bench_load, bench_ref, bench_reduce; "
            "bad = [m for m in sys.modules if m == 'jax' or "
            "m.startswith(('jax.', 'jaxlib', 'etcd_tpu'))]; "
            "assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True)


def test_new_cell_config_traffic_and_metric_are_found_by_name(
        tiny_copy, run_cell):
    """``tiny_copy`` ADDED a configuration, a traffic mix, cells and a
    per-layer metric as files and entries and edited no file (it
    asserts so); the harness finds each by name."""
    rc, line, err = run_cell("tiny-put-c4", "--stand-in", "none")
    assert rc == 0, err
    out = json.loads(line)
    assert list(out)[:5] == KEYS and list(out)[-1] == "compared"
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"acked_ops_per_s", "write_p95_ms",
                                   "setup_s"}
    assert out["attempted"] > 50
    # each number compared beside its limit, last on stderr too
    last = err.strip().splitlines()[-4:]
    assert all(l.startswith("compared ") and "limit 0" in l for l in last)
    rc, _, err = run_cell("no-such-cell", "--stand-in", "none")
    assert rc != 0


@pytest.mark.parametrize("cell,broken,number", [
    ("tiny-put-c4", "ack_without_commit", "lost_writes"),
    ("tiny-put-c4", "alter_answer", "wrong_answers"),
    ("tiny-ycsb-b-c16", "stale_read", "stale_reads"),
    ("tiny-ycsb-b-c16", "ack_without_commit", "stale_reads"),
    ("tiny-restart", "no_fsync", "lost_writes"),
    ("tiny-restart", "ack_without_commit", "lost_writes"),
])
def test_control_comes_out_not_correct(run_cell, cell, broken, number):
    """The reference in the program's place with one guarantee of the
    configuration broken: an acknowledged write that was never applied,
    an answer altered, a read served from a replica that lags, a write
    acknowledged before it reached the file and lost by the restart."""
    rc, line, err = run_cell(cell, "--stand-in", broken, seconds=3.0)
    assert rc == 0, err               # a run that measured exits 0
    out = json.loads(line)
    assert out["correct"] is False
    assert out["compared"][number]["value"] > 0
    assert f"compared {number}: {out['compared'][number]['value']}" in err


@pytest.mark.parametrize("cell", ["tiny-put-c4", "tiny-ycsb-b-c16",
                                  "tiny-restart"])
def test_sound_reference_comes_out_correct(run_cell, cell):
    rc, line, err = run_cell(cell, "--stand-in", "none", seconds=3.0)
    out = json.loads(line)
    assert rc == 0 and out["correct"] is True, (err, line)
    assert out["failed"] == 0
    assert all(c["value"] == 0 for c in out["compared"].values())


def test_cpu_rehearsal_refuses_to_name_a_device_metric(run_cell):
    """The program itself at 64 groups, ``--trace 1``: one well-formed
    last line whose metrics are the cell's per-layer ones minus every
    one that comes from the device trace."""
    rc, line, err = run_cell("tiny-put-c4", "--rehearse-cpu", trace=1,
                             seconds=3.0)
    assert rc == 0, err[-3000:]
    out = json.loads(line)
    assert list(out)[:5] == KEYS and out["correct"] is True
    assert out["device"]["platform"] == "cpu"
    assert "busy_s" not in out["device"] and "breakdown" not in out
    assert not DEVICE_METRICS & set(out["metrics"])
    assert {"client_resends_per_kop", "write_p99_ms", "entries_per_round",
            "engine_round_ms", "persist_ms", "apply_ms", "frontdoor_sheds",
            "wal_bytes_per_write", "fsync_ms"} == set(out["metrics"])
    assert all(isinstance(m["value"], float) for m in out["metrics"].values())
    assert out["window"]["lowerings"] == 0   # nothing compiled in the window


@pytest.mark.parametrize("fault,number", [
    ("alter_answer", "wrong_answers"),   # an answer altered where it is made
    ("drop_apply", "lost_writes"),       # acknowledged, the state unchanged
])
def test_timed_path_broken_underneath_is_not_correct(run_cell, fault,
                                                     number):
    """The rest of a run with the look for a chip skipped and the
    program's apply broken for one write in twenty."""
    rc, line, err = run_cell("tiny-put-c4", "--rehearse-cpu", "--fault",
                             fault, seconds=3.0)
    assert rc == 0, err[-3000:]
    out = json.loads(line)
    assert out["correct"] is False and out["compared"][number]["value"] > 0


def test_restart_cell_on_the_program(run_cell):
    rc, line, err = run_cell("tiny-restart", "--rehearse-cpu", seconds=8.0)
    assert rc == 0, err[-3000:]
    out = json.loads(line)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"replay_to_serving_s", "setup_s"}
    clock = out["window"]["clock"]
    assert 0 < out["metrics"]["replay_to_serving_s"]["value"] < 8.0
    assert clock["restart_to_serving_s"] == pytest.approx(
        clock["device_ready_s"] + clock["ready_to_serving_s"])
    assert 0 < clock["old_exit_s"] < clock["device_ready_s"]


def test_no_accelerator_no_result(run_cell):
    rc, line, err = run_cell("tiny-put-c4")
    assert rc != 0 and line == ""
    assert "no TPU" in err


def test_nothing_but_the_benchmark_s_files_no_result(tiny_copy):
    """In a directory that holds only ``BENCHMARK.json`` and the files
    under ``paths`` there is no program to start."""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny-put-c4",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tiny_copy,
        env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
