#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload NAME --seed N --seconds S --trace 0|1

This parent never imports jax.  It reads the cell's configuration
(``configs/<config>.json``), its traffic (``traffic/<traffic>.json``)
and its per-layer readers (``layer_metrics/<name>.json``) by the names
in ``BENCHMARK.json``, starts the server in ``bench_launcher.py`` (the
only process that touches the chip), drives HTTP ``/v2/keys`` from
closed-loop client threads (``bench_load``), compares every answer
with the plain reference (``bench_ref``) once the window has closed,
and prints one JSON line.  It exits 0 whenever it measured; a failed
operation is a count.  Without a TPU the launcher exits and so does
this, with no line.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import bench_load  # noqa: E402
import bench_reduce  # noqa: E402
import bench_ref  # noqa: E402

LISTENING = "Listening for client requests"
READBACK_DEADLINE_S = 60.0
START_TIMEOUT_S = 900.0


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """The launcher child, its control pipe and its log."""

    def __init__(self, cmd: list[str], log_path: str):
        self.log_path = log_path
        self._log = open(log_path, "ab")
        self.lock = threading.Lock()
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._log, text=True)
        ready = self._read()
        self.device = ready["device"]

    def _read(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            self.proc.wait()
            raise RuntimeError(
                f"launcher exited with {self.proc.returncode}:\n"
                + self.log_text()[-3000:])
        reply = json.loads(line)
        if "error" in reply:
            raise RuntimeError(f"launcher: {reply['error']}")
        return reply

    def ask(self, cmd: str, **kw) -> dict:
        with self.lock:
            self.proc.stdin.write(json.dumps({"cmd": cmd, **kw}) + "\n")
            self.proc.stdin.flush()
            return self._read()

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def wait_listening(self) -> None:
        deadline = time.monotonic() + START_TIMEOUT_S
        while LISTENING not in self.log_text():
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"server exited with {self.proc.returncode} before "
                    f"listening:\n{self.log_text()[-3000:]}")
            if time.monotonic() > deadline:
                raise RuntimeError("server not listening in time")
            time.sleep(0.05)

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        for f in (self.proc.stdin, self.proc.stdout, self._log):
            f.close()


def dir_bytes(data_dir: str, pattern: str) -> int:
    total = 0
    for path in glob.glob(os.path.join(data_dir, pattern), recursive=True):
        try:
            total += os.path.getsize(path)
        except OSError:
            pass                       # collected between glob and stat
    return total


class Run:
    def __init__(self, args, bench: dict):
        self.args = args
        cell = next((w for w in bench["workloads"]
                     if w["name"] == args.workload), None)
        if cell is None:
            raise SystemExit(f"no workload {args.workload!r} in "
                             f"BENCHMARK.json")
        self.cell = cell
        self.config = load_json("configs", cell["config"] + ".json")
        self.traffic = load_json("traffic", cell["traffic"] + ".json")
        for name, over in self.config.get("setup_overrides", {}).items():
            for phase in self.traffic["setup"]:
                if phase["name"] == name:
                    phase.update(over)

        def mine(metric: dict) -> bool:
            return cell["name"] in metric.get("workloads", [cell["name"]])

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        self.per_layer = [m for m in bench["per_layer"] if mine(m)]
        self.specs = {}
        for kind, metrics in (("end_to_end", self.end_to_end),
                              ("layer_metrics", self.per_layer)):
            for m in metrics:
                spec = load_json(kind, m["name"] + ".json")
                spec["name"] = m["name"]
                self.specs[m["name"]] = spec
        self.plan = bench_load.Plan(self.traffic, args.seed)
        self.hist = bench_load.History(self.plan.records)
        self.host, self.port = "127.0.0.1", free_port()
        self.servers: list[Server] = []
        self.ctx: dict = {"registry": {}, "facts": dict(self.config["facts"]),
                          "window_ops": [], "clock": {}}

    # -- the server ----------------------------------------------------------

    def start_server(self, trace_dir: str | None = None) -> Server:
        a = self.args
        n = len(self.servers) + 1
        log_path = os.path.join(self.workdir, f"server{n}.log")
        if a.stand_in:
            cmd = [sys.executable, os.path.join(HERE, "bench_ref.py"),
                   "--break", a.stand_in, "--data-dir", self.data_dir,
                   "--port", str(self.port)]
        else:
            url = f"http://{self.host}:{self.port}"
            cmd = [sys.executable, os.path.join(HERE, "bench_launcher.py"),
                   "--need-chips", str(self.cell["chips"])]
            if a.rehearse_cpu:
                cmd.append("--allow-cpu")
            if a.fault:
                cmd += ["--fault", a.fault]
            if trace_dir:
                cmd += ["--trace-dir", trace_dir]
            cmd += ["--", *self.config["flags"], "--name", "bench",
                    "--data-dir", self.data_dir, "--listen-client-urls", url,
                    "--advertise-client-urls", url]
        srv = Server(cmd, log_path)
        self.servers.append(srv)
        return srv

    @property
    def on_tpu(self) -> bool:
        return self.servers[0].device["platform"] == "tpu"

    def phase(self, **kw) -> list:
        return bench_load.run_phase(self.plan, self.hist, self.host,
                                    self.port, **kw)

    def written(self) -> list[tuple[str, int]]:
        return [("get", r) for r in range(self.plan.records)
                if self.hist.writes[r]]

    def settle(self, srv: Server) -> dict:
        """Wait until the server has lowered no new program for a
        second, so that nothing compiles inside the window."""
        last, since = None, time.monotonic()
        while True:
            stats = srv.ask("stats")
            now = sum(stats.get("events", {}).values())
            if now != last:
                last, since = now, time.monotonic()
            elif time.monotonic() - since >= 1.0:
                return stats
            time.sleep(0.25)

    # -- the run -------------------------------------------------------------

    def run(self) -> dict:
        t_run = time.monotonic()
        os.makedirs(os.path.join(ROOT, ".bench_work"), exist_ok=True)
        self.workdir = tempfile.mkdtemp(
            prefix=self.cell["name"] + "-",
            dir=os.path.join(ROOT, ".bench_work"))
        self.data_dir = os.path.join(self.workdir, "data")
        try:
            srv = self.start_server()
            srv.wait_listening()
            for ph in self.traffic["setup"]:
                work = None
                if ph.get("each_record_once"):
                    work = [("put", r) for r in range(self.plan.records)]
                self.phase(phase=ph["name"], clients=ph["clients"],
                           ops=ph.get("ops"), work=work,
                           put_share=ph.get("put_share"))
            stats0 = self.settle(srv)
            self.ctx["t0"] = t0 = time.monotonic()
            self.ctx["t1"] = t0 + self.args.seconds
            self.ctx["clock"]["setup_s"] = t0 - t_run
            cpu0 = os.times()
            if self.traffic.get("restart"):
                self.window_restart(srv, stats0)
            else:
                self.window_steady(srv, stats0)
            cpu1 = os.times()
            srv = self.servers[-1]
            self.phase(phase="readback", clients=self.traffic["clients"],
                       work=self.written(), deadline_s=READBACK_DEADLINE_S)
            stats_end = srv.ask("stats")
            trace = None
            if self.args.trace and self.on_tpu:
                patterns = {n: s["ops"] for n, s in self.specs.items()
                            if s["kind"] == "trace" and "ops" in s}
                trace = srv.ask("trace_reduce", patterns=patterns)["trace"]
                if not trace.get("busy_s"):
                    raise RuntimeError("the trace holds no device operation")
            self.ctx.update(trace=trace, log_text=srv.log_text(),
                            device_kind=srv.device["kind"])
        finally:
            for s in self.servers:
                s.stop()
            if self.args.keep:         # logs and trace, never the data
                shutil.rmtree(self.data_dir, ignore_errors=True)
                shutil.copytree(self.workdir, self.args.keep,
                                dirs_exist_ok=True)
            shutil.rmtree(self.workdir, ignore_errors=True)
        return self.result(stats_end, cpu0, cpu1)

    def window_steady(self, srv: Server, stats0: dict) -> None:
        t0, seconds = self.ctx["t0"], self.args.seconds
        stop = threading.Event()
        samples: list[int] = []
        pattern = next((s["glob"] for s in self.specs.values()
                        if s["kind"] == "disk"), "**/*.wal")

        def sample_disk() -> None:
            while not stop.is_set():
                samples.append(dir_bytes(self.data_dir, pattern))
                stop.wait(0.5)

        def trace_some() -> None:
            at = min(self.traffic.get("trace_after_s", 3.0), seconds / 4)
            if stop.wait(at):
                return
            srv.ask("trace_start", dir=os.path.join(self.workdir, "trace"))
            before = srv.ask("stats")  # rounds are counted inside the trace
            stop.wait(min(self.traffic.get("trace_s", 4.0), seconds / 2))
            after = srv.ask("stats")
            srv.ask("trace_stop")
            self.ctx["registry"]["trace"] = (before["registry"],
                                             after["registry"])

        side = [threading.Thread(target=sample_disk, daemon=True)]
        if self.args.trace and self.on_tpu:
            side.append(threading.Thread(target=trace_some, daemon=True))
        for t in side:
            t.start()
        ops = self.phase(phase="window", clients=self.traffic["clients"],
                         until=t0 + seconds)
        stats1 = srv.ask("stats")
        stop.set()
        for t in side:
            t.join()
        samples.append(dir_bytes(self.data_dir, pattern))
        self.ctx["window_ops"] = ops
        self.ctx["disk_samples"] = samples
        self.ctx["registry"]["window"] = (stats0.get("registry"),
                                          stats1.get("registry"))
        self.ctx["window_stats"] = (stats0, stats1)

    def window_restart(self, srv: Server, stats0: dict) -> None:
        """The window opens with SIGTERM.  A new process with the same
        flags on the same data directory; one prober sends until the
        first acknowledgement; then every written record is read back
        and the clients write to the end of the window."""
        t0, t1 = self.ctx["t0"], self.ctx["t1"]
        self.ctx["first_peak"] = stats0.get("device", {}).get(
            "memory_peak_bytes")
        self.ctx["t_signal_wall"] = time.time()
        srv.stop()
        parts = self.ctx["clock"]
        parts["old_exit_s"] = time.monotonic() - t0
        tracing = bool(self.args.trace and self.on_tpu)
        srv2 = self.start_server(
            os.path.join(self.workdir, "trace") if tracing else None)
        parts["device_ready_s"] = time.monotonic() - t0
        probe = bench_load.Conn(self.host, self.port)
        a = probe.op("PUT", "/v2/keys/_bench/probe", b"value=probe",
                     deadline_s=START_TIMEOUT_S)
        probe.close()
        if a.outcome != "ack":
            raise RuntimeError(f"the restarted server never served: {a}")
        parts["restart_to_serving_s"] = a.t_end - t0
        parts["ready_to_serving_s"] = a.t_end - t0 - parts["device_ready_s"]
        stats_a = srv2.ask("stats")
        if tracing:
            srv2.ask("trace_stop")
            self.ctx["registry"]["trace"] = (None, stats_a["registry"])
        readbacks = self.phase(
            phase="readback-restart", clients=self.traffic["clients"],
            work=self.written(), deadline_s=READBACK_DEADLINE_S)
        ops = self.phase(phase="window", clients=self.traffic["clients"],
                         until=t1)
        stats1 = srv2.ask("stats")
        self.ctx["window_ops"] = ops + readbacks
        self.ctx["registry"]["window"] = (stats_a.get("registry"),
                                          stats1.get("registry"))
        self.ctx["window_stats"] = (stats_a, stats1)

    # -- the result ----------------------------------------------------------

    def result(self, stats_end, cpu0, cpu1) -> dict:
        c, a = self.ctx, self.args
        ops = c["window_ops"]
        compared = bench_ref.compare(self.hist.ops)
        metrics = {}
        for m in self.per_layer if a.trace else self.end_to_end:
            v = bench_reduce.read_metric(self.specs[m["name"]], c)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device = dict(stats_end.get("device") or self.servers[-1].device)
        peaks = [p for p in (device.get("memory_peak_bytes"),
                             c.get("first_peak")) if p]
        device["memory_peak_bytes"] = max(peaks) if peaks else None
        out = {
            "correct": bench_ref.is_correct(compared),
            "attempted": len(ops),
            "failed": sum(1 for op in ops if op.outcome != "ack"),
            "metrics": metrics,
            "device": device,
        }
        if c.get("trace"):
            device["busy_s"] = c["trace"]["busy_s"]
            device["window_s"] = c["trace"]["window_s"]
            out["breakdown"] = {"device_ops": c["trace"]["device_ops"],
                                "idle_gaps": c["trace"]["idle_gaps"]}
        w0, w1 = c["window_stats"]

        def grew(key: str, name: str) -> int:
            return int((w1.get(key) or {}).get(name, 0)
                       - (w0.get(key) or {}).get(name, 0))

        out["window"] = {
            "seconds": a.seconds,
            "lowerings": sum((w1.get("events") or {}).values())
            - sum((w0.get("events") or {}).values()),
            "snapshots": grew("spans", "mg.snapshot"),
            "resends": sum(op.resends for op in ops),
            "outcomes": {k: sum(1 for op in ops if op.outcome == k)
                         for k in ("deadline", "shed", "wrong")},
            "clock": c["clock"],
            "rate_by_third": [
                sum(1 for op in bench_reduce.acked_in_window(c)
                    if i <= 3 * (op.t_end - c["t0"]) / a.seconds < i + 1)
                / (a.seconds / 3) for i in range(3)],
            "generator_cpu_share": (
                (cpu1.user + cpu1.system - cpu0.user - cpu0.system)
                / max(1e-9, cpu1.elapsed - cpu0.elapsed)),
            "all_ops": len(self.hist.ops),
        }
        out["compared"] = compared
        return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # for the tests and the control runs only
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--stand-in", choices=bench_ref.BREAKS, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--fault", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--keep", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = Run(args, bench).run()
    for name, c in out["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
