"""The one process of a run that touches the chip: ``etcd_tpu.cli``'s
own ``main`` — so the server is built by ``cli.start_multigroup`` with
the flags given and nothing else — plus a control thread through which
the run's parent reads the registry, the device and the profiler.

    bench_launcher.py [--need-chips N] [--allow-cpu] [--trace-dir D]
                      [--fault NAME] -- <etcd_tpu.cli flags>

Control protocol: one JSON object per line on stdin, one reply per
line on the ORIGINAL stdout (fd 1 is then pointed at stderr, where the
server logs).  The first line written is ``{"ready": true, "device":
...}``; a process that finds no TPU, or fewer chips than needed, exits
3 instead and never falls back.

``stats``        the registry (light), span counts, lowerings and the
                 persistent-cache misses counted so far, the device's
                 memory
``trace_start``  start ``jax.profiler`` (``--trace-dir`` starts it
                 before the server is built: a restart's trace)
``trace_stop``   stop it
``trace_reduce`` reduce the stopped trace (``bench_reduce``)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)


class Control:
    def __init__(self, out, trace_dir: str | None):
        self.out = out
        self.trace_dir = trace_dir
        self.events: dict[str, int] = {}

    def listen_for_compiles(self) -> None:
        import jax.monitoring

        def on_event(name: str, *a, **kw) -> None:
            if "compil" in name or "lower" in name:
                self.events[name] = self.events.get(name, 0) + 1

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_event)

    def device(self) -> dict:
        import jax

        devs = jax.devices()
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devs]
        peaks = [p for p in peaks if p is not None]
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs),
                "memory_peak_bytes": max(peaks) if peaks else None}

    def start_trace(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)

    def handle(self, cmd: dict) -> dict:
        what = cmd.get("cmd")
        reply: dict = {"cmd": what, "t": time.time()}
        if what == "stats":
            from etcd_tpu.obs import metrics
            from etcd_tpu.utils.trace import tracer

            reply["registry"] = metrics.registry.snapshot(light=True)
            reply["events"] = dict(self.events)
            reply["device"] = self.device()
            reply["spans"] = {k: v.get("count", 0)
                              for k, v in tracer.snapshot().items()}
        elif what == "trace_start":
            self.trace_dir = cmd["dir"]
            self.start_trace()
        elif what == "trace_stop":
            import jax

            jax.profiler.stop_trace()
        elif what == "trace_reduce":
            import bench_reduce

            reply["trace"] = bench_reduce.reduce_trace_dir(
                self.trace_dir, cmd.get("patterns") or {})
        else:
            reply["error"] = f"unknown command {what!r}"
        return reply

    def loop(self) -> None:
        for line in sys.stdin:
            try:
                reply = self.handle(json.loads(line))
            except Exception as e:  # noqa: BLE001 - reported to the parent
                reply = {"error": f"{type(e).__name__}: {e}"}
            self.out.write(json.dumps(reply) + "\n")
            self.out.flush()
        os._exit(0)                    # the parent is gone


def plant_fault(name: str) -> None:
    """Break the timed path underneath (tests only): what the store
    applies for one write in twenty is altered, or nothing is applied
    and the write is acknowledged all the same."""
    from etcd_tpu.server import multigroup

    real = multigroup.apply_request_to_store
    calls = [0]

    def broken(store, r):
        calls[0] += 1
        if r.method == "PUT" and calls[0] % 20 == 0:
            if name == "alter_answer":
                r.val = r.val[:-1] + ("#" if r.val[-1:] != "#" else "%")
            else:
                resp = real(store, r)
                prev = resp.event.prev_node if resp.event else None
                if prev is not None:   # acknowledged, the state unchanged
                    store.set(r.path, False, prev.value, None)
                return resp
        return real(store, r)

    if name not in ("alter_answer", "drop_apply"):
        raise SystemExit(f"unknown fault {name!r}")
    multigroup.apply_request_to_store = broken


def main(argv: list[str]) -> int:
    split = argv.index("--")
    ap = argparse.ArgumentParser()
    ap.add_argument("--need-chips", type=int, default=1)
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--trace-dir", default=None)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv[:split])
    out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)

    from etcd_tpu import cli
    from etcd_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()
    control = Control(out, args.trace_dir)
    device = control.device()
    if device["platform"] != "tpu" and not args.allow_cpu:
        print(f"bench_launcher: no TPU (jax reports {device})",
              file=sys.stderr)
        return 3
    if device["platform"] == "tpu" and device["count"] < args.need_chips:
        print(f"bench_launcher: {device['count']} chips, the cell needs "
              f"{args.need_chips}", file=sys.stderr)
        return 3
    control.listen_for_compiles()
    # the program builds native/libwalscan.so on first use, which is the
    # replay of a restart: build it in set-up, never inside a window
    from etcd_tpu import native

    native.available()
    if args.fault:
        plant_fault(args.fault)
    if args.trace_dir:
        control.start_trace()
    out.write(json.dumps({"ready": True, "device": device}) + "\n")
    out.flush()
    threading.Thread(target=control.loop, daemon=True).start()
    return cli.main(argv[split + 1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
