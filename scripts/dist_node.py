"""Standalone distributed-multigroup node (one member slot per
process) — the runner behind the kill -9 integration test and
`scripts/dist-cluster`.

Usage:
  python scripts/dist_node.py --data-dir D --slot N \
      --peers http://127.0.0.1:7700,http://127.0.0.1:7701,... \
      [--groups 8] [--bootstrap]

Prints "READY" once serving (and, with --bootstrap, once this node
leads every group).  Writes arrive via POST /mraft/propose (a
marshaled wire Request); peers exchange batched frames on /mraft.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# One member per PROCESS is a CPU layout: a chip belongs to one
# process, so M of these cannot share it.  JAX_PLATFORMS is honoured
# when the caller sets it (the on-chip cluster is three members in
# one process — chip_smoke.py); the default says CPU out loud.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

from etcd_tpu.server.distserver import DistServer  # noqa: E402
from etcd_tpu.utils.jaxenv import configure_compile_cache  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data-dir", required=True)
    ap.add_argument("--slot", type=int, required=True)
    ap.add_argument("--peers", required=True,
                    help="comma-separated slot-indexed base URLs")
    ap.add_argument("--groups", type=int, default=8)
    ap.add_argument("--cap", type=int, default=64)
    ap.add_argument("--max-batch-ents", type=int, default=32)
    ap.add_argument("--pipeline-depth", type=int, default=8,
                    help="max in-flight append frames per peer "
                         "(1 = lockstep-equivalent)")
    ap.add_argument("--coalesce-us", type=int, default=2000)
    ap.add_argument("--lease-ticks", type=int, default=30,
                    help="leader-lease length in ticks for "
                         "linearizable reads (< election - drift; "
                         "0 = lease off, ReadIndex-only)")
    ap.add_argument("--snap-count", type=int, default=None,
                    help="applies between snapshots (snapshot + "
                         "segment GC cadence; default 10000)")
    ap.add_argument("--bootstrap", action="store_true",
                    help="campaign for every group before READY")
    args = ap.parse_args()

    configure_compile_cache()
    srv = DistServer(args.data_dir, slot=args.slot,
                     peer_urls=args.peers.split(","),
                     g=args.groups, cap=args.cap,
                     max_batch_ents=args.max_batch_ents,
                     tick_interval=0.05, post_timeout=2.0,
                     election=60,
                     pipeline_depth=args.pipeline_depth,
                     coalesce_us=args.coalesce_us,
                     snap_count=args.snap_count,
                     lease_ticks=args.lease_ticks)
    srv.start()

    # black-box dump on the way down (PR 8): SIGTERM (the bench's
    # teardown signal) or a crash writes the flight ring to
    # ETCD_FLIGHT_DIR (default: alongside the data dir) — forensics
    # survive the process
    from etcd_tpu.obs.flight import install_crash_dump

    install_crash_dump(srv.flight,
                       os.environ.get("ETCD_FLIGHT_DIR")
                       or os.path.join(args.data_dir,
                                       "trace_artifacts"))

    # SIGUSR1 dumps the tracer span table to stdout (profiling a real
    # cluster process from outside without stopping it)
    import signal as _signal

    prof = None
    if os.environ.get("ETCD_PROFILE_FRAMES"):
        # function-level attribution for the peer-frame hot path:
        # wrap handle_frame in a cProfile that accumulates across
        # calls.  cProfile is strictly single-tool-at-a-time, so a
        # lock serializes concurrent handler threads (this is a
        # diagnostic mode; the serialization is part of the price)
        import cProfile
        import threading as _threading

        prof = cProfile.Profile()
        _prof_lock = _threading.Lock()
        inner = srv.handle_frame

        def profiled(data):
            with _prof_lock:
                prof.enable()
                try:
                    return inner(data)
                finally:
                    prof.disable()

        srv.handle_frame = profiled

    def _dump(signum, frame):
        from etcd_tpu.utils.trace import tracer

        print("SPANS " + tracer.snapshot_json().decode(), flush=True)
        if prof is not None:
            import io
            import pstats

            s = io.StringIO()
            pstats.Stats(prof, stream=s).sort_stats(
                "cumulative").print_stats(25)
            print("PROFILE-BEGIN", flush=True)
            print(s.getvalue(), flush=True)
            print("PROFILE-END", flush=True)

    _signal.signal(_signal.SIGUSR1, _dump)
    if args.bootstrap:
        deadline = time.time() + 60.0
        while time.time() < deadline:
            lead = srv.mr.is_leader()
            if lead.all():
                break
            srv._campaign(~lead)
            time.sleep(0.3)
    print("READY", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        srv.stop()


if __name__ == "__main__":
    main()
