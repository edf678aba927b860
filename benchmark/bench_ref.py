"""The plain reference and the comparison that decides ``correct``.

The reference is a register per record: the same writes in the same
order give the same value.  Every record has one writer, so the order
of its writes is the order in which that client sent them, and what a
read may return follows from the clocks of the client side alone:

- an acknowledged PUT carries the value that was sent;
- a GET that began after write ``a`` of its record was acknowledged
  returns write ``a`` or a later one that had been sent before the
  GET ended (a write the client gave up on may still commit, so it
  stays allowed) — never an older one: that is a stale read;
- so once every client has stopped, each record reads as its last
  acknowledged write, or a later unacknowledged one: anything else is
  a lost write.

It imports nothing of the program.  Run as a program it is the
reference put in the program's place: a small HTTP ``/v2/keys`` server
over a dict with a write-ahead file, which the control runs start with
one guarantee of the configuration broken (``--break``).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

#: every number compared is an exact count with the limit 0
LIMITS = {"wrong_answers": 0, "stale_reads": 0, "lost_writes": 0,
          "unanswered_readbacks": 0}


def floor_seq(writes, t: float) -> int:
    """Sequence of the last write acknowledged at or before ``t``
    (0: none).  One writer sends them in order, so acknowledged
    times rise with the sequence."""
    best = 0
    for w in writes:
        if w.t_ack <= t:
            best = w.seq
    return best


def read_allowed(writes, seq: int, t_first: float, t_end: float) -> bool:
    """May a GET that ran over ``[t_first, t_end]`` return write
    ``seq`` (-1: "key not found")?"""
    floor = floor_seq(writes, t_first)
    if seq == -1:
        return floor == 0
    if seq < max(floor, 1) or seq > len(writes):
        return False
    return writes[seq - 1].t_first <= t_end


def compare(ops) -> dict:
    """The numbers that decide ``correct``, each beside its limit.
    ``ops`` is everything the clients did, set-up included, each
    judged by its own clocks against its record's writes.  The GETs
    of a ``readback`` phase read every written record after the
    clients stopped (in a restart cell also right after the restart):
    one of them that is not allowed is a lost write."""
    def judged(op) -> bool:
        return op.kind == "get" and op.outcome == "ack"

    def readback(op) -> bool:
        return op.phase.startswith("readback")

    def allowed(op) -> bool:
        return read_allowed(op.writes, op.seq, op.t_first, op.t_end)

    values = {
        "wrong_answers": sum(1 for op in ops if op.outcome == "wrong"),
        "stale_reads": sum(1 for op in ops if judged(op)
                           and not readback(op) and not allowed(op)),
        "lost_writes": sum(1 for op in ops if judged(op)
                           and readback(op) and not allowed(op)),
        "unanswered_readbacks": sum(
            1 for op in ops if readback(op)
            and op.outcome in ("deadline", "shed")),
    }
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}


def is_correct(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())


# -- the reference in the program's place (control runs only) -----------------

BREAKS = ("none", "ack_without_commit", "stale_read", "no_fsync",
          "alter_answer")
EVERY = 20                     # a broken guarantee bites one call in EVERY


class RefStore:
    """A dict with a write-ahead file: the semantics of the served
    path with none of its machinery."""

    def __init__(self, data_dir: str, broken: str):
        self.broken = broken
        self.lock = threading.Lock()
        self.kv: dict[str, str] = {}
        self.old: dict[str, str] = {}
        self.calls = 0
        os.makedirs(data_dir, exist_ok=True)
        path = os.path.join(data_dir, "ref.wal")
        if os.path.exists(path):
            with open(path) as f:
                for line in f:
                    if line.endswith("\n"):
                        k, v = json.loads(line)
                        self.kv[k] = v
        self.wal = open(path, "a")
        self.unsynced: list[str] = []

    def _bites(self) -> bool:
        self.calls += 1
        return self.calls % EVERY == 0

    def put(self, key: str, value: str) -> str:
        with self.lock:
            if self.broken == "ack_without_commit" and self._bites():
                return value           # acknowledged, never applied
            if self.broken == "alter_answer" and self._bites():
                value = value[:-1] + ("#" if value[-1:] != "#" else "%")
            if key in self.kv:
                self.old[key] = self.kv[key]
            self.kv[key] = value
            line = json.dumps([key, value]) + "\n"
            if self.broken == "no_fsync":
                # acknowledged from memory; the file gets it 64 writes on
                self.unsynced.append(line)
                if len(self.unsynced) > 64:
                    self.wal.write(self.unsynced.pop(0))
                    self.wal.flush()
            else:
                self.wal.write(line)
                self.wal.flush()
                os.fsync(self.wal.fileno())
            return value

    def get(self, key: str) -> str | None:
        with self.lock:
            if (self.broken == "stale_read" and key in self.old
                    and self._bites()):
                return self.old[key]   # a replica that lags one write
            return self.kv.get(key)


def _handler(store: RefStore):
    class H(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):     # noqa: D102 - quiet
            pass

        def _reply(self, code: int, obj: dict) -> None:
            body = (json.dumps(obj) + "\n").encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_PUT(self):              # noqa: N802
            n = int(self.headers.get("Content-Length") or 0)
            form = urllib.parse.parse_qs(self.rfile.read(n).decode())
            key = self.path[len("/v2/keys"):]
            value = store.put(key, form.get("value", [""])[0])
            self._reply(200, {"action": "set",
                              "node": {"key": key, "value": value}})

        def do_GET(self):              # noqa: N802
            key = self.path[len("/v2/keys"):]
            value = store.get(key)
            if value is None:
                self._reply(404, {"errorCode": 100,
                                  "message": "Key not found", "cause": key})
            else:
                self._reply(200, {"action": "get",
                                  "node": {"key": key, "value": value}})
    return H


def serve(argv: list[str]) -> int:
    """Speak the launcher's control protocol (``bench_launcher.py``) as
    far as a server with no device and no registry can."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--break", dest="broken", choices=BREAKS,
                    default="none")
    ap.add_argument("--data-dir", required=True)
    ap.add_argument("--port", type=int, required=True)
    args = ap.parse_args(argv)
    control = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    store = RefStore(args.data_dir, args.broken)
    httpd = ThreadingHTTPServer(("127.0.0.1", args.port), _handler(store))
    httpd.daemon_threads = True
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    print("Listening for client requests on reference stand-in "
          f"(break={args.broken})", file=sys.stderr, flush=True)
    device = {"platform": "none", "kind": "reference stand-in", "count": 0}
    control.write(json.dumps({"ready": True, "device": device}) + "\n")
    control.flush()
    for line in sys.stdin:
        cmd = json.loads(line)
        reply = {"cmd": cmd.get("cmd"), "t": time.time(), "device": device}
        control.write(json.dumps(reply) + "\n")
        control.flush()
    return 0


if __name__ == "__main__":
    sys.exit(serve(sys.argv[1:]))
