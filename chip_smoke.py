#!/usr/bin/env python3
"""The quickest proof that etcd-tpu still starts on the chip.

    python chip_smoke.py [--seed N] [--legs a,b,...] [--keep]

Drives the served [G] path once through the entry points a user
calls, on one TPU, and exits 0 only if every leg was right:

- ``kernels``   the CRC kernels at the shapes the served path emits
                (replay width classes 128/384/2048 up to 2^17 rows,
                the 80 KB commit-frontier row at 10k groups, the
                monolithic lane's 64-byte floor, the snapshot hash at
                CHUNK=4096, the snapshot-stream verifier at 256 KiB
                + 4), each called directly and compared with
                ``google_crc32c``;
- ``cohosted``  ``python -m etcd_tpu.cli --cohosted-groups 10000
                --cohosted-members 5 --storage-backend tpu``
                (BASELINE config 4): acknowledged 256 B PUTs over
                HTTP spread over 1000 tenants, every key read back,
                the server stopped and restarted on the same data dir
                (strict device replay route), every key read again;
- ``dist``      three ``DistServer`` members in ONE process with the
                CLI's constructor arguments at g=1024, real loopback
                peer frames, PUTs through the leader, every key read
                with the default linearizable GET from each member,
                stop, rebuild from the data dirs, re-elect, read again;
- ``cohosted_mesh`` / ``dist_mesh``  the same two on four chips
                (``--cohosted-mesh-devices 4``; one member per chip),
                added when JAX reports >= 4 devices.

One process per chip: this parent never imports jax; every leg that
needs the chip is a child, and children run strictly one after
another.  Nothing is caught and carried past: a failed check raises,
the exit code is non-zero and no result line is printed.  It fails
without an accelerator (``JAX_PLATFORMS=cpu`` included), when
``native/libwalscan.so`` cannot be rebuilt, and after each restart on
a ``device_error``, a ``host`` replay route or a "falling back to host
path" line.  The last line of stdout is one JSON object naming the
device as JAX reported it from inside the process that held it.
"""

from __future__ import annotations

import argparse
import contextlib
import http.client
import json
import os
import random
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from etcd_tpu.api.client import Client, ClientError  # noqa: E402
from etcd_tpu.utils.jaxenv import (  # noqa: E402  (jax-free at import)
    CACHE_ENV,
    DEFAULT_CACHE_DIR,
)

VALUE_BYTES = 256

#: the sizes of a real run; tests call the leg functions with tiny ones
COHOSTED = dict(g=10_000, members=5, puts=1000, tenants=1000,
                clients=8)
DIST = dict(g=1024, puts=300)

#: (width, rows) for the direct kernel checks — the stream lane's
#: width classes at the one row count each ships in
#: (``replay_device._tile_rows``: 8 MiB of rows at the default
#: chunk, the config-4 frontier row among them), the power-of-two
#: counts of the monolithic lane (floor 8, ceiling 2^17) and its
#: floor class
KERNEL_SHAPES = (
    (128, 8), (128, 1 << 16), (128, 1 << 17),
    (384, 8), (384, 1 << 14), (384, 1 << 17),
    (2048, 8), (2048, 1 << 12), (2048, 1 << 17),
    (131072, 64),
    (64, 8), (64, 1024),
)
SNAP_HASH_BYTES = (8 << 20) + 12345
SNAP_STREAM_CHUNKS = 6

DEVICE_LINE = re.compile(
    r"jax devices: platform=(\S+) device_kind=(.+?) count=(\d+) ")
ROUTE_LINE = re.compile(r"etcdserver: (\w+)-route replay of (\d+) "
                        r"entries \((\d+) bytes")
#: any of these in a ``--storage-backend tpu`` server's log means the
#: device was not what ran
FORBIDDEN_LOG = ("falling back to host path", "device probe failed",
                 "host-route replay")


class SmokeError(RuntimeError):
    """A check of the smoke did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# -- data ---------------------------------------------------------------------


def make_kv(seed: int, n: int, tenants: int) -> list[tuple[str, str]]:
    """``n`` (key, 256 B value) pairs spread over ``tenants`` first
    path segments, a pure function of ``seed``."""
    rng = random.Random(seed)
    alphabet = ("abcdefghijklmnopqrstuvwxyz"
                "ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789")
    return [(f"/t{i % tenants:05d}/k{i:06d}",
             "".join(rng.choices(alphabet, k=VALUE_BYTES)))
            for i in range(n)]


def free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


# -- HTTP client side (jax-free: runs in the parent) --------------------------


def _retrying(fn, what: str, attempts: int = 40, pause: float = 0.25):
    """Call ``fn`` until it ANSWERS.  A timeout or a fail-closed error
    is the system declining to acknowledge, which it may do while a
    round compiles or a leader is being elected, so the same request
    is sent again (PUTs here are idempotent); it is never a licence
    to accept a wrong answer — values are compared by the caller.
    Returns ``(result, retries)``."""
    last: Exception | None = None
    for i in range(attempts):
        try:
            return fn(), i
        except (ClientError, OSError, http.client.HTTPException) as e:
            # a 404 IS an answer: the key is gone
            check(not (isinstance(e, ClientError) and e.code == 404),
                  f"{what}: key not found")
            last = e
            time.sleep(pause)
    raise SmokeError(f"{what}: no answer in {attempts} attempts "
                     f"(last: {last!r})")


def _fan_out(work: list, clients: int, one, counted: str) -> dict:
    """Run ``one(client_cache, item) -> retries`` over ``work`` from
    ``clients`` concurrent closed-loop clients; the first failure of
    any client fails the whole phase.  ``counted`` names the tally."""
    retries = [0] * clients
    errors: list[BaseException] = []

    def worker(w: int) -> None:
        cache: dict[str, Client] = {}
        try:
            for item in work[w::clients]:
                retries[w] += one(cache, item)
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return {counted: len(work), "retries": sum(retries),
            "wall_s": round(time.monotonic() - t0, 1)}


def _client(cache: dict, url: str) -> Client:
    if url not in cache:
        cache[url] = Client([url], timeout=10.0)
    return cache[url]


def put_all(url: str, kv, clients: int) -> dict:
    """PUT every pair through ``url`` from ``clients`` concurrent
    clients; every PUT must be acknowledged with its own value."""
    def one(cache, item) -> int:
        key, val = item
        out, n = _retrying(lambda: _client(cache, url).set(key, val),
                           f"PUT {key}")
        check(out.get("node", {}).get("value") == val,
              f"PUT {key}: acknowledged a different value")
        return n

    return _fan_out(list(kv), clients, one, "acked")


def get_all(urls: list[str], kv, clients: int = 8) -> dict:
    """Read every key back with the DEFAULT GET from every url; each
    answer must carry exactly the acknowledged value."""
    def one(cache, item) -> int:
        u, key, val = item
        out, n = _retrying(lambda: _client(cache, u).get(key),
                           f"GET {key} from {u}")
        got = out.get("node", {}).get("value")
        check(got == val, f"GET {key} from {u}: read {got!r:.40}, "
                          f"acknowledged {val!r:.40}")
        return n

    return _fan_out([(u, k, v) for u in urls for k, v in kv], clients,
                    one, "read")


# -- native -------------------------------------------------------------------


def rebuild_native() -> None:
    """Rebuild ``native/libwalscan.so`` from ``walscan.cc`` (the .so on
    disk is git-ignored and may come from another machine; the loader
    would reuse it by mtime) and require the bindings to load."""
    subprocess.run(["make", "-C", os.path.join(HERE, "native"), "-B",
                    "libwalscan.so"], check=True)
    from etcd_tpu import native

    check(native.available(), "native.available() is False after a "
                              "successful rebuild")
    say("native/libwalscan.so rebuilt, bindings load")


# -- leg: co-hosted, through the CLI ------------------------------------------


class CliServer:
    """One ``python -m etcd_tpu.cli`` child and its log."""

    def __init__(self, argv: list[str], log_path: str):
        self.log_path = log_path
        self._log = open(log_path, "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "etcd_tpu.cli", *argv], cwd=HERE,
            stdout=self._log, stderr=subprocess.STDOUT)

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def wait_listening(self, timeout: float) -> str:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            text = self.log_text()
            if "Listening for client requests" in text:
                return text
            check(self.proc.poll() is None,
                  f"server exited with {self.proc.returncode} before "
                  f"listening:\n{text[-3000:]}")
            time.sleep(0.2)
        raise SmokeError(f"server not listening after {timeout:.0f}s:"
                         f"\n{self.log_text()[-3000:]}")

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()


def _check_server_log(text: str, expect_platform: str) -> dict:
    m = DEVICE_LINE.search(text)
    check(m is not None, "server never logged its jax devices")
    device = {"platform": m.group(1), "kind": m.group(2),
              "count": int(m.group(3))}
    check(device["platform"] == expect_platform,
          f"server holds platform {device['platform']!r}, expected "
          f"{expect_platform!r}")
    for bad in FORBIDDEN_LOG:
        check(bad not in text, f"server log has {bad!r}:\n"
              + "\n".join(l for l in text.splitlines() if bad in l))
    return device


def leg_cohosted(workdir: str, *, seed: int, expect_platform: str,
                 g: int, members: int, puts: int, tenants: int,
                 clients: int, mesh_devices: int = 0,
                 start_timeout: float = 600.0) -> dict:
    data_dir = os.path.join(workdir, "data")
    url = f"http://127.0.0.1:{free_ports(1)[0]}"
    argv = ["--name", "smoke", "--data-dir", data_dir,
            "--cohosted-groups", str(g),
            "--cohosted-members", str(members),
            "--storage-backend", "tpu",
            "--listen-client-urls", url,
            "--advertise-client-urls", url]
    if mesh_devices:
        argv += ["--cohosted-mesh-devices", str(mesh_devices)]
    kv = make_kv(seed, puts, tenants)
    out: dict = {"g": g, "members": members,
                 "mesh_devices": mesh_devices}

    t0 = time.monotonic()
    srv = CliServer(argv, os.path.join(workdir, "server1.log"))
    try:
        srv.wait_listening(start_timeout)
        out["start_s"] = round(time.monotonic() - t0, 1)
        out["put"] = put_all(url, kv, clients)
        out["get"] = get_all([url], kv)
    finally:
        srv.stop()
    text = srv.log_text()
    out["device"] = _check_server_log(text, expect_platform)
    out["placement"] = [l.split("placement ", 1)[1]
                        for l in text.splitlines() if "placement " in l]
    say("cohosted server (pid %d) logged: platform=%s device_kind=%s "
        "count=%d" % (srv.proc.pid, out["device"]["platform"],
                      out["device"]["kind"], out["device"]["count"]))
    for line in out["placement"]:
        say(f"cohosted placement {line}")
    say(f"cohosted g={g}: {out['put']} {out['get']}")

    # the same flags on the same data dir: restart replay must take
    # the strict device route and bring every acknowledged key back
    t0 = time.monotonic()
    srv = CliServer(argv, os.path.join(workdir, "server2.log"))
    try:
        srv.wait_listening(start_timeout)
        out["restart_s"] = round(time.monotonic() - t0, 1)
        out["get_after_restart"] = get_all([url], kv)
    finally:
        srv.stop()
    text = srv.log_text()
    _check_server_log(text, expect_platform)
    m = ROUTE_LINE.search(text)
    check(m is not None, "restarted server logged no replay route:\n"
          + text[-2000:])
    check(m.group(1) == "stream",
          f"restart replay took the {m.group(1)!r} route under "
          f"--storage-backend tpu")
    out["replay"] = {"route": m.group(1), "entries": int(m.group(2)),
                     "wal_bytes": int(m.group(3))}
    say(f"cohosted restart: {out['replay']} "
        f"{out['get_after_restart']}")
    return out


# -- legs that hold the chip themselves (run in a child) ----------------------


def _device_in_child(expect_platform: str) -> dict:
    from etcd_tpu.utils.jaxenv import (
        configure_compile_cache,
        describe_devices,
    )

    cache = configure_compile_cache()
    device = describe_devices()
    say(f"platform={device['platform']} device_kind={device['kind']} "
        f"count={device['count']} compile_cache={cache}")
    check(device["platform"] == expect_platform,
          f"jax.devices()[0].platform is {device['platform']!r}, "
          f"expected {expect_platform!r}")
    return device


def _device_bytes() -> list[dict]:
    import jax

    return [{"device": d.id,
             "bytes_in_use": (d.memory_stats() or {}).get(
                 "bytes_in_use")} for d in jax.devices()]


def leg_kernels(*, seed: int, expect_platform: str,
                shapes=KERNEL_SHAPES,
                snap_hash_bytes: int = SNAP_HASH_BYTES,
                snap_stream_chunks: int = SNAP_STREAM_CHUNKS,
                interpret: bool = False) -> dict:
    """Every kernel shape, called directly, against google_crc32c.
    ``interpret`` is passed only by the CPU test; nothing here picks
    interpret mode by itself."""
    import google_crc32c
    import jax
    import numpy as np

    from etcd_tpu.crc import gf2
    from etcd_tpu.ops import crc_device
    from etcd_tpu.ops.crc_device import contribution_matrix
    from etcd_tpu.ops.crc_kernel import CHUNK, device_crc32c
    from etcd_tpu.ops.crc_pallas import raw_crc_pallas
    from etcd_tpu.snap.stream import DEFAULT_CHUNK_BYTES, ChunkVerifier

    device = _device_in_child(expect_platform)
    on_tpu = device["platform"] == "tpu"
    check(crc_device._default_use_pallas() == on_tpu,
          "raw_crc_batch would not take the Pallas kernel on a TPU")
    rng = np.random.default_rng(seed)
    mask = 0xFFFFFFFF
    rows_out = []
    for width, n in shapes:
        buf = rng.integers(0, 256, size=(n, width), dtype=np.uint8)
        sample = np.arange(n) if n <= 1024 else np.sort(
            rng.choice(n, 1024, replace=False))
        # raw state of a full-width row = Go-convention CRC ^ A[width]
        fix = gf2.matvec(gf2.zero_operator(width), mask) ^ mask
        ref = np.asarray([google_crc32c.value(buf[i].tobytes()) ^ fix
                          for i in sample], np.uint32)
        dev = jax.device_put(buf)
        forms = {"raw_crc_batch": crc_device.raw_crc_batch(dev)}
        if on_tpu or interpret:
            forms["raw_crc_pallas"] = raw_crc_pallas(
                dev, jax.device_put(contribution_matrix(width)),
                interpret=interpret)
        for name, got in forms.items():
            got = np.asarray(got)
            check(got.shape == (n,), f"{name} [{n},{width}]: shape "
                                     f"{got.shape}")
            check(bool((got[sample] == ref).all()),
                  f"{name} [{n},{width}] disagrees with google_crc32c")
        rows_out.append([width, n, sorted(forms)])
        say(f"kernel [{n},{width}] ok: {sorted(forms)}")

    # the snapshot hash exactly as Snapshotter calls it (CHUNK=4096)
    blob = rng.integers(0, 256, size=snap_hash_bytes, dtype=np.uint8)
    check(device_crc32c(blob) == google_crc32c.value(blob.tobytes()),
          f"device_crc32c({snap_hash_bytes} B, chunk {CHUNK}) "
          f"disagrees with google_crc32c")
    say(f"device_crc32c {snap_hash_bytes} B at CHUNK={CHUNK} ok")

    # the snapshot-stream verifier on its device route: rows of
    # DEFAULT_CHUNK_BYTES + 4, a short tail, and one corrupt chunk
    payload = rng.integers(
        0, 256, size=DEFAULT_CHUNK_BYTES * (snap_stream_chunks - 1)
        + 777, dtype=np.uint8).tobytes()
    chunks = [payload[o:o + DEFAULT_CHUNK_BYTES]
              for o in range(0, len(payload), DEFAULT_CHUNK_BYTES)]
    stored, prev = [], 0
    for c in chunks:
        prev = google_crc32c.extend(prev, c)
        stored.append(prev)
    prevs = [0] + stored[:-1]
    verifier = ChunkVerifier(route="device")
    check(verifier.verify(chunks, prevs, stored)
          == [True] * len(chunks),
          "ChunkVerifier(device) rejected a good chunk chain")
    bad = list(chunks)
    bad[1] = bytes([bad[1][0] ^ 1]) + bad[1][1:]
    verdict = verifier.verify(bad, prevs, stored)
    check(verdict == [True, False] + [True] * (len(chunks) - 2),
          f"ChunkVerifier(device) verdicts on a corrupt chunk: "
          f"{verdict}")
    say(f"ChunkVerifier(device) {len(chunks)} x "
        f"{DEFAULT_CHUNK_BYTES + 4} B rows ok")

    out = {"device": device, "shapes": rows_out,
           "device_bytes": _device_bytes()}
    if device["count"] >= 4:
        out["mesh_placement"] = _mesh_placement(device["count"])
    return out


def _mesh_placement(count: int) -> dict:
    """Four chips: which rows of a [G]-leading array each device holds
    under the 2x2 ``(g, s)`` mesh and under the serving ``g`` mesh."""
    import jax.numpy as jnp

    from etcd_tpu.parallel.mesh import (
        group_mesh,
        serving_mesh,
        shard_leading,
    )
    from etcd_tpu.utils.jaxenv import log_placement

    x = jnp.zeros((1024, 8), jnp.int32)
    out = {"group_mesh": log_placement(
        "group_mesh(4)", shard_leading(group_mesh(4), x)),
        "serving_mesh": log_placement(
        "serving_mesh(4)", shard_leading(serving_mesh(4), x))}
    for name, rows in out.items():
        say(f"{name}(4): " + ", ".join(
            f"dev{r['device']}=rows{r['rows']}" for r in rows))
    check(sorted(tuple(r["rows"]) for r in out["serving_mesh"])
          == [(i * 256, (i + 1) * 256) for i in range(4)],
          "serving_mesh(4) does not split the group axis four ways")
    return out


def leg_dist(workdir: str, *, seed: int, expect_platform: str,
             g: int, puts: int, member_devices: bool = False,
             election_ticks: int = 60) -> dict:
    """Three DistServers in this process, built and started by the
    functions ``cli.start_dist`` builds and starts its
    ``--dist-local-cluster`` with, each behind its own HTTP front
    door."""
    import logging

    import numpy as np

    from etcd_tpu import cli
    from etcd_tpu.server import DEFAULT_SNAP_COUNT
    from etcd_tpu.server.frontdoor import serve_frontdoor
    from etcd_tpu.wal.backend_policy import get_policy

    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(name)s: %(message)s")
    device = _device_in_child(expect_platform)
    kv = make_kv(seed, puts, puts)

    def mesh_for(slot: int):
        if not member_devices:
            return None
        import jax
        from jax.sharding import Mesh

        return Mesh(np.asarray([jax.devices()[slot]]), ("g",))

    def build() -> list:
        return cli.local_dist_members(
            workdir, 3, name="smoke", mesh_of=mesh_for, g=g,
            snap_count=DEFAULT_SNAP_COUNT, election=election_ticks,
            storage_backend="tpu", peer_tls=None, pipeline_depth=8,
            coalesce_us=2000, lease_ticks=30)

    def check_policy() -> dict:
        snap = get_policy().snapshot()
        probe = snap.get("probe", {})
        check("device_error" not in probe,
              f"replay router: device_error {probe.get('device_error')}")
        dec = snap["decisions"].get("restart")
        check(dec is not None, "replay router took no restart decision")
        check(dec["route"] == "stream",
              f"restart replay took the {dec['route']!r} route under "
              f"storage_backend='tpu' ({dec['why']})")
        if expect_platform != "cpu":
            check(probe.get("device_verify_bps") is not None,
                  "replay router's device probe measured nothing")
        return dec

    def wait_led(servers, timeout: float = 180.0) -> float:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            led = cli.dist_groups_led(servers)
            if led == g:
                return round(time.monotonic() - t0, 1)
            time.sleep(0.25)
        raise SmokeError(f"{g - led} of {g} groups have no leader "
                         f"after {timeout:.0f}s")

    def max_term(servers) -> int:
        return int(max(np.asarray(s.mr.state.term).max()
                       for s in servers))

    @contextlib.contextmanager
    def serving(servers):
        """Start the members the way ``cli.start_dist`` does — slot 0
        of a brand-new cluster campaigns — each behind its own HTTP
        front door on a port of the system's choosing; yields the
        doors' URLs and stops everything on the way out."""
        doors = []
        try:
            for s in servers:
                doors.append(serve_frontdoor(s, "127.0.0.1", 0))
                s.client_urls = ["http://%s:%d"
                                 % doors[-1].server_address[:2]]
            cli.start_dist_members(servers)
            yield [s.client_urls[0] for s in servers]
        finally:
            for d in doors:
                d.shutdown()
            for s in servers:
                check(s.stop(), "DistServer.stop(): round loop wedged")

    out: dict = {"g": g, "device": device,
                 "member_devices": member_devices}
    servers = build()
    check(all(s.fresh for s in servers), "data dirs are not fresh")
    with serving(servers) as urls:
        out["elect_s"] = wait_led(servers)
        out["put"] = put_all(urls[0], kv, clients=4)
        out["get"] = get_all(urls, kv)
        # election churn: a cold compile that stalls the round loops
        # past the band would show as terms racing upward
        out["max_term"] = max_term(servers)
    say(f"dist g={g}: elect {out['elect_s']}s max_term "
        f"{out['max_term']} {out['put']} {out['get']}")

    # rebuild from the data dirs (DistServer._restart), re-elect, read
    t0 = time.monotonic()
    servers = build()
    out["replay"] = check_policy()
    out["rebuild_s"] = round(time.monotonic() - t0, 1)
    check(not any(s.fresh for s in servers),
          "restart found a fresh data dir")
    with serving(servers) as urls:
        out["reelect_s"] = wait_led(servers)
        out["get_after_restart"] = get_all(urls, kv)
        out["max_term_after_restart"] = max_term(servers)
    out["device_bytes"] = _device_bytes()
    say(f"dist restart: {out['replay']['route']} route, re-elect "
        f"{out['reelect_s']}s max_term {out['max_term_after_restart']} "
        f"{out['get_after_restart']} device_bytes "
        f"{out['device_bytes']}")
    return out


# -- orchestration ------------------------------------------------------------


def run_child(leg: str, workdir: str, seed: int) -> dict:
    """Run one chip-holding leg in a child of its own and return what
    it wrote; a child that fails fails the smoke."""
    result = os.path.join(workdir, "result.json")
    subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", leg,
         "--workdir", workdir, "--seed", str(seed)], check=True)
    with open(result) as f:
        return json.load(f)


def child_main(leg: str, workdir: str, seed: int) -> None:
    if leg == "kernels":
        out = leg_kernels(seed=seed, expect_platform="tpu")
    elif leg == "dist":
        out = leg_dist(workdir, seed=seed, expect_platform="tpu",
                       **DIST)
    elif leg == "dist_mesh":
        out = leg_dist(workdir, seed=seed, expect_platform="tpu",
                       member_devices=True, **DIST)
    else:
        raise SmokeError(f"no child leg {leg!r}")
    with open(os.path.join(workdir, "result.json"), "w") as f:
        json.dump(out, f)


def run_leg(leg: str, workdir: str, seed: int) -> dict:
    os.makedirs(workdir)
    if leg == "cohosted":
        return leg_cohosted(workdir, seed=seed, expect_platform="tpu",
                            **COHOSTED)
    if leg == "cohosted_mesh":
        return leg_cohosted(workdir, seed=seed, expect_platform="tpu",
                            mesh_devices=4, **COHOSTED)
    return run_child(leg, workdir, seed)


LEGS = ("kernels", "cohosted", "dist", "cohosted_mesh", "dist_mesh")


def cache_entries() -> int:
    """Entries in the persistent compile cache: what a leg adds is
    what it compiled and could not find there."""
    try:
        return len(os.listdir(os.environ.get(CACHE_ENV)
                              or DEFAULT_CACHE_DIR))
    except FileNotFoundError:
        return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--legs", default="",
                    help="comma-separated subset of " + ",".join(LEGS)
                    + " (default: the first three, plus the _mesh "
                    "legs when the first child reports >= 4 devices)")
    ap.add_argument("--keep", action="store_true",
                    help="keep the work directory (data dirs, logs)")
    ap.add_argument("--child", default="", help=argparse.SUPPRESS)
    ap.add_argument("--workdir", default="", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        child_main(args.child, args.workdir, args.seed)
        return 0

    legs = [l for l in args.legs.split(",") if l]
    for l in legs:
        if l not in LEGS:
            ap.error(f"unknown leg {l!r}")
    t_start = time.monotonic()
    rebuild_native()
    root = tempfile.mkdtemp(prefix="chip_smoke_")
    say(f"work directory {root}")
    results: dict[str, dict] = {}
    try:
        queue = list(legs or LEGS[:3])
        while queue:
            leg = queue.pop(0)
            t0, cached = time.monotonic(), cache_entries()
            say(f"leg {leg} ...")
            results[leg] = run_leg(leg, os.path.join(root, leg),
                                   args.seed)
            results[leg]["wall_s"] = round(time.monotonic() - t0, 1)
            results[leg]["compiled_new"] = cache_entries() - cached
            say(f"leg {leg} ok in {results[leg]['wall_s']}s, "
                f"{results[leg]['compiled_new']} new compile-cache "
                f"entries")
            first = next(iter(results.values()))["device"]
            check(results[leg]["device"] == first,
                  f"leg {leg} ran on {results[leg]['device']}, the "
                  f"first on {first}")
            if not legs and len(results) == 3 and first["count"] >= 4:
                queue += LEGS[3:]
    finally:
        if args.keep:
            say(f"kept {root}")
        else:
            shutil.rmtree(root, ignore_errors=True)
    device = next(iter(results.values()))["device"]
    check(device["platform"] == "tpu", f"not a TPU: {device}")
    say("summary " + json.dumps(results))
    say(f"all legs ok in {time.monotonic() - t_start:.0f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
