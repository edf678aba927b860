"""The chunked streaming replay pipeline (PR 3 tentpole).

Three proof obligations:

1. **Bit-exactness**: the chunked, GF(2)-seed-stitched scan+verify —
   on the fused host route AND the device stream route — produces
   arrays identical to the monolithic native scan, including chunk
   boundaries that split a record mid-frame, and raises the same
   typed errors (same first-bad-record, torn tails in the last
   chunk).
2. **Overlap**: under a fake transport whose stages each hold a
   chunk until the stage before has started the next one, the run
   ends — proving the double buffering actually overlaps the stages.
3. **Plumbing**: the sharded native chain verify agrees with the
   sequential sweep; per-chunk progress lands in the devledger.
"""

import os
import threading

import numpy as np
import pytest

from etcd_tpu import native
from etcd_tpu.wal import WAL
from etcd_tpu.wal.errors import CRCMismatchError, TornTailError
from etcd_tpu.wal.replay_device import (
    DeviceTransport,
    stream_scan_verify,
)
from etcd_tpu.wire import Entry, HardState

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library unavailable")


def _wal_blob(d, n_entries=120, cuts=(40, 80), sizes=None):
    w = WAL.create(str(d), b"meta")
    for i in range(n_entries):
        size = sizes[i] if sizes else 30 + (i * 7) % 200
        w.save_entry(Entry(term=1, index=i,
                           data=bytes([i % 256]) * size))
        if i + 1 in cuts:
            w.save_state(HardState(term=1, vote=3, commit=i))
            w.cut()
    w.sync()
    w.close()
    return np.concatenate([
        np.fromfile(os.path.join(str(d), f), np.uint8)
        for f in sorted(os.listdir(str(d)))])


def _assert_arrays_equal(a, b):
    assert len(a) == len(b) == 7
    for i, (x, y) in enumerate(zip(a, b)):
        assert np.array_equal(x, y), f"array {i} diverges"


# -- 1. bit-exactness ---------------------------------------------------------


@pytest.mark.parametrize("chunk_bytes", [257, 1024, 1 << 20])
def test_host_route_chunked_equals_monolithic(tmp_path, chunk_bytes):
    """Chunk boundaries at arbitrary byte positions (257: guaranteed
    mid-frame splits) must not change a single output value."""
    blob = _wal_blob(tmp_path / "wal")
    full = native.wal_scan(blob)
    got = stream_scan_verify(blob, route="host",
                             chunk_bytes=chunk_bytes)
    _assert_arrays_equal(full, got)


@pytest.mark.parametrize("chunk_bytes", [513, 4096])
def test_stream_route_chunked_equals_monolithic(tmp_path,
                                                chunk_bytes):
    """The device route (real transport on the in-process backend):
    GF(2)-stitched per-chunk verification, same arrays out."""
    blob = _wal_blob(tmp_path / "wal", n_entries=80)
    full = native.wal_scan(blob)
    got = stream_scan_verify(blob, route="stream",
                             chunk_bytes=chunk_bytes)
    _assert_arrays_equal(full, got)


def test_corruption_names_same_record_on_both_routes(tmp_path):
    blob = _wal_blob(tmp_path / "wal", cuts=())
    bad = blob.copy()
    bad[bad.size // 2] ^= 0xFF
    msgs = []
    for route in ("host", "stream"):
        with pytest.raises(CRCMismatchError, match="at record") as ei:
            stream_scan_verify(bad, route=route, chunk_bytes=777)
        msgs.append(str(ei.value).split("(")[0])
    assert msgs[0] == msgs[1]
    # and it is the same record the monolithic fused pass names
    with pytest.raises(native.NativeError) as ni:
        native.scan_verify(bad)
    assert f"at record {ni.value.bad_index} " in msgs[0]


@pytest.mark.parametrize("route", ["host", "stream"])
@pytest.mark.parametrize("cut", [1, 5, 9])
def test_torn_tail_in_last_chunk(tmp_path, route, cut):
    """A stream ending mid-record (torn frame header, torn body) is
    the typed TornTailError on every route."""
    blob = _wal_blob(tmp_path / "wal", n_entries=30, cuts=())
    torn = blob[:blob.size - cut].copy()
    with pytest.raises(TornTailError):
        stream_scan_verify(torn, route=route, chunk_bytes=512)


def test_empty_and_single_chunk_streams(tmp_path):
    blob = _wal_blob(tmp_path / "wal", n_entries=3, cuts=())
    for route in ("host", "stream"):
        got = stream_scan_verify(blob, route=route,
                                 chunk_bytes=1 << 30)  # one chunk
        _assert_arrays_equal(native.wal_scan(blob), got)
    empty = np.zeros(0, np.uint8)
    for route in ("host", "stream"):
        got = stream_scan_verify(empty, route=route, chunk_bytes=64)
        assert all(a.size == 0 for a in got)


def test_fused_scan_verify_matches_two_pass(tmp_path):
    """The fused single-pass native entry point (the 0.913x fix) is
    the two-pass scan + chain_verify, in one sweep."""
    blob = _wal_blob(tmp_path / "wal")
    full = native.wal_scan(blob)
    fused = native.scan_verify(blob)
    _assert_arrays_equal(full, fused)
    t, c, do, dl, *_ = full
    assert native.chain_verify(blob, do, dl, c) == t.size


def test_sharded_chain_verify_matches_sequential(tmp_path):
    blob = _wal_blob(tmp_path / "wal", n_entries=300, cuts=())
    t, c, do, dl, *_ = native.wal_scan(blob)
    assert native.chain_verify(blob, do, dl, c, threads=4) == t.size
    bad = blob.copy()
    bad[int(do[137])] ^= 0xFF
    seq = native.chain_verify(bad, do, dl, c)
    mt = native.chain_verify(bad, do, dl, c, threads=4)
    assert seq == mt == 137


# -- 2. overlap under a deterministic fake transport --------------------------


class _Stages:
    """How far each stage of the pipeline has come, and the one wait
    the overlap test is made of: a stage holds its chunk until
    another stage has STARTED a later one.  A pipeline that runs its
    stages one after another never lets that happen, and the wait
    runs to its deadline; how fast the machine is changes nothing."""

    DEADLINE_S = 60.0

    def __init__(self, chunks: int):
        self.chunks = chunks
        self.started = {"scan": 0, "h2d": 0}
        self.cond = threading.Condition()
        self.missed: list[str] = []

    def start(self, stage: str) -> int:
        """This call's chunk number within ``stage``."""
        with self.cond:
            k = self.started[stage]
            self.started[stage] = k + 1
            self.cond.notify_all()
        return k

    def hold_until(self, stage: str, k: int, who: str) -> None:
        """Return once ``stage`` has started chunk ``k`` (the last
        chunk has no later one to wait for)."""
        want = min(k + 1, self.chunks)
        with self.cond:
            if self.missed:
                return
            if not self.cond.wait_for(
                    lambda: self.started[stage] >= want,
                    timeout=self.DEADLINE_S):
                self.missed.append(
                    f"{who}: {stage} never started chunk {k}")


class _FakeTransport(DeviceTransport):
    """``ship`` is the H2D seam on the caller thread, ``verify``
    dispatches to a worker (the device working asynchronously),
    ``collect`` joins it.  Verification itself stays REAL (numpy
    host math over the injected-seed rows), so the overlap test also
    re-proves bit-exactness end to end.  With ``stages``, the H2D of
    chunk k holds until the scan of chunk k+1 has started, and the
    verify of chunk k until the H2D of chunk k+1 has."""

    def __init__(self, stages: _Stages | None = None):
        self.stages = stages
        self.verified = 0

    def ship(self, rows):
        if self.stages is not None:
            k = self.stages.start("h2d")
            self.stages.hold_until("scan", k + 1, f"h2d of chunk {k}")
        return rows

    def verify(self, shipped, stored):
        from etcd_tpu.crc import crc32c

        out = {}
        k = self.verified
        self.verified += 1

        def work():
            if self.stages is not None:
                self.stages.hold_until("h2d", k + 1,
                                       f"verify of chunk {k}")
            got = np.empty(shipped.shape[0], np.uint32)
            for i, row in enumerate(shipped):
                got[i] = crc32c.raw_update(0, row.tobytes()) \
                    ^ 0xFFFFFFFF
            out["ok"] = got == np.asarray(stored, np.uint32)

        th = threading.Thread(target=work, daemon=True)
        th.start()
        return (th, out)

    def collect(self, handle):
        th, out = handle
        th.join()
        return out["ok"]


def test_pipeline_stages_overlap(tmp_path, monkeypatch):
    """Host framing of chunk k+1 overlaps the H2D of chunk k and the
    device verify of chunk k-1: each stage of the fake transport
    holds its chunk until the stage before it has started the next
    one, so the run ends only if the stages really run side by
    side."""
    # chunk budget 1 byte -> every record is its own chunk (10
    # entries + the segment's crc/metadata head records = 12 chunks)
    blob = _wal_blob(tmp_path / "wal", n_entries=10, cuts=(),
                     sizes=[64] * 10)
    want = native.wal_scan(blob)
    stages = _Stages(chunks=int(want[0].size))
    real_scan = native.scan_chunk

    def scan(*a, **k):
        stages.start("scan")
        return real_scan(*a, **k)

    monkeypatch.setattr(native, "scan_chunk", scan)
    fake = _FakeTransport(stages)
    got = stream_scan_verify(blob, route="stream", chunk_bytes=1,
                             transport=fake)
    assert not stages.missed, stages.missed
    _assert_arrays_equal(want, got)
    assert stages.started == {"scan": stages.chunks,
                              "h2d": stages.chunks}
    assert stages.chunks >= 9  # really chunked


def test_pipeline_fake_transport_catches_corruption(tmp_path):
    blob = _wal_blob(tmp_path / "wal", n_entries=20, cuts=())
    bad = blob.copy()
    t, c, do, dl, *_ = native.wal_scan(blob)
    # flip deep inside record 11's payload bytes (not the proto tag
    # bytes at the span head — that would be a parse error, not CRC)
    bad[int(do[11]) + int(dl[11]) - 3] ^= 0x01
    fake = _FakeTransport()
    with pytest.raises(CRCMismatchError, match="at record 11"):
        stream_scan_verify(bad, route="stream", chunk_bytes=256,
                           transport=fake)


class _ShapeTransport(_FakeTransport):
    """Records the (rows, width) of every shipment."""

    def __init__(self):
        super().__init__()
        self.shapes = set()

    def ship(self, rows):
        self.shapes.add(rows.shape)
        return rows


@pytest.mark.parametrize("chunk_bytes", [2048, 1 << 14])
def test_shipment_shapes_do_not_follow_the_record_count(tmp_path,
                                                        chunk_bytes):
    """A width class ships in ONE (rows, width) shape whatever a
    chunk holds: a WAL of 7 records and one of 150, short last chunks
    included, hand the device the same shapes, so the second compiles
    nothing the first did not (PR 34: a restart whose WAL ended in a
    short chunk compiled 2-4 s of programs)."""
    seen = []
    for n in (7, 64, 150):
        blob = _wal_blob(tmp_path / f"wal{n}", n_entries=n, cuts=(),
                         sizes=[100] * n)
        tr = _ShapeTransport()
        got = stream_scan_verify(blob, route="stream",
                                 chunk_bytes=chunk_bytes, transport=tr)
        _assert_arrays_equal(native.wal_scan(blob), got)
        seen.append(tr.shapes)
    assert seen[0] == seen[1] == seen[2], seen
    assert len({w for _r, w in seen[0]}) == len(seen[0])


class _CountingTransport(DeviceTransport):
    """The real transport, with every shipment's width and stored
    CRCs written down."""

    def __init__(self):
        self.shipments = []  # (width, stored)

    def verify(self, shipped, stored):
        self.shipments.append((shipped.shape[1], stored.copy()))
        return super().verify(shipped, stored)


def test_stream_verifies_every_width_class_from_one_matrix_build(
        tmp_path):
    """Records of three width classes (384, 512 and 4096), the widest
    in three shipments or more: each class's contribution matrix is a
    suffix of one doubling (ops/crc_device.py), and the suffix rule
    has to hold through the CRC primitive's own path, not in numpy
    alone — the replay verifies, and refuses a flipped bit in a record
    of the widest class's SECOND shipment by that record's number."""
    sizes = [(300, 450, 3000)[i % 3] for i in range(120)]
    blob = _wal_blob(tmp_path / "wal", n_entries=120, cuts=(),
                     sizes=sizes)
    full = native.wal_scan(blob)
    tr = _CountingTransport()
    got = stream_scan_verify(blob, route="stream", chunk_bytes=1 << 15,
                             transport=tr)
    _assert_arrays_equal(full, got)
    # (the metadata and crc records make a fourth class, 128)
    assert {w for w, _ in tr.shipments} >= {384, 512, 4096}
    wide = [st for w, st in tr.shipments if w == 4096]
    assert len(wide) >= 3
    _t, crcs, doff, dlen, *_ = full
    victim = int(np.nonzero(crcs == wide[1][0])[0][0])
    assert dlen[victim] > 2048
    bad = blob.copy()
    bad[int(doff[victim]) + int(dlen[victim]) - 3] ^= 0x01
    with pytest.raises(CRCMismatchError, match=f"at record {victim} "):
        stream_scan_verify(bad, route="stream", chunk_bytes=1 << 15)


@pytest.mark.parametrize("w, chunk_bytes, budget, rows", [
    (128, 4 << 20, 1 << 28, 1 << 16),     # 8 MiB of rows
    (384, 4 << 20, 1 << 28, 1 << 14),
    (131072, 4 << 20, 1 << 28, 64),       # a full chunk's frontier rows
    (128, 1024, 1 << 28, 16),             # a small chunk: two of it
    (131072, 1024, 1 << 28, 8),           # never under the floor of 8
    (128, 1 << 30, 1 << 28, 1 << 16),     # nor over two default chunks
    (1 << 18, 4 << 20, 1 << 17, 1),       # the byte budget still caps
])
def test_tile_rows(w, chunk_bytes, budget, rows):
    from etcd_tpu.wal.replay_device import _tile_rows

    assert _tile_rows(w, chunk_bytes, budget) == rows


# -- 3. ledger plumbing -------------------------------------------------------


def test_per_chunk_progress_lands_in_devledger(tmp_path):
    from etcd_tpu.obs.devledger import ledger

    blob = _wal_blob(tmp_path / "wal", n_entries=60, cuts=())
    before = ledger.snapshot().get("replay.stream", {})
    stream_scan_verify(blob, route="stream", chunk_bytes=1024)
    after = ledger.snapshot()["replay.stream"]
    assert after["dispatches"] > before.get("dispatches", 0)
    assert after["h2d_bytes"] > before.get("h2d_bytes", 0)
    assert after["d2h_bytes"] > before.get("d2h_bytes", 0)


def test_replay_bench_smoke_subprocess():
    """The scripts/test wiring: the --smoke invocation exercises the
    fused native entry point and the streaming path end to end."""
    import json
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable,
         os.path.join(repo, "scripts", "replay_bench.py"), "--smoke"],
        capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["ok"] is True


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
