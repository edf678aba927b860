"""Device-batched WAL replay (north-star config 1).

The reference replays a WAL strictly sequentially: per record, read a
length prefix, proto-unmarshal, update a rolling CRC, compare
(wal/wal.go:164-216, wal/decoder.go:28-47).  Here the same replay is a
three-stage pipeline:

1. **Host framing** (native/walscan.cc, or a numpy fallback): one
   sweep produces per-record arrays — type, stored CRC, data span,
   entry index/term/type.  Byte-granular and branchy: stays native.
2. **Device verification**: payload rows are right-aligned into an
   ``[N, L]`` buffer; every record's raw CRC is one MXU bit-matmul
   (ops/crc_device.py) and every chain link is checked in parallel
   (the chain is sequential only through its *stored* values, which
   the file already holds — so verification parallelizes even though
   computation of the chain did not).
3. **Host semantics**: metadata consistency, HardState selection,
   entry dedup-by-index (wal/wal.go:171-175) — cheap array ops on the
   scan output, no per-record Python objects.

The replay result keeps entries as an :class:`EntryBlock` — a
struct-of-arrays view into the raw blob, which is both the cheap form
(no 1M-object materialization) and the device-resident form the
batched raft engine consumes.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from dataclasses import dataclass
from queue import Empty, Full, Queue

import numpy as np

from .. import native
from ..obs import metrics as _obs
from ..obs.devledger import ledger as _ledger
from ..utils.trace import tracer
from ..wire import Entry, HardState
from ..wire.proto import ProtoError
from .backend_policy import DEFAULT_CHUNK_BYTES, get_policy
from .errors import (
    CRCMismatchError,
    FileNotFoundError_,
    IndexNotFoundError,
    MetadataConflictError,
    TornTailError,
    WALError,
)
from .wal import (
    CRC_TYPE,
    ENTRY_TYPE,
    METADATA_TYPE,
    STATE_TYPE,
    WAL,
    select_segments,
)


@dataclass(slots=True)
class EntryBlock:
    """Struct-of-arrays entry log slice backed by the WAL blob.

    The array form mirrors the device-resident log layout (SURVEY
    §7 "fixed-width array encodings for device residency"): callers
    can ship ``(index, term, type)`` straight to HBM and keep payload
    bytes host-side until apply.
    """

    index: np.ndarray      # uint64 [N]
    term: np.ndarray       # uint64 [N]
    type: np.ndarray       # uint64 [N]
    data_off: np.ndarray   # uint64 [N] into blob
    data_len: np.ndarray   # uint64 [N]
    blob: np.ndarray       # uint8, the raw WAL byte stream
    last_crc: int = 0      # stored CRC of the stream's final record
                           # (seeds WAL.open_at_end for appending)

    def __len__(self) -> int:
        return self.index.size

    def entry(self, i: int) -> Entry:
        """Materialize one Entry object (host convenience)."""
        o, l = int(self.data_off[i]), int(self.data_len[i])
        return Entry.unmarshal(self.blob[o:o + l].tobytes())

    def entries(self) -> list[Entry]:
        return [self.entry(i) for i in range(len(self))]


def _parse_record_span(raw: bytes, base: int, rlen: int):
    """Parse one Record in place, returning exact field positions.

    Walks the proto fields directly (the field loop of
    ``wire.proto.Record.unmarshal``) so the returned data span is the
    byte range the encoder actually wrote — a substring search can
    false-match payload bytes that also occur inside the type/crc
    varint envelope, which is how the native scanner avoids it too
    (walscan.cc tracks offsets while decoding).

    Returns ``(type, crc, data_off_abs, data_len)``.
    """
    from ..wire.proto import _expect_wt, _skip_field, _tag, uvarint

    end = base + rlen
    rtype = crc = 0
    doff, dlen = base, 0
    pos = base
    while pos < end:
        # _tag rejects field number 0 exactly like Record.unmarshal —
        # both replay lanes must agree on record validity
        fnum, wt, pos = _tag(raw, pos)
        if fnum == 1:
            _expect_wt(fnum, wt, 0)  # corrupt framing aborts, never
            rtype, pos = uvarint(raw, pos)  # masks (proto.py parity)
        elif fnum == 2:
            _expect_wt(fnum, wt, 0)
            crc, pos = uvarint(raw, pos)
        elif fnum == 3:
            _expect_wt(fnum, wt, 2)
            dlen, pos = uvarint(raw, pos)
            doff = pos
            pos += dlen
        else:
            pos = _skip_field(raw, pos, wt)
        if pos > end:
            raise WALError("record field overruns frame")
    return rtype, crc, doff, dlen


def _scan_python(blob: np.ndarray):
    """Pure-Python framing fallback mirroring native.wal_scan."""
    raw = blob.tobytes()
    pos, n = 0, len(raw)
    types, crcs, doffs, dlens, eidxs, eterms, etypes = \
        [], [], [], [], [], [], []
    while pos < n:
        if pos + 8 > n:
            raise TornTailError("truncated frame header")
        rlen = int.from_bytes(raw[pos:pos + 8], "little", signed=True)
        pos += 8
        if rlen < 0:
            raise WALError(f"negative record length {rlen}")
        if rlen > n - pos:
            raise TornTailError("truncated record")
        rtype, crc, doff, dlen = _parse_record_span(raw, pos, rlen)
        types.append(rtype)
        crcs.append(crc)
        doffs.append(doff)
        dlens.append(dlen)
        if rtype == ENTRY_TYPE and dlen:
            e = Entry.unmarshal(raw[doff:doff + dlen])
            eidxs.append(e.index)
            eterms.append(e.term)
            etypes.append(e.type)
        else:
            eidxs.append(0)
            eterms.append(0)
            etypes.append(0)
        pos += rlen
    return (np.asarray(types, np.int64), np.asarray(crcs, np.uint32),
            np.asarray(doffs, np.uint64), np.asarray(dlens, np.uint64),
            np.asarray(eidxs, np.uint64), np.asarray(eterms, np.uint64),
            np.asarray(etypes, np.uint64))


def _accelerator_absent() -> bool:
    """True when JAX's default backend is the host CPU — the batched
    device CRC then has no hardware to win on and the native
    sequential verifier is the fast path (VERDICT r4 #2).  Imports
    jax lazily: callers on the CPU-pinned server path already hold an
    initialized jax, and the device path imports it regardless."""
    try:
        import jax

        return jax.default_backend() == "cpu"
    except Exception:  # pragma: no cover - no jax at all
        return True


def _raw_crc_rows(rows):
    """``raw_crc_batch(rows)`` with the build and upload of the rows'
    contribution matrix as the replay's stage ``replay.matrix``.  The
    host matrix is cached (``contribution_matrix``); every shipment
    uploads it anew — 9 ms for the 32 MiB of the 131072-byte class on
    a v5e's host, behind ``jnp.asarray``'s return — so the serving
    process keeps no copy on the device (PERF.md section 6, PR 36)."""
    import jax.numpy as jnp

    from ..ops.crc_device import contribution_matrix, raw_crc_batch

    with tracer.stage("replay.matrix"):
        c = jnp.asarray(contribution_matrix(rows.shape[1]))
    return raw_crc_batch(rows, c=c)


def _pad_rows_numpy(blob, doff, dlen, width):
    n = doff.size
    out = np.zeros((n, width), np.uint8)
    for i in range(n):
        o, l = int(doff[i]), int(dlen[i])
        out[i, width - l:] = blob[o:o + l]
    return out


# -- streaming pipeline (PR 3 tentpole) --------------------------------------
#
# The monolithic device lane serializes scan -> full H2D -> verify, so
# e2e throughput is the *harmonic* mean of the stages — on a slow
# transport it collapses to the transport rate (the r05 0.021x row).
# The streaming lane splits the blob into fixed-size chunks and
# overlaps host framing of chunk k+1 with H2D of chunk k and device
# CRC verify of chunk k-1 (GPipe-style double buffering applied to
# the durability tier), so throughput approaches min(stage) instead.
# GF(2) seed injection makes per-chunk verification composable: chunk
# c's chain seeds from chunk c-1's last *stored* CRC, exactly the
# induction the batched verifier already relies on per link.

_CHUNK_HIST = {
    stage: _obs.registry.histogram("etcd_replay_stream_chunk_seconds",
                                   stage=stage)
    for stage in ("scan", "h2d", "verify")}


class DeviceTransport:
    """The H2D + device-verify legs of the streaming pipeline.

    An injectable seam: production ships padded rows with
    ``jax.device_put`` and dispatches the injected-seed CRC matmul;
    the deterministic pipeline tests swap in a fake with programmable
    per-chunk latencies to prove the overlap (and the bit-exactness
    of the stitched chain) without hardware in the loop.
    ``verify`` must only *dispatch* (async); ``collect`` blocks.
    """

    def ship(self, rows: np.ndarray):
        import jax

        return jax.device_put(rows)

    def verify(self, shipped, stored: np.ndarray):
        from ..ops.crc_device import chain_links_injected

        return chain_links_injected(_raw_crc_rows(shipped), stored)

    def collect(self, handle) -> np.ndarray:
        return np.asarray(handle)


def _raise_native(e: native.NativeError, record_base: int = 0):
    """Map a native scan/verify failure onto the WAL error vocabulary
    (by return CODE, never message text), naming the first bad record
    in stream-global terms."""
    if e.code == native.CRC_MISMATCH:
        bad = record_base + getattr(e, "bad_index", 0)
        raise CRCMismatchError(
            f"crc chain broken at record {bad} "
            f"(stored={getattr(e, 'bad_stored', 0):#x})") from e
    if e.code == native.TRUNCATED:
        raise TornTailError(str(e)) from e
    raise WALError(str(e)) from e


def _width_classes(dlen_v: np.ndarray) -> np.ndarray:
    """Quantized padded row width per record (4 spare bytes for the
    injected seed): multiples of 128 up to 2 KiB, powers of two
    above — bounds the compiled-shape count while keeping one huge
    record from inflating every row's padding."""
    need = dlen_v.astype(np.int64) + 4
    return np.where(
        need <= 2048,
        np.maximum(128, -(-need // 128) * 128),
        np.int64(1) << np.ceil(
            np.log2(np.maximum(need, 1).astype(np.float64))
        ).astype(np.int64))


#: most bytes of padded rows in one shipment of the streaming lane:
#: two default chunks, which is what a chunk's records of one width
#: class pad out to at most (a record of a power-of-two class is
#: longer than half its row)
_TILE_BYTES = 2 * DEFAULT_CHUNK_BYTES


def _tile_rows(w: int, chunk_bytes: int, byte_budget: int) -> int:
    """Rows of width class ``w`` in one shipment of the streaming
    lane: a power of two that follows from the width and the lane's
    settings alone, never from how many records the chunk holds.
    Every (rows, width) pair is a compiled program, and sized to the
    chunk's count a WAL's short last chunk compiled three to five
    programs that no WAL before it had needed — 2 to 4 s of a 10 s
    restart (PERF.md section 6, PR 34).  Short of rows, a shipment
    is padded with trivially-true links; a chunk with more makes
    several."""
    tile = min(2 * chunk_bytes, _TILE_BYTES, byte_budget)
    rows = max(8, min(1 << 17, tile // w))
    return max(1, min(1 << (rows.bit_length() - 1), byte_budget // w))


def _dispatch_chunk_verify(blob, crcs, doff, dlen, prev, transport,
                           chunk_bytes: int, byte_budget: int,
                           ledger_stage: str):
    """Pad + seed-inject one scanned chunk's records and *dispatch*
    the device chain verify (a shipment per :func:`_tile_rows` rows
    of each width class inside the chunk).  Returns ``[(sel, n_real,
    handle), ...]`` for a later blocking collect — the caller keeps
    scanning/shipping while the device works."""
    from ..ops.crc_device import inject_seeds

    stored = np.ascontiguousarray(crcs, np.uint32)
    dlen_v = np.ascontiguousarray(dlen, np.uint64)
    prev = np.ascontiguousarray(prev, np.uint32)
    wcls = _width_classes(dlen_v)
    out = []
    t0 = time.perf_counter()
    for w in np.unique(wcls):
        w = int(w)
        rows_idx = np.nonzero(wcls == w)[0]
        rpc = _tile_rows(w, chunk_bytes, byte_budget)
        for lo in range(0, rows_idx.size, rpc):
            sel = rows_idx[lo:lo + rpc]
            pad = rpc - sel.size
            d_off = doff[sel]
            d_len = dlen_v[sel]
            st = stored[sel]
            pv = prev[sel]
            if pad:  # zero rows + zero prev/stored: trivially true
                d_off = np.pad(d_off, (0, pad))
                d_len = np.pad(d_len, (0, pad))
                st = np.pad(st, (0, pad))
                pv = np.pad(pv, (0, pad))
            if native.available():
                rows = native.pad_rows(blob, d_off, d_len, w)
            else:
                rows = _pad_rows_numpy(blob, d_off, d_len, w)
            inject_seeds(rows, d_len, pv)
            _ledger.h2d(ledger_stage, rows)
            shipped = transport.ship(rows)
            with _ledger.dispatch(ledger_stage):
                handle = transport.verify(shipped, st)
            out.append((sel, sel.size, handle))
    _CHUNK_HIST["h2d"].observe(time.perf_counter() - t0)
    return out


def stream_scan_verify(blob: np.ndarray, *, seed: int = 0,
                       chunk_bytes: int | None = None,
                       route: str = "stream", transport=None,
                       byte_budget: int = 1 << 28, depth: int = 2,
                       ledger_stage: str = "replay.stream"):
    """Chunked streaming scan + rolling-chain verify of a WAL blob.

    Returns the whole stream's scan arrays ``(types, crcs, data_off,
    data_len, ent_index, ent_term, ent_type)`` — identical, bit for
    bit, to ``native.wal_scan(blob)`` with the chain verified — or
    raises the same typed errors the monolithic lanes raise.

    ``route="host"``: each chunk is one FUSED native sweep (frame +
    parse + CRC in a single pass, the Go baseline's shape); no device
    is touched.  ``route="stream"``: host framing of chunk k+1
    overlaps H2D of chunk k and device verify of chunk k-1; at most
    ``depth`` chunks are buffered on each seam (double buffering).
    ``transport`` injects the device legs for tests.
    """
    if not native.available():
        raise native.NativeError("native library unavailable")
    n = int(blob.size)
    if chunk_bytes is None:
        chunk_bytes = get_policy().chunk_bytes
    chunk_bytes = max(1, int(chunk_bytes))
    # ONE length-hop count sizes the whole stream's output arrays, so
    # every chunk sweep writes into its slice — no per-chunk
    # allocation, no final concatenate (the per-chunk tax that made
    # early chunked runs ~35% slower than the fused pass)
    try:
        total, _ = native.wal_count_range(blob, 0, n)
    except native.NativeError as e:
        _raise_native(e)
    full = native.alloc_scan_arrays(total)

    if route == "host":
        pos, base, chain = 0, 0, seed
        while pos < n:
            t0 = time.perf_counter()
            try:
                # one FUSED sweep per chunk; the ledger seam makes the
                # per-chunk cadence readable off /metrics even on the
                # no-device route (dispatches = chunks)
                with _ledger.dispatch(ledger_stage):
                    *arrays, nxt = native.scan_chunk(
                        blob, pos, chunk_bytes, seed=chain,
                        verify=True, out=full, out_base=base)
            except native.NativeError as e:
                _raise_native(e, base)
            _CHUNK_HIST["scan"].observe(time.perf_counter() - t0)
            cnt = arrays[0].size
            if cnt:
                chain = int(arrays[1][-1])
            base += cnt
            if nxt <= pos:  # defensive: no forward progress
                break
            pos = nxt
        return tuple(a[:base] for a in full)

    transport = transport or DeviceTransport()
    scan_q: Queue = Queue(maxsize=depth)
    cancel = threading.Event()
    scan_err: list[BaseException] = []

    def scanner():
        pos, base = 0, 0
        try:
            while pos < n:
                t0 = time.perf_counter()
                *arrays, nxt = native.scan_chunk(
                    blob, pos, chunk_bytes, verify=False,
                    out=full, out_base=base)
                _CHUNK_HIST["scan"].observe(time.perf_counter() - t0)
                _qput(scan_q, ("chunk", base, tuple(arrays)), cancel)
                base += arrays[0].size
                if nxt <= pos:
                    break
                pos = nxt
            _qput(scan_q, ("done", base, None), cancel)
        except _Cancelled:
            pass
        except BaseException as e:  # noqa: BLE001 - relayed to caller
            scan_err.append(e)
            try:
                _qput(scan_q, ("err", 0, None), cancel)
            except _Cancelled:
                pass

    th = threading.Thread(target=scanner, daemon=True,
                          name="replay-stream-scan")
    th.start()
    inflight: deque = deque()
    prev_tail: int | None = None
    first_bad: int | None = None

    def collect_one():
        nonlocal first_bad
        base, crcs, handles = inflight.popleft()
        t0 = time.perf_counter()
        for sel, n_real, handle in handles:
            ok = transport.collect(handle)
            _ledger.d2h(ledger_stage, ok)
            if not ok.all():
                bad = base + int(sel[np.argmin(ok[:n_real])])
                if first_bad is None or bad < first_bad:
                    first_bad = bad
        _CHUNK_HIST["verify"].observe(time.perf_counter() - t0)
        if first_bad is not None:
            raise CRCMismatchError(
                f"crc chain broken at record {first_bad} "
                f"(stored={int(crcs[first_bad - base]):#x})")

    filled = 0
    try:
        while True:
            kind, base, arrays = scan_q.get()
            if kind == "err":
                e = scan_err[0]
                if isinstance(e, native.NativeError):
                    _raise_native(e, base)
                raise e
            if kind == "done":
                filled = base
                break
            types, crcs = arrays[0], arrays[1]
            if crcs.size == 0:
                continue
            if prev_tail is None:
                head = int(crcs[0]) if types[0] == CRC_TYPE else seed
            else:
                head = prev_tail
            prev = np.concatenate(
                [np.asarray([head], np.uint32), crcs[:-1]])
            handles = _dispatch_chunk_verify(
                blob, crcs, arrays[2], arrays[3], prev, transport,
                chunk_bytes, byte_budget, ledger_stage)
            inflight.append((base, crcs, handles))
            prev_tail = int(crcs[-1])
            while len(inflight) >= depth:
                collect_one()
        while inflight:
            collect_one()
    finally:
        cancel.set()
        _drain(scan_q)
        th.join(timeout=10)
    return tuple(a[:filled] for a in full)


class _Cancelled(Exception):
    pass


def _qput(q: Queue, item, cancel: threading.Event) -> None:
    while True:
        if cancel.is_set():
            raise _Cancelled()
        try:
            q.put(item, timeout=0.05)
            return
        except Full:
            continue


def _drain(q: Queue) -> None:
    while True:
        try:
            q.get_nowait()
        except Empty:
            return


def verify_chain_device(blob: np.ndarray, types, crcs, doff, dlen,
                        chunk_rows: int = 1 << 17,
                        byte_budget: int = 1 << 28) -> None:
    """Device-parallel rolling-chain verification of scanned records.

    Raises :class:`CRCMismatchError` naming the first bad record.
    A leading crcType record re-seeds the chain, mirroring the fresh-
    decoder rule of wal/wal.go:184-191 (a mid-file crc record instead
    participates as a regular zero-length link, which its stored value
    satisfies iff it matches the running chain — same check, batched).

    Each link i depends only on the *stored* value of link i-1, so
    verification is order-independent: records are grouped by width
    class (so one huge record cannot inflate every row) and processed
    in fixed-shape chunks (so each (width, rows) pair compiles once;
    short tails are padded with trivially-true links).
    """
    n = int(types.shape[0])
    if n == 0:
        return
    seed = 0
    start = 0
    if types[0] == CRC_TYPE:
        seed = int(crcs[0])
        start = 1
    if start >= n:
        return

    if native.available() and _accelerator_absent():
        # No accelerator: the batched bit-matmul CRC on JAX-CPU is
        # ~50x slower than one native core (VERDICT r4 #2 — the
        # framework must never lose to the reference on any backend).
        # CRC-only sweep over the spans the scan already produced
        # (decoder.go:28-47 chain semantics; no re-parse), naming the
        # first bad record exactly like the batched pass below.
        # Sharded across cores once the CRC work dwarfs thread
        # startup — each link needs only its predecessor's STORED
        # value, so record ranges verify independently.
        threads = 1
        if n - start >= (1 << 16):
            threads = min(os.cpu_count() or 1, 8)
        try:
            r = native.chain_verify(
                blob, doff[start:], dlen[start:], crcs[start:], seed,
                threads=threads)
        except native.NativeError as e:  # pragma: no cover - scan
            raise WALError(str(e)) from e  # guarantees spans in range
        if r == n - start:
            return
        bad = start + r
        raise CRCMismatchError(
            f"crc chain broken at record {bad} "
            f"(stored={int(crcs[bad]):#x})")

    from ..ops.crc_device import _chain_expected

    stored = np.ascontiguousarray(crcs[start:], np.uint32)
    prev = np.concatenate(
        [np.asarray([seed], np.uint32), crcs[start:-1]])
    doff_v = doff[start:]
    dlen_v = np.ascontiguousarray(dlen[start:], np.uint64)

    wcls = np.where(
        dlen_v <= 2048,
        np.maximum(64, -(-dlen_v.astype(np.int64) // 128) * 128),
        np.int64(1) << np.ceil(
            np.log2(np.maximum(dlen_v, 1).astype(np.float64))
        ).astype(np.int64))

    first_bad = None
    for w in np.unique(wcls):
        w = int(w)
        rows_idx = np.nonzero(wcls == w)[0]
        # byte_budget caps host-chunk bytes even for multi-MiB width
        # classes (whose XLA bit expansion is ~8x the chunk size); the
        # floor is 1 row, never a fixed row count
        rpc = max(1, min(chunk_rows, byte_budget // w))
        # don't build a mostly-padding chunk for a tiny class; pow2
        # quantization keeps the compiled-shape count bounded
        rpc = min(rpc, max(8, 1 << (rows_idx.size - 1).bit_length()))
        for lo in range(0, rows_idx.size, rpc):
            sel = rows_idx[lo:lo + rpc]
            pad = rpc - sel.size
            d_off = doff_v[sel]
            d_len = dlen_v[sel]
            st = stored[sel]
            pv = prev[sel]
            if pad:  # zero-length/zero-crc links are trivially true
                d_off = np.pad(d_off, (0, pad))
                d_len = np.pad(d_len, (0, pad))
                st = np.pad(st, (0, pad))
                pv = np.pad(pv, (0, pad))
            if native.available():
                rows = native.pad_rows(blob, d_off, d_len, w)
            else:
                rows = _pad_rows_numpy(blob, d_off, d_len, w)
            # devledger seam: the padded batch is the H2D shipment,
            # the [rows] ok mask the D2H readback — per-chunk cost of
            # the replay lane, readable off /metrics after a restart
            _ledger.h2d("replay.verify", rows)
            with _ledger.dispatch("replay.verify"):
                ok = np.asarray(
                    _chain_expected(pv, _raw_crc_rows(rows),
                                    d_len.astype(np.uint32)) == st)
            _ledger.d2h("replay.verify", ok)
            if not ok.all():
                bad = start + int(sel[np.argmin(ok[:sel.size])])
                if first_bad is None or bad < first_bad:
                    first_bad = bad
    if first_bad is not None:
        raise CRCMismatchError(
            f"crc chain broken at record {first_bad} "
            f"(stored={int(crcs[first_bad]):#x})")


def read_all_device(dirpath: str, index: int = 0,
                    route: str | None = None
                    ) -> tuple[bytes | None, HardState, EntryBlock]:
    """Batched-replay equivalent of ``WAL.open_at_index + read_all``.

    Same semantics as the host path (metadata conflict, state
    selection, entry dedup-by-index, index-gap and not-found errors)
    with the scan/verify lane chosen by ``route`` — ``host`` (one
    fused native sweep), ``device`` (monolithic batched verify),
    ``stream`` (the chunked overlap pipeline) — or, when None, by the
    measured backend router (wal/backend_policy).  Returns entries as
    an :class:`EntryBlock`; the WAL object itself is NOT opened for
    append (use ``WAL.open_at_index`` for the read-then-append
    lifecycle — this path is the bulk-replay fast lane).
    """
    names = select_segments(dirpath, index)
    blobs = [np.fromfile(os.path.join(dirpath, nm), dtype=np.uint8)
             for nm in names]
    blob = np.concatenate(blobs) if len(blobs) > 1 else blobs[0]

    verified = False
    if native.available():
        if route is None:
            route = get_policy().route("replay",
                                       size_bytes=int(blob.size))
        try:
            if route == "host":
                # the Go baseline's fused shape: frame + parse + CRC
                # in ONE pass over the blob — no chain_verify re-read
                types, crcs, doff, dlen, eidx, eterm, etype = \
                    native.scan_verify(blob)
                verified = True
            elif route == "stream":
                types, crcs, doff, dlen, eidx, eterm, etype = \
                    stream_scan_verify(blob, route="stream")
                verified = True
            else:
                types, crcs, doff, dlen, eidx, eterm, etype = \
                    native.wal_scan(blob)
        except native.NativeError as e:
            # error-type parity with the host path: WAL corruption is
            # a WALError regardless of which scanner found it, and a
            # stream that ends mid-record is the same typed
            # TornTailError the host decoder raises (mapped by native
            # return code, never message text)
            _raise_native(e)
    else:
        try:
            types, crcs, doff, dlen, eidx, eterm, etype = \
                _scan_python(blob)
        except ProtoError as e:  # same parity for the python scanner
            raise WALError(str(e)) from e

    known = np.isin(types, (METADATA_TYPE, ENTRY_TYPE, STATE_TYPE,
                            CRC_TYPE))
    if not known.all():
        j = int(np.argmin(known))
        raise WALError(f"unexpected block type {int(types[j])}")

    if not verified:
        verify_chain_device(blob, types, crcs, doff, dlen)

    # -- host semantics over the scan arrays --------------------------------
    metadata: bytes | None = None
    for j in np.nonzero(types == METADATA_TYPE)[0]:
        md = blob[int(doff[j]):int(doff[j]) + int(dlen[j])].tobytes()
        if metadata is not None and metadata != md:
            raise MetadataConflictError()
        metadata = md

    state = HardState()
    st_idx = np.nonzero(types == STATE_TYPE)[0]
    if st_idx.size:
        j = int(st_idx[-1])
        state = HardState.unmarshal(
            blob[int(doff[j]):int(doff[j]) + int(dlen[j])].tobytes())

    # Entry selection mirrors the host read_all loop exactly
    # (wal.py read_all / reference wal/wal.go:171-175): ri = the open
    # index, keep entries with e.index >= ri, dedup-by-index with
    # tail truncation, and the final last-entry >= ri check.
    ei = np.nonzero(types == ENTRY_TYPE)[0]
    ri = index
    if ei.size:
        idxs = eidx[ei].astype(np.int64)
        keep = idxs >= ri
        ei_k = ei[keep]
        idxs_k = idxs[keep]
        if idxs_k.size and np.all(np.diff(idxs_k) == 1) \
                and idxs_k[0] == ri:
            sel = ei_k  # fast path: consecutive from ri, no overwrites
        else:
            # crash-overwrite / gap path: replay dedup-by-index
            kept: list[int] = []
            for j, idx in zip(ei_k, idxs_k):
                slot = int(idx) - ri
                if slot > len(kept):
                    raise WALError(
                        f"entry index gap: {int(idx)} after "
                        f"{len(kept)} entries from {ri}")
                del kept[slot:]
                kept.append(int(j))
            sel = np.asarray(kept, np.int64)
        enti = int(eidx[ei[-1]])  # last entry index SEEN (host parity)
    else:
        sel = np.asarray([], np.int64)
        enti = 0

    if enti < ri:
        raise IndexNotFoundError(f"last entry {enti} < requested {ri}")

    block = EntryBlock(
        index=eidx[sel], term=eterm[sel], type=etype[sel],
        data_off=doff[sel], data_len=dlen[sel], blob=blob,
        last_crc=int(crcs[-1]) if crcs.size else 0)
    return metadata, state, block


def open_replay_device(dirpath: str, index: int = 0,
                       route: str | None = None
                       ) -> tuple[WAL, bytes | None, HardState, EntryBlock]:
    """Replay on the routed fast lane, then open the WAL for appends.

    The device-backed equivalent of ``open_at_index + read_all``: the
    batched pass both verifies the stream and yields the chain tail
    CRC, so the append encoder seeds directly (WAL.open_at_end) with
    no sequential re-read.
    """
    metadata, state, block = read_all_device(dirpath, index, route)
    enti = int(block.index[-1]) if len(block) else 0
    w = WAL.open_at_end(dirpath, metadata, block.last_crc, enti)
    return w, metadata, state, block
