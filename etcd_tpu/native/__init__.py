"""ctypes bindings for the native WAL data-loader tier (native/walscan.cc).

Builds the shared library on demand with ``make`` (g++ is in the
image; the .so is not committed).  ``available()`` is False — with a
logged warning — when no compiler/toolchain is present, and callers
(wal.replay_device) keep a pure-Python path.

The native tier owns the byte-granular, branchy work the reference
does in Go — framing (wal/decoder.go:30-35), proto field walks,
single-core rolling-CRC replay (wal/wal.go:164-216) — while the
batched checksum/commit math runs on device (ops/).
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_SO = os.path.join(_DIR, "libwalscan.so")

log = logging.getLogger(__name__)

_lock = threading.Lock()
_lib = None
_tried = False


class NativeError(RuntimeError):
    """Native-tier failure; ``code`` carries the C return code so
    wrappers can map classes of failure (torn tail, crc) onto the
    repo's typed exception vocabulary without message matching."""

    def __init__(self, msg: str, code: int = 0):
        super().__init__(msg)
        self.code = code


TRUNCATED = -1
PROTO_ERR = -2
CAPACITY = -3
CRC_MISMATCH = -4

_ERRORS = {
    TRUNCATED: "truncated stream",
    PROTO_ERR: "proto parse error",
    CAPACITY: "capacity exceeded",
    CRC_MISMATCH: "crc mismatch",
}


def _check(rc: int) -> int:
    if rc < 0:
        raise NativeError(_ERRORS.get(rc, f"native error {rc}"), rc)
    return rc


def _build() -> bool:
    src = os.path.join(_DIR, "walscan.cc")
    if not os.path.exists(src):
        return False
    if os.path.exists(_SO) and os.path.getmtime(_SO) >= os.path.getmtime(src):
        return True
    try:
        subprocess.run(["make", "-C", _DIR, "libwalscan.so"],
                       check=True, capture_output=True, timeout=120)
        return True
    except (subprocess.SubprocessError, OSError) as e:
        # callers keep a pure-Python path, but never silently: a
        # host that cannot build the scanner must say so once
        log.warning("native: building %s failed (%r); %s", _SO, e,
                    (getattr(e, "stderr", b"") or b"")
                    .decode(errors="replace")[-500:])
        return False


def _load():
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if not _build():
            return None
        lib = ctypes.CDLL(_SO)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        i64p = ctypes.POINTER(ctypes.c_int64)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        lib.etcd_crc32c_update.restype = ctypes.c_uint32
        lib.etcd_crc32c_update.argtypes = [ctypes.c_uint32, u8p,
                                           ctypes.c_uint64]
        lib.etcd_crc32c_raw.restype = ctypes.c_uint32
        lib.etcd_crc32c_raw.argtypes = [ctypes.c_uint32, u8p,
                                        ctypes.c_uint64]
        lib.etcd_wal_count.restype = ctypes.c_int64
        lib.etcd_wal_count.argtypes = [u8p, ctypes.c_uint64]
        lib.etcd_wal_scan.restype = ctypes.c_int64
        lib.etcd_wal_scan.argtypes = [u8p, ctypes.c_uint64, i64p, u32p,
                                      u64p, u64p, u64p, u64p, u64p,
                                      ctypes.c_uint64]
        lib.etcd_replay_verify.restype = ctypes.c_int64
        lib.etcd_replay_verify.argtypes = [u8p, ctypes.c_uint64,
                                           ctypes.c_uint32, u64p, u64p]
        lib.etcd_chain_verify.restype = ctypes.c_int64
        lib.etcd_chain_verify.argtypes = [u8p, ctypes.c_uint64, u64p,
                                          u64p, u32p, ctypes.c_uint64,
                                          ctypes.c_uint32]
        lib.etcd_chain_verify_mt.restype = ctypes.c_int64
        lib.etcd_chain_verify_mt.argtypes = [u8p, ctypes.c_uint64,
                                             u64p, u64p, u32p,
                                             ctypes.c_uint64,
                                             ctypes.c_uint32,
                                             ctypes.c_uint64]
        lib.etcd_wal_count_range.restype = ctypes.c_int64
        lib.etcd_wal_count_range.argtypes = [u8p, ctypes.c_uint64,
                                             ctypes.c_uint64,
                                             ctypes.c_uint64, u64p]
        lib.etcd_wal_scan_chunk.restype = ctypes.c_int64
        lib.etcd_wal_scan_chunk.argtypes = [u8p, ctypes.c_uint64,
                                            ctypes.c_uint64,
                                            ctypes.c_uint64,
                                            ctypes.c_uint32,
                                            ctypes.c_int64, i64p, u32p,
                                            u64p, u64p, u64p, u64p,
                                            u64p, ctypes.c_uint64,
                                            u64p, i64p]
        lib.etcd_wal_gen.restype = ctypes.c_int64
        lib.etcd_wal_gen.argtypes = [ctypes.c_uint64, ctypes.c_uint64,
                                     ctypes.c_uint64, ctypes.c_uint32,
                                     u8p, ctypes.c_uint64]
        lib.etcd_pad_rows.restype = ctypes.c_int64
        lib.etcd_pad_rows.argtypes = [u8p, u64p, u64p, ctypes.c_uint64,
                                      ctypes.c_uint64, u8p]
        lib.etcd_ge_scan.restype = ctypes.c_int64
        lib.etcd_ge_scan.argtypes = [u8p, ctypes.c_uint64, u64p, u64p,
                                     ctypes.c_uint64, i64p, i64p, i64p,
                                     i64p, u64p, u64p]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _u8(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def crc32c_update(crc: int, data) -> int:
    lib = _load()
    buf = np.frombuffer(memoryview(data), dtype=np.uint8) \
        if not isinstance(data, np.ndarray) else data
    if lib is None:
        from ..crc import crc32c
        return crc32c.update(crc, buf.tobytes())
    return int(lib.etcd_crc32c_update(crc, _u8(buf), buf.size))


def wal_scan(blob: np.ndarray):
    """Framing pass: returns (types, crcs, data_off, data_len,
    ent_index, ent_term, ent_type) numpy arrays, one per record."""
    lib = _load()
    if lib is None:
        raise NativeError("native library unavailable")
    # Exact-size allocation via a cheap length-hop sweep (avoids the
    # ~6 bytes-of-array-per-WAL-byte worst-case preallocation).
    cap = max(1, _check(lib.etcd_wal_count(_u8(blob), blob.size)))
    types = np.empty(cap, np.int64)
    crcs = np.empty(cap, np.uint32)
    doff = np.empty(cap, np.uint64)
    dlen = np.empty(cap, np.uint64)
    eidx = np.empty(cap, np.uint64)
    eterm = np.empty(cap, np.uint64)
    etype = np.empty(cap, np.uint64)
    u64 = ctypes.POINTER(ctypes.c_uint64)
    n = _check(lib.etcd_wal_scan(
        _u8(blob), blob.size,
        types.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        crcs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        doff.ctypes.data_as(u64), dlen.ctypes.data_as(u64),
        eidx.ctypes.data_as(u64), eterm.ctypes.data_as(u64),
        etype.ctypes.data_as(u64), cap))
    return (types[:n], crcs[:n], doff[:n], dlen[:n], eidx[:n], eterm[:n],
            etype[:n])


def ge_scan(blob: np.ndarray, data_off: np.ndarray,
            data_len: np.ndarray):
    """Batched GroupEntry envelope parse over entry-data spans:
    returns (kind, group, gindex, gterm, payload_off, payload_len)
    int64/uint64 arrays — the native sweep behind multi-group restart
    replay (one call instead of N ``GroupEntry.unmarshal``)."""
    lib = _load()
    if lib is None:
        raise NativeError("native library unavailable")
    n = data_off.size
    kind = np.empty(n, np.int64)
    group = np.empty(n, np.int64)
    gindex = np.empty(n, np.int64)
    gterm = np.empty(n, np.int64)
    poff = np.empty(n, np.uint64)
    plen = np.empty(n, np.uint64)
    i64 = ctypes.POINTER(ctypes.c_int64)
    u64 = ctypes.POINTER(ctypes.c_uint64)
    _check(lib.etcd_ge_scan(
        _u8(blob), blob.size,
        np.ascontiguousarray(data_off, np.uint64).ctypes.data_as(u64),
        np.ascontiguousarray(data_len, np.uint64).ctypes.data_as(u64),
        n, kind.ctypes.data_as(i64), group.ctypes.data_as(i64),
        gindex.ctypes.data_as(i64), gterm.ctypes.data_as(i64),
        poff.ctypes.data_as(u64), plen.ctypes.data_as(u64)))
    return kind, group, gindex, gterm, poff, plen


def replay_verify(blob: np.ndarray, seed: int = 0):
    """Single-core sequential replay (baseline). Returns
    (n_entries, last_index, last_term); raises on corruption."""
    lib = _load()
    if lib is None:
        raise NativeError("native library unavailable")
    li = ctypes.c_uint64()
    lt = ctypes.c_uint64()
    n = _check(lib.etcd_replay_verify(
        _u8(blob), blob.size, seed, ctypes.byref(li), ctypes.byref(lt)))
    return n, li.value, lt.value


def chain_verify(blob: np.ndarray, data_off: np.ndarray,
                 data_len: np.ndarray, stored: np.ndarray,
                 seed: int = 0, threads: int = 1) -> int:
    """CRC-only rolling-chain verification over pre-scanned record
    spans (one native sweep; no re-parse).  ``threads > 1`` shards the
    sweep across record ranges (each link needs only its predecessor's
    *stored* value, so ranges verify independently; the ctypes call
    releases the GIL either way).  Returns ``stored.size`` when the
    chain verifies, else the index of the first bad record; raises on
    out-of-range spans."""
    lib = _load()
    if lib is None:
        raise NativeError("native library unavailable")
    u64 = ctypes.POINTER(ctypes.c_uint64)
    args = (
        _u8(blob), blob.size,
        np.ascontiguousarray(data_off, np.uint64).ctypes.data_as(u64),
        np.ascontiguousarray(data_len, np.uint64).ctypes.data_as(u64),
        np.ascontiguousarray(stored, np.uint32).ctypes.data_as(
            ctypes.POINTER(ctypes.c_uint32)),
        data_off.size, seed)
    if threads > 1:
        return _check(lib.etcd_chain_verify_mt(*args, threads))
    return _check(lib.etcd_chain_verify(*args))


def wal_count_range(blob: np.ndarray, pos: int = 0,
                    budget: int | None = None) -> tuple[int, int]:
    """Length-hop record count over one chunk: ``(count, next_pos)``
    for the records a ``scan_chunk(pos, budget)`` call would emit."""
    lib = _load()
    if lib is None:
        raise NativeError("native library unavailable")
    if budget is None:
        budget = blob.size
    nxt = ctypes.c_uint64()
    n = _check(lib.etcd_wal_count_range(_u8(blob), blob.size, pos,
                                        budget, ctypes.byref(nxt)))
    return n, nxt.value


_SCAN_DTYPES = (np.int64, np.uint32, np.uint64, np.uint64, np.uint64,
                np.uint64, np.uint64)


def alloc_scan_arrays(n: int) -> tuple:
    """Preallocated (types, crcs, data_off, data_len, ent_index,
    ent_term, ent_type) arrays for ``n`` records — the whole-stream
    buffers streaming callers hand to :func:`scan_chunk` via ``out``
    so per-chunk sweeps write into slices instead of allocating."""
    return tuple(np.empty(max(1, n), dt) for dt in _SCAN_DTYPES)


def scan_chunk(blob: np.ndarray, pos: int = 0,
               budget: int | None = None, seed: int = 0,
               verify: bool = False, out: tuple | None = None,
               out_base: int = 0):
    """One fused chunk sweep: frame + parse (+ rolling-chain CRC check
    when ``verify``) of the records starting at ``pos`` until at least
    ``budget`` bytes are consumed (a straddling record belongs to this
    chunk).  ``out``/``out_base`` write the records into preallocated
    whole-stream arrays (:func:`alloc_scan_arrays`) starting at
    ``out_base`` — no per-chunk allocation, no final concatenate.
    Returns ``(types, crcs, data_off, data_len, ent_index, ent_term,
    ent_type, next_pos)`` (views when ``out`` is given); a CRC
    mismatch raises :class:`NativeError` with ``code == CRC_MISMATCH``
    and ``bad_index`` = the chunk-local index of the first bad
    record."""
    lib = _load()
    if lib is None:
        raise NativeError("native library unavailable")
    if budget is None:
        budget = blob.size
    if out is None:
        cap, _ = wal_count_range(blob, pos, budget)
        out = alloc_scan_arrays(cap)
        out_base = 0
        cap = max(1, cap)
    else:
        cap = out[0].size - out_base
        if cap <= 0:
            raise NativeError(_ERRORS[CAPACITY], CAPACITY)
    types, crcs, doff, dlen, eidx, eterm, etype = (
        a[out_base:] for a in out)
    u64 = ctypes.POINTER(ctypes.c_uint64)
    nxt = ctypes.c_uint64()
    bad = ctypes.c_int64()
    rc = lib.etcd_wal_scan_chunk(
        _u8(blob), blob.size, pos, budget, seed, 1 if verify else 0,
        types.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        crcs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        doff.ctypes.data_as(u64), dlen.ctypes.data_as(u64),
        eidx.ctypes.data_as(u64), eterm.ctypes.data_as(u64),
        etype.ctypes.data_as(u64), cap, ctypes.byref(nxt),
        ctypes.byref(bad))
    if rc == CRC_MISMATCH:
        e = NativeError(_ERRORS[CRC_MISMATCH], CRC_MISMATCH)
        e.bad_index = int(bad.value)
        e.bad_stored = int(crcs[bad.value]) if bad.value >= 0 else 0
        raise e
    n = _check(rc)
    return (types[:n], crcs[:n], doff[:n], dlen[:n], eidx[:n],
            eterm[:n], etype[:n], nxt.value)


def scan_verify(blob: np.ndarray, seed: int = 0):
    """Whole-stream FUSED scan + rolling-chain verify: the Go
    baseline's one-pass shape (wal/wal.go:164-216) with the scan
    arrays as output — parse and CRC in a single sweep over the blob,
    no ``etcd_chain_verify`` re-read.  Returns the same 7 arrays as
    :func:`wal_scan`; raises on corruption (CRC mismatches carry
    ``bad_index``/``bad_stored``)."""
    out = scan_chunk(blob, 0, blob.size, seed=seed, verify=True)
    return out[:7]


def wal_gen(n_entries: int, payload_len: int, start_index: int = 1,
            seed: int = 0) -> np.ndarray:
    """Generate a synthetic framed entry-record stream."""
    lib = _load()
    if lib is None:
        raise NativeError("native library unavailable")
    cap = n_entries * (payload_len + 64) + 64
    out = np.empty(cap, np.uint8)
    n = _check(lib.etcd_wal_gen(n_entries, payload_len, start_index,
                                seed, _u8(out), cap))
    return out[:n]


def pad_rows(blob: np.ndarray, data_off: np.ndarray, data_len: np.ndarray,
             width: int, out: np.ndarray | None = None) -> np.ndarray:
    """Right-align data spans into a zero-padded [n, width] buffer.

    ``out``, when given, is a preallocated C-contiguous uint8
    [n, width] destination (e.g. a slice of one big batch array) —
    large multi-group pipelines write each group straight into its
    batch slot instead of paying a second full copy to concatenate.
    """
    lib = _load()
    if lib is None:
        raise NativeError("native library unavailable")
    n = data_off.size
    if out is None:
        out = np.empty((n, width), np.uint8)
    elif (out.shape != (n, width) or out.dtype != np.uint8
          or not out.flags.c_contiguous or not out.flags.writeable):
        raise ValueError(
            "out must be writeable C-contiguous uint8 [n, width]")
    _check(lib.etcd_pad_rows(
        _u8(blob),
        np.ascontiguousarray(data_off, np.uint64).ctypes.data_as(
            ctypes.POINTER(ctypes.c_uint64)),
        np.ascontiguousarray(data_len, np.uint64).ctypes.data_as(
            ctypes.POINTER(ctypes.c_uint64)),
        n, width, _u8(out)))
    return out
