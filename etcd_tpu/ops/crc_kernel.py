"""Whole-blob CRC32C on device (north-star config 3).

The reference hashes snapshot blobs with one sequential pass
(snap/snapshotter.go:53,98 — ``crc32.Update`` over the whole file).
Here the blob is split into fixed chunks and the sequential dependency
collapses via linearity over GF(2):

    raw(c_0 ++ ... ++ c_{K-1}) = XOR_k  Z^suffix_k @ raw(c_k)

where ``suffix_k`` is the byte count after chunk k.  Every chunk's raw
CRC state is one row of a batched MXU bit-matmul (ops/crc_device.py),
the ``Z^suffix`` shifts run as batched masked matmuls
(shift_crc_batch), and the XOR-reduce is a bit-parity sum — all on
device; only the final 32-bit fix-up happens on host.  This is the
snapshot-hash analog of the blockwise-parallel WAL chain (SURVEY §5.7).
"""

from __future__ import annotations

import logging
import threading
import time

import jax.numpy as jnp
import numpy as np

from ..crc import crc32c as _host
from ..crc import gf2
from .crc_device import (
    _from_bits32,
    _to_bits32,
    raw_crc_batch,
    shift_crc_batch,
)

log = logging.getLogger(__name__)

_MASK32 = 0xFFFFFFFF

# Below this size the sequential host path wins (device dispatch +
# transfer latency); above it the batched path amortizes.
DEVICE_MIN_BYTES = 4 << 20
# Chunk width: the [8*CHUNK, 32] contribution matrix (1 MiB at 4 KiB
# chunks) must fit VMEM beside the per-tile bit expansion, and builds
# in O(CHUNK) host work once per process (lru-cached).
CHUNK = 1 << 12
# Rows dispatched per device call: bounds the XLA-path bit expansion
# ([ROWS, 8*CHUNK] = 1 GiB at these defaults) and H2D staging.
ROW_BATCH = 1 << 15


def _xor_reduce(states: jnp.ndarray) -> jnp.ndarray:
    """XOR over a [K] uint32 vector = per-bit parity sum."""
    bits = _to_bits32(states)  # [K, 32] int8
    return _from_bits32(jnp.sum(bits.astype(jnp.int32), axis=0) & 1)


def device_crc32c(data, chunk: int = CHUNK) -> int:
    """``crc32.Update(0, castagnoli, data)`` via batched device chunks.

    Bit-identical to the host path (crc/crc32c.py:value) for any
    length, including zero and non-chunk-multiple tails.
    """
    buf = np.frombuffer(memoryview(data), dtype=np.uint8) \
        if not isinstance(data, np.ndarray) else data
    n = int(buf.size)
    if n == 0:
        return 0
    if n >= 1 << 32:  # suffix shifts are uint32 (4 GiB ceiling)
        return _host.value(buf)
    k = -(-n // chunk)
    rem = n - (k - 1) * chunk
    # Chunk 0 is the (possibly short) head; right-alignment makes its
    # leading zero-padding free for a zero raw state.
    head = np.zeros((1, chunk), np.uint8)
    head[0, chunk - rem:] = buf[:rem]
    body = buf[rem:].reshape(k - 1, chunk) if k > 1 else \
        np.zeros((0, chunk), np.uint8)

    raw_parts = [np.asarray(raw_crc_batch(head), np.uint32)]
    for lo in range(0, k - 1, ROW_BATCH):
        part = body[lo:lo + ROW_BATCH]
        np_rows = part.shape[0]
        # pad partial batches to a power of two: bounded compiled
        # shapes instead of one per blob size (zero rows are dropped)
        pad_to = 1 << max(0, (np_rows - 1).bit_length())
        if pad_to != np_rows:
            part = np.vstack(
                [part, np.zeros((pad_to - np_rows, chunk), np.uint8)])
        raw_parts.append(np.asarray(
            raw_crc_batch(part), np.uint32)[:np_rows])
    raws = np.concatenate(raw_parts)

    suffix = (np.arange(k - 1, -1, -1, dtype=np.int64) * chunk)
    # pad the fold to a power of two as well — zero states shift to
    # zero and XOR away, and the compile cache stays bounded instead
    # of recompiling per distinct chunk count
    k_pad = 1 << max(0, (k - 1).bit_length())
    if k_pad != k:
        raws = np.concatenate([raws, np.zeros(k_pad - k, np.uint32)])
        suffix = np.concatenate([suffix,
                                 np.zeros(k_pad - k, np.int64)])
    shifted = shift_crc_batch(jnp.asarray(raws),
                              jnp.asarray(suffix, jnp.uint32))
    total = int(_xor_reduce(shifted))

    # Go convention: update(0, m) = Z^n @ ~0 ^ raw(m) ^ ~0
    inv = gf2.matvec(gf2.zero_operator(n), _MASK32)
    return (total ^ inv ^ _MASK32) & _MASK32


# Measured backend policy (VERDICT r3 #7: the device hash must never
# be the slowest available path).  Snapshot blobs are built host-side
# (store.save() JSON), so the device path pays a full H2D transfer;
# whether that ever amortizes depends on the actual link and device.
# Decided by RACING both paths once per process on the first large
# blob's head.  A device fault is not a verdict: it propagates.
_CALIBRATE_BYTES = 8 << 20
_CALIBRATE_REPS = 3        # best-of-N: one stall must not pin policy
_device_wins: bool | None = None
_calibrate_lock = threading.Lock()


def device_hash_wins() -> bool | None:
    """The calibrated policy (None = no large blob hashed yet)."""
    return _device_wins


def _best_of(fn, sample, reps=_CALIBRATE_REPS) -> float:
    """Minimum wall time over reps runs — a transient scheduling
    stall on a shared host inflates one run, not the minimum."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(sample)
        best = min(best, time.perf_counter() - t0)
    return best


def _calibrate(buf: np.ndarray) -> bool:
    """Race both paths on the blob's head; True = the device won."""
    sample = np.ascontiguousarray(buf[:_CALIBRATE_BYTES])
    device_crc32c(sample)  # compile/warm outside the timing
    t_dev = _best_of(device_crc32c, sample)
    t_host = _best_of(_host.value, sample)
    log.info("snapshot-hash calibration: device %.0f MB/s vs host "
             "%.0f MB/s -> %s", sample.size / t_dev / 1e6,
             sample.size / t_host / 1e6,
             "device" if t_dev < t_host else "host")
    return t_dev < t_host


def auto_crc32c(data) -> int:
    """Measured-policy CRC — the drop-in ``crc_fn`` for
    snap.Snapshotter: host path for small blobs, and for large blobs
    whichever path a one-time race on this process's actual
    device/link won (host data + slow transfer means the device path
    frequently loses; it must never be chosen when it does).

    The choice is a measurement, never an exception handler: a
    device fault during the race or the hash raises to the caller
    (a snapshot save or load then fails loudly; it is not
    SnapError, so Snapshotter.load does not quarantine the file).
    """
    global _device_wins
    # the host path takes any buffer as-is (crc32c.update copies an
    # ndarray but not bytes — keep the original object for it)
    n = data.size if isinstance(data, np.ndarray) else len(data)
    if n < DEVICE_MIN_BYTES:
        return _host.value(data)
    if _device_wins is None:
        # non-blocking: exactly one thread runs the multi-second
        # race; concurrent hashers take the host path immediately
        # instead of stalling behind the calibration
        if not _calibrate_lock.acquire(blocking=False):
            return _host.value(data)
        try:
            if _device_wins is None:       # double-checked: one racer
                buf = np.frombuffer(memoryview(data), dtype=np.uint8) \
                    if not isinstance(data, np.ndarray) else data
                _device_wins = _calibrate(buf)
        finally:
            _calibrate_lock.release()
    if not _device_wins:
        return _host.value(data)
    return device_crc32c(data)
