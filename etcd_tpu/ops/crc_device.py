"""Batched CRC32-Castagnoli on device: bit-matmul over GF(2).

The reference verifies WAL records one at a time in a strictly
sequential rolling-CRC loop (wal/decoder.go:28-47, seeded digest
pkg/crc/crc.go:23).  CRC32 is linear over GF(2), which lets the TPU
compute every record's checksum *in parallel* and then verify the
sequential chain with a cheap affine fix-up:

1. **Per-record raw CRC as a matmul.**  For records right-aligned
   (left zero-padded) in a ``[N, L]`` uint8 buffer, the *raw* CRC state
   (no pre/post inversion) of each row is a GF(2)-linear function of
   its bits: ``raw = bits(row) @ C`` where ``C`` is an ``[8L, 32]``
   0/1 contribution matrix (row ``8i+k`` = effect of bit ``k`` of byte
   ``i``).  On TPU this is an int8 matmul on the MXU followed by a
   parity (``& 1``); leading zero-padding is free because a zero raw
   state maps through zero bytes to zero.

2. **Seed/length fix-up.**  Go-convention ``update(c, m)`` equals
   ``Z^len(m) @ (c ^ 0xFFFFFFFF) ^ raw(m) ^ 0xFFFFFFFF`` where ``Z``
   is the one-zero-byte state matrix (crc/gf2.py).  ``Z^len @ x`` is
   evaluated on device by looping over the ~20 bits of ``len`` with
   masked ``[N,32] @ [32,32]`` parity matmuls.

3. **Chain verify.**  The WAL's rolling chain (record i's stored CRC
   must equal ``update(stored[i-1], data_i)``) becomes elementwise:
   verify every link in parallel using the *stored* previous values;
   if all links hold, the chain holds by induction from the seed.

Two execution paths share the math: a pure-XLA path (works on CPU for
tests, and XLA fuses it well) and a Pallas kernel that keeps the 8x
bit-expansion in VMEM instead of materializing ``[N, 8L]`` in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..crc import crc32c as _host
from ..crc import gf2

_MASK32 = 0xFFFFFFFF

# -- host-side constant construction ----------------------------------------


def _byte_tables(m: np.ndarray) -> np.ndarray:
    """[4, 256] uint32: ``t[j][b] = m @ (b << 8j)`` for a ``[32, 32]``
    GF(2) operator ``m`` — evaluates ``m @ x`` over a whole uint32
    array with four table lookups (:func:`_apply_byte_tables`)."""
    cols = gf2.from_bits(m.T).reshape(4, 1, 8)  # image of unit bit 8j+k
    byte_bits = gf2.to_bits(np.arange(256))[:, :8].astype(bool)
    return np.bitwise_xor.reduce(
        np.where(byte_bits[None], cols, np.uint32(0)), axis=2)


def _apply_byte_tables(t: np.ndarray, x: np.ndarray) -> np.ndarray:
    return (t[0, x & 0xFF] ^ t[1, (x >> 8) & 0xFF]
            ^ t[2, (x >> 16) & 0xFF] ^ t[3, x >> 24])


@functools.lru_cache(maxsize=None)
def _packed_contributions(k: int) -> np.ndarray:
    """``contribution_matrix(2**k)`` with each row packed into one
    uint32 (bit j = column j).  A row depends only on its byte's
    distance from the RIGHT end, so the left half of a buffer twice as
    wide is the right half pushed through ``2**(k-1)`` zero bytes:
    ``C(2n) = [Z^n C(n) ; C(n)]`` — k doublings, each four table
    lookups over the half."""
    if k == 0:
        # the state after one byte with only bit b set, from zero
        return _host.TABLE[1 << np.arange(8)].astype(np.uint32)
    half = _packed_contributions(k - 1)
    pushed = _apply_byte_tables(_byte_tables(gf2._POWERS[k - 1]), half)
    return np.concatenate([pushed, half])


@functools.lru_cache(maxsize=16)
def contribution_matrix(length: int) -> np.ndarray:
    """``[8*length, 32]`` int8 matrix C: bits(row) @ C == raw CRC.

    Row ``8*i + k`` is the raw-CRC contribution of bit ``k`` (LSB
    first) of byte ``i`` (byte 0 = leftmost / most-padded position).
    ``C(L)`` is the last ``8L`` rows of ``C(n)`` for any ``n >= L``:
    the suffix of the power of two above, built by doubling on packed
    rows (:func:`_packed_contributions`, shared by every width) and
    expanded to bits once.  Cached and read-only.
    """
    words = _packed_contributions(max(length - 1, 0).bit_length())
    words = words[words.size - 8 * length:].astype("<u4", copy=False)
    c = np.unpackbits(words.view(np.uint8), bitorder="little").view(
        np.int8).reshape(8 * length, 32)
    c.setflags(write=False)
    return c


@functools.lru_cache(maxsize=4)
def _zpow_stack(nbits: int) -> np.ndarray:
    """``[nbits, 32, 32]`` int8 stack of Z^(2^k) transposed for
    right-multiplication: bits_row @ stack[k] == Z^(2^k) @ state."""
    return np.stack([gf2._POWERS[k].T for k in range(nbits)]).astype(np.int8)


@functools.lru_cache(maxsize=16)
def _invert_table(max_len: int) -> np.ndarray:
    """``A[l] = (Z^l @ 0xFFFFFFFF) ^ 0xFFFFFFFF`` for l in [0, max_len].

    With this, Go-convention ``update(0, m) == raw(m) ^ A[len(m)]``.
    """
    out = np.empty(max_len + 1, dtype=np.uint32)
    state = _MASK32  # Z^0 @ ~0
    out[0] = 0
    for l in range(1, max_len + 1):
        state = gf2.matvec(gf2.Z1, state)
        out[l] = np.uint32(state ^ _MASK32)
    return out


# -- device bit helpers ------------------------------------------------------

# NB: no module-level jnp arrays — they would initialize a JAX
# backend at import time, which hangs server boot when the device
# plugin is unreachable (the server imports this module lazily for
# the crc_fn seam). jnp.arange inside traced code constant-folds.


def _to_bits32(x: jnp.ndarray) -> jnp.ndarray:
    """uint32 [...,] -> int8 bits [..., 32] (LSB first)."""
    bit32 = jnp.arange(32, dtype=jnp.uint32)
    return ((x[..., None] >> bit32) & jnp.uint32(1)).astype(jnp.int8)


def _from_bits32(bits: jnp.ndarray) -> jnp.ndarray:
    """int32/int8 0-1 bits [..., 32] -> uint32 [...]."""
    bit32 = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(bits.astype(jnp.uint32) << bit32, axis=-1,
                   dtype=jnp.uint32)


def _unpack_bits(buf: jnp.ndarray) -> jnp.ndarray:
    """uint8 [N, L] -> int8 [N, 8L], LSB-first within each byte."""
    n, length = buf.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (buf[:, :, None] >> shifts) & jnp.uint8(1)
    return bits.reshape(n, 8 * length).astype(jnp.int8)


# -- core ops ----------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def _raw_crc_jit(buf: jnp.ndarray, c: jnp.ndarray,
                 use_pallas: bool = False) -> jnp.ndarray:
    if use_pallas:
        from .crc_pallas import raw_crc_pallas

        return raw_crc_pallas(buf, c)
    bits = _unpack_bits(buf)
    acc = jax.lax.dot_general(
        bits, c, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return _from_bits32(acc & 1)


def _default_use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def raw_crc_batch(buf, use_pallas: bool | None = None,
                  c=None) -> jnp.ndarray:
    """Raw (no-inversion) CRC states of right-aligned rows: uint32 [N].

    ``buf`` is ``[N, L]`` uint8 with each record's bytes occupying the
    *rightmost* ``len`` columns and zeros elsewhere.  ``c`` is the
    uploaded ``contribution_matrix(L)`` where the caller has it (the
    replay times its build and upload as a stage of its own); without
    it the cached host matrix is uploaded on every call, 32 MiB for
    the 131072-byte class.
    """
    buf = jnp.asarray(buf, dtype=jnp.uint8)
    if c is None:
        c = jnp.asarray(contribution_matrix(buf.shape[1]))
    if use_pallas is None:
        use_pallas = _default_use_pallas()
    return _raw_crc_jit(buf, c, use_pallas=use_pallas)


@functools.partial(jax.jit, static_argnames=("nbits",))
def shift_crc_batch(states: jnp.ndarray, lens: jnp.ndarray,
                    nbits: int = 32) -> jnp.ndarray:
    """``Z^lens[i] @ states[i]`` elementwise: uint32 [N].

    Loops over the bits of ``lens`` (default static bound 32: the full
    uint32 range, i.e. shifts up to 4 GiB - 1; callers with a known
    length ceiling pass a smaller ``nbits`` — e.g. WAL-record verify
    with <=512 B rows needs 10 masked matmul rounds, not 32) with
    masked [N,32]@[32,32] parity matmuls — the device form of
    gf2.combine_batch.
    """
    zp = jnp.asarray(_zpow_stack(nbits))  # [nbits, 32, 32] int8
    bits = _to_bits32(jnp.asarray(states, dtype=jnp.uint32))  # [N, 32]
    lens = jnp.asarray(lens, dtype=jnp.uint32)

    def body(k, b):
        shifted = jax.lax.dot_general(
            b, zp[k], dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32) & 1
        take = ((lens >> k) & 1).astype(bool)
        return jnp.where(take[:, None], shifted.astype(jnp.int8), b)

    bits = jax.lax.fori_loop(0, nbits, body, bits)
    return _from_bits32(bits)


def crc32c_batch(buf, lens, use_pallas: bool | None = None) -> jnp.ndarray:
    """Go-convention ``crc32.Update(0, castagnoli, m_i)`` for each row.

    ``buf`` [N, L] uint8 right-aligned, ``lens`` [N] actual byte
    lengths.  Equals ``crc.value(m_i)`` from the host path.
    """
    buf = jnp.asarray(buf, dtype=jnp.uint8)
    raw = raw_crc_batch(buf, use_pallas=use_pallas)
    atab = jnp.asarray(_invert_table(buf.shape[1]))
    lens = jnp.asarray(lens, dtype=jnp.int32)
    return raw ^ jnp.take(atab, lens, axis=0)


@functools.partial(jax.jit, static_argnames=("nbits",))
def _chain_expected(prev_stored: jnp.ndarray, raw: jnp.ndarray,
                    lens: jnp.ndarray,
                    nbits: int = 32) -> jnp.ndarray:
    """update(prev_stored[i], m_i) given raw CRCs: uint32 [N]."""
    inv = prev_stored ^ jnp.uint32(_MASK32)
    shifted = shift_crc_batch(inv, lens, nbits=nbits)
    return shifted ^ raw ^ jnp.uint32(_MASK32)


def chain_verify_device(seed: int, stored, raw, lens,
                        max_len: int | None = None) -> jnp.ndarray:
    """Parallel rolling-chain verification: bool [N].

    ``stored[i]`` is the CRC recorded in record i (must equal
    ``update(stored[i-1], data_i)``, ``stored[-1] == seed``); ``raw``
    is ``raw_crc_batch`` output for the data rows.  True where the
    link holds; all-True implies the full sequential chain holds.
    """
    stored = jnp.asarray(stored, dtype=jnp.uint32)
    if stored.size == 0:
        return jnp.zeros((0,), dtype=bool)
    prev = jnp.concatenate(
        [jnp.asarray([seed], dtype=jnp.uint32), stored[:-1]])
    return chain_links_device(prev, stored, raw, lens, max_len=max_len)


# -- seed injection: the zero-matmul chain verify ----------------------------
#
# CRC is GF(2)-linear, so the seeded update can be folded INTO the raw
# matmul instead of fixed up after it:
#
#   update(prev, m) = Z^len(m) @ (prev ^ ~0) ^ raw(m) ^ ~0
#
# and feeding the 4 little-endian bytes of a value v into a zero CRC
# state yields Z4 @ v (Z4 = the 4-zero-byte operator).  Writing
# p' = Z4^-1 @ (prev ^ ~0) into the 4 padding bytes immediately left
# of each right-aligned record makes the plain raw CRC of the row
#
#   raw(p'_bytes ++ m) = Z^len @ Z4 @ Z4^-1 @ (prev ^ ~0) ^ raw(m)
#                      = Z^len(prev ^ ~0) ^ raw(m)
#
# i.e. update(prev, m) ^ ~0 — the chained value, with NO per-record
# shift matmuls on device (shift_crc_batch runs ~10 masked [N,32]@
# [32,32] rounds; on hardware that costs ~3x the raw matmul itself).
# The 4-byte writes are a vectorized host scatter into padding the
# rows already carry.


@functools.lru_cache(maxsize=1)
def _z4inv_tables() -> np.ndarray:
    """[4, 256] uint32 byte tables of Z4^-1 (:func:`_byte_tables`)."""
    return _byte_tables(gf2.inverse(gf2.zero_operator(4)))


def inject_seeds(rows: np.ndarray, lens, prev) -> np.ndarray:
    """Write Z4^-1(prev ^ ~0) into each row's 4 padding bytes just
    left of its record (host, vectorized, in place).  After this,

        raw_crc_batch(rows) ^ 0xFFFFFFFF == update(prev[i], m_i)

    so the whole rolling-chain verify is one raw-CRC matmul plus an
    elementwise compare against the stored CRCs (decoder.go:28-47
    semantics with zero extra device work).  Requires 4 bytes of
    padding: lens + 4 <= rows.shape[1].
    """
    lens = np.asarray(lens, np.int64)
    n, w = rows.shape
    if n == 0:
        return rows
    if int(lens.max()) + 4 > w:
        raise ValueError(f"need 4 padding bytes: max len "
                         f"{int(lens.max())} + 4 > width {w}")
    t = _z4inv_tables()
    x = np.asarray(prev, np.uint32) ^ np.uint32(_MASK32)
    y = _apply_byte_tables(t, x)
    cols = (w - lens - 4)[:, None] + np.arange(4)
    vals = (y[:, None] >> (8 * np.arange(4, dtype=np.uint32))
            ).astype(np.uint8)
    rows[np.arange(n)[:, None], cols] = vals
    return rows


def chain_links_injected(rows_raw: jnp.ndarray, stored) -> jnp.ndarray:
    """Chain verification for seed-injected rows: bool [N].

    ``rows_raw`` is ``raw_crc_batch`` output for rows prepared by
    :func:`inject_seeds`; ``stored`` the recorded CRCs."""
    return (rows_raw ^ jnp.uint32(_MASK32)) == \
        jnp.asarray(stored, dtype=jnp.uint32)


def chain_links_device(prev, stored, raw, lens,
                       max_len: int | None = None) -> jnp.ndarray:
    """Link-wise chain verification with an explicit prev vector:
    bool [N] where ``update(prev[i], data_i) == stored[i]``.

    The general (multi-stream) form: rows from many independent
    chains — e.g. every co-hosted group's WAL in one batch — verify
    together because each link only needs its own predecessor's
    stored value.  ``max_len``, when known statically (the padded row
    width), bounds the seed-shift loop to ``ceil(log2(max_len+1))``
    masked matmuls instead of 32.
    """
    prev = jnp.asarray(prev, dtype=jnp.uint32)
    if prev.size == 0:
        return jnp.zeros((0,), dtype=bool)
    raw = jnp.asarray(raw, dtype=jnp.uint32)
    lens = jnp.asarray(lens, dtype=jnp.uint32)
    nbits = 32 if max_len is None else max(1, int(max_len).bit_length())
    return _chain_expected(prev, raw, lens, nbits=nbits) == \
        jnp.asarray(stored, dtype=jnp.uint32)
