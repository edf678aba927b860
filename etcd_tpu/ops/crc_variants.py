"""Alternative device formulations of the batched raw-CRC contraction.

The production path (ops/crc_device.py:_raw_crc_jit) materializes the
8x bit expansion ``[N, 8L]`` and contracts with the ``[8L, 32]``
contribution matrix.  VERDICT r3 #2 asks for kernel variants that
avoid the bit expansion and use the MXU better; this module holds the
candidates, all bit-exact with ``raw_crc_batch`` (property-tested on
CPU; the script that raced them on hardware went with PR 27, the
served path's kernel is measured by the benchmark's ``crc_roofline``):

- ``raw_crc_planes``: NO bit unpack.  Because the final reduction is
  a parity, the exact bit values are not needed — only their sum mod
  2.  For byte x, ``(x >> k) & 127 ≡ bit_k(x) (mod 2)`` (dropping bit
  7's value-128 term changes the integer sum by an even number), so

      parity( Σ_k ((x >> k) & 127) @ C_k ) == parity( bits @ C )

  with ``C_k [L, 32]`` = the bit-k rows of the contribution matrix.
  Eight int8 ``[N, L] @ [L, 32]`` matmuls replace the unpack + one
  ``[N, 8L] @ [8L, 32]``: same MACs, but the operands stay packed
  (8x smaller reads) and the int8 planes fit MXU-native tiles.
  Accumulation bound: 8 * L * 127 < 2^31 for any realistic L.

- ``raw_crc_transposed``: the same contraction with the OUTPUT as
  ``[32, N]`` instead of ``[N, 32]``.  A [M, K] @ [K, 32] matmul pads
  its 32 output lanes to the MXU's 128 — 4x of the systolic array's
  work is discarded.  Contracted as ``C^T [32, 8L] @ bits^T [8L, N]``
  the lane dimension is N (fully utilized) and the 32 sits in the
  sublane-tiled M dimension, which int8 tiles at exactly 32.
  Expressed via dot_general dimension numbers; XLA owns the layouts.

- ``raw_crc_planes_t``: both together.

- ``raw_crc_pallas_planes`` / ``raw_crc_pallas_planes_t``: the planes
  contraction as a Pallas kernel.  The round-3 pallas kernel
  (ops/crc_pallas.py) concatenates all 8 bit planes into a
  ``[TILE, 8L]`` VMEM buffer before one matmul; these keep the byte
  tile packed and issue 8 accumulating ``[TILE, L] @ [L, 32]`` (resp.
  transposed) MXU matmuls instead — the bit expansion never exists,
  not even in VMEM, so tiles can be 4x larger in the same budget.

- ``raw_crc_int4`` / ``raw_crc_planes4`` (TPU_RACE_VARIANTS only):
  the same contractions with int4 operands (bits are 0/1; 3-bit
  plane remnants ``(x >> k) & 7`` fit int4's [-8, 7]), betting on the
  MXU's higher int4 throughput.  Excluded from the CPU-tested
  VARIANTS dict: XLA's CPU emulation of s4 dots is pathologically
  slow to compile, and nothing gates them on hardware since the race
  script went (PR 27).

Reference semantics being reproduced: the sequential rolling CRC of
wal/decoder.go:28-47 / pkg/crc (see ops/crc_device.py's module
docstring for the linear-algebra framing).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .crc_device import _from_bits32, _unpack_bits, contribution_matrix


@functools.lru_cache(maxsize=16)
def plane_matrices(length: int) -> np.ndarray:
    """``[8, L, 32]`` int8: plane k's contribution matrix C_k (the
    bit-k rows of contribution_matrix)."""
    c = contribution_matrix(length)              # [8L, 32], row 8i+k
    return np.ascontiguousarray(
        c.reshape(length, 8, 32).transpose(1, 0, 2))


@functools.partial(jax.jit, static_argnames=())
def _planes_jit(buf: jnp.ndarray, ck: jnp.ndarray) -> jnp.ndarray:
    x = buf.astype(jnp.int32)
    acc = None
    for k in range(8):
        p = ((x >> k) & 127).astype(jnp.int8)    # ≡ bit_k (mod 2)
        r = jax.lax.dot_general(
            p, ck[k], dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        acc = r if acc is None else acc + r
    return _from_bits32(acc & 1)


def raw_crc_planes(buf) -> jnp.ndarray:
    """Packed-plane contraction: uint32 [N] raw CRC states."""
    buf = jnp.asarray(buf, dtype=jnp.uint8)
    ck = jnp.asarray(plane_matrices(buf.shape[1]))
    return _planes_jit(buf, ck)


@functools.partial(jax.jit, static_argnames=())
def _transposed_jit(buf: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    bits = _unpack_bits(buf)                     # [N, 8L] int8
    # out[32, N] = C^T @ bits^T, expressed as dot_general contracting
    # c's row axis with bits' column axis — no explicit transpose op,
    # XLA assigns layouts
    acc = jax.lax.dot_general(
        c, bits, dimension_numbers=(((0,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)        # [32, N]
    return _from_bits32((acc & 1).T)


def raw_crc_transposed(buf) -> jnp.ndarray:
    """Lane-filling orientation: uint32 [N] raw CRC states."""
    buf = jnp.asarray(buf, dtype=jnp.uint8)
    c = jnp.asarray(contribution_matrix(buf.shape[1]))
    return _transposed_jit(buf, c)


@functools.partial(jax.jit, static_argnames=())
def _planes_t_jit(buf: jnp.ndarray, ck: jnp.ndarray) -> jnp.ndarray:
    x = buf.astype(jnp.int32)
    acc = None
    for k in range(8):
        p = ((x >> k) & 127).astype(jnp.int8)
        r = jax.lax.dot_general(
            ck[k], p, dimension_numbers=(((0,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)    # [32, N]
        acc = r if acc is None else acc + r
    return _from_bits32((acc & 1).T)


def raw_crc_planes_t(buf) -> jnp.ndarray:
    """Packed planes + lane-filling orientation: uint32 [N]."""
    buf = jnp.asarray(buf, dtype=jnp.uint8)
    ck = jnp.asarray(plane_matrices(buf.shape[1]))
    return _planes_t_jit(buf, ck)


# -- pallas planes kernels ---------------------------------------------------

#: VMEM budget for the packed-planes kernels: the int32 byte tile
#: (4*T*L) + one int8 plane (T*L) + the int32 accumulator — about
#: 5*T*L working set, vs ~12*T*L for the concat kernel's 8-plane
#: expansion, hence the larger default tile.
_PLANES_VMEM_BUDGET = 10 << 20


def _planes_tile_for(length: int, tile: int) -> int:
    t = tile
    while t > 8 and 5 * t * length > _PLANES_VMEM_BUDGET:
        t //= 2
    return t


def _pallas_planes_kernel(perturb_ref, buf_ref, ck_ref, out_ref):
    # perturb: scalar XORed into every byte IN VMEM — a sustained
    # measurement loop uses it to defeat loop-invariant hoisting
    # without materializing a perturbed [N, L] copy in HBM each
    # iteration (the outer `rows ^ i` costs a full extra HBM
    # read+write pass per iteration).  0 = unperturbed (the
    # correctness-gated iteration).
    x = (buf_ref[:].astype(jnp.int32) & 0xFF) ^ perturb_ref[0]
    acc = None
    for k in range(8):                               # unrolled
        p = ((x >> k) & 1).astype(jnp.int8)          # bit plane k
        r = jax.lax.dot_general(
            p, ck_ref[k], dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)        # [T, 32]
        acc = r if acc is None else acc + r
    out_ref[:] = acc & 1


def _pallas_planes_t_kernel(perturb_ref, buf_ref, ck_ref, out_ref):
    x = (buf_ref[:].astype(jnp.int32) & 0xFF) ^ perturb_ref[0]
    acc = None
    for k in range(8):
        p = ((x >> k) & 1).astype(jnp.int8)
        r = jax.lax.dot_general(
            ck_ref[k], p, dimension_numbers=(((0,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)        # [32, T]
        acc = r if acc is None else acc + r
    out_ref[:] = acc & 1


@functools.partial(jax.jit,
                   static_argnames=("tile", "transposed", "interpret"))
def _pallas_planes_jit(buf, ck, tile, transposed, interpret,
                       perturb=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, length = buf.shape
    t = _planes_tile_for(length, tile)
    n_pad = (n + t - 1) // t * t
    buf8 = jax.lax.bitcast_convert_type(
        jnp.pad(buf, ((0, n_pad - n), (0, 0))), jnp.int8)
    if perturb is None:
        perturb = jnp.zeros((1,), jnp.int32)
    else:
        perturb = jnp.asarray(perturb, jnp.int32).reshape(1) & 0xFF
    grid = (n_pad // t,)
    mem = pl.ANY if interpret else pltpu.VMEM
    smem = pl.ANY if interpret else pltpu.SMEM
    if transposed:
        out_shape = jax.ShapeDtypeStruct((32, n_pad), jnp.int32)
        out_spec = pl.BlockSpec((32, t), lambda i: (0, i),
                                memory_space=mem)
        kernel = _pallas_planes_t_kernel
    else:
        out_shape = jax.ShapeDtypeStruct((n_pad, 32), jnp.int32)
        out_spec = pl.BlockSpec((t, 32), lambda i: (i, 0),
                                memory_space=mem)
        kernel = _pallas_planes_kernel
    parity = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,), memory_space=smem),
            pl.BlockSpec((t, length), lambda i: (i, 0),
                         memory_space=mem),
            pl.BlockSpec((8, length, 32), lambda i: (0, 0, 0),
                         memory_space=mem),
        ],
        out_specs=out_spec,
        interpret=interpret,
    )(perturb, buf8, ck)
    if transposed:
        parity = parity.T
    return _from_bits32(parity & 1)[:n]


#: default tile for the packed-planes kernels; override per-call (the
#: race script sweeps it via ETCD_CRC_TILE).
PLANES_TILE = 1024


def _planes_env_tile() -> int:
    import os

    return int(os.environ.get("ETCD_CRC_TILE", PLANES_TILE))


def raw_crc_pallas_planes(buf, tile: int | None = None,
                          interpret: bool | None = None) -> jnp.ndarray:
    """Packed-planes Pallas kernel: uint32 [N] raw CRC states."""
    buf = jnp.asarray(buf, dtype=jnp.uint8)
    ck = jnp.asarray(plane_matrices(buf.shape[1]))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _pallas_planes_jit(buf, ck, tile or _planes_env_tile(),
                              False, interpret)


def raw_crc_pallas_planes_t(buf, tile: int | None = None,
                            interpret: bool | None = None) -> jnp.ndarray:
    """Packed-planes Pallas kernel, lane-filling [32, N] orientation."""
    buf = jnp.asarray(buf, dtype=jnp.uint8)
    ck = jnp.asarray(plane_matrices(buf.shape[1]))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _pallas_planes_jit(buf, ck, tile or _planes_env_tile(),
                              True, interpret)


# -- int4 operand variants (raced on hardware only; see module doc) ----------


@functools.partial(jax.jit, static_argnames=())
def _int4_jit(buf: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    bits = _unpack_bits(buf).astype(jnp.int4)        # [N, 8L] 0/1
    acc = jax.lax.dot_general(
        bits, c.astype(jnp.int4),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return _from_bits32(acc & 1)


def raw_crc_int4(buf) -> jnp.ndarray:
    """Dense bit contraction with int4 MXU operands: uint32 [N]."""
    buf = jnp.asarray(buf, dtype=jnp.uint8)
    c = jnp.asarray(contribution_matrix(buf.shape[1]))
    return _int4_jit(buf, c)


@functools.partial(jax.jit, static_argnames=())
def _planes4_jit(buf: jnp.ndarray, ck: jnp.ndarray) -> jnp.ndarray:
    x = buf.astype(jnp.int32)
    ck4 = ck.astype(jnp.int4)
    acc = None
    for k in range(8):
        p = ((x >> k) & 7).astype(jnp.int4)          # ≡ bit_k (mod 2)
        r = jax.lax.dot_general(
            p, ck4[k], dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        acc = r if acc is None else acc + r
    return _from_bits32(acc & 1)


def raw_crc_planes4(buf) -> jnp.ndarray:
    """Packed-plane contraction with int4 operands: uint32 [N]."""
    buf = jnp.asarray(buf, dtype=jnp.uint8)
    ck = jnp.asarray(plane_matrices(buf.shape[1]))
    return _planes4_jit(buf, ck)


#: name -> callable, the CPU-tested candidates
VARIANTS = {
    "planes": raw_crc_planes,
    "transposed": raw_crc_transposed,
    "planes_t": raw_crc_planes_t,
    "pallas_planes": raw_crc_pallas_planes,
    "pallas_planes_t": raw_crc_pallas_planes_t,
}

#: hardware-only candidates: correct everywhere, but XLA's CPU s4-dot
#: emulation compiles for minutes, so the CPU test matrix skips them;
#: the race script gates them with the same chain verify on chip.
TPU_RACE_VARIANTS = {
    "int4": raw_crc_int4,
    "planes4": raw_crc_planes4,
}


def parse_variant(name: str) -> tuple[str, int | None]:
    """Validate and split a variant name of the ``base`` or
    ``base@tile`` grammar the race script takes.  Returns (base, tile-or-None); raises
    ValueError on an unknown base or a non-numeric tile — a typo
    must fail loudly, not run some other kernel under the wrong
    label in a bench artifact."""
    base, _, tile = name.partition("@")
    known = ({"xla", "pallas"} | set(VARIANTS)
             | set(TPU_RACE_VARIANTS))
    if base not in known:
        raise ValueError(f"unknown CRC variant {name!r}")
    if tile and not tile.isdigit():
        raise ValueError(f"non-numeric tile in variant {name!r}")
    if tile and not base.startswith("pallas_planes"):
        raise ValueError(f"only pallas_planes kernels take @tile: "
                         f"{name!r}")
    return base, int(tile) if tile else None


def pallas_planes_perturbed(name: str = "pallas_planes",
                            tile: int | None = None):
    """``(buf, i) -> raw CRCs of buf ^ uint8(i)`` with the
    perturbation applied inside the kernel (VMEM), for a sustained
    measurement loop: the outer ``rows ^ i`` form costs a full extra
    HBM read+write pass of the batch per iteration purely to defeat
    loop-invariant hoisting; a scalar SMEM operand defeats it for
    free.  ``i == 0`` is the unperturbed, correctness-gated pass."""
    transposed = name.endswith("_t")

    def fn(buf, i):
        buf = jnp.asarray(buf, dtype=jnp.uint8)
        ck = jnp.asarray(plane_matrices(buf.shape[1]))
        interpret = jax.default_backend() != "tpu"
        return _pallas_planes_jit(buf, ck, tile or _planes_env_tile(),
                                  transposed, interpret, perturb=i)

    return fn
