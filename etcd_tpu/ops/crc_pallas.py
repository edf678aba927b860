"""Pallas TPU kernel for the batched raw-CRC bit-matmul.

The pure-XLA path materializes the 8x bit expansion ``[N, 8L]`` in HBM
between the unpack and the matmul unless XLA fuses it; this kernel
guarantees the expansion lives only in VMEM: each grid step DMAs a
``[TILE, KT]`` byte block in, unpacks bits on the VPU, and contracts
with a ``[8*KT, 32]`` block of the contribution matrix on the MXU.

The width rule (stated here, decided by shape, never by an exception
handler): rows up to ``K_TILE`` bytes — the replay lanes' 128-multiple
width classes — keep the WHOLE matrix resident and take one
contraction step; wider rows — the power-of-two classes, 80 KB commit
frontiers at 10k groups, 256 KiB snapshot chunks — walk the
contraction axis in ``K_TILE``-byte blocks, accumulating int32
partial sums in VMEM scratch.  On the v5e the resident form compiles
through L=16384 and is refused at L=131072 (131.87 MiB of a 128 MiB
VMEM; chip run, PR 21); the blocked form compiled at every width
tried, 128 through 262148.

Output is parity bits ``[N, 32]`` (int32); the caller packs to uint32
(a cheap fused elementwise op).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Row tile of the narrow (resident-matrix) shapes.
TILE = 512
# VMEM budget for the per-tile bit expansion ([TILE, 8*KT] int8 plus
# the [TILE, KT] int32 byte tile ≈ 12*TILE*KT bytes). Tiles shrink as
# the K block widens; ~6 MB leaves headroom for the double-buffered
# contribution block and the output.
_VMEM_BUDGET = 6 << 20
# Contraction block in bytes.  2 KiB is where the replay lanes' width
# classes switch from 128-multiples to powers of two
# (wal/replay_device._width_classes), so every wider class is a whole
# number of blocks.
K_TILE = 2048
_LANE = 128


def _tile_for(kt: int) -> int:
    t = TILE
    while t > 8 and 12 * t * kt > _VMEM_BUDGET:
        t //= 2
    return t


def _kernel(buf_ref, c_ref, out_ref, acc_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # buf arrives as int8 (bitcast of uint8); recover 0..255 in int32.
    x = buf_ref[:].astype(jnp.int32) & 0xFF  # [TILE, KT]
    # Unpack all 8 bit planes in VMEM (never HBM — that is the whole
    # point of this kernel) and contract in ONE MXU matmul
    # [TILE, 8*KT] @ [8*KT, 32]: XOR over GF(2) = integer sum +
    # parity.  c_ref rows are bit-plane-major inside the block: row
    # b*KT + i = bit b of the block's byte i.
    bits = jnp.concatenate(
        [((x >> b) & 1).astype(jnp.int8) for b in range(8)], axis=1)
    acc_ref[...] += jax.lax.dot_general(
        bits, c_ref[:], dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        out_ref[:] = acc_ref[...] & 1


@functools.partial(jax.jit, static_argnames=("interpret",))
def raw_crc_pallas(buf: jnp.ndarray, c: jnp.ndarray,
                   interpret: bool = False) -> jnp.ndarray:
    """Raw CRC states of right-aligned rows; uint32 [N].

    ``buf`` [N, L] uint8, ``c`` [8L, 32] int8 contribution matrix.
    N is padded up to a TILE multiple and L on the LEFT up to a whole
    number of K blocks (zero rows and leading zero bytes give raw
    state 0; the matrix gains zero rows to match).
    """
    n, length = buf.shape
    kt = min(-(-length // _LANE) * _LANE, K_TILE)
    l_pad = -(-length // kt) * kt
    tile = _tile_for(kt)
    n_pad = -(-n // tile) * tile
    buf8 = jax.lax.bitcast_convert_type(
        jnp.pad(buf, ((0, n_pad - n), (l_pad - length, 0))), jnp.int8)
    c = jnp.pad(c, ((8 * (l_pad - length), 0), (0, 0)))
    # Reorder contribution rows from byte-major (8i+b) to K-block-
    # major, bit-plane-major inside each block, for the kernel's
    # per-plane slices.
    nb = l_pad // kt
    c = c.reshape(nb, kt, 8, 32).transpose(0, 2, 1, 3).reshape(
        nb * 8 * kt, 32)
    parity = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((n_pad, 32), jnp.int32),
        grid=(n_pad // tile, nb),
        in_specs=[
            pl.BlockSpec((tile, kt), lambda i, k: (i, k)),
            pl.BlockSpec((8 * kt, 32), lambda i, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((tile, 32), lambda i, k: (i, 0)),
        scratch_shapes=[pltpu.VMEM((tile, 32), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="crc32c_rows",
    )(buf8, c)
    bits32 = jnp.arange(32, dtype=jnp.uint32)
    packed = jnp.sum(parity.astype(jnp.uint32) << bits32, axis=1,
                     dtype=jnp.uint32)
    return packed[:n]
