"""On-chip sweep of raw-CRC kernel variants (task: tune the Pallas path).

Methodology notes:
- a value fetch is the sync point (it cannot return before the
  device finishes).
- loop-invariant code motion: a fori_loop whose body reads the same
  buffer computes ONE pass; the body must depend on the loop index.
  Here each iteration XORs the buffer with i (adds ~2x input HBM
  traffic, ~1 ms at 819 GB/s — negligible vs the matmul).

Usage: python scripts/pallas_sweep.py [K_ITERS] [N_ROWS_LOG2]
"""

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from etcd_tpu.ops.crc_device import (
    _from_bits32,
    _unpack_bits,
    contribution_matrix,
)
from etcd_tpu.utils.jaxenv import configure_compile_cache

configure_compile_cache()

K = int(sys.argv[1]) if len(sys.argv) > 1 else 12
N = 1 << (int(sys.argv[2]) if len(sys.argv) > 2 else 20)
L = 384

rng = np.random.default_rng(0)
cnp = contribution_matrix(L)


def measure(name, fn, buf, k=K):
    """fn: [N, L] uint8 -> uint32 [N]; returns GB/s of input bytes."""

    @functools.partial(jax.jit, static_argnames=("kk",))
    def loop(b, kk):
        def body(i, acc):
            r = fn(b ^ i.astype(jnp.uint8))
            return acc ^ r[0] ^ r[-1]

        return jax.lax.fori_loop(0, kk, body, jnp.uint32(0))

    try:
        # warm with the SAME static k: a different k is a different
        # executable and its compile would land in the timed region
        int(loop(buf, k))
        t0 = time.perf_counter()
        int(loop(buf, k))
        dt = time.perf_counter() - t0
    except Exception as e:
        print(f"{name}: FAILED {type(e).__name__}: {str(e)[:160]}")
        return
    gbps = N * L * k / dt / 1e9
    print(f"{name}: {gbps:6.2f} GB/s  ({N*k/dt/1e6:7.1f}M rec/s, "
          f"{dt:.3f}s / {k} iters)", flush=True)


# -- variants ---------------------------------------------------------------

c8 = jnp.asarray(cnp)
cbf = jnp.asarray(cnp, jnp.bfloat16)


def xla_int8(buf):
    bits = _unpack_bits(buf)
    acc = jax.lax.dot_general(
        bits, c8, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return _from_bits32(acc & 1)


def xla_bf16(buf):
    bits = _unpack_bits(buf).astype(jnp.bfloat16)
    acc = jax.lax.dot_general(
        bits, cbf, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return _from_bits32(acc.astype(jnp.int32) & 1)


def pallas_current(buf):
    from etcd_tpu.ops.crc_pallas import raw_crc_pallas
    return raw_crc_pallas(buf, c8)


def make_pallas_planes(tile, dtype):
    """Per-bit-plane dots in VMEM; no concatenate; optional bf16 MXU."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # plane-major contribution: cp[k] is [L, 32] for bit k
    cp = cnp.reshape(L, 8, 32).transpose(1, 0, 2)  # [8, L, 32]
    if dtype == jnp.bfloat16:
        cpj = jnp.asarray(cp, jnp.bfloat16)
    else:
        cpj = jnp.asarray(cp, jnp.int8)

    def kernel(buf_ref, c_ref, out_ref):
        x = buf_ref[:].astype(jnp.int32) & 0xFF
        acc = None
        for k in range(8):
            bits = ((x >> k) & 1).astype(dtype)
            d = jax.lax.dot_general(
                bits, c_ref[k],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32
                if dtype == jnp.bfloat16 else jnp.int32)
            acc = d if acc is None else acc + d
        if dtype == jnp.bfloat16:
            acc = acc.astype(jnp.int32)
        out_ref[:] = acc & 1

    @jax.jit
    def run(buf):
        from jax.experimental import pallas as pl
        n = buf.shape[0]
        n_pad = (n + tile - 1) // tile * tile
        buf8 = jax.lax.bitcast_convert_type(
            jnp.pad(buf, ((0, n_pad - n), (0, 0))), jnp.int8)
        parity = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n_pad, 32), jnp.int32),
            grid=(n_pad // tile,),
            in_specs=[
                pl.BlockSpec((tile, L), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((8, L, 32), lambda i: (0, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((tile, 32), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
        )(buf8, cpj)
        return _from_bits32(parity[:n])

    return run


def make_pallas_concat(tile):
    """Current kernel shape but parametrized tile."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    cr = cnp.reshape(L, 8, 32).transpose(1, 0, 2).reshape(8 * L, 32)
    crj = jnp.asarray(cr, jnp.int8)

    def kernel(buf_ref, c_ref, out_ref):
        x = buf_ref[:].astype(jnp.int32) & 0xFF
        bits = jnp.concatenate(
            [((x >> k) & 1).astype(jnp.int8) for k in range(8)], axis=1)
        acc = jax.lax.dot_general(
            bits, c_ref[:], dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        out_ref[:] = acc & 1

    @jax.jit
    def run(buf):
        n = buf.shape[0]
        n_pad = (n + tile - 1) // tile * tile
        buf8 = jax.lax.bitcast_convert_type(
            jnp.pad(buf, ((0, n_pad - n), (0, 0))), jnp.int8)
        parity = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n_pad, 32), jnp.int32),
            grid=(n_pad // tile,),
            in_specs=[
                pl.BlockSpec((tile, L), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((8 * L, 32), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((tile, 32), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
        )(buf8, crj)
        return _from_bits32(parity[:n])

    return run


def main():
    print(f"backend={jax.default_backend()} N={N} L={L} K={K}",
          flush=True)
    buf = jax.device_put(
        rng.integers(0, 256, size=(N, L), dtype=np.uint8))
    buf.block_until_ready()

    # correctness spot check once
    from etcd_tpu.crc.crc32c import raw_update
    small = np.asarray(buf[:64])
    exp = np.asarray([raw_update(0, r.tobytes()) for r in small],
                     dtype=np.uint32)
    got = np.asarray(xla_int8(jnp.asarray(small)))
    assert (got == exp).all(), "xla_int8 wrong"

    measure("xla_int8        ", xla_int8, buf)
    measure("xla_bf16        ", xla_bf16, buf)
    measure("pallas_current  ", pallas_current, buf)
    for tile in (512, 1024, 2048):
        measure(f"pallas_cat t{tile:4d}",
                make_pallas_concat(tile), buf)
    for tile in (512, 1024, 2048):
        measure(f"pallas_pl8 t{tile:4d}",
                make_pallas_planes(tile, jnp.int8), buf)
    for tile in (1024, 2048):
        measure(f"pallas_bf16 t{tile:3d}",
                make_pallas_planes(tile, jnp.bfloat16), buf)


if __name__ == "__main__":
    main()
