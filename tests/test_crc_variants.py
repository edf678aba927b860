"""CRC contraction variants must be bit-exact with the production
raw-CRC path and the host oracle (ops/crc_variants.py; the reference
semantics is wal/decoder.go:28-47's rolling CRC, raw form)."""

import numpy as np
import pytest

from etcd_tpu.crc import crc32c
from etcd_tpu.ops.crc_device import raw_crc_batch
from etcd_tpu.ops.crc_variants import VARIANTS


def host_raw(rows, lens):
    out = np.empty(rows.shape[0], np.uint32)
    for i in range(rows.shape[0]):
        row = rows[i]
        out[i] = crc32c.raw_update(0, row.tobytes())
    return out


@pytest.mark.parametrize("name", sorted(VARIANTS))
@pytest.mark.parametrize("n,length", [(1, 4), (7, 36), (64, 132),
                                      (130, 384)])
def test_variant_matches_production_and_host(name, n, length):
    rng = np.random.default_rng(hash((name, n, length)) & 0xFFFF)
    rows = rng.integers(0, 256, size=(n, length), dtype=np.uint8)
    # right-aligned records with random lengths: leading zeros must
    # be transparent (zero state through zero bytes stays zero)
    lens = rng.integers(0, length + 1, size=n)
    for i in range(n):
        rows[i, : length - lens[i]] = 0
    want = np.asarray(raw_crc_batch(rows, use_pallas=False))
    got = np.asarray(VARIANTS[name](rows))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, host_raw(rows, lens))


@pytest.mark.parametrize("name", sorted(VARIANTS))
def test_variant_composes_with_seed_injection(name):
    """The variants slot into the seed-injected chain verify exactly
    like the production path (the sustained-loop contract)."""
    from etcd_tpu.ops.crc_device import chain_links_injected, inject_seeds

    rng = np.random.default_rng(5)
    n, width = 33, 68
    lens = rng.integers(1, width - 4, size=n)
    rows = np.zeros((n, width), np.uint8)
    stored = np.empty(n, np.uint32)
    prev = np.empty(n, np.uint32)
    chain = 17
    for i in range(n):
        data = rng.integers(0, 256, size=lens[i], dtype=np.uint8)
        rows[i, width - lens[i]:] = data
        prev[i] = chain
        chain = crc32c.update(chain, data.tobytes())
        stored[i] = chain
    inject_seeds(rows, lens, prev)
    ok = chain_links_injected(VARIANTS[name](rows), stored)
    assert np.asarray(ok).all()


@pytest.mark.parametrize("name", ["pallas_planes", "pallas_planes_t"])
def test_perturbed_kernel_matches_outer_xor(name):
    """The SMEM perturb operand (the sustained-loop LICM
    defeat) must compute exactly raw(buf ^ uint8(i)) — the headline
    TPU number depends on it, and the bench gate only checks i=0."""
    from etcd_tpu.ops.crc_variants import pallas_planes_perturbed

    rng = np.random.default_rng(11)
    rows = rng.integers(0, 256, size=(70, 132), dtype=np.uint8)
    fn = pallas_planes_perturbed(name)
    for i in (0, 3, 255):
        want = np.asarray(raw_crc_batch(rows ^ np.uint8(i),
                                        use_pallas=False))
        got = np.asarray(fn(rows, i))
        np.testing.assert_array_equal(got, want, err_msg=f"i={i}")
