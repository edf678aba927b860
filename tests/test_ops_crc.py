"""Device CRC op parity vs the host pkg/crc implementation.

Mirrors the reference's CRC coverage (wal/record_test.go corruption
cases, pkg/crc seeding semantics) for the batched device path: every
value the device computes must agree bit-for-bit with the sequential
host digest, and every corruption must be detected.
"""

import time

import numpy as np
import pytest

from etcd_tpu.crc import crc32c, gf2
from etcd_tpu.ops import crc_device
from etcd_tpu.ops.crc_pallas import raw_crc_pallas


@pytest.fixture(scope="module")
def records():
    rng = np.random.default_rng(7)
    L, N = 256, 200
    lens = rng.integers(0, L + 1, size=N)
    lens[0] = 0  # empty record edge case
    lens[1] = L  # full-width record
    buf = np.zeros((N, L), dtype=np.uint8)
    msgs = []
    for i, l in enumerate(lens):
        m = rng.integers(0, 256, size=l, dtype=np.uint8).tobytes()
        msgs.append(m)
        buf[i, L - l:] = np.frombuffer(m, dtype=np.uint8)
    return buf, lens, msgs


def test_raw_crc_parity(records):
    buf, lens, msgs = records
    host = np.array([crc32c.raw_update(0, m) for m in msgs],
                    dtype=np.uint32)
    dev = np.asarray(crc_device.raw_crc_batch(buf, use_pallas=False))
    assert np.array_equal(dev, host)


def test_value_parity(records):
    buf, lens, msgs = records
    host = np.array([crc32c.value(m) for m in msgs], dtype=np.uint32)
    dev = np.asarray(crc_device.crc32c_batch(buf, lens, use_pallas=False))
    assert np.array_equal(dev, host)


def test_pallas_interpret_parity(records):
    buf, lens, msgs = records
    host = np.array([crc32c.raw_update(0, m) for m in msgs],
                    dtype=np.uint32)
    c = np.asarray(crc_device.contribution_matrix(buf.shape[1]))
    dev = np.asarray(raw_crc_pallas(buf, c, interpret=True))
    assert np.array_equal(dev, host)


# T8[:, k] = bits of TABLE[1 << k]: the state after one byte with only
# bit k set, from a zero state.
_T8 = gf2.to_bits(crc32c.TABLE[1 << np.arange(8)]).T


def _contribution_matrix_by_walking(length: int) -> np.ndarray:
    """The oracle: the construction the product used until PR 36,
    position by position from the right with an accumulated zero-byte
    operator — two 32x32 GF(2) matmuls a byte."""
    t8 = _T8
    c = np.zeros((8 * length, 32), dtype=np.int8)
    acc = gf2.identity()  # Z^(L-1-i) as i walks right-to-left
    for i in range(length - 1, -1, -1):
        block = gf2.matmul(acc, t8)  # [32, 8]
        c[8 * i:8 * i + 8, :] = block.T
        acc = gf2.matmul(gf2.Z1, acc)
    return c


@pytest.mark.parametrize("length", [1, 2, 3, 7, 8, 9, 127, 128, 129, 384,
                                    512, 1000, 2048, 4100, 16384])
def test_contribution_matrix_equals_the_walked_oracle(length):
    c = crc_device.contribution_matrix(length)
    want = _contribution_matrix_by_walking(length)
    assert c.shape == want.shape == (8 * length, 32)
    assert c.dtype == want.dtype == np.int8
    assert np.array_equal(c, want)


def test_contribution_matrix_is_a_read_only_suffix_and_quick_when_wide():
    # C(L) is the last 8L rows of C(n) for a power of two n above L
    for length, n in ((3, 4), (100, 128), (384, 512), (513, 1024),
                      (4100, 8192), (4100, 16384)):
        wide = crc_device.contribution_matrix(n)
        assert np.array_equal(crc_device.contribution_matrix(length),
                              wide[8 * (n - length):]), (length, n)
    # cached and shared between callers: nobody may write to it
    c = crc_device.contribution_matrix(512)
    assert c is crc_device.contribution_matrix(512)
    assert not c.flags.writeable
    with pytest.raises(ValueError):
        c[0, 0] = 1
    # the replay's widest class: the walked loop needs ~6 s for it

    def build_from_nothing() -> float:
        crc_device.contribution_matrix.cache_clear()
        crc_device._packed_contributions.cache_clear()
        t0 = time.perf_counter()
        crc_device.contribution_matrix(131072)
        return time.perf_counter() - t0

    # (the best of three: a neighbour's burst may slow one)
    assert min(build_from_nothing() for _ in range(3)) < 2.0
    big = crc_device.contribution_matrix(131072)
    assert big.shape == (8 * 131072, 32) and big.dtype == np.int8
    assert not big.flags.writeable
    assert np.array_equal(big[-8:], _T8.T)  # the rightmost byte: Z^0
    assert np.array_equal(
        big[:8], gf2.matmul(gf2.zero_operator(131071), _T8).T)


def test_shift_crc_matches_gf2(records):
    rng = np.random.default_rng(3)
    states = rng.integers(0, 1 << 32, size=64, dtype=np.uint64).astype(
        np.uint32)
    lens = rng.integers(0, 100_000, size=64)
    dev = np.asarray(crc_device.shift_crc_batch(states, lens))
    host = np.array([gf2.shift(int(s), int(l))
                     for s, l in zip(states, lens)], dtype=np.uint32)
    assert np.array_equal(dev, host)


def test_chain_verify_accepts_good_chain(records):
    buf, lens, msgs = records
    stored = np.empty(len(msgs), dtype=np.uint32)
    prev = 0xDEADBEEF  # non-zero seed, like a post-cut segment
    seed = prev
    for i, m in enumerate(msgs):
        prev = crc32c.update(prev, m)
        stored[i] = prev
    raw = np.asarray(crc_device.raw_crc_batch(buf, use_pallas=False))
    ok = np.asarray(crc_device.chain_verify_device(seed, stored, raw, lens))
    assert ok.all()


def test_chain_verify_flags_corruption(records):
    buf, lens, msgs = records
    stored = np.empty(len(msgs), dtype=np.uint32)
    prev = 0
    for i, m in enumerate(msgs):
        prev = crc32c.update(prev, m)
        stored[i] = prev
    raw = np.asarray(crc_device.raw_crc_batch(buf, use_pallas=False))
    # flip a stored crc: that link and the next must fail
    bad = stored.copy()
    bad[50] ^= 1
    ok = np.asarray(crc_device.chain_verify_device(0, bad, raw, lens))
    assert not ok[50] and not ok[51] and ok[:50].all() and ok[52:].all()
    # corrupt a data row (device sees different raw): only that link
    buf2 = buf.copy()
    assert lens[60] > 0
    buf2[60, -1] ^= 0x80
    raw2 = np.asarray(crc_device.raw_crc_batch(buf2, use_pallas=False))
    ok2 = np.asarray(crc_device.chain_verify_device(0, stored, raw2, lens))
    assert not ok2[60] and ok2[:60].all() and ok2[61:].all()


def test_chain_verify_empty():
    ok = np.asarray(crc_device.chain_verify_device(
        0, np.zeros(0, np.uint32), np.zeros(0, np.uint32),
        np.zeros(0, np.uint32)))
    assert ok.shape == (0,)


def test_commit_index_batch():
    from etcd_tpu.ops import commit_index_batch, maybe_commit_batch
    import jax.numpy as jnp

    match = jnp.array([
        [5, 3, 8, 0, 0],   # 3 members: sorted desc 8,5,3 -> q=2 -> 5
        [1, 1, 1, 1, 1],   # 5 members -> q=3 -> 1
        [9, 2, 4, 7, 1],   # 5 members: desc 9,7,4,2,1 -> q=3 -> 4
    ], dtype=jnp.int32)
    n = jnp.array([3, 5, 5], dtype=jnp.int32)
    mci = np.asarray(commit_index_batch(match, n))
    assert list(mci) == [5, 1, 4]

    # term guard: only group 0's candidate entry carries current term
    cap = 16
    log_terms = jnp.zeros((3, cap), dtype=jnp.int32)
    log_terms = log_terms.at[0, 5].set(2).at[1, 1].set(1).at[2, 4].set(1)
    committed = jnp.array([0, 0, 0], dtype=jnp.int32)
    term = jnp.array([2, 2, 2], dtype=jnp.int32)
    offset = jnp.zeros(3, dtype=jnp.int32)
    out = np.asarray(maybe_commit_batch(match, n, committed, term,
                                        log_terms, offset))
    assert list(out) == [5, 0, 0]


def test_gf2_inverse_roundtrip():
    for k in (1, 4, 7, 256):
        z = gf2.zero_operator(k)
        zi = gf2.inverse(z)
        assert np.array_equal(gf2.matmul(z, zi), gf2.identity())
        assert np.array_equal(gf2.matmul(zi, z), gf2.identity())


def test_inject_seeds_chain_parity():
    """Seed injection folds update(prev, m) into one raw matmul:
    raw(rows') ^ ~0 == update(prev, m) for arbitrary prev values."""
    rng = np.random.default_rng(11)
    L, N = 128, 150
    lens = rng.integers(0, L - 4 + 1, size=N)
    prev = rng.integers(0, 2**32, size=N, dtype=np.uint32)
    rows = np.zeros((N, L), dtype=np.uint8)
    expect = np.empty(N, np.uint32)
    for i, l in enumerate(lens):
        m = rng.integers(0, 256, size=l, dtype=np.uint8).tobytes()
        rows[i, L - l:] = np.frombuffer(m, dtype=np.uint8)
        expect[i] = crc32c.update(int(prev[i]), m)
    crc_device.inject_seeds(rows, lens, prev)
    raw = np.asarray(crc_device.raw_crc_batch(rows, use_pallas=False))
    assert np.array_equal(raw ^ np.uint32(0xFFFFFFFF), expect)
    ok = np.asarray(crc_device.chain_links_injected(raw, expect))
    assert ok.all()
    # corruption detection: flip a byte in one record
    bad = rows.copy()
    bad[2, L - 1] ^= 0x40
    raw_bad = np.asarray(crc_device.raw_crc_batch(bad, use_pallas=False))
    ok_bad = np.asarray(crc_device.chain_links_injected(raw_bad, expect))
    assert not ok_bad[2] and ok_bad[3:].all()


def test_inject_seeds_rejects_tight_rows():
    rows = np.zeros((1, 8), np.uint8)
    with pytest.raises(ValueError):
        crc_device.inject_seeds(rows, np.asarray([5]),
                                np.asarray([0], np.uint32))
