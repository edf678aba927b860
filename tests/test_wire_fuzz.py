"""Randomized round-trip fuzz for the hand-rolled gogoproto codec
(wire/proto.py): every message type survives marshal→unmarshal for
arbitrary field values (full uint64 range, empty/None/large bytes),
and the decoder never crashes unrecoverably on mutated input — it
either raises ProtoError or returns a value.

Complements the golden-bytes tests in test_wire.py (exact layout)
with breadth the table tests cannot reach.
"""

import random
import time

import pytest

from etcd_tpu.wire.proto import (
    ConfChange,
    Entry,
    GroupEntry,
    HardState,
    Message,
    ProtoError,
    Record,
    Snapshot,
    SnapPb,
)

U64 = (1 << 64) - 1


def _u64(rng):
    # bias toward varint boundaries: 0, small, 2^7k edges, max
    choice = rng.random()
    if choice < 0.2:
        return 0
    if choice < 0.5:
        return rng.randrange(1 << 7)
    if choice < 0.8:
        k = rng.randrange(1, 10)
        return min(U64, (1 << (7 * k)) + rng.randrange(-1, 2))
    return rng.randrange(U64 + 1)


def _bytes(rng):
    n = rng.choice([0, 1, 7, 64, 1000])
    return rng.randbytes(n)


def _entry(rng):
    return Entry(type=rng.randrange(2), term=_u64(rng),
                 index=_u64(rng), data=_bytes(rng))


def _snapshot(rng):
    return Snapshot(data=_bytes(rng),
                    nodes=[_u64(rng) for _ in range(rng.randrange(4))],
                    index=_u64(rng), term=_u64(rng),
                    removed_nodes=[_u64(rng)
                                   for _ in range(rng.randrange(3))])


def _cases(rng):
    yield _entry(rng)
    yield _snapshot(rng)
    yield Message(type=rng.randrange(12), to=_u64(rng),
                  from_=_u64(rng), term=_u64(rng), log_term=_u64(rng),
                  index=_u64(rng),
                  entries=[_entry(rng) for _ in range(rng.randrange(4))],
                  commit=_u64(rng), snapshot=_snapshot(rng),
                  reject=rng.random() < 0.5)
    yield HardState(term=_u64(rng), vote=_u64(rng), commit=_u64(rng))
    yield ConfChange(id=_u64(rng), type=rng.randrange(2),
                     node_id=_u64(rng), context=_bytes(rng))
    yield Record(type=rng.randrange(5), crc=rng.randrange(1 << 32),
                 data=rng.choice([None, b"", _bytes(rng)]))
    yield GroupEntry(kind=rng.randrange(2), group=_u64(rng),
                     gindex=_u64(rng), gterm=_u64(rng),
                     payload=rng.choice([None, b"", _bytes(rng)]))
    yield SnapPb(crc=rng.randrange(1 << 32),
                 data=rng.choice([None, b"", _bytes(rng)]))


@pytest.mark.parametrize("seed", range(20))
def test_roundtrip_fuzz(seed):
    rng = random.Random(seed)
    for _ in range(25):
        for msg in _cases(rng):
            wire = msg.marshal()
            back = type(msg).unmarshal(wire)
            assert back == msg, type(msg).__name__
            assert back.marshal() == wire  # re-encode is byte-stable


# -- dist frames (wire/distmsg.py): the pipelined [G]-batched tier ---------


def _dist_cases(rng):
    import numpy as np

    from etcd_tpu.wire.distmsg import (
        AppendBatch,
        AppendResp,
        VoteReq,
        VoteResp,
    )

    from etcd_tpu.wire.distmsg import PackedPayloads, flat_entry_table

    g = rng.choice([1, 3, 8])
    e = rng.choice([1, 2, 5])
    i32 = lambda lo=0, hi=1 << 20: np.asarray(  # noqa: E731
        [rng.randrange(lo, hi) for _ in range(g)], np.int32)
    mask = lambda: np.asarray(  # noqa: E731
        [rng.random() < 0.5 for _ in range(g)], bool)
    seq = rng.randrange(1 << 31)
    epoch = rng.randrange(1 << 31)
    prev_idx = i32()
    n_ents = np.asarray([rng.randrange(e + 1) for _ in range(g)],
                        np.int32)
    payloads = [[_bytes(rng) for _ in range(int(n))] for n in n_ents]
    # optional trace block (PR 8): absent (the pre-trace layout,
    # must parse as today) or a few sampled entries that round-trip
    trace = None
    if rng.random() < 0.5:
        trace = [(rng.randrange(g), rng.randrange(1 << 20),
                  rng.randrange(1 << 32), rng.randrange(8))
                 for _ in range(rng.randrange(1, 4))]
    # optional packed multi-group table (PR 14): the DGB3 trailing
    # section; the table is fully determined by (prev_idx, n_ents),
    # so valid frames can only carry the canonical one.  Half the
    # packed cases hand marshal the flat PackedPayloads form (the
    # serving-loop fast path); the rest nested lists.
    ent_group = ent_gindex = None
    pays = payloads
    if rng.random() < 0.5:
        ent_group, ent_gindex = flat_entry_table(prev_idx, n_ents)
        if rng.random() < 0.5:
            pays = PackedPayloads.from_counts(
                [b for grp in payloads for b in grp], n_ents)
    yield AppendBatch(
        sender=rng.randrange(4), term=i32(), prev_idx=prev_idx,
        prev_term=i32(), n_ents=n_ents, commit=i32(), active=mask(),
        need_snap=mask(),
        ent_terms=np.asarray(
            [[rng.randrange(1 << 20) for _ in range(e)]
             for _ in range(g)], np.int32),
        payloads=pays, seq=seq, epoch=epoch, trace=trace,
        ent_group=ent_group, ent_gindex=ent_gindex)
    yield AppendResp(sender=rng.randrange(4), term=i32(), ok=mask(),
                     acked=i32(), hint=i32(), active=mask(),
                     seq=seq, epoch=epoch)
    yield VoteReq(sender=rng.randrange(4), term=i32(), last=i32(),
                  lterm=i32(), active=mask())
    yield VoteResp(sender=rng.randrange(4), term=i32(),
                   granted=mask(), active=mask())


def _dist_eq(a, b) -> bool:
    import numpy as np

    if type(a) is not type(b):
        return False
    for f in a.__dataclass_fields__:
        if f == "appended":
            continue  # local-only, never marshalled
        x, y = getattr(a, f), getattr(b, f)
        if isinstance(x, np.ndarray):
            if not np.array_equal(np.asarray(x, np.int64),
                                  np.asarray(y, np.int64)):
                return False
        elif x != y:
            return False
    return True


@pytest.mark.parametrize("seed", range(14))
def test_dist_frame_roundtrip_fuzz(seed):
    """Every dist frame kind survives marshal→unmarshal with the
    seq/epoch header tags intact (the pipeline's ack matching rides
    on them), and re-encoding is byte-stable — the zero-copy
    preallocated-buffer marshal must produce the same bytes the
    tobytes/join form did."""
    from etcd_tpu.wire.distmsg import unmarshal_any

    rng = random.Random(3000 + seed)
    for _ in range(20):
        for msg in _dist_cases(rng):
            wire = bytes(msg.marshal())
            back = unmarshal_any(wire)
            assert _dist_eq(back, msg), type(msg).__name__
            assert bytes(back.marshal()) == wire


def test_dist_negative_lane_count_rejected_fast():
    """Review regression: one negative + one large-positive n_ents
    lane cancel to a small SUM, so a sum-only guard admits the frame
    and the payload loop spins ~2^30 iterations before an IndexError
    — the per-lane check must reject it as FrameError immediately."""
    import struct

    import numpy as np

    from etcd_tpu.wire.distmsg import (
        AppendBatch,
        FrameError,
        unmarshal_any,
    )

    g = 2
    frame = AppendBatch(
        sender=0, term=np.zeros(g, np.int32),
        prev_idx=np.zeros(g, np.int32),
        prev_term=np.zeros(g, np.int32),
        n_ents=np.zeros(g, np.int32),
        commit=np.zeros(g, np.int32),
        active=np.ones(g, bool), need_snap=np.zeros(g, bool),
        ent_terms=np.zeros((g, 1), np.int32),
        payloads=[[], []])
    wire = bytearray(frame.marshal())
    n_ents_off = 24 + 3 * 4 * g  # header + term/prev_idx/prev_term
    struct.pack_into("<ii", wire, n_ents_off, 1 << 30,
                     -(1 << 30) + 5)
    t0 = time.perf_counter()
    with pytest.raises(FrameError):
        unmarshal_any(bytes(wire))
    assert time.perf_counter() - t0 < 1.0  # fails fast, no spin


def test_dist_packed_table_validated_against_sections():
    """The DGB3 packed table is redundant with the [G] sections by
    construction, so the decoder recomputes it and demands exact
    agreement: a corrupt table that keeps the flag + count intact
    must fail as FrameError, never reach the serving loop's
    fancy-indexing with out-of-contract (group, gindex) pairs."""
    import struct

    import numpy as np

    from etcd_tpu.wire.distmsg import (
        AppendBatch,
        FrameError,
        flat_entry_table,
        unmarshal_any,
    )

    g = 2
    prev_idx = np.asarray([4, 7], np.int32)
    n_ents = np.asarray([2, 1], np.int32)
    eg, ei = flat_entry_table(prev_idx, n_ents)
    frame = AppendBatch(
        sender=0, term=np.ones(g, np.int32), prev_idx=prev_idx,
        prev_term=np.zeros(g, np.int32), n_ents=n_ents,
        commit=np.zeros(g, np.int32), active=np.ones(g, bool),
        need_snap=np.zeros(g, bool),
        ent_terms=np.ones((g, 2), np.int32),
        payloads=[[b"a", b"bb"], [b"ccc"]],
        ent_group=eg, ent_gindex=ei)
    wire = bytearray(frame.marshal())
    back = unmarshal_any(bytes(wire))  # sanity: valid as built
    assert back.ent_gindex is not None
    # the packed table is the trailing section; its last 4 bytes are
    # the final gindex entry — point it outside the lane's window
    struct.pack_into("<i", wire, len(wire) - 4, 99)
    with pytest.raises(FrameError):
        unmarshal_any(bytes(wire))


@pytest.mark.parametrize("seed", range(22))
def test_dist_decoder_total_on_mutations(seed):
    """Bit-flipped / truncated / extended dist frames never escape
    the codec as anything but FrameError (the drop-tolerant peer
    tier treats a bad frame as a dropped message — an unhandled
    decoder exception would kill the handler thread instead)."""
    from etcd_tpu.wire.distmsg import FrameError, unmarshal_any

    rng = random.Random(4000 + seed)
    for _ in range(30):
        for msg in _dist_cases(rng):
            wire = bytearray(msg.marshal())
            op = rng.randrange(3)
            if op == 0 and wire:
                wire[rng.randrange(len(wire))] ^= 1 << rng.randrange(8)
            elif op == 1 and wire:
                del wire[rng.randrange(len(wire)):]
            else:
                wire += rng.randbytes(rng.randrange(1, 9))
            try:
                unmarshal_any(bytes(wire))
            except FrameError:
                pass  # the one allowed failure mode


@pytest.mark.parametrize("seed", range(10))
def test_decoder_total_on_mutations(seed):
    """Bit-flipped / truncated / extended wire bytes never escape the
    codec as anything but ProtoError (the reference's generated
    unmarshalers return io.ErrUnexpectedEOF / proto errors — never
    panic; decoder totality is what the WAL's corruption handling
    sits on)."""
    rng = random.Random(1000 + seed)
    for _ in range(40):
        for msg in _cases(rng):
            wire = bytearray(msg.marshal())
            op = rng.randrange(3)
            if op == 0 and wire:  # flip a byte
                wire[rng.randrange(len(wire))] ^= 1 << rng.randrange(8)
            elif op == 1 and wire:  # truncate
                del wire[rng.randrange(len(wire)):]
            else:  # append garbage
                wire += rng.randbytes(rng.randrange(1, 9))
            try:
                type(msg).unmarshal(bytes(wire))
            except ProtoError:
                pass  # the one allowed failure mode


# -- schema-driven sweeps (PR 19): scripts/wire_fuzz.py as a library --------
#
# The standalone fuzzer owns the big randomized budgets (scripts/test
# runs --smoke; --check is the 100k/format acceptance gate); tier-1
# pins the DETERMINISTIC schema-driven sweeps — truncation at every
# byte offset, every flag bit, every count-field extreme — for all
# five formats, so a new section or bound is covered the day it is
# declared in wire/schema.py.

import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "scripts"))

import wire_fuzz  # noqa: E402


@pytest.mark.parametrize("fmt", sorted(wire_fuzz.FORMATS))
def test_schema_truncation_at_every_offset(fmt):
    """Every prefix of every valid seed frame parses or fails as the
    format's typed error — no truncation point escapes as
    struct.error/IndexError (wire_fuzz._run_one re-raises any escape
    as a Crasher, which pytest reports)."""
    sch, make_seeds = wire_fuzz.FORMATS[fmt]
    for parser, seed in make_seeds():
        for end in range(len(seed) + 1):
            wire_fuzz._run_one(fmt, sch, parser, seed[:end])


@pytest.mark.parametrize("fmt", sorted(wire_fuzz.FORMATS))
def test_schema_flag_and_count_extremes(fmt):
    """Flag-bit flips (declared + undeclared) and count-field
    extremes written through FrameSchema.header_offsets() stay inside
    the typed-error contract."""
    sch, make_seeds = wire_fuzz.FORMATS[fmt]
    for parser, seed in make_seeds():
        for m in wire_fuzz._flag_mutations(sch, seed):
            wire_fuzz._run_one(fmt, sch, parser, m)
        for m in wire_fuzz._field_mutations(sch, seed):
            wire_fuzz._run_one(fmt, sch, parser, m)


def test_the_fuzzer_covers_exactly_the_declared_formats():
    """A format declared in wire/schema.py and not fuzzed, or fuzzed
    and no longer declared, fails here and not at the next
    crasher."""
    from etcd_tpu.wire import schema

    assert {sch.name for sch, _seeds in wire_fuzz.FORMATS.values()} \
        == {f.name for f in schema.FORMATS} == {"DGB2", "DCB1", "GPB1"}
    assert set(wire_fuzz.FORMATS) == {f.name.lower()
                                      for f in schema.FORMATS}


def test_persisted_crashers_stay_fixed():
    """Any crasher scripts/wire_fuzz.py ever persisted under
    tests/fixtures/wire_crashers/ is replayed here — a reintroduced
    parser bug fails tier-1, not just the next fuzz run."""
    for fmt, (sch, make_seeds) in wire_fuzz.FORMATS.items():
        wire_fuzz._replay_fixtures(fmt, sch, make_seeds())
