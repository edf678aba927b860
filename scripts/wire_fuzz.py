#!/usr/bin/env python
"""Schema-driven wire-frame fuzzer (PR 19).

    scripts/wire_fuzz.py --smoke       ~2k mutated frames per format
                                       (wired into scripts/test)
    scripts/wire_fuzz.py --check       >= 100k mutated frames per
                                       format (the acceptance gate)
    scripts/wire_fuzz.py --frames N    explicit per-format budget
    scripts/wire_fuzz.py --formats dgb2,gpb1   restrict formats
    scripts/wire_fuzz.py --seed N      rng seed (default 20190814)

The declarative schemas (etcd_tpu/wire/schema.py) drive the
mutations, so a new section or count field is fuzzed the day it is
declared:

  * truncation at EVERY byte offset of every seed frame,
  * flag-bit flips — each declared bit and every undeclared bit,
  * header count-field extremes (0, 1, 255, 2^16-1, 2^31-1, 2^32-1,
    all-ones) written through ``FrameSchema.header_offsets()``,
  * signed-overflow extremes at random 4-byte-aligned offsets (the
    i32 length-table ranges), and random byte flips.

The ONE assertion, from the schema's ``error`` field: a mutated
frame either parses or raises the format's typed error (FrameError /
ProtoError).  Anything else — struct.error, IndexError, ValueError,
UnicodeDecodeError, MemoryError — is a crasher: it is persisted to
``tests/fixtures/wire_crashers/<fmt>/`` as a regression fixture
(replayed at the start of every run and by tests/test_wire_fuzz.py)
and the run exits nonzero.
"""

from __future__ import annotations

import argparse
import hashlib
import os
import random
import struct
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from etcd_tpu.wire import clientmsg, distmsg, proto  # noqa: E402
from etcd_tpu.wire import schema as wschema  # noqa: E402
from etcd_tpu.wire.requests import Info, Request  # noqa: E402
from etcd_tpu.wire.schema import FrameError  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CRASHER_DIR = os.path.join(REPO, "tests", "fixtures",
                           "wire_crashers")

#: count-field extreme values, masked to the field's width
EXTREMES = (0, 1, 255, (1 << 16) - 1, (1 << 31) - 1,
            (1 << 32) - 1, 1 << 63, (1 << 64) - 1)


# ---------------------------------------------------------------------------
# seed frames: valid marshals, built by the real writers
# ---------------------------------------------------------------------------

def _dgb2_seeds():
    g, e = 3, 2
    i32 = lambda *v: np.asarray(v, "<i4")  # noqa: E731
    ab = distmsg.AppendBatch(
        sender=1, term=i32(5, 5, 6), prev_idx=i32(9, 0, 3),
        prev_term=i32(5, 0, 6), n_ents=i32(2, 0, 1),
        commit=i32(8, 0, 3), active=np.asarray([1, 0, 1], bool),
        need_snap=np.asarray([0, 0, 0], bool),
        ent_terms=i32(5, 5, 0, 0, 6, 0).reshape(g, e),
        payloads=[[b"aa", b"b"], [], [b"ccc"]], seq=7, epoch=2)
    traced = distmsg.AppendBatch(
        **{**ab.__dict__, "trace": [(0, 10, 123, 1), (2, 4, 99, 0)]})
    eg, ei = distmsg.flat_entry_table(ab.prev_idx, ab.n_ents)
    packed = distmsg.AppendBatch(
        **{**ab.__dict__, "ent_group": eg, "ent_gindex": ei})
    resp = distmsg.AppendResp(
        sender=2, term=i32(5, 5, 6),
        ok=np.asarray([1, 0, 1], bool), acked=i32(11, 0, 4),
        hint=i32(8, 0, 3), active=np.asarray([1, 0, 1], bool),
        seq=7, epoch=2)
    vote = distmsg.VoteReq(
        sender=0, term=i32(6, 6, 6), last=i32(9, 1, 3),
        lterm=i32(5, 5, 6), active=np.asarray([1, 1, 1], bool))
    vresp = distmsg.VoteResp(
        sender=1, term=i32(6, 6, 6),
        granted=np.asarray([1, 0, 1], bool),
        active=np.asarray([1, 1, 1], bool))
    return [(lambda d: distmsg.unmarshal_any(d), bytes(f.marshal()))
            for f in (ab, traced, packed, resp, vote, vresp)]


def _dcb1_parse(data):
    for fn in (clientmsg.unpack_get_request,
               clientmsg.unpack_get_response,
               clientmsg.unpack_propose_response):
        try:
            fn(data)
        except FrameError:
            pass  # wrong kind / malformed: typed is the contract
    # re-raise one typed failure so "parses or FrameError" still
    # exercises every endpoint above
    clientmsg.unpack_get_request(data)


def _dcb1_seeds():
    req = clientmsg.pack_get_request(["/a", "/b/cc", "/日本"])
    resp = clientmsg.pack_get_response(
        ["v1", None, b"raw"], {1: (100, "Key not found")})
    prop = clientmsg.pack_propose_response(3, {0: (105, "exists")})
    return [(_dcb1_parse, bytes(f)) for f in (req, resp, prop)]


def _gpb1_seeds():
    ent = proto.Entry(type=1, term=2, index=3, data=b"payload")
    snap = proto.Snapshot(data=b"sd", nodes=[1, 2], index=9,
                          term=2, removed_nodes=[3])
    msg = proto.Message(type=proto.MSG_APP, to=2, from_=1, term=2,
                        log_term=2, index=9, entries=[ent],
                        commit=8, snapshot=snap, reject=True)
    pairs = [
        (proto.Entry, ent), (proto.Snapshot, snap),
        (proto.Message, msg),
        (proto.HardState, proto.HardState(term=2, vote=1, commit=8)),
        (proto.ConfChange, proto.ConfChange(id=4, type=1, node_id=2,
                                            context=b"ctx")),
        (proto.Record, proto.Record(type=1, crc=0xDEAD, data=b"d")),
        (proto.GroupEntry, proto.GroupEntry(kind=0, group=1,
                                            gindex=5, gterm=2,
                                            payload=b"p")),
        (proto.SnapPb, proto.SnapPb(crc=7, data=b"s")),
        (Request, Request(id=3, method="PUT", path="/k", val="v",
                          prev_value="old", expiration=-5)),
        (Info, Info(id=11)),
    ]
    return [((lambda c: (lambda d: c.unmarshal(d)))(cls),
             obj.marshal()) for cls, obj in pairs]


FORMATS = {
    "dgb2": (wschema.DGB2, _dgb2_seeds),
    "dcb1": (wschema.DCB1, _dcb1_seeds),
    "gpb1": (wschema.GPB1, _gpb1_seeds),
}


# ---------------------------------------------------------------------------
# the harness
# ---------------------------------------------------------------------------

class Crasher(Exception):
    def __init__(self, fmt: str, frame: bytes, exc: BaseException):
        self.fmt, self.frame, self.exc = fmt, frame, exc
        super().__init__(f"{fmt}: {type(exc).__name__}: {exc}")


def _typed(sch) -> type[BaseException]:
    if sch.error == "ProtoError":
        return proto.ProtoError
    return FrameError


def _run_one(fmt: str, sch, parser, frame: bytes) -> None:
    try:
        parser(frame)
    except _typed(sch):
        pass
    except Exception as exc:  # noqa: BLE001 - the fuzz oracle
        raise Crasher(fmt, frame, exc) from exc


def _persist(c: Crasher) -> str:
    d = os.path.join(CRASHER_DIR, c.fmt)
    os.makedirs(d, exist_ok=True)
    name = hashlib.sha1(c.frame).hexdigest()[:16] + ".bin"
    path = os.path.join(d, name)
    with open(path, "wb") as fh:
        fh.write(c.frame)
    return path


def _replay_fixtures(fmt: str, sch, seeds) -> int:
    """Re-run persisted crashers first — a regression fires before
    any new exploration."""
    d = os.path.join(CRASHER_DIR, fmt)
    if not os.path.isdir(d):
        return 0
    n = 0
    for name in sorted(os.listdir(d)):
        if not name.endswith(".bin"):
            continue
        with open(os.path.join(d, name), "rb") as fh:
            frame = fh.read()
        for parser, _seed in seeds:
            _run_one(fmt, sch, parser, frame)
            n += 1
    return n


def _flag_mutations(sch, seed: bytes):
    offs = sch.header_offsets() if sch.header else {}
    if "flags" not in offs:
        return
    off, width, _signed = offs["flags"]
    declared = {f.bit for f in sch.flags}
    bits = [1 << i for i in range(8 * width)]
    (cur,) = struct.unpack_from(f"<{'B' if width == 1 else 'H'}",
                                seed, off)
    for bit in bits:
        for val in (cur | bit, cur ^ bit, bit, 0):
            m = bytearray(seed)
            struct.pack_into(f"<{'B' if width == 1 else 'H'}",
                             m, off, val)
            yield bytes(m)
    # every bit at once — declared (trailing sections in flag-bit
    # order) plus every undeclared bit an old peer must ignore
    del declared
    m = bytearray(seed)
    struct.pack_into(f"<{'B' if width == 1 else 'H'}", m, off,
                     (1 << (8 * width)) - 1)
    yield bytes(m)


def _field_mutations(sch, seed: bytes):
    """Count-field (and kind-field) extremes through the schema's
    header offset table."""
    offs = sch.header_offsets() if sch.header else {}
    targets = list(sch.count_fields) + ["kind"]
    fmt_for = {1: "<B", 2: "<H", 4: "<I", 8: "<Q"}
    for field in targets:
        if field not in offs:
            continue
        off, width, _signed = offs[field]
        for v in EXTREMES:
            m = bytearray(seed)
            struct.pack_into(fmt_for[width], m, off,
                             v & ((1 << (8 * width)) - 1))
            yield bytes(m)


def fuzz_format(fmt: str, budget: int, rng: random.Random,
                verbose: bool = True) -> tuple[int, list[str]]:
    sch, make_seeds = FORMATS[fmt]
    seeds = make_seeds()
    crashers: list[str] = []
    count = 0

    def run(parser, frame: bytes) -> None:
        nonlocal count
        count += 1
        try:
            _run_one(fmt, sch, parser, frame)
        except Crasher as c:
            crashers.append(_persist(c))
            print(f"  CRASHER {fmt}: {type(c.exc).__name__}: "
                  f"{c.exc} -> {crashers[-1]}")

    count += _replay_fixtures(fmt, sch, seeds)

    # deterministic sweeps: truncation at every offset, flag flips,
    # count extremes — schema-driven, every seed
    for parser, seed in seeds:
        for end in range(len(seed) + 1):
            run(parser, seed[:end])
        for m in _flag_mutations(sch, seed):
            run(parser, m)
        for m in _field_mutations(sch, seed):
            run(parser, m)

    # randomized remainder: byte flips + aligned signed extremes
    while count < budget:
        parser, seed = seeds[rng.randrange(len(seeds))]
        m = bytearray(seed)
        for _ in range(rng.randrange(1, 4)):
            mode = rng.random()
            if mode < 0.45 and len(m) >= 4:
                off = rng.randrange(0, len(m) - 3) & ~3
                if off + 4 <= len(m):
                    struct.pack_into(
                        "<I", m, off,
                        EXTREMES[rng.randrange(len(EXTREMES))]
                        & 0xFFFFFFFF)
            elif mode < 0.9 and m:
                m[rng.randrange(len(m))] ^= 1 << rng.randrange(8)
            else:
                cut = rng.randrange(len(m) + 1)
                del m[cut:]
        run(parser, bytes(m))

    if verbose:
        print(f"  {fmt}: {count} frames, "
              f"{len(crashers)} crasher(s)")
    return count, crashers


def main() -> int:
    ap = argparse.ArgumentParser(
        description="schema-driven wire fuzzer")
    ap.add_argument("--smoke", action="store_true",
                    help="~2k frames/format (scripts/test budget)")
    ap.add_argument("--check", action="store_true",
                    help=">=100k frames/format (acceptance gate)")
    ap.add_argument("--frames", type=int, default=0,
                    help="explicit per-format frame budget")
    ap.add_argument("--formats", default="",
                    help="comma-separated subset "
                         "(dgb2,dcb1,gpb1)")
    ap.add_argument("--seed", type=int, default=20190814)
    args = ap.parse_args()

    budget = (args.frames or (100_000 if args.check else 2_000))
    fmts = ([f.strip() for f in args.formats.split(",") if f.strip()]
            or list(FORMATS))
    unknown = [f for f in fmts if f not in FORMATS]
    if unknown:
        print(f"unknown format(s): {', '.join(unknown)}",
              file=sys.stderr)
        return 2

    rng = random.Random(args.seed)
    total = 0
    all_crashers: list[str] = []
    t0 = time.monotonic()
    for fmt in fmts:
        n, crashers = fuzz_format(fmt, budget, rng)
        total += n
        all_crashers.extend(crashers)
    dt = time.monotonic() - t0
    print(f"wire_fuzz: {total} frames over {len(fmts)} format(s) "
          f"in {dt:.1f}s, {len(all_crashers)} crasher(s)")
    if all_crashers:
        print("crashers persisted as regression fixtures:")
        for p in all_crashers:
            print(f"  {p}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
