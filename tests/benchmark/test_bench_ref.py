"""The plain reference: what a read may return, and the counts that
decide ``correct``."""

import math

import pytest

import bench_fixtures  # noqa: F401 - puts benchmark/ on sys.path

import bench_ref
from bench_load import Op, Write

INF = math.inf
#: one record: write 1 acked at t=2, write 2 sent at 3 and never acked,
#: write 3 sent at 10 and acked at 11
WRITES = [Write(1, 1.0, 2.0), Write(2, 3.0, INF), Write(3, 10.0, 11.0)]


@pytest.mark.parametrize("seq,t_first,t_end,ok", [
    (-1, 0.0, 0.5, True),      # nothing acknowledged yet: not found is fine
    (-1, 2.5, 2.6, False),     # write 1 was acknowledged: not found is lost
    (1, 1.5, 1.8, True),       # in flight: may already be visible
    (1, 2.5, 2.6, True),
    (2, 2.5, 2.6, False),      # not sent yet when the read ended
    (2, 2.5, 3.5, True),       # overlaps the unacknowledged write
    (2, 8.0, 8.1, True),       # a write given up on may commit later
    (1, 8.0, 8.1, True),       # ... or never
    (3, 8.0, 9.0, False),      # from the future
    (1, 11.5, 11.6, False),    # stale: write 3 was acknowledged before
    (2, 11.5, 11.6, False),
    (3, 11.5, 11.6, True),
    (4, 11.5, 11.6, False),    # never written
    (3, INF, INF, True),       # after every client stopped
    (1, INF, INF, False),
])
def test_read_allowed(seq, t_first, t_end, ok):
    assert bench_ref.read_allowed(WRITES, seq, t_first, t_end) is ok


def op(kind, outcome, seq=-1, t=(20.0, 20.1), phase="window"):
    return Op(kind, 0, t[0], t[1], outcome, 0, seq, phase, WRITES)


@pytest.mark.parametrize("ops,name,value", [
    ([op("put", "ack", 1), op("get", "ack", 3)], None, 0),
    ([op("put", "wrong", 1)], "wrong_answers", 1),
    ([op("get", "wrong")], "wrong_answers", 1),
    ([op("get", "ack", 1)], "stale_reads", 1),
    ([op("get", "ack", 1, phase="readback")], "lost_writes", 1),
    ([op("get", "ack", -1, phase="readback-restart")], "lost_writes", 1),
    ([op("get", "deadline", phase="readback")], "unanswered_readbacks", 1),
    ([op("get", "deadline")], None, 0),   # late is late, not wrong
    ([op("put", "shed")], None, 0),
])
def test_compare_counts(ops, name, value):
    compared = bench_ref.compare(ops)
    assert set(compared) == set(bench_ref.LIMITS)
    for k, c in compared.items():
        assert c["limit"] == 0
        assert c["value"] == (value if k == name else 0)
    assert bench_ref.is_correct(compared) is (name is None)
