"""``utils/trace.py``'s ``TimedRLock`` (PR 40): an ``RLock`` that files
who waited for it, who held it and what a hand-over cost, by the role
the acquiring thread's entry point names (``lock_role``), into a
tracer's ``etcd_stage_seconds`` — and still excludes like the lock it
stands in for."""

from __future__ import annotations

import sys
import threading
import time

import pytest

from etcd_tpu.utils import trace as _trace
from etcd_tpu.utils.trace import TimedRLock, Tracer, lock_role

JOIN_S = 30.0


def make(tr: Tracer) -> TimedRLock:
    """The dist tier's table of roles, into a private tracer."""
    return TimedRLock("dist", wait=("round", "ack", "frame"),
                      handoff=("round", "ack", "read"),
                      annotate=("round",), recorder=tr)


def filed(tr: Tracer) -> dict[str, tuple[int, float, list[float]]]:
    """stage -> (count, sum, samples) of the wall kind."""
    out = {}
    for (stage, kind), h in tr._reg.family(
            "etcd_stage_seconds").children():
        if kind == "wall":
            count, total, _, ring = h.ring_stats()
            out[stage] = (count, total, ring)
    return out


def contend(lock: TimedRLock, role: str, hold_s: float = 0.05,
            since: float | None = None) -> None:
    """Hold ``lock`` on this thread (no role) while a thread of
    ``role`` asks for it, so that thread's acquisition is contended."""
    asked = threading.Event()

    def taker():
        with lock_role(role, since=since):
            asked.set()
            with lock:
                pass

    lock.acquire()
    t = threading.Thread(target=taker)
    t.start()
    assert asked.wait(JOIN_S)
    time.sleep(hold_s)
    lock.release()
    t.join(JOIN_S)
    assert not t.is_alive()


def test_reentry_files_one_wait_and_one_hold():
    tr = Tracer()
    lock = make(tr)
    with lock_role("round"):
        with lock:
            with lock:
                with lock:
                    time.sleep(0.01)
                assert lock._depth == 2
    got = filed(tr)
    assert set(got) == {"dist.lock_wait.round", "dist.lock_hold.round"}
    assert got["dist.lock_wait.round"][0] == 1
    count, total, _ = got["dist.lock_hold.round"]
    assert count == 1 and total >= 0.01
    assert lock._owner is None and lock._depth == 0


def test_uncontended_acquisition_waits_about_zero_and_hands_over_nothing():
    tr = Tracer()
    lock = make(tr)
    with lock_role("ack"):
        for _ in range(20):
            with lock:
                pass
    got = filed(tr)
    assert "dist.lock_handoff" not in got
    count, _, ring = got["dist.lock_wait.ack"]
    assert count == 20 and max(ring) < 0.005
    assert got["dist.lock_hold.ack"][0] == 20


@pytest.mark.parametrize("role", ["round", "ack", "read"])
def test_contended_acquisition_hands_over_in_no_more_than_its_wait(role):
    tr = Tracer()
    lock = make(tr)
    contend(lock, role)
    got = filed(tr)
    count, handoff, _ = got["dist.lock_handoff"]
    assert count == 1 and 0.0 <= handoff
    if role == "read":
        # a GET's wait is dist.read_lock, filed by _linz_read itself
        assert "dist.lock_wait.read" not in got
        assert got["dist.lock_hold.read"][0] == 1
    else:
        _, wait, _ = got[f"dist.lock_wait.{role}"]
        assert wait >= 0.04 and handoff <= wait


def test_a_frame_files_its_wait_and_no_hand_over():
    tr = Tracer()
    lock = make(tr)
    contend(lock, "frame")
    got = filed(tr)
    assert got["dist.lock_wait.frame"][1] >= 0.04
    assert got["dist.lock_hold.frame"][0] == 1
    assert "dist.lock_handoff" not in got


def test_the_wait_counts_from_the_stamp_the_entry_point_gives():
    tr = Tracer()
    lock = make(tr)
    since = time.monotonic() - 0.5      # the response read long ago
    with lock_role("ack", since=since):
        with lock:
            pass
    (wait,) = filed(tr)["dist.lock_wait.ack"][2]
    assert 0.5 <= wait < 5.0


def test_no_role_files_nothing():
    tr = Tracer()
    lock = make(tr)
    for _ in range(5):
        with lock:
            with lock:
                pass
    with lock_role(None):
        with lock:
            pass
    with lock_role("snapshot"):          # a role the table does not know
        with lock:
            pass
    contend(lock, "unknown")
    assert filed(tr) == {}


class _StubAnnotation:
    opened: list[str] = []
    closed = 0

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        _StubAnnotation.opened.append(self.name)
        return self

    def __exit__(self, *exc):
        _StubAnnotation.closed += 1
        return False


def test_only_a_contended_round_opens_an_annotation(monkeypatch):
    monkeypatch.setattr(_trace, "_annotation_cls", _StubAnnotation)
    monkeypatch.setattr(_StubAnnotation, "opened", [])
    monkeypatch.setattr(_StubAnnotation, "closed", 0)
    tr = Tracer()
    lock = make(tr)
    with lock_role("round"):
        with lock:                       # uncontended: none
            pass
    for role in ("ack", "frame", "read"):
        contend(lock, role, hold_s=0.01)
    assert _StubAnnotation.opened == []
    contend(lock, "round", hold_s=0.01)
    assert _StubAnnotation.opened == ["dist.lock_wait.round"]
    assert _StubAnnotation.closed == 1


def test_the_role_is_the_thread_s_and_nests():
    seen = {}
    role = lock_role("frame")            # one instance, many threads

    @role
    def entry(i: int) -> None:
        seen[i] = _trace._role_tls.cur[0]
        with lock_role("read"):
            assert _trace._role_tls.cur[0] == "read"
        assert _trace._role_tls.cur[0] == "frame"

    threads = [threading.Thread(target=entry, args=(i,)) for i in range(8)]
    with lock_role("round"):
        for t in threads:
            t.start()
        for t in threads:
            t.join(JOIN_S)
        assert _trace._role_tls.cur[0] == "round"
    assert all(not t.is_alive() for t in threads)
    assert seen == {i: "frame" for i in range(8)}
    assert getattr(_trace._role_tls, "cur", None) is None


def test_a_thread_that_does_not_hold_it_cannot_release_it():
    lock = make(Tracer())
    with pytest.raises(RuntimeError):
        lock.release()
    lock.acquire()
    raised = []

    def other():
        try:
            lock.release()
        except RuntimeError:
            raised.append(True)

    t = threading.Thread(target=other)
    t.start()
    t.join(JOIN_S)
    assert raised == [True]
    assert lock._owner == threading.get_ident()
    lock.release()
    assert lock._owner is None


def test_it_still_excludes_across_threads():
    """More threads than cores, every role and none, a switch interval
    shortened to force hand-overs inside the critical section: a lost
    update would show in the count; every hand-over is filed."""
    tr = Tracer()
    lock = make(tr)
    box = {"n": 0}
    per, roles = 400, ["round", "ack", "frame", "read", None] * 4

    def worker(role):
        with lock_role(role):
            for _ in range(per):
                with lock:
                    with lock:
                        n = box["n"]
                        time.sleep(0)
                        box["n"] = n + 1

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(r,))
                   for r in roles]
        for t in threads:
            t.start()
        for t in threads:
            t.join(JOIN_S)
    finally:
        sys.setswitchinterval(old)
    assert all(not t.is_alive() for t in threads)
    assert box["n"] == per * len(roles)
    got = filed(tr)
    for role in ("round", "ack", "frame", "read"):
        assert got[f"dist.lock_hold.{role}"][0] == 4 * per
    assert got["dist.lock_wait.round"][0] == 4 * per
    assert 0 < got["dist.lock_handoff"][0] <= 12 * per
