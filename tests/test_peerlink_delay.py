"""The delay line of ``server/peerlink.py`` against a loopback HTTP
echo (no cluster): a frame handed over at *t* is written no earlier
than *t + d*; N frames handed over back to back all arrive by about
*t + d*, not *t + N·d* (the case that tells the line from the
``peerlink.send=delay()`` failpoint, which sleeps once a frame on the
stripe's one writer); order within a stripe is kept; the responses
come back the same way; ``KeepAlivePool.post`` takes two delays more;
``close()`` fails what is held; the failpoints act before the hold;
and with no delay there is no thread, no queue and no stamp."""

import http.server
import queue
import threading
import time

import pytest

from etcd_tpu.obs import metrics as _metrics
from etcd_tpu.server.peerlink import KeepAlivePool, PipeChannel
from etcd_tpu.utils import faults as faults_mod

#: what a sleeper, a loopback hop and a handler thread may add on a
#: busy test host; far under one more delay in every test below
EPS = 0.15


class _Echo(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def do_POST(self):
        body = self.rfile.read(int(self.headers["Content-Length"]))
        self.server.arrivals.append((time.monotonic(), body))
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)
        self.server.answered.append(time.monotonic())

    def log_message(self, *a):
        pass


@pytest.fixture
def echo():
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Echo)
    srv.daemon_threads = True
    srv.arrivals, srv.answered = [], []
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    srv.url = "http://127.0.0.1:%d" % srv.server_address[1]
    yield srv
    srv.shutdown()
    srv.server_close()
    t.join(timeout=5)
    assert not t.is_alive()


class _Sink:
    """The channel's callbacks, stamped."""

    def __init__(self):
        self.resps, self.fails, self.sent = [], [], []
        self.cond = threading.Condition()

    def on_resp(self, seq, status, body):
        with self.cond:
            self.resps.append((time.monotonic(), seq, status, body))
            self.cond.notify_all()

    def on_fail(self, seqs, reason):
        with self.cond:
            self.fails.append((time.monotonic(), list(seqs), reason))
            self.cond.notify_all()

    def on_sent(self, seq, t):
        self.sent.append((time.monotonic(), seq, t))

    def wait(self, n: int, timeout: float = 10.0) -> None:
        with self.cond:
            assert self.cond.wait_for(
                lambda: len(self.resps)
                + sum(len(f[1]) for f in self.fails) >= n, timeout)


def channel(echo, sink, delay, name, **kw):
    return PipeChannel(echo.url, "/x", timeout=1.0,
                       on_resp=sink.on_resp, on_fail=sink.on_fail,
                       on_sent=sink.on_sent, name=name, delay=delay,
                       **kw)


def pipe_threads(name: str) -> list[str]:
    return sorted(t.name for t in threading.enumerate()
                  if t.name.startswith(f"pipe-{name}-"))


def stage_count(stage: str) -> int:
    return _metrics.registry.histogram(
        "etcd_stage_seconds", stage=stage, kind="wall").ring_stats()[0]


def test_a_frame_is_written_no_earlier_than_its_due_time(echo):
    sink, d = _Sink(), 0.2
    chan = channel(echo, sink, d, "due")
    try:
        t = time.monotonic()
        chan.send(1, b"one")
        sink.wait(1)
    finally:
        chan.close()
    (arrived, body), = echo.arrivals
    assert body == b"one" and t + d <= arrived <= t + d + EPS
    # the send edge the caller is told is the hand-over, not the write
    (_, seq, t_link), = sink.sent
    assert seq == 1 and t <= t_link <= t + 0.05
    # and the response took the line back: read stamp + d
    (t_resp, seq, status, body), = sink.resps
    assert (seq, status, body) == (1, 200, b"one")
    assert echo.answered[0] + d <= t_resp + 0.005
    assert t + 2 * d <= t_resp <= t + 2 * d + 2 * EPS


def test_frames_handed_over_back_to_back_cross_in_one_delay(echo):
    """THE property: a link of delay d carries a window of frames at
    once.  Eight frames on one stripe: all written by t + d + eps
    (eight sleeps would take 1.6 s), in the order handed over, and
    all eight responses back by t + 2 d + eps."""
    sink, d, n = _Sink(), 0.2, 8
    holds = stage_count("dist.link_hold")
    over = stage_count("dist.link_overshoot")
    chan = channel(echo, sink, d, "b2b")
    try:
        t = time.monotonic()
        for seq in range(1, n + 1):
            chan.send(seq, b"f%d" % seq)
        t_last = time.monotonic()
        sink.wait(n)
    finally:
        chan.close()
    assert not sink.fails
    arrived = [a for a, _ in echo.arrivals]
    assert [b for _, b in echo.arrivals] == [b"f%d" % s
                                             for s in range(1, n + 1)]
    assert min(arrived) >= t + d
    assert max(arrived) <= t_last + d + EPS < t + 2 * d
    assert [r[1] for r in sink.resps] == list(range(1, n + 1))
    assert min(r[0] for r in sink.resps) >= t + 2 * d
    assert max(r[0] for r in sink.resps) <= t_last + 2 * d + 2 * EPS
    # each crossing filed its stay and what it overshot: 2 a frame
    assert stage_count("dist.link_hold") - holds == 2 * n
    assert stage_count("dist.link_overshoot") - over == 2 * n


def test_the_failpoint_delay_is_one_frame_a_delay(echo):
    """What ``peerlink.send=delay()`` gives instead, and why no
    configuration is built on it: one sleep a frame on the stripe's
    one writer, so four frames take four delays."""
    sink, d, n = _Sink(), 0.1, 4
    faults_mod.FAULTS.configure(f"peerlink.send[sA->sB]=delay({d}s)")
    chan = channel(echo, sink, 0.0, "fp", fault_ctx=("sA", "sB"))
    try:
        t = time.monotonic()
        for seq in range(1, n + 1):
            chan.send(seq, b"x")
        sink.wait(n)
    finally:
        faults_mod.FAULTS.configure("")
        chan.close()
    assert max(a for a, _ in echo.arrivals) >= t + n * d


def test_two_stripes_each_keep_their_order(echo):
    sink, d = _Sink(), 0.1
    chan = channel(echo, sink, d, "str", stripes=2)
    try:
        for seq in range(1, 9):
            chan.send(seq, b"%d" % seq, stripe=seq % 2)
        sink.wait(8)
    finally:
        chan.close()
    got = [r[1] for r in sink.resps]
    assert sorted(got) == list(range(1, 9))
    for parity in (0, 1):
        mine = [s for s in got if s % 2 == parity]
        assert mine == sorted(mine)


def test_no_delay_is_the_channel_as_it_was(echo):
    """Delay 0: two threads a stripe, no line, no stamp on the queued
    item, the send edge is the socket write, no hold is filed."""
    sink = _Sink()
    holds = stage_count("dist.link_hold")
    chan = channel(echo, sink, 0.0, "off", stripes=2)
    try:
        assert pipe_threads("off") == ["pipe-off-r0", "pipe-off-r1",
                                       "pipe-off-w0", "pipe-off-w1"]
        assert all(st.held is None for st in chan._stripes)
        st = chan._stripes[0]
        mine, st.q = st.q, queue.Queue()   # what send() queues, read
        chan.send(1, b"p")                 # before a writer takes it
        assert st.q.get_nowait() == (1, b"p", 0.0)
        st.q = mine
        t = time.monotonic()
        chan.send(7, b"seven", stripe=1)
        sink.wait(1)
    finally:
        chan.close()
    (t_cb, seq, t_link), = sink.sent
    assert seq == 7 and t <= t_link <= t_cb
    assert sink.resps[0][0] - t < EPS
    assert stage_count("dist.link_hold") == holds
    on = channel(echo, _Sink(), 0.05, "on", stripes=2)
    try:
        assert pipe_threads("on") == [
            "pipe-on-d0", "pipe-on-d1", "pipe-on-r0", "pipe-on-r1",
            "pipe-on-w0", "pipe-on-w1"]
    finally:
        on.close()
    with pytest.raises(ValueError):
        PipeChannel(echo.url, "/x", delay=-0.001)


def test_close_fails_every_frame_held_on_the_way_out(echo):
    """Three frames on the line, none due yet: ``close()`` fails all
    three (the one the writer holds and the two behind it), nothing
    reaches the peer, and the threads end."""
    sink = _Sink()
    chan = channel(echo, sink, 5.0, "cout")
    for seq in (1, 2, 3):
        chan.send(seq, b"never")
    time.sleep(0.1)                        # the writer holds seq 1
    chan.close()
    sink.wait(3)
    assert sorted(s for f in sink.fails for s in f[1]) == [1, 2, 3]
    assert {f[2] for f in sink.fails} == {"closed"}
    assert not sink.resps and not echo.arrivals
    for t in chan._threads:
        t.join(timeout=5)
    assert not any(t.is_alive() for t in chan._threads)


def test_close_fails_every_response_held_on_the_way_back(echo):
    """The peer answered all three and the answers are on the line
    back: ``close()`` fails them, each exactly once, and no callback
    fires for a response after it."""
    sink, d = _Sink(), 0.6
    chan = channel(echo, sink, d, "cback")
    for seq in (1, 2, 3):
        chan.send(seq, b"back")
    deadline = time.monotonic() + 5
    while len(echo.answered) < 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(echo.answered) == 3
    time.sleep(0.1)                        # read, stamped, held
    chan.close()
    sink.wait(3)
    time.sleep(d)                          # past every due time
    assert not sink.resps
    assert sorted(s for f in sink.fails for s in f[1]) == [1, 2, 3]


def test_failpoints_act_before_the_hold(echo):
    """``err`` fails the frame at once, not a delay later; ``drop``
    loses it silently; the frame behind them crosses as stated."""
    sink, d = _Sink(), 0.5
    faults_mod.FAULTS.configure(
        "peerlink.send[sA->sB]=err(EIO,times=1)")
    chan = channel(echo, sink, d, "fault", fault_ctx=("sA", "sB"))
    try:
        t = time.monotonic()
        chan.send(1, b"err")
        sink.wait(1)
        (t_fail, seqs, reason), = sink.fails
        assert (seqs, reason) == ([1], "fault") and t_fail - t < d / 2
        faults_mod.FAULTS.configure(
            "peerlink.send[sA->sB]=drop(times=1)")
        chan.send(2, b"drop")
        t3 = time.monotonic()
        chan.send(3, b"kept")
        sink.wait(2)
    finally:
        faults_mod.FAULTS.configure("")
        chan.close()
    assert [b for _, b in echo.arrivals] == [b"kept"]
    assert echo.arrivals[0][0] >= t3 + d
    assert [r[1] for r in sink.resps] == [3] and len(sink.fails) == 1


def test_keepalive_post_takes_two_delays_more(echo):
    d = 0.15
    pool = KeepAlivePool(timeout=1.0, delays={"far": d, "zero": 0.0})
    plain = KeepAlivePool(timeout=1.0)
    try:
        assert plain._delays is None
        plain.post("far", echo.url, "/x", b"warm")
        t = time.monotonic()
        assert plain.post("far", echo.url, "/x", b"a") == (200, b"a")
        base = time.monotonic() - t
        for key in ("near", "zero"):       # no delay stated: as plain
            t = time.monotonic()
            assert pool.post(key, echo.url, "/x", b"n") == (200, b"n")
            assert time.monotonic() - t < d
        n0 = len(echo.arrivals)
        t = time.monotonic()
        assert pool.post("far", echo.url, "/x", b"f") == (200, b"f")
        took = time.monotonic() - t
        assert echo.arrivals[n0][0] >= t + d
        assert 2 * d <= took <= base + 2 * d + EPS
        # the pool is otherwise the same: the connection is kept
        assert pool.post("far", echo.url, "/x", b"g") == (200, b"g")
        assert pool.reconnects == 0 and "far" in pool._conns
    finally:
        pool.close()
        plain.close()
