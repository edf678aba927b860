"""The traffic generator and the client's operation."""

import collections
import itertools
import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

import bench_fixtures  # noqa: F401 - puts benchmark/ on sys.path

import bench_load

TRAFFIC = {"records": 1000, "value_bytes": 256, "clients": 16,
           "mix": {"put": 0.05, "get": 0.95},
           "distribution": {"kind": "zipfian", "theta": 0.99},
           "key": "/t{tenant:05d}/cfg"}


def take(plan, client, n, clients=16, **kw):
    return list(itertools.islice(plan.stream("window", client, clients, **kw),
                                 n))


def test_same_seed_same_operations_other_seed_others():
    a, b = bench_load.Plan(TRAFFIC, 3_000_000_019), \
        bench_load.Plan(TRAFFIC, 3_000_000_019)
    c = bench_load.Plan(TRAFFIC, 3_000_000_020)
    assert a.tenant_of_rank == b.tenant_of_rank and a.pad == b.pad
    assert take(a, 3, 500) == take(b, 3, 500)
    assert take(a, 3, 500) != take(c, 3, 500)
    assert a.tenant_of_rank != c.tenant_of_rank
    assert sorted(a.tenant_of_rank) == sorted(c.tenant_of_rank)


def test_one_writer_per_record_and_reads_anywhere():
    plan = bench_load.Plan(TRAFFIC, 7)
    writers = collections.defaultdict(set)
    read_ranks = set()
    for client in range(16):
        for kind, rank in take(plan, client, 4000, put_share=0.5):
            if kind == "put":
                writers[rank].add(client)
                assert rank % 16 == client
            else:
                read_ranks.add(rank % 16)
    assert all(len(w) == 1 for w in writers.values())
    assert read_ranks == set(range(16))


def test_zipfian_shape():
    plan = bench_load.Plan(TRAFFIC, 11)
    n = 60000
    counts = collections.Counter(
        rank for kind, rank in take(plan, 0, n, put_share=0.0))
    total = sum(1 / (r + 1) ** 0.99 for r in range(1000))
    for rank in (0, 1, 9, 99):
        want = (1 / (rank + 1) ** 0.99) / total
        assert counts[rank] / n == pytest.approx(want, rel=0.15)
    # the aggregate of the 16 writers keeps the shape: client i's
    # hottest own record is rank i
    top = [collections.Counter(
        r for k, r in take(plan, i, 3000, put_share=1.0)).most_common(1)[0][0]
        for i in range(16)]
    assert top == list(range(16))


def test_value_round_trip_and_width():
    plan = bench_load.Plan(TRAFFIC, 5)
    v = plan.value(123, 45)
    assert len(v) == 256 and bench_load.parse_value(v) == (123, 45)
    with pytest.raises(ValueError):
        bench_load.parse_value("not-a-generated-value-at-all")
    assert plan.key(0) == "/t%05d/cfg" % plan.tenant_of_rank[0]


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 0.95, 10),
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 0.5, 5),
    (list(range(1, 101)), 0.95, 95),
    ([], 0.95, None),
])
def test_percentile_nearest_rank(values, q, want):
    assert bench_load.percentile(values, q) == want


class _Script(BaseHTTPRequestHandler):
    """Answers from a per-server script of status codes."""
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def _answer(self):
        n = int(self.headers.get("Content-Length") or 0)
        self.rfile.read(n)
        code = self.server.script.pop(0) if self.server.script else 200
        self.server.seen.append(self.command)
        if code == "hang":
            threading.Event().wait(2.0)
            code = 200
        body = json.dumps(self.server.body).encode()
        self.send_response(code)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    do_PUT = do_GET = _answer


@pytest.fixture
def scripted():
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _Script)
    httpd.daemon_threads = True
    httpd.script, httpd.seen, httpd.body = [], [], {"node": {"value": "v"}}
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    yield httpd
    httpd.shutdown()
    httpd.server_close()


@pytest.mark.parametrize("script,deadline,outcome,resends", [
    ([], 5.0, "ack", 0),
    ([500, 500], 5.0, "ack", 2),          # declined twice, sent again at once
    ([503], 5.0, "ack", 1),
    ([429], 5.0, "shed", 0),              # a shed fails at once
    ([500, 429], 5.0, "shed", 1),
    (["hang"], 0.3, "deadline", 0),       # the deadline passes unanswered
])
def test_operation_resends_inside_the_deadline(scripted, script, deadline,
                                               outcome, resends):
    scripted.script[:] = script
    conn = bench_load.Conn("127.0.0.1", scripted.server_address[1])
    a = conn.op("PUT", "/v2/keys/x", b"value=v", deadline_s=deadline)
    conn.close()
    assert (a.outcome, a.resends) == (outcome, resends)
    assert a.t_end >= a.t_first
    if outcome == "deadline":
        assert 0.25 <= a.t_end - a.t_first < 1.5


def test_operation_is_timed_from_its_first_send(scripted):
    scripted.script[:] = ["hang"]
    conn = bench_load.Conn("127.0.0.1", scripted.server_address[1])
    a = conn.op("GET", "/v2/keys/x", deadline_s=5.0)
    conn.close()
    assert a.outcome == "ack" and a.t_end - a.t_first >= 1.9


def test_refused_connection_is_resent_until_the_deadline():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    a = bench_load.Conn("127.0.0.1", port).op("GET", "/v2/keys/x",
                                              deadline_s=0.3)
    assert a.outcome == "deadline" and a.resends >= 2


def test_put_with_another_value_in_the_answer_is_wrong(scripted):
    plan = bench_load.Plan(TRAFFIC, 1)
    hist = bench_load.History(plan.records)
    conn = bench_load.Conn("127.0.0.1", scripted.server_address[1])
    op = bench_load.one_op(conn, plan, hist, "put", 3, "window")
    assert op.outcome == "wrong" and hist.writes[3][0].t_ack == float("inf")
    scripted.body = {"node": {"value": plan.value(3, 2)}}
    op = bench_load.one_op(conn, plan, hist, "put", 3, "window")
    assert op.outcome == "ack" and hist.writes[3][1].t_ack == op.t_end
    op = bench_load.one_op(conn, plan, hist, "get", 3, "window")
    assert (op.outcome, op.seq) == ("ack", 2)
    op = bench_load.one_op(conn, plan, hist, "get", 4, "window")
    assert op.outcome == "wrong"          # another record's value
    conn.close()
