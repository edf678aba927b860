"""The front door answers a GET that cannot block on the loop thread
that parsed it (``MultiGroupServer.do_local``): the worker path's
response byte for byte, in order on a pipelined connection, admission
balanced, both waits filed; anything that may wait, and every request
to a server without the seam, still goes to a worker."""

from __future__ import annotations

import http.client
import json
import socket
import threading
import time

import pytest

from etcd_tpu.server.frontdoor import FrontDoor, FrontDoorConfig

from test_stage_coverage import wall

FORM = {"Content-Type": "application/x-www-form-urlencoded"}


def waits() -> dict[str, int]:
    """``{stage: count}`` of ``etcd_stage_seconds{kind=wall}``."""
    return {stage: count for stage, (count, _sum) in wall().items()}


def settled() -> dict[str, int]:
    """:func:`waits` once it has stopped moving: the loop thread files
    a request's respond wait after the client has its answer."""
    prev = waits()
    while True:
        time.sleep(0.02)
        now = waits()
        if now == prev:
            return now
        prev = now


def grew(before: dict, names=("fd.read_inline", "fd.do.get",
                              "fd.worker_wait", "fd.respond_wait")):
    """What the named waits grew by, once the loop thread has filed
    the respond wait of every request a worker took."""
    deadline = time.monotonic() + 5.0
    while True:
        now = waits()
        g = {n: now.get(n, 0) - before.get(n, 0) for n in names}
        if g["fd.respond_wait"] == g["fd.worker_wait"] \
                or time.monotonic() > deadline:
            return g
        time.sleep(0.01)


@pytest.fixture(scope="module")
def cohosted(tmp_path_factory):
    """64 co-hosted groups behind two front doors: ``local`` as the
    program builds it, ``workers`` with the seam taken away (the
    parent's path) for the comparison."""
    from etcd_tpu.server.multigroup import MultiGroupServer

    s = MultiGroupServer(str(tmp_path_factory.mktemp("fdlocal") / "d"),
                         g=64, m=5, cap=64, storage_backend="tpu")
    s.start()
    local = FrontDoor(s, "127.0.0.1", 0, server_timeout=60.0)
    workers = FrontDoor(s, "127.0.0.1", 0, server_timeout=60.0)
    workers._do_local = None
    local.start()
    workers.start()
    conn = http.client.HTTPConnection(*local.server_address, timeout=90)
    for path, body in (("/v2/keys/t1/k", "value=v1"),
                       ("/v2/keys/t1/dir/a", "value=a"),
                       ("/v2/keys/t1/dir/b", "value=b")):
        conn.request("PUT", path, body=body, headers=FORM)
        r = conn.getresponse()
        r.read()
        assert r.status in (200, 201)
    conn.close()
    yield {"server": s, "local": local, "workers": workers}
    local.shutdown()
    workers.shutdown()
    s.stop()


def raw(addr, payload: bytes, n_responses: int = 1,
        timeout: float = 30.0) -> bytes:
    """Send ``payload`` in one write, read ``n_responses`` whole
    HTTP responses (Content-Length framed) and return their bytes."""
    sock = socket.create_connection(addr, timeout=timeout)
    try:
        sock.sendall(payload)
        buf = b""
        done = 0
        pos = 0
        while done < n_responses:
            end = buf.find(b"\r\n\r\n", pos)
            if end < 0:
                chunk = sock.recv(65536)
                assert chunk, buf
                buf += chunk
                continue
            head = buf[pos:end].decode("latin-1").lower()
            clen = int(head.split("content-length:")[1].split("\r\n")[0])
            total = end + 4 + clen
            while len(buf) < total:
                chunk = sock.recv(65536)
                assert chunk, buf
                buf += chunk
            pos = total
            done += 1
        return buf[:pos]
    finally:
        sock.close()


def get_line(path: str) -> bytes:
    return f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode()


def split(buf: bytes) -> list[tuple[int, dict, bytes]]:
    out = []
    while buf:
        head, _, rest = buf.partition(b"\r\n\r\n")
        lines = head.decode("latin-1").split("\r\n")
        headers = {k.lower(): v.strip() for k, _, v in
                   (ln.partition(":") for ln in lines[1:])}
        n = int(headers["content-length"])
        out.append((int(lines[0].split()[1]), headers, rest[:n]))
        buf = rest[n:]
    return out


# -- the same bytes ---------------------------------------------------------


@pytest.mark.parametrize("path,status", [
    ("/v2/keys/t1/k", 200),                   # a hit
    ("/v2/keys/t1/none", 404),                # EtcdError body + index
    ("/v2/keys/t1/dir", 200),                 # a directory's listing
    ("/v2/keys/t1/dir?sorted=true", 200),
    ("/v2/keys/t1/k?serializable=true", 200),
    ("/v2/keys/t1/k?quorum=maybe", 400),      # a parse error: neither path
])
def test_local_answer_is_the_worker_paths_bytes(cohosted, path, status):
    before = settled()
    here = raw(cohosted["local"].server_address, get_line(path))
    mid = grew(before)
    there = raw(cohosted["workers"].server_address, get_line(path))
    after = grew(before)
    assert here == there
    (got_status, headers, body), = split(here)
    assert got_status == status
    if status == 400:
        assert mid["fd.do.get"] == after["fd.do.get"] == 0
        return
    assert {"x-etcd-index", "content-type"} <= set(headers)
    if status == 200:
        assert {"x-raft-index", "x-raft-term"} <= set(headers)
        assert json.loads(body)["node"]["key"].startswith("/t1/")
    else:
        assert json.loads(body)["errorCode"] == 100
    # the first went by the loop thread alone, the second by a worker
    assert mid == {"fd.read_inline": 1, "fd.do.get": 1,
                   "fd.worker_wait": 0, "fd.respond_wait": 0}
    assert after == {"fd.read_inline": 1, "fd.do.get": 2,
                     "fd.worker_wait": 1, "fd.respond_wait": 1}


def test_cors_headers_ride_the_local_answer(cohosted):
    fd = cohosted["local"]
    fd.cors = {"*"}
    try:
        got = raw(fd.server_address,
                  b"GET /v2/keys/t1/k HTTP/1.1\r\nHost: x\r\n"
                  b"Origin: http://o\r\n\r\n")
    finally:
        fd.cors = None
    (_s, headers, _b), = split(got)
    assert headers["access-control-allow-origin"] == "*"


# -- what still goes to a worker ----------------------------------------------


@pytest.mark.parametrize("query", ["quorum=true", "recursive=true",
                                   "recursive=true&sorted=true"])
def test_a_get_that_may_wait_goes_to_a_worker(cohosted, query):
    before = settled()
    got = raw(cohosted["local"].server_address,
              get_line(f"/v2/keys/t1/dir?{query}"))
    (status, _h, body), = split(got)
    assert status == 200 and json.loads(body)["node"]["dir"]
    assert grew(before) == {"fd.read_inline": 0, "fd.do.get": 1,
                            "fd.worker_wait": 1, "fd.respond_wait": 1}


def test_a_wait_get_parks_a_watcher_and_is_no_inline_read(cohosted):
    fd = cohosted["local"]
    before = settled()
    sock = socket.create_connection(fd.server_address, timeout=30)
    try:
        sock.sendall(get_line("/v2/keys/t1/watched?wait=true"))
        head = b""
        while b"\r\n\r\n" not in head:
            head += sock.recv(4096)
        assert b"Transfer-Encoding: chunked" in head
        conn = http.client.HTTPConnection(*fd.server_address,
                                          timeout=30)
        conn.request("PUT", "/v2/keys/t1/watched", body="value=w",
                     headers=FORM)
        assert conn.getresponse().status in (200, 201)
        conn.close()
        buf = head
        while b'"value": "w"' not in buf and b'"value":"w"' not in buf:
            chunk = sock.recv(4096)
            assert chunk, buf
            buf += chunk
    finally:
        sock.close()
    g = grew(before)
    assert g["fd.read_inline"] == 0 and g["fd.do.get"] == 0
    assert g["fd.worker_wait"] == 1      # the PUT


def test_do_local_declines_what_may_wait(cohosted):
    from etcd_tpu.wire.requests import Request

    s = cohosted["server"]
    plain = Request(method="GET", id=7, path="/t1/k")
    assert s.do_local(plain).event.node.value == "v1"
    for kw in ({"wait": True}, {"quorum": True}, {"recursive": True}):
        assert s.do_local(Request(method="GET", id=7, path="/t1/k",
                                  **kw)) is None, kw
    for method in ("PUT", "POST", "DELETE", "QGET"):
        assert s.do_local(Request(method=method, id=7, path="/t1/k",
                                  val="x")) is None, method
    with pytest.raises(ValueError):
        s.do_local(Request(method="GET", id=0, path="/t1/k"))


def test_local_and_worker_reads_bill_the_same_read_path(cohosted):
    s = cohosted["server"]

    def billed() -> tuple[int, int]:
        by_path = s.store.stats.reads_by_path
        return by_path["cohosted"], by_path["serializable"]

    n0 = billed()
    for fd in ("local", "workers"):
        addr = cohosted[fd].server_address
        raw(addr, get_line("/v2/keys/t1/k"))
        raw(addr, get_line("/v2/keys/t1/k?serializable=true"))
        n1 = billed()
        assert (n1[0] - n0[0], n1[1] - n0[1]) == (1, 1), fd
        n0 = n1


@pytest.mark.parametrize("module,cls,seam", [
    ("distserver", "DistServer", False),
    ("server", "EtcdServer", False),
    ("multigroup", "MultiGroupServer", True),
])
def test_only_the_server_whose_get_cannot_wait_has_the_seam(
        module, cls, seam):
    """The front door asks for ``do_local`` by name: a server whose
    default GET may wait (lease, ReadIndex, the raft loop) must not
    grow one, and the co-hosted server must keep it."""
    import importlib

    server = getattr(importlib.import_module(
        f"etcd_tpu.server.{module}"), cls)
    assert hasattr(server, "do_local") is seam


@pytest.mark.parametrize("hook", [
    {"extra_routes": {}},
    {"watch_redirect": "http://127.0.0.1:1"},
])
def test_the_front_door_takes_no_routes_of_a_callers(cohosted, hook):
    """The paths a front door serves are the ones in its source: a
    caller cannot hang a handler on it or send its watches away."""
    with pytest.raises(TypeError):
        FrontDoor(cohosted["server"], "127.0.0.1", 0, **hook)


def test_every_get_to_a_dist_server_goes_to_a_worker(tmp_path):
    """A ``DistServer``'s default GET is linearizable by lease or
    ReadIndex and may wait: its front door hands every one to a
    worker, as before."""
    from conftest import bootstrap_dist_leader, make_dist_cluster

    servers, _ports = make_dist_cluster(tmp_path, m=3, g=8)
    fd = None
    try:
        bootstrap_dist_leader(servers)
        fd = FrontDoor(servers[0], "127.0.0.1", 0,
                       server_timeout=30.0).start()
        assert fd._do_local is None
        conn = http.client.HTTPConnection(*fd.server_address,
                                          timeout=60)
        deadline = time.monotonic() + 60.0
        while True:
            conn.request("PUT", "/v2/keys/d/k", body="value=dv",
                         headers=FORM)
            r = conn.getresponse()
            r.read()
            if r.status in (200, 201) or time.monotonic() > deadline:
                break
        assert r.status in (200, 201)
        before = settled()
        sent = 0
        for query in ("", "?serializable=true", "?quorum=true"):
            # a loaded CPU box can time a ReadIndex round out: the
            # GET is sent again, and every attempt is counted
            while True:
                conn.request("GET", "/v2/keys/d/k" + query)
                r = conn.getresponse()
                body = r.read()
                sent += 1
                if r.status == 200 or time.monotonic() > deadline:
                    break
            assert r.status == 200, body
            assert json.loads(body)["node"]["value"] == "dv"
        conn.close()
        assert grew(before) == {"fd.read_inline": 0, "fd.do.get": sent,
                                "fd.worker_wait": sent,
                                "fd.respond_wait": sent}
    finally:
        if fd is not None:
            fd.shutdown()
        for s in servers:
            s.stop()


# -- order on one connection ------------------------------------------------


def test_three_pipelined_gets_come_back_in_order(cohosted):
    payload = (get_line("/v2/keys/t1/k") + get_line("/v2/keys/t1/none")
               + get_line("/v2/keys/t1/dir/b"))
    got = split(raw(cohosted["local"].server_address, payload, 3))
    assert [s for s, _h, _b in got] == [200, 404, 200]
    assert json.loads(got[0][2])["node"]["value"] == "v1"
    assert json.loads(got[1][2])["errorCode"] == 100
    assert json.loads(got[2][2])["node"]["value"] == "b"


@pytest.mark.parametrize("n", [1, 5])
def test_a_get_behind_a_put_is_answered_after_it(cohosted, n):
    """One segment: a PUT, then ``n`` GETs of the same key.  The GETs
    wait on the connection until the PUT's answer has left, and read
    what it wrote."""
    value = f"piped{n}"
    body = f"value={value}".encode()
    put = (b"PUT /v2/keys/t1/piped HTTP/1.1\r\nHost: x\r\n"
           b"Content-Type: application/x-www-form-urlencoded\r\n"
           b"Content-Length: %d\r\n\r\n" % len(body)) + body
    before = settled()
    got = split(raw(cohosted["local"].server_address,
                    put + get_line("/v2/keys/t1/piped") * n, 1 + n))
    assert got[0][0] in (200, 201)
    assert json.loads(got[0][2])["action"] == "set"
    for status, headers, answer in got[1:]:
        assert status == 200
        node = json.loads(answer)["node"]
        assert node["value"] == value
        assert int(headers["x-etcd-index"]) >= node["modifiedIndex"]
    assert grew(before) == {"fd.read_inline": n, "fd.do.get": n,
                            "fd.worker_wait": 1, "fd.respond_wait": 1}


def test_connection_close_after_a_local_answer(cohosted):
    sock = socket.create_connection(cohosted["local"].server_address,
                                    timeout=30)
    try:
        sock.sendall(b"GET /v2/keys/t1/k HTTP/1.1\r\nHost: x\r\n"
                     b"Connection: close\r\n\r\n")
        buf = b""
        while True:
            chunk = sock.recv(65536)
            if not chunk:
                break
            buf += chunk
    finally:
        sock.close()
    (status, headers, _b), = split(buf)
    assert status == 200 and headers["connection"] == "close"


# -- admission ----------------------------------------------------------------


def test_admission_is_balanced_and_bills_each_get(cohosted):
    fd = cohosted["local"]
    admitted = fd.admission.counts.get(("admit", "ok"), 0)
    payload = get_line("/v2/keys/adm/k") * 20
    got = split(raw(fd.server_address, payload, 20))
    assert [s for s, _h, _b in got] == [404] * 20
    assert fd.admission.counts[("admit", "ok")] - admitted == 20
    assert fd.admission.inflight == 0
    assert fd.admission.state("adm").inflight == 0
    assert json.loads(fd.stats_json())["inflight"] == 0


def test_a_shed_get_is_still_a_429(tmp_path):
    from etcd_tpu.server.multigroup import MultiGroupServer

    s = MultiGroupServer(str(tmp_path / "d"), g=8, m=3, cap=32)
    s.start()
    # a bucket that holds one read (cost 0.2) and refills in minutes
    fd = FrontDoor(s, "127.0.0.1", 0, config=FrontDoorConfig(
        tenant_rate=0.001, tenant_burst=0.3)).start()
    try:
        before = settled()
        got = split(raw(fd.server_address,
                        get_line("/v2/keys/shed/k") * 3, 3))
        assert [st for st, _h, _b in got] == [404, 429, 429]
        assert "retry-after" in got[1][1]
        assert json.loads(got[1][2])["errorCode"] == 406
        assert grew(before)["fd.read_inline"] == 1
        assert fd.admission.inflight == 0
    finally:
        fd.shutdown()
        s.stop()


# -- the waits ----------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 7, 32])
def test_each_get_files_both_waits_once(cohosted, n):
    before = settled()
    conn = http.client.HTTPConnection(
        *cohosted["local"].server_address, timeout=30)
    for i in range(n):
        conn.request("GET", "/v2/keys/t1/k" if i % 2 else
                     "/v2/keys/t1/none")
        conn.getresponse().read()
    conn.close()
    assert grew(before) == {"fd.read_inline": n, "fd.do.get": n,
                            "fd.worker_wait": 0, "fd.respond_wait": 0}


# -- the guarantee ------------------------------------------------------------


def test_no_get_returns_a_write_older_than_one_acknowledged_before_it(
        cohosted):
    """16 writers, one record each, and 8 readers over all records at
    64 groups for two seconds: a GET never returns a value older than
    the newest write acknowledged before the GET was sent (the
    benchmark's ``stale_reads`` rule), and every read was answered on
    the loop thread."""
    addr = cohosted["local"].server_address
    n_writers, n_readers = 16, 8
    acked = [0] * n_writers       # newest acknowledged version a record
    stop = threading.Event()
    faults: list = []
    reads = [0]

    def check(conn, k: int) -> None:
        floor = acked[k]          # read BEFORE the GET is sent
        conn.request("GET", f"/v2/keys/w{k}/rec")
        r = conn.getresponse()
        body = r.read()
        if r.status == 404:
            got = 0
        else:
            got = int(json.loads(body)["node"]["value"])
        if got < floor:
            faults.append((k, got, floor))
        reads[0] += 1

    def writer(k: int) -> None:
        conn = http.client.HTTPConnection(*addr, timeout=60)
        v = 0
        while not stop.is_set():
            v += 1
            conn.request("PUT", f"/v2/keys/w{k}/rec",
                         body=f"value={v}", headers=FORM)
            r = conn.getresponse()
            r.read()
            if r.status not in (200, 201):
                faults.append(("put", k, r.status))
                break
            acked[k] = v
            check(conn, k)
        conn.close()

    def reader(i: int) -> None:
        conn = http.client.HTTPConnection(*addr, timeout=60)
        k = i
        while not stop.is_set():
            k = (k + 5) % n_writers
            check(conn, k)
        conn.close()

    before = settled()
    threads = [threading.Thread(target=writer, args=(k,))
               for k in range(n_writers)]
    threads += [threading.Thread(target=reader, args=(i,))
                for i in range(n_readers)]
    for t in threads:
        t.start()
    time.sleep(2.0)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    assert not faults, faults[:5]
    assert min(acked) >= 1 and reads[0] >= 100
    g = grew(before)
    assert g["fd.read_inline"] == g["fd.do.get"] == reads[0]
