"""What a ``DistServer``'s peer port says to a request it cannot
serve: a body that is not the route's format, a path it does not
know, a member that is stopping.  The bodies come from other hosts,
so each refusal is typed and none is a 500."""

from __future__ import annotations

import http.client
import json

import pytest

from conftest import make_dist_cluster

NOT_A_FRAME = b"\xff\xfe not a frame \x00\x01"

ROUTES = ["/mraft", "/mraft/propose", "/mraft/propose_many",
          "/mraft/readindex", "/mraft/get_many",
          "/mraft/snapshot/meta", "/mraft/snapshot/chunk"]


@pytest.fixture(scope="module")
def members(tmp_path_factory):
    """Three members, no leader asked for: slot 0 serves, slot 2 is
    between ``done`` and the close of its listener, where a stopping
    member's peers still reach it."""
    servers, ports = make_dist_cluster(
        tmp_path_factory.mktemp("peer_routes"), m=3, g=8)
    servers[2].done.set()
    yield {"live": ports[0], "stopping": ports[2]}
    for s in servers:
        s.stop()


def post(port: int, path: str, body: bytes) -> tuple[int, bytes]:
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        c.request("POST", path, body=body)
        r = c.getresponse()
        return r.status, r.read()
    finally:
        c.close()


# the replies a forwarding member reads as an answer ride a 200 with
# the refusal in the body; the rest refuse with a 400
@pytest.mark.parametrize("path,status,said", [
    ("/mraft", 400, "message"),
    ("/mraft/propose", 200, "message"),
    ("/mraft/propose_many", 400, "message"),
    ("/mraft/readindex", 200, "err"),
    ("/mraft/get_many", 400, "message"),
    ("/mraft/snapshot/chunk", 400, None),
])
def test_a_body_that_is_not_the_routes_format_is_refused_typed(
        members, path, status, said):
    got, body = post(members["live"], path, NOT_A_FRAME)
    assert got == status
    if said is None:
        assert body == b""
        return
    d = json.loads(body)
    assert d.get("ok", False) is False and "rd" not in d
    assert isinstance(d[said], str) and d[said]


@pytest.mark.parametrize("path", ["/mraft/role_fwd", "/mraft/nope"])
def test_a_path_the_peer_port_does_not_know_is_a_404(members, path):
    assert post(members["live"], path, NOT_A_FRAME) == (404, b"")


@pytest.mark.parametrize("path", ROUTES)
def test_a_stopping_member_answers_503_on_every_route(members, path):
    assert post(members["stopping"], path, NOT_A_FRAME) == (503, b"")
