"""The load generator: traffic from a data file and a seed, the
client's operation, and closed-loop phases.  JAX-free.

An operation is what a user of ``etcd_tpu/api/client.py`` issues: one
PUT or GET over HTTP ``/v2/keys`` on a keep-alive connection with the
client's deadline (5 s).  Inside the deadline an answer in which the
server DECLINES (a 5xx such as the typed "request timed out" of the
0.5 s server timeout, a reset connection) has the same request sent
again at once.  The operation is attempted once, timed from its first
send, and every re-send is counted.  It fails when the deadline passes
unacknowledged, on a 429 shed, or on an answer that is wrong.

Every record has ONE writer: client ``i`` of ``c`` draws its PUTs only
among the records whose popularity rank is ``i`` (mod ``c``), so the
order of a record's acknowledged writes is known to the thread that
made them and a timed-out write that commits late cannot be mistaken
for a lost one.  Reads go to any record from any client.
"""

from __future__ import annotations

import bisect
import http.client
import itertools
import json
import math
import random
import threading
import time
from dataclasses import dataclass, field

CLIENT_DEADLINE_S = 5.0        # etcd_tpu/api/client.py Client(timeout=5.0)
ALPHABET = ("abcdefghijklmnopqrstuvwxyz"
            "ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789")
HEAD = 15                      # "rrrrr.ssssssss." — record rank and sequence


def zipf_weights(n: int, theta: float) -> list[float]:
    """YCSB's Zipfian: P(rank r) proportional to 1 / (r + 1) ** theta."""
    return [1.0 / (r + 1) ** theta for r in range(n)]


def _cdf(weights: list[float]) -> list[float]:
    total = sum(weights)
    return list(itertools.accumulate(w / total for w in weights))


def weights_of(dist: dict, n: int) -> list[float]:
    if dist["kind"] == "zipfian":
        return zipf_weights(n, float(dist["theta"]))
    if dist["kind"] == "uniform":
        return [1.0] * n
    raise ValueError(f"unknown distribution kind {dist['kind']!r}")


def make_value(rank: int, seq: int, pad: str, nbytes: int) -> str:
    return f"{rank:05d}.{seq:08d}." + pad[:nbytes - HEAD]


def parse_value(value: str) -> tuple[int, int]:
    """``(rank, seq)`` of a value this generator wrote; raises
    ValueError on anything else."""
    if len(value) < HEAD or value[5] != "." or value[14] != ".":
        raise ValueError(f"not a generated value: {value[:24]!r}")
    return int(value[:5]), int(value[6:14])


class Plan:
    """Everything a run's traffic is, as a pure function of the
    traffic file and the seed: which tenant holds which popularity
    rank, the value padding, and each client's stream of operations.
    Every seed has the same sizes and the same distribution, in
    another order."""

    def __init__(self, traffic: dict, seed: int):
        self.traffic = traffic
        self.seed = int(seed)
        self.records = int(traffic["records"])
        self.value_bytes = int(traffic["value_bytes"])
        rng = random.Random(f"{self.seed}:plan")
        tenants = list(range(self.records))
        rng.shuffle(tenants)
        self.tenant_of_rank = tenants
        self.pad = "".join(rng.choices(ALPHABET, k=self.value_bytes))
        self.weights = weights_of(traffic["distribution"], self.records)
        self.read_cdf = _cdf(self.weights)
        self.put_share = float(traffic["mix"].get("put", 0.0))

    def key(self, rank: int) -> str:
        return self.traffic["key"].format(tenant=self.tenant_of_rank[rank])

    def value(self, rank: int, seq: int) -> str:
        return make_value(rank, seq, self.pad, self.value_bytes)

    def stream(self, phase: str, client: int, clients: int,
               put_share: float | None = None):
        """Client ``client`` of ``clients``: an endless stream of
        ``("put" | "get", rank)``.  PUTs stay on the client's own
        ranks, GETs go anywhere."""
        rng = random.Random(f"{self.seed}:{phase}:{client}")
        own = list(range(client, self.records, clients))
        own_cdf = _cdf([self.weights[r] for r in own]) if own else []
        share = self.put_share if put_share is None else put_share
        while True:
            if own and rng.random() < share:
                yield "put", own[min(len(own) - 1, bisect.bisect_left(
                    own_cdf, rng.random()))]
            else:
                yield "get", min(self.records - 1, bisect.bisect_left(
                    self.read_cdf, rng.random()))


# -- the client's operation ---------------------------------------------------


@dataclass
class Answer:
    outcome: str               # ack | deadline | shed | wrong
    status: int = 0
    body: dict | None = None
    resends: int = 0
    t_first: float = 0.0
    t_end: float = 0.0


class Conn:
    """One keep-alive connection of one client thread."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self._c: http.client.HTTPConnection | None = None

    def close(self) -> None:
        if self._c is not None:
            self._c.close()
            self._c = None

    def _send(self, method: str, path: str, body: bytes | None,
              timeout: float) -> tuple[int, bytes]:
        if self._c is None:
            self._c = http.client.HTTPConnection(self.host, self.port,
                                                 timeout=timeout)
        elif self._c.sock is not None:
            self._c.sock.settimeout(timeout)
        headers = ({"Content-Type": "application/x-www-form-urlencoded"}
                   if body is not None else {})
        self._c.request(method, path, body=body, headers=headers)
        resp = self._c.getresponse()
        return resp.status, resp.read()

    def op(self, method: str, path: str, body: bytes | None = None,
           deadline_s: float = CLIENT_DEADLINE_S) -> Answer:
        """One operation: sent again at once while the server declines,
        until an answer or the deadline."""
        t_first = time.monotonic()
        resends = -1
        while True:
            resends += 1
            left = t_first + deadline_s - time.monotonic()
            if left <= 0:
                return Answer("deadline", 0, None, resends - 1, t_first,
                              time.monotonic())
            try:
                status, raw = self._send(method, path, body, left)
            except ConnectionRefusedError:
                self.close()
                time.sleep(0.02)   # nobody listens: do not spin
                continue
            except (OSError, http.client.HTTPException):
                self.close()
                continue
            t_end = time.monotonic()
            if status >= 500:
                continue           # declined: the same request again
            if status == 429:
                return Answer("shed", status, None, resends, t_first, t_end)
            try:
                parsed = json.loads(raw)
            except ValueError:
                parsed = None
            if not isinstance(parsed, dict):
                return Answer("wrong", status, None, resends, t_first,
                              t_end)
            return Answer("ack", status, parsed, resends, t_first, t_end)


# -- what the clients saw -----------------------------------------------------


@dataclass
class Write:
    seq: int
    t_first: float
    t_ack: float               # math.inf while unacknowledged


@dataclass
class Op:
    kind: str                  # put | get
    rank: int
    t_first: float
    t_end: float
    outcome: str               # ack | deadline | shed | wrong
    resends: int
    seq: int = -1              # GET: the sequence read, -1 for "not found"
    phase: str = ""
    writes: list = field(default_factory=list, repr=False)  # its record's


@dataclass
class History:
    """What every client sent and was told, shared by the phases of
    one run.  ``writes[rank]`` is appended only by the rank's one
    writer of the phase; phases do not overlap."""

    records: int
    writes: list[list[Write]] = field(default_factory=list)
    ops: list[Op] = field(default_factory=list)

    def __post_init__(self):
        self.writes = [[] for _ in range(self.records)]


def one_op(conn: Conn, plan: Plan, hist: History, kind: str, rank: int,
           phase: str, deadline_s: float = CLIENT_DEADLINE_S) -> Op:
    path = "/v2/keys" + plan.key(rank)
    if kind == "put":
        w = Write(len(hist.writes[rank]) + 1, time.monotonic(), math.inf)
        value = plan.value(rank, w.seq)
        hist.writes[rank].append(w)
        a = conn.op("PUT", path, ("value=" + value).encode(), deadline_s)
        w.t_first = a.t_first
        outcome = a.outcome
        if outcome == "ack":
            node = a.body.get("node") or {}
            if a.status in (200, 201) and node.get("value") == value:
                w.t_ack = a.t_end
            else:
                outcome = "wrong"
        return Op("put", rank, a.t_first, a.t_end, outcome, a.resends,
                  w.seq, phase, hist.writes[rank])
    a = conn.op("GET", path, None, deadline_s)
    outcome, seq = a.outcome, -1
    if outcome == "ack":
        if a.status == 200:
            try:
                got_rank, seq = parse_value(
                    (a.body.get("node") or {}).get("value") or "")
                if got_rank != rank:
                    outcome = "wrong"
            except ValueError:
                outcome = "wrong"
        elif not (a.status == 404 and a.body.get("errorCode") == 100):
            outcome = "wrong"
    return Op("get", rank, a.t_first, a.t_end, outcome, a.resends, seq,
              phase, hist.writes[rank])


def run_phase(plan: Plan, hist: History, host: str, port: int, *,
              phase: str, clients: int, put_share: float | None = None,
              ops: int | None = None, until: float | None = None,
              work: list[tuple[str, int]] | None = None,
              deadline_s: float = CLIENT_DEADLINE_S) -> list[Op]:
    """One closed-loop phase of ``clients`` threads.  It ends after
    ``ops`` operations were started, at the monotonic time ``until``,
    or when the explicit ``work`` list (dealt round-robin by position)
    is done.  Every operation started is finished and returned."""
    counter = itertools.count()
    out: list[list[Op]] = [[] for _ in range(clients)]

    def worker(i: int) -> None:
        conn = Conn(host, port)
        try:
            if work is not None:
                todo = iter(work[i::clients])
            else:
                todo = plan.stream(phase, i, clients, put_share)
            for kind, rank in todo:
                if ops is not None and next(counter) >= ops:
                    break
                if until is not None and time.monotonic() >= until:
                    break
                out[i].append(one_op(conn, plan, hist, kind, rank, phase,
                                     deadline_s))
        finally:
            conn.close()

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    done = [op for per in out for op in per]
    hist.ops.extend(done)
    return done


def percentile(values: list[float], q: float) -> float | None:
    """Nearest-rank percentile over ALL the values given."""
    if not values:
        return None
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]
