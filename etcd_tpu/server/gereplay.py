"""Array-form GroupEntry replay for multi-group restart.

Round-2 weakness #5: the co-hosted server replayed its WAL through
the device lane and then walked every entry with
``GroupEntry.unmarshal`` and a winners dict — reintroducing the
per-record scalar loop the project exists to kill (at 1M entries,
the restart bottleneck).  This module keeps the whole pass in arrays:

1. envelope fields come from ONE native sweep over the entry-data
   spans (native/walscan.cc:etcd_ge_scan; Python fallback when the
   toolchain is absent),
2. last-record-wins dedup per (group, gindex) — the replay-overwrite
   semantics of wal.go:171-175 generalized to the group axis — is a
   sort + run-boundary scan,
3. frontier / ballot selection is a reverse argmax; the co-hosted
   tier's sparse frontier records fold over it (:func:`fold_frontier`).

Payload bytes stay in the blob; only the (rare) committed winners
that actually apply to the store materialize Python objects.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .. import native
from ..wire import GroupEntry

#: GroupEntry kinds of the co-hosted WAL that carry its commit
#: frontier (the dist tier's kinds are in distserver.py).  The dense
#: record holds the whole ``[G]`` commit vector and the ``[G]``
#: term-at-commit vector; data directories written before the sparse
#: record hold it, and a restart still reads it.  The sparse record
#: holds the groups whose commit moved since the last record
#: (:func:`sparse_frontier`).
K_FRONTIER = 1
K_FRONTIER_SPARSE = 3


def sparse_frontier(g: int, groups, commit, terms) -> bytes:
    """Payload of a sparse commit-frontier record: an int32
    little-endian header ``(G, n)``, then the ``n`` groups, their
    commit indexes and their terms at commit, each ``n`` int32 LE."""
    n = len(groups)
    out = np.empty(2 + 3 * n, "<i4")
    out[0], out[1] = g, n
    out[2:2 + n] = groups
    out[2 + n:2 + 2 * n] = commit
    out[2 + 2 * n:] = terms
    return out.tobytes()


def read_sparse_frontier(payload: bytes):
    """``(G, groups, commit, terms)`` of a :func:`sparse_frontier`
    payload, the arrays int64."""
    v = np.frombuffer(payload, "<i4")
    if v.size < 2 or v.size != 2 + 3 * int(v[1]):
        raise ValueError(f"sparse frontier record of {len(payload)} B "
                         f"is malformed")
    n = int(v[1])
    body = v[2:].astype(np.int64)
    return int(v[0]), body[:n], body[n:2 * n], body[2 * n:]


def _check_g(written: int, g: int) -> None:
    if written != g:
        raise RuntimeError(
            f"data dir was written with --cohosted-groups {written}, "
            f"not {g}; group routing would silently change")


@dataclass(slots=True)
class GEStream:
    """Struct-of-arrays view of a replayed GroupEntry record stream."""

    seq: np.ndarray       # int64 [N] WAL entry index per record
    kind: np.ndarray      # int64 [N]
    group: np.ndarray     # int64 [N]
    gindex: np.ndarray    # int64 [N]
    gterm: np.ndarray     # int64 [N]
    # payloads: either spans into ``blob`` or a list of bytes
    poff: np.ndarray | None
    plen: np.ndarray | None
    blob: np.ndarray | None
    plist: list | None

    def __len__(self) -> int:
        return self.kind.size

    def payload(self, i: int) -> bytes | None:
        if self.plist is not None:
            return self.plist[i]
        ln = int(self.plen[i])
        if ln == 0:
            return None
        o = int(self.poff[i])
        return self.blob[o:o + ln].tobytes()

    # -- batch selections --------------------------------------------------

    def last_of_kind(self, kind: int) -> int:
        """Position of the last record of ``kind`` (-1 if none)."""
        hits = np.nonzero(self.kind == kind)[0]
        return int(hits[-1]) if hits.size else -1

    def winner_positions(self) -> np.ndarray:
        """Positions (ascending = stream order) of the kind-0 records
        that win last-record-wins dedup for their (group, gindex)."""
        pos = np.nonzero(self.kind == 0)[0]
        if pos.size == 0:
            return pos
        key = self.group[pos].astype(np.int64) * (1 << 40) \
            + self.gindex[pos].astype(np.int64)
        order = np.argsort(key, kind="stable")
        k_sorted = key[order]
        last_in_run = np.ones(k_sorted.size, bool)
        last_in_run[:-1] = k_sorted[1:] != k_sorted[:-1]
        return np.sort(pos[order[last_in_run]])


def fold_frontier(stream: GEStream, frontier: np.ndarray,
                  terms: np.ndarray, g: int):
    """The co-hosted commit frontier and term-at-commit vectors at
    the end of ``stream``, over the snapshot's ``frontier``/``terms``
    (the stream holds only records after the snapshot).  The last
    dense record replaces both whole; every sparse record after it
    sets its own groups, the last record of a group winning.  The
    work follows the records and the groups they name, never G.
    Raises when a record was written for another G."""
    frontier = np.array(frontier, np.int64)
    terms = np.array(terms, np.int64)
    dpos = stream.last_of_kind(K_FRONTIER)
    if dpos >= 0:
        v = np.frombuffer(stream.payload(dpos), np.int32)
        _check_g(v.size // 2, g)
        frontier = v[:g].astype(np.int64)
        terms = v[g:].astype(np.int64)
    spos = np.nonzero(stream.kind[dpos + 1:] == K_FRONTIER_SPARSE)[0]
    if spos.size == 0:
        return frontier, terms
    parts = [read_sparse_frontier(stream.payload(int(p) + dpos + 1))
             for p in spos]
    for written, *_ in parts:
        _check_g(written, g)
    groups = np.concatenate([p[1] for p in parts])
    if groups.size == 0:
        return frontier, terms
    if groups.min() < 0 or groups.max() >= g:
        raise ValueError("sparse frontier record names a group "
                         f"outside [0, {g})")
    # the last occurrence of each group: the first in reversed order
    named, first = np.unique(groups[::-1], return_index=True)
    last = groups.size - 1 - first
    frontier[named] = np.concatenate([p[2] for p in parts])[last]
    terms[named] = np.concatenate([p[3] for p in parts])[last]
    return frontier, terms


def scan(block_or_entries, blob: np.ndarray | None = None) -> GEStream:
    """Build a :class:`GEStream` from either a device-replay
    ``EntryBlock`` (native array sweep — no per-entry objects) or a
    host-replay ``list[Entry]`` (Python fallback loop)."""
    from ..wal.replay_device import EntryBlock

    if isinstance(block_or_entries, EntryBlock):
        b = block_or_entries
        if native.available():
            kind, group, gindex, gterm, poff, plen = native.ge_scan(
                b.blob, b.data_off, b.data_len)
            return GEStream(seq=b.index.astype(np.int64), kind=kind,
                            group=group, gindex=gindex, gterm=gterm,
                            poff=poff, plen=plen, blob=b.blob,
                            plist=None)
        entries = b.entries()
    else:
        entries = block_or_entries

    n = len(entries)
    seq = np.empty(n, np.int64)
    kind = np.empty(n, np.int64)
    group = np.empty(n, np.int64)
    gindex = np.empty(n, np.int64)
    gterm = np.empty(n, np.int64)
    plist: list[bytes | None] = []
    for i, e in enumerate(entries):
        ge = GroupEntry.unmarshal(e.data)
        seq[i] = e.index
        kind[i] = ge.kind
        group[i] = ge.group
        gindex[i] = ge.gindex
        gterm[i] = ge.gterm
        plist.append(ge.payload)
    return GEStream(seq=seq, kind=kind, group=group, gindex=gindex,
                    gterm=gterm, poff=None, plen=None, blob=None,
                    plist=plist)


def seed_log_arrays(stream: GEStream, winners: np.ndarray,
                    frontier: np.ndarray, fterms: np.ndarray,
                    g: int, cap: int):
    """Rebuild the engine's per-group log window from the replayed
    tail, entirely in arrays.

    Returns ``(log_term [g, cap], last [g], tail_positions)`` where
    slot 0 of each row carries the frontier term, slots 1.. carry the
    CONTIGUOUS run of winner terms above the frontier (a gap ends the
    run — a non-contiguous higher entry is unreachable garbage from
    a dropped batch), and ``tail_positions`` are the stream positions
    of the retained tail entries (callers hydrate their payload
    rings from these).
    """
    log_term = np.zeros((g, cap), np.int32)
    log_term[:, 0] = fterms
    last = frontier.astype(np.int64).copy()
    if winners.size == 0:
        return log_term, last, winners
    wg = stream.group[winners]
    wi = stream.gindex[winners]
    wt = stream.gterm[winners]
    rel = wi - frontier[wg]
    tail = (rel >= 1) & (rel < cap)
    if not tail.any():
        return log_term, last, winners[:0]
    tg, tt, tr = wg[tail], wt[tail], rel[tail].astype(np.int64)
    # presence matrix + cumulative product = per-group contiguous run
    # length from slot 1 (restart-only [g, cap] scratch; 100k groups
    # x cap 1024 is ~100 MB transiently)
    pres = np.zeros((g, cap), np.uint8)
    pres[tg, tr] = 1
    runlen = np.cumprod(pres[:, 1:], axis=1).sum(
        axis=1).astype(np.int64)
    last += runlen
    keep = tr <= runlen[tg]
    log_term[tg[keep], tr[keep]] = tt[keep]
    return log_term, last, np.sort(winners[tail][keep])
