"""Offline cross-node trace stitcher (PR 8).

Merges flight-recorder dumps from several nodes (written by
``GET /mraft/obs/flight`` harvests or SIGTERM crash dumps), aligns
their monotonic clocks, reconstructs per-proposal timelines and
prints the per-stage wall breakdown plus the cluster CPU budget
table: WHICH stage eats the core, and where a proposal's wall time
actually goes (queue wait vs marshal vs network vs fsync vs apply).

Clock alignment: each node's events carry ITS monotonic clock.  For
every traced frame the leader stamps send (socket write) and ack
(response read) while the follower stamps recv and resp — a
symmetric NTP-style quad.  Per (sender, receiver) pair the offset
estimate is the median over quads of ``((t_recv - t_send) +
(t_resp - t_ack)) / 2`` (receiver clock minus sender clock, exact
under symmetric network delay); nodes reach the reference clock via
BFS over the pair graph, so a node aligns even when it only ever
exchanged traced frames with a non-reference node.

Usage:
  python scripts/trace_stitch.py DUMP_DIR_OR_FILES...
      [--json] [--min-complete N]
  python scripts/trace_stitch.py --smoke     # fixture self-check

A timeline is COMPLETE when every origin-side stage from ingest to
client-ack is present AND at least one follower hop (send → recv →
follower_fsync → resp → ack) stitched.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile

#: origin-side stages every complete timeline must carry, in causal
#: order (ingest -> coalesce/queue -> engine append -> leader fsync
#: -> quorum commit -> apply -> client ack)
ORIGIN_STAGES = ("ingest", "append", "leader_fsync", "commit",
                 "apply", "client_ack")


def _pctl(xs: list[float], q: float) -> float:
    xs = sorted(xs)
    if not xs:
        return 0.0
    return xs[min(len(xs) - 1, int(len(xs) * q))]


def load_dumps(paths: list[str]) -> list[dict]:
    """Load flight dumps from files and/or directories (every
    ``*.json`` under a directory)."""
    files: list[str] = []
    for p in paths:
        if os.path.isdir(p):
            files += sorted(glob.glob(os.path.join(p, "*.json")))
        else:
            files.append(p)
    nodes = []
    for f in files:
        with open(f) as fh:
            d = json.load(fh)
        if "events" not in d or "slot" not in d:
            raise ValueError(f"{f}: not a flight dump")
        d["_file"] = f
        nodes.append(d)
    if not nodes:
        raise ValueError(f"no flight dumps under {paths}")
    return nodes


def _frame_quads(nodes: list[dict]) -> dict[tuple, list]:
    """(sender slot, receiver slot) -> [(t_send, t_recv, t_resp,
    t_ack), ...] joined on the frame's per-channel seq."""
    send: dict[tuple, float] = {}
    ack: dict[tuple, float] = {}
    recv: dict[tuple, float] = {}
    resp: dict[tuple, float] = {}
    for n in nodes:
        slot = n["slot"]
        for e in n["events"]:
            if e["c"] != "frame":
                continue
            if e["dir"] == "send":
                send[(slot, e["peer"], e["seq"])] = e["t"]
            elif e["dir"] == "ack":
                ack[(slot, e["peer"], e["seq"])] = e["t"]
            elif e["dir"] == "recv":
                recv[(e["src"], slot, e["seq"])] = e["t"]
            elif e["dir"] == "resp":
                resp[(e["src"], slot, e["seq"])] = e["t"]
    quads: dict[tuple, list] = {}
    for key, t0 in send.items():
        t1, t2, t3 = recv.get(key), resp.get(key), ack.get(key)
        if t1 is None or t2 is None or t3 is None:
            continue
        a, b, _seq = key
        quads.setdefault((a, b), []).append((t0, t1, t2, t3))
    return quads


def align(nodes: list[dict]) -> dict[int, float]:
    """slot -> clock offset vs the reference node (subtract
    it from a node's event times to land on the reference clock).
    The reference is the process with the most span events (normally
    the serving leader)."""
    quads = _frame_quads(nodes)
    # pair offsets: receiver clock minus sender clock (NTP midpoint)
    pair_off: dict[tuple, float] = {}
    for (a, b), qs in quads.items():
        ests = sorted(((t1 - t0) + (t2 - t3)) / 2
                      for t0, t1, t2, t3 in qs)
        pair_off[(a, b)] = ests[len(ests) // 2]
    spans_per_slot: dict[int, int] = {}
    for n in nodes:
        spans = sum(1 for e in n["events"] if e["c"] == "span")
        spans_per_slot[n["slot"]] = \
            spans_per_slot.get(n["slot"], 0) + spans
    ref = max(spans_per_slot, key=spans_per_slot.get)
    off = {ref: 0.0}
    # BFS over the (undirected) pair graph
    frontier = [ref]
    while frontier:
        cur = frontier.pop()
        for (a, b), ab in pair_off.items():
            if a == cur and b not in off:
                off[b] = off[a] + ab       # b_clock - ref_clock
                frontier.append(b)
            elif b == cur and a not in off:
                off[a] = off[b] - ab
                frontier.append(a)
    for n in nodes:
        if n["slot"] not in off:
            # no traced exchange with the aligned set: leave its
            # events out rather than stitch on a wild clock
            print(f"trace_stitch: WARNING node {n['slot']} "
                  f"has no alignment path to {ref}; "
                  f"skipping its events", file=sys.stderr)
    return off


def stitch(nodes: list[dict]) -> dict:
    """Merge + align + reconstruct.  Returns the report dict.

    One dump per SLOT: a killed-and-restarted node leaves two dumps
    for the same slot (the victim's crash dump + the restarted
    incarnation's live ring) whose pipe seqs, trace ids and
    monotonic clock bases all restart — joining across incarnations
    would mix unrelated clock bases into the offset quads and merge
    unrelated proposals into one timeline.  We keep the incarnation
    with the newest wall anchor (the one that served last) and warn;
    stitch an earlier incarnation by passing only its files."""
    by_slot: dict[int, dict] = {}
    for n in nodes:
        cur = by_slot.get(n["slot"])
        if cur is None:
            by_slot[n["slot"]] = n
            continue
        newer, older = ((n, cur) if n.get("wall_anchor", 0)
                        >= cur.get("wall_anchor", 0) else (cur, n))
        print(f"trace_stitch: WARNING node {n['slot']} has "
              f"multiple incarnations; keeping {newer.get('_file')},"
              f" dropping {older.get('_file')}", file=sys.stderr)
        by_slot[n["slot"]] = newer
    nodes = list(by_slot.values())
    offsets = align(nodes)
    aligned = [n for n in nodes if n["slot"] in offsets]

    # per-(origin, trace) timeline: stage -> earliest aligned t
    timelines: dict[tuple[int, int], dict[str, float]] = {}

    def note(key, stage, t):
        tl = timelines.setdefault(key, {})
        if stage not in tl or t < tl[stage]:
            tl[stage] = t

    # frame events indexed per trace for the network hop legs
    for n in aligned:
        off = offsets[n["slot"]]
        for e in n["events"]:
            if e["c"] == "span":
                note((e["origin"], e["trace"]), e["stage"],
                     e["t"] - off)
            elif e["c"] == "frame" and "traces" in e:
                leg = {"send": "net_send", "recv": "net_recv"}.get(
                    e["dir"])
                if leg:
                    for tid, org in e["traces"]:
                        note((org, tid), leg, e["t"] - off)

    complete = []
    partial = 0
    for key, tl in timelines.items():
        if all(s in tl for s in ORIGIN_STAGES) \
                and "net_send" in tl and "net_recv" in tl \
                and "follower_fsync" in tl:
            complete.append(tl)
        else:
            partial += 1

    # per-stage deltas over complete timelines (milliseconds)
    legs = (
        ("queue_wait", "ingest", "append"),        # coalesce queue
        ("leader_fsync", "append", "leader_fsync"),
        ("net_out", "net_send", "net_recv"),
        ("follower_fsync", "net_recv", "follower_fsync"),
        ("commit_wait", "append", "commit"),       # send->quorum ack
        ("apply", "commit", "apply"),
        ("client_ack", "apply", "client_ack"),
        ("total", "ingest", "client_ack"),
    )
    breakdown = {}
    for name, a, b in legs:
        ds = [(tl[b] - tl[a]) * 1e3 for tl in complete
              if a in tl and b in tl]
        if ds:
            breakdown[name] = {
                "n": len(ds),
                "p50_ms": round(_pctl(ds, 0.5), 3),
                "p99_ms": round(_pctl(ds, 0.99), 3),
                "mean_ms": round(sum(ds) / len(ds), 3),
            }

    # cluster CPU budget: per-stage wall/cpu/device sums across
    # every dump (the etcd_stage_seconds families the stage()
    # facade feeds).  The sums are PROCESS-wide (each dump's
    # stages_scope), so dumps sharing a pid — an in-process
    # multi-server test cluster — carry the same combined table and
    # must count ONCE, not once per co-hosted node.
    budget: dict[str, dict[str, float]] = {}
    seen_pids: set = set()
    # budget sums need no clock alignment — include processes that
    # never exchanged traced frames
    for n in nodes:
        pid = n.get("pid")
        if pid and pid in seen_pids:
            continue
        seen_pids.add(pid)
        for stage, kinds in (n.get("stages") or {}).items():
            row = budget.setdefault(
                stage, {"wall_s": 0.0, "cpu_s": 0.0, "device_s": 0.0,
                        "passes": 0})
            row["wall_s"] += kinds.get("wall", {}).get("sum", 0.0)
            row["cpu_s"] += kinds.get("cpu", {}).get("sum", 0.0)
            row["device_s"] += kinds.get("device", {}).get("sum", 0.0)
            row["passes"] += kinds.get("wall", {}).get("count", 0)
    for row in budget.values():
        for k in ("wall_s", "cpu_s", "device_s"):
            row[k] = round(row[k], 4)

    return {
        "nodes": sorted(n["slot"] for n in aligned),
        "offsets_s": {str(slot): round(o, 6)
                      for slot, o in sorted(offsets.items())},
        "traces": len(timelines),
        "complete": len(complete),
        "partial": partial,
        "stage_breakdown_ms": breakdown,
        "cpu_budget": dict(sorted(
            budget.items(), key=lambda kv: -kv[1]["cpu_s"])),
    }


def stitch_dir(path: str) -> dict:
    return stitch(load_dumps([path]))


def print_report(rep: dict) -> None:
    print(f"nodes {rep['nodes']}  clock offsets "
          f"{rep['offsets_s']}")
    print(f"traces: {rep['traces']} total, {rep['complete']} "
          f"complete, {rep['partial']} partial")
    bd = rep["stage_breakdown_ms"]
    if bd:
        print(f"{'stage':16s} {'n':>6s} {'p50 ms':>9s} "
              f"{'p99 ms':>9s} {'mean ms':>9s}")
        for name, row in bd.items():
            print(f"{name:16s} {row['n']:6d} {row['p50_ms']:9.3f} "
                  f"{row['p99_ms']:9.3f} {row['mean_ms']:9.3f}")
    cb = rep["cpu_budget"]
    if cb:
        print(f"\n{'cpu budget':24s} {'passes':>8s} {'wall s':>9s} "
              f"{'cpu s':>9s} {'device s':>9s}")
        for stage, row in cb.items():
            print(f"{stage:24s} {row['passes']:8d} "
                  f"{row['wall_s']:9.3f} {row['cpu_s']:9.3f} "
                  f"{row['device_s']:9.3f}")


# -- fixtures (the --smoke self-check and tests/test_trace_pipeline) --------


def make_fixture(directory: str) -> list[str]:
    """Write a synthetic 3-node dump set with KNOWN clock offsets
    (node1 +5 s, node2 -3 s vs node0) and three proposals whose
    per-stage times are exact: queue 1 ms, leader fsync 3 ms,
    network 2 ms each way, follower fsync 2 ms, commit at +10 ms,
    apply +1 ms, client ack +1 ms.  Returns the file paths."""
    os.makedirs(directory, exist_ok=True)
    off = {0: 0.0, 1: 5.0, 2: -3.0}
    events: dict[int, list] = {0: [], 1: [], 2: []}
    idx = {0: 0, 1: 0, 2: 0}

    def ev(slot, t, cls, **fields):
        events[slot].append(
            {"t": t + off[slot], "i": idx[slot], "c": cls, **fields})
        idx[slot] += 1

    for k in range(1, 4):
        t0 = 1000.0 + k
        tid, org = 100 + k, 0
        ev(0, t0, "span", trace=tid, origin=org, stage="ingest",
           group=k)
        ev(0, t0 + 0.001, "span", trace=tid, origin=org,
           stage="append", group=k, gindex=k)
        ev(0, t0 + 0.004, "span", trace=tid, origin=org,
           stage="leader_fsync")
        for peer in (1, 2):
            ev(0, t0 + 0.0015, "frame", dir="send", peer=peer,
               seq=k, traces=[[tid, org]])
            ev(peer, t0 + 0.0035, "frame", dir="recv", src=0,
               seq=k, traces=[[tid, org]])
            ev(peer, t0 + 0.0055, "span", trace=tid, origin=org,
               stage="follower_fsync", host=peer)
            ev(peer, t0 + 0.006, "frame", dir="resp", src=0, seq=k)
            ev(0, t0 + 0.008, "frame", dir="ack", peer=peer, seq=k)
        ev(0, t0 + 0.010, "span", trace=tid, origin=org,
           stage="commit", group=k, gindex=k)
        ev(0, t0 + 0.011, "span", trace=tid, origin=org,
           stage="apply")
        ev(0, t0 + 0.012, "span", trace=tid, origin=org,
           stage="client_ack")
    paths = []
    for slot in (0, 1, 2):
        d = {
            "node": f"fix{slot}", "slot": slot, "pid": 100 + slot,
            "wall_anchor": 1.7e9, "mono_anchor": 2000.0 + off[slot],
            "capacity": 8192, "sample_n": 1, "dropped": 0,
            "stages": {"dist.propose": {
                "wall": {"sum": 0.5, "count": 10, "max": 0.1},
                "cpu": {"sum": 0.4, "count": 10, "max": 0.1},
                "device": {"sum": 0.2, "count": 10, "max": 0.05}}},
            "events": events[slot],
        }
        p = os.path.join(directory, f"flight_fix{slot}.json")
        with open(p, "w") as f:
            json.dump(d, f)
        paths.append(p)
    return paths


def smoke() -> None:
    """Self-check on the fixture set: offsets recovered to the ms,
    all three timelines complete, leg durations exact."""
    with tempfile.TemporaryDirectory() as td:
        make_fixture(td)
        rep = stitch_dir(td)
        print_report(rep)
        assert rep["complete"] == 3, rep
        off = {int(k): v for k, v in rep["offsets_s"].items()}
        assert abs(off[1] - 5.0) < 1e-3, off
        assert abs(off[2] - (-3.0)) < 1e-3, off
        bd = rep["stage_breakdown_ms"]
        for leg, want in (("queue_wait", 1.0), ("net_out", 2.0),
                          ("follower_fsync", 2.0), ("total", 12.0)):
            got = bd[leg]["p50_ms"]
            assert abs(got - want) < 0.01, (leg, got, want)
        assert rep["cpu_budget"]["dist.propose"]["cpu_s"] == 1.2
    print("TRACE STITCH SMOKE CLEAN: 3/3 timelines, offsets "
          "recovered, legs exact")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("paths", nargs="*",
                    help="flight dump files and/or directories")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as one JSON line")
    ap.add_argument("--min-complete", type=int, default=None,
                    help="exit nonzero unless at least N complete "
                         "timelines were reconstructed")
    ap.add_argument("--smoke", action="store_true",
                    help="fixture self-check (wired into "
                         "scripts/test)")
    args = ap.parse_args()
    if args.smoke:
        smoke()
        return
    if not args.paths:
        ap.error("give dump files/directories or --smoke")
    rep = stitch(load_dumps(args.paths))
    if args.json:
        print(json.dumps(rep))
    else:
        print_report(rep)
    if args.min_complete is not None \
            and rep["complete"] < args.min_complete:
        print(f"FAIL: {rep['complete']} complete timelines "
              f"< {args.min_complete}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    try:
        main()
    except BrokenPipeError:  # | head closed stdout mid-report
        os.dup2(os.open(os.devnull, os.O_WRONLY), 1)
        sys.exit(0)
