"""Cluster doctor: one CLI that turns the observability plane into
a single human-readable health report (PR 17).

Feed it dist nodes' peer URLs; it harvests, per host:

  - the metrics snapshot (``/mraft/obs``) — the profiler's
    stage×domain sample attribution;
  - the time-series ring (``/mraft/obs/timeseries``) — the last
    ~2 minutes of windowed deltas, pooled cross-host into the
    standard windowed row (acked/s and reads/s over 10 s, RTT p99
    over 60 s, shed rate);
  - the SLO verdict (``/mraft/obs/slo``) — merged worst-of across
    hosts with per-objective burn rates;
  - the flight ring (``/mraft/obs/flight``) — cross-node clock
    offsets recovered by
    scripts/trace_stitch.py's NTP-style frame-quad alignment.

A host that fails to answer is reported DOWN and skipped — the
doctor never turns one dead process into a harvest error.

  JAX_PLATFORMS=cpu python scripts/doctor.py URL [URL ...]
  JAX_PLATFORMS=cpu python scripts/doctor.py --json URL [URL ...]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from etcd_tpu.obs import slo as _slo  # noqa: E402
from etcd_tpu.obs import timeseries as _timeseries  # noqa: E402


def _get_json(url: str, timeout: float = 5.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _get_bytes(url: str, timeout: float = 5.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read()


def harvest_host(base: str, timeout: float = 5.0) -> dict:
    """Everything one host's obs plane offers, each endpoint
    independently best-effort."""
    host: dict = {"url": base, "up": False}
    for key, sub in (("obs", "/mraft/obs"),
                     ("timeseries", "/mraft/obs/timeseries"),
                     ("slo", "/mraft/obs/slo")):
        try:
            host[key] = _get_json(base + sub, timeout)
            host["up"] = True
        except Exception:
            pass
    try:
        host["flight"] = _get_bytes(base + "/mraft/obs/flight",
                                    timeout)
    except Exception:
        pass
    return host


def collect(urls: list[str], timeout: float = 5.0) -> dict:
    hosts = [harvest_host(u, timeout) for u in urls]
    ts_snaps = [h["timeseries"] for h in hosts
                if "timeseries" in h]
    verdicts = [h["slo"] for h in hosts if "slo" in h]
    rep: dict = {
        "t": time.time(),
        "hosts": hosts,
        "windowed": (_timeseries.windowed_summary(ts_snaps)
                     if ts_snaps else None),
        "slo": (_slo.merge_verdicts(verdicts)
                if verdicts else None),
    }
    rep["profile"] = profile_table(hosts)
    rep["clocks"] = clock_offsets(hosts)
    return rep


def profile_table(hosts: list[dict], top: int = 8) -> list[dict]:
    """Top stage×domain rows off the always-on sampling
    profiler's etcd_profile_samples_total — where the threads
    actually were, merged across every harvested host."""
    agg: dict[tuple, float] = {}
    for h in hosts:
        obs = h.get("obs") or {}
        for s in (obs.get("etcd_profile_samples_total") or
                  {}).get("samples", []):
            lb = s.get("labels", {})
            k = (lb.get("stage", "-"), lb.get("domain", "-"))
            agg[k] = agg.get(k, 0.0) + s.get("value", 0.0)
    total = sum(agg.values())
    rows = []
    for (stage, domain), n in sorted(agg.items(),
                                     key=lambda kv: -kv[1]):
        rows.append({"stage": stage, "domain": domain,
                     "samples": int(n),
                     "share": round(n / total, 4) if total else 0.0})
    return rows[:top]


def clock_offsets(hosts: list[dict]) -> dict | None:
    """Cross-node clock offsets recovered from the flight rings via
    trace_stitch's frame-quad alignment — the same offsets the
    stitcher subtracts to land every span on one clock."""
    import trace_stitch

    dumps = [h["flight"] for h in hosts if h.get("flight")]
    if len(dumps) < 2:
        return None
    td = tempfile.mkdtemp(prefix="doctor_flight_")
    try:
        paths = []
        for i, body in enumerate(dumps):
            p = os.path.join(td, f"flight_{i}.json")
            with open(p, "wb") as f:
                f.write(body)
            paths.append(p)
        nodes = trace_stitch.load_dumps(paths)
        off = trace_stitch.align(nodes)
        return {f"slot{slot}": round(v * 1e3, 3)
                for slot, v in sorted(off.items())}
    except Exception as e:
        return {"error": str(e)}
    finally:
        shutil.rmtree(td, ignore_errors=True)


def render(rep: dict) -> str:
    """The human-readable report."""
    L: list[str] = []
    L.append("== cluster doctor "
             + time.strftime("%Y-%m-%dT%H:%M:%SZ",
                             time.gmtime(rep["t"])) + " ==")
    up = sum(1 for h in rep["hosts"] if h["up"])
    L.append(f"hosts: {up}/{len(rep['hosts'])} answering")
    for h in rep["hosts"]:
        mark = "up" if h["up"] else "DOWN"
        L.append(f"  {h['url']} {mark}")
    w = rep.get("windowed")
    if w:
        L.append("windowed (time-series rings):")
        L.append(f"  acked/s (10s):      {w['acked_per_s_10s']}")
        L.append(f"  reads/s (10s):      {w['reads_per_s_10s']}")
        L.append(f"  ack p99 ms (60s):   {w['ack_rtt_p99_ms_60s']}")
        L.append(f"  read p99 ms (60s):  {w['read_rtt_p99_ms_60s']}")
        L.append(f"  shed rate (60s):    {w['shed_rate_60s']}")
    s = rep.get("slo")
    if s:
        L.append(f"slo: verdict={s['verdict']}"
                 + (f" worst={s['worst']}" if s.get("worst")
                    else ""))
        for name, o in sorted(s.get("objectives", {}).items()):
            L.append(f"  {name:<14} burn={o['burn_rate']:<8.3f} "
                     f"{'ok' if o.get('ok') else 'BURNING'}"
                     f" (target {o['target']}, "
                     f"{o.get('samples', 0)} samples)")
    if rep.get("profile"):
        L.append("profiler (top stage x domain by samples):")
        for r in rep["profile"]:
            L.append(f"  {r['share'] * 100:5.1f}%  "
                     f"stage={r['stage']} domain={r['domain']} "
                     f"({r['samples']})")
    c = rep.get("clocks")
    if c:
        L.append("clock offsets vs reference (ms, flight-ring "
                 "frame quads):")
        for k, v in c.items():
            L.append(f"  {k:<20} {v}")
    return "\n".join(L)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("urls", nargs="*",
                    help="dist nodes' peer base URLs")
    ap.add_argument("--json", action="store_true",
                    help="emit the raw report dict instead of the "
                         "rendered text")
    ap.add_argument("--timeout", type=float, default=5.0)
    args = ap.parse_args()
    if not args.urls:
        ap.error("need at least one URL")
    rep = collect(args.urls, timeout=args.timeout)
    if args.json:
        # flight bodies are bytes and huge — the JSON view carries
        # everything else
        for h in rep["hosts"]:
            h.pop("flight", None)
        print(json.dumps(rep, indent=1, sort_keys=True))
    else:
        print(render(rep))


if __name__ == "__main__":
    main()
