"""The yardstick: the table of peaks, the work a round and a CRC pass
need (from shapes and counts only), the reduction of a profiler trace
to busy time, named operations and attributed idle gaps, and the
readers that turn a ``layer_metrics/<name>.json`` file into a number.

A reader that finds nothing to read returns ``None`` and the harness
leaves the metric out.  A share of a roofline above 100 % raises: the
work was then counted too high or the time leaves out part of it.
"""

from __future__ import annotations

import datetime
import glob
import os
import re

from bench_load import percentile

#: published peaks per chip, keyed by ``device_kind`` as JAX reports it.
#: Source: Google Cloud documentation, "TPU v5e" (system architecture).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add "
                       f"it to bench_reduce.PEAKS with its source")
    return PEAKS[device_kind]


# -- work that does not depend on the implementation --------------------------

#: int32 scalars of Raft state a member keeps per group and a round
#: reads and writes once: term, vote, commit, applied, last index, log
#: offset, role, leader, election timer; and per peer of the group the
#: leader's next and match index.
MEMBER_SCALARS = 9
PEER_SCALARS = 2


def engine_round_work(groups: int, slots: int, entries: float) -> dict:
    """Least bytes one consensus round of ``groups`` groups with
    ``slots`` member slots moves when it appends ``entries`` entries:
    every state scalar read once and written once, and each entry's
    int32 term written into every slot's log."""
    state = groups * slots * (MEMBER_SCALARS + PEER_SCALARS) * 4
    return {"bytes": 2 * state + entries * slots * 4, "int8_ops": 0.0}


def crc_verify_work(nbytes: float) -> dict:
    """Least work to verify the CRC32C of ``nbytes`` of log: each byte
    read once, and eight shift-and-xor steps a byte."""
    return {"bytes": float(nbytes), "int8_ops": 8.0 * nbytes}


WORK = {"engine_round": engine_round_work, "crc_verify": crc_verify_work}


def least_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    by_ops = work["int8_ops"] / peaks["int8_ops"]
    return (by_bytes, "hbm") if by_bytes >= by_ops else (by_ops, "int8")


def roofline_share(least_s: float, measured_s: float) -> float:
    """Per cent of the roofline; never clipped."""
    share = 100.0 * least_s / measured_s
    if share > 100.0:
        raise ValueError(
            f"roofline share {share:.1f} % is over 100 %: least "
            f"{least_s:.3e} s against measured {measured_s:.3e} s")
    return share


# -- profiler trace -> events -> numbers --------------------------------------


def load_events(trace_dir: str) -> dict:
    """``{"device": [[chip, name, start_ns, dur_ns], ...], "host":
    [[name, start_ns, dur_ns], ...]}`` of the newest ``.xplane.pb``
    under ``trace_dir``.  Device events are the ``XLA Ops`` lines of
    the ``/device:`` planes; host events every line of ``/host:`` planes."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    device, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            chip = plane.name
            lines = [l for l in plane.lines if l.name == "XLA Ops"]
            for line in lines:
                for ev in line.events:
                    device.append([chip, ev.name, int(ev.start_ns),
                                   int(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append([ev.name, int(ev.start_ns),
                                 int(ev.duration_ns)])
    return {"device": device, "host": host}


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _host_name_in(host: list, s: int, e: int) -> str:
    """What the host was doing in the gap ``[s, e]``: the shortest
    host event that covers at least half of it, else the one that
    overlaps it most."""
    best_cover, best_any = None, None
    for name, hs, hd in host:
        ov = min(e, hs + hd) - max(s, hs)
        if ov <= 0:
            continue
        if 2 * ov >= e - s and (best_cover is None or hd < best_cover[0]):
            best_cover = (hd, name)
        if best_any is None or ov > best_any[0]:
            best_any = (ov, name)
    if best_cover:
        return best_cover[1]
    return best_any[1] if best_any else "host-unattributed"


NAME_SHOWN = 96                # an XLA op's name is its whole HLO line


def reduce_events(events: dict, patterns: dict[str, str]) -> dict:
    """Busy seconds (union of device operations, averaged over the
    chips seen), the traced window, the ten operations with most time,
    the ten longest idle gaps of the first chip named by what the host
    was doing, and per pattern the summed time and count of the
    operations whose name matches."""
    device, host = events["device"], events["host"]
    if not device:
        return {"busy_s": None, "window_s": None, "device_ops": [],
                "idle_gaps": [], "ops": {}}
    starts = [s for _, _, s, _ in device] + [s for _, s, _ in host]
    ends = [s + d for _, _, s, d in device] + [s + d for _, s, d in host]
    t0, t1 = min(starts), max(ends)
    chips = sorted({c for c, *_ in device})
    busy_ns, unions = 0, {}
    for chip in chips:
        unions[chip] = _union([(s, s + d) for c, _, s, d in device
                               if c == chip])
        busy_ns += sum(e - s for s, e in unions[chip])
    by_name: dict[str, int] = {}
    for _, name, _, d in device:
        by_name[name] = by_name.get(name, 0) + d
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    first = unions[chips[0]]
    edges = [t0] + [x for iv in first for x in iv] + [t1]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)[:10]
    ops = {}
    for key, pattern in patterns.items():
        rx = re.compile(pattern)
        hit = [d for _, name, _, d in device if rx.search(name)]
        if hit:
            ops[key] = {"seconds": sum(hit) / 1e9 / len(chips),
                        "count": len(hit)}
    return {
        "busy_s": busy_ns / 1e9 / len(chips),
        "window_s": (t1 - t0) / 1e9,
        "device_ops": [[n[:NAME_SHOWN], d / 1e9] for n, d in top],
        "idle_gaps": [[_host_name_in(host, s, e), g / 1e9]
                      for g, s, e in gaps],
        "ops": ops,
    }


def reduce_trace_dir(trace_dir: str, patterns: dict[str, str]) -> dict:
    return reduce_events(load_events(trace_dir), patterns)


# -- readers of layer_metrics/<name>.json -------------------------------------


def registry_read(snapshot: dict | None, spec: dict) -> float | None:
    """Sum of ``spec["field"]`` (count | sum | value) over the children
    of ``spec["family"]`` whose labels match ``labels`` and differ from
    ``labels_not`` (0 when none matches); ``None`` when the snapshot
    does not have the family at all."""
    fam = (snapshot or {}).get(spec["family"])
    if fam is None:
        return None
    want, avoid = spec.get("labels", {}), spec.get("labels_not", {})
    total = 0.0
    for child in fam["samples"]:
        labels = child["labels"]
        if any(labels.get(k) != v for k, v in want.items()):
            continue
        if any(labels.get(k) == v for k, v in avoid.items()):
            continue
        total += child.get(spec["field"], 0.0)
    return total


def registry_delta(before: dict | None, after: dict | None,
                   spec: dict) -> float | None:
    b = registry_read(after, spec)
    if b is None:
        return None
    return b - (registry_read(before, spec) or 0.0)


def _ratio(ctx: dict, spec: dict, span: str) -> float | None:
    before, after = ctx["registry"].get(span, (None, None))
    num = registry_delta(before, after, spec["numerator"])
    if num is None:
        return None
    if spec.get("denominator") is None:
        return num * spec.get("scale", 1.0)
    den = registry_delta(before, after, spec["denominator"])
    if not den:
        return None
    return num / den * spec.get("scale", 1.0)


def read_registry(spec: dict, ctx: dict) -> float | None:
    return _ratio(ctx, spec, spec.get("over", "window"))


def acked_in_window(ctx: dict, kind: str = "all") -> list:
    """The operations of kind ``put`` | ``get`` | ``all`` that were
    acknowledged inside the window."""
    return [op for op in ctx["window_ops"]
            if kind in (op.kind, "all") and op.outcome == "ack"
            and ctx["t0"] <= op.t_end <= ctx["t1"]]


def read_generator(spec: dict, ctx: dict) -> float | None:
    ops = acked_in_window(ctx, spec["ops"])
    if not ops:
        return None
    if spec["stat"] == "per_second":
        return len(ops) / (ctx["t1"] - ctx["t0"])
    if spec["stat"] == "percentile_ms":
        return 1000.0 * percentile([op.t_end - op.t_first for op in ops],
                                   spec["q"])
    if spec["stat"] == "resends_per_kop":
        return 1000.0 * sum(op.resends for op in ops) / len(ops)
    raise ValueError(f"unknown generator stat {spec['stat']!r}")


def read_clock(spec: dict, ctx: dict) -> float | None:
    """Seconds the run's parent took on its own monotonic clock:
    ``setup_s``, and the parts of a restart."""
    return ctx["clock"].get(spec["key"])


def read_log(spec: dict, ctx: dict) -> float | None:
    """A line of the window's server log: whether it is there, or how
    long after the restart signal it was written."""
    text = ctx.get("log_text")
    if text is None:
        return None
    m = re.search(spec["pattern"], text, re.M)
    if spec["value"] == "present":
        return 1.0 if m else 0.0
    if spec["value"] == "seconds_after_signal":
        if not m or ctx.get("t_signal_wall") is None:
            return None
        stamp = datetime.datetime.strptime(m.group("stamp"),
                                           "%Y-%m-%d %H:%M:%S,%f")
        return stamp.timestamp() - ctx["t_signal_wall"]
    raise ValueError(f"unknown log value {spec['value']!r}")


def read_disk(spec: dict, ctx: dict) -> float | None:
    """Bytes the data directory's files matching ``glob`` grew by over
    the window (drops, a collected segment, are not subtracted), per
    acknowledged write."""
    samples = ctx.get("disk_samples") or []
    writes = len(acked_in_window(ctx, "put"))
    if len(samples) < 2 or not writes:
        return None
    grown = sum(max(0, b - a) for a, b in zip(samples, samples[1:]))
    return grown / writes


def read_trace(spec: dict, ctx: dict) -> float | None:
    """Numbers of the device trace; ``None`` without one — a run on
    anything but a TPU takes no trace, so none of these is ever named
    from a CPU."""
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s"):
        return None
    stat = spec["stat"]
    if stat == "idle_share":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    if "ops" in spec:
        hit = tr["ops"].get(spec["name"])
        if hit is None:
            return None
        seconds = hit["seconds"]
    else:
        seconds = tr["busy_s"]
    per = 1.0
    if spec.get("per") is not None:
        before, after = ctx["registry"].get("trace", (None, None))
        per = registry_delta(before, after, spec["per"])
        if not per:
            return None
    if stat == "ms_per":
        return 1000.0 * seconds / per
    if stat == "roofline":
        args = {}
        for key, src in spec["work_args"].items():
            if isinstance(src, dict) and "log" in src:
                m = re.search(src["log"], ctx.get("log_text") or "")
                val = float(m.group("value")) if m else None
            elif isinstance(src, dict):
                val = _ratio(ctx, src, "trace")
            elif isinstance(src, str):
                val = ctx["facts"].get(src)
            else:
                val = src
            if val is None:
                return None
            args[key] = val
        work = WORK[spec["work"]](**args)
        least, _bound = least_seconds(work, peaks_of(ctx["device_kind"]))
        return roofline_share(least, seconds / per)
    raise ValueError(f"unknown trace stat {stat!r}")


READERS = {"registry": read_registry, "generator": read_generator,
           "clock": read_clock, "log": read_log, "disk": read_disk, "trace": read_trace}


def read_metric(spec: dict, ctx: dict) -> float | None:
    return READERS[spec["kind"]](spec, ctx)
