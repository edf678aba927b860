"""Streaming-replay chunk-size micro-harness (PR 3 satellite).

Sweeps the streaming pipeline's chunk size over the host path —
1 / 4 / 16 / 64 MiB — on a synthetic WAL stream, plus the unchunked
fused pass as the reference point, and writes one JSON artifact to
``trace_artifacts/replay_pipeline_<stamp>.json``.  This is the
measurement behind ``wal/backend_policy.DEFAULT_CHUNK_BYTES``.

    python scripts/replay_bench.py [entries] [payload]
    python scripts/replay_bench.py --smoke

``--smoke`` is the tier-1 wiring (scripts/test): a small blob driven
through BOTH the fused native entry point and the streaming path
end-to-end, with the outputs cross-checked record for record — a fast
structural exercise, not a measurement (no artifact written).

Prints ONE JSON line either way.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))

import numpy as np  # noqa: E402

SWEEP_MIB = (1, 4, 16, 64)
_ART_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "trace_artifacts")


def _gen(entries: int, payload: int):
    from etcd_tpu import native

    if not native.available():
        print(json.dumps({"error": "native toolchain unavailable"}))
        raise SystemExit(1)
    return native.wal_gen(entries, payload, start_index=1, seed=0)


def sweep(entries: int, payload: int) -> dict:
    from etcd_tpu import native
    from etcd_tpu.wal.replay_device import stream_scan_verify

    blob = _gen(entries, payload)
    out = {"metric": "replay_pipeline_chunk_sweep",
           "entries": entries, "payload": payload,
           "blob_mb": round(blob.nbytes / 1e6, 1), "rows": []}

    def timed(fn):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    t = timed(lambda: native.scan_verify(blob, seed=0))
    out["rows"].append({"chunk_mib": None, "mode": "fused-unchunked",
                        "seconds": round(t, 4),
                        "entries_per_sec": round(entries / t, 0)})
    for mib in SWEEP_MIB:
        t = timed(lambda: stream_scan_verify(
            blob, seed=0, route="host", chunk_bytes=mib << 20))
        out["rows"].append({"chunk_mib": mib, "mode": "host-chunked",
                            "seconds": round(t, 4),
                            "entries_per_sec":
                            round(entries / t, 0)})
    return out


def smoke() -> dict:
    """Small blob through the fused entry point AND the streaming
    path (host + fake-device-free stream on the in-process backend),
    outputs cross-checked — exits nonzero on any divergence."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from etcd_tpu import native
    from etcd_tpu.wal.replay_device import stream_scan_verify

    entries, payload = 4096, 64
    blob = _gen(entries, payload)
    fused = native.scan_verify(blob, seed=0)
    two_pass = native.wal_scan(blob)
    chunked = stream_scan_verify(blob, seed=0, route="host",
                                 chunk_bytes=64 << 10)
    streamed = stream_scan_verify(blob, seed=0, route="stream",
                                  chunk_bytes=64 << 10)
    for name, got in (("two-pass", two_pass), ("chunked", chunked),
                      ("streamed", streamed)):
        for i, (a, b) in enumerate(zip(fused, got)):
            if not np.array_equal(a, b):
                print(json.dumps({"error": f"{name} diverges from "
                                           f"fused at array {i}"}))
                raise SystemExit(1)
    # corruption must be caught by the fused lane too
    bad = blob.copy()
    bad[bad.nbytes // 2] ^= 0xFF
    try:
        native.scan_verify(bad, seed=0)
        print(json.dumps({"error": "fused scan missed corruption"}))
        raise SystemExit(1)
    except native.NativeError:
        pass
    return {"metric": "replay_pipeline_smoke", "entries": entries,
            "lanes": ["fused", "two-pass", "chunked", "streamed"],
            "ok": True}


def main() -> int:
    args = [a for a in sys.argv[1:]]
    if "--smoke" in args:
        print(json.dumps(smoke()))
        return 0
    entries = int(args[0]) if args else 500_000
    payload = int(args[1]) if len(args) > 1 else 256
    out = sweep(entries, payload)
    os.makedirs(_ART_DIR, exist_ok=True)
    stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
    path = os.path.join(_ART_DIR, f"replay_pipeline_{stamp}.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=2)
        fh.write("\n")
    out["artifact"] = os.path.relpath(path)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
