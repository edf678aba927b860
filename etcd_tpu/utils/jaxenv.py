"""Process-level JAX set-up shared by every entry point that compiles.

One chip belongs to one process, and every process that reaches it
starts cold unless the persistent compilation cache is on.  The cache
directory is part of the cache key's environment, so it must be a
FIXED path: ``JAX_COMPILATION_CACHE_DIR`` when the operator (or the
chip tool) set it — then nothing here names a directory — and
``<checkout>/.jax_cache`` (git-ignored) otherwise.  Never a temp
name, a pid or a timestamp.
"""

from __future__ import annotations

import json
import logging
import os

log = logging.getLogger(__name__)

#: repo root (the directory holding ``etcd_tpu/``)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Turn the persistent compile cache on before the first compile
    and return the directory in use.

    The engine is dozens of sub-second jits (one per fused op and
    shape); JAX's default 1 s minimum-compile-time threshold would
    keep nearly all of them out of the cache, so the threshold drops
    to zero on both branches."""
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def describe_devices() -> dict:
    """``{"platform", "kind", "count"}`` as JAX reports them — this
    initializes the backend, so only the process that is meant to
    hold the chip may call it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def log_devices() -> dict:
    """Log the device line once at server start: no endpoint names
    the device, and a run's numbers mean nothing without it."""
    import jax

    d = describe_devices()
    log.info("jax devices: platform=%s device_kind=%s count=%d "
             "compile_cache=%s", d["platform"], d["kind"], d["count"],
             jax.config.jax_compilation_cache_dir)
    return d


def log_placement(what: str, arr) -> list[dict]:
    """Log where one mesh-sharded array really lives: the shard each
    addressable device holds and that device's bytes in use.  Called
    by the serving tiers after ``shard(mesh)`` — a mesh whose axes
    the placement does not use shows up here as identical shards."""
    rows = []
    for sh in arr.addressable_shards:
        stats = sh.device.memory_stats() or {}
        rows.append({
            "device": sh.device.id,
            "rows": [sh.index[0].start or 0,
                     sh.index[0].stop
                     if sh.index[0].stop is not None
                     else arr.shape[0]],
            "shard": list(sh.data.shape),
            "bytes_in_use": stats.get("bytes_in_use")})
    log.info("placement %s %s: %s", what, list(arr.shape),
             json.dumps(rows))
    return rows
