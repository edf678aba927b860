"""Config 5 (BASELINE configs[4]): G raft groups sharded over a
device mesh — batched leader append + msgAppResp absorb + quorum
commit with the match-index quorum running under the mesh's
collectives (parallel/mesh.py make_sharded_step).

Runs the sharded program on whatever devices JAX_PLATFORMS names and
prints them with the result; the default is the CPU (pass
XLA_FLAGS=--xla_force_host_platform_device_count=8 for a virtual
mesh), and a CPU wall time is never a TPU throughput claim.

Prints ONE JSON line:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python scripts/config5_bench.py [GROUPS] [ITERS]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402
import numpy as np  # noqa: E402

from etcd_tpu.utils.jaxenv import (  # noqa: E402
    configure_compile_cache,
    describe_devices,
)


def main() -> None:
    groups = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 8

    configure_compile_cache()
    from __graft_entry__ import _example_args
    from etcd_tpu.parallel import (
        group_mesh,
        make_sharded_step,
        place_step_inputs,
    )

    mesh = group_mesh(len(jax.devices()))
    ng, ns = mesh.shape["g"], mesh.shape["s"]
    g = max(1, groups // ng) * ng
    args = place_step_inputs(mesh, _example_args(
        n=8 * ng, max_len=8 * ns, g=g, m=5, cap=32))

    step = make_sharded_step(mesh)

    def once():
        out = step(*args)
        jax.block_until_ready(out)
        return out

    t0 = time.perf_counter()
    out = once()  # compile
    compile_s = time.perf_counter() - t0
    assert bool(np.all(np.asarray(out[3]) == 2)), "commit stalled"

    t0 = time.perf_counter()
    for _ in range(iters):
        once()
    dt = (time.perf_counter() - t0) / iters

    # The serving-path form: MultiRaft state sharded over the mesh
    # (multiraft.py shard — what --cohosted-mesh-devices deploys),
    # fused proposal trains running SPMD across the mesh devices.
    from etcd_tpu.raft.multiraft import MultiRaft

    # same log-window/append-window class as the step above (cap 32);
    # e=4 covers the 1-proposal/round serving load with headroom
    mr = MultiRaft(g=g, m=5, cap=32, max_batch_ents=4)
    mr.shard(mesh)
    mr.campaign(0)
    one = np.ones(g, np.int32)
    train = 4
    mr.propose_rounds(one, train)  # compile at this static train
    mr.mark_applied(mr.commit_index())
    mr.compact()
    # average over several fused-train dispatches (same discipline
    # as the step metric above; compaction between trains stays
    # outside the timed regions)
    times = []
    for _ in range(max(2, iters // 2)):
        t0 = time.perf_counter()
        newly = mr.propose_rounds(one, train)
        times.append(time.perf_counter() - t0)
        assert int(newly.sum()) == g * train
        mr.mark_applied(mr.commit_index())
        mr.compact()
    serve_dt = sum(times) / len(times) / train

    print(json.dumps({
        "groups": g, "members": 5,
        "mesh": f"{ng}x{ns}",
        "device": describe_devices(),
        "step_ms": round(dt * 1e3, 2),
        "compile_s": round(compile_s, 1),
        "group_commits_per_sec": round(2 * g / dt, 0),
        "serving_sharded_round_ms": round(serve_dt * 1e3, 2),
        "serving_sharded_commits_per_sec": round(g / serve_dt, 0),
    }), flush=True)


if __name__ == "__main__":
    main()
