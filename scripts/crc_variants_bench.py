"""Race the raw-CRC contraction variants on the current backend.

For each variant (production XLA path, Pallas kernel, and the
ops/crc_variants.py candidates) this measures the device-sustained
rate: the batch stays device-resident, the body XORs the loop index
in so XLA cannot hoist it, and one scalar fetch at the end is the
only sync.
A correctness gate (iteration-0 chain verify against stored CRCs)
must pass or the variant's number is reported as failed.

Prints one JSON line per variant plus a `best` summary line.

  python scripts/crc_variants_bench.py [N_ROWS] [WIDTH] [ITERS]

(Run through the chip tool for real-chip numbers; runs anywhere for
a relative CPU sanity check, labeled by backend.)
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402


def main() -> None:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1 << 18
    width = int(sys.argv[2]) if len(sys.argv) > 2 else 384
    iters = int(sys.argv[3]) if len(sys.argv) > 3 else 8

    import jax

    import jax.numpy as jnp

    from etcd_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()

    from etcd_tpu.crc import crc32c
    from etcd_tpu.obs import roofline
    from etcd_tpu.ops.crc_device import (
        _raw_crc_jit,
        chain_links_injected,
        contribution_matrix,
        inject_seeds,
    )
    from etcd_tpu.ops.crc_variants import VARIANTS, plane_matrices

    backend = jax.default_backend()

    # Measured MFU denominator for the per-variant roofline fields
    # (obs/roofline.py is the single source of truth for every
    # MFU/entries-per-TFLOP derivation — PR 2).  The probe costs a
    # ~1.1 TFLOP train: free on a chip, minutes on the 1-core CPU
    # box, so CPU runs skip it unless explicitly asked.
    ceiling_bf16 = None
    if backend == "tpu" or os.environ.get("BENCH_PROBE_CEILING"):
        ceiling_bf16 = roofline.probe_matmul_ceiling(jax, "bf16")
        print(json.dumps({"env_matmul_tflops_bf16":
                          round(ceiling_bf16, 2)
                          if ceiling_bf16 else None}), flush=True)

    # synthetic right-aligned chained records (seed-injected, so every
    # variant's gate is the full rolling-chain verify).  Generation is
    # vectorized — a python-loop crc32c.update over N rows costs tens
    # of minutes of chip time at N=1M: raw CRCs come from
    # one batched contraction, the rolling chain from a GF(2) matvec
    # scan (~23 us/row), and an INDEPENDENT host-table CRC spot check
    # over 256 random rows guards against the generator and the
    # device-under-test sharing a bug.
    from etcd_tpu.crc import gf2

    c = jnp.asarray(contribution_matrix(width))
    t_gen = time.perf_counter()
    rng = np.random.default_rng(3)
    lens = rng.integers(width // 2, width - 4, size=n)
    fill = rng.integers(0, 256, size=(n, width), dtype=np.uint8)
    mask = np.arange(width)[None, :] >= (width - lens)[:, None]
    rows = np.where(mask, fill, 0).astype(np.uint8)
    del fill, mask
    raw = np.asarray(_raw_crc_jit(rows, c, use_pallas=False))
    zmats = {int(ln): gf2.zero_operator(int(ln))
             for ln in np.unique(lens)}
    stored = np.empty(n, np.uint32)
    prev_ = np.empty(n, np.uint32)
    chain = 0
    inv = 0xFFFFFFFF
    for i in range(n):
        prev_[i] = chain
        chain = (gf2.matvec(zmats[int(lens[i])], chain ^ inv)
                 ^ int(raw[i]) ^ inv)
        stored[i] = chain
    # independent gate on the generator itself: host table CRC
    for i in rng.choice(n, size=min(n, 256), replace=False):
        li = int(lens[i])
        want = crc32c.update(int(prev_[i]),
                             rows[i, width - li:].tobytes())
        assert want == int(stored[i]), f"generator mismatch at {i}"
    inject_seeds(rows, lens, prev_)
    print(json.dumps({"generated": n,
                      "seconds": round(time.perf_counter() - t_gen,
                                       1)}), flush=True)

    drows = jax.device_put(rows)
    dstored = jax.device_put(stored)

    ck = jnp.asarray(plane_matrices(width))

    def make_fn(name):
        """(raw_fn, perturb_fn) for one variant: ``raw_fn(buf)``
        computes raw CRCs, ``perturb_fn(buf, i)`` (pallas_planes
        kernels only) folds the LICM-defeating XOR into the kernel
        via the SMEM scalar.  The race loop below uses perturb_fn
        when present, so the ranking does not charge these kernels
        an extra outer HBM pass a sustained loop never pays
        (ADVICE r5)."""
        if name == "xla":
            return (lambda b: _raw_crc_jit(b, c,
                                           use_pallas=False)), None
        if name == "pallas":
            return (lambda b: _raw_crc_jit(b, c,
                                           use_pallas=True)), None
        from etcd_tpu.ops import crc_variants

        # one validator for the name grammar: a typo fails loudly
        base, tile = crc_variants.parse_variant(name)
        if base.startswith("pallas_planes"):
            # default-tile resolution of the kernel wrappers
            # (ETCD_CRC_TILE override included)
            t = tile or crc_variants._planes_env_tile()
            transposed = base.endswith("_t")
            interp = backend != "tpu"
            return (lambda b: crc_variants._pallas_planes_jit(
                b, ck, t, transposed, interp),
                lambda b, i: crc_variants._pallas_planes_jit(
                    b, ck, t, transposed, interp, perturb=i))
        jit_map = {"planes": lambda b: crc_variants._planes_jit(b, ck),
                   "transposed":
                   lambda b: crc_variants._transposed_jit(b, c),
                   "planes_t":
                   lambda b: crc_variants._planes_t_jit(b, ck),
                   "int4": lambda b: crc_variants._int4_jit(b, c),
                   "planes4":
                   lambda b: crc_variants._planes4_jit(b, ck)}
        return jit_map[base], None

    from etcd_tpu.ops import crc_variants as _cv

    # every registered variant races (future VARIANTS additions are
    # picked up automatically); on TPU the pallas_planes pair is
    # covered by its explicit tile sweep instead of the default tile
    names = ["xla"] + sorted(VARIANTS)
    if backend == "tpu":
        names.insert(1, "pallas")
        # likely winners (the pallas tile sweep) race BEFORE the
        # speculative int4 bets: an s4 lowering with a pathological
        # compile time must not eat the window's race budget first
        names = [x for x in names
                 if x not in ("pallas_planes", "pallas_planes_t")]
        names += ["pallas_planes@512", "pallas_planes@1024",
                  "pallas_planes@2048",
                  "pallas_planes_t@1024", "pallas_planes_t@2048"]
        names += sorted(_cv.TPU_RACE_VARIANTS)

    results = {}
    for name in names:
        fn, perturb_fn = make_fn(name)

        @functools.partial(jax.jit, static_argnames=("k",))
        def loop(rows_, stored_, k, _fn=fn, _pfn=perturb_fn):
            def body(i, acc):
                if _pfn is not None:
                    # in-kernel SMEM perturbation — the
                    # sustained-loop form for these kernels; i == 0
                    # stays the unperturbed, correctness-gated pass
                    raw = _pfn(rows_, i)
                else:
                    raw = _fn(rows_ ^ i.astype(jnp.uint8))
                ok = chain_links_injected(raw, stored_)
                return acc + jnp.where(
                    i == 0, jnp.sum(ok, dtype=jnp.int32), 0)

            return jax.lax.fori_loop(0, k, body, jnp.int32(0))

        try:
            t0 = time.perf_counter()
            n_ok = int(loop(drows, dstored, iters))  # compile+gate
            compile_s = time.perf_counter() - t0
            if n_ok != n:
                results[name] = {"error": f"gate {n_ok}/{n}"}
                print(json.dumps({"variant": name,
                                  **results[name]}), flush=True)
                continue
            t0 = time.perf_counter()
            int(loop(drows, dstored, iters))
            dt = time.perf_counter() - t0
            eps = n * iters / dt
            gbps = n * width * iters / dt / 1e9
            results[name] = {"entries_per_sec": round(eps, 1),
                             "gbps": round(gbps, 3),
                             "compile_s": round(compile_s, 2)}
            # roofline-derived fields (generous + honest FLOP
            # definitions; ceiling_suspect tagging on impossible
            # fractions)
            results[name].update(roofline.mfu_fields(
                eps, width,
                measured_tflops_bf16=ceiling_bf16,
                provenance={"probe": "roofline.probe_matmul_ceiling",
                            "bf16_tflops": ceiling_bf16,
                            "backend": backend}))
            print(json.dumps({"variant": name, "backend": backend,
                              **results[name]}), flush=True)
        except Exception as e:  # per-variant isolation
            results[name] = {"error": repr(e)[:200]}
            print(json.dumps({"variant": name,
                              **results[name]}), flush=True)

    ok = {k: v for k, v in results.items() if "entries_per_sec" in v}
    if ok:
        best = max(ok, key=lambda k: ok[k]["entries_per_sec"])
        print(json.dumps({
            "best": best, "backend": backend, "n": n, "width": width,
            "iters": iters, **ok[best]}), flush=True)


if __name__ == "__main__":
    main()
