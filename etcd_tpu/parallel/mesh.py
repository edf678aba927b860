"""Device-mesh sharding of the consensus data plane.

Two mesh axes, chosen to mirror the two "sequence" dimensions the
reference processes serially (SURVEY §5.7):

- ``g`` (groups): data-parallel axis.  Raft group state ([G, ...]
  arrays) and WAL record rows ([N, L]) shard their leading axis here.
  The reference runs ONE raft group per process; here every device
  steps its local slice of tens of thousands of groups and the
  commit frontier is ``all_gather``-ed over ICI (BASELINE config 5).
- ``s`` (sequence): the WAL byte dimension.  Per-record CRC is a
  GF(2) contraction ``bits(row) @ C`` (ops/crc_device.py); sharding
  the contraction dimension makes each device compute a partial
  checksum of its byte-range which ``psum`` combines — the
  sequence-parallel analog of the reference's strictly sequential
  decoder loop (wal/decoder.go:28-47).

The rolling-chain seam between ``g`` shards (record i's expected CRC
depends on record i-1's stored CRC, which may live on the previous
device) is stitched with a ring ``ppermute``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.crc_device import (
    _chain_expected,
    _from_bits32,
    _unpack_bits,
    chain_verify_device,
    contribution_matrix,
    raw_crc_batch,
)
from ..ops.quorum import maybe_commit_batch
from ..raft.batched import GroupState, replication_round


def group_mesh(n_devices: int | None = None) -> Mesh:
    """Build a 2D ``(g, s)`` mesh over the first ``n_devices`` devices.

    The sequence axis gets a factor of 2 when the device count allows
    (even and >= 4); otherwise all devices go to the group axis.
    """
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    n = len(devs)
    s = 2 if (n >= 4 and n % 2 == 0) else 1
    g = n // s
    arr = np.asarray(devs[: g * s]).reshape(g, s)
    return Mesh(arr, ("g", "s"))


def serving_mesh(n_devices: int | None = None) -> Mesh:
    """A ``g``-only mesh over the first ``n_devices`` devices, for the
    serving tiers: ``MultiRaft.shard`` / ``DistMember.shard`` place
    [G]-leading state over ``g`` alone, so on :func:`group_mesh`'s
    ``(g, s)`` layout every ``s`` column would hold a full COPY of
    its row's groups (four chips, two of them replicas).  The fused
    replay+commit step keeps the ``s`` axis (make_sharded_step)."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), ("g",))


def check_group_divisible(mesh: Mesh, g: int) -> None:
    """Raise ValueError unless ``g`` splits evenly over the mesh's
    group axis — the one shared guard for every shard() entry point
    and the servers' pre-disk validation."""
    per = mesh.shape["g"]
    if g % per:
        raise ValueError(f"g={g} not divisible by mesh g-axis {per}")


def shard_leading(mesh: Mesh, x, axis: str = "g"):
    """Place ``x`` with its leading axis sharded over ``axis``."""
    spec = P(axis, *([None] * (jnp.ndim(x) - 1)))
    return jax.device_put(jnp.asarray(x), NamedSharding(mesh, spec))


def leading_placer(mesh: Mesh, axis: str = "g"):
    """The ONE recipe for placing per-call [G]-leading HOST inputs
    alongside g-sharded engine state (used by both batched runtimes'
    shard() paths).  A bare jnp.asarray commits such an input to one
    device, and XLA then reshards/replicates the big sharded state
    arrays around the mismatch on EVERY dispatch — measured as the
    37x serving-vs-raw-step gap of VERDICT r3 weakness #3.

    Returns ``put(arr, dtype=None)``: numpy conversion + device_put
    with the leading axis sharded (scalars pass through unsharded).
    """
    cache: dict[int, NamedSharding] = {}

    def put(arr, dtype=None):
        a = np.asarray(arr, dtype)
        if a.ndim == 0:
            return jnp.asarray(a)
        sh = cache.get(a.ndim)
        if sh is None:
            sh = NamedSharding(
                mesh, P(axis, *([None] * (a.ndim - 1))))
            cache[a.ndim] = sh
        return jax.device_put(a, sh)

    return put


# ---------------------------------------------------------------------------
# The fused data-plane step: WAL-chunk CRC chain verify + batched quorum
# commit.  One jittable function covering north-star configs 1 and 4; the
# sharded builder below adds config 5.
# ---------------------------------------------------------------------------


def replay_commit_local(buf, lens, stored, seed,
                        match, nmembers, committed, term,
                        log_terms, offset):
    """Single-chip fused step: returns ``(links_ok, new_committed)``.

    ``buf`` [N, L] uint8 right-aligned record payloads, ``lens`` [N]
    byte lengths, ``stored`` [N] the rolling CRCs recorded in the WAL
    (wal/encoder.go:25), ``seed`` scalar uint32 chain seed.  The raft
    arrays are the [G, ...] group-batched state of ops/quorum.py.

    ``links_ok`` [N] bool — every True link means record i's stored
    CRC equals ``update(stored[i-1], data_i)``; all-True implies the
    sequential chain of wal/decoder.go:45-46 holds by induction.

    Composes :func:`raw_crc_batch` (which picks the Pallas VMEM
    kernel on TPU) + :func:`chain_verify_device`; jittable as-is.
    """
    raw = raw_crc_batch(buf)
    links_ok = chain_verify_device(seed, stored, raw, lens)
    new_committed = maybe_commit_batch(
        match, nmembers, committed, term, log_terms, offset)
    return links_ok, new_committed


def data_plane_step(buf, lens, stored, seed, state: GroupState,
                    n_new, self_slot, resp_slots, resp_idx, resp_mask):
    """The flagship single-chip step: one fused device round of

    1. WAL-chunk CRC chain verification (north-star config 1), and
    2. the batched-raft leader pipeline — append proposals, absorb
       msgAppResp progress, advance quorum commit over all G groups
       (north-star config 4; raft/batched.py:replication_round).

    Returns ``(links_ok [N], state', err [G], n_committed [G])``.
    Jittable as-is; the mesh-sharded form is make_sharded_step.
    """
    raw = raw_crc_batch(buf)
    links_ok = chain_verify_device(seed, stored, raw, lens)
    state, err, ncomm = replication_round(
        state, n_new, self_slot, resp_slots, resp_idx, resp_mask)
    return links_ok, state, err, ncomm


def place_step_inputs(mesh: Mesh, args):
    """Shard a :func:`data_plane_step` argument tuple onto ``mesh``
    (the one placement recipe the dryrun and the config-5 bench both
    use — keep it HERE so a new argument is placed once, not in two
    divergent copies): ``buf`` over ``P('g', 's')``, every [G, ...]
    array and the GroupState pytree over ``P('g')``; the seed scalar
    stays replicated."""
    from jax.sharding import NamedSharding

    (buf, lens, stored, seed, state, n_new, self_slot, resp_slots,
     resp_idx, resp_mask) = args
    buf = jax.device_put(buf, NamedSharding(mesh, P("g", "s")))
    (lens, stored, n_new, self_slot, resp_slots, resp_idx,
     resp_mask) = (shard_leading(mesh, x) for x in (
         lens, stored, n_new, self_slot, resp_slots, resp_idx,
         resp_mask))
    state = jax.tree.map(lambda x: shard_leading(mesh, x), state)
    return (buf, lens, stored, seed, state, n_new, self_slot,
            resp_slots, resp_idx, resp_mask)


def make_sharded_step(mesh: Mesh):
    """jit-compiled mesh-sharded :func:`data_plane_step`.

    Shardings: ``buf`` [N, L] over ``P('g', 's')`` (rows data-parallel,
    bytes sequence-parallel with a psum'd GF(2) contraction); all
    [G, ...] group state over ``P('g')``; the commit frontier is
    ``all_gather``-ed over ICI so every device and the host apply loop
    see the full vector (BASELINE config 5).
    """
    def step(buf, lens, stored, seed, state, n_new, self_slot,
             resp_slots, resp_idx, resp_mask, c):
        links_ok = _chain_links_local(buf, lens, stored, seed, c)
        state, err, ncomm = replication_round(
            state, n_new, self_slot, resp_slots, resp_idx, resp_mask)
        commit_all = jax.lax.all_gather(state.commit, "g", tiled=True)
        return links_ok, state, err, ncomm, commit_all

    gspec = GroupState(*([P("g")] * len(GroupState._fields)))
    mapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P("g", "s"), P("g"), P("g"), P(), gspec, P("g"),
                  P("g"), P("g", None), P("g", None), P("g", None),
                  P("s", None)),
        out_specs=(P("g"), gspec, P("g"), P("g"), P()),
        check_vma=False,  # all_gather output is replicated over 'g'
    )

    @jax.jit
    def run(buf, lens, stored, seed, state, n_new, self_slot,
            resp_slots, resp_idx, resp_mask):
        buf = jnp.asarray(buf, dtype=jnp.uint8)
        c = jnp.asarray(contribution_matrix(buf.shape[1]))
        return mapped(buf, jnp.asarray(lens, jnp.int32),
                      jnp.asarray(stored, jnp.uint32),
                      jnp.asarray(seed, jnp.uint32), state,
                      jnp.asarray(n_new, jnp.int32),
                      jnp.asarray(self_slot, jnp.int32),
                      jnp.asarray(resp_slots, jnp.int32),
                      jnp.asarray(resp_idx, jnp.int32),
                      jnp.asarray(resp_mask, bool), c)

    return run


def _chain_links_local(buf, lens, stored, seed, c):
    """Shard-local body of the sequence-parallel CRC chain check:
    psum the GF(2) contraction over 's', ppermute the chain seam
    over 'g'.  Must run inside shard_map on a ('g', 's') mesh."""
    bits = _unpack_bits(buf)  # [N_loc, 8*L_loc]
    acc = jax.lax.dot_general(
        bits, c, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    acc = jax.lax.psum(acc, "s")  # XOR = sum mod 2 across byte shards
    raw = _from_bits32(acc & 1)

    ng = jax.lax.psum(1, "g")
    idx = jax.lax.axis_index("g")
    last = stored[-1]
    prev_last = jax.lax.ppermute(
        last, "g", [(i, (i + 1) % ng) for i in range(ng)])
    head_prev = jnp.where(idx == 0, seed.astype(jnp.uint32), prev_last)
    prev = jnp.concatenate([head_prev[None], stored[:-1]])
    return _chain_expected(prev, raw, lens.astype(jnp.uint32)) == stored


def make_replay_commit_step(mesh: Mesh):
    """jit-compiled mesh-sharded variant of :func:`replay_commit_local`.

    Shardings:
      - ``buf`` [N, L]: ``P('g', 's')`` — rows over groups-axis,
        bytes over sequence-axis; the GF(2) contraction partial-sums
        over ``s`` via ``psum``.
      - ``lens/stored`` [N]: ``P('g')``.
      - raft state [G, ...]: ``P('g')`` (log capacity replicated).
    Returns ``(links_ok [N] P('g'), committed_all [G] replicated)``
    — the commit frontier is all_gathered over ICI so every device
    (and the host apply loop) sees the full vector.
    """
    def step(buf, lens, stored, seed, match, nmembers, committed,
             term, log_terms, offset, c):
        links_ok = _chain_links_local(buf, lens, stored, seed, c)
        # -- group-local quorum commit, then gather the frontier.
        new_committed = maybe_commit_batch(
            match, nmembers, committed, term, log_terms, offset)
        committed_all = jax.lax.all_gather(
            new_committed, "g", tiled=True)
        return links_ok, committed_all

    mapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P("g", "s"), P("g"), P("g"), P(), P("g"), P("g"),
                  P("g"), P("g"), P("g", None), P("g"), P("s", None)),
        out_specs=(P("g"), P()),
        # all_gather's output IS replicated over 'g' but the static
        # varying-mesh-axes analysis cannot prove it.
        check_vma=False,
    )

    @jax.jit
    def run(buf, lens, stored, seed, match, nmembers, committed,
            term, log_terms, offset):
        buf = jnp.asarray(buf, dtype=jnp.uint8)
        c = jnp.asarray(contribution_matrix(buf.shape[1]))
        # Contribution rows are byte-major (8i+k): sharding C's rows
        # over 's' must align with buf's byte shards, which it does —
        # row block [8*lo, 8*hi) pairs with byte block [lo, hi).
        return mapped(
            buf, jnp.asarray(lens, jnp.int32),
            jnp.asarray(stored, jnp.uint32),
            jnp.asarray(seed, jnp.uint32),
            jnp.asarray(match, jnp.int32),
            jnp.asarray(nmembers, jnp.int32),
            jnp.asarray(committed, jnp.int32),
            jnp.asarray(term, jnp.int32),
            jnp.asarray(log_terms, jnp.int32),
            jnp.asarray(offset, jnp.int32), c)

    return run
