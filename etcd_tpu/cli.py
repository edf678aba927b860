"""CLI entry point (reference main.go): etcd-compatible flags and
ETCD_* env fallback; etcd mode or proxy mode.

Run as ``python -m etcd_tpu.cli --name node1 --data-dir /var/etcd ...``.
"""

from __future__ import annotations

import argparse
import logging
import os
import sys
import time
import urllib.parse

from . import __version__
from .api import make_client_handler, make_peer_handler, serve
from .api.proxy import NewProxyHandler
from .server import (
    Cluster,
    DEFAULT_SNAP_COUNT,
    ServerConfig,
    new_server,
)
from .utils.flags import (
    DEPRECATED_FLAGS,
    IGNORED_FLAGS,
    PROXY_VALUES,
    PROXY_VALUE_OFF,
    PROXY_VALUE_READONLY,
    parse_cors,
    parse_ip_address_port,
    set_flags_from_env,
    urls_from_flags,
    validate_urls,
)
from .utils.transport import TLSInfo, new_listener_context

log = logging.getLogger(__name__)


def build_parser() -> argparse.ArgumentParser:
    """Flag registry (reference main.go:27-99)."""
    p = argparse.ArgumentParser(
        prog="etcd-tpu", add_help=True,
        description="TPU-native etcd: highly-available key value store")
    p.add_argument("--name", default="default",
                   help="Unique human-readable name for this node")
    p.add_argument("--data-dir", default="",
                   help="Path to the data directory")
    p.add_argument("--discovery", default="",
                   help="Discovery service used to bootstrap the cluster")
    p.add_argument("--snapshot-count", type=int,
                   default=DEFAULT_SNAP_COUNT,
                   help="Number of committed transactions to trigger a "
                        "snapshot")
    p.add_argument("--version", action="store_true",
                   help="Print the version and exit")
    p.add_argument("--initial-cluster",
                   default="default=http://localhost:2380,"
                           "default=http://localhost:7001",
                   help="Initial cluster configuration for bootstrapping")
    p.add_argument("--initial-cluster-state", default="new",
                   choices=["new"],
                   help="Initial cluster state")
    p.add_argument("--advertise-peer-urls",
                   default="http://localhost:2380,http://localhost:7001")
    p.add_argument("--advertise-client-urls",
                   default="http://localhost:2379,http://localhost:4001")
    p.add_argument("--listen-peer-urls",
                   default="http://localhost:2380,http://localhost:7001")
    p.add_argument("--listen-client-urls",
                   default="http://localhost:2379,http://localhost:4001")
    p.add_argument("--cors", default="",
                   help="Comma-separated white list of origins for CORS")
    p.add_argument("--frontdoor",
                   default=os.environ.get("ETCD_FRONTDOOR", "on"),
                   choices=["on", "off"],
                   help="Serve the client API through the event-"
                        "driven front door (admission control, "
                        "per-tenant quotas, 50k-connection scale; "
                        "PR 12). 'off' falls back to the threaded "
                        "server")
    p.add_argument("--proxy", default=PROXY_VALUE_OFF,
                   choices=list(PROXY_VALUES))
    p.add_argument("--ca-file", default="")
    p.add_argument("--cert-file", default="")
    p.add_argument("--key-file", default="")
    p.add_argument("--peer-ca-file", default="")
    p.add_argument("--peer-cert-file", default="")
    p.add_argument("--peer-key-file", default="")
    p.add_argument("--storage-backend", default="auto",
                   choices=["auto", "tpu", "host"],
                   help="Data-plane backend: tpu uses the device replay/"
                        "hash kernels when a device is present")
    p.add_argument("--cohosted-groups", type=int, default=0,
                   help="Run the co-hosted multi-group server: N raft "
                        "groups batched through the device data plane "
                        "behind one /v2/keys endpoint (namespace = "
                        "first path segment). 0 = classic single-group "
                        "mode")
    p.add_argument("--cohosted-members", type=int, default=3,
                   help="Members per co-hosted group (default 3)")
    p.add_argument("--cohosted-mesh-devices", type=int, default=0,
                   help="Shard the co-hosted group batch over the "
                        "first N local devices (--cohosted-groups "
                        "must divide by the mesh's group axis; 0 = "
                        "single device)")
    p.add_argument("--dist-slot", type=int, default=-1,
                   help="Run the DISTRIBUTED multi-group server as "
                        "member slot N of --dist-peers: each host "
                        "owns one member of every co-hosted group, "
                        "rounds exchange batched frames over HTTP "
                        "(-1 = off)")
    p.add_argument("--dist-peers", default="",
                   help="Comma-separated slot-indexed peer base URLs "
                        "for --dist-slot mode (this host's own slot "
                        "included)")
    p.add_argument("--dist-local-cluster", type=int, default=0,
                   metavar="M",
                   help="Host ALL M member slots of a distributed "
                        "multi-group cluster in this one process "
                        "(three hosts cut to one chip): member i "
                        "keeps <data-dir>/slot<i> with its own WAL "
                        "and fsync, peers exchange the real frames "
                        "over loopback ports the program binds, "
                        "--listen-client-urls serves slot 0; "
                        "exclusive with --dist-slot/--dist-peers "
                        "(0 = off)")
    p.add_argument("--dist-local-link-delay-ms", default="",
                   metavar="A-B:MS[,...]",
                   help="With --dist-local-cluster: the ONE-WAY delay "
                        "in milliseconds of the peer link between "
                        "slots A and B, a pair each (e.g. "
                        "0-1:10,0-2:100,1-2:100: slots 0 and 1 a "
                        "20 ms round trip apart, slot 2 200 ms from "
                        "both); a pair not named has none. Every "
                        "peer frame and response between the two is "
                        "held that long by a delay line that does "
                        "not serialise the link (a window of frames "
                        "crosses in one delay); no jitter, loss or "
                        "bandwidth limit")
    p.add_argument("--dist-mesh-devices", type=int, default=0,
                   help="Shard this host's group batch over its first "
                        "N local devices (intra-host tier composed "
                        "under the cross-host tier; --cohosted-groups "
                        "must divide by the mesh's group axis; 0 = "
                        "single device)")
    # default 60 ticks (6s at the 0.1s tick): wide enough for every
    # supported host count's stratified bands and the jit-compile
    # first round; the timeout-bands lint checker guards this default
    # against the members default, and start_dist re-checks it
    # against the actual --dist-peers count (the DistMember clamp
    # would silently stretch a too-small value)
    p.add_argument("--dist-election-ticks", type=int, default=60,
                   help="Election timeout in ticks of 0.1 s for the "
                        "dist modes (default 60 = 6 s; a heartbeat "
                        "goes every tick); must be >= the number of "
                        "--dist-peers hosts so per-slot election "
                        "bands stay disjoint")
    # lease band (PR 7): the lease-band lint rule guards this
    # default against --dist-election-ticks (lease < election -
    # drift), and start_dist re-checks the actual values the same
    # way DistServer will
    p.add_argument("--dist-lease-ticks", type=int, default=30,
                   help="Leader-lease length in ticks of 0.1 s "
                        "(default 30 = 3 s) for "
                        "linearizable reads (must be < "
                        "--dist-election-ticks minus the clock-"
                        "drift margin; 0 disables the lease — "
                        "every linearizable read then takes the "
                        "batched ReadIndex confirmation)")
    p.add_argument("--dist-pipeline-depth", type=int, default=8,
                   help="Max in-flight append frames per peer "
                        "(windowed replication pipeline; 1 = "
                        "lockstep-equivalent, one frame per peer at "
                        "a time; >4 adds a second striped "
                        "connection per peer)")
    p.add_argument("--dist-coalesce-us", type=int, default=2000,
                   help="Adaptive drain cadence: a batch flushes "
                        "when full (entries/bytes) or this many "
                        "microseconds after its first proposal, "
                        "whichever first")
    # v0.4.6 back-compat (main.go:87-98); values are validated as
    # strict IP:port (pkg/flags/ipaddressport.go semantics)
    p.add_argument("--addr", default=None, type=parse_ip_address_port,
                   help="DEPRECATED: Use --advertise-client-urls instead.")
    p.add_argument("--bind-addr", default=None,
                   type=parse_ip_address_port,
                   help="DEPRECATED: Use --listen-client-urls instead.")
    p.add_argument("--peer-addr", default=None,
                   type=parse_ip_address_port,
                   help="DEPRECATED: Use --advertise-peer-urls instead.")
    p.add_argument("--peer-bind-addr", default=None,
                   type=parse_ip_address_port,
                   help="DEPRECATED: Use --listen-peer-urls instead.")
    for f in IGNORED_FLAGS:
        p.add_argument(f"--{f}", nargs="?", const="", default=None,
                       help=argparse.SUPPRESS)
    for f in DEPRECATED_FLAGS:
        p.add_argument(f"--{f}", default=None, help=argparse.SUPPRESS)
    return p


def _explicit_flags(argv: list[str]) -> set[str]:
    out = set()
    for a in argv:
        if a.startswith("--"):
            out.add(a[2:].split("=", 1)[0])
        elif a.startswith("-") and len(a) > 1:
            out.add(a[1:].split("=", 1)[0])
    return out


def main(argv: list[str] | None = None) -> int:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s: %(message)s")
    argv = argv if argv is not None else sys.argv[1:]
    parser = build_parser()
    args = parser.parse_args(argv)
    explicit = _explicit_flags(argv)

    if args.version:
        print("etcd version", __version__)
        return 0

    for f in DEPRECATED_FLAGS:
        if getattr(args, f.replace("-", "_")) is not None:
            print(f'flag "--{f}" is no longer supported.', file=sys.stderr)
            return 1
    for f in IGNORED_FLAGS:
        if getattr(args, f.replace("-", "_"), None) is not None:
            log.warning('flag "--%s" is no longer supported - ignoring.', f)

    set_flags_from_env(parser, args, explicit)

    cluster = Cluster()
    if args.discovery:
        # temporary self-only cluster until discovery completes
        # (reference main.go:253-275)
        apurls = urls_from_flags(args, "advertise_peer_urls", "peer_addr",
                                 explicit)
        cluster.set_from_string(
            ",".join(f"{args.name}={u}" for u in apurls))
    else:
        cluster.set_from_string(args.initial_cluster)

    if args.proxy != PROXY_VALUE_OFF:
        return start_proxy(args, cluster, explicit)
    # every serving mode below may compile (JAX_PLATFORMS picks the
    # platform; one chip belongs to one process)
    from .utils.jaxenv import configure_compile_cache

    configure_compile_cache()
    if args.dist_slot >= 0 or args.dist_local_cluster:
        return start_dist(args, explicit)
    if args.cohosted_groups > 0:
        return start_multigroup(args, explicit)
    return start_etcd(args, cluster, explicit)


#: how long --dist-local-cluster waits for every group's leader
#: before it gives up instead of serving (the v5e took 8-9 s, PR 21)
LOCAL_LEADERS_LIMIT_S = 120.0


def bind_loopback(n: int) -> list:
    """``n`` sockets bound to free loopback ports, kept OPEN: whoever
    listens there takes the socket itself (``DistServer``'s
    ``peer_sock``), so no other process can take the port between
    the choice and the listen."""
    import socket

    socks = []
    for _ in range(n):
        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        socks.append(sock)
    return socks


def parse_link_delays(spec: str, m: int) -> dict[tuple[int, int], float]:
    """``--dist-local-link-delay-ms``'s ``A-B:MS[,...]`` as unordered
    slot pair -> one-way delay in SECONDS, for a cluster of ``m``
    slots.  ValueError says what is wrong with a pair."""
    out: dict[tuple[int, int], float] = {}
    for part in filter(None, (x.strip() for x in spec.split(","))):
        try:
            pair, ms = part.split(":")
            a, b = sorted(int(x) for x in pair.split("-"))
            delay = float(ms)
        except ValueError:
            raise ValueError(
                f"{part!r} is not A-B:MS (two slots and a delay in "
                f"milliseconds)") from None
        if a == b or a < 0 or b >= m:
            raise ValueError(
                f"{part!r} names no pair of the slots 0..{m - 1}")
        if not 0 <= delay < float("inf"):
            raise ValueError(f"{part!r}: a delay is >= 0 ms")
        if (a, b) in out:
            raise ValueError(f"{part!r} states slots {a}-{b} twice")
        out[a, b] = delay / 1000.0
    return out


def dist_member(data_dir: str, slot: int, peers: list[str], **kw):
    """Member ``slot`` of the cluster ``peers`` names, on a data
    directory of its own: the one call of the ``DistServer``
    constructor that ``--dist-slot``, ``--dist-local-cluster``,
    ``chip_smoke.py`` and the tests' clusters share."""
    from .server.distserver import DistServer

    return DistServer(data_dir, slot=slot, peer_urls=peers, **kw)


def local_dist_members(root: str, peers: list[str] | int, *,
                       name: str | None = None, mesh_of=None,
                       link_delays: dict | None = None,
                       **kw) -> list:
    """Every member slot of a cluster in THIS process, not started:
    member i keeps ``<root>/slot<i>`` with its own WAL, snapshots and
    fsync and listens for its peers on ``peers[i]``; nothing stands
    in for the hosts or their network.  ``peers`` is the slot-indexed
    URL list, or the number of members: then each gets a loopback
    port bound here and keeps the socket.  ``name`` and ``mesh_of``
    give each member its own; ``kw`` is every member's.
    ``link_delays`` (:func:`parse_link_delays`) states a one-way
    delay a pair of slots: each member gets its row, and its peer
    links hold every frame and response that long."""
    socks = None
    if isinstance(peers, int):
        socks = bind_loopback(peers)
        peers = ["http://%s:%d" % sock.getsockname() for sock in socks]
    members = []
    for i in range(len(peers)):
        own = dict(kw)
        if name is not None:
            own["name"] = f"{name}-{i}"
        if mesh_of is not None:
            own["mesh"] = mesh_of(i)
        if socks is not None:
            own["peer_sock"] = socks[i]
        if link_delays:
            own["link_delay_s"] = {
                a + b - i: d for (a, b), d in link_delays.items()
                if i in (a, b)}
        members.append(dist_member(os.path.join(root, f"slot{i}"), i,
                                   peers, **own))
    return members


def start_dist_members(servers: list, bootstrap: bool = True) -> None:
    """Start each member's peer listener and its round thread.
    Slot 0 of a BRAND-NEW cluster (fresh = no prior WAL) then
    campaigns for every group; a restarted slot 0 rejoins through
    ordinary elections — mass-campaigning there would depose every
    established leader on the surviving hosts."""
    import numpy as np

    for s in servers:
        s.start()
    for s in servers:
        if bootstrap and s.slot == 0 and s.fresh:
            s._campaign(np.ones(s.g, bool))


def dist_groups_led(servers: list) -> int:
    """Groups that have a leader among ``servers`` which every one of
    them knows of: a client of any of these members then meets no
    leaderless group."""
    import numpy as np

    led = np.zeros(servers[0].g, bool)
    for s in servers:
        led |= np.asarray(s.mr.is_leader())
    for s in servers:
        led &= np.asarray(s.mr.leader_hint()) >= 0
    return int(led.sum())


def start_dist(args, explicit: set[str]) -> int:
    """Distributed multi-group mode: a process is ONE member slot of
    every co-hosted group and the peers listed in --dist-peers carry
    the other slots (server/distserver.py), or, with
    --dist-local-cluster M, it hosts all M slots itself.  The standard
    /v2 client API serves from the local replica; writes route to
    group leaders."""
    import signal
    import threading

    from .utils.jaxenv import log_devices

    local = args.dist_local_cluster
    if local:
        if args.dist_slot >= 0 or args.dist_peers:
            log.error("--dist-local-cluster hosts every member slot "
                      "itself: it excludes --dist-slot and "
                      "--dist-peers")
            return 1
        if local < 2:
            log.error("--dist-local-cluster needs at least 2 members")
            return 1
        n_peers = local
    else:
        peers = [u.strip() for u in args.dist_peers.split(",")
                 if u.strip()]
        if len(peers) < 2 or not (0 <= args.dist_slot < len(peers)):
            log.error("dist mode needs --dist-peers with >=2 "
                      "slot-indexed URLs and --dist-slot within range")
            return 1
        n_peers = len(peers)
    link_delays = None
    if args.dist_local_link_delay_ms:
        if not local:
            log.error("--dist-local-link-delay-ms places the members "
                      "of a --dist-local-cluster: between processes "
                      "the network is the delay")
            return 1
        try:
            link_delays = parse_link_delays(
                args.dist_local_link_delay_ms, local)
        except ValueError as e:
            log.error("--dist-local-link-delay-ms: %s", e)
            return 1
    if args.dist_election_ticks < n_peers:
        # the distmember election>=m clamp made mechanical at the
        # config surface: refuse rather than silently stretching the
        # operator's number (timeout-bands invariant)
        log.error("--dist-election-ticks=%d is below the host count "
                  "%d: %d disjoint per-slot election bands cannot "
                  "fit in [%d, %d) — pass at least %d",
                  args.dist_election_ticks, n_peers, n_peers,
                  args.dist_election_ticks,
                  2 * args.dist_election_ticks, n_peers)
        return 1
    if args.dist_lease_ticks > 0:
        from .server.readindex import lease_drift_ticks

        eff = max(args.dist_election_ticks, n_peers)
        if args.dist_lease_ticks >= eff - lease_drift_ticks(eff):
            # the lease-band invariant made loud at the config
            # surface (the DistServer constructor re-raises the same
            # rule): a lease at or past election - drift can serve
            # reads after a new leader commits
            log.error("--dist-lease-ticks=%d must be strictly below "
                      "--dist-election-ticks minus the clock-drift "
                      "margin (%d - %d); pass a smaller lease or 0 "
                      "to disable lease reads",
                      args.dist_lease_ticks, eff,
                      lease_drift_ticks(eff))
            return 1
    data_dir = args.data_dir or (
        f"{args.name}_dist_local_data" if local
        else f"{args.name}_dist{args.dist_slot}_data")
    os.makedirs(data_dir, mode=0o700, exist_ok=True)
    g = args.cohosted_groups or 64
    client_tls = TLSInfo(args.cert_file, args.key_file, args.ca_file)
    acurls = urls_from_flags(args, "advertise_client_urls", "addr",
                             explicit, client_tls.empty())
    lcurls = urls_from_flags(args, "listen_client_urls", "bind_addr",
                             explicit, client_tls.empty())
    log_devices()
    try:
        # with --dist-local-cluster every member shares this mesh
        # (or, without one, the default device)
        mesh = _local_mesh(args.dist_mesh_devices, g)
    except ValueError as e:
        log.error("--dist-mesh-devices: %s", e)
        return 1
    peer_tls = TLSInfo(args.peer_cert_file, args.peer_key_file,
                       args.peer_ca_file)
    kw = dict(g=g, snap_count=args.snapshot_count,
              election=args.dist_election_ticks,
              storage_backend=args.storage_backend,
              peer_tls=peer_tls if not peer_tls.empty() else None,
              pipeline_depth=args.dist_pipeline_depth,
              coalesce_us=args.dist_coalesce_us,
              lease_ticks=args.dist_lease_ticks)
    # member identity folds the slot in: hosts commonly share a
    # --name (the default!), and identical names would collapse to
    # one sha1 id whose registry entries overwrite each other
    try:
        # peer-TLS/https scheme agreement is validated by the
        # DistServer constructor (the single copy of that rule)
        if local:
            # no port in a configuration file: each member's peer
            # listener gets a free loopback port, bound here and kept
            servers = local_dist_members(
                data_dir, local, name=args.name,
                mesh_of=lambda slot: mesh, link_delays=link_delays,
                **kw)
        else:
            servers = [dist_member(
                data_dir, args.dist_slot, peers,
                name=f"{args.name}-{args.dist_slot}", mesh=mesh, **kw)]
    except ValueError as e:
        log.error("%s", e)
        return 1
    # --listen-client-urls is the first member's (slot 0 of a local
    # cluster, the slot of the one-slot form)
    servers[0].client_urls = list(acurls)
    cors = parse_cors(args.cors) if args.cors else None
    doors = []

    def serve_clients(s, u: str) -> str:
        host, port = _split_hostport(u)
        doors.append(_serve_client(args, s, cors, host, port,
                                   new_listener_context(client_tls)))
        return "%s://%s:%d" % (urllib.parse.urlsplit(u).scheme,
                               *doors[-1].server_address[:2])

    # the other members of a local cluster serve clients on loopback
    # ports of the system's choosing, known (and published under
    # /_etcd/machines) before the member starts
    scheme = "http" if client_tls.empty() else "https"
    for s in servers[1:]:
        s.client_urls = [serve_clients(s, f"{scheme}://127.0.0.1:0")]
        log.info("dist slot %d/%d serves clients on %s", s.slot,
                 n_peers, s.client_urls[0])
    # one way down, whatever the number of members: SIGTERM wakes the
    # main thread, which dumps each member's black-box ring next to
    # its data (or ETCD_FLIGHT_DIR) — what the chaos drill's
    # post-mortem reads — and stops each member.  An unhandled crash
    # dumps through install_crash_dump's hooks (PR 8).
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    start_dist_members(servers)
    from .obs.flight import install_crash_dump

    def flight_dir(s) -> str:
        return (os.environ.get("ETCD_FLIGHT_DIR")
                or os.path.join(s.data_dir, "trace_artifacts"))

    for s in servers:
        install_crash_dump(s.flight, flight_dir(s), signals=())
    leaderless = 0
    if local:
        # every group has a leader before the line that says the
        # cluster serves: no client meets a leaderless group.  (One
        # slot of several hosts cannot see the others' leadership and
        # listens at once, as before.)
        deadline = time.monotonic() + LOCAL_LEADERS_LIMIT_S
        while (leaderless := g - dist_groups_led(servers)) \
                and time.monotonic() < deadline \
                and not stop.wait(0.05):
            pass
        log.info("dist local cluster: slot 0 leads %d of %d groups",
                 int(servers[0].mr.is_leader().sum()), g)
    if leaderless and not stop.is_set():
        log.error("dist local cluster: %d of %d groups have no leader "
                  "that every member knows of %.0f s after the start; "
                  "not serving", leaderless, g, LOCAL_LEADERS_LIMIT_S)
        stop.set()
    for u in () if stop.is_set() else lcurls:
        serve_clients(servers[0], u)
        log.info("Listening for client requests on %s (dist slot "
                 "%d/%d, %d groups)", u, servers[0].slot, n_peers, g)
    stop.wait()
    for d in doors:
        d.shutdown()
    for s in servers:
        s.flight.dump_to(flight_dir(s), tag="sigterm")
    clean = all([s.stop() for s in servers])
    return 0 if clean and not leaderless else 1


def start_multigroup(args, explicit: set[str]) -> int:
    """Co-hosted multi-group mode: G groups' consensus runs as one
    batched device data plane behind the standard client API
    (server/multigroup.py — no reference counterpart; the reference
    is one group per process)."""
    from .server.multigroup import MultiGroupServer
    from .utils.jaxenv import log_devices

    log_devices()
    data_dir = args.data_dir or f"{args.name}_multigroup_data"
    os.makedirs(data_dir, mode=0o700, exist_ok=True)
    client_tls = TLSInfo(args.cert_file, args.key_file, args.ca_file)
    acurls = urls_from_flags(args, "advertise_client_urls", "addr",
                             explicit, client_tls.empty())
    try:
        mesh = _local_mesh(args.cohosted_mesh_devices,
                           args.cohosted_groups)
    except ValueError as e:
        log.error("--cohosted-mesh-devices: %s", e)
        return 1
    s = MultiGroupServer(
        data_dir, g=args.cohosted_groups, m=args.cohosted_members,
        name=args.name, snap_count=args.snapshot_count,
        storage_backend=args.storage_backend,
        client_urls=list(acurls), mesh=mesh)
    s.start()
    cors = parse_cors(args.cors) if args.cors else None
    lcurls = urls_from_flags(args, "listen_client_urls", "bind_addr",
                             explicit, client_tls.empty())
    for u in lcurls:
        host, port = _split_hostport(u)
        _serve_client(args, s, cors, host, port,
                      new_listener_context(client_tls))
        log.info("Listening for client requests on %s "
                 "(%d co-hosted groups x %d members)",
                 u, args.cohosted_groups, args.cohosted_members)

    _block_forever()
    return 0


def start_etcd(args, cluster: Cluster, explicit: set[str]) -> int:
    """Reference startEtcd (main.go:126-209)."""
    self_m = cluster.find_name(args.name)
    if self_m is None:
        log.error("etcd: no member with name=%r exists", args.name)
        return 1

    data_dir = args.data_dir
    if not data_dir:
        data_dir = f"{self_m.id}_etcd_data"
        log.info("main: no data-dir provided, using default data-dir "
                 "./%s", data_dir)
    os.makedirs(data_dir, mode=0o700, exist_ok=True)

    client_tls = TLSInfo(args.cert_file, args.key_file, args.ca_file)
    peer_tls = TLSInfo(args.peer_cert_file, args.peer_key_file,
                       args.peer_ca_file)

    acurls = urls_from_flags(args, "advertise_client_urls", "addr",
                             explicit, client_tls.empty())
    cfg = ServerConfig(
        name=args.name,
        client_urls=acurls,
        data_dir=data_dir,
        snap_count=args.snapshot_count,
        cluster=cluster,
        discovery_url=args.discovery,
        cluster_state=args.initial_cluster_state,
        storage_backend=args.storage_backend,
        peer_tls=peer_tls if not peer_tls.empty() else None,
    )
    s = new_server(cfg)
    s.start()

    cors = parse_cors(args.cors) if args.cors else None
    ph = make_peer_handler(s)

    lpurls = urls_from_flags(args, "listen_peer_urls", "peer_bind_addr",
                             explicit, peer_tls.empty())
    for u in lpurls:
        host, port = _split_hostport(u)
        serve(ph, host, port, new_listener_context(peer_tls))
        log.info("Listening for peers on %s", u)

    lcurls = urls_from_flags(args, "listen_client_urls", "bind_addr",
                             explicit, client_tls.empty())
    for u in lcurls:
        host, port = _split_hostport(u)
        _serve_client(args, s, cors, host, port,
                      new_listener_context(client_tls))
        log.info("Listening for client requests on %s", u)

    _block_forever()
    return 0


def start_proxy(args, cluster: Cluster, explicit: set[str]) -> int:
    """Reference startProxy (main.go:212-249) + discovery bootstrap
    (main.go:253-275's glue): with --discovery set, the proxy's
    endpoint list comes from the discovery registry instead of the
    flag-built cluster."""
    client_tls = TLSInfo(args.cert_file, args.key_file, args.ca_file)
    peer_urls = cluster.peer_urls_all()
    if args.discovery:
        from .discovery.discovery import proxy_endpoints

        discovered = proxy_endpoints(args.discovery)
        if discovered:
            peer_urls = discovered
            log.info("proxy: discovered %d endpoints via %s",
                     len(discovered), args.discovery)
    addrs = [urllib.parse.urlsplit(u).netloc for u in peer_urls]
    scheme = "https" if not client_tls.empty() else "http"
    handler = NewProxyHandler(
        addrs, scheme=scheme,
        readonly=args.proxy == PROXY_VALUE_READONLY)

    lcurls = urls_from_flags(args, "listen_client_urls", "bind_addr",
                             explicit, client_tls.empty())
    for u in lcurls:
        host, port = _split_hostport(u)
        serve(handler, host, port, new_listener_context(client_tls))
        log.info("Listening for client requests on %s", u)

    _block_forever()
    return 0


def _serve_client(args, s, cors, host: str, port: int, ssl_context):
    """One client listener: the event-driven front door by default,
    the threaded server with --frontdoor=off (or under TLS, where the
    front door itself falls back)."""
    if args.frontdoor == "on":
        from .server.frontdoor import serve_frontdoor

        return serve_frontdoor(s, host, port, ssl_context=ssl_context,
                               cors=cors)
    return serve(make_client_handler(s, cors=cors), host, port,
                 ssl_context)


def _local_mesh(n: int, groups: int):
    """Build a ``g``-only serving mesh over the first ``n`` local
    devices, or None when ``n`` is 0.  Fails fast (ValueError) on
    every flag misconfiguration — negative/oversized counts
    (serving_mesh would silently truncate) and a group count that
    does not split over the mesh — so the servers' own pre-disk
    guards never fire from the CLI path."""
    if not n:
        return None
    if n < 0:
        raise ValueError(f"mesh device count must be positive, "
                         f"got {n}")
    import jax

    from .parallel.mesh import check_group_divisible, serving_mesh

    avail = len(jax.devices())
    if n > avail:
        raise ValueError(f"{n} mesh devices requested but only "
                         f"{avail} available")
    mesh = serving_mesh(n)
    check_group_divisible(mesh, groups)
    return mesh


def _split_hostport(u: str) -> tuple[str, int]:
    parsed = urllib.parse.urlsplit(u)
    return parsed.hostname or "", parsed.port or 0


def _block_forever() -> None:  # pragma: no cover
    import threading

    threading.Event().wait()


if __name__ == "__main__":
    sys.exit(main())
