"""Test harness configuration.

Device-path tests run on a virtual 8-device CPU mesh so sharding
semantics are exercised without TPU hardware: ``JAX_PLATFORMS=cpu``
and ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` must both
be in the environment before jax first initializes a backend.  The
chip is reached only through ``python chip_smoke.py`` (one process
per chip; README "Quick start").
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()


# -- shared distributed-cluster test helpers --------------------------------


def free_ports(n: int) -> list[int]:
    """Reserve n distinct localhost ports (bind/close)."""
    import socket

    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def make_dist_cluster(tmp_path, m=3, g=8, ports=None, **kw):
    """Start m DistServers on localhost HTTP.  election=60 ticks
    (3s): first-round jit compiles and the shared-CPU test host push
    round latency past the production 0.5-1s window; the protocol is
    what's under test, not the timing margin."""
    from etcd_tpu import cli

    ports = ports or free_ports(m)
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    kw.setdefault("cap", 64)
    kw.setdefault("tick_interval", 0.05)
    kw.setdefault("post_timeout", 2.0)
    kw.setdefault("election", 60)
    # the members --dist-local-cluster hosts (tmp_path/slot<i>),
    # started with no campaign: bootstrap_dist_leader is the tests'
    servers = cli.local_dist_members(str(tmp_path), urls, g=g, **kw)
    cli.start_dist_members(servers, bootstrap=False)
    return servers, ports


def bootstrap_dist_leader(servers, timeout=30.0) -> None:
    """Converge host 0 onto leadership of every group (re-campaign
    lanes lost to peer-timer races)."""
    import time as _time

    deadline = _time.time() + timeout
    while _time.time() < deadline:
        lead = servers[0].mr.is_leader()
        if lead.all():
            return
        servers[0]._campaign(~lead)
        _time.sleep(0.3)
    raise AssertionError("bootstrap election did not converge")
