"""timeout-bands: election/heartbeat/member-count band invariants.

DistMember's stratified election bands carve ``m`` disjoint
width->=1 bands out of ``[election, 2*election)`` — impossible when
``election < m``, which is why the constructor clamps ``election =
max(election, m)`` (PR 1).  A clamp protects the process but hides
the misconfiguration: the operator asked for a 4-tick election on an
8-host cluster and silently got 8.  This checker lifts the invariant
to every *config surface* so the bad number is caught where it is
written down:

- ``election-band``: a construction call (``DistMember`` /
  ``MultiRaft`` / ``init_groups`` / ``DistServer``) whose member
  count and election ticks are both statically known with
  ``election < m``.  ``DistServer``'s ``m`` is ``len(peer_urls)``
  when the list is a literal; omitted ``election`` uses the callee's
  known default.
- ``heartbeat-band``: classic-tier ``Raft`` / ``start_node`` /
  ``restart_node`` calls with constant ``heartbeat >= election`` —
  a leader that beats slower than followers time out can never hold
  leadership (raft.go invariant).
- ``cli-band``: in an argparse surface, an ``--*election*`` flag
  whose literal default is smaller than a ``--*members*`` flag's
  default in the same module, or a non-positive election default —
  the CLI is a config surface too, and its defaults are the most
  widely deployed config of all.
- ``lease-band`` (PR 7): a leader lease may only vouch for reads
  while no quorum-heard follower can have fired its election timer,
  so ``lease_ticks < election − drift`` (drift = ``max(1,
  election // 10)``, the clock-drift margin) at every surface: a
  ``DistServer`` call with literal ``lease_ticks`` and a known
  election, and an argparse ``--*lease*`` default against the
  ``--*election*`` default in the same module.  A lease at or past
  the band is a linearizability violation waiting for a partition —
  a new leader can commit while the stale lease still serves.
  ``lease_ticks <= 0`` (lease disabled / auto) stays quiet.

Dynamic values stay quiet (the runtime clamp still covers them);
this checker exists so constants written in code and flag tables
obey the band *before* the clamp rewrites them.
"""

from __future__ import annotations

import ast

from .engine import Checker, Finding, dotted_name, scope_map


def _const_int(node: ast.AST | None) -> int | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return node.value
    return None


def _arg(call: ast.Call, pos: int | None, kw: str):
    for k in call.keywords:
        if k.arg == kw:
            return k.value
    if pos is not None and pos < len(call.args):
        return call.args[pos]
    return None


#: callee leaf name -> (m positional index, election positional
#: index, election default).  Positions track the real signatures:
#: DistMember(g, m, slot, cap, election=10),
#: MultiRaft(g, m, cap, election=10),
#: init_groups(g, m, cap, election=10).
_ELECTION_CTORS = {
    "DistMember": (1, 4, 10),
    "MultiRaft": (1, 3, 10),
    "init_groups": (1, 3, 10),
}

def _lease_drift(election: int) -> int:
    """The lease band's clock-drift margin in ticks.  This package
    is stdlib-only, so this is a COPY of the runtime's formula
    (server/readindex.py:lease_drift_ticks) — pinned equal by
    tests/test_analysis.py's drift-guard so the static band and the
    runtime validation can never disagree."""
    return max(1, election // 10)


#: classic tier: (election positional index, heartbeat positional
#: index) — Raft(id, peers, election, heartbeat),
#: start_node(id, peers, election, heartbeat),
#: restart_node(id, election, heartbeat, ...)
_HEARTBEAT_CTORS = {
    "Raft": (2, 3),
    "start_node": (2, 3),
    "restart_node": (1, 2),
}


class TimeoutBandChecker(Checker):
    name = "timeout-bands"
    targets = ("etcd_tpu/", "scripts/")

    def check(self, relpath, tree, source, root=None, ctx=None):
        findings: list[Finding] = []
        scopes = scope_map(tree)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            leaf = dotted_name(node.func).split(".")[-1]
            if leaf in _ELECTION_CTORS:
                self._check_election(relpath, scopes.get(node, ""),
                                     leaf, node, findings)
            elif leaf == "DistServer":
                self._check_distserver(relpath,
                                       scopes.get(node, ""), node,
                                       findings)
            elif leaf in _HEARTBEAT_CTORS:
                self._check_heartbeat(relpath,
                                      scopes.get(node, ""), leaf,
                                      node, findings)
        self._check_argparse(relpath, tree, scopes, findings)
        return findings

    def _check_election(self, relpath, scope, leaf, call,
                        findings) -> None:
        # DistMember is the engine seam: g is positional, m may be
        # positional or keyword
        m_pos, e_pos, e_default = _ELECTION_CTORS[leaf]
        m = _const_int(_arg(call, m_pos, "m"))
        e_node = _arg(call, e_pos, "election")
        e = _const_int(e_node) if e_node is not None else e_default
        if m is None or e is None:
            return
        if e < m:
            findings.append(Finding(
                checker=self.name, path=relpath, line=call.lineno,
                rule="election-band", scope=scope,
                message=(
                    f"`{leaf}(... m={m}, election={e})`: "
                    f"{m} disjoint election bands cannot fit in "
                    f"[{e}, {2 * e}) — the runtime clamps election "
                    f"up to {m}, so this config lies about its "
                    f"recovery bound; pass election >= m"),
                detail=f"{leaf}:m>{e}"))

    def _check_distserver(self, relpath, scope, call,
                          findings) -> None:
        peers = _arg(call, None, "peer_urls")
        m = (len(peers.elts)
             if isinstance(peers, (ast.List, ast.Tuple)) else None)
        e_node = _arg(call, None, "election")
        e = _const_int(e_node) if e_node is not None else 10
        if e is not None and m:
            if e < m:
                findings.append(Finding(
                    checker=self.name, path=relpath,
                    line=call.lineno,
                    rule="election-band", scope=scope,
                    message=(
                        f"`DistServer(... peer_urls=<{m} hosts>, "
                        f"election={e})`: {m} disjoint election "
                        f"bands cannot fit in [{e}, {2 * e}) — pass "
                        f"election >= len(peer_urls)"),
                    detail=f"DistServer:m>{e}"))
        # lease-band (PR 7): only when lease_ticks is an explicit
        # literal (the omitted default, election//2, always sits in
        # band; <= 0 disables the lease).  election must be known
        # too — the constructor clamps election up to m, so use the
        # clamped value when the peer list is literal.
        lease = _const_int(_arg(call, None, "lease_ticks"))
        if lease is None or lease <= 0 or e is None or not m:
            # dynamic values stay quiet — the runtime validation
            # (DistServer.__init__ raises) still covers them
            return
        e_eff = max(e, m)
        if lease >= e_eff - _lease_drift(e_eff):
            findings.append(Finding(
                checker=self.name, path=relpath, line=call.lineno,
                rule="lease-band", scope=scope,
                message=(
                    f"`DistServer(... election={e}, "
                    f"lease_ticks={lease})`: the lease must sit "
                    f"strictly below election - drift = {e_eff} - "
                    f"{_lease_drift(e_eff)} ticks, or a stale "
                    f"lease can serve reads after a new leader "
                    f"commits (linearizability violation under "
                    f"partition)"),
                detail=f"DistServer:lease>={lease}"))

    def _check_heartbeat(self, relpath, scope, leaf, call,
                         findings) -> None:
        e_pos, h_pos = _HEARTBEAT_CTORS[leaf]
        e = _const_int(_arg(call, e_pos, "election"))
        h = _const_int(_arg(call, h_pos, "heartbeat"))
        if e is None or h is None:
            return
        if h >= e:
            findings.append(Finding(
                checker=self.name, path=relpath, line=call.lineno,
                rule="heartbeat-band", scope=scope,
                message=(
                    f"`{leaf}(... election={e}, heartbeat={h})`: "
                    f"the heartbeat interval must be strictly "
                    f"below the election timeout or followers "
                    f"campaign against a healthy leader"),
                detail=f"{leaf}:hb>={h}"))

    def _check_argparse(self, relpath, tree, scopes,
                        findings) -> None:
        election: list[tuple[str, int, ast.Call]] = []
        members: list[tuple[str, int]] = []
        leases: list[tuple[str, int, ast.Call]] = []
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "add_argument"
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            flag = node.args[0].value
            default = _const_int(_arg(node, None, "default"))
            if default is None:
                continue
            if "election" in flag:
                election.append((flag, default, node))
            elif "members" in flag:
                members.append((flag, default))
            elif "lease" in flag:
                leases.append((flag, default, node))
        # lease-band on flag tables: a --*lease* default must clear
        # the --*election* default's band in the same module
        # (<= 0 = lease disabled/auto, quiet)
        for lflag, ldefault, lnode in leases:
            if ldefault <= 0:
                continue
            for eflag, edefault, _enode in election:
                if edefault <= 0:
                    continue
                if ldefault >= edefault - _lease_drift(edefault):
                    findings.append(Finding(
                        checker=self.name, path=relpath,
                        line=lnode.lineno, rule="lease-band",
                        scope=scopes.get(lnode, ""),
                        message=(
                            f"`{lflag}` default {ldefault} is not "
                            f"strictly below `{eflag}` default "
                            f"{edefault} minus the "
                            f"{_lease_drift(edefault)}-tick drift "
                            f"margin — a stale lease could serve "
                            f"reads after a new leader commits; "
                            f"lower the lease default"),
                        detail=f"{lflag}>={ldefault}"))
        for flag, default, node in election:
            scope = scopes.get(node, "")
            if default <= 0:
                findings.append(Finding(
                    checker=self.name, path=relpath,
                    line=node.lineno, rule="cli-band", scope=scope,
                    message=(f"`{flag}` default {default} is not a "
                             f"positive tick count"),
                    detail=f"{flag}:nonpos"))
                continue
            for mflag, mdefault in members:
                if default < mdefault:
                    findings.append(Finding(
                        checker=self.name, path=relpath,
                        line=node.lineno, rule="cli-band",
                        scope=scope,
                        message=(
                            f"`{flag}` default {default} is below "
                            f"`{mflag}` default {mdefault}: "
                            f"{mdefault} member election bands "
                            f"cannot fit in [{default}, "
                            f"{2 * default}) — raise the election "
                            f"default to at least the member "
                            f"default"),
                        detail=f"{flag}<{mflag}"))

