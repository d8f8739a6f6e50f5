"""Controls: the evidence of a run with one stated guarantee broken at one
object, as a system that gave that guarantee up would leave it.  Each has
to come out as not correct (``run.py --controls`` judges the run's evidence
again under each; the driver never passes it).  The system states no
precision, so there is no lower one to compute in: a control breaks a
guarantee of the configuration's file."""

from __future__ import annotations

import datetime
import random


def _pick(names, seed: int) -> str:
    names = sorted(names)
    if not names:
        raise ValueError("this run has no object the control could break")
    return random.Random(seed).choice(names)


def _with_pod(ev: dict, name: str, pod) -> dict:
    """``ev`` with ``name`` listed as ``pod`` (None: not listed); the run's
    own evidence is left as it was."""
    listed = dict(ev["listed"])
    if pod is None:
        listed.pop(name, None)
    else:
        listed[name] = pod
    return {**ev, "listed": listed}


def lost_ack(ev: dict, seed: int) -> dict:
    """Durability given up: one acknowledged create is not read back."""
    alive = [n for n in ev["created"] if n not in ev["deleted"] and n in ev["listed"]]
    return _with_pod(ev, _pick(alive, seed), None)


def undeleted(ev: dict, seed: int) -> dict:
    """One acknowledged delete is still served."""
    n = _pick(ev["deleted"], seed)
    return _with_pod(ev, n, {"metadata": {"name": n},
                             "status": ev["running_status"].get(n, {})})


def stale_watch(ev: dict, seed: int) -> dict:
    """The watch ran ahead of the store: a pod reported Running is Pending
    and without an address in the final LIST."""
    alive = [n for n in ev["running_seen"] if n in ev["listed"] and n not in ev["deleted"]]
    n = _pick(alive, seed)
    return _with_pod(ev, n, {**ev["listed"][n], "status": {"phase": "Pending"}})


def altered_status(ev: dict, seed: int) -> dict:
    """An approximate answer where it was exact: one pod's status lacks the
    ContainersReady condition."""
    n = _pick(ev["in_window"], seed)

    def cut(status: dict) -> dict:
        return {**status, "conditions": [c for c in status.get("conditions") or []
                                         if c.get("type") != "ContainersReady"]}

    if n in ev["deleted"]:
        return {**ev, "running_status": {**ev["running_status"],
                                         n: cut(ev["running_status"][n])}}
    return _with_pod(ev, n, {**ev["listed"][n], "status": cut(ev["listed"][n]["status"])})


def shared_address(ev: dict, seed: int) -> dict:
    """Two live pods are given one address."""
    alive = sorted(n for n in ev["created"] if n not in ev["deleted"] and n in ev["listed"])
    a = _pick(alive, seed)
    b = alive[(alive.index(a) + 1) % len(alive)]
    status = {**ev["listed"][a]["status"], "podIP": ev["listed"][b]["status"]["podIP"]}
    return _with_pod(ev, a, {**ev["listed"][a], "status": status})


def stuck_lease(ev: dict, seed: int) -> dict:
    """The lease plane gave one node up: its Lease is left as the window
    found it, and no renewal of it is seen again."""
    lease = ev["lease"]
    n = _pick(lease["nodes"], seed)
    events = [e for e in lease["events"] if e[1] != n or e[0] < lease["t0"]]
    return {**ev, "lease": {**lease, "events": events}}


def hasty_lease(ev: dict, seed: int) -> dict:
    """A heartbeat at twice the configured pace: every node is renewed once
    more halfway between any two of its renewals."""
    lease = ev["lease"]

    def at(renew: str) -> datetime.datetime:
        return datetime.datetime.fromisoformat(renew.replace("Z", "+00:00"))

    by_node: dict = {}
    for e in sorted(e for e in lease["events"] if lease["t0"] <= e[0] <= lease["t_end"]):
        by_node.setdefault(e[1], []).append(e)
    extra = [((ta + tb) / 2, n,
              (at(ra) + (at(rb) - at(ra)) / 2).isoformat(timespec="microseconds")
              .replace("+00:00", "Z"))
             for n, mine in by_node.items()
             for (ta, _n, ra), (tb, _m, rb) in zip(mine, mine[1:]) if ra != rb]
    if not extra:
        raise ValueError("this run has no object the control could break")
    return {**ev, "lease": {**lease, "events": lease["events"] + extra}}


def released_lease(ev: dict, seed: int) -> dict:
    """One node's Lease is held by nobody at the close."""
    lease = ev["lease"]
    n = _pick(lease["listed"], seed)
    spec = {**lease["listed"][n]["spec"], "holderIdentity": None}
    return {**ev, "lease": {**lease, "listed": {**lease["listed"],
                                                n: {**lease["listed"][n], "spec": spec}}}}


def lost_in_crash(ev: dict, seed: int) -> dict:
    """No log, or one not read at start: a pod acknowledged after the last
    snapshot is gone once the apiserver has crashed and come back."""
    n = _pick([n for n, p in ev["after_crash"].items() if p is not None], seed)
    return {**ev, "after_crash": {**ev["after_crash"], n: None}}


CONTROLS = {f.__name__: f for f in (lost_ack, undeleted, stale_watch, altered_status,
                                    shared_address, stuck_lease, hasty_lease, released_lease,
                                    lost_in_crash)}
