"""Plain reference of the default stage sets (upstream's
``kustomize/stage/pod/fast`` and ``node/fast``), written down from the
stages' published meaning and importing nothing of the program.

``pod-ready``: a pod bound to a node, not being deleted and without a
``status.podIP`` gets, at once, conditions Initialized / Ready /
ContainersReady (and one per readiness gate) all ``True``, one running
container status per container, one terminated-Completed status per init
container (running for a ``restartPolicy: Always`` side-car), the node's
IP as ``hostIP``, an address from the pod CIDR as ``podIP``, phase
``Running`` and a ``startTime``.  ``pod-delete``: a pod with a
``deletionTimestamp`` loses its finalizers and is deleted.

Which address a pod gets is the allocator's order, not a property of the
pod: the reference says what an address has to be (an IPv4 address that
no other live pod has), not which one."""

from __future__ import annotations

import ipaddress
from typing import List, Optional

TIME = "<time>"


def pod_ready_status(pod: dict, node_ip: str) -> dict:
    """The status ``pod-ready`` gives ``pod``, with every time as
    ``TIME`` and without ``podIP``."""
    spec = pod.get("spec") or {}
    conditions = [{"lastTransitionTime": TIME, "status": "True", "type": t}
                  for t in ("Initialized", "Ready", "ContainersReady")]
    conditions += [{"lastTransitionTime": TIME, "status": "True", "type": g["conditionType"]}
                   for g in spec.get("readinessGates") or []]
    status = {
        "conditions": conditions,
        "containerStatuses": [
            {"name": c["name"], "image": c["image"], "ready": True, "restartCount": 0,
             "state": {"running": {"startedAt": TIME}}}
            for c in spec.get("containers") or []
        ],
        "hostIP": node_ip,
        "phase": "Running",
        "startTime": TIME,
    }
    inits = []
    for c in spec.get("initContainers") or []:
        st = {"name": c["name"], "image": c["image"], "ready": True, "restartCount": 0}
        if c.get("restartPolicy") == "Always":
            st.update(started=True, state={"running": {"startedAt": TIME}})
        else:
            st["state"] = {"terminated": {"exitCode": 0, "reason": "Completed",
                                          "startedAt": TIME, "finishedAt": TIME}}
        inits.append(st)
    if inits:
        status["initContainerStatuses"] = inits
    return status


def _is_time(v) -> bool:
    """RFC 3339 as the cluster stamps it: ``2026-09-30T08:29:26Z`` with
    optional fraction."""
    if not isinstance(v, str) or len(v) < 20 or v[10] != "T" or not v.endswith("Z"):
        return False
    return v[:4].isdigit() and v[4] == "-" and v[13] == ":"


def _normal(x):
    """Times to ``TIME`` (by key, checked to be times), empty lists and
    nulls dropped: a status that says ``initContainerStatuses: null`` says
    what one without the key says."""
    if isinstance(x, dict):
        out = {}
        for k, v in x.items():
            if v is None or v == []:
                continue
            if k.endswith(("Time", "At", "Timestamp")) and _is_time(v):
                out[k] = TIME
            else:
                out[k] = _normal(v)
        return out
    if isinstance(x, list):
        return [_normal(v) for v in x]
    return x


def pod_mismatch(pod_sent: dict, status: Optional[dict], node_ip: str) -> Optional[str]:
    """None when ``status`` is what ``pod-ready`` makes of ``pod_sent``;
    else what differs."""
    got = _normal(status or {})
    ip = got.pop("podIP", None)
    try:
        ipaddress.IPv4Address(ip)
    except (ValueError, TypeError):
        return f"podIP {ip!r} is no IPv4 address"
    want = pod_ready_status(pod_sent, node_ip)
    if got != want:
        keys = sorted(k for k in set(got) | set(want) if got.get(k) != want.get(k))
        return f"status differs in {keys}"
    return None


def duplicate_ips(statuses: List[dict]) -> int:
    ips = [s.get("podIP") for s in statuses if s.get("podIP")]
    return len(ips) - len(set(ips))
