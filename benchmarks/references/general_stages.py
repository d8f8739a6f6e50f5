"""Plain reference of upstream's realistic pod lifecycle (the stage sets
``kustomize/stage/pod/general`` and ``pod/chaos``), written down from the
stages' published meaning and importing nothing of the program.

Each stage's ``statusTemplate`` is applied to ``status`` as a JSON merge
patch (RFC 7386), the Stage API's default patch type: an object in the
patch merges key by key, a list in it replaces the list that was there.

``pod-create``: a pod bound to a node, not being deleted and without a
``status.podIP`` turns ``Pending``: conditions Initialized (``True``, or
``False`` / ContainersNotInitialized where it has init containers), Ready
and ContainersReady ``False`` / ContainersNotReady, one ``True`` condition
per readiness gate; every container, and init container, waiting
(``ContainerCreating``, or ``PodInitializing`` where there are init
containers); the node's IP as ``hostIP`` and an address of the pod CIDR as
``podIP``; the finalizer ``kwok.x-k8s.io/fake`` and an Event ``Created``.
``pod-init-container-running`` and ``pod-init-container-completed`` take the
init containers to running and to terminated-Completed, the latter with
conditions of Initialized ``True`` alone.  ``pod-ready`` turns the pod
``Running``: conditions Ready and ContainersReady ``True``, every container
running; it writes no ``startTime``.  ``pod-complete`` (a Job's pods)
terminates the containers Completed and turns the pod ``Succeeded``.
``pod-container-running-failed`` (opted into by label) turns a Running pod
``Failed``: the named container, or every one, terminated with the
annotations' or the default reason, message and exit code, a ``startTime``;
``pod-ready`` then matches again, so the pod crash-loops.
``pod-init-container-running-failed`` does the same to the init containers
of a Pending pod.  ``pod-remove-finalizer`` and ``pod-delete`` take a pod
that was asked to go away.

A status is judged by the phase it shows: it has to be one of the statuses
the pod as sent can have in that phase, and a phase the pod cannot reach
(``Failed`` without an opt-in label, ``Succeeded`` without a Job owner) is
refused.  Which address a pod gets is the allocator's order, not a
property of the pod: the reference says what an address has to be."""

from __future__ import annotations

import ipaddress
from typing import Iterator, List, Optional

TIME = "<time>"
CHAOS = "pod-container-running-failed.stage.kwok.x-k8s.io"
INIT_CHAOS = "pod-init-container-running-failed.stage.kwok.x-k8s.io"


def merge(target, patch):
    """RFC 7386."""
    if not isinstance(patch, dict):
        return patch
    out = dict(target) if isinstance(target, dict) else {}
    for k, v in patch.items():
        if v is None:
            out.pop(k, None)
        else:
            out[k] = merge(out.get(k), v)
    return out


def _condition(ctype: str, status: str, **more) -> dict:
    return {"type": ctype, "status": status, **more, "lastTransitionTime": TIME}


def _names(containers: list) -> str:
    return "".join(f" {c['name']} " for c in containers)


def _container(c: dict, ready: bool, started: Optional[bool], state: dict) -> dict:
    out = {"name": c["name"], "image": c["image"], "ready": ready, "restartCount": 0,
           "state": state}
    if started is not None:
        out["started"] = started
    return out


def _waiting(containers: list, reason: str) -> list:
    return [_container(c, False, False, {"waiting": {"reason": reason}}) for c in containers]


def _terminated(reason: str = "Completed", code: int = 0, message: Optional[str] = None) -> dict:
    t = {"exitCode": code, "reason": reason, "startedAt": TIME, "finishedAt": TIME}
    if message is not None:
        t["message"] = message
    return {"terminated": t}


def pod_create(pod: dict, node_ip: str) -> dict:
    spec = pod.get("spec") or {}
    containers, inits = spec.get("containers") or [], spec.get("initContainers") or []
    unready = {"reason": "ContainersNotReady",
               "message": f"containers with unready status: [{_names(containers)}]"}
    if inits:
        first = _condition("Initialized", "False", reason="ContainersNotInitialized",
                           message=f"containers with incomplete status: [{_names(inits)}]")
    else:
        first = _condition("Initialized", "True")
    status = {
        "conditions": [first, _condition("Ready", "False", **unready),
                       _condition("ContainersReady", "False", **unready)]
        + [_condition(g["conditionType"], "True") for g in spec.get("readinessGates") or []],
        "containerStatuses": _waiting(containers,
                                      "PodInitializing" if inits else "ContainerCreating"),
        "hostIP": node_ip,
        "phase": "Pending",
    }
    if inits:
        status["initContainerStatuses"] = _waiting(inits, "PodInitializing")
    return status


def init_running(pod: dict) -> dict:
    return {"initContainerStatuses": [
        _container(c, True, True, {"running": {"startedAt": TIME}})
        for c in pod["spec"].get("initContainers") or []]}


def init_completed(pod: dict) -> dict:
    spec = pod["spec"]
    return {
        "conditions": [_condition("Initialized", "True", reason="")],
        "initContainerStatuses": [_container(c, True, False, _terminated())
                                  for c in spec.get("initContainers") or []],
        "containerStatuses": _waiting(spec.get("containers") or [], "ContainerCreating"),
    }


def pod_ready(pod: dict) -> dict:
    return {
        "conditions": [_condition(t, "True", reason="", message="")
                       for t in ("Ready", "ContainersReady")],
        "containerStatuses": [_container(c, True, True, {"running": {"startedAt": TIME}})
                              for c in pod["spec"].get("containers") or []],
        "phase": "Running",
    }


def pod_complete(pod: dict) -> dict:
    return {"containerStatuses": [_container(c, True, False, _terminated())
                                  for c in pod["spec"].get("containers") or []],
            "phase": "Succeeded"}


def _fault(pod: dict, stage: str, reason: str, message: str):
    """(target container or "", reason, message, exit code) of a chaos
    stage, from the pod's annotations or the stage's defaults."""
    ann = (pod.get("metadata") or {}).get("annotations") or {}
    prefix = f"{stage}.stage.kwok.x-k8s.io/"
    return (ann.get(prefix + "container-name") or "", ann.get(prefix + "reason") or reason,
            ann.get(prefix + "message") or message, int(ann.get(prefix + "exit-code") or 1))


def container_failed(pod: dict, node_ip: str) -> dict:
    target, reason, message, code = _fault(pod, "pod-container-running-failed",
                                           "containerFailed", "container failed")
    return {
        "conditions": [_condition("Initialized", "True", reason=""),
                       _condition("Ready", "False", reason=""),
                       _condition("ContainersReady", "False", reason="")],
        "containerStatuses": [
            _container(c, False, False, _terminated(reason, code, message))
            if not target or c["name"] == target
            else _container(c, True, None, {"running": {"startedAt": TIME}})
            for c in pod["spec"].get("containers") or []],
        "hostIP": node_ip,
        "phase": "Failed",
        "startTime": TIME,
    }


def init_container_failed(pod: dict, node_ip: str) -> dict:
    target, reason, message, code = _fault(pod, "pod-init-container-running-failed",
                                           "initContainerError", "initContainer reported errors")
    spec = pod["spec"]
    return {
        "conditions": [_condition(t, "False", reason="")
                       for t in ("Initialized", "Ready", "ContainersReady")],
        "initContainerStatuses": [
            _container(c, False, False, _terminated(reason, code, message))
            if not target or c["name"] == target
            else _container(c, True, None, _terminated())
            for c in spec.get("initContainers") or []],
        "containerStatuses": _waiting(spec.get("containers") or [], "PodInitializing"),
        "hostIP": node_ip,
        "phase": "Failed",
        "startTime": TIME,
    }


def _opted(pod: dict, label: str) -> bool:
    return ((pod.get("metadata") or {}).get("labels") or {}).get(label) == "true"


def _job_owned(pod: dict) -> bool:
    return any(o.get("kind") == "Job"
               for o in (pod.get("metadata") or {}).get("ownerReferences") or [])


def reachable(pod: dict, node_ip: str) -> Iterator[dict]:
    """Every status the stage set can give ``pod``, without ``podIP`` and
    with every time as ``TIME``, each once."""
    created = pod_create(pod, node_ip)
    yield created
    before_ready = created
    if (pod.get("spec") or {}).get("initContainers"):
        running = merge(created, init_running(pod))
        before_ready = merge(running, init_completed(pod))
        yield running
        yield before_ready
        if _opted(pod, INIT_CHAOS):
            for base in (created, running, before_ready):
                yield merge(base, init_container_failed(pod, node_ip))
    ready = merge(before_ready, pod_ready(pod))
    yield ready
    if _job_owned(pod):
        yield merge(ready, pod_complete(pod))
    if _opted(pod, CHAOS):
        failed = merge(ready, container_failed(pod, node_ip))
        yield failed
        # pod-ready matches a failed pod again where no container is
        # left running; the failure's startTime stays
        again = merge(failed, pod_ready(pod))
        yield again
        if _job_owned(pod):
            yield merge(again, pod_complete(pod))


def _is_time(v) -> bool:
    """RFC 3339 as the cluster stamps it: ``2026-09-30T08:29:26Z`` with
    optional fraction."""
    if not isinstance(v, str) or len(v) < 20 or v[10] != "T" or not v.endswith("Z"):
        return False
    return v[:4].isdigit() and v[4] == "-" and v[13] == ":"


def _normal(x):
    """Times to ``TIME`` (by key, checked to be times), empty lists and
    nulls dropped: a status that says ``lastProbeTime: null`` says what one
    without the key says."""
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
    """None when ``status`` is one the stage set gives ``pod_sent`` in the
    phase it shows; else what differs from the nearest such status."""
    got = _normal(status or {})
    ip = got.pop("podIP", None)
    try:
        ipaddress.IPv4Address(ip)
    except (ValueError, TypeError):
        return f"podIP {ip!r} is no IPv4 address"
    phase = got.get("phase")
    wants = [w for w in reachable(pod_sent, node_ip) if w["phase"] == phase]
    if not wants:
        return f"phase {phase!r} is none this pod can reach"
    if got in wants:
        return None

    def differing(want: dict) -> list:
        return sorted(k for k in set(got) | set(want) if got.get(k) != want.get(k))

    return f"status differs in {min(map(differing, wants), key=len)}"


def duplicate_ips(statuses: List[dict]) -> int:
    ips = [s.get("podIP") for s in statuses if s.get("podIP")]
    return len(ips) - len(set(ips))
