"""Plain reference of ``sched-basic-5k``: the default stage sets of
``fast_stages`` (a bound pod turns Running as ``pod-ready`` says), and the
scheduling semantics the cluster's scheduler claims, written down from
kube-scheduler's filters and importing nothing of the program.

A pod may be bound to a node only if the node exists and is Ready (its
``Ready`` condition ``True``, not ``spec.unschedulable``, not being
deleted), every ``NoSchedule`` taint of the node is tolerated by one of the
pod's tolerations (an ``Exists`` toleration with no key tolerates every
taint; a key, a value under ``Equal`` and an effect, where given, have to
match), and the node's labels hold every key and value of the pod's
``spec.nodeSelector``.  On every node the pods bound there and not finished
(``Succeeded`` or ``Failed``) request in sum no more CPU and memory than the
node allocates (``status.allocatable``, else ``status.capacity``) and are no
more than its ``pods``.  A requested quantity is the sum over the pod's
containers of ``resources.requests``.  A pod left unbound, and not being
deleted, is a violation unless no node could take it: none is feasible, or
each feasible one is full for it."""

from __future__ import annotations

from typing import Dict, List, Tuple

from .fast_stages import duplicate_ips, pod_mismatch  # noqa: F401  (the stages' half)

#: binary and decimal suffixes of a Kubernetes quantity
_SUFFIX = {
    "Ki": 2 ** 10, "Mi": 2 ** 20, "Gi": 2 ** 30, "Ti": 2 ** 40, "Pi": 2 ** 50, "Ei": 2 ** 60,
    "n": 1e-9, "u": 1e-6, "m": 1e-3, "k": 1e3, "M": 1e6, "G": 1e9, "T": 1e12, "P": 1e15,
    "E": 1e18,
}
#: a node that states no pod count holds the Kubernetes default
DEFAULT_PODS = 110


def quantity(text) -> float:
    """``"100m"`` -> 0.1, ``"500Mi"`` -> 524288000.0, ``"32"`` -> 32.0."""
    s = str(text).strip()
    for suffix in sorted(_SUFFIX, key=len, reverse=True):
        if s.endswith(suffix) and s[:-len(suffix)]:
            return float(s[:-len(suffix)]) * _SUFFIX[suffix]
    return float(s)


def requests(pod: dict) -> Tuple[float, float]:
    """(CPU cores, memory bytes) the pod's containers request."""
    cpu = mem = 0.0
    for c in (pod.get("spec") or {}).get("containers") or []:
        req = (c.get("resources") or {}).get("requests") or {}
        cpu += quantity(req["cpu"]) if "cpu" in req else 0.0
        mem += quantity(req["memory"]) if "memory" in req else 0.0
    return cpu, mem


def allocatable(node: dict) -> Tuple[float, float, float]:
    """(CPU, memory, pods) the node offers; what it does not state is unlimited
    (the pod count: the default)."""
    status = node.get("status") or {}
    res = status.get("allocatable") or status.get("capacity") or {}
    inf = float("inf")
    return (quantity(res["cpu"]) if "cpu" in res else inf,
            quantity(res["memory"]) if "memory" in res else inf,
            quantity(res["pods"]) if "pods" in res else DEFAULT_PODS)


def ready(node: dict) -> bool:
    if (node.get("spec") or {}).get("unschedulable"):
        return False
    if (node.get("metadata") or {}).get("deletionTimestamp"):
        return False
    return any(c.get("type") == "Ready" and c.get("status") == "True"
               for c in (node.get("status") or {}).get("conditions") or [])


def _tolerated(taint: dict, tolerations: List[dict]) -> bool:
    for t in tolerations:
        op = t.get("operator") or "Equal"
        if t.get("key"):
            if t["key"] != taint.get("key"):
                continue
        elif op != "Exists":
            continue
        if op == "Equal" and (t.get("value") or "") != (taint.get("value") or ""):
            continue
        if t.get("effect") and t["effect"] != taint.get("effect"):
            continue
        return True
    return False


def tolerates(pod: dict, node: dict) -> bool:
    tolerations = (pod.get("spec") or {}).get("tolerations") or []
    return all(_tolerated(taint, tolerations)
               for taint in (node.get("spec") or {}).get("taints") or []
               if taint.get("effect") == "NoSchedule")


def selects(pod: dict, node: dict) -> bool:
    labels = (node.get("metadata") or {}).get("labels") or {}
    return all(labels.get(k) == v
               for k, v in ((pod.get("spec") or {}).get("nodeSelector") or {}).items())


def feasible(pod: dict, node: dict) -> bool:
    """Everything but capacity."""
    return ready(node) and tolerates(pod, node) and selects(pod, node)


def _name(obj: dict) -> str:
    return (obj.get("metadata") or {}).get("name") or ""


def usage(pods: List[dict]) -> Dict[str, List[float]]:
    """node -> [CPU, memory, pods] requested by the unfinished pods bound there."""
    used: Dict[str, List[float]] = {}
    for p in pods:
        node = (p.get("spec") or {}).get("nodeName")
        if not node or (p.get("status") or {}).get("phase") in ("Succeeded", "Failed"):
            continue
        cpu, mem = requests(p)
        u = used.setdefault(node, [0.0, 0.0, 0.0])
        u[0] += cpu
        u[1] += mem
        u[2] += 1
    return used


def violations(pods: List[dict], nodes: List[dict]) -> List[str]:
    """What breaks the scheduling semantics, one finding a pod or node."""
    by_name = {_name(n): n for n in nodes}
    used = usage(pods)
    out = []
    for p in pods:
        node = (p.get("spec") or {}).get("nodeName")
        if not node:
            continue
        if node not in by_name:
            out.append(f"{_name(p)}: bound to {node}, which does not exist")
        elif not feasible(p, by_name[node]):
            out.append(f"{_name(p)}: bound to {node}, which is not Ready, has a taint "
                       "the pod does not tolerate, or lacks a label its nodeSelector asks")
    for name, (cpu, mem, count) in sorted(used.items()):
        if name in by_name:
            a_cpu, a_mem, a_pods = allocatable(by_name[name])
            if cpu > a_cpu + 1e-9 or mem > a_mem or count > a_pods:
                out.append(f"{name}: holds {count:.0f} pods, {cpu:g} CPU, {mem:.0f} bytes "
                           f"of memory, over its {a_pods:g}, {a_cpu:g}, {a_mem:.0f}")
    for p in pods:
        if (p.get("spec") or {}).get("nodeName") or \
                (p.get("metadata") or {}).get("deletionTimestamp"):
            continue
        cpu, mem = requests(p)
        for n in nodes:
            a_cpu, a_mem, a_pods = allocatable(n)
            u_cpu, u_mem, u_pods = used.get(_name(n), (0.0, 0.0, 0))
            # the sums of CPU are of floats: a node counts as full where a
            # rounding in either direction would make it so
            if feasible(p, n) and u_cpu + cpu <= a_cpu - 1e-9 and u_mem + mem <= a_mem \
                    and u_pods + 1 <= a_pods:
                out.append(f"{_name(p)}: left unbound, though {_name(n)} could take it")
                break
    return out


def bind_violations(pods: List[dict], nodes: List[dict]) -> int:
    """How many of ``violations`` there are: 0 in a sound run."""
    return len(violations(pods, nodes))
