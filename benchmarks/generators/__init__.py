"""Traffic generators, one file each, found by a traffic file's ``kind``.

A generator is a module with three functions over a ``Load`` (below):
``warm(load)`` runs before the window and is set-up; ``run(load, t0, t1)``
offers the window's load and returns when nothing more is to be sent;
``settle(load, t1)`` waits, up to a minute past the close, for the answers
that are still due."""

from __future__ import annotations

import random
import time
from typing import Dict, List, Set

SETTLE_S = 60.0


def pod(name: str, node: str, finalizer: str = "") -> dict:
    """A pod as ``kwokctl scale pod`` renders it (``ctl/scale.py``'s
    template: one container, the kwok toleration), bound to ``node``."""
    meta = {"name": name, "namespace": "default"}
    if finalizer:
        meta["finalizers"] = [finalizer]
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": meta,
        "spec": {
            "nodeName": node,
            "containers": [{"name": "app", "image": "fake-image"}],
            "tolerations": [{"key": "kwok.x-k8s.io/node", "operator": "Exists",
                             "effect": "NoSchedule"}],
        },
    }


class Load:
    """What a generator works with and what it leaves for the check."""

    def __init__(self, client, watcher, sizes: dict, params: dict, seed: int, log):
        self.client = client
        self.watcher = watcher
        self.sizes = sizes
        self.params = params
        self.rng = random.Random(seed)
        self.log = log
        #: pod name -> the pod as sent, for every acknowledged create
        self.created: Dict[str, dict] = {}
        #: pod name -> instant its bulk request was sent
        self.sent_at: Dict[str, float] = {}
        #: names of acknowledged deletes
        self.deleted: Set[str] = set()
        #: operations the apiserver refused (result status not ok)
        self.refused: List[dict] = []
        #: names created inside the window
        self.in_window: List[str] = []

    def bulk_create(self, pods: List[dict], in_window: bool) -> None:
        t = time.monotonic()
        results = self.client.bulk([{"verb": "create", "data": p} for p in pods])
        for p, r in zip(pods, results):
            if r.get("status") != "ok":
                self.refused.append(r)
                continue
            name = p["metadata"]["name"]
            self.created[name] = p
            self.sent_at[name] = t
            if in_window:
                self.in_window.append(name)
        if len(results) != len(pods):
            self.refused.append({"status": f"{len(pods) - len(results)} results missing"})

    def bulk_delete(self, names: List[str]) -> None:
        results = self.client.bulk([{"verb": "delete", "kind": "Pod", "name": n,
                                     "namespace": "default"} for n in names])
        for n, r in zip(names, results):
            if r.get("status") == "ok":
                self.deleted.add(n)
            else:
                self.refused.append(r)
