"""``wave``: a scale-up.  ``clients`` closed-loop clients post bulks of
creates, each its next as soon as its last is acknowledged, until the wave
is sent or the window ends.

Parameters: ``warm_pods`` (created before the window and waited to Running:
they warm the daemon's programs and are the standing population),
``warm_nodes`` (how many nodes they are spread over, round-robin; 0 = all),
``wave_pods``, ``bulk_size``, ``clients`` (1 where the file has none).  The wave goes round-robin over all nodes, in
an order the seed draws."""

from __future__ import annotations

import threading
import time

from ..harness.cluster import Failed
from . import SETTLE_S, pod


def _bulks(load, prefix: str, nodes: list, count: int):
    size = load.params["bulk_size"]
    pods = [pod(f"{prefix}-{i}", nodes[i % len(nodes)]) for i in range(count)]
    return [pods[lo:lo + size] for lo in range(0, count, size)]


def _nodes(load) -> list:
    return [f"node-{i}" for i in range(load.sizes["nodes"])]


def warm(load) -> None:
    nodes = _nodes(load)
    nodes = nodes[:load.params.get("warm_nodes") or len(nodes)]
    for b in _bulks(load, "warm", nodes, load.params["warm_pods"]):
        load.bulk_create(b, in_window=False)
    if not load.watcher.wait_running(list(load.created), 300, poll=0.05):
        raise Failed("warm pods did not all reach Running in 300 s")


def run(load, t0: float, t1: float) -> None:
    nodes = _nodes(load)
    load.rng.shuffle(nodes)
    bulks = iter(_bulks(load, "wave", nodes, load.params["wave_pods"]))

    def client() -> None:
        # next() on one iterator is atomic under the GIL: each bulk goes out once
        for b in bulks:
            if time.monotonic() >= t1:
                break
            load.bulk_create(b, in_window=True)

    threads = [threading.Thread(target=client, name=f"bench-create-{k}")
               for k in range(load.params.get("clients", 1))]
    for th in threads:
        th.start()
    for th in threads:
        th.join()


def settle(load, t1: float) -> None:
    left = t1 + SETTLE_S - time.monotonic()
    load.watcher.wait_running(load.in_window, max(left, 0.0), poll=0.25)
