"""``burst_cycle``: create a burst, wait for it, tear it down, go again.
One closed-loop client with no think time: bulk-create ``burst_pods`` pods
that carry a finalizer, wait until the watcher has seen every one Running,
bulk-delete the same names, wait until the watcher has seen every DELETED,
and begin the next cycle at once.  A cycle begins only before the window
closes; ``settle`` takes the open one to its end.

Parameters: ``standing_pods`` (created before the window without a
finalizer, round-robin over all nodes, and waited to Running: the
population the bursts come and go beside), ``burst_pods``, ``bulk_size``
(a burst goes out in bulks of so many: one where the two are equal),
``warm_cycles`` (whole cycles before the window), ``clients`` (1: the
cycles of one client follow each other).  A burst is bound round-robin over
all nodes in an order the seed draws, each going on where the last one
stopped; names are unique over the run (``burst-<cycle>-<i>``).

This apiserver removes a pod without a finalizer at once, so the finalizer
stands for the kubelet's graceful termination: the pod stays, terminating,
until the ``pod-delete`` stage has emptied its finalizers."""

from __future__ import annotations

import time

from ..harness.cluster import Failed
from . import SETTLE_S, pod

FINALIZER = "kwok.x-k8s.io/fake"
#: seconds a warm cycle may take before set-up gives up
WARM_CYCLE_S = 120.0
POLL_S = 0.01


def _nodes(load) -> list:
    return [f"node-{i}" for i in range(load.sizes["nodes"])]


def _bulks(load, items: list):
    size = load.params["bulk_size"]
    return (items[lo:lo + size] for lo in range(0, len(items), size))


class Cycles:
    """The client's place in its loop: which cycle is open and how far it
    has come, so that ``run`` can stop at the close of the window and
    ``settle`` go on from there."""

    def __init__(self, load):
        self.load = load
        self.nodes = _nodes(load)
        load.rng.shuffle(self.nodes)
        #: index into ``nodes`` at which the next burst starts
        self.at = 0
        #: cycles begun
        self.count = 0
        #: the open cycle's pods that are still to be waited for
        self.names: list = []
        #: "idle", "created" (waiting for Running) or "deleted" (for DELETED)
        self.phase = "idle"

    def begin(self, in_window: bool) -> None:
        n = self.load.params["burst_pods"]
        pods = [pod(f"burst-{self.count}-{i}", self.nodes[(self.at + i) % len(self.nodes)],
                    FINALIZER) for i in range(n)]
        self.at = (self.at + n) % len(self.nodes)
        self.count += 1
        for bulk in _bulks(self.load, pods):
            self.load.bulk_create(bulk, in_window)
        self.names = [q["metadata"]["name"] for q in pods
                      if q["metadata"]["name"] in self.load.created]
        self.phase = "created"

    def advance(self, until: float) -> bool:
        """Take the open cycle as far as it gets by ``until``; True once it
        is closed.  A wait that runs out leaves the cycle where it is and
        raises nothing: the check counts what never came."""
        w = self.load.watcher
        if self.phase == "created":
            if not w.wait_running(self.names, max(until - time.monotonic(), 0.0), POLL_S):
                return False
            for bulk in _bulks(self.load, self.names):
                self.load.bulk_delete(bulk)
            self.names = [m for m in self.names if m in self.load.deleted]
            self.phase = "deleted"
        if self.phase == "deleted":
            if not w.wait_deleted(self.names, max(until - time.monotonic(), 0.0), POLL_S):
                return False
            self.phase = "idle"
        return True


def warm(load) -> None:
    if load.params.get("clients", 1) != 1:
        raise Failed("burst_cycle has one client: its cycles follow each other")
    nodes = _nodes(load)
    standing = [pod(f"standing-{i}", nodes[i % len(nodes)])
                for i in range(load.params["standing_pods"])]
    for bulk in _bulks(load, standing):
        load.bulk_create(bulk, in_window=False)
    if not load.watcher.wait_running(list(load.created), 300, poll=0.05):
        raise Failed("standing pods did not all reach Running in 300 s")
    # the Load is what the harness hands to run and settle: the loop's place rides on it
    load.cycles = cycles = Cycles(load)
    for k in range(load.params["warm_cycles"]):
        cycles.begin(in_window=False)
        if not cycles.advance(time.monotonic() + WARM_CYCLE_S):
            raise Failed(f"warm cycle {k} did not close in {WARM_CYCLE_S:.0f} s "
                         f"(stuck {cycles.phase})")


def run(load, t0: float, t1: float) -> None:
    cycles = load.cycles
    while time.monotonic() < t1:
        if cycles.phase == "idle":
            cycles.begin(in_window=True)
        if not cycles.advance(t1):
            return


def settle(load, t1: float) -> None:
    load.cycles.advance(t1 + SETTLE_S)
