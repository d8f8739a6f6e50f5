"""``churn``: a population kept alive under a rolling restart, beside a
standing population and a set of crash-looping pods.

Before the window ``standing_pods`` plain pods (round-robin over all
nodes) and ``crashloop_pods`` pods that carry the chaos stage's opt-in
label (``pod-container-running-failed.stage.kwok.x-k8s.io: "true"``, one
on node ``i mod nodes``) are created in bulks of ``bulk_size`` and waited
to Running; the crash-loopers flip Running -> Failed -> Running from then
on by themselves.  Then one closed-loop client keeps ``rolling_pods`` pods
of a stream alive, as a ReplicaSet under a rolling restart does: every
``poll_s`` it bulk-deletes the rolling pods the watcher has newly seen
Running and bulk-creates one new pod of the stream for every rolling pod
the watcher has newly seen DELETED (a round's creates or deletes above
``bulk_size`` go in more than one bulk).  Pod ``i`` of the stream is
``roll-<i>`` on node ``order[i mod nodes]`` (``order`` drawn from the
seed), of shape ``SHAPES[(i // nodes) mod 4]``; the client gives it no
finalizer: the stage set's ``pod-create`` adds its own.  The loop runs
``warm_s`` before the window (``clients`` is 1: one loop).

``settle`` creates and deletes nothing more of the stream: it waits until
every created pod was seen Running and every deleted one DELETED, then
deletes the crash-loopers, as an operator does with ``kubectl delete pod
-l``, and waits for their DELETED.  The rolling pods that are Running then
stay alive for the final LIST.  A wait that runs out raises nothing: the
check counts what never came."""

from __future__ import annotations

import time

from ..harness.cluster import Failed
from . import SETTLE_S, pod

CHAOS_LABEL = "pod-container-running-failed.stage.kwok.x-k8s.io"
#: a rolling pod's shape by ``(i // nodes) mod 4``: init containers it has
SHAPES = ((), (), (), ({"name": "init", "image": "fake-init"},))
#: seconds the crash-loopers' delete may take where the window's own wait
#: has used up the minute (remove-finalizer 1-6 s, pod-delete 1 s)
CRASHLOOP_GONE_S = 30.0
POLL_WAIT_S = 0.05


def _nodes(load) -> list:
    return [f"node-{i}" for i in range(load.sizes["nodes"])]


def _bulks(load, items: list):
    size = load.params["bulk_size"]
    return (items[lo:lo + size] for lo in range(0, len(items), size))


def rolling_pod(i: int, order: list) -> dict:
    p = pod(f"roll-{i}", order[i % len(order)])
    inits = SHAPES[(i // len(order)) % len(SHAPES)]
    if inits:
        p["spec"]["initContainers"] = [dict(c) for c in inits]
    return p


def crashloop_pod(i: int, nodes: list) -> dict:
    p = pod(f"crashloop-{i}", nodes[i % len(nodes)])
    p["metadata"]["labels"] = {CHAOS_LABEL: "true"}
    return p


class Stream:
    """The client's place in its loop, so that ``run`` goes on where
    ``warm`` stopped and ``settle`` knows what is still due."""

    def __init__(self, load):
        self.load = load
        self.order = _nodes(load)
        load.rng.shuffle(self.order)
        #: index of the next pod of the stream
        self.next = 0
        #: rolling pods created and not yet asked to go
        self.alive: set = set()
        #: rolling pods asked to go whose DELETED is still due
        self.going: set = set()

    def create(self, count: int, in_window: bool) -> None:
        pods = [rolling_pod(self.next + k, self.order) for k in range(count)]
        self.next += count
        for bulk in _bulks(self.load, pods):
            self.load.bulk_create(bulk, in_window)
        self.alive.update(q["metadata"]["name"] for q in pods
                          if q["metadata"]["name"] in self.load.created)

    def round(self, in_window: bool) -> None:
        """Delete what has newly turned Running, replace what is newly gone."""
        w = self.load.watcher
        running = sorted((n for n in self.alive if n in w.running_at),
                         key=lambda n: int(n[5:]))
        for bulk in _bulks(self.load, running):
            self.load.bulk_delete(bulk)
        acked = [n for n in running if n in self.load.deleted]
        self.alive.difference_update(acked)
        self.going.update(acked)
        gone = [n for n in self.going if n in w.deleted_at]
        self.going.difference_update(gone)
        # the population is held: a create the apiserver refused is made up
        # for here, under the stream's next name
        short = self.load.params["rolling_pods"] - len(self.alive) - len(self.going)
        if short > 0:
            self.create(short, in_window)

    def loop(self, until: float, in_window: bool) -> None:
        poll = self.load.params["poll_s"]
        while time.monotonic() < until:
            t = time.monotonic()
            self.round(in_window)
            time.sleep(max(min(t + poll, until) - time.monotonic(), 0.0))


def warm(load) -> None:
    if load.params.get("clients", 1) != 1:
        raise Failed("churn has one client: one loop keeps the population")
    nodes = _nodes(load)
    before = [pod(f"standing-{i}", nodes[i % len(nodes)])
              for i in range(load.params["standing_pods"])]
    before += [crashloop_pod(i, nodes) for i in range(load.params["crashloop_pods"])]
    for bulk in _bulks(load, before):
        load.bulk_create(bulk, in_window=False)
    if not load.watcher.wait_running(list(load.created), 300, poll=POLL_WAIT_S):
        raise Failed("standing and crash-looping pods did not all reach Running in 300 s")
    # the Load is what the harness hands to run and settle: the loop's place rides on it
    load.stream = stream = Stream(load)
    stream.loop(time.monotonic() + load.params["warm_s"], in_window=False)


def run(load, t0: float, t1: float) -> None:
    load.stream.loop(t1, in_window=True)


def settle(load, t1: float) -> None:
    w = load.watcher
    crashloopers = [n for n in load.created if n.startswith("crashloop-")]
    rolling = [n for n in load.created if n.startswith("roll-")]
    left = max(t1 + SETTLE_S - time.monotonic(), 0.0)
    deadline = time.monotonic() + left
    w.wait_running(rolling, left, poll=POLL_WAIT_S)
    w.wait_deleted([n for n in rolling if n in load.deleted],
                   max(deadline - time.monotonic(), 0.0), poll=POLL_WAIT_S)
    for bulk in _bulks(load, crashloopers):
        load.bulk_delete(bulk)
    w.wait_deleted([n for n in crashloopers if n in load.deleted],
                   max(deadline - time.monotonic(), CRASHLOOP_GONE_S), poll=POLL_WAIT_S)
