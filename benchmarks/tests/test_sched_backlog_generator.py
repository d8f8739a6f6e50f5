"""``generators/sched_backlog.py`` on a fake apiserver that plays the
scheduler: one template for every pod, standing pods bound and the rest
unbound, the closed loop's backlog, what counts as inside the window, and
``settle`` holding the result to the scheduling reference."""

import copy
import os
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import generators  # noqa: E402
from benchmarks.generators import sched_backlog  # noqa: E402
from benchmarks.harness.cluster import Failed  # noqa: E402
from benchmarks.harness.watch import Watcher  # noqa: E402

PARAMS = {"standing_pods": 4, "init_pods": 3, "backlog": 10, "bulk_size": 4,
          "max_pods": 1000, "cpu_milli": 100, "memory_mi": 500, "clients": 1}
SIZES = {"nodes": 4}


def ready_node(i: int) -> dict:
    return {"kind": "Node", "metadata": {"name": f"node-{i}"},
            "spec": {"taints": [{"key": "kwok.x-k8s.io/node", "value": "fake",
                                 "effect": "NoSchedule"}]},
            "status": {"allocatable": {"cpu": "32", "memory": "256Gi", "pods": "110"},
                       "conditions": [{"type": "Ready", "status": "True"}]}}


class FakeApiserver:
    """Acknowledges every create; binds an unbound pod round-robin and lets
    the watcher see it Running ``rate`` pods a call of ``play`` (all at once
    where ``rate`` is None)."""

    def __init__(self, watcher, rate=None):
        self.watcher = watcher
        self.rate = rate
        self.requests = []
        self.pods = {}
        self.queue = []
        self.rr = 0

    def bulk(self, ops):
        self.requests.append([op["data"] for op in ops])
        for op in ops:
            p = copy.deepcopy(op["data"])
            self.pods[p["metadata"]["name"]] = p
            if p["spec"].get("nodeName"):
                self.watcher.running_at[p["metadata"]["name"]] = time.monotonic()
            else:
                self.queue.append(p)
        if self.rate is None:
            self.play(len(self.queue))
        return [{"status": "ok"}] * len(ops)

    def play(self, count: int) -> None:
        for p in self.queue[:count]:
            p["spec"]["nodeName"] = f"node-{self.rr % SIZES['nodes']}"
            self.rr += 1
            self.watcher.running_at[p["metadata"]["name"]] = time.monotonic()
        del self.queue[:count]

    def list_paged(self, kind, namespace=None, page_size=None):
        return list(self.pods.values()), 1

    def list(self, kind, **_kw):
        return [ready_node(i) for i in range(SIZES["nodes"])], 1


@pytest.fixture
def load():
    watcher = Watcher(client=None)  # never started: the fake feeds what it "saw"
    return generators.Load(FakeApiserver(watcher), watcher, dict(SIZES), dict(PARAMS),
                           seed=4200000001, log=lambda _m: None)


def test_warm_binds_the_standing_pods_and_leaves_the_init_pods_to_the_scheduler(load):
    sched_backlog.warm(load)
    standing, init = load.client.requests
    assert sorted(p["spec"]["nodeName"] for p in standing) == [f"node-{i}" for i in range(4)]
    assert [p["metadata"]["name"] for p in init] == ["init-0", "init-1", "init-2"]
    assert all("nodeName" not in load.created[p["metadata"]["name"]]["spec"] for p in init)
    # one template: a standing pod and a pod the scheduler binds differ in nodeName alone
    a = dict(standing[0]["spec"])
    b = dict(load.created["init-0"]["spec"])
    a.pop("nodeName")
    assert a == b
    assert a["containers"][0]["resources"] == {"requests": {"cpu": "100m",
                                                            "memory": "500Mi"}}
    assert a["tolerations"][0]["key"] == "kwok.x-k8s.io/node"
    assert load.in_window == []


def test_the_loop_keeps_the_backlog_and_posts_whole_bulks(load):
    api = load.client
    sched_backlog.warm(load)
    api.rate = 3  # the scheduler falls behind from here on
    warm_requests = len(api.requests)
    t0 = time.monotonic()
    stop = t0 + 0.6

    def scheduler():
        while time.monotonic() < stop:
            api.play(api.rate)
            time.sleep(0.05)

    th = threading.Thread(target=scheduler)
    th.start()
    sched_backlog.run(load, t0, stop)
    th.join()
    bulks = api.requests[warm_requests:]
    assert bulks and all(len(b) == PARAMS["bulk_size"] for b in bulks)
    assert all("nodeName" not in load.created[p["metadata"]["name"]]["spec"]
               for b in bulks for p in b)
    names = [p["metadata"]["name"] for b in bulks for p in b]
    assert names == [f"sched-{i}" for i in range(len(names))] == load.in_window
    # never more than backlog + one bulk - 1 outstanding; and it did fall behind
    assert len(names) - sum(1 for n in names if n in load.watcher.running_at) \
        <= PARAMS["backlog"] + PARAMS["bulk_size"] - 1
    assert len(api.queue) > 0


def test_max_pods_stops_the_loop(load):
    load.params["max_pods"] = 6
    sched_backlog.warm(load)
    t0 = time.monotonic()
    sched_backlog.run(load, t0, t0 + 0.3)
    assert [len(b) for b in load.client.requests[2:]] == [4, 2]


def test_settle_holds_the_result_to_the_reference(load):
    load.params["max_pods"] = 40  # the fake binds at once: the loop would go on to the guard
    sched_backlog.warm(load)
    t0 = time.monotonic()
    sched_backlog.run(load, t0, t0 + 0.2)
    sched_backlog.settle(load, t0 + 0.2)  # sound: nothing raised
    # a bind to a node that is not there, and one pod left unbound
    load.client.pods["sched-0"]["spec"]["nodeName"] = "node-99"
    del load.client.pods["sched-1"]["spec"]["nodeName"]
    with pytest.raises(Failed, match="2 pods or nodes break the scheduling semantics"):
        sched_backlog.settle(load, t0 + 0.2)
