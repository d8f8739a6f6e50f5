"""``generators/burst_cycle.py`` on a fake apiserver and the real watching
client's waits: the order of a cycle, what counts as inside the window,
``settle`` taking the open cycle to its end, and a wait that runs out
leaving evidence for the check and no exception."""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import generators  # noqa: E402
from benchmarks.generators import burst_cycle  # noqa: E402
from benchmarks.harness.cluster import Failed  # noqa: E402
from benchmarks.harness.watch import Watcher  # noqa: E402

PARAMS = {"standing_pods": 10, "burst_pods": 6, "bulk_size": 6, "clients": 1, "warm_cycles": 2}
SIZES = {"nodes": 4}


class FakeApiserver:
    """Acknowledges every op and lets the watcher see its effect at once,
    unless the test holds that kind of event back."""

    def __init__(self, watcher):
        self.watcher = watcher
        #: (verb, names) a request
        self.requests = []
        self.hold = set()
        #: name -> the pod as created
        self.pods = {}

    def bulk(self, ops):
        verb = ops[0]["verb"]
        names = [op["data"]["metadata"]["name"] if verb == "create" else op["name"]
                 for op in ops]
        self.requests.append((verb, names))
        if verb == "create":
            self.pods.update((op["data"]["metadata"]["name"], op["data"]) for op in ops)
        seen = self.watcher.running_at if verb == "create" else self.watcher.deleted_at
        if verb not in self.hold:
            seen.update((n, time.monotonic()) for n in names)
        return [{"status": "ok"}] * len(ops)

    def release(self, verb):
        self.hold.discard(verb)
        seen = self.watcher.running_at if verb == "create" else self.watcher.deleted_at
        for v, names in self.requests:
            if v == verb:
                seen.update((n, time.monotonic()) for n in names if n not in seen)


@pytest.fixture
def load():
    watcher = Watcher(client=None)  # never started: the fake feeds what it "saw"
    api = FakeApiserver(watcher)
    return generators.Load(api, watcher, dict(SIZES), dict(PARAMS), seed=2800000001,
                           log=lambda _m: None)


def cycles_of(requests):
    """The requests after the standing pods', as (verb, cycle number)."""
    out = []
    for verb, names in requests:
        if names[0].startswith("burst-"):
            assert len({n.split("-")[1] for n in names}) == 1
            out.append((verb, int(names[0].split("-")[1])))
    return out


def test_a_cycle_is_create_running_delete_gone_and_the_next_at_once(load):
    api = load.client
    burst_cycle.warm(load)
    warm_requests = len(api.requests)
    t0 = time.monotonic()
    burst_cycle.run(load, t0, t0 + 0.2)
    burst_cycle.settle(load, t0 + 0.2)

    standing = api.requests[:2]  # 10 pods in bulks of 6
    assert [v for v, _n in standing] == ["create", "create"]
    names = [n for _v, ns in standing for n in ns]
    assert names == [f"standing-{i}" for i in range(10)]
    # no finalizer, round-robin over all nodes in plain order
    assert all("finalizers" not in api.pods[n]["metadata"] for n in names)
    assert [api.pods[n]["spec"]["nodeName"] for n in names] == [f"node-{i % 4}" for i in range(10)]

    order = cycles_of(api.requests)
    n_cycles = load.cycles.count
    assert n_cycles > 4 and load.cycles.phase == "idle"
    assert order == [(v, k) for k in range(n_cycles) for v in ("create", "delete")]
    # the two warm cycles are set-up; every pod of a later one is of the window
    assert cycles_of(api.requests[:warm_requests]) == [("create", 0), ("delete", 0),
                                                       ("create", 1), ("delete", 1)]
    assert load.in_window == [f"burst-{k}-{i}" for k in range(2, n_cycles) for i in range(6)]
    bursts = [n for n in load.created if n.startswith("burst-")]
    assert len(bursts) == len(set(bursts)) == 6 * n_cycles
    assert load.deleted == set(bursts) and not load.refused
    assert all(api.pods[n]["metadata"]["finalizers"] == [burst_cycle.FINALIZER] for n in bursts)
    # bound round-robin in the order the seed drew, each burst going on where the last stopped
    drawn = load.cycles.nodes
    assert sorted(drawn) == [f"node-{i}" for i in range(4)]
    assert [api.pods[n]["spec"]["nodeName"] for n in bursts] == [
        drawn[j % 4] for j in range(len(bursts))]
    # one seed, one order
    again = generators.Load(api, load.watcher, dict(SIZES), dict(PARAMS), seed=2800000001,
                            log=lambda _m: None)
    assert burst_cycle.Cycles(again).nodes == drawn


def test_settle_finishes_the_cycle_the_window_left_open(load):
    api = load.client
    burst_cycle.warm(load)
    api.hold.add("create")  # the next burst is not seen Running inside the window
    t0 = time.monotonic()
    burst_cycle.run(load, t0, t0 + 0.1)
    assert time.monotonic() - t0 < 1.0  # run returns as the window closes
    assert load.cycles.phase == "created" and load.cycles.count == 3
    assert api.requests[-1][0] == "create" and not any(n in load.deleted for n in load.in_window)
    api.release("create")
    burst_cycle.settle(load, t0 + 0.1)
    # Running, delete, gone; and no cycle begun after the close
    assert load.cycles.phase == "idle" and load.cycles.count == 3
    assert api.requests[-1] == ("delete", load.in_window)
    assert set(load.in_window) <= load.deleted
    assert all(n in load.watcher.deleted_at for n in load.in_window)


@pytest.mark.parametrize("held,number", [("create", "never_running"),
                                         ("delete", "never_deleted")])
def test_a_wait_that_runs_out_leaves_evidence_and_raises_nothing(load, held, number,
                                                                 monkeypatch):
    monkeypatch.setattr(burst_cycle, "SETTLE_S", 0.2)
    api = load.client
    burst_cycle.warm(load)
    api.hold.add(held)
    t0 = time.monotonic()
    burst_cycle.run(load, t0, t0 + 0.1)
    burst_cycle.settle(load, t0 + 0.1)
    assert 0.3 <= time.monotonic() - t0 < 2.0
    assert load.cycles.count == 3  # the loop ended with the cycle that stuck
    w = load.watcher
    counts = {  # as harness/check.py counts them
        "never_running": sum(1 for n in load.in_window if n not in w.running_at),
        "never_deleted": sum(1 for n in load.deleted if n not in w.deleted_at),
    }
    assert counts[number] == 6
    assert counts == {**{"never_running": 0, "never_deleted": 0}, number: 6}


def test_set_up_gives_up_on_a_warm_cycle_that_does_not_close(load, monkeypatch):
    monkeypatch.setattr(burst_cycle, "WARM_CYCLE_S", 0.1)
    load.client.hold.add("delete")
    with pytest.raises(Failed, match="warm cycle 0"):
        burst_cycle.warm(load)


def test_the_traffic_file_holds_the_parameters_of_the_cell():
    with open(os.path.join(ROOT, "benchmarks", "traffic", "burst-1k.json"),
              encoding="utf-8") as f:
        traffic = json.load(f)
    assert traffic["kind"] == "burst_cycle"
    assert traffic["params"] == {"standing_pods": 5000, "burst_pods": 1000, "bulk_size": 1000,
                                 "clients": 1, "warm_cycles": 2}
