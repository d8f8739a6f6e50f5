"""``generators/churn.py`` on a fake apiserver and the real watching
client's waits: the population held at ``rolling_pods``, the stream's pods
covering every node x shape, what counts as inside the window, ``settle``
taking the crash-loopers away and leaving live rolling pods, and a wait that
runs out leaving evidence for the check and no exception."""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import generators  # noqa: E402
from benchmarks.generators import churn  # noqa: E402
from benchmarks.harness.cluster import Failed  # noqa: E402
from benchmarks.harness.watch import Watcher  # noqa: E402

PARAMS = {"standing_pods": 12, "crashloop_pods": 4, "rolling_pods": 16, "poll_s": 0.01,
          "bulk_size": 10, "clients": 1, "warm_s": 0.1}
SIZES = {"nodes": 4, "pods": 400}


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


@pytest.fixture
def load():
    watcher = Watcher(client=None)  # never started: the fake feeds what it "saw"
    api = FakeApiserver(watcher)
    return generators.Load(api, watcher, dict(SIZES), dict(PARAMS), seed=3200000001,
                           log=lambda _m: None)


def rolling(names):
    return [n for n in names if n.startswith("roll-")]


def test_the_population_is_held_and_the_stream_covers_every_node_and_shape(load):
    api = load.client
    churn.warm(load)
    # standing and crash-looping pods first, in bulks of bulk_size, plain node order
    before = [n for v, ns in api.requests[:2] for n in ns]
    assert before == [f"standing-{i}" for i in range(12)] + [f"crashloop-{i}" for i in range(4)]
    assert [api.pods[n]["spec"]["nodeName"] for n in before] == \
        [f"node-{i % 4}" for i in range(12)] + [f"node-{i}" for i in range(4)]
    for n in before:
        labels = api.pods[n]["metadata"].get("labels")
        assert labels == ({churn.CHAOS_LABEL: "true"} if n.startswith("crashloop-") else None)
    t0 = time.monotonic()
    churn.run(load, t0, t0 + 0.2)
    stream = load.stream
    made = rolling(load.created)
    assert made == [f"roll-{i}" for i in range(len(made))] and stream.next == len(made) > 64
    # the population: whatever was created and is not asked to go, plus what is going
    assert len(stream.alive) + len(stream.going) == 16
    assert len([n for n in made if n not in load.deleted]) == 16
    # pod i: node order[i mod nodes], shape (i // nodes) mod 4; no finalizer from the client
    assert sorted(stream.order) == [f"node-{i}" for i in range(4)]
    for i, n in enumerate(made):
        p = api.pods[n]
        assert p["spec"]["nodeName"] == stream.order[i % 4]
        assert p["spec"].get("initContainers") == (
            [{"name": "init", "image": "fake-init"}] if (i // 4) % 4 == 3 else None)
        assert "finalizers" not in p["metadata"] and "labels" not in p["metadata"]
    # the first rolling_pods pods cover every node x shape
    first = {(api.pods[n]["spec"]["nodeName"], (i // 4) % 4) for i, n in enumerate(made[:16])}
    assert len(first) == 16
    # what the loop made before the window is set-up, the rest is the window's
    assert load.in_window and load.in_window == made[-len(load.in_window):]
    assert not any(n in load.in_window for n in before) and not load.refused
    # a round's deletes are the pods newly seen Running, in bulks of bulk_size
    deletes = [ns for v, ns in api.requests if v == "delete"]
    assert all(len(ns) <= 10 for ns in deletes)
    assert [n for ns in deletes for n in ns] == [n for n in made if n in load.deleted]
    # one seed, one order
    again = generators.Load(api, load.watcher, dict(SIZES), dict(PARAMS), seed=3200000001,
                            log=lambda _m: None)
    assert churn.Stream(again).order == stream.order


def test_settle_takes_the_crash_loopers_away_and_leaves_live_rolling_pods(load):
    api = load.client
    churn.warm(load)
    t0 = time.monotonic()
    churn.run(load, t0, t0 + 0.1)
    api.hold.add("create")  # the last round's pods are not Running yet at the close
    load.stream.round(in_window=True)
    api.hold.discard("create")
    fresh = sorted(load.stream.alive)
    assert len(fresh) == 16 and not any(n in load.watcher.running_at for n in fresh)
    created, requests = len(load.created), len(api.requests)
    for n in fresh:  # the answers come while settle waits
        load.watcher.running_at[n] = time.monotonic()
    churn.settle(load, t0 + 0.1)
    # nothing more of the stream is created or deleted
    assert len(load.created) == created
    assert api.requests[requests:] == [("delete", [f"crashloop-{i}" for i in range(4)])]
    assert {n for n in load.deleted if n.startswith("crashloop-")} == \
        {f"crashloop-{i}" for i in range(4)}
    # the rolling pods that turned Running after the close stay alive
    assert sorted(n for n in rolling(load.created) if n not in load.deleted) == fresh
    assert all(n in load.watcher.deleted_at for n in load.deleted)
    assert not any(n.startswith("standing-") for n in load.deleted)


@pytest.mark.parametrize("held,number", [("create", "never_running"),
                                         ("delete", "never_deleted")])
def test_a_wait_that_runs_out_leaves_evidence_and_raises_nothing(load, held, number,
                                                                 monkeypatch):
    monkeypatch.setattr(churn, "SETTLE_S", 0.2)
    monkeypatch.setattr(churn, "CRASHLOOP_GONE_S", 0.2)
    api = load.client
    churn.warm(load)
    api.hold.add(held)
    t0 = time.monotonic()
    churn.run(load, t0, t0 + 0.1)
    churn.settle(load, t0 + 0.1)
    assert 0.3 <= time.monotonic() - t0 < 3.0
    w = load.watcher
    counts = {  # as harness/check.py counts them
        "never_running": sum(1 for n in load.in_window if n not in w.running_at),
        "never_deleted": sum(1 for n in load.deleted if n not in w.deleted_at),
    }
    assert counts[number] > 0
    assert counts == {**{"never_running": 0, "never_deleted": 0}, number: counts[number]}


def test_set_up_gives_up_where_the_standing_pods_do_not_turn_running(load, monkeypatch):
    load.client.hold.add("create")
    monkeypatch.setattr(load.watcher, "wait_running", lambda names, timeout, poll: False)
    with pytest.raises(Failed, match="did not all reach Running"):
        churn.warm(load)
    load.params["clients"] = 2
    with pytest.raises(Failed, match="one client"):
        churn.warm(load)


def test_the_traffic_file_holds_the_parameters_of_the_cell():
    with open(os.path.join(ROOT, "benchmarks", "traffic", "churn.json"), encoding="utf-8") as f:
        traffic = json.load(f)
    assert traffic["kind"] == "churn"
    assert traffic["params"] == {"standing_pods": 15000, "crashloop_pods": 1000,
                                 "rolling_pods": 4000, "poll_s": 0.25, "bulk_size": 1000,
                                 "clients": 1, "warm_s": 25}
