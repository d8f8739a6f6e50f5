"""``generators/informed_churn.py`` on ``test_churn_generator.py``'s fake
apiserver: with ``informers`` 0 it sends, seed for seed, what ``churn`` sends
but for the label ``app=roll-<i mod scoped>`` on the rolling pods; who each
restart of a window takes; the files of the cell hold the issue's numbers."""

import json
import os
import random
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import generators, run  # noqa: E402
from benchmarks.generators import churn, informed_churn  # noqa: E402
from benchmarks.harness.cluster import Failed  # noqa: E402
from benchmarks.harness.watch import Watcher  # noqa: E402
from benchmarks.tests.test_churn_generator import PARAMS, SIZES, FakeApiserver  # noqa: E402

INFORMED = {**PARAMS, "informers": 0, "cluster_wide": 2, "scoped": 4, "page_size": 20,
            "restart_every_s": 2}


def play(generator, params, rounds=12):
    """warm, ``rounds`` rounds of the window's loop, settle: every request."""
    watcher = Watcher(client=None)
    api = FakeApiserver(watcher)
    load = generators.Load(api, watcher, dict(SIZES), dict(params), seed=3500000031,
                           log=lambda _m: None)
    generator.warm(load)
    for _ in range(rounds):
        load.stream.round(in_window=True)
    generator.settle(load, time.monotonic())
    return api, load


def test_with_no_informer_it_sends_what_churn_sends_but_for_the_label():
    plain, plain_load = play(churn, PARAMS)
    informed, load = play(informed_churn, INFORMED)
    assert load.informers is None
    assert informed.requests[:2] == plain.requests[:2]  # standing pods and crash-loopers
    assert load.stream.order == plain_load.stream.order and load.stream.next > 64
    # name for name the same stream, as far as both got (the warm loop goes by the clock)
    made = [n for n in load.created if n.startswith("roll-")]
    twin = [n for n in plain_load.created if n.startswith("roll-")]
    both = min(len(made), len(twin))
    assert both > 64 and made[:both] == twin[:both]
    for name, pod in informed.pods.items():
        twin = plain.pods.get(name)
        if twin is None:
            continue
        if name.startswith("roll-"):
            i = int(name[5:])
            assert pod["metadata"].pop("labels") == {"app": f"roll-{i % 4}"}
        assert pod == twin, name
    assert sum(1 for n in informed.pods if n.startswith("roll-") and n in plain.pods) > 64
    # the label follows the node slot: nodes mod scoped == 0 in the cell and here
    for name, pod in informed.pods.items():
        if name.startswith("roll-"):
            slot = load.stream.order.index(pod["spec"]["nodeName"])
            assert int(name[5:]) % 4 == slot % 4


def test_who_a_windows_restarts_take():
    params = {"informers": 30, "cluster_wide": 10, "scoped": 20}
    order = informed_churn.restart_order(random.Random(1), params, 10)
    assert len(order) == 10
    wide = [j for k, j in enumerate(order) if k % 3 == 0]
    scoped = [j for k, j in enumerate(order) if k % 3]
    assert len(wide) == 4 and all(j < 10 for j in wide) and len(set(wide)) == 4
    assert len(scoped) == 6 and all(10 <= j < 30 for j in scoped) and len(set(scoped)) == 6
    # one seed, one order; another seed, another
    assert order == informed_churn.restart_order(random.Random(1), params, 10)
    assert order != informed_churn.restart_order(random.Random(2), params, 10)
    # a class used up starts again; a population of one class only is taken as it is
    small = {"informers": 3, "cluster_wide": 1, "scoped": 2}
    assert [j for k, j in enumerate(informed_churn.restart_order(random.Random(1), small, 7))
            if k % 3 == 0] == [0, 0, 0]
    assert set(informed_churn.restart_order(
        random.Random(1), {"informers": 2, "cluster_wide": 2, "scoped": 0}, 4)) == {0, 1}
    assert informed_churn.selector_of(9, params) is None
    assert informed_churn.selector_of(10, params) == ("app", "roll-0")
    assert informed_churn.selector_of(29, params) == ("app", "roll-19")


def test_the_schedule_restarts_while_a_whole_period_is_left(monkeypatch):
    calls = []

    class Lone(informed_churn.Informers):
        def __init__(self):
            self.restarts = 0

        def restart(self, j):
            calls.append((round(time.monotonic() - t0, 1), j))

    t0 = time.monotonic()
    Lone().restart_on_schedule(list(range(10)), t0, t0 + 0.51, 0.05)
    assert [j for _t, j in calls] == list(range(10))
    assert calls[0][0] == 0.0 and 0.4 <= calls[-1][0] <= 0.5


def test_a_population_that_does_not_add_up_is_refused():
    watcher = Watcher(client=None)
    load = generators.Load(FakeApiserver(watcher), watcher, dict(SIZES),
                           {**INFORMED, "informers": 5}, seed=1, log=lambda _m: None)
    with pytest.raises(Failed, match="cluster_wide \\+ scoped"):
        informed_churn.Informers(load, "http://127.0.0.1:1")


def test_the_files_of_the_cell_hold_the_issues_numbers():
    bench, cell = run.find_cell("watched-churn")
    assert cell == {**cell, "config": "informers-1k-100k", "traffic": "informed-churn", "chips": 1}
    assert bench["workloads"][-1] is cell and len(cell["why"]) <= 200
    traffic = run.load_json("traffic", "informed-churn.json")
    churns = run.load_json("traffic", "churn.json")
    assert traffic["kind"] == "informed_churn"
    assert traffic["params"] == {**churns["params"], "informers": 30, "cluster_wide": 10,
                                 "scoped": 20, "page_size": 500, "restart_every_s": 5}
    assert churns["params"]["rolling_pods"] == 4000  # churn.json's seven, unchanged
    config = run.load_json("configs", "informers-1k-100k.json")
    parent = run.load_json("configs", "general-chaos-1k-100k.json")
    assert config["reduced"] == [] and config["reference"] == "informer_general_stages"
    for key in ("sizes", "create_cluster_args", "kwok_configuration", "stages", "node_ip",
                "lease", "soa"):
        assert config[key] == parent[key], key
    assert set(parent["assumed"]) < set(config["assumed"])
    assert {"informers", "informer_processes", "informer_store", "informer_client_name",
            "watch_timeoutSeconds", "page_size"} <= set(config["assumed"])
    assert len(config["guarantees"]) == len(parent["guarantees"]) + 4
    assert [g[:3] for g in config["guarantees"][-4:]] == ["g1:", "g2:", "g3:", "g4:"]
    assert config["informers"] == {**config["informers"], "count": 30, "cluster_wide": 10,
                                   "scoped": 20, "page_size": 500, "processes": 3}
    entry = next(c for c in bench["configs"] if c["name"] == "informers-1k-100k")
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == [] and bench["configs"][-1] is entry
    assert informed_churn.PROCESSES == 3 and informed_churn.LABEL == "app"
