"""The three per-layer metrics of the cell ``sched-5k``, layer
``scheduler``: ``create_to_bind_mean_s`` (the apiserver's store, create
commit to bind commit), ``bind_request_mean_ms`` (the scheduler's bind, a
``PATCH`` of a pod) and ``sched_create_to_running_p95_s`` (the watching
client).  Each is found by name through the harness's own discovery, names
``sched-5k`` alone, reads the expected value off two canned scrapes with
readers the harness had, is left out of the line, not 0, where the program
has no such series (a program without the bind histogram), and reads what the
run printed off a scrape recorded on the chip (a TPU v5e, the traced run of
``sched-5k``, 51 s; only the series these metrics read were kept)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.harness import promtext  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "sched-5k"
NEW = ("create_to_bind_mean_s", "bind_request_mean_ms", "sched_create_to_running_p95_s")

#: an apiserver's /metrics around a window of 50 s: 10,000 binds that waited
#: 12 s each from their create, their 10,000 PATCHes at 2 ms, beside the
#: Events' POSTs and the daemon's bulks
BEFORE = """
kwok_pod_binds_total 1000
kwok_pod_create_to_bind_seconds_sum 3000.0
kwok_pod_create_to_bind_seconds_count 1000
kwok_apiserver_request_duration_seconds_sum{verb="PATCH",kind="pods",level="controllers",shard="-"} 2.0
kwok_apiserver_request_duration_seconds_count{verb="PATCH",kind="pods",level="controllers",shard="-"} 1000
kwok_apiserver_request_duration_seconds_sum{verb="POST",kind="events",level="controllers",shard="-"} 3.0
kwok_apiserver_request_duration_seconds_count{verb="POST",kind="events",level="controllers",shard="-"} 1000
kwok_apiserver_request_duration_seconds_sum{verb="POST",kind="bulk",level="system",shard="-"} 40.0
kwok_apiserver_request_duration_seconds_count{verb="POST",kind="bulk",level="system",shard="-"} 300
"""
AFTER = """
kwok_pod_binds_total 11000
kwok_pod_create_to_bind_seconds_sum 123000.0
kwok_pod_create_to_bind_seconds_count 11000
kwok_apiserver_request_duration_seconds_sum{verb="PATCH",kind="pods",level="controllers",shard="-"} 22.0
kwok_apiserver_request_duration_seconds_count{verb="PATCH",kind="pods",level="controllers",shard="-"} 11000
kwok_apiserver_request_duration_seconds_sum{verb="POST",kind="events",level="controllers",shard="-"} 33.0
kwok_apiserver_request_duration_seconds_count{verb="POST",kind="events",level="controllers",shard="-"} 11000
kwok_apiserver_request_duration_seconds_sum{verb="POST",kind="bulk",level="system",shard="-"} 80.0
kwok_apiserver_request_duration_seconds_count{verb="POST",kind="bulk",level="system",shard="-"} 600
"""
CLIENT = {"create_to_running_p95_s": 14.5}
EXPECTED = {"create_to_bind_mean_s": 12.0, "bind_request_mean_ms": 2.0,
            "sched_create_to_running_p95_s": 14.5}


def scrape(t, text):
    return {"t": t, "kwok": [], "apiserver": list(promtext.iter_samples(text))}


@pytest.fixture(scope="module")
def bench():
    return run.find_cell(CELL)[0]


def test_the_entries_are_found_by_name_and_name_the_new_cell_alone(bench):
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = entries[name]
        spec = run.load_json("layer_metrics", f"{name}.json")
        assert m["workloads"] == [CELL] and m["layer"] == spec["layer"] == "scheduler"
        assert m["moves"] == spec["moves"] == "transitions_per_s"
        assert m["unit"] == spec["unit"] and m["better"] == "lower"
        assert spec["reader"]["kind"] in ("prom_delta", "client")
    readers = dict((m["name"], r) for m, r in run.layer_readers(bench, CELL))
    assert set(NEW) <= set(readers)
    for other in ("scaleup-100k", "burst-1k", "churn-100k", "watched-churn"):
        assert not set(NEW) & {m["name"] for m, _r in run.layer_readers(bench, other)}


def test_the_readers_read_the_canned_scrapes(bench):
    got = run.layer_values(bench, CELL, scrape(0.0, BEFORE), scrape(50.0, AFTER), {}, CLIENT)
    for name, want in EXPECTED.items():
        assert got[name]["value"] == pytest.approx(want), name


def test_a_program_without_the_bind_series_leaves_its_metric_out(bench):
    def parents(text):
        return "\n".join(ln for ln in text.splitlines() if not ln.startswith("kwok_pod_"))

    got = run.layer_values(bench, CELL, scrape(0.0, parents(BEFORE)),
                           scrape(50.0, parents(AFTER)), {}, {})
    assert "create_to_bind_mean_s" not in got and "sched_create_to_running_p95_s" not in got
    assert got["bind_request_mean_ms"]["value"] == pytest.approx(2.0)


def test_the_recorded_chip_scrape_reads_what_the_run_printed(bench):
    with open(os.path.join(HERE, "data", "scrapes_v5e_sched.json"), encoding="utf-8") as f:
        rec = json.load(f)
    client = {"create_to_running_p95_s": rec["printed"]["sched_create_to_running_p95_s"]}
    got = run.layer_values(bench, CELL, rec["before"], rec["after"], {}, client)
    for name in NEW:
        assert got[name]["value"] == pytest.approx(rec["printed"][name]), name
    # every bind of the window was one PATCH of the scheduler
    binds = promtext.delta(rec["before"]["apiserver"], rec["after"]["apiserver"],
                           "kwok_pod_binds_total", {})
    patches = promtext.delta(rec["before"]["apiserver"], rec["after"]["apiserver"],
                             "kwok_apiserver_request_duration_seconds_count",
                             {"verb": "PATCH", "kind": "pods"})
    assert binds == patches > 1000
