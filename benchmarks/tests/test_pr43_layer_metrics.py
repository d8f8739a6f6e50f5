"""The per-layer metric of the scheduler's batched bind, layer ``scheduler``:
``binds_per_request``, the mean pods one request bound, Δsum/Δcount of the
apiserver's ``kwok_pod_binds_per_request`` (one observation a request that
bound any pod: a ``/bulk``'s or ``/txn``'s binds, 1 for a ``PATCH``, ``PUT``
or ``binding``).  It is found by name through the harness's own discovery,
names ``sched-5k`` alone, reads the expected value off two canned scrapes with
a reader the harness had, is left out of the line, not 0, where the program
has no such series (the parent), and reads a scrape recorded on the chip (a
TPU v5e, ``sched-5k``, 51 s; only the series the scheduler's metrics read were
kept)."""

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
NEW = "binds_per_request"
SERIES = "kwok_pod_binds_per_request"

#: an apiserver's /metrics around a window of 50 s: 10,000 binds of the
#: scheduler in 55 requests (39 bulks of 256 and 16 lone PATCHes), beside
#: the bind counter and its create-to-bind histogram
BEFORE = """
kwok_pod_binds_total 1000
kwok_pod_binds_per_request_sum 1000
kwok_pod_binds_per_request_count 4
kwok_pod_create_to_bind_seconds_sum 3000.0
kwok_pod_create_to_bind_seconds_count 1000
kwok_apiserver_request_duration_seconds_sum{verb="PATCH",kind="pods",level="controllers",shard="-"} 0.0
kwok_apiserver_request_duration_seconds_count{verb="PATCH",kind="pods",level="controllers",shard="-"} 0
"""
AFTER = """
kwok_pod_binds_total 11000
kwok_pod_binds_per_request_sum 11000
kwok_pod_binds_per_request_count 59
kwok_pod_create_to_bind_seconds_sum 13000.0
kwok_pod_create_to_bind_seconds_count 11000
kwok_apiserver_request_duration_seconds_sum{verb="PATCH",kind="pods",level="controllers",shard="-"} 0.032
kwok_apiserver_request_duration_seconds_count{verb="PATCH",kind="pods",level="controllers",shard="-"} 16
"""
EXPECTED = 10000 / 55


def scrape(t, text):
    return {"t": t, "kwok": [], "apiserver": list(promtext.iter_samples(text))}


def parents(text):
    """The scrape a program without the new series gives."""
    return "\n".join(ln for ln in text.splitlines() if not ln.startswith(SERIES))


@pytest.fixture(scope="module")
def bench():
    return run.find_cell(CELL)[0]


def test_the_entry_is_found_by_name_and_names_the_cell_alone(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # appended after every entry that was there
    assert list(by_name)[-1] == NEW
    m, spec = by_name[NEW], run.load_json("layer_metrics", f"{NEW}.json")
    assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) == (
        m["name"], m["unit"], m["layer"], m["moves"])
    assert (m["unit"], m["better"], m["source"]) == ("1", "higher", "program_counter")
    assert m["workloads"] == [CELL] and m["moves"] == "transitions_per_s"
    # the layer the scheduler's metrics already name, letter for letter
    assert m["layer"] == by_name["create_to_bind_mean_s"]["layer"] == "scheduler"
    # a reader the harness had
    assert spec["reader"] == {"kind": "prom_delta", "component": "apiserver",
                              "how": "sum_over_count", "series": SERIES}
    assert len(spec["what"]) > 200
    assert NEW in {e["name"] for e, _s in run.layer_readers(bench, CELL)}
    for other in (w["name"] for w in bench["workloads"] if w["name"] != CELL):
        assert NEW not in {e["name"] for e, _s in run.layer_readers(bench, other)}


def test_two_canned_scrapes_read_binds_over_requests(bench):
    before, after = scrape(100.0, BEFORE), scrape(150.0, AFTER)
    spec = run.load_json("layer_metrics", f"{NEW}.json")["reader"]
    assert promtext.read(spec, before, after) == pytest.approx(EXPECTED)
    got = run.layer_values(bench, CELL, before, after, {}, {})
    assert got[NEW] == {"value": pytest.approx(EXPECTED), "unit": "1"}
    # the histogram's sum is the bind counter
    b, a = before["apiserver"], after["apiserver"]
    assert promtext.delta(b, a, SERIES + "_sum", {}) == promtext.delta(
        b, a, "kwok_pod_binds_total", {})


def test_a_program_without_the_series_leaves_the_metric_out(bench):
    """The parent: its /metrics has the bind counter and no per-request
    histogram.  The line then lacks the metric; it does not carry a 0."""
    before, after = scrape(100.0, parents(BEFORE)), scrape(150.0, parents(AFTER))
    spec = run.load_json("layer_metrics", f"{NEW}.json")["reader"]
    assert promtext.read(spec, before, after) is None
    got = run.layer_values(bench, CELL, before, after, {}, {})
    assert NEW not in got
    assert got["create_to_bind_mean_s"]["value"] == pytest.approx(1.0)
    assert got["bind_request_mean_ms"]["value"] == pytest.approx(2.0)


def test_the_recorded_chip_scrape_reads_the_scheduler_s_runs(bench):
    """74,000 binds in 338 requests: every bind of the window rode a /bulk
    (no PATCH of a pod, no Event POST), about 219 a request."""
    with open(os.path.join(HERE, "data", "scrapes_v5e_pr43.json"), encoding="utf-8") as f:
        rec = json.load(f)
    got = run.layer_values(bench, CELL, rec["before"], rec["after"], {}, {})
    for name, want in rec["read"].items():
        assert got[name]["value"] == pytest.approx(want), name
    assert got[NEW]["value"] >= 20
    # the lone bind's request fell silent: no PATCH of a pod in the window
    assert "bind_request_mean_ms" not in got
    b, a = rec["before"]["apiserver"], rec["after"]["apiserver"]
    binds = promtext.delta(b, a, "kwok_pod_binds_total", {})
    assert promtext.delta(b, a, SERIES + "_sum", {}) == binds > 1000
