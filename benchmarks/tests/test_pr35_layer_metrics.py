"""The five per-layer metrics PR 35 appends for the cell ``watched-churn``:
``list_share`` and ``list_page_mean_ms`` (what the pages of the informers'
LISTs cost inside the store), ``watch_filter_share`` (what the watchers cost a
commit's locked pass), ``watch_evictions_in_window`` and
``list_snapshots_expired``.  Each is found by name through the harness's own
discovery, names ``watched-churn`` alone, reads the expected value off two
canned scrapes with readers the harness had, is left out of the line, not 0,
where the program has no such series (the parent; the eviction count it has),
and reads what the run printed off a scrape recorded on the chip (a TPU v5e,
the traced run of ``watched-churn``, seed 3500002701, 51 s; only the series
these metrics and the three ``watch_*`` ones read were kept, without
buckets)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.harness import promtext  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "watched-churn"
NEW = ("list_share", "list_page_mean_ms", "watch_filter_share", "watch_evictions_in_window",
       "list_snapshots_expired")
SERIES = ("kwok_list_page_seconds", "kwok_list_page_objects", "kwok_list_snapshots",
          "kwok_watch_filter_seconds")

#: an apiserver's /metrics around a window of 50 s: 400 pages of Pod LISTs in
#: 0.5 s, 2,000 commits that spent 0.25 s handing events to Pod watchers, one
#: watcher cut, no snapshot expired (Node pages and Lease commits beside them)
BEFORE = """
kwok_list_page_seconds_sum{kind="Pod"} 2.0
kwok_list_page_seconds_count{kind="Pod"} 1300
kwok_list_page_seconds_sum{kind="Node"} 0.5
kwok_list_page_seconds_count{kind="Node"} 4
kwok_list_page_objects_sum{kind="Pod"} 200000
kwok_list_page_objects_count{kind="Pod"} 1300
kwok_list_snapshots{outcome="opened"} 30
kwok_list_snapshots{outcome="served"} 30
kwok_list_snapshots{outcome="expired"} 0
kwok_watch_filter_seconds_sum{kind="Pod"} 1.0
kwok_watch_filter_seconds_count{kind="Pod"} 9000
kwok_watch_filter_seconds_sum{kind="Lease"} 0.5
kwok_watch_filter_seconds_count{kind="Lease"} 500
kwok_apiserver_watch_evictions_total 0
"""
AFTER = """
kwok_list_page_seconds_sum{kind="Pod"} 2.5
kwok_list_page_seconds_count{kind="Pod"} 1700
kwok_list_page_seconds_sum{kind="Node"} 0.5
kwok_list_page_seconds_count{kind="Node"} 4
kwok_list_page_objects_sum{kind="Pod"} 281000
kwok_list_page_objects_count{kind="Pod"} 1700
kwok_list_snapshots{outcome="opened"} 40
kwok_list_snapshots{outcome="served"} 40
kwok_list_snapshots{outcome="expired"} 0
kwok_watch_filter_seconds_sum{kind="Pod"} 1.25
kwok_watch_filter_seconds_count{kind="Pod"} 11000
kwok_watch_filter_seconds_sum{kind="Lease"} 0.75
kwok_watch_filter_seconds_count{kind="Lease"} 750
kwok_apiserver_watch_evictions_total 1
"""
EXPECTED = {"list_share": 1.0, "list_page_mean_ms": 1.25, "watch_filter_share": 0.5,
            "watch_evictions_in_window": 1.0, "list_snapshots_expired": 0.0}


def scrape(t, text):
    return {"t": t, "kwok": [], "apiserver": list(promtext.iter_samples(text))}


def parents(text):
    """The scrape a program without this PR's series gives."""
    return "\n".join(ln for ln in text.splitlines() if not ln.startswith(SERIES))


@pytest.fixture(scope="module")
def bench():
    return run.find_cell(CELL)[0]


def reader(name):
    return run.load_json("layer_metrics", f"{name}.json")


def test_the_entries_are_found_by_name_and_name_the_new_cell_alone(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    names = list(by_name)
    # appended, in order, after every entry that was there
    assert tuple(names[-5:]) == NEW and names[-6] == "watch_encode_share"
    layers = {m["layer"] for m in bench["per_layer"][:-5]}
    for name in NEW:
        m, spec = by_name[name], reader(name)
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) == (
            m["name"], m["unit"], m["layer"], m["moves"])
        assert m["workloads"] == [CELL] and m["moves"] == "transitions_per_s"
        assert m["better"] == "lower" and m["layer"] in layers  # no new layer name
        assert spec["reader"]["kind"] == "prom_delta"
        assert spec["reader"]["component"] == "apiserver" and len(spec["what"]) > 80
    assert {by_name[n]["layer"] for n in NEW[:2] + NEW[4:]} == {"apiserver and store commit"}
    assert {by_name[n]["layer"] for n in NEW[2:4]} == {"watch delivery"}
    assert [by_name[n]["unit"] for n in NEW] == ["%", "ms", "%", "1", "1"]
    # readers that were there
    hows = [reader(n)["reader"]["how"] for n in NEW]
    assert hows == ["sum_over_window", "sum_over_count", "sum_over_window", "count_delta",
                    "count_delta"]
    # the new cell reports them, the three older cells do not
    for cell in (w["name"] for w in bench["workloads"]):
        got = {e["name"] for e, _s in run.layer_readers(bench, cell)}
        assert (set(NEW) <= got) == (cell == CELL), cell
    # and the new cell reports every metric that names no cell, PR 34's two among them
    mine = {e["name"] for e, _s in run.layer_readers(bench, CELL)}
    assert {m["name"] for m in bench["per_layer"] if "workloads" not in m} <= mine
    assert {"watch_line_encoded_share", "watch_encode_share", "watch_lag_mean_ms",
            "device_idle_share", "tick_roofline"} <= mine


def test_two_canned_scrapes_read_the_expected_values(bench):
    before, after = scrape(100.0, BEFORE), scrape(150.0, AFTER)
    for name, want in EXPECTED.items():
        assert promtext.read(reader(name)["reader"], before, after) == pytest.approx(want), name
    got = run.layer_values(bench, CELL, before, after, {}, {})
    assert {k: got[k]["value"] for k in NEW} == pytest.approx(EXPECTED)
    # what PERF.md reads beside them: objects a page, seconds a commit
    b, a = before["apiserver"], after["apiserver"]
    assert promtext.delta(b, a, "kwok_list_page_objects_sum", {"kind": "Pod"}) / promtext.delta(
        b, a, "kwok_list_page_objects_count", {"kind": "Pod"}) == pytest.approx(202.5)


def test_a_program_without_the_series_leaves_the_metrics_out(bench):
    """The parent: its /metrics has the eviction count alone.  The line then
    carries that one and lacks the four others; none is a 0 it could not read."""
    before, after = scrape(100.0, parents(BEFORE)), scrape(150.0, parents(AFTER))
    for name in NEW:
        got = promtext.read(reader(name)["reader"], before, after)
        assert got == (1.0 if name == "watch_evictions_in_window" else None), name
    got = run.layer_values(bench, CELL, before, after, {}, {})
    assert set(NEW) & set(got) == {"watch_evictions_in_window"}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "scrapes_v5e_pr35.json"), encoding="utf-8") as f:
        data = json.load(f)
    for side in ("before", "after"):
        data[side]["apiserver"] = [tuple(s) for s in data[side]["apiserver"]]
    return data


def test_the_recorded_scrape_reads_what_the_run_printed(recorded):
    printed = recorded["printed"]
    for name in NEW + ("watch_line_encoded_share", "watch_encode_share", "watch_lag_mean_ms",
                       "api_bulk_mean_ms"):
        got = promtext.read(reader(name)["reader"], recorded["before"], recorded["after"])
        assert got == pytest.approx(printed[name], rel=1e-9), name
    # the acceptance's numbers: of ~16 Pod lines an event at most two are
    # encoded, no snapshot expired, the LIST path and the filter are small
    assert printed["watch_line_encoded_share"] < 20.0
    assert printed["list_snapshots_expired"] == 0
    assert 0 < printed["list_share"] < 10 and 0 < printed["watch_filter_share"] < 10
    b, a = recorded["before"]["apiserver"], recorded["after"]["apiserver"]
    pages = promtext.delta(b, a, "kwok_list_page_seconds_count", {"kind": "Pod"})
    objects = promtext.delta(b, a, "kwok_list_page_objects_sum", {"kind": "Pod"})
    # ten restarts: 4 cluster-wide LISTs of ~20,000 and 6 scoped ones of ~200,
    # each some 40 pages of 500 keys (and the harness's own read-back is outside)
    assert 300 <= pages <= 480 and 60_000 <= objects <= 100_000
    opened = promtext.delta(b, a, "kwok_list_snapshots", {"outcome": "opened"})
    served = promtext.delta(b, a, "kwok_list_snapshots", {"outcome": "served"})
    assert 9 <= opened <= 11 and abs(opened - served) <= 1
    written = promtext.delta(b, a, "kwok_watch_lines_total", {"kind": "Pod"})
    encoded = promtext.delta(b, a, "kwok_watch_lines_encoded_sum", {"kind": "Pod"})
    assert written / encoded > 10  # 5 + 10 + a twentieth of 20 streams a Pod event
