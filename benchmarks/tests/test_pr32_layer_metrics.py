"""The four per-layer metrics PR 32 appends for ``churn-100k``:
``event_post_share``, ``events_per_transition``, ``finalizer_row_share`` and
``crashloop_row_share``.  Each is found by name through the harness's own
discovery, names ``churn-100k`` alone, reads the expected value off two canned
scrapes with the readers the harness had, and is left out of the line, not
0, where the program has no such series (the parent).  The recorded pair is
of the chip (a TPU v5e, a traced run of ``churn-100k``, seed 3200002701, 51 s; only the series
the four metrics and the acceptance read were kept, without their buckets)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.harness import promtext  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "churn-100k"
NEW = ("event_post_share", "events_per_transition", "finalizer_row_share",
       "crashloop_row_share")

#: a kwok daemon's /metrics around a window of 50 s: 1,000 transitions played,
#: 400 of them finalizer rows with an Event each, sent in 8 requests that took
#: 4 s of the Pod player's thread, 150 rows of the CrashLoop path
BEFORE = """
kwok_stage_transitions_total{kind="Pod",backend="device"} 5000
kwok_stage_transitions_total{kind="Node",backend="device"} 1000
kwok_tick_stage_seconds_sum{kind="Pod",stage="event_post"} 2.0
kwok_tick_stage_seconds_count{kind="Pod",stage="event_post"} 10
kwok_tick_stage_seconds_sum{kind="Pod",stage="slow_commit"} 9.0
kwok_events_recorded_sum{kind="Pod",outcome="created"} 2000
kwok_events_recorded_count{kind="Pod",outcome="created"} 10
kwok_stage_fired_rows_sum{kind="Pod",stage="pod-create",path="slow"} 1000
kwok_stage_fired_rows_sum{kind="Pod",stage="pod-remove-finalizer",path="slow"} 1000
kwok_stage_fired_rows_sum{kind="Pod",stage="pod-ready",path="batch"} 2500
kwok_stage_fired_rows_sum{kind="Pod",stage="pod-container-running-failed",path="batch"} 500
kwok_stage_fired_rows_sum{kind="Node",stage="node-initialize",path="batch"} 1000
"""
AFTER = """
kwok_stage_transitions_total{kind="Pod",backend="device"} 6000
kwok_stage_transitions_total{kind="Node",backend="device"} 1000
kwok_tick_stage_seconds_sum{kind="Pod",stage="event_post"} 6.0
kwok_tick_stage_seconds_count{kind="Pod",stage="event_post"} 18
kwok_tick_stage_seconds_sum{kind="Pod",stage="slow_commit"} 20.0
kwok_events_recorded_sum{kind="Pod",outcome="created"} 2380
kwok_events_recorded_count{kind="Pod",outcome="created"} 18
kwok_events_recorded_sum{kind="Pod",outcome="aggregated"} 15
kwok_events_recorded_count{kind="Pod",outcome="aggregated"} 3
kwok_events_recorded_sum{kind="Pod",outcome="dropped"} 5
kwok_events_recorded_count{kind="Pod",outcome="dropped"} 1
kwok_stage_fired_rows_sum{kind="Pod",stage="pod-create",path="slow"} 1210
kwok_stage_fired_rows_sum{kind="Pod",stage="pod-remove-finalizer",path="slow"} 1185
kwok_stage_fired_rows_sum{kind="Pod",stage="pod-remove-finalizer",path="batch"} 5
kwok_stage_fired_rows_sum{kind="Pod",stage="pod-ready",path="batch"} 2950
kwok_stage_fired_rows_sum{kind="Pod",stage="pod-container-running-failed",path="batch"} 650
kwok_stage_fired_rows_sum{kind="Node",stage="node-initialize",path="batch"} 1000
"""
EXPECTED = {"event_post_share": 8.0, "events_per_transition": 0.4,
            "finalizer_row_share": 40.0, "crashloop_row_share": 15.0}
NEW_SERIES = ("kwok_events_recorded", "kwok_stage_fired_rows",
              'kwok_tick_stage_seconds_sum{kind="Pod",stage="event_post"}',
              'kwok_tick_stage_seconds_count{kind="Pod",stage="event_post"}')


def scrape(t, text):
    return {"t": t, "kwok": list(promtext.iter_samples(text)), "apiserver": []}


def parents(text):
    """The scrape a program without this PR's span and histograms gives."""
    return "\n".join(ln for ln in text.splitlines() if not ln.startswith(NEW_SERIES))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "scrapes_v5e_pr32.json"), encoding="utf-8") as f:
        data = json.load(f)
    for side in ("before", "after"):
        for comp in ("kwok", "apiserver"):
            data[side][comp] = [tuple(s) for s in data[side][comp]]
    return data


@pytest.fixture(scope="module")
def bench():
    return run.find_cell(CELL)[0]


def reader(name):
    return run.load_json("layer_metrics", f"{name}.json")


def test_the_entries_are_found_by_name_and_name_the_new_cell_alone(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    names = list(by_name)
    # appended, in order, after every entry that was there
    assert tuple(names[-4:]) == NEW and names[-5] == "api_save_inproc_share"
    readers = {"prom_delta"}
    for name in NEW:
        m, spec = by_name[name], reader(name)
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) == (
            m["name"], m["unit"], m["layer"], m["moves"])
        assert m["workloads"] == [CELL] and m["moves"] == "transitions_per_s"
        assert m["layer"] == by_name["slow_commit_share"]["layer"]
        assert spec["reader"]["kind"] in readers and spec["reader"]["component"] == "kwok"
    assert by_name["event_post_share"]["source"] == "program_span"
    # the readers that were there: the span's as delete_commit_share's,
    # the counters' as slow_row_share's
    span, theirs = reader("event_post_share")["reader"], reader("delete_commit_share")["reader"]
    assert {**span, "labels": None} == {**theirs, "labels": None}
    assert span["labels"] == {"kind": "Pod", "stage": "event_post"}
    for name in NEW[1:]:
        assert reader(name)["reader"]["how"] == reader("slow_row_share")["reader"]["how"]
        assert reader(name)["reader"]["other"] == reader("slow_row_share")["reader"]["other"]
    # the new cell reports them, the cells that were there do not
    for cell in (w["name"] for w in bench["workloads"]):
        mine = {e["name"] for e, _s in run.layer_readers(bench, cell)}
        assert set(NEW) <= mine if cell == CELL else not set(NEW) & mine
    # the cell: the new configuration under the new traffic, on one chip
    cell = run.find_cell(CELL)[1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "general-chaos-1k-100k", "churn", 1)
    config = run.load_json("configs", "general-chaos-1k-100k.json")
    assert config["reference"] == "general_stages" and config["reduced"] == []
    assert [c["name"] for c in bench["configs"]][-1] == config["name"]
    assert bench["configs"][-1]["source"] == config["source"]
    for path in config["create_cluster_args"][3::2]:  # the stage files, from the root
        assert os.path.isfile(os.path.join(ROOT, path))


def test_two_canned_scrapes_read_the_expected_values(bench):
    before, after = scrape(100.0, BEFORE), scrape(150.0, AFTER)
    for name, want in EXPECTED.items():
        assert promtext.read(reader(name)["reader"], before, after) == pytest.approx(want), name
    got = run.layer_values(bench, CELL, before, after, {}, {})
    assert {k: got[k]["value"] for k in NEW} == pytest.approx(EXPECTED)
    # what the acceptance reads beside them: Events a request, and none dropped here but 5
    kb, ka = before["kwok"], after["kwok"]
    events = promtext.delta(kb, ka, "kwok_events_recorded_sum", {"kind": "Pod"})
    requests = promtext.delta(kb, ka, "kwok_events_recorded_count", {"kind": "Pod"})
    assert (events, requests) == (400, 12)
    assert promtext.delta(kb, ka, "kwok_events_recorded_sum",
                          {"kind": "Pod", "outcome": "dropped"}) == 5


def test_a_program_without_the_series_leaves_the_metrics_out(bench):
    """The parent has no ``event_post`` span and neither histogram: the
    line then lacks the four metrics; it does not carry a 0 (nor raise)."""
    before, after = scrape(100.0, parents(BEFORE)), scrape(150.0, parents(AFTER))
    for name in NEW:
        assert promtext.read(reader(name)["reader"], before, after) is None
    got = run.layer_values(bench, CELL, before, after, {}, {})
    assert not set(NEW) & set(got)
    assert "slow_commit_share" not in got  # it names burst-1k alone (PERF.md §7)


def test_recorded_scrapes_read_what_the_run_printed(recorded):
    printed = recorded["printed"]
    for name in NEW:
        got = promtext.read(reader(name)["reader"], recorded["before"], recorded["after"])
        assert got == pytest.approx(printed[name], rel=1e-9), name
    # the acceptance: finalizer rows at least 30 %, an Event to four transitions or more
    assert printed["finalizer_row_share"] >= 30 and printed["events_per_transition"] >= 0.25
    assert 0 < printed["event_post_share"] < 25 and 0 < printed["crashloop_row_share"] < 40
    kb, ka = recorded["before"]["kwok"], recorded["after"]["kwok"]
    events = promtext.delta(kb, ka, "kwok_events_recorded_sum", {"kind": "Pod"})
    requests = promtext.delta(kb, ka, "kwok_events_recorded_count", {"kind": "Pod"})
    # Events go a drain at a time, none was dropped, and none went one a request
    assert events / requests > 20
    assert not promtext.delta(kb, ka, "kwok_events_recorded_sum",
                              {"kind": "Pod", "outcome": "dropped"})
    assert promtext.delta(kb, ka, "kwok_tick_stage_seconds_count",
                          {"kind": "Pod", "stage": "event_post"}) == requests
    ab, aa = recorded["before"]["apiserver"], recorded["after"]["apiserver"]
    assert not promtext.delta(ab, aa, "kwok_apiserver_request_duration_seconds_count",
                              {"kind": "events"})
    # the fired rows add up to the transitions, a drain at either edge aside
    fired = promtext.delta(kb, ka, "kwok_stage_fired_rows_sum", {"kind": "Pod"})
    played = promtext.delta(kb, ka, "kwok_stage_transitions_total", {"kind": "Pod"})
    assert fired == pytest.approx(played, rel=0.05)
    for path in ("batch", "slow"):
        assert promtext.delta(kb, ka, "kwok_stage_fired_rows_sum", {"kind": "Pod", "path": path}) \
            == pytest.approx(promtext.delta(kb, ka, "kwok_status_commit_rows_sum",
                                            {"kind": "Pod", "path": path}), rel=0.05)
