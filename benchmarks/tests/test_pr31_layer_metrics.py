"""The per-layer metric PR 31 appends: ``api_save_inproc_share``, what the
apiserver's saves cost the interpreter its request threads share, beside
``api_save_share``, which goes on reading the whole save (now mostly the
wait for the child that serialises).  It is found by name through the
harness's own discovery, names no cell (so both cells and every later one
report it), reads the expected share off two canned scrapes, and is left
out of the line, not 0, where the program has no such series (the parent).
The recorded pair is of the chip (a TPU v5e, two traced runs of
``burst-1k``, seed 3100000601, 51 s: the change and its parent ``6c8bf0a``;
only the save's series were kept, without their buckets)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.harness import promtext  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
NEW = "api_save_inproc_share"
WHOLE = "api_save_share"

#: an apiserver's /metrics around a window of 50 s with four saves in it:
#: 9.0 s of saves, 0.25 s of them in the serving process
BEFORE = """
kwok_apiserver_save_seconds_sum 21.5
kwok_apiserver_save_seconds_count 6
kwok_apiserver_save_inprocess_seconds_sum 0.5
kwok_apiserver_save_inprocess_seconds_count 6
kwok_apiserver_save_children_total{outcome="ok"} 6
kwok_apiserver_save_children_total{outcome="failed"} 0
"""
AFTER = """
kwok_apiserver_save_seconds_sum 30.5
kwok_apiserver_save_seconds_count 10
kwok_apiserver_save_inprocess_seconds_sum 0.75
kwok_apiserver_save_inprocess_seconds_count 10
kwok_apiserver_save_children_total{outcome="ok"} 10
kwok_apiserver_save_children_total{outcome="failed"} 0
"""


def scrape(t, text):
    return {"t": t, "kwok": [], "apiserver": list(promtext.iter_samples(text))}


def without(text, series):
    return "\n".join(ln for ln in text.splitlines() if not ln.startswith(series))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "scrapes_v5e_pr31.json"), encoding="utf-8") as f:
        data = json.load(f)
    for tree in ("change", "parent"):
        for side in ("before", "after"):
            data[tree][side]["apiserver"] = [tuple(s) for s in data[tree][side]["apiserver"]]
    return data


@pytest.fixture(scope="module")
def bench():
    return run.find_cell("burst-1k")[0]


def reader(name):
    return run.load_json("layer_metrics", f"{name}.json")


def test_the_entry_is_found_by_name_and_names_no_cell(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    names = list(by_name)
    # appended: after every entry that was there
    assert names.index(NEW) > names.index("delete_commit_share")
    m, spec = by_name[NEW], reader(NEW)
    assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) == (
        m["name"], m["unit"], m["layer"], m["moves"])
    assert "workloads" not in m
    assert (m["unit"], m["better"], m["source"], m["moves"]) == (
        "%", "lower", "program_span", "transitions_per_s")
    # the layer the whole save's metric already names, letter for letter
    assert m["layer"] == by_name[WHOLE]["layer"] == "apiserver snapshot"
    # the reader that was there, pointed at the new series
    mine, theirs = spec["reader"], reader(WHOLE)["reader"]
    assert {**mine, "series": None} == {**theirs, "series": None}
    assert mine["series"] == "kwok_apiserver_save_inprocess_seconds"
    # every cell the benchmark has reports it
    for cell in (w["name"] for w in bench["workloads"]):
        assert NEW in {e["name"] for e, _s in run.layer_readers(bench, cell)}


def test_two_canned_scrapes_read_the_expected_share():
    before, after = scrape(100.0, BEFORE), scrape(150.0, AFTER)
    assert promtext.read(reader(NEW)["reader"], before, after) == pytest.approx(0.5)
    assert promtext.read(reader(WHOLE)["reader"], before, after) == pytest.approx(18.0)
    # a child a save, none failed: what the acceptance reads beside the share
    api_b, api_a = before["apiserver"], after["apiserver"]
    saves = promtext.delta(api_b, api_a, "kwok_apiserver_save_seconds_count", {})
    assert promtext.delta(api_b, api_a, "kwok_apiserver_save_children_total",
                          {"outcome": "ok"}) == saves == 4
    assert promtext.delta(api_b, api_a, "kwok_apiserver_save_children_total",
                          {"outcome": "failed"}) == 0


@pytest.mark.parametrize("cell", ["scaleup-100k", "burst-1k"])
def test_a_program_without_the_series_leaves_the_metric_out(bench, cell):
    """The parent: its /metrics has the whole save's series alone.  The
    line then lacks the metric; it does not carry a 0."""
    series = "kwok_apiserver_save_inprocess_seconds"
    before, after = scrape(100.0, without(BEFORE, series)), scrape(150.0, without(AFTER, series))
    assert promtext.read(reader(NEW)["reader"], before, after) is None
    got = run.layer_values(bench, cell, before, after, {}, {})
    assert NEW not in got
    if cell == "scaleup-100k":  # the whole save's metric names that cell alone
        assert got[WHOLE] == {"value": pytest.approx(18.0), "unit": "%"}
    # and the change reports it in the same line
    got = run.layer_values(bench, cell, scrape(100.0, BEFORE), scrape(150.0, AFTER), {}, {})
    assert got[NEW] == {"value": pytest.approx(0.5), "unit": "%"}


def test_recorded_scrapes_read_what_the_runs_printed(recorded):
    """The change printed the new share and forked a child a save, none of
    which failed; its whole saves are a tenth of the parent's, whose
    /metrics has no series for the new reader to find."""
    change, parent = recorded["change"], recorded["parent"]
    got = promtext.read(reader(NEW)["reader"], change["before"], change["after"])
    assert got == pytest.approx(change["printed"][NEW], rel=1e-9) and 0 < got < 5
    assert parent["printed"] == {}
    assert promtext.read(reader(NEW)["reader"], parent["before"], parent["after"]) is None
    b, a = change["before"]["apiserver"], change["after"]["apiserver"]
    saves = promtext.delta(b, a, "kwok_apiserver_save_seconds_count", {})
    assert saves == 4 == promtext.delta(b, a, "kwok_apiserver_save_inprocess_seconds_count", {})
    assert promtext.delta(b, a, "kwok_apiserver_save_children_total", {"outcome": "ok"}) == saves
    assert promtext.delta(b, a, "kwok_apiserver_save_children_total", {"outcome": "failed"}) == 0
    whole = {tree: promtext.read(reader(WHOLE)["reader"], pair["before"], pair["after"])
             for tree, pair in recorded.items()}
    assert got < whole["change"] < whole["parent"] / 5 and whole["parent"] > 25
