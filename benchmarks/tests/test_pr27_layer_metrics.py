"""``status_batch_row_share`` on two pairs of scrapes recorded on the chip
(a TPU v5e, traced runs of `scaleup-100k`, seed 2700000011, 51 s; only the
series read here were kept, without their buckets): the change of PR 27, whose
Pod player commits through the status-batch verb, and its parent under the
same benchmark files, which has neither the verb nor the series.  Each value
is held against what that run itself printed."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import promtext  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "status_batch_row_share"
#: side -> metric -> what the recorded run printed (None: left out of its line)
PRINTED = {
    "change": {NAME: 100.0, "status_bulk_share": 24.710709664329368,
               "api_bulk_mean_ms": 738.1634436896542},
    "parent": {NAME: None, "status_bulk_share": 85.78305331445017,
               "api_bulk_mean_ms": 1042.5124545000026},
}


@pytest.fixture(scope="module")
def scrapes():
    with open(os.path.join(HERE, "data", "scrapes_v5e_pr27.json"), encoding="utf-8") as f:
        recorded = json.load(f)
    for pair in recorded.values():
        for side in pair.values():
            for comp in ("kwok", "apiserver"):
                side[comp] = [tuple(s) for s in side[comp]]
    return recorded


def reader(name):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics", f"{name}.json"),
              encoding="utf-8") as f:
        return json.load(f)


def test_the_metric_is_found_by_name_like_the_ones_that_were_there():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    spec = reader(NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
                     "layer": "kwok daemon to apiserver status path",
                     "moves": "transitions_per_s", "workloads": ["scaleup-100k"]}
    assert (spec["unit"], spec["layer"], spec["moves"]) == (
        entry["unit"], entry["layer"], entry["moves"])
    assert spec["reader"]["kind"] == "prom_delta" and spec["reader"]["component"] == "kwok"
    # the layer is one the benchmark already names, letter for letter
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"][:-1]}


@pytest.mark.parametrize("side", sorted(PRINTED))
@pytest.mark.parametrize("name", sorted(PRINTED["change"]))
def test_recorded_scrapes_read_what_the_run_printed(side, name, scrapes):
    pair = scrapes[side]
    got = promtext.read(reader(name)["reader"], pair["before"], pair["after"])
    want = PRINTED[side][name]
    if want is None:
        # the parent has no such series: nothing to read, and no 0 in its place
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9)


def test_the_series_gives_requests_and_rows_a_request(scrapes):
    b, a = scrapes["change"]["before"], scrapes["change"]["after"]
    labels = {"kind": "Pod", "path": "batch"}
    rows = promtext.delta(b["kwok"], a["kwok"], "kwok_status_commit_rows_sum", labels)
    requests = promtext.delta(b["kwok"], a["kwok"], "kwok_status_commit_rows_count", labels)
    played = promtext.delta(b["kwok"], a["kwok"], "kwok_stage_transitions_total", {"kind": "Pod"})
    assert (rows, requests, played) == (66000.0, 223.0, 66000.0)
    # no row of the window went as a merge patch
    assert not promtext.delta(b["kwok"], a["kwok"], "kwok_status_commit_rows_sum",
                              {"kind": "Pod", "path": "slow"})
    # and the apiserver timed as many requests under the verb's own kind
    assert promtext.delta(b["apiserver"], a["apiserver"],
                          "kwok_apiserver_request_duration_seconds_count",
                          {"verb": "POST", "kind": "status-batch"}) == requests


def test_a_share_of_rows_that_went_the_other_way_is_read_as_one(scrapes):
    """The recorded window is all batch; with 1,500 of its rows moved to the
    slow path in a copy of the closing scrape, the share follows."""
    b = scrapes["change"]["before"]
    a = dict(scrapes["change"]["after"])
    a["kwok"] = [(n, ls, v - 1500.0) if n == "kwok_status_commit_rows_sum"
                 and ls.get("path") == "batch" and ls.get("kind") == "Pod" else (n, ls, v)
                 for n, ls, v in a["kwok"]]
    got = promtext.read(reader(NAME)["reader"], b, a)
    assert got == pytest.approx(100.0 * 64500 / 66000, rel=1e-12)
