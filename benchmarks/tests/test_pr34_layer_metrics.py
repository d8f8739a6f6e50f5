"""The two per-layer metrics PR 34 appends for the layer ``watch delivery``:
``watch_line_encoded_share`` (of the lines the apiserver's streams wrote, the
share the writing stream had to encode itself) and ``watch_encode_share`` (what
that encoding costs the window).  Each is found by name through the harness's
own discovery, names no cell (so every cell and every later one reports it),
reads the expected value off two canned scrapes with readers the harness had,
is left out of the line, not 0, where the program has no such series (the
parent), and is read from a whole CPU rehearsal of ``burst-1k``.  The recorded
pair is of the chip (a TPU v5e, two traced runs of ``burst-1k``, seed
3400001701, 51 s: the change and its parent ``6191ca5``; only the series the two
metrics, ``watch_lag_mean_ms`` and ``api_bulk_mean_ms`` read were kept, without
their buckets)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import generators, run  # noqa: E402
from benchmarks.generators import burst_cycle  # noqa: E402
from benchmarks.harness import promtext  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ("watch_line_encoded_share", "watch_encode_share")
LAG = "watch_lag_mean_ms"
SERIES = ("kwok_watch_lines_encoded", "kwok_watch_lines_total", "kwok_watch_encode_seconds")

#: an apiserver's /metrics around a window of 50 s: 10,000 Pod events to five
#: streams and 1,000 Lease events to two, each encoded once but 200 Pod lines
#: that two streams raced for; 2.5 s of the streams' threads went into it
BEFORE = """
kwok_watch_lines_encoded_sum{kind="Pod"} 5000
kwok_watch_lines_encoded_count{kind="Pod"} 400
kwok_watch_lines_encoded_sum{kind="Node"} 1000
kwok_watch_lines_encoded_count{kind="Node"} 40
kwok_watch_lines_total{kind="Pod"} 25000
kwok_watch_lines_total{kind="Node"} 4000
kwok_watch_encode_seconds_sum{kind="Pod"} 1.0
kwok_watch_encode_seconds_count{kind="Pod"} 400
kwok_watch_encode_seconds_sum{kind="Node"} 0.25
kwok_watch_encode_seconds_count{kind="Node"} 40
kwok_watch_delivery_lag_seconds_sum{shard="-"} 4.0
kwok_watch_delivery_lag_seconds_count{shard="-"} 440
"""
AFTER = """
kwok_watch_lines_encoded_sum{kind="Pod"} 15200
kwok_watch_lines_encoded_count{kind="Pod"} 1400
kwok_watch_lines_encoded_sum{kind="Node"} 1000
kwok_watch_lines_encoded_count{kind="Node"} 40
kwok_watch_lines_encoded_sum{kind="Lease"} 1000
kwok_watch_lines_encoded_count{kind="Lease"} 100
kwok_watch_lines_total{kind="Pod"} 75000
kwok_watch_lines_total{kind="Node"} 4000
kwok_watch_lines_total{kind="Lease"} 2000
kwok_watch_encode_seconds_sum{kind="Pod"} 3.25
kwok_watch_encode_seconds_count{kind="Pod"} 1400
kwok_watch_encode_seconds_sum{kind="Node"} 0.25
kwok_watch_encode_seconds_count{kind="Node"} 40
kwok_watch_encode_seconds_sum{kind="Lease"} 0.25
kwok_watch_encode_seconds_count{kind="Lease"} 100
kwok_watch_delivery_lag_seconds_sum{shard="-"} 14.0
kwok_watch_delivery_lag_seconds_count{shard="-"} 1540
"""
EXPECTED = {"watch_line_encoded_share": 100 * 11200 / 52000, "watch_encode_share": 5.0}


def scrape(t, text):
    return {"t": t, "kwok": [], "apiserver": list(promtext.iter_samples(text))}


def parents(text):
    """The scrape a program without this PR's three series gives."""
    return "\n".join(ln for ln in text.splitlines() if not ln.startswith(SERIES))


@pytest.fixture(scope="module")
def bench():
    return run.find_cell("burst-1k")[0]


def reader(name):
    return run.load_json("layer_metrics", f"{name}.json")


def test_the_entries_are_found_by_name_and_name_no_cell(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    names = list(by_name)
    # appended, in order, after every entry that was there
    assert names.index(NEW[0]) > names.index("crashloop_row_share")
    assert names.index(NEW[1]) == names.index(NEW[0]) + 1
    for name in NEW:
        m, spec = by_name[name], reader(name)
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) == (
            m["name"], m["unit"], m["layer"], m["moves"])
        assert "workloads" not in m
        assert (m["unit"], m["better"], m["moves"]) == ("%", "lower", "transitions_per_s")
        # the layer the lag's metric already names, letter for letter
        assert m["layer"] == by_name[LAG]["layer"] == "watch delivery"
        assert spec["reader"]["kind"] == "prom_delta"
        assert spec["reader"]["component"] == "apiserver" and spec["reader"]["scale"] == 100
        assert len(spec["what"]) > 80
    assert by_name[NEW[0]]["source"] == "program_counter"
    assert by_name[NEW[1]]["source"] == "program_span"
    # readers that were there: the ratio of two series as events_per_transition's,
    # the seconds over the window as api_save_inproc_share's
    assert reader(NEW[0])["reader"]["how"] == reader("events_per_transition")["reader"]["how"]
    assert reader(NEW[0])["reader"]["series"] == SERIES[0]
    assert reader(NEW[0])["reader"]["other"] == {"series": SERIES[1]}
    assert {**reader(NEW[1])["reader"], "series": None} == {
        **reader("api_save_inproc_share")["reader"], "series": None}
    assert reader(NEW[1])["reader"]["series"] == SERIES[2]
    # every cell the benchmark has reports both
    for cell in (w["name"] for w in bench["workloads"]):
        assert set(NEW) <= {e["name"] for e, _s in run.layer_readers(bench, cell)}


def test_two_canned_scrapes_read_the_expected_values():
    before, after = scrape(100.0, BEFORE), scrape(150.0, AFTER)
    for name, want in EXPECTED.items():
        assert promtext.read(reader(name)["reader"], before, after) == pytest.approx(want), name
    # by kind, what PERF.md's §5 reads beside them: streams a kind and seconds a line
    b, a = before["apiserver"], after["apiserver"]
    for kind, streams in (("Pod", 50000 / 10200), ("Lease", 2.0)):
        written = promtext.delta(b, a, SERIES[1], {"kind": kind})
        encoded = promtext.delta(b, a, SERIES[0] + "_sum", {"kind": kind})
        assert written / encoded == pytest.approx(streams)
    # a kind with no event in the window adds nothing to either side
    assert promtext.delta(b, a, SERIES[1], {"kind": "Node"}) == 0


@pytest.mark.parametrize("cell", ["scaleup-100k", "burst-1k", "churn-100k"])
def test_a_program_without_the_series_leaves_the_metrics_out(bench, cell):
    """The parent: its /metrics has the lag's series alone.  The line then
    lacks both metrics; it does not carry a 0."""
    before, after = scrape(100.0, parents(BEFORE)), scrape(150.0, parents(AFTER))
    for name in NEW:
        assert promtext.read(reader(name)["reader"], before, after) is None
    got = run.layer_values(bench, cell, before, after, {}, {})
    assert not set(NEW) & set(got)
    assert got[LAG] == {"value": pytest.approx(1000 * 10 / 1100), "unit": "ms"}
    # and the change reports them in the same line
    got = run.layer_values(bench, cell, scrape(100.0, BEFORE), scrape(150.0, AFTER), {}, {})
    assert {k: got[k]["value"] for k in NEW} == pytest.approx(EXPECTED)


def test_a_cpu_rehearsal_of_burst_1k_reads_both(monkeypatch, capfd):
    """Counts, not speeds: of the lines the streams of a default cluster
    write, between a fifth (Pods go to five streams) and a half (Leases to
    two) are encoded, so most lines are somebody else's bytes."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    for mod in (generators, burst_cycle):
        monkeypatch.setattr(mod, "SETTLE_S", 4.0)
    rc = run.main(["--workload", "burst-1k", "--seed", "3400000007", "--seconds", "8",
                   "--trace", "1", "--override",
                   "nodes=20,standing_pods=50,burst_pods=20,bulk_size=20,"
                   "deviceCapacity=512,nodeLeaseDurationSeconds=4"])
    assert rc == 0
    line = json.loads(capfd.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line
    share, cost = (line["metrics"][name] for name in NEW)
    assert share["unit"] == cost["unit"] == "%"
    assert 18.0 <= share["value"] <= 45.0
    assert 0.0 < cost["value"] < 100.0


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "scrapes_v5e_pr34.json"), encoding="utf-8") as f:
        data = json.load(f)
    for tree in ("change", "parent"):
        for side in ("before", "after"):
            data[tree][side]["apiserver"] = [tuple(s) for s in data[tree][side]["apiserver"]]
    return data


def test_recorded_scrapes_read_what_the_runs_printed(recorded):
    """The change printed both shares: a Pod line went to five streams and a
    Lease line to two and each was encoded once, all but a few.  The
    parent's /metrics has no series for either reader to find; its lag and
    its bulks, which the acceptance reads beside them, are the longer."""
    change, parent = recorded["change"], recorded["parent"]
    for name in NEW + (LAG, "api_bulk_mean_ms"):
        got = promtext.read(reader(name)["reader"], change["before"], change["after"])
        assert got == pytest.approx(change["printed"][name], rel=1e-9), name
    assert 20.0 <= change["printed"][NEW[0]] <= 30.0 and 0 < change["printed"][NEW[1]] < 30
    assert set(parent["printed"]) == {LAG, "api_bulk_mean_ms"}
    for name in NEW:
        assert promtext.read(reader(name)["reader"], parent["before"], parent["after"]) is None
    for name in (LAG, "api_bulk_mean_ms"):
        got = promtext.read(reader(name)["reader"], parent["before"], parent["after"])
        assert got == pytest.approx(parent["printed"][name], rel=1e-9)
        assert change["printed"][name] < got
    b, a = change["before"]["apiserver"], change["after"]["apiserver"]
    for kind, streams in (("Pod", 5), ("Lease", 2)):
        written = promtext.delta(b, a, SERIES[1], {"kind": kind})
        encoded = promtext.delta(b, a, SERIES[0] + "_sum", {"kind": kind})
        assert written > 10_000 and streams - 0.05 < written / encoded <= streams
    # one observation of each histogram a flushed burst
    assert promtext.delta(b, a, SERIES[0] + "_count", {}) == promtext.delta(
        b, a, SERIES[2] + "_count", {})
