"""The two per-layer metrics PR 37 appends for the layer ``apiserver and store
commit``: ``object_json_reuse_share`` (of the uses of a committed object's JSON
inside the apiserver, the share that wrote bytes another writer had left) and
``bulk_cpu_us_per_op`` (thread CPU of the ``/bulk`` route per op).  Each is
found by name through the harness's own discovery, names no cell (so every cell
and every later one reports it), reads the expected value off two canned
scrapes with a reader the harness had, is left out of the line, not 0, where the
program has no such series (the parent), and is read from a scrape of a CPU
cluster in a whole rehearsal of ``burst-1k``."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import generators, run  # noqa: E402
from benchmarks.generators import burst_cycle  # noqa: E402
from benchmarks.harness import promtext  # noqa: E402

NEW = ("object_json_reuse_share", "bulk_cpu_us_per_op")
BULK = "api_bulk_mean_ms"
SERIES = ("kwok_object_json_total", "kwok_object_json_reused", "kwok_bulk_cpu_seconds",
          "kwok_bulk_ops_total")

#: an apiserver's /metrics around a window of 50 s: 30,000 Pod ops and 5,000
#: Lease renewals through 700 bulks (an encode and two reuses each), 25,000 Pod
#: events of status and delete batches (an encode each, by a stream); the
#: bulks took 2.1 s of their threads
BEFORE = """
kwok_object_json_total{kind="Pod",source="encoded"} 10000
kwok_object_json_total{kind="Pod",source="reused"} 12000
kwok_object_json_total{kind="Node",source="encoded"} 1000
kwok_object_json_total{kind="Node",source="reused"} 2000
kwok_object_json_reused_sum{kind="Pod"} 12000
kwok_object_json_reused_count{kind="Pod"} 300
kwok_object_json_reused_sum{kind="Node"} 2000
kwok_object_json_reused_count{kind="Node"} 10
kwok_bulk_cpu_seconds_sum 0.4
kwok_bulk_cpu_seconds_count 100
kwok_bulk_ops_total 6000
kwok_apiserver_request_duration_seconds_sum{verb="POST",kind="bulk",level="workloads",shard="-"} 5.0
kwok_apiserver_request_duration_seconds_count{verb="POST",kind="bulk",level="workloads",shard="-"} 100
"""
AFTER = """
kwok_object_json_total{kind="Pod",source="encoded"} 65000
kwok_object_json_total{kind="Pod",source="reused"} 72000
kwok_object_json_total{kind="Node",source="encoded"} 1000
kwok_object_json_total{kind="Node",source="reused"} 2000
kwok_object_json_total{kind="Lease",source="encoded"} 5000
kwok_object_json_total{kind="Lease",source="reused"} 10000
kwok_object_json_reused_sum{kind="Pod"} 72000
kwok_object_json_reused_count{kind="Pod"} 1500
kwok_object_json_reused_sum{kind="Node"} 2000
kwok_object_json_reused_count{kind="Node"} 10
kwok_object_json_reused_sum{kind="Lease"} 10000
kwok_object_json_reused_count{kind="Lease"} 250
kwok_bulk_cpu_seconds_sum 2.5
kwok_bulk_cpu_seconds_count 800
kwok_bulk_ops_total 41000
kwok_apiserver_request_duration_seconds_sum{verb="POST",kind="bulk",level="workloads",shard="-"} 54.0
kwok_apiserver_request_duration_seconds_count{verb="POST",kind="bulk",level="workloads",shard="-"} 800
"""
EXPECTED = {"object_json_reuse_share": 100 * 70000 / 130000, "bulk_cpu_us_per_op": 60.0}


def scrape(t, text):
    return {"t": t, "kwok": [], "apiserver": list(promtext.iter_samples(text))}


def parents(text):
    """The scrape a program without this PR's four series gives."""
    return "\n".join(ln for ln in text.splitlines() if not ln.startswith(SERIES))


@pytest.fixture(scope="module")
def bench():
    return run.find_cell("watched-churn")[0]


def reader(name):
    return run.load_json("layer_metrics", f"{name}.json")


def test_the_entries_are_found_by_name_and_name_no_cell(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    names = list(by_name)
    # appended, in order, after every entry that was there
    assert names[-2:] == list(NEW)
    assert names.index(NEW[0]) > names.index("list_snapshots_expired")
    for name in NEW:
        m, spec = by_name[name], reader(name)
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) == (
            m["name"], m["unit"], m["layer"], m["moves"])
        assert "workloads" not in m and m["moves"] == "transitions_per_s"
        # the layer the bulk's metric already names, letter for letter
        assert m["layer"] == by_name[BULK]["layer"] == "apiserver and store commit"
        # a reader that was there: the ratio of two series, as events_per_transition's
        assert spec["reader"]["kind"] == "prom_delta"
        assert spec["reader"]["component"] == "apiserver"
        assert spec["reader"]["how"] == reader("events_per_transition")["reader"]["how"]
        assert len(spec["what"]) > 200
    share, cpu = by_name[NEW[0]], by_name[NEW[1]]
    assert (share["unit"], share["better"], share["source"]) == ("%", "higher", "program_counter")
    assert (cpu["unit"], cpu["better"], cpu["source"]) == ("us", "lower", "program_span")
    assert reader(NEW[0])["reader"]["series"] == SERIES[1]
    assert reader(NEW[0])["reader"]["other"] == {"series": SERIES[0]}
    assert reader(NEW[1])["reader"]["series"] == SERIES[2]
    assert reader(NEW[1])["reader"]["other"] == {"series": SERIES[3]}
    # the `what` names the three writers and where a batch's events count
    what = reader(NEW[0])["what"]
    for word in ("WAL", "watch line", "answer", "status batch", "delete batch", "encoded"):
        assert word in what
    # every cell the benchmark has reports both
    for cell in (w["name"] for w in bench["workloads"]):
        assert set(NEW) <= {e["name"] for e, _s in run.layer_readers(bench, cell)}


def test_two_canned_scrapes_read_the_expected_values():
    before, after = scrape(100.0, BEFORE), scrape(150.0, AFTER)
    for name, want in EXPECTED.items():
        assert promtext.read(reader(name)["reader"], before, after) == pytest.approx(want), name
    # the histogram's sum is the counter's reused child, kind for kind
    b, a = before["apiserver"], after["apiserver"]
    for kind in ("Pod", "Lease"):
        assert promtext.delta(b, a, SERIES[1] + "_sum", {"kind": kind}) == promtext.delta(
            b, a, SERIES[0], {"kind": kind, "source": "reused"})
    # a kind with no commit in the window adds nothing to either side
    assert promtext.delta(b, a, SERIES[0], {"kind": "Node"}) == 0


@pytest.mark.parametrize("cell", ["scaleup-100k", "burst-1k", "churn-100k", "watched-churn"])
def test_a_program_without_the_series_leaves_the_metrics_out(bench, cell):
    """The parent: its /metrics has the request durations alone.  The line
    then lacks both metrics; it does not carry a 0."""
    before, after = scrape(100.0, parents(BEFORE)), scrape(150.0, parents(AFTER))
    for name in NEW:
        assert promtext.read(reader(name)["reader"], before, after) is None
    got = run.layer_values(bench, cell, before, after, {}, {})
    assert not set(NEW) & set(got)
    assert got[BULK] == {"value": pytest.approx(70.0), "unit": "ms"}
    # and the change reports them in the same line
    got = run.layer_values(bench, cell, scrape(100.0, BEFORE), scrape(150.0, AFTER), {}, {})
    assert {k: got[k]["value"] for k in NEW} == pytest.approx(EXPECTED)


def test_a_cpu_rehearsal_of_burst_1k_reads_both(monkeypatch, capfd):
    """Counts, not speeds: the client's creates and deletes and the lease
    lane's renewals go through /bulk (an encode and two reuses an op), the
    daemon's rows through the status and the delete batch (an encode an
    event), so between a third and two thirds of the uses write kept bytes."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    for mod in (generators, burst_cycle):
        monkeypatch.setattr(mod, "SETTLE_S", 4.0)
    rc = run.main(["--workload", "burst-1k", "--seed", "3700000007", "--seconds", "8",
                   "--trace", "1", "--override",
                   "nodes=20,standing_pods=50,burst_pods=20,bulk_size=20,"
                   "deviceCapacity=512,nodeLeaseDurationSeconds=4"])
    assert rc == 0
    line = json.loads(capfd.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line
    share, cpu = (line["metrics"][name] for name in NEW)
    assert (share["unit"], cpu["unit"]) == ("%", "us")
    assert 30.0 <= share["value"] <= 66.7
    assert 0.0 < cpu["value"] < 100000.0
