"""The eleven per-layer metrics PR 39 appends: the first that move ``setup_s``
(two of the layer ``set-up: the daemon's start``, four of ``set-up: the node
Ready wave``, ``cold_compile_s``), a cold compile apart from a fetch in the
window, and the tick loop's lag and virtual pace.  Each is found by name through
the harness's own discovery, names no cell, uses a reader the harness had, is
left out of the line where the program has no such series (the parent: all but
``cold_compiles_in_window``, whose series it has), and reads a number from the
scrapes of a CPU cluster; what set-up froze reads the same at the opening and
at the closing scrape."""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import generators, run  # noqa: E402
from benchmarks.generators import wave  # noqa: E402
from benchmarks.harness import promtext  # noqa: E402

#: name -> (layer, unit, the end-to-end metric it moves, the reader's how)
NEW = {
    "kwok_start_to_device_s": ("set-up: the daemon's start", "s", "setup_s", "gauge_at_end"),
    "kwok_start_to_reconciling_s": ("set-up: the daemon's start", "s", "setup_s", "gauge_at_end"),
    "node_wave_wall_s": ("set-up: the node Ready wave", "s", "setup_s", "gauge_at_end"),
    "node_lease_acquire_worker_s": ("set-up: the node Ready wave", "s", "setup_s",
                                    "gauge_at_end"),
    "node_sync_worker_s": ("set-up: the node Ready wave", "s", "setup_s", "gauge_at_end"),
    "node_signature_compile_s": ("set-up: the node Ready wave", "s", "setup_s", "gauge_at_end"),
    "cold_compile_s": ("jit compiles", "s", "setup_s", "gauge_at_end"),
    "cold_compile_stall_share": ("jit compiles", "%", "transitions_per_s", "sum_over_window"),
    "cold_compiles_in_window": ("jit compiles", "1", "transitions_per_s", "count_delta"),
    "tick_lag_mean_ms": ("kwok daemon tick loop", "ms", "transitions_per_s", "sum_over_count"),
    "virtual_pace_share": ("kwok daemon tick loop", "%", "transitions_per_s",
                           "sum_over_window"),
}
#: what set-up froze: the same at the opening and at the closing scrape
FROZEN = [n for n, (_l, _u, moves, _h) in NEW.items() if moves == "setup_s"
          and n != "cold_compile_s"]

#: a kwok daemon's /metrics around a window of 50 s: 1,000 nodes acquired
#: before it, a cold compile of 1.5 s and a fetch in it, a loop 0.2 s late an
#: iteration that plays four fifths of the wall
BEFORE = """
kwok_process_milestone_seconds{milestone="main"} 1.2
kwok_process_milestone_seconds{milestone="device_ready"} 9.5
kwok_process_milestone_seconds{milestone="reconciling"} 11.25
kwok_process_milestone_seconds{milestone="first_tick",kind="Pod"} 14.0
kwok_node_wave_wall_seconds 31.5
kwok_tick_stage_seconds_sum{kind="NodeBringup",stage="lease_acquire"} 40.0
kwok_tick_stage_seconds_count{kind="NodeBringup",stage="lease_acquire"} 1000
kwok_tick_stage_seconds_sum{kind="NodeBringup",stage="node_sync"} 60.0
kwok_tick_stage_seconds_count{kind="NodeBringup",stage="node_sync"} 1000
kwok_tick_stage_seconds_sum{kind="Node",stage="compile"} 26.0
kwok_compile_stall_seconds_sum{kind="Node",program="run_ticks_collect",cause="signatures",outcome="cold"} 4.0
kwok_compile_stall_seconds_sum{kind="Node",program="run_ticks_collect",cause="signatures",outcome="fetched"} 20.0
kwok_compile_stall_seconds_sum{kind="Node",program="lease_tick",cause="first",outcome="cold"} 2.0
kwok_compile_stall_seconds_sum{kind="Pod",program="run_ticks_collect",cause="first",outcome="cold"} 6.0
kwok_compile_stall_seconds_sum{kind="Pod",program="run_ticks_collect",cause="num_ticks",outcome="fetched"} 3.0
kwok_jit_compile_cache_misses_total 7
kwok_jit_compilations_total 40
kwok_tick_lag_seconds_sum{kind="Pod"} 10.0
kwok_tick_lag_seconds_count{kind="Pod"} 400
kwok_tick_lag_seconds_sum{kind="Node"} 1.0
kwok_tick_lag_seconds_count{kind="Node"} 500
kwok_virtual_seconds_played_total{kind="Pod"} 60.0
kwok_virtual_seconds_played_total{kind="Node"} 70.0
"""
AFTER = BEFORE.replace(
    'cause="first",outcome="cold"} 6.0', 'cause="first",outcome="cold"} 7.5').replace(
    'cause="num_ticks",outcome="fetched"} 3.0', 'cause="num_ticks",outcome="fetched"} 4.0').replace(
    "kwok_jit_compile_cache_misses_total 7", "kwok_jit_compile_cache_misses_total 8").replace(
    "kwok_jit_compilations_total 40", "kwok_jit_compilations_total 45").replace(
    'kwok_tick_lag_seconds_sum{kind="Pod"} 10.0', 'kwok_tick_lag_seconds_sum{kind="Pod"} 60.0').replace(
    'kwok_tick_lag_seconds_count{kind="Pod"} 400', 'kwok_tick_lag_seconds_count{kind="Pod"} 650').replace(
    'kwok_virtual_seconds_played_total{kind="Pod"} 60.0',
    'kwok_virtual_seconds_played_total{kind="Pod"} 100.0')
EXPECTED = {
    "kwok_start_to_device_s": 9.5, "kwok_start_to_reconciling_s": 11.25,
    "node_wave_wall_s": 31.5, "node_lease_acquire_worker_s": 40.0, "node_sync_worker_s": 60.0,
    "node_signature_compile_s": 24.0, "cold_compile_s": 13.5,
    "cold_compile_stall_share": 3.0, "cold_compiles_in_window": 1.0,
    "tick_lag_mean_ms": 200.0, "virtual_pace_share": 80.0,
}
#: the series this PR adds to the program
SERIES = ("kwok_process_milestone_seconds", "kwok_node_wave_wall_seconds",
          'kwok_tick_stage_seconds_sum{kind="NodeBringup"',
          'kwok_tick_stage_seconds_count{kind="NodeBringup"', "kwok_compile_stall_seconds",
          "kwok_tick_lag_seconds", "kwok_virtual_seconds_played_total")


def scrape(t, text):
    return {"t": t, "kwok": list(promtext.iter_samples(text)), "apiserver": []}


def parents(text):
    """The scrape of a program without this PR's series."""
    return "\n".join(ln for ln in text.splitlines() if not ln.startswith(SERIES))


@pytest.fixture(scope="module")
def bench():
    return run.find_cell("scaleup-100k")[0]


def reader(name):
    return run.load_json("layer_metrics", f"{name}.json")


def test_the_entries_are_found_by_name_and_name_no_cell(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    had = {reader(m["name"])["reader"]["how"] for m in bench["per_layer"]
           if m["name"] not in NEW and reader(m["name"])["reader"]["kind"] == "prom_delta"}
    for name, (layer, unit, moves, how) in NEW.items():
        m, spec = by_name[name], reader(name)
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) == (
            m["name"], m["unit"], m["layer"], m["moves"]) == (name, unit, layer, moves)
        assert "workloads" not in m
        assert m["source"] in ("program_span", "program_counter")
        assert m["better"] == ("higher" if name == "virtual_pace_share" else "lower")
        r = spec["reader"]
        assert (r["kind"], r["component"], r["how"]) == ("prom_delta", "kwok", how)
        assert how in had  # a reader the harness had
        assert len(spec["what"]) > 150
    # seven of them move setup_s (a later PR may add to those)
    assert {m["name"] for m in bench["per_layer"] if m["moves"] == "setup_s"} >= {
        n for n, v in NEW.items() if v[2] == "setup_s"}
    assert by_name["compiles_in_window"]["layer"] == "jit compiles"
    for name in FROZEN:
        assert "no cell adds a node" in reader(name)["what"]
    for name in ("node_lease_acquire_worker_s", "node_sync_worker_s"):
        assert "SUMMED over the four workers" in reader(name)["what"]
    # every cell the benchmark has reports all eleven
    for cell in (w["name"] for w in bench["workloads"]):
        assert set(NEW) <= {e["name"] for e, _s in run.layer_readers(bench, cell)}


def test_two_canned_scrapes_read_the_expected_values(bench):
    before, after = scrape(100.0, BEFORE), scrape(150.0, AFTER)
    for name, want in EXPECTED.items():
        assert promtext.read(reader(name)["reader"], before, after) == pytest.approx(want), name
    # what set-up froze reads the same from the opening scrape alone
    for name in FROZEN:
        assert promtext.read(reader(name)["reader"], before, before) == EXPECTED[name]
    # cold_compile_s grew by the window's cold compiles alone
    r = reader("cold_compile_s")["reader"]
    grown = promtext.read(r, before, after) - promtext.read(r, before, before)
    share = promtext.read(reader("cold_compile_stall_share")["reader"], before, after)
    assert grown == pytest.approx(share / 100 * 50.0) == pytest.approx(1.5)
    got = run.layer_values(bench, "burst-1k", before, after, {}, {})
    assert {k: got[k]["value"] for k in NEW} == pytest.approx(EXPECTED)


@pytest.mark.parametrize("cell", ["scaleup-100k", "burst-1k", "churn-100k", "watched-churn"])
def test_a_program_without_the_series_leaves_the_metrics_out(bench, cell):
    """The parent: of the eleven it reports ``cold_compiles_in_window`` alone,
    whose series it has; the line lacks the others, it does not carry a 0."""
    before, after = scrape(100.0, parents(BEFORE)), scrape(150.0, parents(AFTER))
    got = run.layer_values(bench, cell, before, after, {}, {})
    assert set(NEW) & set(got) == {"cold_compiles_in_window"}
    assert got["cold_compiles_in_window"] == {"value": 1.0, "unit": "1"}
    assert got["compiles_in_window"] == {"value": 5.0, "unit": "1"}


def test_a_warm_daemon_reads_zeros_not_nothing(bench):
    """A run with no cold compile and no new signature: the sums stand at 0
    from the daemon's start (engine/simulator.py::ShapeLog), so the metrics
    read 0 and are in the line."""
    zeros = "\n".join(
        f'kwok_compile_stall_seconds_sum{{kind="{kind}",program="run_ticks_collect",'
        f'cause="signatures",outcome="{outcome}"}} 0'
        for kind in ("Node", "Pod") for outcome in ("cold", "fetched"))
    before, after = scrape(100.0, zeros), scrape(150.0, zeros)
    for name in ("node_signature_compile_s", "cold_compile_s", "cold_compile_stall_share"):
        assert promtext.read(reader(name)["reader"], before, after) == 0.0


def test_a_cpu_rehearsal_reads_all_eleven_and_set_up_stands_still(monkeypatch, capfd, tmp_path):
    """Counts, not speeds.  A whole rehearsal of ``scaleup-100k`` at a tiny
    size, traced, with ``--keep``: the line holds all eleven, and the six
    that set-up froze read from the opening scrape what they read at the
    close (the two worker sums where no lease was acquired again in the
    window, which the stage's count shows)."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    for mod in (generators, wave):
        monkeypatch.setattr(mod, "SETTLE_S", 4.0, raising=False)
    keep = str(tmp_path / "keep")
    nodes = 20
    began = time.monotonic()
    rc = run.main(["--workload", "scaleup-100k", "--seed", "3900000007", "--seconds", "8",
                   "--trace", "1", "--keep", keep, "--override",
                   f"nodes={nodes},warm_pods=40,wave_pods=200,bulk_size=20,"
                   "deviceCapacity=512,nodeLeaseDurationSeconds=4"])
    assert rc == 0
    line = json.loads(capfd.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line
    got = {name: line["metrics"][name] for name in NEW}  # a KeyError names the one left out
    assert {name: m["unit"] for name, m in got.items()} == {n: v[1] for n, v in NEW.items()}
    value = {name: m["value"] for name, m in got.items()}
    assert 0.0 < value["kwok_start_to_device_s"] < value["kwok_start_to_reconciling_s"]
    assert value["node_wave_wall_s"] > 0.0
    assert value["node_lease_acquire_worker_s"] > 0.0 and value["node_sync_worker_s"] > 0.0
    assert value["node_signature_compile_s"] >= 0.0
    assert value["cold_compile_s"] >= 0.0 and value["cold_compile_stall_share"] >= 0.0
    assert value["cold_compiles_in_window"] >= 0.0
    assert value["tick_lag_mean_ms"] >= 0.0
    assert 20.0 < value["virtual_pace_share"] < 130.0

    with open(os.path.join(keep, "scrapes.json"), encoding="utf-8") as f:
        scrapes = json.load(f)
    before, after = scrapes["before"], scrapes["after"]
    # the daemon's start and the wave after it lie inside set-up (a traced
    # run's line has no setup_s: the opening scrape is stamped on this clock)
    assert value["kwok_start_to_reconciling_s"] + value["node_wave_wall_s"] \
        < before["t"] - began

    def count(s):
        return promtext.total([tuple(x) for x in s["kwok"]], "kwok_tick_stage_seconds_count",
                              {"kind": "NodeBringup"})

    assert count(before) >= 2 * nodes
    for name in FROZEN:
        r = reader(name)["reader"]
        if name.endswith("_worker_s") and count(before) != count(after):
            continue  # a lease was acquired again inside the window
        assert promtext.read(r, before, before) == promtext.read(r, before, after) \
            == value[name], name
    r = reader("cold_compile_s")["reader"]
    grown = promtext.read(r, before, after) - promtext.read(r, before, before)
    window = after["t"] - before["t"]
    assert grown == pytest.approx(value["cold_compile_stall_share"] / 100 * window, abs=1e-6)
