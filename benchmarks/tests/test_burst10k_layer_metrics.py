"""The four per-layer metrics that come with ``burst-10k``, found by name
through the harness's own discovery and reported by every cell (no
``workloads`` list): ``fired_scan_share`` (the Pod player's pass over the
dense fired-stage output, a share of the window), ``node_device_rows``
(rows of the Node player's SoA at the close), ``pod_tick_device_ms``
(device ms a Pod tick, off the profiler trace, counted as
``tick_roofline`` counts) and ``node_tick_device_ms`` (the same of the
Node player's program, which runs under a name of its own).  Each reads the expected value off canned
scrapes or a synthetic trace with a reader the harness had, is left out of
the line, not 0, where the program has no such series or program, and the
two program metrics read a scrape of ``burst-10k`` rehearsed on the CPU."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.harness import promtext  # noqa: E402
from benchmarks.reductions import node_tick_ms, pod_tick_ms, tick_roofline  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "burst-10k"
NEW = ("pod_tick_device_ms", "fired_scan_share", "node_device_rows", "node_tick_device_ms")

#: a kwok daemon's /metrics around a window of 50 s: 2.5 s of the Pod
#: player's fired_scan in 500 drains; a Node SoA of 16,384 rows beside a Pod
#: SoA of 1,048,576
BEFORE = """
kwok_tick_stage_seconds_sum{kind="Pod",stage="fired_scan"} 1.0
kwok_tick_stage_seconds_count{kind="Pod",stage="fired_scan"} 100
kwok_tick_stage_seconds_sum{kind="Node",stage="fired_scan"} 0.5
kwok_device_rows{kind="Pod"} 1048576
kwok_device_rows{kind="Node"} 16384
kwok_device_rows{kind="Lease"} 16384
"""
AFTER = """
kwok_tick_stage_seconds_sum{kind="Pod",stage="fired_scan"} 3.5
kwok_tick_stage_seconds_count{kind="Pod",stage="fired_scan"} 600
kwok_tick_stage_seconds_sum{kind="Node",stage="fired_scan"} 0.9
kwok_device_rows{kind="Pod"} 1048576
kwok_device_rows{kind="Node"} 16384
kwok_device_rows{kind="Lease"} 16384
"""


def scrape(t, text):
    return {"t": t, "kwok": list(promtext.iter_samples(text)), "apiserver": []}


def parents(text):
    """The scrape a program without the new stage and gauge gives."""
    return "\n".join(ln for ln in text.splitlines()
                     if "fired_scan" not in ln and not ln.startswith("kwok_device_rows"))


def synthetic(node_program="jit__run_node_ticks_collect_impl"):
    """A Pod macro-tick of 4 ticks in 0.2 s, a single Pod tick of 0.04 s,
    and a Node macro-tick of 2 ticks in 0.01 s."""
    ops = [("%while.1 = (...)", 1.0, 0.2)]
    for k in range(4):
        ops += [("%fusion.1 = f()", 1.0 + 0.05 * k, 0.02), ("%fusion.2 = g()", 1.02 + 0.05 * k, 0.02)]
    ops += [("%fusion.3 = h()", 2.0, 0.04)]
    ops += [("%while.2 = (...)", 3.0, 0.01)]
    for k in range(2):
        ops += [("%fusion.5 = n()", 3.0 + 0.004 * k, 0.002)]
    return {
        "/device:TPU:0": {
            "XLA Modules": [("jit__run_ticks_collect_impl(1)", 1.0, 0.2),
                            ("jit__run_ticks_collect_impl(2)", 2.0, 0.04),
                            (f"{node_program}(3)", 3.0, 0.01)],
            "XLA Ops": ops,
        },
        "/host:CPU": {"python3": [("$device_player.py:602 step_pipelined", 0.0, 5.0)]},
    }


@pytest.fixture(scope="module")
def bench():
    return run.find_cell(CELL)[0]


def test_the_entries_are_found_by_name_and_every_cell_reports_them(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    want = {
        "pod_tick_device_ms": ("ms", "lower", "device_trace", "device programs, ops/tick.py",
                               "transitions_per_s"),
        "fired_scan_share": ("%", "lower", "program_span", "kwok daemon tick loop, host drain",
                             "transitions_per_s"),
        "node_device_rows": ("rows", "lower", "program_counter", "lease plane",
                             "lease_renew_interval_p95_s"),
        "node_tick_device_ms": ("ms", "lower", "device_trace", "device programs, ops/tick.py",
                                "lease_renew_interval_p95_s"),
    }
    layers = {m["layer"] for name, m in by_name.items() if name not in NEW}
    for name in NEW:
        m, spec = by_name[name], run.load_json("layer_metrics", f"{name}.json")
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == want[name]
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) == (
            m["name"], m["unit"], m["layer"], m["moves"])
        # a layer the benchmark already names, letter for letter
        assert m["layer"] in layers and "workloads" not in m
        assert len(spec["what"]) > 200
        for cell in (w["name"] for w in bench["workloads"]):
            assert name in {e["name"] for e, _s in run.layer_readers(bench, cell)}
    assert run.load_json("layer_metrics", "pod_tick_device_ms.json")["reader"] == {
        "kind": "trace", "reduction": "pod_tick_ms"}
    assert run.load_json("layer_metrics", "node_tick_device_ms.json")["reader"] == {
        "kind": "trace", "reduction": "node_tick_ms"}


def test_two_canned_scrapes_read_the_scan_and_the_node_rows(bench):
    before, after = scrape(100.0, BEFORE), scrape(150.0, AFTER)
    got = run.layer_values(bench, CELL, before, after, {}, {})
    assert got["fired_scan_share"] == {"value": pytest.approx(100 * 2.5 / 50), "unit": "%"}
    assert got["node_device_rows"] == {"value": 16384.0, "unit": "rows"}
    # the Node player's scan and the Pod SoA's rows are not read
    assert promtext.read(run.load_json("layer_metrics", "node_device_rows.json")["reader"],
                         before, after) != 1048576


def test_a_program_without_the_series_leaves_the_metrics_out(bench):
    before, after = scrape(100.0, parents(BEFORE)), scrape(150.0, parents(AFTER))
    got = run.layer_values(bench, CELL, before, after, {}, {})
    assert "fired_scan_share" not in got and "node_device_rows" not in got


def test_the_pod_tick_is_the_pod_programs_time_over_their_ticks():
    # 4 + 1 Pod ticks in 0.24 s; the Node program is not a tick program here
    assert pod_tick_ms.reduce(synthetic(), {}) == pytest.approx(1000 * 0.24 / 5)
    assert tick_roofline.ticks_and_seconds(synthetic()) == (5, pytest.approx(0.24))
    # a program that plays both kinds under one name: the mean of the two
    both = synthetic(node_program="jit__run_ticks_collect_impl")
    assert pod_tick_ms.reduce(both, {}) == pytest.approx(1000 * 0.25 / 7)
    # no tick program, no device plane: nothing to read
    bare = synthetic()
    bare["/device:TPU:0"]["XLA Modules"] = []
    assert pod_tick_ms.reduce(bare, {}) is None
    assert pod_tick_ms.reduce({"/host:CPU": {}}, {}) is None


def test_the_node_tick_is_the_node_programs_time_over_its_ticks():
    # 2 Node ticks in 0.01 s; the Pod programs are not read
    assert node_tick_ms.reduce(synthetic(), {}) == pytest.approx(1000 * 0.01 / 2)
    assert node_tick_ms.ticks_and_seconds(synthetic()) == (2, pytest.approx(0.01))
    # counted as tick_roofline counts the Pod programs
    assert node_tick_ms.ticks_and_seconds(
        synthetic(), tick_roofline.TICK_PROGRAMS) == tick_roofline.ticks_and_seconds(synthetic())
    # a program that plays both kinds under one name, as the parent's does:
    # nothing to read, and nothing raised
    assert node_tick_ms.reduce(synthetic(node_program="jit__run_ticks_collect_impl"), {}) is None
    assert node_tick_ms.reduce({"/host:CPU": {}}, {}) is None


def test_the_trace_reductions_reach_the_line(bench):
    got = run.layer_values(bench, CELL, scrape(0.0, ""), scrape(1.0, ""),
                           {"pod_tick_ms": 12.5, "node_tick_ms": 0.75}, {})
    assert got["pod_tick_device_ms"] == {"value": 12.5, "unit": "ms"}
    assert got["node_tick_device_ms"] == {"value": 0.75, "unit": "ms"}


def test_a_scrape_of_the_cpu_rehearsal_reads_both_program_metrics(bench):
    with open(os.path.join(HERE, "data", "scrapes_cpu_burst10k.json"), encoding="utf-8") as f:
        rec = json.load(f)
    got = run.layer_values(bench, CELL, rec["before"], rec["after"], {}, {})
    assert got["node_device_rows"]["value"] == 4096.0
    window = rec["after"]["t"] - rec["before"]["t"]
    b, a = rec["before"]["kwok"], rec["after"]["kwok"]
    scan = promtext.delta(b, a, "kwok_tick_stage_seconds_sum",
                          {"kind": "Pod", "stage": "fired_scan"})
    assert got["fired_scan_share"]["value"] == pytest.approx(100 * scan / window)
    # one pass a drain: as many scans as host drains
    assert promtext.delta(b, a, "kwok_tick_stage_seconds_count",
                          {"kind": "Pod", "stage": "fired_scan"}) == promtext.delta(
        b, a, "kwok_tick_stage_seconds_count", {"kind": "Pod", "stage": "host_drain"}) > 0
