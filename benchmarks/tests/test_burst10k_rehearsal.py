"""``burst-10k`` rehearsed whole on the CPU at 20 nodes: the north star's
cell with its Pod SoA 8,192 rows deep, so that the Node SoA and the lease
lane, which start at 4,096 rows at most (``controller.NODE_ROWS``), are
sized apart from it as on the chip.  A sound run reads ``correct`` true with every delete
seen and gone, and its traced line carries the cell's program metrics."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import generators, run  # noqa: E402
from benchmarks.generators import burst_cycle  # noqa: E402

CELL = "burst-10k"
OVERRIDE = ("nodes=20,standing_pods=60,burst_pods=20,bulk_size=20,"
            "deviceCapacity=8192,nodeLeaseDurationSeconds=4")


def rehearse(monkeypatch, capfd, trace):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    for mod in (generators, burst_cycle):
        monkeypatch.setattr(mod, "SETTLE_S", 4.0)
    rc = run.main(["--workload", CELL, "--seed", "4500000007", "--seconds", "6",
                   "--trace", str(trace), "--override", OVERRIDE])
    assert rc == 0
    return json.loads(capfd.readouterr().out.strip().splitlines()[-1])


def test_the_cell_names_its_configuration_and_traffic():
    bench, cell = run.find_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "stage-fast-10k-1m", "burst-10k", 1)
    # appended after the cells and configurations that were there
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) > cells.index("sched-5k")
    (entry,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert bench["configs"].index(entry) > [c["name"] for c in bench["configs"]].index(
        "sched-basic-5k")
    conf = run.load_json("configs", "stage-fast-10k-1m.json")
    assert conf["kwok_configuration"] == {"deviceCapacity": 1048576,
                                          "nodeLeaseDurationSeconds": 40}
    assert conf["sizes"]["nodes"] == 10000 and conf["reference"] == "fast_stages"
    assert conf["reduced"] == entry["reduced"] == ["pods"] and entry["file"] == (
        "benchmarks/configs/stage-fast-10k-1m.json")
    assert conf["stages"] == ["pod-fast", "node-fast", "node-heartbeat-with-lease"]
    traffic = run.load_json("traffic", "burst-10k.json")
    assert traffic["kind"] == "burst_cycle" and traffic["params"] == {
        "standing_pods": 100000, "burst_pods": 5000, "bulk_size": 1000, "clients": 1,
        "warm_cycles": 2}
    # live pods stay inside what the configuration says it cut them to
    p = traffic["params"]
    assert p["standing_pods"] + p["burst_pods"] <= conf["sizes"]["pods"]
    # two warm cycles cover every node once
    assert p["warm_cycles"] * p["burst_pods"] == conf["sizes"]["nodes"]


def test_a_sound_rehearsal_is_correct(monkeypatch, capfd):
    line = rehearse(monkeypatch, capfd, trace=0)
    assert line["correct"] is True and line["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line
    assert set(line["metrics"]) == {"transitions_per_s", "lease_renew_interval_p95_s", "setup_s"}
    assert line["metrics"]["transitions_per_s"]["value"] * 6 >= 10 * 20
    assert (line["attempted"] - 20) % 2 == 0 and line["attempted"] > 20


def test_a_traced_rehearsal_reports_the_program_metrics(monkeypatch, capfd):
    line = rehearse(monkeypatch, capfd, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    m = line["metrics"]
    # the Node player and the lane start at 4,096 rows, not at the pods' 8,192
    assert m["node_device_rows"] == {"value": 4096.0, "unit": "rows"}
    assert 0.0 < m["fired_scan_share"]["value"] < 100.0
    # a CPU trace has no device plane: the device's metrics are left out
    assert "pod_tick_device_ms" not in m and "tick_roofline" not in m
    assert "node_tick_device_ms" not in m
