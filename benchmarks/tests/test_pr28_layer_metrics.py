"""The five per-layer metrics of ``burst-1k`` on a pair of scrapes recorded
on the chip (a TPU v5e, a traced run of `burst-1k`, seed 2800000011, 51 s:
27 cycles of 1,000 pods closed in the window and a 28th was open; only the
series read here were kept, without their buckets).  Each value is held
against what that run itself printed, and against a program that lacks what
PR 28 added: its readers find nothing and leave the metric out."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.harness import promtext  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "burst-1k"
#: metric -> what the recorded run printed
PRINTED = {
    "slow_row_share": 49.09090909090909,
    "slow_build_us_per_row": 30.87603548148997,
    "slow_commit_share": 45.51525255513511,
    "delete_to_gone_mean_s": 1.3510941599828226,
    # metrics that were there, read in the new cell
    "status_bulk_share": 8.054483527756842,
    "drain_us_per_row": 34.40878567271826,
    "api_bulk_mean_ms": 122.52472933642585,
}
NEW = ("slow_row_share", "slow_build_us_per_row", "slow_commit_share", "delete_to_gone_mean_s",
       "burst_create_to_running_p95_s")
#: what the parent of PR 28 does not expose: the two stages and the histogram
ADDED = ("slow_build", "slow_commit", "kwok_delete_to_gone_seconds")


@pytest.fixture(scope="module")
def scrapes():
    with open(os.path.join(HERE, "data", "scrapes_v5e_pr28.json"), encoding="utf-8") as f:
        pair = json.load(f)["change"]
    for side in pair.values():
        for comp in ("kwok", "apiserver"):
            side[comp] = [tuple(s) for s in side[comp]]
    return pair


@pytest.fixture(scope="module")
def bench():
    return run.find_cell(CELL)[0]


def reader(name):
    return run.load_json("layer_metrics", f"{name}.json")


def test_the_entries_follow_what_was_there_and_name_the_new_cell(bench):
    """By name and not by position from the end, so that the next PR's
    appended entries do not fail this test."""
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("status_batch_row_share") + 1  # the last entry before PR 28
    assert tuple(names[at:at + 5]) == NEW
    layers_before = {m["layer"] for m in bench["per_layer"][:at]}
    for m in bench["per_layer"][at:at + 5]:
        spec = reader(m["name"])
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) == (
            m["name"], m["unit"], m["layer"], m["moves"])
        assert m["workloads"] == [CELL] and m["moves"] == "transitions_per_s"
        # a layer the benchmark already names, letter for letter
        assert m["layer"] in layers_before
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("ci-gate-2k-5k", CELL, 1)
    config = next(c for c in bench["configs"] if c["name"] == "ci-gate-2k-5k")
    assert config["reduced"] == [] and config["file"] == "benchmarks/configs/ci-gate-2k-5k.json"
    # the cell that was there reports none of them
    assert not set(NEW) & {m["name"] for m, _s in run.layer_readers(bench, "scaleup-100k")}


@pytest.mark.parametrize("name", sorted(PRINTED))
def test_recorded_scrapes_read_what_the_run_printed(name, scrapes):
    got = promtext.read(reader(name)["reader"], scrapes["before"], scrapes["after"])
    assert got == pytest.approx(PRINTED[name], rel=1e-9)


def test_the_counts_behind_the_shares(scrapes):
    b, a = scrapes["before"]["kwok"], scrapes["after"]["kwok"]

    def rows(path, suffix="_sum"):
        return promtext.delta(b, a, "kwok_status_commit_rows" + suffix, {"kind": "Pod", "path": path})

    played = promtext.delta(b, a, "kwok_stage_transitions_total", {"kind": "Pod"})
    # 28 bursts turned Running in the window, 27 were deleted and gone in it
    assert (rows("batch"), rows("slow"), played) == (28000.0, 27000.0, 55000.0)
    assert (rows("batch", "_count"), rows("slow", "_count")) == (122.0, 83.0)
    gone = promtext.delta(b, a, "kwok_delete_to_gone_seconds_count", {"kind": "Pod"})
    assert gone == rows("slow")
    # the Pod player's stages, the two new ones among them, make the window
    window = scrapes["after"]["t"] - scrapes["before"]["t"]
    stages = {ls["stage"] for n, ls, _v in a if n == "kwok_tick_stage_seconds_sum"
              and ls["kind"] == "Pod"}
    assert {"slow_build", "slow_commit", "store_bulk", "host_drain", "pace_wait"} <= stages
    total = sum(promtext.delta(b, a, "kwok_tick_stage_seconds_sum", {"kind": "Pod", "stage": s})
                for s in stages - {"compile"})
    assert 0.95 * window <= total <= 1.05 * window


def test_a_program_without_what_pr28_added_leaves_its_metrics_out(bench, scrapes):
    """The parent's ``/metrics``: no ``slow_build`` or ``slow_commit`` stage,
    no ``kwok_delete_to_gone_seconds``.  It has counted rows under
    ``path="slow"`` since PR 27, so ``slow_row_share`` reads there too."""
    def cut(side):
        return {**side, "kwok": [s for s in side["kwok"] if s[1].get("stage") not in ADDED
                                 and not s[0].startswith(ADDED[2])]}

    client = {"create_to_running_p95_s": 0.88}
    got = run.layer_values(bench, CELL, cut(scrapes["before"]), cut(scrapes["after"]), {}, client)
    assert {"slow_row_share", "burst_create_to_running_p95_s"} <= set(got)
    assert not {"slow_build_us_per_row", "slow_commit_share", "delete_to_gone_mean_s"} & set(got)
    whole = run.layer_values(bench, CELL, scrapes["before"], scrapes["after"], {}, client)
    assert set(NEW) <= set(whole)
    assert whole["burst_create_to_running_p95_s"] == {"value": 0.88, "unit": "s"}


def test_the_configuration_states_its_source_its_assumptions_and_its_guarantees(bench):
    entry = next(c for c in bench["configs"] if c["name"] == "ci-gate-2k-5k")
    conf = run.load_json("configs", "ci-gate-2k-5k.json")
    was = run.load_json("configs", "readme-1k-100k.json")
    assert conf["source"] == entry["source"] and "kwokctl_benchmark_test.sh:110-112" in conf["source"]
    assert conf["reduced"] == [] and conf["sizes"]["nodes"] == 2000
    assert conf["kwok_configuration"] == {"deviceCapacity": 8192, "nodeLeaseDurationSeconds": 40}
    assert {"deviceCapacity", "pod_binding", "finalizer"} <= set(conf["assumed"])
    assert all(len(why) > 40 for why in conf["assumed"].values())
    # the same stage set, so the same plain reference, lease block and SoA columns
    for key in ("stages", "reference", "node_ip", "lease", "soa", "create_cluster_args"):
        assert conf[key] == was[key], key
    # the guarantees of the cell that was there, word for word, but the delete's, stated in full
    assert len(conf["guarantees"]) == len(was["guarantees"])
    differ = [(a, b) for a, b in zip(conf["guarantees"], was["guarantees"]) if a != b]
    assert differ == [("every acknowledged delete ends in a DELETED event the watcher sees and is "
                       "gone from the final LIST", "every acknowledged delete is gone")]
