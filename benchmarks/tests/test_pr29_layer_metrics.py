"""The two per-layer metrics PR 29 appends for ``burst-1k`` on pairs of
scrapes recorded on the chip (a TPU v5e, two traced runs of ``burst-1k``,
seed 2900000409, 51 s: the change, whose deleting stage's rows cross as
delete batches, and its parent, whose rows cross as a finalizer patch and a
delete a pod; only the series read here were kept, without their buckets).
Each value is held against what that run itself printed; the parent's
``/metrics`` has no ``path="delete"`` and no ``delete_commit`` stage, so
there both readers find nothing and leave their metric out."""

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
NEW = ("delete_batch_row_share", "delete_commit_share")
#: the metrics of PR 28 that read the per-row path the deletes left
SILENT = ("slow_row_share", "slow_build_us_per_row", "slow_commit_share")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "scrapes_v5e_pr29.json"), encoding="utf-8") as f:
        data = json.load(f)
    for tree in ("change", "parent"):
        for side in ("before", "after"):
            for comp in ("kwok", "apiserver"):
                data[tree][side][comp] = [tuple(s) for s in data[tree][side][comp]]
    return data


@pytest.fixture(scope="module")
def bench():
    return run.find_cell(CELL)[0]


def reader(name):
    return run.load_json("layer_metrics", f"{name}.json")


def test_the_entries_are_found_by_name_and_name_the_cell(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    names = list(by_name)
    # appended: after every entry that was there
    assert names.index("burst_create_to_running_p95_s") < min(names.index(n) for n in NEW)
    layers_before = {m["layer"] for m in bench["per_layer"][:names.index(NEW[0])]}
    for name in NEW:
        m, spec = by_name[name], reader(name)
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) == (
            m["name"], m["unit"], m["layer"], m["moves"])
        assert m["workloads"] == [CELL] and m["moves"] == "transitions_per_s"
        assert m["layer"] == "kwok daemon to apiserver status path" and m["layer"] in layers_before
    assert (by_name[NEW[0]]["better"], by_name[NEW[0]]["source"]) == ("higher", "program_counter")
    assert (by_name[NEW[1]]["better"], by_name[NEW[1]]["source"]) == ("lower", "program_span")
    # the readers that were there, pointed at the new path and the new stage
    for new, old, key in ((NEW[0], "slow_row_share", "path"), (NEW[1], "slow_commit_share", "stage")):
        mine, theirs = reader(new)["reader"], reader(old)["reader"]
        assert {**mine, "labels": None} == {**theirs, "labels": None}
        differ = {k for k in mine["labels"] if mine["labels"][k] != theirs["labels"][k]}
        assert differ == {key}
    assert (reader(NEW[0])["reader"]["labels"]["path"], reader(NEW[1])["reader"]["labels"]["stage"]
            ) == ("delete", "delete_commit")
    # the cell that was there before burst-1k reports neither
    assert not set(NEW) & {m["name"] for m, _s in run.layer_readers(bench, "scaleup-100k")}


@pytest.mark.parametrize("tree", ["change", "parent"])
def test_recorded_scrapes_read_what_the_run_printed(tree, recorded):
    pair, printed = recorded[tree], recorded[tree]["printed"]
    assert set(NEW) <= set(printed) if tree == "change" else not set(NEW) & set(printed)
    for name, value in printed.items():
        got = promtext.read(reader(name)["reader"], pair["before"], pair["after"])
        assert got == pytest.approx(value, rel=1e-9), name


def test_the_counts_behind_the_shares(recorded):
    pair = recorded["change"]
    b, a = pair["before"]["kwok"], pair["after"]["kwok"]

    def rows(path, suffix="_sum"):
        return promtext.delta(b, a, "kwok_status_commit_rows" + suffix, {"kind": "Pod", "path": path})

    played = promtext.delta(b, a, "kwok_stage_transitions_total", {"kind": "Pod"})
    # every played row went by one of the two batches, or was refused there
    assert rows("batch") + rows("delete") + (rows("slow") or 0.0) == played
    assert rows("delete") >= 0.45 * played and (rows("slow") or 0.0) <= 0.05 * played
    gone = promtext.delta(b, a, "kwok_delete_to_gone_seconds_count", {"kind": "Pod"})
    assert rows("delete") <= gone <= rows("delete") + (rows("slow") or 0.0)
    # a request carries a part of a burst, as a slow bulk did
    assert 100 <= rows("delete") / rows("delete", "_count") <= 1000
    # the Pod player's stages, the new one among them, still make the window
    window = pair["after"]["t"] - pair["before"]["t"]
    stages = {ls["stage"] for n, ls, _v in a if n == "kwok_tick_stage_seconds_sum"
              and ls["kind"] == "Pod"}
    assert {"delete_commit", "store_bulk", "host_drain", "ingest", "pace_wait"} <= stages
    total = sum(promtext.delta(b, a, "kwok_tick_stage_seconds_sum", {"kind": "Pod", "stage": s})
                for s in stages - {"compile"})
    assert 0.99 * window <= total <= 1.01 * window
    # and the apiserver timed the verb under its own kind, apart from the bulks
    api_b, api_a = pair["before"]["apiserver"], pair["after"]["apiserver"]
    served = promtext.delta(api_b, api_a, "kwok_apiserver_request_duration_seconds_count",
                            {"verb": "POST", "kind": "delete-batch"})
    assert served == rows("delete", "_count")


def test_the_parent_and_the_change_in_one_line_each(bench, recorded):
    """What ``run.layer_values`` makes of each tree's scrapes: the change
    reports the two new metrics and the parent neither; what PR 28's three
    read of the per-row path is there for the parent and silent, or next to
    nothing, for the change."""
    client = {"create_to_running_p95_s": 0.7}
    got = {tree: run.layer_values(bench, CELL, recorded[tree]["before"], recorded[tree]["after"],
                                  {}, client) for tree in ("change", "parent")}
    assert set(NEW) <= set(got["change"]) and not set(NEW) & set(got["parent"])
    assert set(SILENT) <= set(got["parent"])
    assert got["parent"]["slow_row_share"]["value"] >= 45.0
    assert got["change"]["delete_batch_row_share"] == {
        "value": recorded["change"]["printed"]["delete_batch_row_share"], "unit": "%"}
    assert got["change"]["delete_batch_row_share"]["value"] >= 45.0
    for name in ("slow_row_share", "slow_commit_share"):
        assert got["change"].get(name, {"value": 0.0})["value"] <= 5.0
    assert (got["change"]["delete_commit_share"]["value"]
            + got["change"].get("slow_commit_share", {"value": 0.0})["value"]
            ) < got["parent"]["slow_commit_share"]["value"] / 3
    assert (got["change"]["delete_to_gone_mean_s"]["value"]
            < got["parent"]["delete_to_gone_mean_s"]["value"])
