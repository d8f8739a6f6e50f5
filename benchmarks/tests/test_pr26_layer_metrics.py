"""The per-layer metrics that read the tick threads' own accounts, on a pair
of scrapes recorded on the chip (a TPU v5e, a traced run of `scaleup-100k`,
seed 4300000023, 51 s; only the series these metrics read were kept, without
their buckets) against the values that run itself printed; and that each is
found by name through `BENCHMARK.json` like the ones that were there."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import promtext  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
#: name -> (what the recorded run printed, the unit it declares)
PRINTED = {
    "ingest_share": (4.676800703221536, "%"),
    "compile_stall_share": (4.649565796106316, "%"),
    "new_shapes_in_window": (6.0, "1"),
    "lease_tick_share": (50.310787552476675, "%"),
    "lease_delay_mean_s": (0.9388517735814974, "s"),
    "api_save_share": (30.79036172390863, "%"),
}
TRACED = ("idle_in_store_bulk_share", "idle_unattributed_share")


@pytest.fixture(scope="module")
def scrapes():
    with open(os.path.join(HERE, "data", "scrapes_v5e_pr26.json"), encoding="utf-8") as f:
        pair = json.load(f)
    for side in pair.values():
        for comp in ("kwok", "apiserver"):
            side[comp] = [tuple(s) for s in side[comp]]
    return pair


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def reader(name):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics", f"{name}.json"),
              encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(PRINTED))
def test_recorded_scrapes_read_what_the_run_printed(name, scrapes, bench):
    spec = reader(name)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert spec["reader"]["kind"] == "prom_delta"
    assert (spec["unit"], spec["layer"], spec["moves"]) == (
        entry["unit"], entry["layer"], entry["moves"])
    assert entry["unit"] == PRINTED[name][1] and entry["workloads"] == ["scaleup-100k"]
    got = promtext.read(spec["reader"], scrapes["before"], scrapes["after"])
    assert got == pytest.approx(PRINTED[name][0], rel=1e-9)


def test_the_stage_shares_of_one_thread_make_the_window(scrapes):
    """Every stage reports self time but ``compile``, which overlays the
    stage it stalls: the Pod player's stages less it fill the window."""
    b, a = scrapes["before"], scrapes["after"]
    stages = {ls["stage"] for n, ls, _v in a["kwok"]
              if n == "kwok_tick_stage_seconds_sum" and ls["kind"] == "Pod"}
    assert {"ingest", "device_tick", "compile", "host_drain", "store_bulk", "pace_wait"} <= stages
    share = {st: promtext.delta(b["kwok"], a["kwok"], "kwok_tick_stage_seconds_sum",
                                {"kind": "Pod", "stage": st}) / (a["t"] - b["t"])
             for st in stages}
    assert 0.95 <= sum(share.values()) - share["compile"] <= 1.02, share


def test_a_program_without_the_series_reads_nothing(scrapes):
    """The parent of the PR that brought them: no series, no metric, no error."""
    bare = {side: {"t": s["t"], "kwok": [], "apiserver": []} for side, s in scrapes.items()}
    for name in PRINTED:
        assert promtext.read(reader(name)["reader"], bare["before"], bare["after"]) is None


@pytest.mark.parametrize("name", TRACED)
def test_the_trace_metrics_name_reductions_that_exist(name, bench):
    import importlib

    spec = reader(name)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["source"] == "device_trace" and entry["workloads"] == ["scaleup-100k"]
    assert spec["reader"] == {"kind": "trace", "reduction": name}
    mod = importlib.import_module(f"benchmarks.reductions.{name}")
    assert mod.reduce({}, {}) is None
