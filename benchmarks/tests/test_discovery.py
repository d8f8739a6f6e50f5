"""A later PR adds a cell, a configuration, a traffic mix and a
``prom_delta`` per-layer metric as new files and new entries of
``BENCHMARK.json`` only: the harness finds each by name, and no file that
was there is edited."""

import importlib.util
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import promtext  # noqa: E402


@pytest.fixture
def tree(tmp_path):
    """A copy of the benchmark with one new file of each kind."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("out", "__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    b = tmp_path / "benchmarks"
    with open(b / "configs" / "readme-1k-100k.json", encoding="utf-8") as f:
        config = json.load(f)
    config.update(name="small-200-20k", sizes={"nodes": 200, "pods_per_node": 100})
    (b / "configs" / "small-200-20k.json").write_text(json.dumps(config))
    (b / "traffic" / "wave_new_nodes.json").write_text(json.dumps({
        "kind": "wave", "what": "the wave onto nodes that hold no pod yet",
        "params": {"warm_pods": 2000, "warm_nodes": 20, "wave_pods": 8000, "bulk_size": 1000}}))
    (b / "layer_metrics" / "lease_bulk_share.json").write_text(json.dumps({
        "name": "lease_bulk_share", "layer": "lease plane", "unit": "%",
        "moves": "lease_renew_interval_p95_s",
        "reader": {"kind": "prom_delta", "component": "kwok", "how": "sum_over_window",
                   "series": "kwok_tick_stage_seconds",
                   "labels": {"kind": "Node", "stage": "store_bulk"}, "scale": 100}}))
    bench["configs"].append({"name": "small-200-20k", "source": "a test",
                             "file": "benchmarks/configs/small-200-20k.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "new-nodes-20k", "config": "small-200-20k",
                               "traffic": "wave_new_nodes", "chips": 1, "why": "a test"})
    bench["per_layer"].append({"name": "lease_bulk_share", "unit": "%", "better": "lower",
                               "source": "program_span", "layer": "lease plane",
                               "moves": "lease_renew_interval_p95_s",
                               "workloads": ["new-nodes-20k"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = importlib.util.spec_from_file_location("run_copy", b / "run.py")
    run = importlib.util.module_from_spec(spec)
    saved = list(sys.path)
    spec.loader.exec_module(run)
    sys.path[:] = saved
    return run


def test_new_files_are_found_by_name(tree):
    run = tree
    bench, cell = run.find_cell("new-nodes-20k")
    config = run.load_json("configs", f"{cell['config']}.json")
    traffic = run.load_json("traffic", f"{cell['traffic']}.json")
    assert config["sizes"]["nodes"] == 200 and traffic["kind"] == "wave"
    assert traffic["params"]["warm_nodes"] == 20
    assert run.apply_overrides("nodes=20,wave_pods=100", config, traffic)
    assert config["sizes"]["nodes"] == 20 and traffic["params"]["wave_pods"] == 100
    with pytest.raises(run.Failed):
        run.apply_overrides("no_such_size=1", config, traffic)

    before = {"t": 10.0, "kwok": list(promtext.iter_samples(
        'kwok_tick_stage_seconds_sum{kind="Node",stage="store_bulk"} 1.0\n')), "apiserver": []}
    after = {"t": 50.0, "kwok": list(promtext.iter_samples(
        'kwok_tick_stage_seconds_sum{kind="Node",stage="store_bulk"} 3.0\n')), "apiserver": []}
    got = run.layer_values(bench, "new-nodes-20k", before, after, {}, {})
    # the new metric reads; those whose series the scrapes lack are left out, not 0
    assert got == {"lease_bulk_share": {"value": pytest.approx(5.0), "unit": "%"}}
    # and the cells that were there do not report it
    assert "lease_bulk_share" not in run.layer_values(bench, "scaleup-100k", before, after, {}, {})


def test_a_new_cell_reports_the_metrics_that_name_no_cell(tree):
    run = tree
    bench, _cell = run.find_cell("new-nodes-20k")
    mine = [m["name"] for m in bench["end_to_end"] if run.applies(m, "new-nodes-20k")]
    assert mine == ["transitions_per_s", "lease_renew_interval_p95_s", "setup_s"]
    layers = {m["name"] for m, _spec in run.layer_readers(bench, "new-nodes-20k")}
    assert {"device_idle_share", "tick_roofline", "drain_us_per_row"} <= layers
    assert "wave_create_to_running_p95_s" not in layers


def test_an_unknown_cell_or_reader_is_an_error(tree):
    run = tree
    with pytest.raises(run.Failed):
        run.find_cell("no-such-cell")
