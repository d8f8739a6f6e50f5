"""The trace reductions: on a synthetic trace whose answers are known by
hand, and on one small trace recorded on the chip (a TPU v5e, the kwok
daemon of a 20-node rehearsal, 0.25 s asked for; brought back by PR 25)."""

import gzip
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.reductions import device_idle_share, tick_roofline, trace_model  # noqa: E402

RECORDED = os.path.join(os.path.dirname(__file__), "data", "tiny_v5e.xplane.pb.gz")
V5E = {"hbm_bytes_per_s": 819e9}


def ctx(capacity, columns=2, peaks=V5E):
    return {"peaks": peaks, "config": {"kwok_configuration": {"deviceCapacity": capacity},
                                       "soa": {"pod_feature_columns": columns}}}


def synthetic():
    """Two tick programs on the device: one of 3 ticks (a loop whose body
    ops repeat), one single tick; a lease tick; 10 s of host activity."""
    ops = [("%while.6 = (...)", 1.0, 0.3)]
    for k in range(3):
        ops += [("%fusion.1 = f()", 1.0 + 0.1 * k, 0.05), ("%fusion.2 = g()", 1.05 + 0.1 * k, 0.05)]
    ops += [("%fusion.7 = h()", 5.0, 0.1), ("%fusion.9 = lease()", 7.0, 0.01)]
    return {
        "/device:TPU:0": {
            "XLA Modules": [("jit__run_ticks_collect_impl(111)", 1.0, 0.3),
                            ("jit__run_ticks_collect_impl(222)", 5.0, 0.1),
                            ("jit__lease_tick_impl(333)", 7.0, 0.01)],
            "XLA Ops": ops,
        },
        "/host:CPU": {"python3": [("$device_player.py:602 step_pipelined", 0.0, 10.0),
                                  ("$client.py:862 bulk", 1.4, 3.5),
                                  ("$threading.py:637 wait", 0.0, 10.0)]},
    }


def test_busy_is_the_union_of_device_op_intervals():
    assert trace_model.union_s([("a", 0.0, 1.0), ("b", 0.5, 1.0), ("c", 3.0, 0.5)]) == 2.0
    busy, win = trace_model.busy_and_window(synthetic())
    assert busy == pytest.approx(0.3 + 0.1 + 0.01) and win == pytest.approx(10.0)
    assert device_idle_share.reduce(synthetic(), {}) == pytest.approx(100 * (1 - 0.41 / 10))


def test_ticks_are_counted_from_the_loop_body():
    ticks, seconds = tick_roofline.ticks_and_seconds(synthetic())
    assert ticks == 3 + 1 and seconds == pytest.approx(0.4)
    per_tick = tick_roofline.soa_bytes_per_tick(131072, 2)
    assert per_tick == 131072 * (2 * (8 + 20 + 2) + 1)
    share = tick_roofline.reduce(synthetic(), ctx(131072))
    assert share == pytest.approx(100 * (4 * per_tick / 819e9) / 0.4)


def test_no_device_plane_or_unknown_peaks_reads_nothing():
    host_only = {"/host:CPU": synthetic()["/host:CPU"]}
    assert device_idle_share.reduce(host_only, {}) is None
    assert tick_roofline.reduce(host_only, ctx(512)) is None
    assert tick_roofline.reduce(synthetic(), ctx(512, peaks=None)) is None
    assert trace_model.busy_and_window(host_only) is None and trace_model.breakdown(host_only) is None


def test_breakdown_names_programs_and_gaps():
    b = trace_model.breakdown(synthetic())
    assert b["device_ops"][0] == ["jit__run_ticks_collect_impl", pytest.approx(0.4)]
    # the longest gap (1.3 -> 5.0) lies inside the daemon's bulk call
    assert b["idle_gaps"][0] == ["$client.py:862 bulk", pytest.approx(3.7)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_the_trace_recorded_on_the_chip(tmp_path):
    path = tmp_path / "tiny.xplane.pb"
    with gzip.open(RECORDED, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    assert trace_model.find_xplane(str(tmp_path)) == str(path)
    trace = trace_model.load(str(path))
    assert list(trace_model.device_planes(trace)) == ["/device:TPU:0"]
    names = {trace_model.program_name(m[0]) for m in trace_model.module_events(trace)}
    assert "jit__run_ticks_collect_impl" in names and "jit__lease_tick_impl" in names
    ticks, seconds = tick_roofline.ticks_and_seconds(trace)
    assert ticks == 7 and seconds == pytest.approx(0.000342913, rel=1e-6)
    busy, win = trace_model.busy_and_window(trace)
    assert busy == pytest.approx(0.000296492, rel=1e-5) and win == pytest.approx(1.135235, rel=1e-5)
    idle = device_idle_share.reduce(trace, {})
    assert idle == pytest.approx(99.97388, abs=1e-4)
    share = tick_roofline.reduce(trace, ctx(512))
    assert 0 < share < 100
    assert share == pytest.approx(100 * 7 * tick_roofline.soa_bytes_per_tick(512, 2) / 819e9
                                  / seconds)
    b = trace_model.breakdown(trace)
    assert b["device_ops"][0][0] == "jit__run_ticks_collect_impl"
