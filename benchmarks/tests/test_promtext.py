"""The prom-text delta reader on two recorded scrapes."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.harness import promtext  # noqa: E402

BEFORE = """\
# HELP kwok_tick_stage_seconds per-macro-tick stage time
kwok_tick_stage_seconds_sum{kind="Pod",stage="host_build"} 1.5
kwok_tick_stage_seconds_count{kind="Pod",stage="host_build"} 100
kwok_tick_stage_seconds_sum{kind="Pod",stage="host_drain"} 0.5
kwok_tick_stage_seconds_sum{kind="Pod",stage="store_bulk"} 4.0
kwok_tick_stage_seconds_sum{kind="Node",stage="store_bulk"} 9.0
kwok_stage_transitions_total{kind="Pod",backend="device"} 10000
kwok_jit_compilations_total 30
kwok_lease_renew_lag_seconds{quantile="0.99"} 0.25
"""
AFTER = """\
kwok_tick_stage_seconds_sum{kind="Pod",stage="host_build"} 3.5
kwok_tick_stage_seconds_count{kind="Pod",stage="host_build"} 300
kwok_tick_stage_seconds_sum{kind="Pod",stage="host_drain"} 1.5
kwok_tick_stage_seconds_sum{kind="Pod",stage="store_bulk"} 14.0
kwok_tick_stage_seconds_sum{kind="Node",stage="store_bulk"} 9.5
kwok_stage_transitions_total{kind="Pod",backend="device"} 40000
kwok_jit_compilations_total 33
kwok_lease_renew_lag_seconds{quantile="0.99"} 0.75
kwok_wal_fsync_seconds_sum{shard="0",note="a \\"quoted\\", comma"} 2.0
kwok_wal_fsync_seconds_count{shard="0",note="a \\"quoted\\", comma"} 50
"""


def scrapes():
    return ({"t": 100.0, "kwok": list(promtext.iter_samples(BEFORE))},
            {"t": 140.0, "kwok": list(promtext.iter_samples(AFTER))})


def read(**spec):
    b, a = scrapes()
    return promtext.read({"component": "kwok", **spec}, b, a)


def test_sum_over_other_count_sums_label_sets():
    v = read(how="sum_over_other_count", series="kwok_tick_stage_seconds",
             label_sets=[{"kind": "Pod", "stage": "host_build"},
                         {"kind": "Pod", "stage": "host_drain"}],
             other={"series": "kwok_stage_transitions_total", "labels": {"kind": "Pod"}},
             scale=1e6)
    assert v == pytest.approx((2.0 + 1.0) / 30000 * 1e6)


def test_sum_over_window_uses_the_scrapes_own_clock():
    v = read(how="sum_over_window", series="kwok_tick_stage_seconds",
             labels={"kind": "Pod", "stage": "store_bulk"}, scale=100)
    assert v == pytest.approx(10.0 / 40.0 * 100)


def test_sum_over_count_and_a_series_born_inside_the_window():
    assert read(how="sum_over_count", series="kwok_tick_stage_seconds",
                labels={"kind": "Pod", "stage": "host_build"}) == pytest.approx(2.0 / 200)
    # no sample before the window: the counter started at 0
    assert read(how="sum_over_count", series="kwok_wal_fsync_seconds",
                scale=1e3) == pytest.approx(40.0)


def test_count_delta_gauge_and_nothing_to_read():
    assert read(how="count_delta", series="kwok_jit_compilations_total") == 3
    assert read(how="gauge_at_end", series="kwok_lease_renew_lag_seconds",
                labels={"quantile": "0.99"}) == 0.75
    assert read(how="sum_over_count", series="kwok_no_such_series") is None
    with pytest.raises(ValueError):
        read(how="median_of_chunks", series="kwok_jit_compilations_total")


def test_quoted_label_values_keep_their_commas():
    labels = [ls for n, ls, _v in promtext.iter_samples(AFTER)
              if n == "kwok_wal_fsync_seconds_sum"][0]
    assert labels == {"shard": "0", "note": 'a "quoted", comma'}
