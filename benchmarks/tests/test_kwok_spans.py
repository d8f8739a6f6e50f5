"""The reductions over the program's own ``kwok/<kind>/<stage>`` spans, on
hand-built ``plane -> line -> events`` dicts whose answers are known."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.reductions import (idle_in_store_bulk_share, idle_unattributed_share,  # noqa: E402
                                   kwok_spans)

TICK = "jit__run_ticks_collect_impl(1)"


def trace(host_events, modules=((TICK, 0.0, 1.0), (TICK, 9.0, 1.0))):
    """A device busy for the first and the last of 10 s, idle for 8."""
    return {"/device:TPU:0": {"XLA Modules": list(modules)},
            "/host:CPU": {"tick-Pod": list(host_events), "python3": [("$threading.py wait", 0.0, 10.0)]}}


def test_interval_arithmetic():
    assert kwok_spans.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert kwok_spans.complement([(0, 2), (3, 4)], 0, 10) == [(2, 3), (4, 10)]
    assert kwok_spans.complement([(1, 2)], 0, 3) == [(0, 1), (2, 3)]
    assert kwok_spans.intersect([(0, 2), (3, 6)], [(1, 4), (5, 9)]) == [(1, 2), (3, 4), (5, 6)]
    assert kwok_spans.length([(1, 2), (3, 4.5)]) == 2.5


EDGES = [("kwok/Pod/ingest", 0.5, 0.5), ("kwok/Pod/pace_wait", 9.0, 0.5)]


@pytest.mark.parametrize("events, in_bulk, unattributed", [
    # idle wholly under one store_bulk span
    ([("kwok/Pod/host_drain", 0.9, 8.2), ("kwok/Pod/store_bulk", 1.0, 8.0)], 100.0, 0.0),
    # a gap half covered by a store_bulk span, the rest under no span
    (EDGES + [("kwok/Pod/store_bulk", 1.0, 4.0)], 50.0, 50.0),
    # spans of the Pod player all through, a quarter of it in store_bulk
    ([("kwok/Pod/ingest", 1.0, 2.0), ("kwok/Pod/store_bulk", 3.0, 2.0),
      ("kwok/Pod/pace_wait", 5.0, 4.0)], 25.0, 0.0),
    # the Node player's spans do not attribute the Pod player's time
    (EDGES + [("kwok/Node/post_tick", 0.0, 10.0), ("kwok/Pod/store_bulk", 1.0, 2.0)], 25.0, 75.0),
    # a stage open when the session started is not in the trace: the idle
    # time is judged from the first to the last span of the Pod player
    ([("kwok/Pod/store_bulk", 3.0, 2.0), ("kwok/Pod/ingest", 5.0, 1.0)], 2 / 3 * 100, 0.0),
], ids=["all_in_bulk", "half_covered", "whole_loop", "node_spans_apart", "head_and_tail"])
def test_idle_shares(events, in_bulk, unattributed):
    t = trace(events)
    assert kwok_spans.length(kwok_spans.device_idle(t)) == pytest.approx(8.0)
    assert idle_in_store_bulk_share.reduce(t, {}) == pytest.approx(in_bulk)
    assert idle_unattributed_share.reduce(t, {}) == pytest.approx(unattributed)


@pytest.mark.parametrize("t", [
    trace([("$client.py:862 bulk", 1.0, 8.0)]),                                   # no kwok/ event
    trace([("kwok/Node/post_tick", 1.0, 8.0)]),                                   # none of the Pod player
    {"/host:CPU": {"tick-Pod": [("kwok/Pod/store_bulk", 1.0, 8.0)]}},               # no device plane
    trace([("kwok/Pod/store_bulk", 0.0, 10.0)], modules=[(TICK, 0.0, 10.0)]),       # never idle
], ids=["no_kwok_event", "no_pod_span", "no_device_plane", "no_idle_time"])
def test_nothing_to_read(t):
    assert idle_in_store_bulk_share.reduce(t, {}) is None
    assert idle_unattributed_share.reduce(t, {}) is None


def test_gaps_are_named_by_the_stage_of_each_kind_that_covers_most():
    t = trace([("kwok/Pod/host_drain", 0.5, 6.5), ("kwok/Pod/store_bulk", 1.0, 6.0),
               ("kwok/Pod/store_bulk", 1.0, 3.0),  # a slice of it, once more
               ("kwok/Pod/ingest", 7.1, 1.0), ("kwok/Node/post_tick", 2.0, 4.0),
               ("kwok/Pod/pace_wait", 8.7, 0.3)],
              modules=[(TICK, 0.0, 1.0), (TICK, 8.5, 0.5), (TICK, 9.0, 1.0)])
    (seconds, by_kind), *rest = kwok_spans.name_gaps(t)
    assert seconds == pytest.approx(7.5) and not rest
    # the inner stage of two that cover alike; a slice twice is counted once
    assert by_kind["Pod"] == ["store_bulk", pytest.approx(0.8)]
    assert by_kind["Node"] == ["post_tick", pytest.approx(4.0 / 7.5)]
    assert kwok_spans.name_gaps(trace([])) == []


def test_the_trace_recorded_on_the_chip(tmp_path):
    """A TPU v5e, the kwok daemon of a 20-node rehearsal, 0.25 s asked for,
    with the program's ``kwok/`` spans in it (brought back by PR 26)."""
    import gzip
    import shutil

    from benchmarks.reductions import trace_model

    path = tmp_path / "tiny.xplane.pb"
    with gzip.open(os.path.join(os.path.dirname(__file__), "data",
                                "tiny_kwok_v5e.xplane.pb.gz"), "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    t = trace_model.load(str(path))
    assert list(trace_model.device_planes(t)) == ["/device:TPU:0"]
    per = {}
    for name, _s, d in kwok_spans.spans(t):
        n, total = per.get(name, (0, 0.0))
        per[name] = (n + 1, total + d)
    assert {n: c for n, (c, _d) in per.items()} == {
        "kwok/Pod/ingest": 6, "kwok/Pod/device_tick": 6, "kwok/Pod/host_drain": 6,
        "kwok/Pod/pace_wait": 10, "kwok/Node/ingest": 5, "kwok/Node/device_tick": 6,
        "kwok/Node/host_drain": 6, "kwok/Node/post_tick": 8, "kwok/Node/pace_wait": 7}
    # (a stage the session saw open and close, and its slices, are both in the count)
    assert per["kwok/Pod/pace_wait"][1] == pytest.approx(0.444101933, rel=1e-6)
    # an idle daemon: no bulk in flight, nearly all of the idle time in pace_wait
    assert idle_in_store_bulk_share.reduce(t, {}) == 0.0
    assert idle_unattributed_share.reduce(t, {}) == pytest.approx(0.15759495, rel=1e-5)
    assert kwok_spans.idle_share(t, "Pod", "pace_wait") == pytest.approx(
        100 - 0.15759495 - kwok_spans.idle_share(t, "Pod", "device_tick")
        - kwok_spans.idle_share(t, "Pod", "ingest") - kwok_spans.idle_share(t, "Pod", "host_drain"),
        abs=1e-6)
    seconds, by_kind = kwok_spans.name_gaps(t)[0]
    assert by_kind["Pod"][0] == "pace_wait" and seconds > 0.05
