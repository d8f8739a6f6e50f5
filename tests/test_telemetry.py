"""SLO telemetry substrate (kwok_tpu.utils.telemetry) + the observed
increment path on the CEL collectors (metrics/collectors.py): bucket
placement, exposition parity, cardinality backstop, flight-recorder
ring semantics, and the store's commit-time ring feeding delivery lag."""

import json
import threading

import pytest

from kwok_tpu.metrics.collectors import Histogram, Registry
from kwok_tpu.utils import telemetry
from kwok_tpu.utils.telemetry import (
    FlightRecorder,
    HistogramFamily,
    Telemetry,
)


# ------------------------------------------------------ HistogramFamily


def test_family_observe_buckets_and_exposition():
    fam = HistogramFamily(
        "t_fam_seconds", help="h", buckets=(0.01, 0.1, 1.0), labelnames=("op",)
    )
    fam.observe(0.005, "get")   # <= 0.01
    fam.observe(0.05, "get")    # <= 0.1
    fam.observe(0.5, "get")     # <= 1.0
    fam.observe(5.0, "get")     # +Inf
    snap = fam.snapshot()[("get",)]
    assert snap["counts"] == [1, 1, 1, 1]
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(5.555)
    lines = fam.expose_lines()
    assert "# TYPE t_fam_seconds histogram" in lines
    # cumulative per le, labels intact
    assert 't_fam_seconds_bucket{op="get",le="0.01"} 1' in lines
    assert 't_fam_seconds_bucket{op="get",le="0.1"} 2' in lines
    assert 't_fam_seconds_bucket{op="get",le="1"} 3' in lines
    assert 't_fam_seconds_bucket{op="get",le="+Inf"} 4' in lines
    assert 't_fam_seconds_count{op="get"} 4' in lines


def test_family_boundary_value_lands_in_its_bucket():
    fam = HistogramFamily("t_edge", buckets=(0.1, 1.0))
    fam.observe(0.1)  # exactly on the bound -> le=0.1 bucket
    assert fam.snapshot()[()]["counts"] == [1, 0, 0]


def test_family_negative_value_clamped_not_corrupting():
    fam = HistogramFamily("t_neg", buckets=(0.1,))
    fam.observe(-5.0)
    snap = fam.snapshot()[()]
    assert snap["counts"][0] == 1 and snap["sum"] == 0.0


def test_family_label_width_normalized():
    fam = HistogramFamily("t_lab", buckets=(1.0,), labelnames=("a", "b"))
    fam.observe(0.5, "only-one")          # short -> padded
    fam.observe(0.5, "x", "y", "extra")   # long -> truncated
    assert set(fam.snapshot()) == {("only-one", ""), ("x", "y")}


def test_family_cardinality_backstop_folds_overflow():
    fam = HistogramFamily("t_cap", buckets=(1.0,), labelnames=("v",))
    for i in range(telemetry.MAX_CHILDREN + 10):
        fam.observe(0.5, f"v{i}")
    snap = fam.snapshot()
    assert len(snap) <= telemetry.MAX_CHILDREN + 1
    assert fam.overflowed == 10
    other = snap[("(other)",)]
    assert other["count"] == 10


def test_family_quantile_estimate():
    fam = HistogramFamily("t_q", buckets=(0.01, 0.1, 1.0))
    for _ in range(99):
        fam.observe(0.005)
    fam.observe(0.5)
    assert fam.quantile(0.5) <= 0.01
    assert 0.1 <= fam.quantile(1.0) <= 1.0
    empty = HistogramFamily("t_q2", buckets=(1.0,))
    assert empty.quantile(0.5) is None


def test_set_enabled_disarms_observe():
    fam = HistogramFamily("t_off", buckets=(1.0,))
    prev = telemetry.set_enabled(False)
    try:
        fam.observe(0.5)
        assert fam.total_count() == 0
    finally:
        telemetry.set_enabled(prev)
    fam.observe(0.5)
    assert fam.total_count() == 1


def test_family_thread_safety_no_lost_increments():
    fam = HistogramFamily("t_thr", buckets=(1.0,))
    n, threads = 5000, 4

    def worker():
        for _ in range(n):
            fam.observe(0.5)

    ts = [threading.Thread(target=worker) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert fam.total_count() == n * threads


def test_registry_idempotent_and_summary():
    reg = Telemetry()
    a = reg.histogram("t_reg", buckets=(1.0,))
    b = reg.histogram("t_reg", buckets=(9.0,))  # first geometry wins
    assert a is b
    a.observe(0.5)
    summ = reg.summary()
    assert summ["t_reg"]["count"] == 1
    text = reg.expose()
    assert "# TYPE t_reg histogram" in text


# -------------------------------------------------------- FlightRecorder


def test_recorder_ring_overwrites_oldest():
    rec = FlightRecorder(size=3)
    for i in range(5):
        rec.record_tick("Pod", i + 1, {"device_tick_s": 0.001})
    dump = rec.dump()
    assert len(dump["ticks"]) == 3
    assert [t["fired"] for t in dump["ticks"]] == [3, 4, 5]
    assert dump["size"] == 3


def test_recorder_slow_threshold_gates_samples():
    rec = FlightRecorder(size=8)
    rec.slow_threshold_s = 0.25
    rec.note_request("GET", "/r/pods", "system", 0.1)
    rec.note_request("POST", "/r/pods/p1", "system", 0.9, trace_id="abc123")
    dump = rec.dump()
    assert dump["slow_seen"] == 2 and dump["slow_recorded"] == 1
    (sample,) = dump["slow_requests"]
    assert sample["verb"] == "POST"
    assert sample["seconds"] == pytest.approx(0.9)
    # the trace-id exemplar links the outlier to its distributed trace
    assert sample["trace_id"] == "abc123"


def test_recorder_disabled_records_nothing():
    rec = FlightRecorder(size=4)
    prev = telemetry.set_enabled(False)
    try:
        rec.record_tick("Pod", 1, {})
        rec.note_request("GET", "/", "", 99.0)
    finally:
        telemetry.set_enabled(prev)
    dump = rec.dump()
    assert dump["ticks"] == [] and dump["slow_requests"] == []


def test_recorder_dump_is_json_serializable():
    rec = FlightRecorder(size=2)
    rec.record_tick("Node", 2, {"host_build_s": 0.02})
    rec.note_request("GET", "/r/nodes", "system", 99.0, trace_id="t")
    json.dumps(rec.dump())


# --------------------------------------------- collectors.Histogram path


def test_collector_observe_folds_with_set_and_exposes():
    h = Histogram("req_seconds", buckets=[0.1, 1.0])
    h.set(0.05, 7)      # CEL-set hidden le folds into le=0.1
    h.observe(0.5)      # observed lands in le=1.0
    h.observe(2.0)      # observed +Inf
    dist, count, total = h.distribution()
    assert dist == [(0.1, 7), (1.0, 8), (pytest.approx(float("inf")), 9)]
    assert count == 9
    assert total == pytest.approx(7 * 0.05 + 0.5 + 2.0)
    reg = Registry()
    reg.register("req_seconds", h)
    text = reg.expose()
    assert 'req_seconds_bucket{le="0.1"} 7' in text
    assert 'req_seconds_bucket{le="1"} 8' in text
    assert 'req_seconds_bucket{le="+Inf"} 9' in text
    assert "req_seconds_count 9" in text


def test_collector_observe_matches_pure_set_exposition():
    """Parity: N observed values expose identically to the same
    distribution expressed through set() on the visible bounds."""
    a = Histogram("par_a", buckets=[0.1, 1.0])
    for v in (0.05, 0.05, 0.5):
        a.observe(v)
    b = Histogram("par_b", buckets=[0.1, 1.0])
    b.set(0.1, 2)
    b.set(1.0, 1)
    da, ca, _ = a.distribution()
    db, cb, _ = b.distribution()
    assert [c for _, c in da] == [c for _, c in db]
    assert ca == cb


def test_collector_time_observe_and_threads():
    h = Histogram("timed", buckets=[10.0])
    with h.time_observe():
        pass
    assert h.distribution()[1] == 1

    n = 2000

    def worker():
        for _ in range(n):
            h.observe(0.5)

    ts = [threading.Thread(target=worker) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert h.distribution()[1] == 1 + 4 * n


# --------------------------------------------------- store commit ring


def test_store_delivery_lag_ring():
    from kwok_tpu.cluster.store import ResourceStore

    store = ResourceStore()
    # no watcher -> no commit notes -> no lag
    store.create({"apiVersion": "v1", "kind": "Pod",
                  "metadata": {"name": "a", "namespace": "default"}})
    assert store.delivery_lag(store.resource_version) is None
    w = store.watch("Pod")
    try:
        store.create({"apiVersion": "v1", "kind": "Pod",
                      "metadata": {"name": "b", "namespace": "default"}})
        rv = store.resource_version
        hit = store.delivery_lag(rv)
        assert hit is not None
        lag, shard = hit
        assert 0.0 <= lag < 5.0 and shard == 0
    finally:
        w.stop()


def test_store_commit_ring_is_bounded():
    from kwok_tpu.cluster.store import ResourceStore

    store = ResourceStore()
    w = store.watch("Pod")
    try:
        first_rv = None
        for i in range(store.COMMIT_RING + 50):
            store.create({"apiVersion": "v1", "kind": "Pod",
                          "metadata": {"name": f"p{i}", "namespace": "default"}})
            if first_rv is None:
                first_rv = store.resource_version
            w.drain()
        assert len(store._commit_times) <= store.COMMIT_RING
        # the oldest rv aged out of the ring
        assert store.delivery_lag(first_rv) is None
        assert store.delivery_lag(store.resource_version) is not None
    finally:
        w.stop()


def test_sharded_delivery_lag_resolves_owning_shard():
    from kwok_tpu.cluster.sharding import build_sharded_store

    store = build_sharded_store(2)
    w = store.watch("Pod")
    try:
        store.create({"apiVersion": "v1", "kind": "Pod",
                      "metadata": {"name": "x", "namespace": "ns-a"}})
        rv = store.resource_version
        hit = store.delivery_lag(rv)
        assert hit is not None
        lag, shard = hit
        assert shard == store.shard_for("Pod", "ns-a")
    finally:
        w.stop()


# ------------------------------------------------------ review regressions


def test_scheduler_first_seen_bounded_by_pending():
    """A pod that binds OUTSIDE _bind_inner (gang txn, peer binder,
    standby watching) must still drop its time-to-bind anchor when the
    bound echo arrives — the map stays bounded by pending pods."""
    from types import SimpleNamespace

    from kwok_tpu.cluster.store import ResourceStore
    from kwok_tpu.controllers.scheduler import Scheduler

    store = ResourceStore()
    sched = Scheduler(store, gang_policy="none")
    pod = {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": "p", "namespace": "default", "uid": "u1"},
        "spec": {},
        "status": {},
    }
    sched._note_pending(pod)
    assert "u1" in sched._first_seen
    bound = dict(pod, spec={"nodeName": "n0"})
    sched.handle_event(SimpleNamespace(type="MODIFIED", object=bound))
    assert "u1" not in sched._first_seen


def test_apiserver_junk_paths_cannot_mint_kind_labels():
    """Client-supplied junk paths collapse into one '(unknown)' kind
    bucket instead of minting label values until the family cap folds
    legitimate series into '(other)'."""
    import urllib.error
    import urllib.request

    from kwok_tpu.cluster.apiserver import APIServer, _H_REQ
    from kwok_tpu.cluster.store import ResourceStore

    with APIServer(ResourceStore()) as srv:
        for i in range(5):
            try:
                urllib.request.urlopen(
                    f"{srv.url}/r/junk-kind-{i}", timeout=5
                ).read()
            except urllib.error.HTTPError:
                pass
            try:
                urllib.request.urlopen(
                    f"{srv.url}/no-such-head-{i}/x", timeout=5
                ).read()
            except urllib.error.HTTPError:
                pass
    kinds = {lv[1] for lv in _H_REQ.snapshot()}
    assert not any(k.startswith("junk-kind-") for k in kinds), kinds
    assert not any(k.startswith("no-such-head-") for k in kinds), kinds
    assert "(unknown)" in kinds


def test_apiserver_junk_shard_indexes_cannot_mint_shard_labels():
    """/shards/{N} digit strings are client-supplied too: indexes the
    store does not have (any, on an unsharded store) collapse into one
    '(invalid)' bucket instead of minting children."""
    import json as _json
    import urllib.error
    import urllib.request

    from kwok_tpu.cluster.apiserver import APIServer, _H_REQ
    from kwok_tpu.cluster.store import ResourceStore

    with APIServer(ResourceStore()) as srv:
        for i in (7, 99, 123456):
            req = urllib.request.Request(
                f"{srv.url}/shards/{i}/bulk",
                data=_json.dumps({"ops": []}).encode(),
                method="POST",
                headers={"Content-Type": "application/json"},
            )
            try:
                urllib.request.urlopen(req, timeout=5).read()
            except urllib.error.HTTPError:
                pass
    shards = {lv[3] for lv in _H_REQ.snapshot()}
    assert not any(s in ("7", "99", "123456") for s in shards), shards
    assert "(invalid)" in shards


def test_registry_reset_keeps_family_handles_live():
    """reset() clears observations IN PLACE — import-time family
    references (the hot-path module globals) keep feeding series a
    scrape can still see."""
    reg = Telemetry()
    fam = reg.histogram("t_reset", buckets=(1.0,))
    fam.observe(0.5)
    reg.reset()
    assert fam.total_count() == 0
    fam.observe(0.5)  # the old handle still feeds the exposed series
    assert reg.histogram("t_reset") is fam
    assert "t_reset_count 1" in reg.expose()


def test_standby_gang_engine_drops_admit_anchor_on_bound_echo():
    """A non-admitting engine (HA standby) that learns of a gang's
    bind only through watch echoes must drop its time-to-admit anchor,
    or a post-failover re-admit would observe an hours-old first
    sight."""
    from kwok_tpu.cluster.store import ResourceStore
    from kwok_tpu.sched.engine import GangEngine

    engine = GangEngine(ResourceStore())

    def member(name, node=None):
        pod = {
            "apiVersion": "v1",
            "kind": "Pod",
            "metadata": {
                "name": name,
                "namespace": "default",
                "annotations": {"kwok.io/pod-group": "g"},
            },
            "spec": {},
            "status": {},
        }
        if node:
            pod["spec"]["nodeName"] = node
        return pod

    engine.observe("ADDED", member("a"))
    engine.observe("ADDED", member("b"))
    key = ("default", "g")
    assert key in engine._gang_seen
    # the admitting leader bound them; this engine only sees echoes
    engine.observe("MODIFIED", member("a", node="n0"))
    assert key in engine._gang_seen  # one member still pending
    engine.observe("MODIFIED", member("b", node="n1"))
    assert key not in engine._gang_seen


# ------------------------------------------------- CounterFamily / stage


def test_counter_family_counts_exposes_and_disarms():
    reg = Telemetry()
    c = reg.counter("t_shapes_total", help="h", labelnames=("program", "cause"))
    assert reg.counter("t_shapes_total") is c
    c.inc(1, "tick", "first")
    c.inc(2, "tick", "first")
    c.inc(1, "scatter", "capacity")
    prev = telemetry.set_enabled(False)
    try:
        c.inc(5, "tick", "first")
    finally:
        telemetry.set_enabled(prev)
    assert c.snapshot() == {("tick", "first"): 3, ("scatter", "capacity"): 1}
    text = reg.expose()
    assert "# TYPE t_shapes_total counter" in text
    assert 't_shapes_total{program="tick",cause="first"} 3' in text
    assert "t_shapes_total" not in reg.summary()
    reg.reset()
    assert c.snapshot() == {}


def _stage_sums(kind):
    fam = telemetry.tick_stage_family()
    return {
        lv[1]: (d["sum"], d["count"])
        for lv, d in fam.snapshot().items()
        if lv[0] == kind
    }


def test_stage_observes_self_time_and_nests():
    import time

    with telemetry.stage("TStage", "outer") as outer:
        time.sleep(0.02)
        with telemetry.stage("TStage", "inner") as inner:
            time.sleep(0.03)
        # an overlay (compile) is not taken from the stage it stalls
        with telemetry.stage("TStage", "compile", overlay=True) as comp:
            time.sleep(0.01)
    got = _stage_sums("TStage")
    assert {k: n for k, (_s, n) in got.items()} == {"outer": 1, "inner": 1, "compile": 1}
    assert inner.elapsed >= 0.03 and comp.elapsed >= 0.01
    assert outer.elapsed >= inner.elapsed + comp.elapsed + 0.02
    assert outer.nested == pytest.approx(inner.elapsed)
    assert got["inner"][0] == pytest.approx(inner.elapsed)
    assert got["outer"][0] == pytest.approx(outer.elapsed - inner.elapsed)
    # every instant once: the stages less the overlay make the wall time
    total = sum(s for s, _n in got.values()) - got["compile"][0]
    assert total == pytest.approx(outer.elapsed)
    # stages of another thread do not nest in this one's
    with telemetry.stage("TStage", "outer") as again:
        def elsewhere():
            with telemetry.stage("TStage", "inner"):
                pass

        t = threading.Thread(target=elsewhere)
        t.start()
        t.join()
    assert again.nested == 0.0


def test_stage_disabled_keeps_its_clock_and_observes_nothing():
    before = _stage_sums("TOff")
    prev = telemetry.set_enabled(False)
    try:
        with telemetry.stage("TOff", "x") as sp:
            pass
    finally:
        telemetry.set_enabled(prev)
    # the accumulators the callers feed from ``elapsed`` go on; the
    # family saw one attribute check and no observation
    assert sp.elapsed > 0.0
    assert _stage_sums("TOff") == before == {}


@pytest.mark.parametrize(
    "code",
    [
        "from kwok_tpu.utils import telemetry\n"
        "with telemetry.stage('Pod', 'ingest') as sp: pass\n"
        "assert sp.elapsed > 0",
        "import kwok_tpu.cmd.apiserver",
        "import kwok_tpu.cmd.kwokctl",
        "import runpy; runpy.run_path('benchmarks/run.py', run_name='bench_run')",
    ],
    ids=["stage", "apiserver", "kwokctl", "benchmarks_run"],
)
def test_no_jax_in_a_process_that_had_none(code):
    """``stage`` looks jax up in ``sys.modules`` and never imports it:
    the apiserver, kwokctl and the benchmark's parent stay without."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", code + "\nimport sys\nassert 'jax' not in sys.modules, 'jax imported'"],
        cwd=root, env={**os.environ, "PYTHONPATH": root},
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_stage_annotates_on_the_profilers_clock(tmp_path):
    """With jax imported and a profiler session on, a stage is an event
    ``kwok/<kind>/<name>`` of the host plane in the ``.xplane.pb``."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.stage("TProf", "device_tick"):
            jax.numpy.zeros(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {
        ev.name
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    }
    assert "kwok/TProf/device_tick" in names


def test_a_stage_that_outlasts_a_profiler_session_is_in_its_trace(tmp_path):
    """The profiler drops an annotation it did not see open and close,
    and a bulk of seconds outlasts a trace of two: while a session is on,
    the keeper cuts the open stages into slices under their own names.
    Another thread's stages (the Node player's, at tick cadence) are what
    notices the session; nothing polls outside one."""
    import glob
    import time

    import jax
    from jax.profiler import ProfileData

    stop = threading.Event()

    def ticking():
        while not stop.is_set():
            with telemetry.stage("TKeepNode", "pace_wait"):
                time.sleep(0.01)

    def blocked():
        with telemetry.stage("TKeepPod", "host_drain"):
            with telemetry.stage("TKeepPod", "store_bulk"):
                stop.wait(10.0)

    threads = [threading.Thread(target=f) for f in (ticking, blocked)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.1)
        assert not telemetry._KEEPER.in_session
        jax.profiler.start_trace(str(tmp_path))
        try:
            time.sleep(0.5)
        finally:
            jax.profiler.stop_trace()
    finally:
        stop.set()
        for t in threads:
            t.join()
    deadline = time.monotonic() + 5
    while telemetry._KEEPER.in_session and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not telemetry._KEEPER.in_session  # asleep again
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    seconds = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("kwok/TKeepPod/"):
                    seconds[ev.name] = seconds.get(ev.name, 0.0) + ev.duration_ns / 1e9
    # both stages of the blocked thread's chain, for most of the session
    assert set(seconds) == {"kwok/TKeepPod/host_drain", "kwok/TKeepPod/store_bulk"}
    assert all(0.3 <= s <= 0.6 for s in seconds.values()), seconds


def test_a_scrape_sees_the_time_of_a_stage_still_open():
    """A stage is observed as it ends; one that lasts seconds would move
    whole from one side of a scrape to the other.  Every exposition puts
    the open stages' self time so far into the sums, and the observation
    that ends a stage adds only the rest."""
    import time

    entered, leave = threading.Event(), threading.Event()
    spans = {}

    def blocked():
        with telemetry.stage("TOpen", "host_drain") as spans["outer"]:
            time.sleep(0.02)
            with telemetry.stage("TOpen", "store_bulk") as spans["inner"]:
                entered.set()
                leave.wait(10.0)

    t = threading.Thread(target=blocked)
    t.start()
    try:
        assert entered.wait(5.0)
        time.sleep(0.05)
        telemetry.registry().expose()  # a scrape
        first = _stage_sums("TOpen")
        assert first["store_bulk"][0] >= 0.05 and first["store_bulk"][1] == 0
        assert 0.02 <= first["host_drain"][0] < 0.05 and first["host_drain"][1] == 0
        time.sleep(0.05)
        telemetry.registry().expose()
        second = _stage_sums("TOpen")
        assert second["store_bulk"][0] >= first["store_bulk"][0] + 0.05
        # the outer stage's self time does not grow while the inner one runs
        assert second["host_drain"][0] == pytest.approx(first["host_drain"][0], abs=0.005)
    finally:
        leave.set()
        t.join()
    done = _stage_sums("TOpen")
    outer, inner = spans["outer"], spans["inner"]
    assert done["store_bulk"] == (pytest.approx(inner.elapsed), 1)
    assert done["host_drain"] == (pytest.approx(outer.elapsed - inner.elapsed), 1)
