"""The device tick threads account for themselves (ISSUE 26): every
instant of ``DeviceStagePlayer``'s loop lies in one stage of
``kwok_tick_stage_seconds`` (utils/telemetry.stage), a program shape
used for the first time is counted with what made it new, the lease
lane times a renewal to the return of its write (the apiserver's save
histogram is in test_slo_e2e.py, with the other served families)."""

import time

import pytest

from kwok_tpu.cluster.store import EventRecorder, ResourceStore
from kwok_tpu.controllers.device_player import DeviceStagePlayer
from kwok_tpu.engine import simulator
from kwok_tpu.stages import load_builtin
from kwok_tpu.utils import telemetry
from kwok_tpu.utils.clock import FakeClock

NEW_STAGES = ("ingest", "compile", "post_tick", "pace_wait")


def make_pod(name, finalizers=(), annotations=None):
    meta = {"name": name, "namespace": "default", "uid": f"uid-{name}"}
    if finalizers:
        meta["finalizers"] = list(finalizers)
    if annotations:
        meta["annotations"] = dict(annotations)
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": meta,
        "spec": {"nodeName": "node-0", "containers": [{"name": "app", "image": "x"}]},
        "status": {},
    }


def make_player(store, capacity, clock=None, stages=None, recorder=None):
    from kwok_tpu.controllers.pod_controller import PodEnv

    env = PodEnv()
    return DeviceStagePlayer(
        store, "Pod", stages or load_builtin("pod-fast"), capacity=capacity, tick_ms=20,
        clock=clock, recorder=recorder, funcs_for=env.funcs, on_delete=env.release,
    )


def stage_table(kind="Pod"):
    fam = telemetry.tick_stage_family()
    return {lv[1]: (d["sum"], d["count"]) for lv, d in fam.snapshot().items() if lv[0] == kind}


def shapes(kind="Pod"):
    fam = telemetry.registry().counter("kwok_device_new_shapes_total")
    return {lv[1:]: n for lv, n in fam.snapshot().items() if lv[0] == kind}


def ticks_total():
    return telemetry.registry().counter("kwok_device_ticks_total").snapshot().get(("Pod",), 0)


@pytest.fixture(autouse=True)
def fresh():
    """Shape keys are remembered for the process, as the jit cache is,
    and the series are the process's: a test that counts first uses and
    sums stages starts from none."""
    saved = {k: set(v) for k, v in simulator.ShapeLog._seen.items()}
    simulator.ShapeLog._seen.clear()
    reg = telemetry.registry()
    telemetry.tick_stage_family().clear()
    reg.counter("kwok_device_new_shapes_total").clear()
    yield
    simulator.ShapeLog._seen.clear()
    simulator.ShapeLog._seen.update(saved)


@pytest.mark.parametrize("paced,deletes,events",
                         [(True, False, False), (False, False, False), (False, True, False),
                          (False, True, True)],
                         ids=["paced", "unpaced", "unpaced-deletes", "unpaced-events"])
def test_the_stages_of_the_tick_thread_make_its_wall_time(paced, deletes, events):
    """The clock is injected: a paced loop waits for the test to
    advance it.  With ``deletes`` the pods carry a finalizer and are
    deleted once Running: ``pod-delete`` plays them through the delete
    batch, whose commit is one more stage of the sum (``delete_commit``;
    ``_drain_slow``'s two are in the sum when a row goes that way).  With
    ``events`` the stage set is ``pod-general`` and the player has a
    recorder: ``pod-create`` and ``pod-remove-finalizer`` go through
    ``_drain_slow`` and leave an Event each, which ``event_post`` hands
    over a drain at a time."""
    clock = FakeClock(1000.0)
    store = ResourceStore(clock=clock)  # one clock: a deletionTimestamp is the player's time
    annotations = None
    if events:
        stages = load_builtin("pod-general")
        annotations = {f"{s.name}.stage.kwok.x-k8s.io/{k}": v for s in stages
                       for k, v in (("delay", "20ms"), ("jitter-delay", "40ms"))}
        player = make_player(store, capacity=16, clock=clock, stages=stages,
                             recorder=EventRecorder(store, "kwok", clock=clock))
    else:
        player = make_player(store, capacity=16, clock=clock)
    posts = []
    player.post_tick = posts.append
    # pod-ready and pod-delete a pod; with events pod-create, pod-ready and
    # pod-remove-finalizer (the store reaps the pod as its finalizer goes,
    # and pod-delete, which fired for what the row held before, is dropped)
    played = 120 if events else 80 if deletes else 40
    def waited():
        return stage_table().get("pace_wait", (0.0, 0))[1]

    t0 = time.perf_counter()
    player.start(paced=paced)
    t_started = time.perf_counter()
    try:
        for i in range(40):
            store.create(make_pod(
                f"pod-{i}", ("kwok.x-k8s.io/fake",) if deletes and not events else (),
                annotations))
        # virtual time at a quarter of real time: once the first
        # programs have compiled, a paced loop is ahead of its schedule
        deadline = time.monotonic() + 30
        asked = False
        while time.monotonic() < deadline and (
            player.transitions < played or (paced and waited() < 3)
        ):
            if deletes and not asked and player.transitions >= (80 if events else 40):
                asked = True
                for i in range(40):
                    store.delete("Pod", f"pod-{i}", namespace="default")
            time.sleep(0.01)
            clock.advance(0.0025)
    finally:
        t_stopping = time.perf_counter()
        player._done.set()
        clock.advance(0.02)  # wake a paced wait
        player.stop()
    wall = time.perf_counter() - t0
    assert player.transitions >= played and posts
    table = stage_table()
    want = set(NEW_STAGES) | {"device_tick", "host_drain", "host_build", "store_bulk",
                              "fired_scan"}
    if not paced:
        want.discard("pace_wait")
    if deletes:
        want |= {"slow_build", "slow_commit", "event_post"} if events else {"delete_commit"}
        assert store.list("Pod")[0] == []
    if events:
        assert len(store.list("Event")[0]) == 80
    assert want <= {k for k, (_s, n) in table.items() if n > 0}, table
    # every stage reports self time but that compile overlays the stage
    # it stalls: the sum less the overlay is the thread's wall time,
    # which began inside start() and ended inside stop()
    total = sum(s for s, _n in table.values()) - table["compile"][0]
    assert (t_stopping - t_started) * 0.95 <= total <= wall * 1.05, (table, wall)
    # and the accumulators bench.py reads are fed from the same clocks
    assert player.t_device == pytest.approx(table["device_tick"][0])
    slow_build, slow_commit, delete_commit, event_post = (
        table.get(k, (0.0, 0))[0]
        for k in ("slow_build", "slow_commit", "delete_commit", "event_post"))
    assert player.t_store == pytest.approx(
        table["store_bulk"][0] + slow_commit + delete_commit + event_post)
    assert player.t_build == pytest.approx(table["host_build"][0])
    # fired_scan nests in host_drain, which reports self time
    assert player.t_host - player.t_build == pytest.approx(
        table["host_drain"][0] + slow_build + table["fired_scan"][0])


@pytest.mark.parametrize("cause", ["num_ticks", "capacity"])
def test_a_new_shape_is_counted_once_with_its_cause(cause):
    store = ResourceStore()
    player = make_player(store, capacity=8)
    sim = player.sim
    for i in range(4):
        sim.admit(make_pod(f"pod-{i}"))
    player.step_batch(20, 1)
    first = shapes()
    assert first == {("upload", "first"): 1, ("run_ticks_collect", "first"): 1}
    ticks0 = ticks_total()
    if cause == "num_ticks":
        # counts up to COLLECT_TICKS share one program; a longer dispatch
        # is a program of its own length
        player.step_batch(20, 3)
        assert shapes() == first
        player.step_batch(20, simulator.COLLECT_TICKS + 1)
        ticked = 3 + simulator.COLLECT_TICKS + 1
    else:
        for i in range(4, 12):  # past 8 rows: the SoA doubles
            sim.admit(make_pod(f"pod-{i}"))
        assert sim.capacity == 64
        player.step_batch(20, 1)
        ticked = 1
    grown = {k: n - first.get(k, 0) for k, n in shapes().items() if n != first.get(k, 0)}
    want = {("run_ticks_collect", cause): 1}
    if cause == "capacity":
        want[("upload", "capacity")] = 1
        # the rows admitted before the doubling reach the device first,
        # so that the download before the re-upload does not lose them
        want[("scatter_rows", "first")] = 1
    assert grown == want
    assert stage_table()["compile"][1] == 2 + len(want)
    # a repeat of either shape is no new shape and no compile stage
    before = (shapes(), stage_table()["compile"][1])
    if cause == "num_ticks":
        player.step_batch(20, 3)
        player.step_batch(20, simulator.COLLECT_TICKS + 1)
    else:
        player.step_batch(20, 1)
    assert (shapes(), stage_table()["compile"][1]) == before
    assert ticks_total() - ticks0 == 2 * ticked


def test_a_scatter_of_a_new_width_names_its_cause():
    player = make_player(ResourceStore(), capacity=64)
    sim = player.sim
    sim.admit(make_pod("pod-0"))
    player.step_batch(20, 1)
    for width in (1, 3, 3):  # padded to 1, 4, 4 rows
        for i in range(width):
            sim.admit(make_pod(f"pod-{width}-{i}-{time.monotonic_ns()}"))
        player.step_batch(20, 1)
    got = shapes()
    assert got[("scatter_rows", "first")] == 1
    assert got[("scatter_rows", "scatter_width")] == 1


def test_lease_delay_runs_to_the_return_of_the_write():
    from kwok_tpu.controllers.device_lease import DeviceLeaseLane

    class SlowCtrl:
        renew_interval = 10.0
        renew_jitter = 0.04

        def renew_batch(self, names):
            time.sleep(0.05)
            return [n for n in names if n == "lost"]

        def reacquire(self, name):
            pass

    fam = telemetry.registry().histogram("kwok_lease_renew_delay_seconds")
    before = fam.snapshot().get((), {"sum": 0.0, "count": 0})
    lane = DeviceLeaseLane(SlowCtrl(), capacity=16)
    for name in ("a", "b", "lost"):
        lane.register(name)
    assert lane.tick(10_000 + 300) == 2  # 0.3 s past the scheduled time
    after = fam.snapshot()[()]
    # the two renewed leases: the lane's lag plus the write's round trip
    assert after["count"] - before["count"] == 2
    mean = (after["sum"] - before["sum"]) / 2
    assert 0.3 + 0.05 <= mean < 0.3 + 0.05 + 5.0
    assert list(lane.renew_lags) == pytest.approx([0.3] * 3)
    assert shapes("Node").get(("lease_tick", "first")) == 1
    assert stage_table("Node")["compile"][1] >= 1


def commit_rows(kind="Pod"):
    fam = telemetry.registry().histogram("kwok_status_commit_rows")
    return {lv[1]: (d["sum"], d["count"]) for lv, d in fam.snapshot().items() if lv[0] == kind}


@pytest.mark.parametrize("lane", ["staged", "wire"])
def test_commit_rows_counts_every_committed_row_once_under_its_path(lane):
    """``kwok_status_commit_rows{kind,path}``: one observation a request,
    valued with the rows it committed.  Five pods turn Running through
    the batch; the sixth was written by somebody else after the
    player read it, is refused there and goes through ``_drain_slow``.
    Then all six are deleted: five go by the delete batch, the third path;
    the one somebody else wrote meanwhile is refused there too."""
    import contextlib
    import datetime

    from kwok_tpu.cluster.apiserver import APIServer
    from kwok_tpu.cluster.client import ClusterClient
    from kwok_tpu.cluster.informer import InformerEvent

    telemetry.registry().histogram("kwok_status_commit_rows").clear()
    store = ResourceStore()
    with contextlib.ExitStack() as stack:
        handle = store
        if lane == "wire":
            handle = ClusterClient(stack.enter_context(APIServer(store)).url)
        for i in range(6):
            handle.create(make_pod(f"pod-{i}", ("kwok.x-k8s.io/fake",)))
        player = make_player(handle, capacity=8)
        # as start() sets it: a deletionTimestamp is milliseconds from here
        player.sim.epoch = datetime.datetime.now(datetime.timezone.utc)
        for obj in handle.list("Pod")[0]:
            player.events.add(InformerEvent("ADDED", obj))
        player._drain_events()
        handle.patch("Pod", "pod-5", {"status": {"qosClass": "Burstable"}}, "merge",
                     namespace="default", subresource="status")

        def play(until):
            for _ in range(40):
                player.step(100)
                if player.transitions >= until:
                    break
            assert player.transitions == until

        play(6)
        running = commit_rows()
        assert store.get("Pod", "pod-5", namespace="default")["status"]["phase"] == "Running"
        for i in range(6):
            handle.delete("Pod", f"pod-{i}", namespace="default")
        for obj in handle.list("Pod")[0]:
            assert obj["metadata"]["deletionTimestamp"]
            player.events.add(InformerEvent("MODIFIED", obj))
        player._drain_events()
        handle.patch("Pod", "pod-4", {"metadata": {"labels": {"tier": "gold"}}}, "merge",
                     namespace="default")
        play(12)
        assert store.list("Pod")[0] == [] and not player._rows
    got = commit_rows()
    assert running == {"batch": (5.0, 1), "slow": (1.0, 1)}
    assert got == {"batch": (5.0, 1), "delete": (5.0, 1), "slow": (2.0, 2)}
    assert sum(rows for rows, _n in got.values()) == player.transitions


def test_memory_stats_of_a_backend_that_keeps_none():
    from kwok_tpu.utils import accel

    # XLA:CPU has no memory_stats: the gauge is then absent, not 0
    assert accel.memory_stats() == {}
