"""Set-up accounts for itself (ISSUE 39): the kwok daemon's start-up
milestones, a node's bring-up on the lease workers' threads
(``NodeBringup`` stages, the Ready wave's wall), a compile that says
whether the persistent cache had it, and the tick loop's lag and
virtual pace as series a window can read."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from kwok_tpu.api.config import KwokConfiguration
from kwok_tpu.cluster.store import ResourceStore
from kwok_tpu.controllers.controller import Controller
from kwok_tpu.controllers.node_lease_controller import (
    NAMESPACE_NODE_LEASE,
    NodeLeaseController,
)
from kwok_tpu.ctl.scale import scale
from kwok_tpu.engine import simulator
from kwok_tpu.metrics.collectors import Registry
from kwok_tpu.stages import default_node_stages, default_pod_stages
from kwok_tpu.utils import telemetry
from kwok_tpu.utils.clock import FakeClock
from kwok_tpu.utils.promtext import iter_samples
from tests.test_tick_stages import make_player, make_pod, stage_table

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def wait_until(cond, budget=60.0):
    deadline = time.monotonic() + budget
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return cond()


def bringup_counts():
    table = stage_table("NodeBringup")
    return {name: table.get(name, (0.0, 0))[1] for name in ("lease_acquire", "node_sync")}


def family(name):
    return {fam.name: fam for fam in telemetry.registry().families()}[name]


@pytest.fixture()
def fresh():
    """The series and the remembered shape keys are the process's: a test
    that counts them starts from none."""
    saved = {k: set(v) for k, v in simulator.ShapeLog._seen.items()}
    simulator.ShapeLog._seen.clear()
    telemetry.tick_stage_family().clear()
    for name in ("kwok_tick_lag_seconds", "kwok_virtual_seconds_played_total",
                 "kwok_compile_stall_seconds", "kwok_device_ticks_total"):
        family(name).clear()
    yield
    simulator.ShapeLog._seen.clear()
    simulator.ShapeLog._seen.update(saved)


# ---------------------------------------------------------------- milestones


def milestone_lines(update):
    reg = Registry()
    update(reg)
    return [(ls, v) for n, ls, v in iter_samples(reg.expose())
            if n == "kwok_process_milestone_seconds"]


def test_milestones_are_set_once_in_order_and_stand_between_scrapes():
    from kwok_tpu.cmd.kwok import _controller_self_metrics

    marks = telemetry.Milestones()
    names = ["main", "device_ready", "apiserver_ready", "leading", "reconciling"]
    for name in names:
        marks.mark(name)
        time.sleep(0.002)
    marks.mark("first_tick", kind="Node")
    marks.mark("first_tick", kind="Pod")
    first = marks.snapshot()
    assert [n for n, _ls, _v in first] == names + ["first_tick", "first_tick"]
    assert [ls for _n, ls, _v in first][-2:] == [{"kind": "Node"}, {"kind": "Pod"}]
    values = [v for _n, _ls, v in first]
    assert values == sorted(values) and values[0] >= 0.0
    # the origin is the process's start where the kernel tells it: the
    # interpreter and the imports of this test run lie before `main`
    assert marks.origin == "the kernel's start of the process"
    assert values[0] > 0.05
    # a second mark of a name (a controller that leads again) moves nothing
    time.sleep(0.01)
    for name in names:
        marks.mark(name)
    marks.mark("first_tick", kind="Pod")
    assert marks.snapshot() == first

    # through the daemon's self-metrics: one gauge a milestone, the same
    # at two scrapes
    saved, telemetry.registry().milestones = telemetry.registry().milestones, marks
    try:
        update = _controller_self_metrics(lambda: None)
        one = milestone_lines(update)
        time.sleep(0.01)
        assert milestone_lines(update) == one
    finally:
        telemetry.registry().milestones = saved
    assert [ls["milestone"] for ls, _v in one] == names + ["first_tick", "first_tick"]
    assert one[-1][0] == {"milestone": "first_tick", "kind": "Pod"}
    assert [v for _ls, v in one] == [round(v, 3) for v in values]


def test_milestones_count_from_the_first_where_proc_cannot_be_read(monkeypatch):
    monkeypatch.setattr(telemetry, "_process_age", lambda: None)
    marks = telemetry.Milestones()
    marks.mark("main")
    time.sleep(0.01)
    marks.mark("device_ready")
    (_m, _l, main), (_d, _l2, ready) = marks.snapshot()
    assert main == 0.0 and 0.01 <= ready < 1.0
    assert marks.origin == "the first milestone"


# ------------------------------------------------------- a node's bring-up


def test_only_a_sync_that_takes_a_node_is_a_lease_acquire(fresh):
    """A renewal, a wait for another holder's live lease and the re-read
    after a Conflict observe nothing; taking the node back after the
    lane lost it (``reacquire``) is an acquisition."""
    store = ResourceStore()
    taken = []
    a = NodeLeaseController(store, "inst-a", lease_duration_seconds=40,
                            on_node_managed=taken.append)
    b = NodeLeaseController(store, "inst-b", lease_duration_seconds=40)
    assert a._sync("n0") > 0 and taken == ["n0"]
    assert bringup_counts()["lease_acquire"] == 1
    assert a._sync("n0") > 0  # a renewal
    assert b._sync("n0") > 0  # a's lease is live: b waits for its expiry
    assert bringup_counts()["lease_acquire"] == 1 and taken == ["n0"]

    # a Conflict: the Lease changes between b's read and its write
    store.patch("Lease", "n0", {"spec": {"holderIdentity": None}}, patch_type="merge",
                namespace=NAMESPACE_NODE_LEASE)
    real_update = store.update

    def racing_update(obj, **kw):
        store.patch("Lease", "n0", {"metadata": {"labels": {"raced": "1"}}},
                    patch_type="merge", namespace=NAMESPACE_NODE_LEASE)
        return real_update(obj, **kw)

    store.update = racing_update
    try:
        assert b._sync("n0") == 0.1
    finally:
        store.update = real_update
    assert bringup_counts()["lease_acquire"] == 1
    assert b._sync("n0") > 0.1  # the re-read takes it
    assert bringup_counts()["lease_acquire"] == 2

    # the lane's renewal of n0 failed: a no longer holds it, and takes it again
    store.patch("Lease", "n0", {"spec": {"holderIdentity": None}}, patch_type="merge",
                namespace=NAMESPACE_NODE_LEASE)
    a._wanted.add("n0")
    a.reacquire("n0")
    assert a._sync("n0") > 0.1
    assert bringup_counts()["lease_acquire"] == 3 and taken == ["n0", "n0"]


def test_bringup_counts_nodes_acquired_and_the_wave_stands_still(fresh):
    """An in-process device-backend controller: N nodes acquired are N
    ``lease_acquire`` and N ``node_sync`` stages on the lease workers'
    threads; renewals and status heartbeats add nothing and leave the
    wave's wall where it was; a Lease taken away is acquired once more; a
    node added moves the wall.  The Node tick thread's own stages still
    make its wall time with those stages running beside it."""
    node_stages = default_node_stages(lease=True)
    heartbeat = [s for s in node_stages if s.name == "node-heartbeat-with-lease"][0]
    heartbeat.delay.duration_milliseconds = 300
    heartbeat.delay.jitter_duration_milliseconds = 400
    store = ResourceStore()
    ctr = Controller(
        store,
        KwokConfiguration(manage_all_nodes=True, backend="device", device_tick_ms=20,
                          node_lease_duration_seconds=4),  # renewed every second
        local_stages={"Node": node_stages, "Pod": default_pod_stages()},
        seed=0,
    )
    n = 12

    def ready():
        nodes = store.list("Node")[0]
        return sum(1 for node in nodes
                   for c in (node.get("status") or {}).get("conditions") or []
                   if c.get("type") == "Ready" and c.get("status") == "True")

    t0 = time.perf_counter()
    ctr.start()
    t_started = time.perf_counter()
    try:
        player = ctr.device_players["Node"]
        assert ctr.device_players["Pod"].wave_wall_s is None
        scale(store, "node", n)
        lane = None
        assert wait_until(lambda: len(ctr.node_leases.held_nodes()) == n and ready() == n)
        lane = ctr.node_leases._lane
        assert wait_until(lambda: len(lane) == n)
        assert wait_until(lambda: bringup_counts() == {"lease_acquire": n, "node_sync": n})
        wall = player.wave_wall_s
        assert 0.0 < wall < time.perf_counter() - t0

        # two rounds of lease renewals and of status heartbeats
        renewed, played = ctr.node_leases.renew_count, player.transitions
        assert wait_until(lambda: ctr.node_leases.renew_count >= renewed + 2 * n
                          and player.transitions >= played + 2 * n)
        assert bringup_counts() == {"lease_acquire": n, "node_sync": n}
        assert player.wave_wall_s == wall

        # somebody deletes a Lease: the lane's renewal fails, the host path
        # takes the node again
        store.delete("Lease", "node-3", namespace=NAMESPACE_NODE_LEASE)
        assert wait_until(
            lambda: bringup_counts() == {"lease_acquire": n + 1, "node_sync": n + 1})
        assert store.get("Lease", "node-3", namespace=NAMESPACE_NODE_LEASE)
        assert player.wave_wall_s == wall  # no row it had not committed

        scale(store, "node", 1, name_prefix="late")
        assert wait_until(lambda: ready() == n + 1)
        assert wait_until(
            lambda: bringup_counts() == {"lease_acquire": n + 2, "node_sync": n + 2})
        assert player.wave_wall_s > wall + 1.0  # the rounds above lie inside it
        started = player._threads[0]
        t_stopping = time.perf_counter()
    finally:
        ctr.stop()
    t_end = time.perf_counter()
    assert not started.is_alive()
    table = stage_table("Node")
    total = sum(s for s, _n in table.values()) - table["compile"][0]
    # the thread started inside ctr.start() and ended inside ctr.stop(), each
    # of which spends time off the thread (the other players' starts and
    # joins): its wall lies between stop's call less start's return and
    # stop's return less start's call
    assert (t_stopping - t_started) * 0.9 <= total <= (t_end - t0) * 1.1, table
    assert not {"lease_acquire", "node_sync"} & set(table)
    # both kinds' first ticks are milestones of the process by now
    got = {(m, ls.get("kind")) for m, ls, _v in telemetry.milestones().snapshot()}
    assert {("first_tick", "Node"), ("first_tick", "Pod")} <= got


# ------------------------------------------------- the loop's lag and pace


def virtual_played():
    return family("kwok_virtual_seconds_played_total").snapshot().get(("Pod",), 0.0)


@pytest.mark.parametrize("k", [1, 5])
def test_a_macro_tick_of_k_plays_k_ticks_of_virtual_time(fresh, k):
    player = make_player(ResourceStore(), capacity=16)
    player.sim.admit(make_pod("pod-0"))
    player.step_batch(20, k)
    assert virtual_played() == pytest.approx(k * 0.020)
    player.step_pipelined(20, k)
    player.flush_pipeline()
    assert virtual_played() == pytest.approx(2 * k * 0.020)
    assert family("kwok_device_ticks_total").snapshot()[("Pod",)] == 2 * k


@pytest.mark.parametrize("paced", [True, False], ids=["paced", "unpaced"])
def test_the_tick_lag_is_observed_once_an_iteration(fresh, paced):
    clock = FakeClock(1000.0)
    store = ResourceStore(clock=clock)
    player = make_player(store, capacity=16, clock=clock)
    player.start(paced=paced)
    try:
        for i in range(8):
            store.create(make_pod(f"pod-{i}"))
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and player.transitions < 8:
            time.sleep(0.01)
            clock.advance(0.0025)
    finally:
        player._done.set()
        clock.advance(0.02)
        player.stop()
    assert player.transitions >= 8
    lag = family("kwok_tick_lag_seconds").snapshot()[("Pod",)]
    # every iteration of the loop opens with one `ingest` stage
    assert lag["count"] == stage_table("Pod")["ingest"][1] > 0
    assert lag["sum"] >= 0.0 and (paced or lag["sum"] == 0.0)
    ticks = family("kwok_device_ticks_total").snapshot()[("Pod",)]
    assert virtual_played() == pytest.approx(ticks * 0.020)
    text = telemetry.registry().expose()
    assert '# TYPE kwok_tick_lag_seconds histogram' in text
    assert 'kwok_tick_lag_seconds_count{kind="Pod"}' in text
    assert "kwok_tick_lag_seconds_max" not in text


# ------------------------------------------------- a compile says what it was

_COMPILE_CHILD = r"""
import json, sys, threading
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
from kwok_tpu.utils import accel, telemetry
from kwok_tpu.engine.simulator import ShapeLog

accel.enable_compile_cache()
mode = sys.argv[1]
log = ShapeLog("Pod")

def program(n):
    return jax.jit(lambda x: (x * n + 1).sum())

def first_use(name, n, gate=None):
    with log.first_use(name, (n,), ("capacity",)):
        if gate is not None:
            gate.wait(30)  # both spans are open before either compiles
        program(n)(jnp.arange(64.0)).block_until_ready()

if mode == "one":
    first_use("alpha", 3)
else:
    # alpha is in the directory, beta is not: at once, on two threads
    gate = threading.Barrier(2)
    ts = [threading.Thread(target=first_use, args=("alpha", 3, gate)),
          threading.Thread(target=first_use, args=("beta", 7, gate))]
    [t.start() for t in ts]
    [t.join(60) for t in ts]
fam = {{f.name: f for f in telemetry.registry().families()}}["kwok_compile_stall_seconds"]
print(json.dumps([[list(lv), d["count"], d["sum"]] for lv, d in fam.snapshot().items()
                  if d["count"]]))
"""


def run_compile_child(tmp_path, mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               # XLA:CPU compiles these in milliseconds: keep them all
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    proc = subprocess.run([sys.executable, "-c", _COMPILE_CHILD.format(root=ROOT), mode],
                          env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = json.loads(proc.stdout.strip().splitlines()[-1])
    return {(lv[1], lv[3]): (count, total) for lv, count, total in rows}, rows


def test_a_compile_is_cold_in_one_process_and_fetched_in_the_next(tmp_path):
    """A fresh ``JAX_COMPILATION_CACHE_DIR``: the first process compiles
    ``alpha`` cold; the second, on the same directory, fetches it while
    another of its threads compiles ``beta`` cold inside a span that is
    open at the same time: each span reads its own thread's programs."""
    first, rows = run_compile_child(tmp_path, "one")
    assert set(first) == {("alpha", "cold")}, rows
    assert rows[0][0] == ["Pod", "alpha", "first", "cold"]
    assert first[("alpha", "cold")][0] == 1 and first[("alpha", "cold")][1] > 0.0
    second, rows = run_compile_child(tmp_path, "two")
    assert set(second) == {("alpha", "fetched"), ("beta", "cold")}, rows
    assert {tuple(lv[:3]) for lv, _c, _s in rows} == {
        ("Pod", "alpha", "first"), ("Pod", "beta", "first")}


def test_a_first_use_with_nothing_to_compile_says_so(fresh):
    """No listener in this process (nothing called
    ``enable_compile_cache``) or a program the jit already holds: the
    span saw no program asked of the backend."""
    log = simulator.ShapeLog("Pod")
    with log.first_use("nothing", (1,), ("capacity",)):
        pass
    stall = family("kwok_compile_stall_seconds").snapshot()
    assert stall[("Pod", "nothing", "first", "none")]["count"] == 1
    # the sums a set-up metric reads are there from the start, at 0
    for outcome in ("cold", "fetched"):
        zero = stall[("Pod", "run_ticks_collect", "signatures", outcome)]
        assert zero["count"] == 0 and zero["sum"] == 0.0
    assert stage_table("Pod")["compile"][1] == 1


# ------------------------------------------------------------ a real daemon


@pytest.fixture()
def home(tmp_path, monkeypatch):
    monkeypatch.setenv("KWOK_TPU_HOME", str(tmp_path / "home"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    return tmp_path


def test_a_daemon_accounts_for_its_set_up(home):
    """A default device-backend cluster: the scrape the benchmark reads
    holds the milestones in order, N of each bring-up stage, the wave's
    wall, the compile stalls by outcome and the loop's two series; what
    set-up froze reads the same a second later."""
    from kwok_tpu.cmd.kwokctl import main as kwokctl_main
    from kwok_tpu.ctl.runtime import BinaryRuntime

    name, n = "setup-accounting", 6
    assert kwokctl_main(["--name", name, "create", "cluster", "--backend", "device"]) == 0
    try:
        rt = BinaryRuntime(name)
        port = rt.load_config()["ports"]["kubelet"]

        def scrape():
            try:
                body = urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
            except OSError:  # the daemon is not listening yet
                return [], ""
            return list(iter_samples(body)), body

        def value(samples, series, **labels):
            vals = [v for s, ls, v in samples if s == series and labels.items() <= ls.items()]
            return sum(vals) if vals else None

        assert kwokctl_main(["--name", name, "scale", "node", "--replicas", str(n)]) == 0
        assert wait_until(lambda: value(
            scrape()[0], "kwok_tick_stage_seconds_count", kind="NodeBringup",
            stage="node_sync") == n, 90)
        assert wait_until(lambda: (value(scrape()[0], "kwok_node_wave_wall_seconds") or 0) > 0, 60)
        time.sleep(1.0)
        one, body = scrape()
        order = [ls["milestone"] for s, ls, _v in one if s == "kwok_process_milestone_seconds"]
        assert order[:5] == ["main", "device_ready", "apiserver_ready", "leading",
                             "reconciling"], order
        assert sorted(order[5:]) == ["first_tick", "first_tick"]
        stamps = [v for s, _ls, v in one if s == "kwok_process_milestone_seconds"]
        assert stamps == sorted(stamps) and stamps[0] > 0.0
        assert "the kernel's start of the process" in body
        frozen = [
            ("kwok_process_milestone_seconds", {}),
            ("kwok_node_wave_wall_seconds", {}),
            ("kwok_tick_stage_seconds_sum", {"kind": "NodeBringup", "stage": "lease_acquire"}),
            ("kwok_tick_stage_seconds_sum", {"kind": "NodeBringup", "stage": "node_sync"}),
            ("kwok_tick_stage_seconds_count", {"kind": "NodeBringup"}),
            ("kwok_compile_stall_seconds_sum", {"kind": "Node", "cause": "signatures"}),
        ]
        assert value(one, "kwok_tick_stage_seconds_count", kind="NodeBringup") == 2 * n
        assert value(one, "kwok_compile_stall_seconds_sum", outcome="cold") > 0.0
        assert value(one, "kwok_tick_lag_seconds_count", kind="Pod") > 0
        time.sleep(1.5)
        two, _body = scrape()
        for series, labels in frozen:
            assert value(two, series, **labels) == value(one, series, **labels), series
        played = (value(two, "kwok_virtual_seconds_played_total", kind="Pod")
                  - value(one, "kwok_virtual_seconds_played_total", kind="Pod"))
        assert 0.5 < played < 3.0  # an idle loop keeps its pace: ~1.5 s in 1.5 s
        assert (value(two, "kwok_tick_lag_seconds_count", kind="Pod")
                > value(one, "kwok_tick_lag_seconds_count", kind="Pod"))
        assert "kwok_tick_lag_seconds_max" not in body
        assert "kwok_leader_election_last_renew_age_seconds" not in body
    finally:
        assert kwokctl_main(["--name", name, "delete", "cluster"]) == 0
