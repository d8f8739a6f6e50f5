"""The staged drain against an in-process store (build, one
``apply_status_batch``, confirm; a refused row played as a merge
patch), with the native build/confirm loops and with their Python twins
(reference hot loop: pkg/kwok/controllers/pod_controller.go:196-360 —
per-object patch with per-write resourceVersion, NotFound releasing the
object)."""

import time

import pytest

from kwok_tpu.cluster.informer import WatchOptions
from kwok_tpu.cluster.store import ResourceStore
from kwok_tpu.controllers import device_player
from kwok_tpu.controllers.device_player import DeviceStagePlayer
from kwok_tpu.controllers.pod_controller import PodEnv
from kwok_tpu.stages import load_builtin

from tests.test_controllers import make_pod


@pytest.fixture(
    autouse=True,
    params=[
        pytest.param(
            "native",
            marks=pytest.mark.skipif(
                device_player._FAST is None, reason="native fastdrain unavailable"
            ),
        ),
        "python",
    ],
)
def drain_loops(request, monkeypatch):
    if request.param == "python":
        monkeypatch.setattr(device_player, "_FAST", None)
    return request.param


def make_player(store, capacity=16):
    stages = load_builtin("pod-general") + load_builtin("pod-chaos")
    env = PodEnv()
    player = DeviceStagePlayer(
        store, "Pod", stages, capacity=capacity, tick_ms=100,
        funcs_for=env.funcs, on_delete=env.release, seed=5,
    )
    return player


def chaos_pod(name):
    pod = make_pod(name)
    pod["metadata"]["labels"] = {
        "pod-container-running-failed.stage.kwok.x-k8s.io": "true"
    }
    return pod


def drive(player, rounds=8):
    for _ in range(rounds):
        player._drain_events()
        player.step_batch(100, 10)


def test_drain_commits_and_matches_store_state():
    store = ResourceStore()
    for i in range(4):
        store.create(chaos_pod(f"p{i}"))
    player = make_player(store)
    player.cache = player._informer.watch_with_cache(
        WatchOptions(), player.events, done=player._done
    )
    time.sleep(0.2)
    drive(player)
    assert player.transitions >= 8  # all 4 pods cycling
    # the store's objects carry coherent status + monotonically
    # advancing resourceVersions written by the batch
    for i in range(4):
        obj = store.get("Pod", f"p{i}", namespace="default")
        assert obj["status"]["phase"] in ("Running", "Failed")
        assert int(obj["metadata"]["resourceVersion"]) > 4
        # the row mirror equals the stored instance
        row = player._rows[("default", f"p{i}")]
        assert player.sim.objects[row]["status"] == obj["status"]
    player._done.set()


def test_a_second_status_watcher_sees_the_transitions():
    """A second watcher with status interest is handed the events of
    every batch the player commits."""
    store = ResourceStore()
    for i in range(2):
        store.create(chaos_pod(f"p{i}"))
    player = make_player(store)
    player.cache = player._informer.watch_with_cache(
        WatchOptions(), player.events, done=player._done
    )
    w = store.watch("Pod")
    time.sleep(0.2)
    drive(player)
    assert player.transitions >= 4
    # the external watcher saw the status transitions
    events = list(w._events)
    assert any(
        (ev.object.get("status") or {}).get("phase") == "Failed"
        for ev in events
    )
    w.stop()
    player._done.set()


def test_drain_releases_rows_gone_from_store():
    """A row whose object vanished from the store (external delete not
    yet drained) must be released: the batch answers None for it."""
    store = ResourceStore()
    store.create(chaos_pod("p0"))
    player = make_player(store)
    player.cache = player._informer.watch_with_cache(
        WatchOptions(), player.events, done=player._done
    )
    time.sleep(0.2)
    drive(player, 4)
    assert ("default", "p0") in player._rows
    # strip the stage-added finalizer, then delete out from under the
    # player; do not drain the events
    store.patch("Pod", "p0", {"metadata": {"finalizers": None}}, "merge",
                namespace="default")
    store.delete("Pod", "p0", namespace="default")
    player.events.drain()  # discard the DELETED event: the drain must cope alone
    drive(player, 12)
    assert ("default", "p0") not in player._rows
    player._done.set()


def test_a_stale_mirror_never_overwrites_an_external_write():
    """An external write replacing the stored instance between drains:
    the batch must NOT commit through the stale mirror (the store
    refuses the row by resourceVersion and keeps the external write),
    and the informer event re-syncs."""
    store = ResourceStore()
    store.create(chaos_pod("p0"))
    player = make_player(store)
    player.cache = player._informer.watch_with_cache(
        WatchOptions(), player.events, done=player._done
    )
    time.sleep(0.2)
    drive(player, 6)
    # external annotation write -> new stored instance, rv bumped
    store.patch(
        "Pod", "p0", {"metadata": {"annotations": {"x": "1"}}},
        "merge", namespace="default",
    )
    drive(player, 8)
    obj = store.get("Pod", "p0", namespace="default")
    assert obj["metadata"]["annotations"] == {"x": "1"}, (
        "external write lost through a stale-mirror commit"
    )
    # and the cycle kept going after the event re-sync
    assert obj["status"]["phase"] in ("Running", "Failed")
    player._done.set()


def test_drain_converges_under_external_interleaving():
    """Stress the drain's sharpest edges: external writers
    patching labels/annotations, deleting pods, and re-creating them
    WHILE the drain churns.  Invariants at the end: every
    surviving pod's store object is coherent (status written by some
    stage, rv monotonic), the player's mirrors equal the store state,
    and no row leaked after deletes."""
    import random

    rng = random.Random(7)
    store = ResourceStore()
    N = 64
    for i in range(N):
        store.create(chaos_pod(f"p{i}"))
    player = make_player(store, capacity=N + 16)
    player.cache = player._informer.watch_with_cache(
        WatchOptions(), player.events, done=player._done
    )
    time.sleep(0.2)
    drive(player, 4)
    deleted = set()
    for round_no in range(12):
        # a burst of external mutations between drains
        for _ in range(6):
            i = rng.randrange(N)
            name = f"p{i}"
            op = rng.random()
            try:
                if op < 0.5:
                    store.patch(
                        "Pod", name,
                        {"metadata": {"annotations": {"ext": str(round_no)}}},
                        "merge", namespace="default",
                    )
                elif op < 0.75 and name not in deleted:
                    store.patch(
                        "Pod", name, {"metadata": {"finalizers": None}},
                        "merge", namespace="default",
                    )
                    store.delete("Pod", name, namespace="default")
                    deleted.add(name)
                elif name in deleted:
                    store.create(chaos_pod(name))
                    deleted.discard(name)
            except Exception:  # noqa: BLE001 — racing the drain is the point
                pass
        drive(player, 1)
    # let everything settle
    drive(player, 6)
    pods, _ = store.list("Pod")
    by_name = {p["metadata"]["name"]: p for p in pods}
    # no zombie rows: every player row maps to a live store object
    for (ns, name), row in list(player._rows.items()):
        assert name in by_name, f"row for deleted pod {name} leaked"
        mirror = player.sim.objects[row]
        assert mirror is not None
        assert mirror["status"] == by_name[name]["status"], name
        assert (
            mirror["metadata"]["resourceVersion"]
            == by_name[name]["metadata"]["resourceVersion"]
        ), name
    # surviving managed pods all progressed through the FSM
    for name, p in by_name.items():
        st = p.get("status") or {}
        if ("default", name) in player._rows:
            assert st.get("phase") in ("Running", "Failed", "Pending"), (name, st)
    player._done.set()
