"""Bring-up guards: nothing on the device path may hide what it runs on.

Covers the compile-cache helper and the platform guard
(kwok_tpu/utils/accel.py), the native loader's keyed artifacts
(kwok_tpu/native/_artifact.py), bench.py's exit code, the device
player's swallowed-error counter, and the controller's player snapshot.
All on the CPU, in seconds, without a cluster.
"""

import ctypes
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from kwok_tpu.utils import accel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**overrides):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    for key, val in overrides.items():
        if val is None:
            env.pop(key, None)
        else:
            env[key] = val
    return env


# ------------------------------------------------------------ compile cache


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them (the
    test process keeps its own jit configuration)."""
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.append((k, v)))
    return calls


def test_cache_dir_from_environment_is_left_alone(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert accel.enable_compile_cache() == "/some/dir"
    assert config_updates == [], "the helper must set nothing when the variable is set"


def test_cache_dir_default_is_fixed_inside_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = accel.enable_compile_cache()
    second = accel.enable_compile_cache()
    assert first == second == os.path.join(REPO, ".jax_compile_cache")
    assert ("jax_compilation_cache_dir", first) in config_updates
    # and a child process, started anywhere, lands on the same directory
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; from kwok_tpu.utils import accel; "
         "print(accel.enable_compile_cache()); "
         "print(jax.config.jax_compilation_cache_dir)"],
        env=_env(JAX_COMPILATION_CACHE_DIR=None), cwd="/", capture_output=True,
        text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [first, first]


def test_compile_stats_count_programs(monkeypatch, tmp_path):
    import jax
    import jax.numpy as jnp

    # with the variable set the helper only starts counting
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    accel.enable_compile_cache()
    before = accel.compile_stats()
    jax.jit(lambda x: x * 3 + before["compilations"])(jnp.ones(7)).block_until_ready()
    after = accel.compile_stats()
    assert after["compilations"] > before["compilations"]
    assert after["compile_seconds"] >= before["compile_seconds"]


# ----------------------------------------------------------- platform guard


def test_require_accelerator_on_cpu_needs_the_explicit_pin(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    info = accel.require_accelerator()
    assert info["platform"] == "cpu" and info["count"] >= 1 and info["device_kind"]
    # the same CPU backend, but nobody asked for it: a fallback
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(accel.NoAccelerator, match="JAX_PLATFORMS=cpu"):
        accel.require_accelerator()
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(accel.NoAccelerator):
        accel.require_accelerator()


def _kwok_daemon(jax_platforms):
    """`kwok --backend device` against a port nobody listens on: the
    device is taken before the apiserver is waited for."""
    return subprocess.run(
        [sys.executable, "-m", "kwok_tpu.cmd.kwok", "--backend", "device",
         "--server", "http://127.0.0.1:9", "--wait-timeout", "0.2",
         "--server-address", ""],
        env=_env(JAX_PLATFORMS=jax_platforms), capture_output=True, text=True,
        timeout=180,
    )


def test_kwok_device_backend_refuses_a_silent_cpu():
    out = _kwok_daemon(None)
    assert out.returncode != 0
    assert "error: --backend device" in out.stderr and "no accelerator" in out.stderr
    assert "kwok controller started" not in out.stdout


def test_kwok_device_backend_starts_on_a_pinned_cpu():
    out = _kwok_daemon("cpu")
    # past the guard: what stops it is the missing apiserver
    assert "error: --backend device" not in out.stderr
    assert "not ready" in out.stderr


# ------------------------------------------------------------ native loader

_C_SRC = "int kwok_answer(void) { return 41 + 1; }\n"


def _artifact_in(tmp_path, monkeypatch):
    from kwok_tpu.native import _artifact

    monkeypatch.setattr(_artifact, "_HERE", str(tmp_path))
    src = tmp_path / "unit.c"
    src.write_text(_C_SRC)

    def command(target):
        return ["g++", "-shared", "-fPIC", "-o", target, "-x", "c", str(src)]

    return _artifact, str(src), command


needs_gxx = pytest.mark.skipif(
    subprocess.run(["which", "g++"], capture_output=True).returncode != 0,
    reason="no g++",
)


@needs_gxx
def test_truncated_or_stale_artifact_is_never_loaded(tmp_path, monkeypatch):
    art, src, command = _artifact_in(tmp_path, monkeypatch)
    path = art.ensure("unit", src, command)
    # a copy cut short, under the very name the loader looks for, and a
    # binary under the fixed name used before artifacts were keyed
    # (loading either would not raise: dlopen maps it and dies of SIGBUS)
    with open(path, "rb") as f:
        whole = f.read()
    os.remove(path)
    with open(path, "wb") as f:
        f.write(whole[:1000])
    (tmp_path / "unit.so").write_bytes(whole[:1000])
    again = art.ensure("unit", src, command)
    with open(again, "rb") as f:
        assert len(f.read()) == len(whole), "the damaged file was handed out"
    assert ctypes.CDLL(again).kwok_answer() == 42
    assert not (tmp_path / "unit.so").exists(), "stale fixed-name artifact kept"
    # another source revision gets another name; the old one goes
    with open(src, "w") as f:
        f.write(_C_SRC.replace("41", "42"))
    newer = art.ensure("unit", src, command)
    assert newer != again and ctypes.CDLL(newer).kwok_answer() == 43
    assert sorted(p.name for p in tmp_path.glob("unit.*.so")) == [os.path.basename(newer)]


@needs_gxx
def test_compiler_failure_is_reported_not_hidden(tmp_path, monkeypatch):
    art, src, command = _artifact_in(tmp_path, monkeypatch)
    with open(src, "w") as f:
        f.write("this is not C\n")
    with pytest.raises(art.BuildError, match="exited 1"):
        art.ensure("unit", src, command)
    got = art.load_unit("kwok_native", lambda: ctypes.CDLL(art.ensure("unit", src, command)),
                        "the fallback")
    assert got is None
    assert art.status()["kwok_native"].startswith("failed: ")
    assert "error" in art.status()["kwok_native"], "compiler output must be kept"
    art.note("kwok_native", "loaded")  # what this process really has


@needs_gxx
def test_concurrent_first_builds_all_end_with_the_artifact(tmp_path):
    (tmp_path / "unit.c").write_text(_C_SRC)
    script = (
        "import ctypes, sys\n"
        "from kwok_tpu.native import _artifact as a\n"
        "a._HERE = sys.argv[1]\n"
        "src = sys.argv[1] + '/unit.c'\n"
        "cmd = lambda t: ['g++', '-shared', '-fPIC', '-o', t, '-x', 'c', src]\n"
        "p = a.ensure('unit', src, cmd)\n"
        "assert ctypes.CDLL(p).kwok_answer() == 42\n"
        "print(p)\n"
    )
    procs = [
        subprocess.Popen([sys.executable, "-c", script, str(tmp_path)], env=_env(),
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for _ in range(6)
    ]
    outs = [p.communicate(timeout=120) for p in procs]
    assert [p.returncode for p in procs] == [0] * 6, [e for _o, e in outs]
    assert len({o.strip() for o, _e in outs}) == 1
    assert len(list(tmp_path.glob("unit.*"))) == 2, "one artifact and the source"


def test_native_status_names_both_units():
    from kwok_tpu import native

    st = native.status()
    assert set(st) == {"fastdrain", "kwok_native"}
    if os.environ.get("KWOK_TPU_NATIVE", "1") != "0" and native.available():
        assert st["kwok_native"] == "loaded"


# ------------------------------------------------------------------ bench.py


@pytest.fixture
def bench(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import bench as mod

    for knob in ("SCHED_NODES", "STORE_PODS", "OBS_PODS", "OVERLOAD_S", "FLEET_TENANTS"):
        monkeypatch.setattr(mod, knob, 0)
    monkeypatch.setattr(mod, "E2E_PODS", 1)
    monkeypatch.setattr(
        mod, "init_backend",
        lambda: {"platform": "cpu", "device_kind": "cpu", "count": 1},
    )
    monkeypatch.setattr(mod, "run_kernel_bench", lambda: {"tps": 123.0, "fired": 7})
    monkeypatch.setattr(
        mod, "run_e2e_bench",
        lambda: {"pods": 1, "transitions": 3, "transitions_per_sec": 9, "setup_s": 1.0},
    )
    return mod


def test_bench_cpu_run_prints_counts_not_rates(bench, capsys):
    assert bench.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert (out["platform"], out["device_kind"], out["device_count"]) == ("cpu", "cpu", 1)
    assert out["value"] is None and out["kernel_fired"] == 7
    assert out["e2e"] == {"pods": 1, "transitions": 3}


def test_bench_section_that_raises_fails_the_run(bench, capsys, monkeypatch):
    def boom():
        raise AssertionError("gate tripped")

    monkeypatch.setattr(bench, "run_e2e_bench", boom)
    assert bench.main() == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["e2e"] == {"error": "AssertionError: gate tripped"}
    assert "error" not in out, "the other sections' numbers stay a result"


def test_bench_without_a_device_prints_no_result(bench, capsys, monkeypatch):
    def nothing():
        raise accel.NoAccelerator("no accelerator here")

    monkeypatch.setattr(bench, "init_backend", nothing)
    assert bench.main() == 1
    cap = capsys.readouterr()
    assert cap.out == "" and "no accelerator here" in cap.err


# --------------------------------------------------- swallowed tick errors


def test_player_counts_what_its_loop_swallows(capsys):
    from kwok_tpu.cluster.store import ResourceStore
    from kwok_tpu.controllers.device_player import DeviceStagePlayer
    from kwok_tpu.stages import default_pod_stages

    player = DeviceStagePlayer(ResourceStore(), "Pod", default_pod_stages(), capacity=8)
    assert player.swallowed_errors == 0

    def bad_hook(now_ms):
        raise RuntimeError("lease lane down")

    player.post_tick = bad_hook
    player.step()
    assert player.swallowed_errors == 1

    def bad_drain(rows, st, t_ms):
        raise ValueError("cannot drain")

    player.post_tick = None
    player._drain_tick = bad_drain
    player._drain_stages(np.zeros((2, 8), np.int8), 0, 100, player._dispatches)
    assert player.swallowed_errors == 3  # one per sub-tick

    def bad_step(*a, **kw):
        raise RuntimeError("tick does not compile")

    player.step = bad_step
    player.start()
    try:
        deadline = threading.Event()
        for _ in range(100):
            if player.swallowed_errors > 3:
                break
            deadline.wait(0.05)
    finally:
        player.stop()
    assert player.swallowed_errors > 3, "the loop went on without counting"
    assert "tick does not compile" in capsys.readouterr().err


# -------------------------------------------------------- controller facade


def test_node_owned_while_a_player_starts():
    """A lease worker lands in _on_node_owned while
    _start_device_controller inserts the next kind's player: iterating
    the live dict raised ``dictionary changed size during iteration``
    and the node's pod catch-up was skipped."""
    from kwok_tpu.api.config import KwokConfiguration
    from kwok_tpu.cluster.store import ResourceStore
    from kwok_tpu.controllers import Controller

    ctr = Controller(
        ResourceStore(),
        KwokConfiguration(manage_all_nodes=True, node_lease_duration_seconds=0),
    )
    inside = threading.Event()
    go_on = threading.Event()
    synced = []

    class Player:
        def __init__(self, kind):
            self.kind = kind

        def sync_node(self, node):
            inside.set()
            assert go_on.wait(10)
            synced.append(self.kind)

    ctr.device_players["Node"] = Player("Node")
    ctr.device_players["Pod"] = Player("Pod")
    errors = []

    def owned():
        try:
            ctr._on_node_owned("node-0")
        except Exception as exc:  # noqa: BLE001 — the assertion below reports it
            errors.append(exc)

    t = threading.Thread(target=owned)
    t.start()
    assert inside.wait(10)
    with ctr._mut:  # what _start_device_controller does for a third kind
        ctr.device_players["Widget"] = Player("Widget")
    go_on.set()
    t.join(10)
    assert not t.is_alive()
    assert errors == []
    assert synced == ["Node", "Pod"]
    assert [(k, b) for k, b, _p in ctr.players()] == [
        ("Node", "device"), ("Pod", "device"), ("Widget", "device"),
    ]


def test_host_fallback_of_a_kind_is_said_out_loud(caplog):
    import logging

    from kwok_tpu.api.config import KwokConfiguration
    from kwok_tpu.api.types import Stage
    from kwok_tpu.cluster.store import ResourceStore
    from kwok_tpu.controllers import Controller

    # weightFrom on a non-annotation path does not lower to the tick
    stage = Stage.from_dict({
        "apiVersion": "kwok.x-k8s.io/v1alpha1",
        "kind": "Stage",
        "metadata": {"name": "odd"},
        "spec": {
            "resourceRef": {"apiGroup": "v1", "kind": "Pod"},
            "selector": {"matchExpressions": [
                {"key": ".metadata.deletionTimestamp", "operator": "DoesNotExist"}]},
            "weight": 1,
            "weightFrom": {"expressionFrom": ".spec.priority"},
            "next": {"statusTemplate": "phase: Running"},
        },
    })
    ctr = Controller(
        ResourceStore(),
        KwokConfiguration(manage_all_nodes=True, backend="device",
                          node_lease_duration_seconds=0),
        local_stages={"Pod": [stage]},
    )
    try:
        with caplog.at_level(logging.WARNING, logger="kwok.controller"):
            ctr.start()
    finally:
        ctr.stop()
    assert "Pod" in ctr.host_fallbacks and "Pod" not in ctr.device_players
    assert ctr.pods is not None, "the kind must still be played, on the host"
    assert any("do not lower" in r.getMessage() for r in caplog.records)
    assert ("Pod", "host") in [(k, b) for k, b, _p in ctr.players()]
