"""Unconditional clean shutdown (VERDICT r04 next-#2): stopping the
device player mid-drain — even with a pathologically slow store — must
end the tick thread promptly and let the process exit rc=0, never the
daemon-thread-killed-mid-XLA abort (rc=134).  Reference analog: the
controller's Stop cancels its context and the play workers drain
(pkg/kwok/controllers/controller.go:286-296)."""

import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os, sys, time, threading
sys.path.insert(0, %(repo)r)
import jax
jax.config.update("jax_platforms", "cpu")

from kwok_tpu.cluster.store import ResourceStore
from kwok_tpu.controllers.device_player import DeviceStagePlayer
from kwok_tpu.controllers.pod_controller import PodEnv
from kwok_tpu.stages import load_builtin

N = 20000

class SlowStore(ResourceStore):
    # status commits crawl: a macro-tick outlives any bounded grace
    def apply_status_batch(self, kind, items, exclude=None):
        time.sleep(1.0)
        return super().apply_status_batch(kind, items, exclude=exclude)

store = SlowStore()
stages = load_builtin("pod-general") + load_builtin("pod-chaos")
env = PodEnv()
player = DeviceStagePlayer(
    store, "Pod", stages, capacity=N, tick_ms=100,
    funcs_for=env.funcs, on_delete=env.release, seed=7,
)
pod = {
    "apiVersion": "v1", "kind": "Pod",
    "metadata": {"name": "p", "namespace": "default", "uid": "u",
                 "labels": {"pod-container-running-failed.stage.kwok.x-k8s.io": "true"}},
    "spec": {"nodeName": "n", "containers": [{"name": "c", "image": "x"}]},
    "status": {},
}
ops = []
for i in range(N):
    p = {k: (dict(v) if isinstance(v, dict) else v) for k, v in pod.items()}
    p["metadata"] = dict(pod["metadata"], name=f"p{i}")
    ops.append({"verb": "create", "data": p})
for i in range(0, N, 5000):
    store.bulk(ops[i:i+5000])
player.start(paced=False)
deadline = time.time() + 60
while len(player._rows) < N and time.time() < deadline:
    time.sleep(0.2)
# let a macro-tick drain get properly underway against the slow store
while player.patches == 0 and time.time() < deadline:
    time.sleep(0.2)
MODE = os.environ.get("MODE", "clean")
if MODE == "crash":
    # the embedder crashes mid-drain, never calling stop(): the atexit
    # net must abort the drain, join the thread, and exit without the
    # teardown abort
    print("CRASHING", flush=True)
    raise SystemExit(3)
t0 = time.time()
player.stop()
took = time.time() - t0
alive = any(t.is_alive() for t in player._threads)
print(f"STOPPED in {took:.1f}s alive={alive}", flush=True)
assert not alive, "tick thread survived stop()"
assert took < 60, f"stop() took {took:.1f}s"
"""


def run_mode(mode, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu", MODE=mode)
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT % {"repo": REPO}],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    return proc, time.time() - t0


def test_stop_mid_drain_exits_clean():
    proc, wall = run_mode("clean")
    assert "STOPPED" in proc.stdout, proc.stdout + proc.stderr
    assert proc.returncode == 0, (
        f"rc={proc.returncode}\n{proc.stdout}\n{proc.stderr[-2000:]}"
    )
    assert "Aborted" not in proc.stderr and "terminate called" not in proc.stderr


def test_crash_without_stop_still_no_abort():
    """A SystemExit from an embedder that never calls stop() mid-drain
    must not turn into rc=134 at teardown (the atexit net joins)."""
    proc, wall = run_mode("crash")
    assert proc.returncode == 3, (
        f"rc={proc.returncode}\n{proc.stdout}\n{proc.stderr[-2000:]}"
    )
    assert "terminate called" not in proc.stderr
