"""Multi-host seam (parallel/distributed.py, VERDICT r01 #10):

1. compute plane — a 2-process jax.distributed CPU world runs ONE
   logical simulator over a cross-process rows mesh with trajectory
   parity vs single-device (distributed_worker.py does the in-world
   checks);
2. ownership plane — two DEVICE-backend kwok daemons shard a cluster's
   rows by lease ownership and the survivor takes over a SIGKILLed
   peer's rows (reference controller.go:286-296 multi-instance
   scale-out)."""

import os
import signal
import socket
import subprocess
import sys
import time

from kwok_tpu.cluster.apiserver import APIServer
from kwok_tpu.cluster.store import ResourceStore

NAMESPACE_NODE_LEASE = "kube-node-lease"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_for(cond, timeout):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.2)
    return cond()


def test_two_process_global_mesh_parity():
    """2 processes x 4 virtual devices = one 8-way rows mesh; SPMD
    ticks fire identically to a single-device run and each process only
    drains its own row block."""
    port = free_port()
    n_rows = 64
    env = {**os.environ, "PYTHONPATH": REPO}
    env.pop("XLA_FLAGS", None)
    workers = [
        subprocess.Popen(
            [
                sys.executable,
                os.path.join(REPO, "tests", "distributed_worker.py"),
                str(pid),
                "2",
                str(port),
                str(n_rows),
            ],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for w in workers:
            out, _ = w.communicate(timeout=240)
            outs.append(out)
    finally:
        for w in workers:
            if w.poll() is None:
                w.kill()
    for w, out in zip(workers, outs):
        assert w.returncode == 0, out
    lines = [
        line
        for out in outs
        for line in out.splitlines()
        if line.startswith("proc=")
    ]
    assert len(lines) == 2, outs
    assert all("parity=OK" in line and "block_ok=True" in line for line in lines), lines
    # the two processes drained disjoint halves that sum to the total
    totals = [int(line.split("total=")[1].split()[0]) for line in lines]
    locals_ = [int(line.split("local_fired=")[1].split()[0]) for line in lines]
    assert totals[0] == totals[1] == sum(locals_)
    assert all(n > 0 for n in locals_)


def spawn_device_kwok(server_url, ident, work_dir, lease_s=4):
    """A device-backend daemon with what it leaves behind kept under
    ``work_dir``: its own compile cache (a checkout's shared
    ``.jax_compile_cache/`` answers with entries of other runs, and
    XLA:CPU logs pages for each one it loads) and its output in a file
    (nobody reads a pipe here, and a daemon blocks on a full one)."""
    with open(os.path.join(work_dir, f"{ident}.log"), "w") as log:
        return subprocess.Popen(
            [
                sys.executable,
                "-m",
                "kwok_tpu.cmd.kwok",
                "--server",
                server_url,
                "--id",
                ident,
                "--backend",
                "device",
                "--node-lease-duration-seconds",
                str(lease_s),
                "--server-address",
                "",
                # sharding needs BOTH instances active: node-lease
                # ownership partitions the rows; process-level leader
                # election would park one instance as a standby
                "--no-leader-elect",
            ],
            stdout=log,
            stderr=subprocess.STDOUT,
            env={
                **os.environ,
                "PYTHONPATH": REPO,
                "JAX_PLATFORMS": "cpu",
                "JAX_COMPILATION_CACHE_DIR": os.path.join(work_dir, "jax_cache"),
            },
            start_new_session=True,
        )


def make_node(name):
    return {
        "apiVersion": "v1",
        "kind": "Node",
        "metadata": {"name": name},
        "spec": {},
        "status": {},
    }


def make_pod(name, node):
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": name, "namespace": "default"},
        "spec": {"nodeName": node, "containers": [{"name": "c", "image": "i"}]},
        "status": {},
    }


def test_device_backend_shards_rows_and_survives_kill(tmp_path):
    """Two device-backend daemons split the nodes by lease ownership
    (each simulates only its own rows); killing one hands its rows to
    the survivor, which keeps driving them."""
    store = ResourceStore()
    with APIServer(store) as srv:
        a = spawn_device_kwok(srv.url, "kwok-a", str(tmp_path))
        b = None
        try:
            # phase 1: A owns the first node alone
            store.create(make_node("n0"))

            def holder(name):
                try:
                    lease = store.get("Lease", name, namespace=NAMESPACE_NODE_LEASE)
                    return (lease.get("spec") or {}).get("holderIdentity")
                except KeyError:
                    return None

            assert wait_for(lambda: holder("n0") == "kwok-a", 60), holder("n0")

            # phase 2: B joins; new nodes land on B (A defers to B's
            # lease or vice versa — whichever grabs first, ownership is
            # EXCLUSIVE, which is the sharding invariant)
            b = spawn_device_kwok(srv.url, "kwok-b", str(tmp_path))
            time.sleep(2)
            for i in range(1, 5):
                store.create(make_node(f"n{i}"))
            assert wait_for(
                lambda: all(holder(f"n{i}") in ("kwok-a", "kwok-b") for i in range(5)),
                60,
            )
            owners = {f"n{i}": holder(f"n{i}") for i in range(5)}
            # pods on every node converge regardless of which instance
            # owns the rows
            for i in range(5):
                store.create(make_pod(f"p{i}", f"n{i}"))

            def running(name):
                try:
                    return (store.get("Pod", name).get("status") or {}).get(
                        "phase"
                    ) == "Running"
                except KeyError:
                    return False

            assert wait_for(lambda: all(running(f"p{i}") for i in range(5)), 90)

            # phase 3: kill A hard; B takes over A's rows after expiry
            os.killpg(os.getpgid(a.pid), signal.SIGKILL)
            a.wait(timeout=10)
            assert wait_for(
                lambda: all(holder(f"n{i}") == "kwok-b" for i in range(5)), 60
            ), {f"n{i}": holder(f"n{i}") for i in range(5)}

            # and B actually simulates the inherited rows: a fresh pod
            # on a node A used to own reaches Running
            victim = next(
                (n for n, o in owners.items() if o == "kwok-a"), "n0"
            )
            store.create(make_pod("after-kill", victim))
            assert wait_for(lambda: running("after-kill"), 90)
        finally:
            for proc in (a, b):
                if proc is not None and proc.poll() is None:
                    os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
                    proc.wait(timeout=10)
