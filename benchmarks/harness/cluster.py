"""Cluster driver: one default ``kwokctl create cluster --backend device``
cluster per run, cut from ``chip_smoke.py::phase_deployed`` into create /
nodes / teardown.

The one departure from the CLI: ``kwokctl create cluster`` is
``install`` + ``up`` + ``ready`` (``cmd/kwokctl.py::cmd_create_cluster``);
the driver makes the same three calls with the CLI parser's own defaults
and, between ``install`` and ``up``, points the kwok daemon's command at
``harness/traced_daemon.py``.  That wrapper runs ``kwok_tpu.cmd.kwok.main``
with the same arguments; beside it one thread sleeps in a blocking read of
a FIFO until the harness asks for a profiler trace or the device's memory
statistics, which only the process that holds the chip can give.  (ISSUE
25 wanted the wrapper in traced runs only; every run has to name the
device's peak memory, which nothing else can read, so every run has the
sleeping thread, and traced and untraced runs measure one process.)"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.request

from . import promtext

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
WRAPPER = os.path.join(HERE, "traced_daemon.py")
CONTROL_ENV = "KWOK_BENCH_CONTROL_DIR"
FIFO = "commands"  # as in traced_daemon.py


class Failed(Exception):
    """The run cannot give a result; the message says why."""


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


_T0 = time.monotonic()


def wait_for(pred, timeout: float, what: str, poll: float = 0.5):
    deadline = time.monotonic() + timeout
    while True:
        got = pred()
        if got:
            return got
        if time.monotonic() >= deadline:
            raise Failed(f"timed out after {timeout:.0f}s: {what}")
        time.sleep(poll)


def is_ready(obj: dict) -> bool:
    """A Node or a Pod whose Ready condition is True."""
    return any(
        c.get("type") == "Ready" and c.get("status") == "True"
        for c in (obj.get("status") or {}).get("conditions") or []
    )


def pid_alive(pid: int) -> bool:
    """False once ``pid`` has exited; a zombie child of this process (the
    runtime forks the components from the caller) is reaped and is dead."""
    try:
        os.waitpid(pid, os.WNOHANG)
    except (ChildProcessError, OSError):
        pass
    try:
        with open(f"/proc/{pid}/stat", encoding="ascii") as f:
            return f.read().rsplit(")", 1)[-1].split()[0] != "Z"
    except (OSError, IndexError):
        return False


def pid_gone(pid: int, timeout: float) -> bool:
    deadline = time.monotonic() + timeout
    while pid_alive(pid) and time.monotonic() < deadline:
        time.sleep(0.1)
    return not pid_alive(pid)


class Cluster:
    """One cluster under ``<work>/home``; ``work`` is this run's scratch
    directory inside the checkout."""

    def __init__(self, name: str, work: str, config: dict):
        self.name = name
        self.work = work
        self.config = config
        self.control = os.path.join(work, "control")
        self.home = os.path.join(work, "home")
        self.kwok_pid = None
        self.device = None
        self.kubelet = None
        self.server = None
        self.client = None
        self.rt = None

    # ------------------------------------------------------------- create

    def create(self) -> dict:
        """Install, wrap the daemon, start, wait for the daemon's device.
        Returns ``{"platform", "kind", "count"}`` as the daemon reports."""
        import yaml

        os.environ["KWOK_TPU_HOME"] = self.home
        os.environ[CONTROL_ENV] = self.control
        os.environ["PYTHONPATH"] = ROOT + (
            os.pathsep + os.environ["PYTHONPATH"] if os.environ.get("PYTHONPATH") else ""
        )
        self.reap()  # a run that was killed may have left one behind
        os.makedirs(self.control)
        os.mkfifo(os.path.join(self.control, FIFO))
        conf_path = os.path.join(self.work, "kwok-config.yaml")
        with open(conf_path, "w", encoding="utf-8") as f:
            yaml.safe_dump(
                {
                    "apiVersion": "config.kwok.x-k8s.io/v1alpha1",
                    "kind": "KwokConfiguration",
                    "options": self.config["kwok_configuration"],
                },
                f,
            )
        from kwok_tpu.cmd import kwokctl
        from kwok_tpu.ctl.runtime import BinaryRuntime

        a = kwokctl.build_parser().parse_args(
            ["--name", self.name, "create", "cluster", "--config", conf_path]
            + list(self.config["create_cluster_args"])
        )
        self.rt = rt = BinaryRuntime(self.name)
        rt.install(
            secure=a.secure, backend=a.backend, config_paths=a.config,
            controller_args=a.controller_arg, enable_tracing=a.enable_tracing,
            chaos_profile=a.chaos_profile or None, flow_config=a.flow_config or None,
            max_inflight=a.max_inflight, controller_replicas=a.controller_replicas,
            leader_elect=a.leader_elect, gang_policy=a.gang_policy,
            store_shards=a.store_shards,
        )
        self._wrap_daemon(rt)
        rt.up(wait=120)
        if not rt.ready(timeout=120):
            raise Failed("cluster failed to become ready")
        self.client = rt.client(timeout=180.0)
        conf = rt.load_config()
        self.kubelet, self.server = conf["ports"]["kubelet"], conf["serverURL"]
        self.kwok_pid = self._pid("kwok-controller")

        def daemon_device():
            if not rt.component_alive("kwok-controller"):
                raise Failed("the kwok daemon exited at start: no accelerator, or "
                             "another process holds the chip (logs/kwok-controller.log)")
            try:
                ms = self.kwok_metrics()
            except OSError:
                return None
            for name, d, _v in ms:
                if name == "kwok_device_info":
                    return {"platform": d["platform"], "kind": d["device_kind"],
                            "count": int(d["devices"])}
            return None

        self.device = wait_for(daemon_device, 180, "kwok daemon reports its device")
        return self.device

    def _pid(self, component: str) -> int:
        with open(os.path.join(self.rt.workdir, "pids", f"{component}.pid"),
                  encoding="utf-8") as f:
            return int(f.read().strip())

    def _wrap_daemon(self, rt) -> None:
        path = os.path.join(rt.workdir, "components.json")
        with open(path, encoding="utf-8") as f:
            comps = json.load(f)
        wrapped = 0
        for c in comps:
            args = c["args"]
            for i in range(len(args) - 1):
                if args[i] == "-m" and args[i + 1] == "kwok_tpu.cmd.kwok":
                    args[i:i + 2] = [WRAPPER]
                    wrapped += 1
                    break
        if wrapped != 1:
            raise Failed(f"expected one kwok daemon among the components, found {wrapped}")
        with open(path, "w", encoding="utf-8") as f:
            json.dump(comps, f)

    # -------------------------------------------------------------- nodes

    def scale_nodes(self, n: int) -> None:
        """``kwokctl scale node --replicas n`` as a user runs it, then
        wait until all are Ready."""
        cmd = [sys.executable, "-m", "kwok_tpu.cmd.kwokctl", "--name", self.name,
               "scale", "node", "--replicas", str(n)]
        proc = subprocess.run(cmd, stdout=subprocess.DEVNULL, timeout=600)
        if proc.returncode != 0:
            raise Failed(f"kwokctl scale node exited {proc.returncode}")

        def ready():
            nodes = self.client.list("Node")[0]
            return len(nodes) == n and all(map(is_ready, nodes))

        wait_for(ready, 300, f"{n} nodes Ready")

    # ------------------------------------------------------------ scrapes

    def _scrape(self, url: str) -> list:
        body = urllib.request.urlopen(url, timeout=30).read().decode()
        return list(promtext.iter_samples(body))

    def kwok_metrics(self) -> list:
        return self._scrape(f"http://127.0.0.1:{self.kubelet}/metrics")

    def scrape(self) -> dict:
        """Both components' ``/metrics`` with the host time of the read."""
        return {"t": time.monotonic(), "kwok": self.kwok_metrics(),
                "apiserver": self._scrape(f"{self.server}/metrics")}

    # ------------------------------------------------------- the save loop

    def _snapshots(self) -> set:
        """The whole snapshots in the apiserver's archive (a save writes the
        state file, then its copy here under a temporary name, then renames)."""
        try:
            return {fn for fn in os.listdir(os.path.join(self.rt.workdir, "pitr"))
                    if fn.startswith("snap-") and fn.endswith(".json")}
        except OSError:
            return set()

    def wait_save_end(self, timeout: float) -> bool:
        """Return as a save of the apiserver ends.  It saves the whole store
        ``--save-interval`` (10 s) after the last save ended, for seconds
        at these sizes, and every request is slower meanwhile: a window
        that opens here meets the saves at the same phase in every run.
        False when none ended in ``timeout`` (the window opens anyway)."""
        had = self._snapshots()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._snapshots() - had:
                return True
            time.sleep(0.05)
        return False

    # ------------------------------------------------------ daemon control

    def ask_daemon(self, command: str, answer: str, timeout: float, arg: str = ""):
        """Write one command line into the wrapper's FIFO and wait for its
        ``answer`` file; returns its text."""
        ans = os.path.join(self.control, answer)
        if os.path.exists(ans):
            os.remove(ans)

        def opened():  # no reader yet (or a dead daemon) is ENXIO, not a hang
            try:
                return os.open(os.path.join(self.control, FIFO), os.O_WRONLY | os.O_NONBLOCK)
            except OSError:
                return None

        fd = wait_for(opened, 30, "the daemon's wrapper reads its FIFO", 0.05)
        try:
            os.write(fd, (f"{command} {arg}".strip() + "\n").encode())
        finally:
            os.close(fd)
        wait_for(lambda: os.path.exists(ans), timeout, f"daemon answers {command}", 0.05)
        with open(ans, encoding="utf-8") as f:
            return f.read()

    def memory_peak_bytes(self):
        stats = json.loads(self.ask_daemon("memstats", "memstats.json", 30))
        return stats.get("peak_bytes_in_use")

    # ----------------------------------------------------------- teardown

    def save_logs(self, dest: str) -> list:
        """Copy the component logs to ``dest``; names of those that hold
        a Traceback."""
        bad = []
        if self.rt is None or not os.path.isdir(self.rt.workdir):
            return bad
        for fn in self.rt.collect_logs(dest):
            if fn.endswith(".log") and fn != "audit.log":
                with open(os.path.join(dest, fn), "rb") as f:
                    if b"Traceback (most recent call last)" in f.read():
                        bad.append(fn)
        return bad

    def stop_controllers(self) -> bool:
        """Stop every component but the apiserver as ``kwokctl delete
        cluster`` does, so the daemon releases its leases and the chip by
        itself.  True when the daemon's pid is gone."""
        rt = self.rt
        if rt is not None and rt.exists():
            comps = [c.name for c in rt.load_components() if c.name != "apiserver"]
            for name in comps:
                rt.signal_component(name, signal.SIGTERM)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and any(map(rt.component_alive, comps)):
                time.sleep(0.05)
            for name in comps:
                rt.signal_component(name, signal.SIGKILL)
        return self.kwok_pid is None or pid_gone(self.kwok_pid, 10)

    def kill_apiserver(self) -> None:
        """End the apiserver as a crash would: SIGKILL, so no shutdown
        snapshot (which outlasts the runtime's 10 s at 100,000 pods and
        serves no request)."""
        try:
            pid = self._pid("apiserver")
        except (AttributeError, OSError, ValueError):
            return  # the run failed before there was one
        self.rt.signal_component("apiserver", signal.SIGKILL)
        pid_gone(pid, 10)

    def after_kill(self) -> None:
        """Between the crash and the restart; a fault test loses the log here."""

    def crash_and_read_back(self, canaries: list, names: list) -> dict:
        """The durability the configuration states, as far as a run can
        show it: with the controllers stopped, ``canaries`` are created and
        acknowledged, the apiserver is killed at once (no snapshot can hold
        them: a save takes seconds), started again from its snapshot and
        WAL, and ``names`` and the canaries are read one by one.  Returns
        name -> pod, or None where the apiserver has none."""
        rt = self.rt
        results = self.client.bulk([{"verb": "create", "data": p} for p in canaries])
        if [r for r in results if r.get("status") != "ok"] or len(results) != len(canaries):
            raise Failed("the idle apiserver refused a create before its crash")
        self.kill_apiserver()
        self.after_kill()
        t = time.monotonic()
        rt.start_component(next(c for c in rt.load_components() if c.name == "apiserver"))
        if not rt.ready(timeout=180):
            raise Failed("the apiserver did not come back from its snapshot and WAL in 180 s")
        log(f"apiserver back from snapshot + WAL in {time.monotonic() - t:.1f}s")
        from kwok_tpu.cluster.store import NotFound

        out = {}
        for n in [p["metadata"]["name"] for p in canaries] + list(names):
            try:
                out[n] = self.client.get("Pod", n, namespace="default")
            except NotFound:
                out[n] = None
        self.kill_apiserver()
        return out

    def reap(self) -> None:
        """No daemon of a run may outlive it, whatever happened."""
        piddir = os.path.join(self.home, "clusters", self.name, "pids")
        if os.path.isdir(piddir):
            for fn in os.listdir(piddir):
                try:
                    with open(os.path.join(piddir, fn), encoding="utf-8") as f:
                        os.kill(int(f.read().strip()), signal.SIGKILL)
                except (OSError, ValueError):
                    pass
        shutil.rmtree(self.home, ignore_errors=True)
        shutil.rmtree(self.control, ignore_errors=True)
