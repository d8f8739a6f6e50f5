"""Binary runtime: cluster lifecycle as local OS processes.

The reference ``Runtime`` interface (runtime/config.go:30-147) has
Install/Uninstall/Up/Down/Start/Stop/Ready plus per-component ops and
snapshot hooks; the binary implementation forks real control-plane
binaries (runtime/binary/cluster.go).  This runtime does the same with
this framework's own daemons, one process per component, logs and
pidfiles under the cluster workdir:

    <workdir>/
      kwok.yaml          cluster config (reference saves the same)
      components.json    resolved component specs
      pki/               CA + server/admin certs (secure mode)
      logs/<name>.log    component stdout/stderr
      pids/<name>.pid
      state.json         apiserver persistence (etcd-snapshot analog)

Dry-run prints every command instead of executing
(reference dryrun.go:30-60 + golden tests).
"""

from __future__ import annotations

import json
import os
import random
import shutil
import signal
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

import yaml

from kwok_tpu.cluster.client import ClusterClient
from kwok_tpu.utils.backoff import Backoff
from kwok_tpu.ctl.components import (
    Component,
    build_core_components,
    build_tracing_component,
    free_port,
)
from kwok_tpu.ctl.dryrun import dry_run
from kwok_tpu.ctl.pki import generate_pki

DEFAULT_HOME = os.path.join(os.path.expanduser("~"), ".kwok-tpu")


def clusters_home() -> str:
    return os.environ.get("KWOK_TPU_HOME", DEFAULT_HOME)


def cluster_dir(name: str) -> str:
    return os.path.join(clusters_home(), "clusters", name)


def list_clusters() -> List[str]:
    base = os.path.join(clusters_home(), "clusters")
    if not os.path.isdir(base):
        return []
    return sorted(
        d
        for d in os.listdir(base)
        if os.path.exists(os.path.join(base, d, "kwok.yaml"))
    )


class BinaryRuntime:
    """One cluster's lifecycle (reference runtime/binary/cluster.go)."""

    #: recorded in kwok.yaml so later commands re-select the runtime
    runtime_label = "binary"

    def __init__(self, name: str = "kwok-tpu"):
        self.name = name
        self.workdir = cluster_dir(name)
        self._installed_components: Optional[List[Component]] = None

    # ------------------------------------------------------------ layout

    def _path(self, *parts: str) -> str:
        return os.path.join(self.workdir, *parts)

    @property
    def config_path(self) -> str:
        return self._path("kwok.yaml")

    def exists(self) -> bool:
        return os.path.exists(self.config_path)

    def load_config(self) -> dict:
        with open(self.config_path, "r", encoding="utf-8") as f:
            return yaml.safe_load(f)

    def load_components(self) -> List[Component]:
        with open(self._path("components.json"), "r", encoding="utf-8") as f:
            return [Component.from_dict(d) for d in json.load(f)]

    # ----------------------------------------------------------- install

    def install(
        self,
        secure: bool = False,
        apiserver_port: int = 0,
        kubelet_port: int = 0,
        backend: str = "host",
        config_paths: Optional[List[str]] = None,
        controller_args: Optional[List[str]] = None,
        enable_tracing: bool = False,
        chaos_profile: Optional[str] = None,
        flow_config: Optional[str] = None,
        max_inflight: Optional[int] = None,
        controller_replicas: int = 1,
        leader_elect: bool = True,
        gang_policy: str = "binpack",
        store_shards: int = 1,
        fleet_tenants: int = 0,
        fleet_idle_s: Optional[float] = None,
        fleet_cold_s: Optional[float] = None,
    ) -> dict:
        """Generate pki/config/component specs (reference
        binary/cluster.go:217-314 Install)."""
        if dry_run.enabled:
            dry_run.emit(f"mkdir -p {self.workdir}")
        else:
            os.makedirs(self._path("logs"), exist_ok=True)
            os.makedirs(self._path("pids"), exist_ok=True)

        pki_dir = self._path("pki")
        if secure:
            if dry_run.enabled:
                dry_run.emit(f"generate-pki {pki_dir}")
            else:
                generate_pki(pki_dir)

        apiserver_port = apiserver_port or free_port()
        kubelet_port = kubelet_port or free_port()
        scheme = "https" if secure else "http"
        server_url = f"{scheme}://127.0.0.1:{apiserver_port}"

        # copy user config files into the cluster dir so the cluster is
        # self-contained (reference copies kwokctl config the same way)
        stored_paths: List[str] = []
        for i, src in enumerate(config_paths or []):
            dst = self._path(f"config-{i}.yaml")
            if dry_run.enabled:
                dry_run.emit(f"cp {src} {dst}")
            else:
                shutil.copyfile(src, dst)
            stored_paths.append(dst)

        stored_chaos: Optional[str] = None
        if chaos_profile:
            # copied like user configs, so the cluster dir stays
            # self-contained and restarts re-arm the same seeded plan
            stored_chaos = self._path("chaos-profile.yaml")
            if dry_run.enabled:
                dry_run.emit(f"cp {chaos_profile} {stored_chaos}")
            else:
                shutil.copyfile(chaos_profile, stored_chaos)

        stored_flow: Optional[str] = None
        if flow_config:
            # same self-containment as the chaos profile: restarts
            # re-arm the same priority levels and flow schema
            stored_flow = self._path("flow-config.yaml")
            if dry_run.enabled:
                dry_run.emit(f"cp {flow_config} {stored_flow}")
            else:
                shutil.copyfile(flow_config, stored_flow)

        components = build_core_components(
            self.workdir,
            server_url,
            apiserver_port,
            kubelet_port,
            secure=secure,
            pki_dir=pki_dir,
            config_paths=stored_paths,
            backend=backend,
            extra_args=controller_args,
            chaos_profile=stored_chaos,
            flow_config=stored_flow,
            max_inflight=max_inflight,
            controller_replicas=controller_replicas,
            leader_elect=leader_elect,
            gang_policy=gang_policy,
            store_shards=store_shards,
            fleet_tenants=fleet_tenants,
            fleet_idle_s=fleet_idle_s,
            fleet_cold_s=fleet_cold_s,
        )
        tracing_port = 0
        if enable_tracing:
            # the jaeger seat: collector first, every other component
            # exports to it (reference wires the apiserver's OTLP
            # endpoint at jaeger the same way,
            # k8s/kube_apiserver_tracing_config.go:34-47)
            tracing_port = free_port()
            endpoint = f"http://127.0.0.1:{tracing_port}/v1/traces"
            for comp in components:
                comp.env["KWOK_TRACE_ENDPOINT"] = endpoint
                comp.env["KWOK_TRACE_SERVICE"] = comp.name
                comp.depends_on = list(set(comp.depends_on) | {"tracing"})
            components.insert(0, build_tracing_component(tracing_port))
        conf = {
            "kind": "KwokctlConfiguration",
            "name": self.name,
            "runtime": self.runtime_label,
            "serverURL": server_url,
            "secure": secure,
            "backend": backend,
            "ports": {"apiserver": apiserver_port, "kubelet": kubelet_port},
        }
        if tracing_port:
            conf["ports"]["tracing"] = tracing_port
        if stored_chaos:
            conf["chaosProfile"] = stored_chaos
        if stored_flow:
            conf["flowConfig"] = stored_flow
        if max_inflight is not None:
            conf["maxInflight"] = int(max_inflight)
        if int(controller_replicas) > 1:
            conf["controllerReplicas"] = int(controller_replicas)
        if not leader_elect:
            conf["leaderElect"] = False
        if gang_policy and gang_policy != "binpack":
            conf["gangPolicy"] = gang_policy
        if int(store_shards) > 1:
            conf["storeShards"] = int(store_shards)
        if int(fleet_tenants) > 0:
            conf["fleetTenants"] = int(fleet_tenants)
            if fleet_idle_s is not None:
                conf["fleetIdleSeconds"] = float(fleet_idle_s)
            if fleet_cold_s is not None:
                conf["fleetColdSeconds"] = float(fleet_cold_s)
        self.write_prometheus_config(kubelet_port, secure=secure)
        self._installed_components = components
        if dry_run.enabled:
            dry_run.emit(f"write {self.config_path}")
            dry_run.emit(f"write {self._path('components.json')}")
        else:
            with open(self.config_path, "w", encoding="utf-8") as f:
                yaml.safe_dump(conf, f, sort_keys=False)
            with open(self._path("components.json"), "w", encoding="utf-8") as f:
                json.dump([c.to_dict() for c in components], f, indent=2)
        return conf

    def uninstall(self) -> None:
        if dry_run.enabled:
            dry_run.emit(f"rm -rf {self.workdir}")
            return
        shutil.rmtree(self.workdir, ignore_errors=True)

    # ----------------------------------------------------------- process ops

    def _pidfile(self, name: str) -> str:
        return self._path("pids", f"{name}.pid")

    def _pid(self, name: str) -> Optional[int]:
        try:
            with open(self._pidfile(name), "r", encoding="utf-8") as f:
                return int(f.read().strip())
        except (OSError, ValueError):
            return None

    @staticmethod
    def _alive(pid: Optional[int]) -> bool:
        if not pid:
            return False
        try:
            os.kill(pid, 0)
        except OSError:
            return False
        # signal 0 also succeeds on zombies: a SIGKILLed component whose
        # parent (an in-process runtime embedder, e.g. the test suite)
        # has not reaped it yet would read as alive forever — and the
        # supervisor would never restart it.  /proc state Z is dead for
        # every practical purpose; reap it here when it is our child.
        try:
            with open(f"/proc/{pid}/stat", "r", encoding="ascii") as f:
                state = f.read().rsplit(")", 1)[-1].split()
            if state and state[0] == "Z":
                try:
                    os.waitpid(pid, os.WNOHANG)
                except (ChildProcessError, OSError):
                    pass
                return False
        except (OSError, IndexError, ValueError):
            pass  # no /proc (non-Linux): keep the signal-0 answer
        return True

    def start_component(self, comp: Component) -> None:
        """(reference binary runtime forks via os/exec, logging to files)"""
        if dry_run.enabled:
            dry_run.emit_cmd(comp.args)
            return
        if self._alive(self._pid(comp.name)):
            return
        log = open(self._path("logs", f"{comp.name}.log"), "ab")
        env = dict(os.environ)
        env.update(comp.env)
        # the daemon's ClusterClient stamps this as X-Kwok-Client, so
        # chaos partitions (and debug tooling) can target one component
        env.setdefault("KWOK_COMPONENT_NAME", comp.name)
        # daemons import kwok_tpu regardless of the caller's cwd
        pkg_parent = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        pkg_root = os.path.dirname(pkg_parent)
        env["PYTHONPATH"] = (
            pkg_root + os.pathsep + env["PYTHONPATH"]
            if env.get("PYTHONPATH")
            else pkg_root
        )
        # the daemons only need CPU JAX unless the device backend is on
        proc = subprocess.Popen(
            comp.args,
            stdout=log,
            stderr=subprocess.STDOUT,
            env=env,
            start_new_session=True,
        )
        log.close()
        with open(self._pidfile(comp.name), "w", encoding="utf-8") as f:
            f.write(str(proc.pid))

    def stop_component(self, name: str, timeout: float = 10.0) -> None:
        self._signal_component(name)
        self._await_component_exit(name, timeout)

    def component_alive(self, name: str) -> bool:
        """True when the component's recorded pid answers signal 0
        (includes SIGSTOPped processes — paused is not dead)."""
        return self._alive(self._pid(name))

    def signal_component(self, name: str, sig: int) -> bool:
        """Deliver a raw signal to a component (the chaos process-fault
        lane: SIGKILL / SIGSTOP / SIGCONT).  Unlike stop_component this
        neither waits nor removes the pidfile — a SIGKILLed component
        stays visible as dead, which is exactly what the supervisor
        keys on.  Returns False when no live pid was found."""
        if dry_run.enabled:
            dry_run.emit(f"kill -{sig} {name}")
            return True
        pid = self._pid(name)
        if not self._alive(pid):
            return False
        try:
            os.kill(pid, sig)
            return True
        except OSError:
            return False

    def _signal_component(self, name: str) -> None:
        if dry_run.enabled:
            dry_run.emit(f"kill {name}")
            return
        pid = self._pid(name)
        if not self._alive(pid):
            return
        try:
            os.kill(pid, signal.SIGTERM)
        except OSError:
            pass

    def _await_component_exit(self, name: str, timeout: float = 10.0) -> None:
        if dry_run.enabled:
            return
        pid = self._pid(name)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if not self._alive(pid):
                break
            time.sleep(0.05)
        else:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        try:
            os.remove(self._pidfile(name))
        except OSError:
            pass

    # -------------------------------------------------------------- up/down

    def up(self, wait: float = 30.0) -> None:
        """Start all components in dependency order (reference Up)."""
        components = (
            self.load_components() if not dry_run.enabled else self._dry_components()
        )
        started: Dict[str, Component] = {}
        pending = list(components)
        while pending:
            progressed = False
            for comp in list(pending):
                if all(d in started for d in comp.depends_on):
                    self.start_component(comp)
                    if comp.name == "apiserver" and not dry_run.enabled:
                        if not self.ready(timeout=wait):
                            raise RuntimeError(
                                f"apiserver did not become ready within {wait}s "
                                f"(see {self._path('logs', 'apiserver.log')})"
                            )
                    started[comp.name] = comp
                    pending.remove(comp)
                    progressed = True
            if not progressed:
                raise RuntimeError(
                    f"dependency cycle among components: {[c.name for c in pending]}"
                )

    def _dry_components(self) -> List[Component]:
        if self._installed_components is not None:
            return self._installed_components
        if self.exists():
            return self.load_components()
        return []

    def down(self) -> None:
        if dry_run.enabled:
            dry_run.emit(f"stop-cluster {self.name}")
            return
        if not os.path.isdir(self._path("pids")):
            return
        # reverse dependency order, one wave at a time: a component is
        # signalled only once nothing still running depends on it.  The
        # controllers' shutdown WRITES (node-lease and election-lease
        # releases) need the apiserver; signalled together with it they
        # retried against a dead port until the SIGKILL below, so the
        # kwok daemon never exited by itself — under --backend device,
        # holding the chip.  Within a wave everything is signalled
        # first so slow shutdowns overlap (wait ~= the slowest, not the
        # sum — a loaded box was paying 4x10s sequentially).
        remaining = self.load_components() if self.exists() else []
        while remaining:
            needed = {d for c in remaining for d in c.depends_on}
            wave = [c for c in remaining if c.name not in needed] or remaining
            for comp in reversed(wave):
                self._signal_component(comp.name)
            for comp in reversed(wave):
                self._await_component_exit(comp.name)
            remaining = [c for c in remaining if c not in wave]

    def running_components(self) -> Dict[str, bool]:
        out = {}
        for comp in self.load_components():
            out[comp.name] = self._alive(self._pid(comp.name))
        return out

    # ------------------------------------------------------------- client

    def client(self, timeout: float = 30.0) -> ClusterClient:
        conf = self.load_config()
        kwargs = {}
        if conf.get("secure"):
            pki_dir = self._path("pki")
            kwargs = {
                "ca_cert": os.path.join(pki_dir, "ca.crt"),
                "client_cert": os.path.join(pki_dir, "admin.crt"),
                "client_key": os.path.join(pki_dir, "admin.key"),
            }
        return ClusterClient(conf["serverURL"], timeout=timeout, **kwargs)

    def ready(self, timeout: float = 30.0) -> bool:
        try:
            return self.client().wait_ready(timeout=timeout)
        except OSError:
            return False

    def collect_logs(self, dest: str) -> List[str]:
        """Export logs + cluster config into ``dest`` (reference
        Runtime.CollectLogs: logs, audit, components yaml)."""
        os.makedirs(dest, exist_ok=True)
        collected: List[str] = []
        for rel in ("kwok.yaml", "components.json", "prometheus.yaml"):
            src = self._path(rel)
            if os.path.exists(src):
                shutil.copyfile(src, os.path.join(dest, rel))
                collected.append(rel)
        logdir = self._path("logs")
        if os.path.isdir(logdir):
            for fn in sorted(os.listdir(logdir)):
                shutil.copyfile(
                    os.path.join(logdir, fn), os.path.join(dest, fn)
                )
                collected.append(fn)
        return collected

    def write_prometheus_config(
        self, kubelet_port: int, secure: bool = False
    ) -> str:
        """Generate a scrape config for the cluster (reference
        components/prometheus_config.go + prometheus_config.yaml.tpl:
        static kwok-controller target + HTTP SD for Metric CR routes).
        Secure clusters scrape the kubelet over https, verified against
        the cluster CA — the cmux port serves both, and the reference's
        generated config uses the https scheme the same way."""
        path = self._path("prometheus.yaml")
        kwok_job = {
            "job_name": "kwok-controller",
            "static_configs": [{"targets": [f"127.0.0.1:{kubelet_port}"]}],
        }
        sd_job = {
            "job_name": "kwok-metric-crs",
            "http_sd_configs": [
                {"url": f"http://127.0.0.1:{kubelet_port}/discovery/prometheus"}
            ],
        }
        if secure:
            ca = os.path.join(self._path("pki"), "ca.crt")
            kwok_job["scheme"] = "https"
            kwok_job["tls_config"] = {"ca_file": ca}
            sd_job["http_sd_configs"][0]["url"] = (
                f"https://127.0.0.1:{kubelet_port}/discovery/prometheus"
            )
            sd_job["http_sd_configs"][0]["tls_config"] = {"ca_file": ca}
            sd_job["scheme"] = "https"
            sd_job["tls_config"] = {"ca_file": ca}
        doc = {
            "global": {"scrape_interval": "15s"},
            "scrape_configs": [kwok_job, sd_job],
        }
        if dry_run.enabled:
            dry_run.emit(f"write {path}")
        else:
            with open(path, "w", encoding="utf-8") as f:
                yaml.safe_dump(doc, f, sort_keys=False)
        return path

    def logs(self, component: str, follow: bool = False) -> str:
        path = self._path("logs", f"{component}.log")
        if not os.path.exists(path):
            return ""
        with open(path, "r", encoding="utf-8", errors="replace") as f:
            return f.read()


class ComponentSupervisor:
    """Probe components and restart crashed ones — the seat a real
    deployment fills with systemd/kubelet restart policy (reference
    runtime/config.go:30-147 exposes per-component Start/Stop but
    nothing watches them; a dead component simply stayed dead here
    too, until this loop).

    - **probe**: pid liveness each ``poll_interval``; the apiserver
      additionally must answer /healthz after a restart before it
      counts as recovered (a bound process that cannot serve is still
      down).  SIGSTOPped components look alive — pausing is the chaos
      plan's business, not ours to "fix".
    - **readiness-gated, not readiness-restarted**: a serving apiserver
      whose /readyz answers 503 (storage degraded: full disk, poisoned
      fsync) is *alive but read-only* — a restart cannot fix the disk,
      so degraded components are tracked in :attr:`degraded` (and as
      ``degraded``/``ready`` events) without consuming restart budget
      or counting toward crash-loop parking.  The liveness/readiness
      split exists precisely so this loop never restart-loops a daemon
      whose only problem is ENOSPC.
    - **restart with backoff**: per-component jittered exponential
      backoff (shared :class:`kwok_tpu.utils.backoff.Backoff`; the rng
      is explicit so a seeded chaos run replays the same schedule).
    - **crash-loop detection**: more than ``crash_loop_threshold``
      restarts inside ``crash_loop_window`` seconds parks the
      component (no further restarts) and records a ``crash-loop``
      event — flapping forever is worse than staying down loudly.
    - **self-metrics**: ``events`` (timestamped action log),
      ``recovery_times`` (death-detected → serving again, seconds) —
      the chaos e2e asserts recovery time is bounded from these.
    """

    def __init__(
        self,
        runtime: "BinaryRuntime",
        poll_interval: float = 0.25,
        backoff: Optional[Backoff] = None,
        crash_loop_threshold: int = 5,
        crash_loop_window: float = 30.0,
        rng: Optional[random.Random] = None,
    ):
        self.runtime = runtime
        self.poll_interval = poll_interval
        self.backoff = backoff or Backoff(duration=0.25, cap=5.0)
        self.crash_loop_threshold = crash_loop_threshold
        self.crash_loop_window = crash_loop_window
        self.rng = rng or random.Random()
        self.events: List[dict] = []
        self.recovery_times: List[float] = []
        self.crash_looped: set = set()
        #: component -> degraded reason (e.g. "StorageDegraded") while
        #: its /readyz fails with the process alive and serving
        self.degraded: Dict[str, str] = {}
        self._restart_times: Dict[str, List[float]] = {}
        self._death_time: Dict[str, float] = {}
        self._restart_due: Dict[str, float] = {}
        self._client: Optional[ClusterClient] = None
        self._done = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------ lifecycle

    def start(self) -> "ComponentSupervisor":
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        """Stop supervising (call BEFORE runtime.down(), or the
        supervisor resurrects what down() is killing)."""
        self._done.set()
        if self._thread is not None:
            self._thread.join(timeout=10)

    def _loop(self) -> None:
        while not self._done.wait(self.poll_interval):
            try:
                self.tick()
            except Exception:  # noqa: BLE001 — a probe hiccup (e.g. the
                # cluster dir vanishing mid-read during delete) must not
                # kill the supervision loop; next tick re-reads
                continue

    # ----------------------------------------------------------------- probe

    def _serving(self, name: str) -> bool:
        """Process-alive, plus /healthz for the apiserver (serving is
        the bar for 'recovered', not just forked)."""
        if not self.runtime.component_alive(name):
            return False
        if name != "apiserver":
            return True
        if self._client is None:
            try:
                self._client = self.runtime.client(timeout=2.0)
            except (OSError, KeyError, ValueError):
                return False
        return self._client.healthy()

    def tick(self, now: Optional[float] = None) -> None:
        """One probe+restart pass (public so tests can drive it without
        the thread)."""
        now = time.monotonic() if now is None else now
        for comp in self.runtime.load_components():
            name = comp.name
            if name in self.crash_looped:
                continue
            if self._serving(name):
                death = self._death_time.pop(name, None)
                if death is not None:
                    self.recovery_times.append(now - death)
                    self._record(now, name, "recovered")
                self._restart_due.pop(name, None)
                # alive and serving: readiness is a separate axis.  A
                # degraded (read-only) apiserver is tracked, never
                # restarted — no restart budget, no crash-loop credit.
                self._track_readiness(now, name)
                continue
            if self.runtime.component_alive(name):
                # alive-but-not-serving (apiserver mid-boot): keep the
                # death clock running, nothing to restart
                continue
            if name not in self._death_time:
                self._death_time[name] = now
                self._record(now, name, "died")
            due = self._restart_due.get(name)
            if due is None:
                recent = [
                    t
                    for t in self._restart_times.get(name, [])
                    if now - t < self.crash_loop_window
                ]
                if len(recent) >= self.crash_loop_threshold:
                    self.crash_looped.add(name)
                    self._record(now, name, "crash-loop")
                    continue
                delay = self.backoff.delay(len(recent), self.rng)
                self._restart_due[name] = now + delay
                continue
            if now >= due:
                self.runtime.start_component(comp)
                self._restart_times.setdefault(name, []).append(now)
                self._restart_due.pop(name, None)
                self._record(now, name, "restarted")

    def _track_readiness(self, now: float, name: str) -> None:
        """Probe /readyz for the apiserver (the only component with a
        storage axis today) and record degraded/ready transitions.
        Degraded is explicitly NOT death: the restart machinery is
        never touched from here."""
        if name != "apiserver" or self._client is None:
            return
        probe = getattr(self._client, "readiness", None)
        if probe is None:
            return
        ok, reason = probe()
        was = self.degraded.get(name)
        if ok and was is not None:
            del self.degraded[name]
            self._record(now, name, "ready")
        elif not ok and reason is not None and was is None:
            # reason None means unreachable — the liveness probe owns
            # that case; only a *served* not-ready marks degraded
            self.degraded[name] = reason
            self._record(now, name, "degraded")

    def _record(self, now: float, component: str, action: str) -> None:
        self.events.append(
            {"t": round(now, 3), "component": component, "action": action}
        )
