"""The benchmark's watching client: a watch on Pods and one on node
Leases, opened before the window, every event stamped on arrival."""

from __future__ import annotations

import threading
import time
from typing import Dict, Iterable, List

from .cluster import is_ready
from .metrics import LeaseEvent, PodEvent

LEASE_NAMESPACE = "kube-node-lease"


def _phase_ready(obj: dict):
    return (obj.get("status") or {}).get("phase"), is_ready(obj)


class Watcher:
    def __init__(self, client, namespace: str = "default"):
        self._client = client
        self._namespace = namespace
        self._streams = []
        self._threads = []
        self._stop = False
        self.pod_events: List[PodEvent] = []
        self.lease_events: List[LeaseEvent] = []
        #: pod name -> (phase, Ready) of its last event
        self._last: Dict[str, tuple] = {}
        #: pod name -> arrival of its first Running event; of its DELETED
        self.running_at: Dict[str, float] = {}
        self.deleted_at: Dict[str, float] = {}
        #: pod name -> status of the Running event, for the final read-back
        self.running_status: Dict[str, dict] = {}
        self.evicted = False

    def start(self) -> "Watcher":
        for kind, ns, fn in (("Pod", self._namespace, self._on_pod),
                             ("Lease", LEASE_NAMESPACE, self._on_lease)):
            stream = self._client.watch(kind, namespace=ns)
            self._streams.append(stream)
            th = threading.Thread(target=self._pump, args=(stream, fn), daemon=True,
                                  name=f"bench-watch-{kind}")
            th.start()
            self._threads.append(th)
        return self

    def _pump(self, stream, fn) -> None:
        while not self._stop:
            ev = stream.next(timeout=0.5)
            if ev is None:
                if stream.stopped:
                    self.evicted = self.evicted or stream.evicted
                    return
                continue
            fn(time.monotonic(), ev)

    def _on_pod(self, t: float, ev) -> None:
        name = ev.object["metadata"]["name"]
        if ev.type == "DELETED":
            self.deleted_at[name] = t
            self._last.pop(name, None)
            self.pod_events.append((t, name, "deleted"))
            return
        now = _phase_ready(ev.object)
        if now == self._last.get(name, (None, False)):
            return
        self._last[name] = now
        if now[0] == "Running" and name not in self.running_at:
            self.running_at[name] = t
            self.running_status[name] = ev.object.get("status") or {}
            self.pod_events.append((t, name, "running"))
        else:
            self.pod_events.append((t, name, "transition"))

    def _on_lease(self, t: float, ev) -> None:
        if ev.type == "DELETED":
            return
        renew = (ev.object.get("spec") or {}).get("renewTime")
        self.lease_events.append((t, ev.object["metadata"]["name"], renew))

    def wait(self, pred, timeout: float, poll: float = 0.01) -> bool:
        """Poll ``pred()`` until it holds or ``timeout`` passes.  The two
        pump threads each write their own structures, whole entries at a
        time, so a reader needs no lock."""
        deadline = time.monotonic() + timeout
        while not pred():
            if time.monotonic() >= deadline or self.evicted:
                return False
            time.sleep(poll)
        return True

    def wait_running(self, names: Iterable[str], timeout: float, poll: float = 0.01) -> bool:
        return self._wait_all(self.running_at, names, timeout, poll)

    def wait_deleted(self, names: Iterable[str], timeout: float, poll: float = 0.01) -> bool:
        return self._wait_all(self.deleted_at, names, timeout, poll)

    def _wait_all(self, seen: Dict[str, float], names: Iterable[str], timeout: float,
                  poll: float) -> bool:
        pending = set(names)

        def done() -> bool:
            pending.difference_update([n for n in pending if n in seen])
            return not pending

        return self.wait(done, timeout, poll)

    def stop(self) -> None:
        self._stop = True
        for s in self._streams:
            s.stop()
        for th in self._threads:
            th.join(timeout=5)
