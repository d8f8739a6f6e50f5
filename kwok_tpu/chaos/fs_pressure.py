"""Seeded filesystem-pressure injection: disk-full / fsync-error /
quota windows against the WAL's own syscalls.

The fifth injection layer (after HTTP, process, commit-boundary and
disk corruption — ``kwok_tpu/chaos/__init__.py:1``): the disk does not
*lie* (that is ``kwok_tpu/chaos/disk_faults.py:1``'s corruption
vocabulary), it *refuses*.  A :class:`FsPressure` shim installs into
the write-ahead log's pressure seam
(``kwok_tpu/cluster/wal.py:1`` ``WriteAheadLog.set_pressure``) and is
consulted before every one of the log's own write/fsync syscalls, and by
the apiserver's snapshot child before each file it writes
(``WriteAheadLog.guard_io``; ``kwok_tpu/snapshot/child.py:1``: the
snapshot shares the log's disk, so a window refuses it too):

- ``disk-full`` — every write raises ENOSPC until headroom is freed;
  releasing the WAL's preallocated emergency reserve credits the shim
  (``freed``), exactly like unlinking a real file frees real blocks,
  so the reserve-powered retry and lease renewals behave as they would
  on a genuinely full disk.
- ``quota`` — the EDQUOT twin (per-tenant storage budgets; the
  KUBEDIRECT-shape multi-tenant direction in ROADMAP.md).
- ``fsync-error`` — writes land but every fsync raises EIO: the
  fsyncgate shape, driving the poison-handle seal-and-reopen path.

Window *state* is toggled by the owner (the daemon's
:class:`PressureDriver` on wall-clock offsets, the DST harness at
virtual instants, smokes inline), so the shim itself is clock-free and
consumes no randomness at check time — a pressure schedule is a pure
function of the plan, byte-identical per seed.
"""

from __future__ import annotations

import errno
import logging
import os
import threading
import time
from typing import Dict, List, Optional

__all__ = ["EXHAUSTION_KINDS", "FsPressure", "PressureDriver"]

log = logging.getLogger(__name__)

#: fault kinds the pressure shim models (the ``disk:`` profile section
#: accepts these alongside the corruption kinds of disk_faults.py)
EXHAUSTION_KINDS = ("disk-full", "fsync-error", "quota")

_ERRNOS = {
    "disk-full": errno.ENOSPC,
    "quota": getattr(errno, "EDQUOT", errno.ENOSPC),
}


class FsPressure:
    """One pressure window's state: a duck-typed shim for
    ``WriteAheadLog.set_pressure`` (``on_write``/``on_fsync`` raise the
    injected OSError; ``freed`` credits released reserve space)."""

    def __init__(self, kind: str, free_bytes: int = 0):
        if kind not in EXHAUSTION_KINDS:
            raise ValueError(
                f"pressure kind {kind!r} not in {EXHAUSTION_KINDS}"
            )
        self.kind = kind
        #: simulated free space: writes consume it, ``freed`` refills
        #: it (disk-full/quota only; fsync-error never blocks writes)
        self._free = int(free_bytes)
        self.writes_failed = 0
        self.fsyncs_failed = 0
        self.bytes_written = 0

    def on_write(self, nbytes: int) -> None:
        if self.kind == "fsync-error":
            return
        if nbytes <= self._free:
            self._free -= nbytes
            self.bytes_written += nbytes
            return
        self.writes_failed += 1
        eno = _ERRNOS[self.kind]
        raise OSError(eno, os.strerror(eno))

    def on_fsync(self) -> None:
        if self.kind != "fsync-error":
            return
        self.fsyncs_failed += 1
        raise OSError(errno.EIO, os.strerror(errno.EIO))

    def freed(self, nbytes: int) -> None:
        """Space was genuinely released (the WAL unlinked its reserve):
        credit the simulated free-block budget with it."""
        self._free += int(nbytes)

    def snapshot(self) -> Dict[str, int]:
        return {
            "writes_failed": self.writes_failed,
            "fsyncs_failed": self.fsyncs_failed,
            "bytes_written": self.bytes_written,
            "free_bytes": self._free,
        }


class PressureDriver:
    """Arm a plan's exhaustion windows against a live WriteAheadLog on
    wall-clock offsets — the in-daemon twin of
    :class:`~kwok_tpu.chaos.disk_faults.DiskFaultDriver` (corruption
    faults hit files from outside; pressure faults must sit inside the
    process that owns the file handles).  ``cmd/apiserver`` starts one
    when its ``--chaos-profile`` carries ``disk:`` entries with
    exhaustion kinds; after each window it force-probes the re-arm path
    so the cluster leaves degraded mode without waiting for traffic."""

    def __init__(self, plan, wal, store=None, wals=None):
        self.plan = plan
        self.wal = wal
        #: per-shard WAL handles of a sharded store (index = shard);
        #: a spec's ``shard:`` picks its target, out-of-range entries
        #: fall back to the primary ``wal`` (shard 0's handle)
        self.wals = list(wals) if wals else [wal]
        #: when given, re-arm probes route through
        #: ``store.probe_writable()`` — the store mutex serializes them
        #: against request-thread appends (a bare ``wal.try_rearm()``
        #: from this thread would race the unlocked WAL's sequence
        #: bookkeeping); shim install/remove stays a plain reference
        #: swap, which is safe without the lock
        self.store = store
        #: [{"t", "kind", "event", ...}] — window open/close log
        self.events: List[dict] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    @staticmethod
    def specs(plan) -> List:
        """The plan's ``disk:`` entries this driver owns."""
        return [s for s in plan.disk if s.kind in EXHAUSTION_KINDS]

    def start(self) -> "PressureDriver":
        self._thread = threading.Thread(target=self.run, daemon=True)
        self._thread.start()
        return self

    def _rearm(self) -> bool:
        if self.store is not None:
            return bool(self.store.probe_writable())
        return bool(self.wal.try_rearm())

    def _wal_for(self, spec) -> tuple:
        """(wal, shard index actually pressured): an out-of-range
        ``shard:`` (a stale profile after a shard-count change) falls
        back to the primary WAL — the event log must record THAT
        index, not the spec's, or a per-shard isolation readout
        concludes the wrong shard was degraded."""
        shard = int(getattr(spec, "shard", 0))
        if 0 <= shard < len(self.wals):
            return self.wals[shard], shard
        log.warning(
            "pressure window spec shard=%d out of range (%d shards); "
            "falling back to shard 0",
            shard,
            len(self.wals),
        )
        return self.wal, 0

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
        # never leave a dangling shim behind a cancelled schedule
        for w in self.wals:
            w.set_pressure(None)
        self._rearm()

    def run(self) -> None:
        t0 = time.monotonic()
        pending = sorted(self.specs(self.plan), key=lambda s: s.at)
        for spec in pending:
            now = time.monotonic() - t0
            if spec.at > now and self._stop.wait(spec.at - now):
                return
            shim = FsPressure(spec.kind)
            wal, shard = self._wal_for(spec)
            wal.set_pressure(shim)
            self.events.append(
                {
                    "t": round(time.monotonic() - t0, 3),
                    "kind": spec.kind,
                    "shard": shard,
                    "event": "window-open",
                }
            )
            self._stop.wait(max(spec.duration, 0.0))
            wal.set_pressure(None)
            rearmed = self._rearm()
            self.events.append(
                {
                    "t": round(time.monotonic() - t0, 3),
                    "kind": spec.kind,
                    "event": "window-close",
                    "rearmed": bool(rearmed),
                    **shim.snapshot(),
                }
            )
            if self._stop.is_set():
                return
