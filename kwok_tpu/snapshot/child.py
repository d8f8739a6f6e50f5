"""The apiserver's periodic snapshot, written by a forked child.

The etcd-snapshot analog (reference pkg/kwokctl/etcd/save.go:1) is the
whole store as one JSON document.  Serialising it holds the interpreter
for seconds, and the request threads share it.  So the save loop
(``kwok_tpu/cmd/apiserver.py:462`` commit) only cuts the state by
reference, forks, waits without the GIL and commits by rename; the child,
whose heap is the parent's as of the fork, serialises the cut once and
writes it durably under names of its own:

1. cut     ``store.dump_state(copy=False)`` (the caller's)
2. child   ``<state-file>.tmp.<pid>`` and ``<archive>/snap-<rv>.json.tmp.<pid>``,
           the same bytes, each fsynced; the exit status is its whole report
3. parent  ``waitpid``, then rename both into place and fsync the directories
4. caller  WAL compaction up to the snapshot's rv, archive prune

Only a renamed snapshot exists for a reader, and only the parent renames.
A parent killed in mid-save leaves an orphan that finishes temporaries
nobody will rename; :func:`sweep_temporaries` removes them before the next
save, and a temporary never ends in ``.json``
(``PitrArchive.snapshots`` takes every ``snap-*.json`` for a whole one).
"""

from __future__ import annotations

import gc
import os
import re
import time
import warnings
from typing import Any, Callable, Dict, List, Optional

from kwok_tpu.cluster.wal import (
    _fsync_dir,
    _note_os_error,
    encode_state,
    write_durable,
)
from kwok_tpu.snapshot.pitr import SNAP_PREFIX, PitrArchive
from kwok_tpu.utils import telemetry

__all__ = ["save_in_child", "sweep_temporaries"]

_TEMPORARY = re.compile(r"\.tmp\.\d+$")

#: exit status of a child that failed with anything but an errno
_FAILED = 255


_CHILDREN = telemetry.counter(
    "kwok_apiserver_save_children_total",
    help="snapshot children forked by the save loop, by how they ended",
    labelnames=("outcome",),
)


def sweep_temporaries(path: str, archive: Optional[PitrArchive] = None) -> int:
    """Remove what a child of an earlier save (an orphan's, after a kill)
    left beside the state file ``path`` and in ``archive``.  No child of
    this process is alive when the save loop calls it."""
    places = [(os.path.dirname(path) or ".", os.path.basename(path))]
    if archive is not None:
        places.append((archive.root, SNAP_PREFIX))
    removed = 0
    for where, prefix in places:
        try:
            names = os.listdir(where)
        except OSError as exc:
            _note_os_error("snapshot.sweep.listdir", exc)
            continue
        for n in names:
            if n.startswith(prefix) and _TEMPORARY.search(n):
                removed += _unlink(os.path.join(where, n))
    return removed


def _unlink(path: str) -> int:
    try:
        os.unlink(path)
        return 1
    except FileNotFoundError:
        return 0
    except OSError as exc:
        _note_os_error("snapshot.unlink", exc)
        return 0


def _write_and_exit(
    state: Dict[str, Any],
    finals: List[str],
    guard: Optional[Callable[[int], None]],
) -> None:
    """The child.  Another thread of the parent may have held any lock at
    the fork, so nothing here takes one: no store call, no print or
    logging, no telemetry, no import."""
    status = _FAILED
    try:
        gc.disable()
        # the listening socket among them: an orphan must not hold the port
        os.closerange(3, min(os.sysconf("SC_OPEN_MAX"), 1 << 16))
        data = encode_state(state)
        for final in finals:
            if guard is not None:
                guard(len(data))
            write_durable(f"{final}.tmp.{os.getpid()}", data)
        status = 0
    except OSError as exc:
        if exc.errno and 0 < exc.errno < _FAILED:
            status = exc.errno
    finally:
        os._exit(status)


def save_in_child(
    state: Dict[str, Any],
    path: str,
    archive: Optional[PitrArchive] = None,
    guard: Optional[Callable[[int], None]] = None,
) -> float:
    """Write ``state`` (a ``dump_state(copy=False)`` cut) to ``path`` and,
    with an ``archive``, to its ``snap-<rv>.json``: serialised and fsynced
    by a forked child, renamed into place by this process.  ``guard`` is
    asked before each file's write (``WriteAheadLog.guard_io``: a chaos
    disk-pressure window refuses the snapshot as it refuses the log).

    Returns the seconds spent waiting for the child.  Raises ``OSError``
    when no whole snapshot was committed; no temporary is left then."""
    finals = [path]
    if archive is not None:
        finals.append(archive.snapshot_path(state.get("resourceVersion", 0)))
    sweep_temporaries(path, archive)
    with warnings.catch_warnings():
        # Python 3.12 warns that a fork in a threaded process may deadlock
        # the child.  This child cannot: it takes no lock that exists in
        # the parent (see _write_and_exit) and leaves through os._exit
        warnings.filterwarnings(
            "ignore", message=".*fork.*", category=DeprecationWarning
        )
        pid = os.fork()
    if pid == 0:
        _write_and_exit(state, finals, guard)
    temporaries = [f"{final}.tmp.{pid}" for final in finals]
    t_wait = time.perf_counter()
    try:
        _, status = os.waitpid(pid, 0)
        code = os.waitstatus_to_exitcode(status)
    except ChildProcessError:
        code = -1  # reaped elsewhere: what it wrote cannot be vouched for
    waited = time.perf_counter() - t_wait
    # both series from the first save on: a scrape reads "failed 0", not nothing
    _CHILDREN.inc(int(code == 0), "ok")
    _CHILDREN.inc(int(code != 0), "failed")
    try:
        if code != 0:
            if 0 < code < _FAILED:
                raise OSError(code, os.strerror(code))
            raise OSError(f"snapshot child {pid} ended with status {code}")
        for tmp, final in zip(temporaries, finals):
            os.replace(tmp, final)
            _fsync_dir(final)
    except OSError:
        for tmp in temporaries:
            _unlink(tmp)
        raise
    return waited
