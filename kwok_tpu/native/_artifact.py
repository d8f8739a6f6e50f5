"""Build-and-locate for the native units (``native/*.c*``).

Both units (the ctypes delay heap and the CPython drain extension) are
compiled with ``g++`` on first use and kept beside this package.  An
artifact is named by a hash of what it was built from — source bytes,
compiler command, ABI tag — so a binary left by another source
revision, another interpreter or a plain copy of the directory (which
need not keep mtimes) is never mistaken for the current one — and by a
hash of its own bytes, checked before loading, because dlopen answers a
truncated file with SIGBUS, not an error.  Five daemons import
``cluster/store.py`` at once on a fresh checkout, so a build goes to a
temporary name and is moved into place with ``os.replace`` under a
cross-process file lock: nobody sees a half-written file, and one
process compiles while the others wait.

No reference counterpart (the reference is pure Go, CGO disabled,
hack/releases.sh:186).
"""

from __future__ import annotations

import fcntl
import glob
import hashlib
import os
import subprocess
from typing import Callable, Dict, List, Optional, TypeVar

from kwok_tpu.utils.log import get_logger

__all__ = ["BuildError", "ensure", "load_unit", "note", "source_path", "status"]

T = TypeVar("T")

_HERE = os.path.dirname(os.path.abspath(__file__))

#: unit -> "loaded" | "disabled" | "failed: <why>"; units nobody asked
#: for yet read "not-requested" (the ctypes heap loads lazily)
_status: Dict[str, str] = {}
_UNITS = ("fastdrain", "kwok_native")


class BuildError(RuntimeError):
    """The compiler failed or is missing; the message carries its output."""


def source_path(name: str) -> str:
    repo_root = os.path.dirname(os.path.dirname(_HERE))
    return os.path.join(repo_root, "native", name)


def note(unit: str, state: str) -> None:
    """Record a unit's load outcome for :func:`status`."""
    _status[unit] = state


def status() -> Dict[str, str]:
    """Per-unit load state of this process, served on the apiserver's
    ``/stats`` and the kwok daemon's ``/metrics``."""
    return {u: _status.get(u, "not-requested") for u in _UNITS}


def _digest(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        h.update(f.read())
    return h.hexdigest()[:16]


def _find(prefix: str) -> Optional[str]:
    """The artifact named ``<prefix>.<digest of its own bytes>.so``, if
    one is there whole.  dlopen maps a file cut short by a copy and
    dies of SIGBUS instead of raising, so the bytes are checked before
    anything is loaded."""
    for path in glob.glob(f"{prefix}.*.so"):
        try:
            if path == f"{prefix}.{_digest(path)}.so":
                return path
        except OSError:
            continue
    return None


def ensure(
    stem: str, src: str, command: Callable[[str], List[str]], abi: str = ""
) -> str:
    """Path of the artifact for ``src`` as built by ``command(target)``,
    compiling it if no whole one is there.  The name carries a hash of
    the inputs (source bytes, command, ``abi``) and a hash of the
    artifact's own bytes.  Raises :class:`BuildError` with the
    compiler's output."""
    if not os.path.exists(src):
        raise BuildError(f"source not found: {src}")
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update("\0".join(command("<target>") + [abi]).encode())
    prefix = os.path.join(_HERE, f"{stem}.{h.hexdigest()[:16]}")
    found = _find(prefix)
    if found is not None:
        return found
    with open(os.path.join(_HERE, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        found = _find(prefix)
        if found is not None:
            return found  # a peer built it while we waited
        tmp = f"{prefix}.tmp.{os.getpid()}"
        try:
            proc = subprocess.run(
                command(tmp), capture_output=True, text=True, timeout=300
            )
        except (OSError, subprocess.TimeoutExpired) as exc:
            raise BuildError(f"{command(tmp)[0]}: {exc}") from exc
        if proc.returncode != 0:
            _remove(tmp)
            raise BuildError(
                f"{' '.join(command(tmp))} exited {proc.returncode}:\n"
                f"{proc.stderr.strip() or proc.stdout.strip()}"
            )
        final = f"{prefix}.{_digest(tmp)}.so"
        os.replace(tmp, final)
        # artifacts of other revisions, damaged copies, and the fixed
        # names used before artifacts were keyed (a running peer keeps
        # its mapping of a removed file)
        for old in glob.glob(os.path.join(_HERE, f"{stem}.*so")):
            if old != final:
                _remove(old)
    return final


def load_unit(
    unit: str, open_artifact: Callable[[], T], fallback: str
) -> Optional[T]:
    """``open_artifact()`` builds (via :func:`ensure`) and loads a
    unit.  A failure is logged with the compiler's output — once per
    process, callers cache the outcome — recorded for :func:`status`,
    and answered with None: the pure-Python ``fallback`` takes over,
    visibly."""
    try:
        obj = open_artifact()
    except (BuildError, OSError, ImportError) as exc:
        note(unit, f"failed: {exc}")
        get_logger("native").warn(
            f"{unit} not loaded, using {fallback}: {exc}"
        )
        return None
    note(unit, "loaded")
    return obj


def _remove(path: str) -> None:
    try:
        os.remove(path)
    except OSError:
        pass
