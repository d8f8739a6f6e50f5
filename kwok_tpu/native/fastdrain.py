"""Loader for the kwok_fastdrain CPython extension.

The accelerator exists because "only dirty rows cross the boundary"
(SURVEY.md:373) leaves the drain's dict-building as the host
bottleneck; the reference has no native analog (CGO is disabled,
hack/releases.sh:186).  Unlike the ctypes-based delay heap
(kwok_tpu/native/__init__.py), the
drain accelerator manipulates Python dicts directly, so it is a real
extension module compiled against Python.h and imported from its build
path (kwok_tpu/native/_artifact.py: keyed by source hash and Python ABI,
built atomically).  ``KWOK_TPU_NATIVE=0`` is the explicit switch to the
pure-Python implementations everywhere it is used; any other reason for
not loading (no toolchain, a compile error) is logged once per process
with the compiler's output and shows in ``kwok_tpu.native.status()``.
"""

from __future__ import annotations

import importlib.machinery
import importlib.util
import os
import sysconfig
import threading

from kwok_tpu.native._artifact import ensure, load_unit, note, source_path

_lock = threading.Lock()
_mod = None
_tried = False


def _command(target: str) -> list:
    include = sysconfig.get_paths().get("include") or ""
    return [
        "g++", "-O2", "-shared", "-fPIC", f"-I{include}",
        "-o", target, "-x", "c", source_path("kwok_fastdrain.c"),
    ]


def _open():
    path = ensure(
        "kwok_fastdrain",
        source_path("kwok_fastdrain.c"),
        _command,
        abi=sysconfig.get_config_var("SOABI") or "",
    )
    loader = importlib.machinery.ExtensionFileLoader("kwok_fastdrain", path)
    spec = importlib.util.spec_from_file_location(
        "kwok_fastdrain", path, loader=loader
    )
    mod = importlib.util.module_from_spec(spec)
    loader.exec_module(mod)
    return mod


def load():
    """The extension module, building it if necessary; None if
    unavailable or disabled via KWOK_TPU_NATIVE=0."""
    global _mod, _tried
    if os.environ.get("KWOK_TPU_NATIVE", "1") == "0":
        note("fastdrain", "disabled")
        return None
    with _lock:
        if _mod is not None or _tried:
            return _mod
        _tried = True
        # the compile runs under the lock on purpose: build-once
        # semantics — concurrent first callers must block until the
        # extension exists rather than race duplicate compiles
        _mod = load_unit("fastdrain", _open, "the pure-Python drain")
        return _mod
