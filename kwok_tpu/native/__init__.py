"""ctypes bindings for the C++ runtime core (native/kwok_native.cpp).

The shared library is built on demand with g++ the first time it is
needed (kwok_tpu/native/_artifact.py: keyed by source hash, built
atomically).  ``KWOK_TPU_NATIVE=0`` is the explicit switch to the
pure-Python implementations; any other reason for not loading (no
toolchain, a compile error) is logged once per process with the
compiler's output and shows in :func:`kwok_tpu.native.status`.
"""

from __future__ import annotations

import ctypes
import threading
from typing import Optional

from kwok_tpu.native._artifact import ensure, load_unit, source_path, status

__all__ = ["NativeDelayHeap", "available", "fnv1a64", "load", "status"]

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _command(target: str) -> list:
    return [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC",
        "-o", target, source_path("kwok_native.cpp"),
    ]


def _open() -> ctypes.CDLL:
    return ctypes.CDLL(
        ensure("libkwok_native", source_path("kwok_native.cpp"), _command)
    )


def load() -> Optional[ctypes.CDLL]:
    """The shared library, building it if necessary; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        # the compile runs under _lock on purpose: build-once semantics —
        # concurrent first callers must block until the library exists
        # rather than race duplicate compiler invocations
        lib = load_unit("kwok_native", _open, "the pure-Python heap")
        if lib is None:
            return None
        lib.kn_heap_new.restype = ctypes.c_void_p
        lib.kn_heap_free.argtypes = [ctypes.c_void_p]
        lib.kn_heap_add.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_int32,
            ctypes.c_double,
        ]
        lib.kn_heap_cancel.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.kn_heap_cancel.restype = ctypes.c_int
        lib.kn_heap_promote.argtypes = [ctypes.c_void_p, ctypes.c_double]
        lib.kn_heap_pop_ready.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int,
        ]
        lib.kn_heap_pop_ready.restype = ctypes.c_int
        lib.kn_heap_next_deadline.argtypes = [ctypes.c_void_p]
        lib.kn_heap_next_deadline.restype = ctypes.c_double
        lib.kn_heap_ready_count.argtypes = [ctypes.c_void_p]
        lib.kn_heap_ready_count.restype = ctypes.c_int
        lib.kn_heap_size.argtypes = [ctypes.c_void_p]
        lib.kn_heap_size.restype = ctypes.c_int
        lib.kn_fnv1a64_batch.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint64),
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None


class NativeDelayHeap:
    """Python face of the C++ delay/weight heap.

    Schedules opaque int64 ids: :meth:`add` (re-add reschedules),
    :meth:`cancel`, :meth:`promote` (move due entries to their weight
    buckets), :meth:`pop_ready` (lowest weight first, FIFO within a
    weight), :meth:`next_deadline`."""

    __slots__ = ("_h", "_lib", "_buf")

    _POP_BATCH = 1024

    def __init__(self):
        lib = load()
        if lib is None:
            raise RuntimeError("kwok_native library unavailable")
        self._lib = lib
        self._h = lib.kn_heap_new()
        self._buf = (ctypes.c_int64 * self._POP_BATCH)()

    def __del__(self):
        h, self._h = getattr(self, "_h", None), None
        if h:
            self._lib.kn_heap_free(h)

    def add(self, id_: int, weight: int, deadline: float) -> None:
        self._lib.kn_heap_add(self._h, id_, weight, deadline)

    def cancel(self, id_: int) -> bool:
        return bool(self._lib.kn_heap_cancel(self._h, id_))

    def promote(self, now: float) -> None:
        self._lib.kn_heap_promote(self._h, now)

    def pop_ready(self, max_items: Optional[int] = None):
        out = []
        budget = max_items if max_items is not None else 1 << 31
        while budget > 0:
            n = self._lib.kn_heap_pop_ready(
                self._h, self._buf, min(budget, self._POP_BATCH)
            )
            if n <= 0:
                break
            out.extend(self._buf[:n])
            budget -= n
        return out

    def next_deadline(self) -> Optional[float]:
        d = self._lib.kn_heap_next_deadline(self._h)
        return None if d < 0 else d

    @property
    def ready_count(self) -> int:
        return self._lib.kn_heap_ready_count(self._h)

    def __len__(self) -> int:
        return self._lib.kn_heap_size(self._h)


def fnv1a64(values) -> list:
    """Batch FNV-1a 64 over a list of str/bytes."""
    lib = load()
    enc = [v.encode() if isinstance(v, str) else bytes(v) for v in values]
    if lib is None:
        out = []
        for b in enc:
            h = 0xCBF29CE484222325
            for byte in b:
                h = ((h ^ byte) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
            out.append(h)
        return out
    buf = b"".join(enc)
    n = len(enc)
    offs = (ctypes.c_int64 * n)()
    lens = (ctypes.c_int64 * n)()
    pos = 0
    for i, b in enumerate(enc):
        offs[i] = pos
        lens[i] = len(b)
        pos += len(b)
    out = (ctypes.c_uint64 * n)()
    lib.kn_fnv1a64_batch(buf, offs, lens, n, out)
    return list(out)
