"""The accelerator a process runs on: compile cache, identity, guard.

Every process that jits (the kwok daemon under ``--backend device``,
``bench.py``, ``chip_smoke.py``'s children, ``__graft_entry__.py``)
calls :func:`enable_compile_cache` before its first jit and, where the
device is the point of the run, :func:`require_accelerator` right
after.  No reference counterpart: the reference has no device; the
backend seam this guards is the one SURVEY.md:417-422 draws (the
``--simulator-backend=tpu`` selection, ``--backend device`` here).

- **Compile cache.**  The tick programs recompile per ``num_ticks``,
  per capacity doubling and per power-of-two scatter width; on the chip
  each compile stalls the tick thread, and a restarted daemon pays them
  all again.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already
  uses that directory and this module sets nothing.  Where it is not,
  the cache lives in ONE fixed directory inside the checkout
  (:data:`DEFAULT_CACHE_DIR`) — never a temporary name, pid or time, so
  a second process finds what the first compiled.  Children inherit
  the environment (``ctl/runtime.py::start_component``), so a variable
  set outside reaches every daemon.
- **No silent CPU.**  With ``JAX_PLATFORMS`` unset, a JAX that cannot
  get the TPU carries on on the CPU with a warning.
  :func:`require_accelerator` turns that into an error unless
  ``JAX_PLATFORMS`` names ``cpu`` itself (tests, ``tools/check.sh``).
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Tuple

__all__ = [
    "DEFAULT_CACHE_DIR",
    "NoAccelerator",
    "compile_stats",
    "device_info",
    "enable_compile_cache",
    "pinned_platforms",
    "require_accelerator",
    "thread_compiles",
]

#: the in-checkout compile cache used when JAX_COMPILATION_CACHE_DIR is
#: not set (git-ignored; fixed so every process of a checkout shares it)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache",
)

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"

_mut = threading.Lock()
_listening = False
#: the calling thread's own programs asked of the backend and, of them,
#: persistent-cache hits: JAX calls a listener on the thread that compiles
_mine = threading.local()
_stats = {
    "compilations": 0,
    "compile_seconds": 0.0,
    "trace_seconds": 0.0,
    "cache_hits": 0,
    "cache_misses": 0,
}


class NoAccelerator(RuntimeError):
    """JAX initialised on the CPU without ``JAX_PLATFORMS`` asking for it."""


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _mine.hits = getattr(_mine, "hits", 0) + 1
        with _mut:
            _stats["cache_hits"] += 1
    elif event == _CACHE_MISS:
        with _mut:
            _stats["cache_misses"] += 1


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    if event == _BACKEND_COMPILE:
        # one per program JAX asks the backend for: a cold compile or a
        # persistent-cache retrieval (the duration covers either)
        _mine.programs = getattr(_mine, "programs", 0) + 1
        with _mut:
            _stats["compilations"] += 1
            _stats["compile_seconds"] += duration_secs
    elif event in _TRACE_EVENTS:
        with _mut:
            _stats["trace_seconds"] += duration_secs


def enable_compile_cache() -> str:
    """Place the persistent compile cache and start counting compiles;
    returns the directory in effect.  Idempotent; call before the first
    jit (a program compiled earlier is neither cached nor counted)."""
    global _listening
    import jax

    with _mut:
        if not _listening:
            _listening = True
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # JAX's default skips programs that compiled in under a second; the
    # tick thread stalls on those too (scatter widths, lease lanes)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_CACHE_DIR


def compile_stats() -> Dict[str, float]:
    """Programs requested from the backend since
    :func:`enable_compile_cache`, seconds spent on them (backend
    compile or cache retrieval, and tracing + lowering apart), and
    persistent-cache hits and misses."""
    with _mut:
        out = dict(_stats)
    out["compile_seconds"] = round(out["compile_seconds"], 3)
    out["trace_seconds"] = round(out["trace_seconds"], 3)
    return out


def thread_compiles() -> Tuple[int, int]:
    """Programs the calling thread has asked of the backend since
    :func:`enable_compile_cache`, and how many of them the persistent
    cache had.  The difference of two readings round a call tells a cold
    compile (programs grew by more than hits) from a fetch, whatever
    another thread compiled meanwhile."""
    return getattr(_mine, "programs", 0), getattr(_mine, "hits", 0)


def device_info() -> Dict[str, object]:
    """``platform``, ``device_kind`` and device count as JAX reports
    them.  Initialises the backend — the caller now holds the chip."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_stats() -> Dict[str, int]:
    """``memory_stats()`` of the local device with the highest peak;
    {} where the backend keeps none (XLA:CPU)."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(stats, key=lambda s: s.get("peak_bytes_in_use") or 0, default={})


def pinned_platforms() -> List[str]:
    """The platforms ``JAX_PLATFORMS`` names, in order; [] when unset."""
    return [
        p.strip().lower()
        for p in os.environ.get("JAX_PLATFORMS", "").split(",")
        if p.strip()
    ]


def require_accelerator() -> Dict[str, object]:
    """:func:`device_info`, or :class:`NoAccelerator` when JAX fell
    back to the CPU without ``JAX_PLATFORMS`` naming ``cpu``."""
    info = device_info()
    if info["platform"] == "cpu" and "cpu" not in pinned_platforms():
        raise NoAccelerator(
            "JAX found no accelerator and is running on the CPU "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}); "
            "set JAX_PLATFORMS=cpu to run on the CPU on purpose"
        )
    return info
