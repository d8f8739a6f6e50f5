#!/usr/bin/env python3
"""The kwok daemon with one more thread, started by the harness in the
daemon's place with the daemon's own arguments.

Only the process that holds the chip can trace it or read its memory
statistics, and the program has no switch for either yet.  The thread
sleeps in a blocking read of the FIFO ``commands`` in the directory named
by ``KWOK_BENCH_CONTROL_DIR`` (it polls nothing, so an untraced run holds
an idle thread and no more) and serves one command a line:

- ``start_trace <directory>``: ``jax.profiler.start_trace``, then the file
  ``trace_started`` is written
- ``stop_trace``: ``jax.profiler.stop_trace``, then ``trace_done``
- ``memstats``: ``memory_stats()`` of the fullest device, as JSON, into
  ``memstats.json``

Everything else is ``kwok_tpu.cmd.kwok.main``."""

import json
import os
import sys
import threading
import time

FIFO = "commands"


def _answer(ctl: str, name: str, text: str) -> None:
    tmp = os.path.join(ctl, f".{name}.tmp")
    with open(tmp, "w", encoding="utf-8") as f:
        f.write(text)
    os.replace(tmp, os.path.join(ctl, name))


def _do(ctl: str, command: str, arg: str) -> None:
    import jax

    if command == "start_trace":
        jax.profiler.start_trace(arg)
        _answer(ctl, "trace_started", str(time.monotonic()))
    elif command == "stop_trace":
        t = time.monotonic()
        jax.profiler.stop_trace()
        _answer(ctl, "trace_done", str(t))
    elif command == "memstats":
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        best = max(stats, key=lambda s: s.get("peak_bytes_in_use") or 0)
        _answer(ctl, "memstats.json", json.dumps(best))


def _serve(ctl: str) -> None:
    while True:
        # open() blocks until the harness opens the FIFO to write, and the
        # loop ends when it closes it: no polling in between
        with open(os.path.join(ctl, FIFO), encoding="utf-8") as f:
            for line in f:
                command, _, arg = line.strip().partition(" ")
                try:
                    _do(ctl, command, arg)
                except Exception as exc:  # noqa: BLE001 — the daemon must go on
                    print(f"traced_daemon: {type(exc).__name__}: {exc}", file=sys.stderr,
                          flush=True)
                    _answer(ctl, "error", f"{type(exc).__name__}: {exc}")


if __name__ == "__main__":
    ctl = os.environ.get("KWOK_BENCH_CONTROL_DIR")
    if ctl:
        threading.Thread(target=_serve, args=(ctl,), daemon=True, name="bench-control").start()
    from kwok_tpu.cmd.kwok import main

    sys.exit(main(sys.argv[1:]))
