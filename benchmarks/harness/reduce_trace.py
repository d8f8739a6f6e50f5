#!/usr/bin/env python3
"""Child of ``run.py``: read the profiler trace the daemon wrote and run
the named reductions of ``benchmarks/reductions/`` over it.  Pinned to the
CPU by its parent.  Prints one JSON object: ``busy_s``, ``window_s``,
``metrics`` (reduction name -> value; a reduction that finds nothing to
read is left out) and ``breakdown``."""

from __future__ import annotations

import importlib
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))]

from benchmarks.reductions import trace_model  # noqa: E402


def main(argv) -> int:
    trace_dir, ctx_path = argv
    with open(ctx_path, encoding="utf-8") as f:
        ctx = json.load(f)
    path = trace_model.find_xplane(trace_dir)
    if path is None:
        print(json.dumps({"metrics": {}}))
        return 0
    trace = trace_model.load(path)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json"),
              encoding="utf-8") as f:
        ctx["peaks"] = json.load(f).get(ctx.get("device_kind"))
    out = {"metrics": {}}
    for name in ctx["names"]:
        mod = importlib.import_module(f"benchmarks.reductions.{name}")
        v = mod.reduce(trace, ctx)
        if v is not None:
            out["metrics"][name] = v
    busy = trace_model.busy_and_window(trace)
    if busy is not None:
        out["busy_s"], out["window_s"] = busy
    out["breakdown"] = trace_model.breakdown(trace)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
