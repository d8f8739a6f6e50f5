#!/usr/bin/env python3
"""The apiserver with watch events withheld from one informer, for
``test_informed_churn_rehearsal.py``: started by the harness in the
apiserver's place.  Once armed (the file ``fault_on`` in
``KWOK_BENCH_CONTROL_DIR``, as for ``faulty_daemon.py``; the test drops it
inside the window after the last restart of an informer, so set-up is sound
and no later LIST repairs the store) the first rolling pod whose ADDED goes
to a label-scoped watcher is the victim: every later event of that pod is
left out of the share of the watchers with that selector.  The informer
behind it keeps the pod as it was created, the final LIST has it as it
turned Running: guarantee g3 broken at one object of the window."""

import os
import sys

_FLAG = os.path.join(os.environ["KWOK_BENCH_CONTROL_DIR"], "fault_on")


def break_delivery() -> None:
    from kwok_tpu.cluster.store import Watcher

    victim = []  # (pod name, the route of the watchers that lose its events)

    def withhold(w, ev) -> bool:
        if w._route[0] != "label":
            return False
        name = ev.object["metadata"]["name"]
        if victim:
            return (name, w._route) == victim[0]
        if ev.type == "ADDED" and name.startswith("roll-") and os.path.exists(_FLAG):
            victim.append((name, w._route))
            print(f"faulty_watch_delivery: {name}'s events after this ADDED are withheld "
                  f"from {w._route}", file=sys.stderr, flush=True)
        return False

    push, push_batch = Watcher._push, Watcher._push_batch
    Watcher._push = lambda w, ev: None if withhold(w, ev) else push(w, ev)
    Watcher._push_batch = lambda w, evs: push_batch(
        w, [ev for ev in evs if not withhold(w, ev)])


if __name__ == "__main__":
    break_delivery()
    from kwok_tpu.cmd.apiserver import main

    sys.exit(main(sys.argv[1:]))
