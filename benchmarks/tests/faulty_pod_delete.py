#!/usr/bin/env python3
"""The kwok daemon with ``pod-delete`` broken underneath, for
``test_burst_rehearsal.py``: once armed (the file ``fault_on`` in
``KWOK_BENCH_CONTROL_DIR``, as for ``faulty_daemon.py``), a row that fired a
deleting stage is dropped before ``_drain_slow`` plays it, so a pod that was
asked to go keeps its finalizer and stays."""

import os
import sys

_FLAG = os.path.join(os.environ["KWOK_BENCH_CONTROL_DIR"], "fault_on")


def break_pod_delete() -> None:
    from kwok_tpu.controllers.device_player import DeviceStagePlayer

    real = DeviceStagePlayer._drain_slow

    def _drain_slow(self, transitions):
        if os.path.exists(_FLAG):
            transitions = [tr for tr in transitions if not tr.deleted]
        return real(self, transitions)

    DeviceStagePlayer._drain_slow = _drain_slow


if __name__ == "__main__":
    import threading

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                    "harness"))
    import traced_daemon

    threading.Thread(target=traced_daemon._serve, daemon=True,
                     args=(os.environ["KWOK_BENCH_CONTROL_DIR"],)).start()
    break_pod_delete()
    from kwok_tpu.cmd.kwok import main

    sys.exit(main(sys.argv[1:]))
