#!/usr/bin/env python3
"""The kwok daemon with ``pod-delete`` broken underneath, for
``test_delete_batch_fault.py``: ``faulty_pod_delete.py`` for the path the
rows of a deleting stage take since PR 29.  Once armed (the file ``fault_on``
in ``KWOK_BENCH_CONTROL_DIR``), a delete batch is dropped before
``_commit_delete_locked`` sends it, and so is a row that ``_drain_slow``
would delete, so a pod that was asked to go keeps its finalizer and stays."""

import os
import sys

_FLAG = os.path.join(os.environ["KWOK_BENCH_CONTROL_DIR"], "fault_on")


def break_pod_delete() -> None:
    from kwok_tpu.controllers.device_player import DeviceStagePlayer

    commit, slow = DeviceStagePlayer._commit_delete_locked, DeviceStagePlayer._drain_slow

    def _commit_delete_locked(self, rows, items):
        if os.path.exists(_FLAG):
            return []  # nothing sent, nothing refused
        return commit(self, rows, items)

    def _drain_slow(self, transitions):
        if os.path.exists(_FLAG):
            transitions = [tr for tr in transitions if not tr.deleted]
        return slow(self, transitions)

    DeviceStagePlayer._commit_delete_locked = _commit_delete_locked
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
