#!/usr/bin/env python3
"""The kwok daemon with ``pod-create``'s status altered where it is
produced, for ``test_churn_rehearsal.py``: once armed (the file ``fault_on``
in ``KWOK_BENCH_CONTROL_DIR``, as for ``faulty_daemon.py``), the status
patch of a row that ``_drain_slow`` plays beside a finalizer patch (what
``pod-create`` of ``pod-general`` is, and no other stage) names another host
address.  ``pod-ready`` leaves ``hostIP`` as it finds it, so the pod turns
Running with the address ``pod-create`` gave it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("KWOK_BENCH_FAULT", "altered_answer")
import faulty_daemon  # noqa: E402  (armed, _alter)


def break_pod_create() -> None:
    from kwok_tpu.controllers.device_player import DeviceStagePlayer

    real = DeviceStagePlayer._collect_ops

    def _collect_ops(self, tr):
        got = real(self, tr)
        if got is not None and faulty_daemon.armed() and tr.stage_name == "pod-create":
            faulty_daemon._alter([op.get("data") for op in got[1]])
        return got

    DeviceStagePlayer._collect_ops = _collect_ops


if __name__ == "__main__":
    import threading

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                    "harness"))
    import traced_daemon

    threading.Thread(target=traced_daemon._serve, daemon=True,
                     args=(os.environ["KWOK_BENCH_CONTROL_DIR"],)).start()
    break_pod_create()
    from kwok_tpu.cmd.kwok import main

    sys.exit(main(sys.argv[1:]))
