#!/usr/bin/env python3
"""The kwok daemon with the answer of its status-batch verb altered where
it is produced, for ``test_status_batch_fault.py``: ``faulty_daemon.py``'s
``altered_answer`` breaks ``ClusterClient.bulk``, which since PR 27 carries
only what a batch cannot express; the Pod player's fired rows go through
``ClusterClient.apply_status_batch``.  Armed the same way (the file
``fault_on`` in ``KWOK_BENCH_CONTROL_DIR``)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import faulty_daemon  # noqa: E402  (armed, _alter; reads KWOK_BENCH_FAULT)


def break_status_batch() -> None:
    from kwok_tpu.cluster.client import ClusterClient

    real = ClusterClient.apply_status_batch

    def apply_status_batch(self, kind, items, exclude=None):
        if faulty_daemon.armed():
            for item in items:
                faulty_daemon._alter(item[2])
        return real(self, kind, items, exclude=exclude)

    ClusterClient.apply_status_batch = apply_status_batch


if __name__ == "__main__":
    import threading

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                    "harness"))
    import traced_daemon

    threading.Thread(target=traced_daemon._serve, daemon=True,
                     args=(os.environ["KWOK_BENCH_CONTROL_DIR"],)).start()
    break_status_batch()
    from kwok_tpu.cmd.kwok import main

    sys.exit(main(sys.argv[1:]))
