"""``test_burst_rehearsal.py``'s broken ``pod-delete`` for the path the rows
of a deleting stage take since PR 29: a whole CPU rehearsal of ``burst-1k``
with the delete batch dropped underneath (``faulty_delete_batch.py``, armed
as the window opens) has to read ``correct`` false by ``never_deleted``.
(``faulty_pod_delete.py`` plants that fault in ``_drain_slow``, which those
rows no longer take; it is a file this PR may not edit, PERF.md §7.)"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import generators, run  # noqa: E402
from benchmarks.generators import burst_cycle  # noqa: E402
from benchmarks.harness import cluster  # noqa: E402

CELL = "burst-1k"
OVERRIDE = ("nodes=20,standing_pods=50,burst_pods=20,bulk_size=20,"
            "deviceCapacity=512,nodeLeaseDurationSeconds=4")


def test_a_dropped_delete_batch_is_not_correct(monkeypatch, capfd):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    for mod in (generators, burst_cycle):
        monkeypatch.setattr(mod, "SETTLE_S", 4.0)
    monkeypatch.setattr(cluster, "WRAPPER",
                        os.path.join(os.path.dirname(__file__), "faulty_delete_batch.py"))
    real = burst_cycle.run

    def armed_run(load, t0, t1):
        open(os.path.join(ROOT, "benchmarks", "out", CELL, "control", "fault_on"), "w").close()
        return real(load, t0, t1)

    monkeypatch.setattr(burst_cycle, "run", armed_run)
    rc = run.main(["--workload", CELL, "--seed", "2900000007", "--seconds", "6", "--trace", "0",
                   "--override", OVERRIDE])
    assert rc == 0
    line = json.loads(capfd.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    caught = line["compared"]["never_deleted"]
    assert caught["value"] == 20 and caught["limit"] == 0
    # the burst that stuck is still served, terminating; it did turn Running
    assert line["compared"]["acked_deletes_present"]["value"] == 20
    assert line["compared"]["never_running"]["value"] == 0
    assert line["failed"] == 20
