"""``test_faults.py``'s ``altered_answer`` for the path the Pod player's
status takes since PR 27: a whole CPU rehearsal with the items of
``ClusterClient.apply_status_batch`` altered where they are produced has to
read ``correct`` false by ``status_mismatch``.  (``faulty_daemon.py`` plants
that fault in ``ClusterClient.bulk``, which the fired rows no longer take;
it is a file this PR may not edit, PERF.md §7.)"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import generators, run  # noqa: E402
from benchmarks.generators import wave  # noqa: E402
from benchmarks.harness import cluster  # noqa: E402

CELL = "scaleup-100k"
OVERRIDE = ("nodes=20,warm_pods=40,wave_pods=400,bulk_size=100,"
            "deviceCapacity=512,nodeLeaseDurationSeconds=4")


def test_an_altered_status_batch_is_not_correct(monkeypatch, capfd):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    for mod in (generators, wave):
        monkeypatch.setattr(mod, "SETTLE_S", 4.0)
    monkeypatch.setenv("KWOK_BENCH_FAULT", "altered_answer")
    monkeypatch.setattr(cluster, "WRAPPER",
                        os.path.join(os.path.dirname(__file__), "faulty_status_batch.py"))
    real = wave.run

    def armed_run(load, t0, t1):
        open(os.path.join(ROOT, "benchmarks", "out", CELL, "control", "fault_on"), "w").close()
        return real(load, t0, t1)

    monkeypatch.setattr(wave, "run", armed_run)
    rc = run.main(["--workload", CELL, "--seed", "7", "--seconds", "6", "--trace", "0",
                   "--override", OVERRIDE])
    assert rc == 0
    line = json.loads(capfd.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    caught = line["compared"]["status_mismatch"]
    assert caught["value"] > caught["limit"]
    # every other number of the comparison holds: only the answer was altered
    assert all(c["value"] <= c["limit"] for name, c in line["compared"].items()
               if name != "status_mismatch")
