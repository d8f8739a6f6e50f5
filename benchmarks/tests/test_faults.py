"""The rest of a run with the timed path broken underneath: ``correct``
has to come out false, once for each fault a cell can have.  (One chip, so
no exchange between chips to leave out.)  The pod player's faults are planted
in its tick and in the status it writes, the lease lane's in ``lease_tick``,
and the log's by emptying it between the apiserver's crash and restart.  Each case is a whole rehearsal
run on the CPU at 20 nodes, about 20 s; the harness's look for a TPU is
skipped as in any rehearsal (``--override``)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import generators, run  # noqa: E402
from benchmarks.generators import wave  # noqa: E402
from benchmarks.harness import cluster  # noqa: E402

#: a lease of 4 s, renewed every second, so that a window of seconds judges the lease plane
CELLS = {
    "scaleup-100k": (wave, "nodes=20,warm_pods=40,wave_pods=400,bulk_size=100,"
                           "deviceCapacity=512,nodeLeaseDurationSeconds=4"),
}
#: fault -> the number that has to catch it
FAULTS = {"frozen_state": "never_running", "half_batch": "never_running",
          "altered_answer": "status_mismatch", "lease_frozen_state": "lease_longest_gap_s",
          "lease_half_batch": "lease_longest_gap_s", "lease_hasty": "lease_pace_ahead_s",
          "log_lost": "lost_after_crash"}


def rehearse(cell, monkeypatch, capfd, fault=None):
    gen, override = CELLS[cell]
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    for mod in (generators, wave):
        monkeypatch.setattr(mod, "SETTLE_S", 4.0)
    if fault == "log_lost":
        # the write-ahead log given up: what it held is gone when the apiserver comes back
        def lose_log(self):
            open(os.path.join(self.rt.workdir, "wal.jsonl"), "w").close()

        monkeypatch.setattr(cluster.Cluster, "after_kill", lose_log)
    elif fault:
        monkeypatch.setenv("KWOK_BENCH_FAULT", fault)
        monkeypatch.setattr(cluster, "WRAPPER",
                            os.path.join(os.path.dirname(__file__), "faulty_daemon.py"))
        real = gen.run

        def armed_run(load, t0, t1):
            flag = os.path.join(ROOT, "benchmarks", "out", cell, "control", "fault_on")
            open(flag, "w").close()
            return real(load, t0, t1)

        monkeypatch.setattr(gen, "run", armed_run)
    rc = run.main(["--workload", cell, "--seed", "7", "--seconds", "6", "--trace", "0",
                   "--override", override])
    assert rc == 0
    return json.loads(capfd.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_sound_rehearsal_is_correct(cell, monkeypatch, capfd):
    line = rehearse(cell, monkeypatch, capfd)
    assert line["correct"] is True
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(cell, fault, monkeypatch, capfd):
    line = rehearse(cell, monkeypatch, capfd, fault)
    assert line["correct"] is False
    caught = line["compared"][FAULTS[fault]]
    assert caught["value"] > caught["limit"]
