"""``burst-1k`` rehearsed whole on the CPU at 20 nodes: a sound run reads
``correct`` true with every delete seen and gone; with ``pod-delete`` broken
underneath (``faulty_pod_delete.py``, armed as the window opens, so set-up
and its warm cycles are sound) the pods that were asked to go stay, and the
run reads ``correct`` false by ``never_deleted``."""

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


def rehearse(monkeypatch, capfd, broken=False):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    for mod in (generators, burst_cycle):
        monkeypatch.setattr(mod, "SETTLE_S", 4.0)
    if broken:
        monkeypatch.setattr(cluster, "WRAPPER",
                            os.path.join(os.path.dirname(__file__), "faulty_pod_delete.py"))
        real = burst_cycle.run

        def armed_run(load, t0, t1):
            open(os.path.join(ROOT, "benchmarks", "out", CELL, "control", "fault_on"),
                 "w").close()
            return real(load, t0, t1)

        monkeypatch.setattr(burst_cycle, "run", armed_run)
    rc = run.main(["--workload", CELL, "--seed", "2800000007", "--seconds", "6", "--trace", "0",
                   "--override", OVERRIDE])
    assert rc == 0
    return json.loads(capfd.readouterr().out.strip().splitlines()[-1])


def test_a_sound_rehearsal_is_correct(monkeypatch, capfd):
    line = rehearse(monkeypatch, capfd)
    assert line["correct"] is True and line["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line
    # the three end-to-end metrics the benchmark has, and cycles enough to judge a rate
    assert set(line["metrics"]) == {"transitions_per_s", "lease_renew_interval_p95_s", "setup_s"}
    assert line["metrics"]["transitions_per_s"]["value"] * 6 >= 10 * 20
    # attempted: creates and deletes of the window (one each a pod) and the 20 nodes
    assert (line["attempted"] - 20) % 2 == 0 and line["attempted"] > 20


def test_a_broken_pod_delete_is_not_correct(monkeypatch, capfd):
    line = rehearse(monkeypatch, capfd, broken=True)
    assert line["correct"] is False
    caught = line["compared"]["never_deleted"]
    assert caught["value"] == 20 and caught["limit"] == 0
    # the burst that stuck is still served, terminating; it did turn Running
    assert line["compared"]["acked_deletes_present"]["value"] == 20
    assert line["compared"]["never_running"]["value"] == 0
    assert line["failed"] == 20
