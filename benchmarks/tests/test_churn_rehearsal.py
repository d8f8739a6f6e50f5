"""``churn-100k`` rehearsed whole on the CPU at 20 nodes: a sound run reads
``correct`` true, every window pod's status against
``references/general_stages.py``; with ``pod-create``'s status altered
underneath (``faulty_pod_create.py``, armed as the window opens, so set-up
is sound) it reads ``correct`` false by ``status_mismatch``."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.generators import churn  # noqa: E402
from benchmarks.harness import cluster  # noqa: E402

CELL = "churn-100k"
OVERRIDE = ("nodes=20,standing_pods=40,crashloop_pods=20,rolling_pods=80,bulk_size=100,"
            "deviceCapacity=512,nodeLeaseDurationSeconds=4,warm_s=10")


def rehearse(monkeypatch, capfd, broken=False):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    if broken:
        monkeypatch.setattr(cluster, "WRAPPER",
                            os.path.join(os.path.dirname(__file__), "faulty_pod_create.py"))
        real = churn.run

        def armed_run(load, t0, t1):
            open(os.path.join(ROOT, "benchmarks", "out", CELL, "control", "fault_on"),
                 "w").close()
            return real(load, t0, t1)

        monkeypatch.setattr(churn, "run", armed_run)
    rc = run.main(["--workload", CELL, "--seed", "3200000007", "--seconds", "20", "--trace", "0",
                   "--override", OVERRIDE])
    assert rc == 0
    return json.loads(capfd.readouterr().out.strip().splitlines()[-1])


def test_a_sound_rehearsal_is_correct(monkeypatch, capfd):
    line = rehearse(monkeypatch, capfd)
    assert line["correct"] is True and line["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line
    assert set(line["metrics"]) == {"transitions_per_s", "lease_renew_interval_p95_s", "setup_s"}
    # 20 crash-loopers flip every 5 s and 80 rolling pods come and go every 12
    assert line["metrics"]["transitions_per_s"]["value"] > 10
    assert line["attempted"] > 20 + 80


def test_an_altered_pod_create_status_is_not_correct(monkeypatch, capfd):
    line = rehearse(monkeypatch, capfd, broken=True)
    assert line["correct"] is False
    caught = line["compared"]["status_mismatch"]
    assert caught["value"] > caught["limit"]
    # every other number of the comparison holds: only the answer was altered
    assert all(c["value"] <= c["limit"] for name, c in line["compared"].items()
               if name != "status_mismatch")
