"""``sched-5k`` rehearsed whole on the CPU at 20 nodes: one standing pod a
node, 20 init pods and a backlog of 40 that only the cluster's scheduler
can bind.  A traced run reads ``correct`` true, prints the three per-layer
metrics of the layer ``scheduler``, and every pod created unbound was
counted as one bind by the apiserver's ``kwok_pod_binds_total``."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.harness import promtext  # noqa: E402

CELL = "sched-5k"
OVERRIDE = ("nodes=20,standing_pods=20,init_pods=20,backlog=40,bulk_size=20,"
            "deviceCapacity=512,nodeLeaseDurationSeconds=4")
NEW = ("create_to_bind_mean_s", "bind_request_mean_ms", "sched_create_to_running_p95_s")


def test_a_traced_rehearsal_is_correct_and_counts_every_bind(monkeypatch, capfd, tmp_path):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    keep = str(tmp_path / "keep")
    rc = run.main(["--workload", CELL, "--seed", "4200000007", "--seconds", "12",
                   "--trace", "1", "--keep", keep, "--override", OVERRIDE])
    assert rc == 0
    out, err = capfd.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line
    assert set(NEW) <= set(line["metrics"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m["create_to_bind_mean_s"] < m["sched_create_to_running_p95_s"]
    assert 0 < m["bind_request_mean_ms"] < 1000
    assert "20 init pods bound by the scheduler and Running" in err
    assert "0 violations of the reference" in err
    with open(os.path.join(keep, "scrapes.json"), encoding="utf-8") as f:
        scrapes = json.load(f)
    binds = {k: promtext.total(scrapes[k]["apiserver"], "kwok_pod_binds_total", {})
             for k in ("before", "after")}
    # set-up bound the 20 init pods and nothing else; by the closing scrape
    # every bind was one scheduler PATCH, and nothing was left to a second
    assert binds["before"] == 20
    patches = promtext.total(scrapes["after"]["apiserver"],
                             "kwok_apiserver_request_duration_seconds_count",
                             {"verb": "PATCH", "kind": "pods"})
    assert binds["after"] == patches > 20
    # no pod the scheduler bound met a signature the tick had not seen
    assert promtext.delta(scrapes["before"]["kwok"], scrapes["after"]["kwok"],
                          "kwok_device_new_shapes_total", {"cause": "signatures"}) in (None, 0)
