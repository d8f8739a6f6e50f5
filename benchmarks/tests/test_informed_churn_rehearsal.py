"""``watched-churn`` rehearsed whole on the CPU at 20 nodes with 6 informers
(2 cluster-wide, 4 scoped) and a restart every 2 s: a sound run reads
``correct`` true with every informer's store equal to a final LIST through
its selector, and a traced one prints the five per-layer metrics PR 35
appends; with one watch event withheld from one informer underneath
(``faulty_watch_delivery.py``, armed after the window's last restart, so that no
later LIST repairs the store) it reads ``correct`` false by ``status_mismatch``
alone, naming the informer."""

import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.generators import informed_churn  # noqa: E402
from benchmarks.harness import cluster  # noqa: E402

CELL = "watched-churn"
OVERRIDE = ("nodes=20,standing_pods=40,crashloop_pods=20,rolling_pods=80,bulk_size=100,"
            "deviceCapacity=512,nodeLeaseDurationSeconds=4,warm_s=10,"
            "informers=6,cluster_wide=2,scoped=4,page_size=20,restart_every_s=2")
NEW = ("list_share", "list_page_mean_ms", "watch_filter_share", "watch_evictions_in_window",
       "list_snapshots_expired")


def wrap_apiserver(monkeypatch):
    """The harness's own seam, one component further: after it has pointed
    the kwok daemon at its wrapper, point the apiserver at the faulty one."""
    real = cluster.Cluster._wrap_daemon
    faulty = os.path.join(os.path.dirname(__file__), "faulty_watch_delivery.py")

    def wrap(self, rt):
        real(self, rt)
        path = os.path.join(rt.workdir, "components.json")
        with open(path, encoding="utf-8") as f:
            comps = json.load(f)
        wrapped = 0
        for c in comps:
            args = c["args"]
            for i in range(len(args) - 1):
                if args[i] == "-m" and args[i + 1] == "kwok_tpu.cmd.apiserver":
                    args[i:i + 2] = [faulty]
                    wrapped += 1
                    break
        assert wrapped == 1
        with open(path, "w", encoding="utf-8") as f:
            json.dump(comps, f)

    monkeypatch.setattr(cluster.Cluster, "_wrap_daemon", wrap)


def rehearse(monkeypatch, capfd, trace=0, broken=False):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    if broken:
        wrap_apiserver(monkeypatch)
        real = informed_churn.run

        def arm(at):
            time.sleep(max(at - time.monotonic(), 0))
            open(os.path.join(ROOT, "benchmarks", "out", CELL, "control", "fault_on"),
                 "w").close()

        def armed_run(load, t0, t1):
            # the last restart is at t1 - 2 s; the pods created after it are
            # the window's and are never deleted
            threading.Thread(target=arm, args=(t1 - 1.6,), daemon=True).start()
            return real(load, t0, t1)

        monkeypatch.setattr(informed_churn, "run", armed_run)
    rc = run.main(["--workload", CELL, "--seed", "3500000007", "--seconds", "20",
                   "--trace", str(trace), "--override", OVERRIDE])
    assert rc == 0
    out, err = capfd.readouterr()
    return json.loads(out.strip().splitlines()[-1]), err


def test_a_sound_traced_rehearsal_is_correct_and_prints_the_new_metrics(monkeypatch, capfd):
    line, err = rehearse(monkeypatch, capfd, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line
    assert set(NEW) <= set(line["metrics"])
    assert {"watch_line_encoded_share", "watch_encode_share", "watch_lag_mean_ms"} <= \
        set(line["metrics"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["watch_evictions_in_window"] == 0 and m["list_snapshots_expired"] == 0
    assert 0 < m["list_share"] < 100 and 0 < m["watch_filter_share"] < 100
    assert 0 < m["list_page_mean_ms"] < 1000
    # counts, not speeds: five /r/ streams and six informers share a Pod line
    assert m["watch_line_encoded_share"] < 30
    assert "informers restarted in the window: 10" in err
    assert "0 window pods an informer got wrong, 0 findings that name no window pod" in err
    # 16 LISTs (6 first ones, 10 restarts) of 3 or more pages of 20
    counts = err.split("informers: {", 1)[1].split("}", 1)[0]
    got = dict(kv.split(": ") for kv in counts.replace("'", "").split(", "))
    assert int(got["lists"]) == 16 and int(got["pages"]) >= 16 * 3
    assert int(got["gone_410"]) == 0 and int(got["events"]) > 1000


def test_a_withheld_watch_event_is_not_correct(monkeypatch, capfd):
    line, err = rehearse(monkeypatch, capfd, broken=True)
    assert line["correct"] is False
    caught = line["compared"]["status_mismatch"]
    assert caught["value"] == 1 and caught["limit"] == 0
    # every other number of the comparison holds: only that delivery was broken
    assert all(c["value"] <= c["limit"] for name, c in line["compared"].items()
               if name != "status_mismatch")
    first = next(ln for ln in err.splitlines() if ln.startswith("first mismatch: "))
    assert "informer" in first and "in the informer's store" in first and "final LIST" in first
    assert "1 window pods an informer got wrong, 0 findings that name no window pod" in err
