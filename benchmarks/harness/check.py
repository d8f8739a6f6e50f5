"""What decides ``correct``: the guarantees the configuration states, held
against what the timed window produced, every answer and not a sample.

Each number is an exact count with the limit 0, but for two of the lease
plane's, which are seconds held to the configuration's lease block.
``evidence`` is what the run gathered after the window closed and the due
answers were waited for:

- ``created``: name -> pod as sent, for every acknowledged create
- ``deleted``: names of acknowledged deletes
- ``in_window``: names created inside the window
- ``running_seen``: names the watcher saw turn Running
- ``deleted_seen``: names whose DELETED event the watcher saw
- ``listed``: name -> pod, from a paged LIST after the wait
- ``kwok``: the daemon's ``/metrics`` samples after the window
- ``node_ip``: the configuration's node address
- ``lease``: the lease plane's: ``events`` (the watcher's, every renewal
  stamped on arrival), ``t0`` and ``t_end`` (the window's opening, the final
  LIST of the leases), ``nodes``, ``listed`` (node -> Lease of that LIST),
  and the configuration's ``duration_s``, ``renew_every_s``,
  ``early_tolerance`` and ``holder``
- ``after_crash``: name -> pod (None: gone) as the apiserver serves it
  after it was killed and started again from its snapshot and WAL, for
  the pods of ``crash_expected`` (name -> pod as acknowledged)

A late answer is late, not wrong; only one that never came inside the
wait, or says the wrong thing, counts here."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from . import metrics, promtext


def numbers(evidence: dict, reference) -> Tuple[Dict[str, Tuple[float, float]], Optional[str]]:
    """(name -> (number, limit), the first status mismatch in words); the
    run is correct when no number is above its limit."""
    created, deleted = evidence["created"], set(evidence["deleted"])
    listed, running_seen = evidence["listed"], evidence["running_seen"]
    alive = [n for n in created if n not in deleted]

    def status_of(n: str) -> dict:
        return (listed.get(n) or {}).get("status") or {}

    live_status = [status_of(n) for n in alive if n in listed]
    mismatch = 0
    first = None
    for n in evidence["in_window"]:
        if n in deleted:
            status = evidence["running_status"].get(n)
        else:
            status = status_of(n)
        why = reference.pod_mismatch(created[n], status, evidence["node_ip"])
        if why is not None:
            mismatch += 1
            first = first or f"{n}: {why}"
    stale = sum(
        1 for n in alive
        if n in running_seen and n in listed
        and (status_of(n).get("phase") != "Running" or not status_of(n).get("podIP"))
    )
    kwok = evidence["kwok"]
    on_device = sum(
        promtext.total(kwok, "kwok_stage_backend_info", {"kind": k, "backend": "device"}) or 0
        for k in ("Pod", "Node"))
    lease = evidence["lease"]
    gap, _who = metrics.lease_longest_gap(lease["events"], lease["t0"], lease["t_end"],
                                          lease["nodes"])
    ahead, _pairs = metrics.lease_pace(lease["events"], lease["t0"], lease["t_end"],
                                       lease["renew_every_s"], lease["early_tolerance"])
    held = {"holderIdentity": lease["holder"], "leaseDurationSeconds": lease["duration_s"]}
    unheld = sum(
        1 for n in lease["nodes"]
        if any(((lease["listed"].get(n) or {}).get("spec") or {}).get(k) != v
               for k, v in held.items()))
    lost = sum(1 for n, want in evidence["crash_expected"].items()
               if not _same_pod(want, evidence["after_crash"].get(n)))
    out = {
        "acked_creates_missing": (sum(1 for n in alive if n not in listed), 0),
        "acked_deletes_present": (sum(1 for n in deleted if n in listed), 0),
        "never_running": (sum(1 for n in evidence["in_window"] if n not in running_seen), 0),
        "never_deleted": (sum(1 for n in deleted if n not in evidence["deleted_seen"]), 0),
        "seen_running_not_running": (stale, 0),
        "status_mismatch": (mismatch, 0),
        "duplicate_pod_ips": (reference.duplicate_ips(live_status), 0),
        "kinds_off_device": (2 - on_device, 0),
        "host_backend_transitions": (
            promtext.total(kwok, "kwok_stage_transitions_total", {"backend": "host"}) or 0, 0),
        "lost_after_crash": (lost, 0),
        "leases_not_held": (unheld, 0),
        "lease_longest_gap_s": (gap, lease["duration_s"]),
        "lease_pace_ahead_s": (ahead, lease["early_tolerance"] * lease["renew_every_s"]),
    }
    return out, first


def _same_pod(want: dict, got: Optional[dict]) -> bool:
    """The pod read back says what was acknowledged: its name, spec and,
    where one was acknowledged, status."""
    if got is None:
        return False
    return all((got.get(k) or {}) == want[k] for k in ("spec", "status") if k in want) \
        and got["metadata"]["name"] == want["metadata"]["name"]


def correct(nums: Dict[str, Tuple[float, float]]) -> bool:
    return all(v <= limit for v, limit in nums.values())
