"""Point-in-time recovery: archived snapshots + WAL segments.

The reference's disaster-recovery story is "snapshot etcd wholesale"
(reference pkg/kwokctl/etcd/save.go:1) — one restore point, no
history.  This archive keeps *every* retired WAL segment plus the
periodic integrity-checked snapshots the apiserver daemon cuts
(``kwok_tpu/cmd/apiserver.py:1`` save loop), which together cover the
full committed history between the oldest retained snapshot and the
live log's head.  Two consumers:

- **PITR** — ``kwokctl snapshot restore --to-rv N``
  (``kwok_tpu/cmd/kwokctl.py:384``) calls :meth:`PitrArchive.build_state`:
  pick the newest verifiable snapshot at or below ``N``, replay
  archived + live WAL records up to ``N``, and hand back a
  ``dump_state``-shaped document that is byte-identical to what the
  live store held at resourceVersion ``N``.
- **boot fallback** — :func:`boot_recover` is the apiserver's boot
  path: when the primary state file fails its checksum
  (``kwok_tpu/cluster/wal.py:283`` read_state_file), fall back to the
  newest *verifiable* archived snapshot and replay forward through the
  archive + live log, surfacing exactly what (if anything) was lost —
  the tolerant :meth:`~kwok_tpu.cluster.store.ResourceStore.recover_wal`
  contract, never a silent guess.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Tuple

from kwok_tpu.cluster.store import ResourceStore
from kwok_tpu.cluster.wal import (
    BATCH_RECORDS,
    SEG_INFIX,
    SnapshotCorruption,
    _note_os_error,
    read_state_file,
    record_rvs,
    scan_files,
    segment_files,
    write_state_file,
)

__all__ = ["PitrArchive", "boot_recover"]

SNAP_PREFIX = "snap-"


class PitrArchive:
    """One directory of ``snap-<rv>.json`` snapshots and retired
    ``*.seg-*`` WAL segments (the WriteAheadLog's ``archive_dir``)."""

    def __init__(self, root: str):
        self.root = root
        #: per-segment max-rv cache for prune(): sealed segments are
        #: immutable, and re-reading + CRC-verifying the whole archive
        #: on every save tick would cost O(archive bytes) per interval
        self._seg_max_rv: Dict[str, Optional[int]] = {}
        os.makedirs(root, exist_ok=True)

    # ------------------------------------------------------------ contents

    def snapshot_path(self, rv: int) -> str:
        return os.path.join(self.root, f"{SNAP_PREFIX}{int(rv):012d}.json")

    def add_snapshot(self, state: Dict[str, Any]) -> str:
        path = self.snapshot_path(state.get("resourceVersion", 0))
        write_state_file(path, state)
        return path

    def snapshots(self) -> List[Tuple[int, str]]:
        """(rv, path) pairs, oldest first."""
        out: List[Tuple[int, str]] = []
        try:
            names = os.listdir(self.root)
        # a not-yet-created archive is normal; counted + logged when
        # it is anything else (cluster/wal.py tolerated-I/O tally)
        except OSError as exc:
            _note_os_error("pitr.snapshots.listdir", exc)
            return out
        for n in names:
            if n.startswith(SNAP_PREFIX) and n.endswith(".json"):
                try:
                    rv = int(n[len(SNAP_PREFIX):-len(".json")])
                except ValueError:
                    continue
                out.append((rv, os.path.join(self.root, n)))
        out.sort()
        return out

    def segments(self) -> List[str]:
        """Archived WAL segments, oldest first (their sealed names sort
        in write order)."""
        try:
            names = os.listdir(self.root)
        # same tolerant-but-counted posture as snapshots()
        except OSError as exc:
            _note_os_error("pitr.segments.listdir", exc)
            return []
        return sorted(
            os.path.join(self.root, n) for n in names if SEG_INFIX in n
        )

    def newest_verifiable(
        self, max_rv: Optional[int] = None
    ) -> Optional[Tuple[int, Dict[str, Any], List[str]]]:
        """Newest snapshot (at or below ``max_rv``) that passes its
        integrity check; corrupt candidates are skipped — and named —
        rather than trusted.  Returns ``(rv, state, skipped)``."""
        skipped: List[str] = []
        for rv, path in reversed(self.snapshots()):
            if max_rv is not None and rv > max_rv:
                continue
            try:
                return rv, read_state_file(path), skipped
            except (OSError, SnapshotCorruption, ValueError) as exc:
                skipped.append(f"{path}: {exc}")
        return None

    # ---------------------------------------------------------------- PITR

    @staticmethod
    def _filter_records(
        records: List[dict],
        to_rv: int,
        seqs: Optional[List[Optional[int]]] = None,
    ) -> List[dict]:
        """Drop (parts of) records beyond the target resourceVersion —
        status and delete batches are trimmed per item, everything else
        is kept or dropped whole.

        The target state is "immediately after commit ``to_rv``", so a
        ``type`` record must also be excluded when it was *written
        after* that commit: type registrations stamp the current rv
        without bumping it, so one registered right after the cut
        shares its rv — the frame sequence number orders them."""
        last_keep_seq = None
        if seqs is not None:
            for rec, seq in zip(records, seqs):
                if seq is None:
                    continue
                t = rec.get("t")
                covered = False
                if t in BATCH_RECORDS:
                    covered = any(
                        int(it[-1]) <= to_rv for it in rec.get("i") or []
                    )
                elif t == "txn":
                    covered = any(
                        int(sub.get("rv", 0) or 0) <= to_rv
                        for sub in rec.get("recs") or []
                    )
                elif t in ("ev", "reset"):
                    covered = int(rec.get("rv", 0) or 0) <= to_rv
                if covered and (last_keep_seq is None or seq > last_keep_seq):
                    last_keep_seq = seq
        out: List[dict] = []
        for i, rec in enumerate(records):
            t = rec.get("t")
            if t == "txn":
                # a txn is atomic for crash replay, but a point-in-time
                # rebuild targets one exact rv: trim per inner event
                # like a status batch (the byte-identity contract is
                # with the live state at that rv, which the store held
                # — under its mutex — mid-commit)
                keep = [
                    sub
                    for sub in rec.get("recs") or []
                    if sub.get("t") == "ev"
                    and int(sub.get("rv", 0) or 0) <= to_rv
                ]
                if not keep:
                    continue
                trimmed = dict(rec)
                trimmed["recs"] = keep
                trimmed["rv"] = max(
                    int(sub.get("rv", 0) or 0) for sub in keep
                )
                out.append(trimmed)
                continue
            if t in BATCH_RECORDS:
                items = [
                    it
                    for it in rec.get("i") or []
                    if int(it[-1]) <= to_rv
                ]
                if not items:
                    continue
                trimmed = dict(rec)
                trimmed["i"] = items
                trimmed["rv"] = int(items[-1][-1])
                out.append(trimmed)
                continue
            try:
                rv = int(rec.get("rv", 0) or 0)
            except (TypeError, ValueError):
                rv = 0
            if t in ("ev", "reset", "type") and rv > to_rv:
                continue
            if t == "type" and rv == to_rv and seqs is not None:
                seq = seqs[i] if i < len(seqs) else None
                if (
                    seq is not None
                    and last_keep_seq is not None
                    and seq > last_keep_seq
                ):
                    continue  # registered after the target commit
            out.append(rec)
        return out

    @staticmethod
    def _covered_rvs(records) -> set:
        """Every rv a record list commits (event, status- or
        delete-batch item, txn sub-event, voided allocation)."""
        return {
            rv
            for rec in records
            for rv in record_rvs(rec, include_void=True)
        }

    def build_state(
        self,
        to_rv: int,
        live_wal: Optional[str] = None,
        rv_continuity: bool = True,
    ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """Reconstruct the cluster state as of resourceVersion
        ``to_rv``: newest verifiable snapshot at or below it, plus the
        archived + live WAL records up to it.  Returns ``(state,
        info)`` where ``state`` is ``dump_state``-shaped (byte-identical
        to the live state at that rv) and ``info`` reports the base
        snapshot, applied record count, and any integrity findings.

        ``rv_continuity=False`` is the per-shard posture (one shard of
        a sharded store archives a deliberately sparse slice of the
        cluster rv sequence): the empty-base retention check is
        skipped here and ``info["_observed"]`` exposes the rvs this
        archive covers, so the sharded composition
        (``kwok_tpu/snapshot/sharded.py`` build_sharded_state) can run
        the retention/continuity check over the union instead."""
        base = self.newest_verifiable(max_rv=to_rv)
        files = self.segments()
        if live_wal:
            files += segment_files(live_wal)
        s = scan_files(files)
        skipped: List[str] = []
        store = ResourceStore()
        if base is not None:
            base_rv, state, skipped = base
            store.restore_state(state)
        else:
            # no snapshot at or below the target: the archive may still
            # hold the FULL log history (segments are retired by
            # renaming, never rewritten) — rebuild from an empty base,
            # but only if every committed rv up to the target is
            # provably present; otherwise the target predates retention
            base_rv = 0
            if rv_continuity:
                covered = self._covered_rvs(s.records)
                holes = [
                    rv
                    for rv in range(1, int(to_rv) + 1)
                    if rv not in covered
                ]
                if holes:
                    raise SnapshotCorruption(
                        f"rv {to_rv} is below the archive's retention floor "
                        f"(no snapshot at or below it, and rvs "
                        f"{holes[:10]}{'...' if len(holes) > 10 else ''} are "
                        "not in the retained log)"
                    )
        applied = store.replay_records(
            self._filter_records(s.records, int(to_rv), seqs=s.seqs)
        )
        built = store.dump_state()
        info = {
            "base_rv": base_rv,
            "to_rv": int(to_rv),
            "built_rv": int(built.get("resourceVersion", 0)),
            "applied_records": applied,
            "skipped_snapshots": skipped,
            "corruptions": s.corruptions,
            "torn_tail": s.torn_tail,
        }
        if not rv_continuity:
            info["_observed"] = {
                rv
                for rv in self._covered_rvs(s.records)
                if rv <= int(to_rv)
            }
            # earliest retained frame: a shard rebuilding without a
            # base snapshot is only complete when its log reaches back
            # to genesis (seq 1) — the sharded composition refuses
            # otherwise instead of silently merging a tail-only slice
            info["_first_seq"] = next(
                (q for q in s.seqs if q is not None), None
            )
        return built, info

    # ------------------------------------------------------------- hygiene

    def prune(
        self,
        keep_snapshots: int = 5,
        sealed: Optional[Dict[str, int]] = None,
    ) -> Dict[str, int]:
        """Bound the archive: keep the newest ``keep_snapshots``
        snapshots, drop older ones plus any segment fully covered by
        the oldest kept snapshot (restores below it are given up —
        deliberately, and only here).

        ``sealed`` is ``WriteAheadLog.take_archived()`` of the log that
        feeds this archive: the highest rv that log wrote into each
        segment it moved here.  A segment so vouched for is not read
        back (a scan decodes every record of it, seconds of the save
        loop's process at a cluster's write rate); one found here with
        no such word — after a restart, or put here by hand — is
        scanned, and kept for ever if damaged."""
        if sealed:
            self._seg_max_rv.update(sealed)
        snaps = self.snapshots()
        dropped = {"snapshots": 0, "segments": 0}
        if len(snaps) > keep_snapshots:
            for _rv, path in snaps[: len(snaps) - keep_snapshots]:
                try:
                    os.unlink(path)
                    dropped["snapshots"] += 1
                # prune is best-effort by design (a vanished file IS
                # pruned); anything else is counted + logged
                except OSError as exc:
                    _note_os_error("pitr.prune.snapshot", exc)
            snaps = snaps[len(snaps) - keep_snapshots:]
        if not snaps:
            return dropped
        floor = snaps[0][0]
        for seg in self.segments():
            if seg not in self._seg_max_rv:
                s = scan_files([seg])
                if s.corruptions:
                    # keep damaged segments as evidence, forever
                    self._seg_max_rv[seg] = None
                else:
                    rvs = [int(r.get("rv", 0) or 0) for r in s.records]
                    self._seg_max_rv[seg] = max(rvs) if rvs else 0
            max_rv = self._seg_max_rv[seg]
            if max_rv is not None and max_rv <= floor:
                try:
                    os.unlink(seg)
                    dropped["segments"] += 1
                    del self._seg_max_rv[seg]
                # same best-effort prune posture as the snapshot loop
                except OSError as exc:
                    _note_os_error("pitr.prune.segment", exc)
        return dropped


def boot_recover(
    store: ResourceStore,
    state_file: Optional[str],
    wal_file: Optional[str],
    pitr_root: Optional[str] = None,
    rv_continuity: bool = True,
) -> Dict[str, Any]:
    """The apiserver's boot path: snapshot, then WAL, with integrity.

    1. Load ``state_file`` if present; a checksum failure falls back to
       the newest *verifiable* archived snapshot (and replays the
       archived segments the primary snapshot would have covered).
    2. Tolerantly recover the WAL: every verifiable record is applied,
       mid-log corruption and missing resourceVersions are *reported*
       in the returned dict — never silently skipped.
    3. No snapshot verifiable anywhere → raise (refuse to serve a
       guessed state).

    Returns ``{"state_loaded", "fell_back", "fallback_rv",
    "snapshot_error", "recovery": RecoveryReport|None}``.
    """
    report: Dict[str, Any] = {
        "state_loaded": False,
        "fell_back": False,
        "fallback_rv": None,
        "snapshot_error": None,
        "recovery": None,
    }
    state = None
    if state_file and os.path.exists(state_file):
        try:
            state = read_state_file(state_file)
        except (SnapshotCorruption, ValueError) as exc:
            report["snapshot_error"] = str(exc)
    elif state_file:
        report["snapshot_error"] = f"{state_file}: state file missing"
    files = None
    if state is None:
        # corrupt OR missing state file: the archive may still hold a
        # verifiable snapshot (plus the segments compaction retired
        # behind it) — a missing file must not silently boot the
        # post-compaction tail as if it were the whole cluster
        archive = PitrArchive(pitr_root) if pitr_root else None
        best = archive.newest_verifiable() if archive is not None else None
        if best is not None:
            rv0, state, _skipped = best
            report["fell_back"] = True
            report["fallback_rv"] = rv0
            store.snapshot_fallbacks += 1
            # the fallback snapshot predates the live log's compaction
            # floor: the gap lives in the archived segments — replay
            # them ahead of the live log
            files = archive.segments()
            if wal_file:
                files = files + segment_files(wal_file)
        elif state_file and os.path.exists(state_file):
            # a present-but-corrupt state file with nothing verifiable
            # to fall back on: refuse to serve a guessed state
            raise SnapshotCorruption(
                f"state file {state_file} failed its integrity check "
                f"({report['snapshot_error']}) and no verifiable archived "
                "snapshot exists — refusing to guess at cluster state"
            )
        else:
            # genuine first boot (no state anywhere): fresh store
            report["snapshot_error"] = None
    if state is not None:
        store.restore_state(state)
        report["state_loaded"] = True
    if wal_file and (files or segment_files(wal_file)):
        # rv_continuity=False: one shard of a sharded store replays a
        # sparse slice of the cluster rv sequence — the union check
        # lives in kwok_tpu/cluster/sharding/recovery.py
        report["recovery"] = store.recover_wal(
            wal_file, files=files, rv_continuity=rv_continuity
        )
    return report
