"""The apiserver's snapshot through a forked child (ISSUE 31): the save
loop cuts the store by reference, a child serialises the cut once and
fsyncs it under names of its own, the parent waits and commits by rename
(``kwok_tpu/snapshot/child.py``, ``kwok_tpu/cmd/apiserver.py``).  What a
save guaranteed it still does: a snapshot is visible only whole, a save
that cannot be written leaves the previous snapshot and the whole WAL, a
kill in mid-save loses nothing acknowledged, a SIGTERM in mid-save ends in
one whole snapshot at the last rv.

No test sleeps for a fixed time: each wait polls its condition under a
deadline, and each test runs under an alarm of its own."""

import contextlib
import errno
import functools
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.request
import warnings

import pytest

from kwok_tpu.chaos.fs_pressure import FsPressure
from kwok_tpu.cluster import wal as walmod
from kwok_tpu.cluster.client import ClusterClient
from kwok_tpu.cluster.sharding import shard_of
from kwok_tpu.cluster.sharding.layout import (
    shard_pitr_dir,
    shard_state_path,
    shard_wal_path,
)
from kwok_tpu.cluster.store import ResourceStore
from kwok_tpu.ctl.components import free_port
from kwok_tpu.snapshot.child import save_in_child, sweep_temporaries
from kwok_tpu.snapshot.pitr import PitrArchive
from kwok_tpu.utils import telemetry

# this process has imported jax (tests/conftest.py), whose fork hook warns;
# the apiserver process imports none of it
pytestmark = pytest.mark.filterwarnings("ignore:os.fork\\(\\) was called:RuntimeWarning")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TEMPORARY = re.compile(r"\.tmp\.\d+$")


def limit(seconds):
    """The test's own time limit: an alarm that also interrupts a
    ``waitpid`` that would never return."""

    def deco(fn):
        @functools.wraps(fn)
        def run(*a, **kw):
            def expired(signum, frame):
                raise TimeoutError(f"{fn.__name__} ran over its {seconds} s")

            before = signal.signal(signal.SIGALRM, expired)
            signal.alarm(seconds)
            try:
                return fn(*a, **kw)
            finally:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, before)

        return run

    return deco


def wait_for(cond, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = cond()
        if got:
            return got
        time.sleep(0.005)
    raise AssertionError(f"not in {timeout} s: {what}")


def make_pod(i, ns="default"):
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": f"pod-{i}", "namespace": ns, "labels": {"app": "x", "é": "ü"}},
        "spec": {"nodeName": "node-0", "containers": [{"name": "app", "image": "x"}]},
        "status": {"phase": "Pending",
                   "conditions": [{"type": t, "status": "False"}
                                  for t in ("Initialized", "Ready", "ContainersReady")]},
    }


def temporaries(*dirs):
    return sorted(os.path.join(d, n) for d in dirs if os.path.isdir(d)
                  for n in os.listdir(d) if TEMPORARY.search(n))


def sans_integrity(state):
    return {k: v for k, v in state.items() if k != "integrity"}


def children_counted():
    snap = telemetry.counter("kwok_apiserver_save_children_total",
                             labelnames=("outcome",)).snapshot()
    return snap.get(("ok",), 0), snap.get(("failed",), 0)


@pytest.fixture
def durable(tmp_path):
    """A store over a WAL that archives into a PITR archive, as the daemon
    builds them."""
    root = str(tmp_path / "pitr")
    store = ResourceStore(namespace_finalizers=True)
    log = walmod.WriteAheadLog(str(tmp_path / "wal.jsonl"), fsync="off", archive_dir=root)
    store.attach_wal(log)
    return store, log, PitrArchive(root), str(tmp_path / "state.json")


# ------------------------------------------------------------- (a) the helper


@limit(60)
def test_a_save_through_the_child_is_whole_and_equals_the_cut(durable, tmp_path):
    """In a process with other threads running, and with the fork's
    DeprecationWarning turned into an error: both files verify, hold the
    same bytes and equal ``dump_state()`` of the cut; nothing else is left."""
    store, _log, archive, state_file = durable
    for i in range(300):
        store.create(make_pod(i))
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            sum(range(100))

    spinners = [threading.Thread(target=spin, daemon=True) for _ in range(3)]
    for t in spinners:
        t.start()
    try:
        cut = store.dump_state(copy=False)
        expected = store.dump_state()
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            waited = save_in_child(cut, state_file, archive)
        store.create(make_pod("later"))  # after the cut: in neither file
    finally:
        stop.set()
        for t in spinners:
            t.join(timeout=10)
    assert waited > 0
    (rv, archived), = archive.snapshots()
    assert rv == expected["resourceVersion"] == 300
    with open(state_file, "rb") as a, open(archived, "rb") as b:
        assert a.read() == b.read()
    for path in (state_file, archived):
        assert sans_integrity(walmod.read_state_file(path)) == expected
    assert temporaries(str(tmp_path), archive.root) == []
    restored = ResourceStore(namespace_finalizers=True)
    restored.load_file(state_file)
    assert restored.count("Pod") == 300


@limit(30)
def test_the_document_is_the_one_write_state_file_writes(tmp_path):
    """One serialisation carries the checksum of its own body: the child's
    bytes and ``write_state_file``'s are the same file, an empty state and
    one that was loaded with an ``integrity`` block among them."""
    store = ResourceStore()
    for state in ({}, store.dump_state(),
                  {"resourceVersion": 7, "objects": [make_pod(1)], "integrity": {"crc32": 1}}):
        data = walmod.encode_state(state)
        walmod.write_state_file(str(tmp_path / "s.json"), state)
        assert (tmp_path / "s.json").read_bytes() == data
        read = walmod.read_state_file(str(tmp_path / "s.json"))
        assert read["integrity"] == {"v": 1, "crc32": walmod.state_crc(state)}
        assert sans_integrity(read) == sans_integrity(state)
    flipped = bytearray(data)
    flipped[data.index(b"pod-1")] ^= 0x01
    (tmp_path / "s.json").write_bytes(bytes(flipped))
    with pytest.raises(walmod.SnapshotCorruption):
        walmod.read_state_file(str(tmp_path / "s.json"))


# ------------------------------------------------- (b) a child that cannot write


@limit(60)
@pytest.mark.parametrize("window", ["disk-full", "quota", "fsync-error"])
def test_a_chaos_pressure_window_refuses_the_snapshot_as_it_refuses_the_log(durable, tmp_path,
                                                                            window):
    """The child asks the log's pressure shim before each file: inside a
    window it writes nothing, the parent raises the window's errno, the
    previous snapshot and every WAL record stay, no temporary is left, and
    the save after the window succeeds."""
    store, log, archive, state_file = durable
    for i in range(20):
        store.create(make_pod(i))
    save_in_child(store.dump_state(copy=False), state_file, archive, guard=log.guard_io)
    before = open(state_file, "rb").read()
    for i in range(20, 40):
        store.create(make_pod(i))
    ok0, failed0 = children_counted()

    log.set_pressure(FsPressure(window))
    with pytest.raises(OSError) as refused:
        save_in_child(store.dump_state(copy=False), state_file, archive, guard=log.guard_io)
    assert refused.value.errno == {"disk-full": errno.ENOSPC, "fsync-error": errno.EIO,
                                   "quota": getattr(errno, "EDQUOT", errno.ENOSPC)}[window]
    assert children_counted() == (ok0, failed0 + 1)
    assert open(state_file, "rb").read() == before
    assert [rv for rv, _p in archive.snapshots()] == [20]
    assert temporaries(str(tmp_path), archive.root) == []
    covered = {rv for r in walmod.scan(log.path).records for rv in walmod.record_rvs(r)}
    assert covered >= set(range(1, 41))

    log.set_pressure(None)
    save_in_child(store.dump_state(copy=False), state_file, archive, guard=log.guard_io)
    assert children_counted() == (ok0 + 1, failed0 + 1)
    assert walmod.read_state_file(state_file)["resourceVersion"] == 40
    assert [rv for rv, _p in archive.snapshots()] == [20, 40]


@limit(30)
def test_a_child_that_dies_leaves_no_temporary(durable, tmp_path, monkeypatch):
    """A child killed before it is done (here by itself, with its first
    temporary half written) ends in ``OSError`` and a swept directory."""
    store, _log, archive, state_file = durable
    store.create(make_pod(0))

    def dies(path, data):
        with open(path, "wb") as f:
            f.write(data[:10])
        os.kill(os.getpid(), signal.SIGKILL)

    monkeypatch.setattr("kwok_tpu.snapshot.child.write_durable", dies)
    with pytest.raises(OSError, match="status -9"):
        save_in_child(store.dump_state(copy=False), state_file, archive)
    assert not os.path.exists(state_file) and archive.snapshots() == []
    assert temporaries(str(tmp_path), archive.root) == []


@limit(30)
def test_the_sweep_takes_temporaries_and_nothing_else(tmp_path):
    archive = PitrArchive(str(tmp_path / "pitr"))
    state_file = str(tmp_path / "state.json")
    keep = [state_file, state_file + ".tmp", str(tmp_path / "wal.jsonl.tmp.12"),
            os.path.join(archive.root, "snap-000000000007.json"),
            os.path.join(archive.root, "wal.jsonl.seg-00000001")]
    orphaned = [state_file + ".tmp.4242", os.path.join(archive.root, "snap-000000000009.json.tmp.4242")]
    for path in keep + orphaned:
        with open(path, "w") as f:
            f.write("x")
    assert sweep_temporaries(state_file, archive) == 2
    assert all(os.path.exists(p) for p in keep) and not any(os.path.exists(p) for p in orphaned)
    assert [rv for rv, _p in archive.snapshots()] == [7]


# --------------------------------------------------------------- the real daemon


class Daemon:
    """``python -m kwok_tpu.cmd.apiserver`` over files under ``home``."""

    def __init__(self, home, *extra, save_interval="0.2", shards=1, state_dir=None):
        self.home = str(home)
        self.port = free_port()
        self.state_file = os.path.join(str(state_dir or home), "state.json")
        self.wal_file = shard_wal_path(self.home, 0)
        self.pitr = shard_pitr_dir(self.home, 0)
        self.log_file = os.path.join(self.home, "apiserver.log")
        self.argv = [sys.executable, "-W", "error::DeprecationWarning", "-m",
                     "kwok_tpu.cmd.apiserver", "--port", str(self.port),
                     "--state-file", self.state_file, "--wal-file", self.wal_file,
                     "--pitr-dir", self.pitr, "--save-interval", save_interval,
                     "--store-shards", str(shards), *extra]
        self.client = ClusterClient(f"http://127.0.0.1:{self.port}")
        self.proc = None

    def start(self):
        self.proc = subprocess.Popen(
            self.argv, stdout=open(self.log_file, "ab"), stderr=subprocess.STDOUT,
            env={**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu"},
            start_new_session=True)
        assert self.client.wait_ready(60)
        return self

    def log(self):
        with open(self.log_file, encoding="utf-8", errors="replace") as f:
            return f.read()

    def metric(self, series):
        text = urllib.request.urlopen(f"http://127.0.0.1:{self.port}/metrics", timeout=10).read()
        for line in text.decode().splitlines():
            if line.startswith(series + " "):
                return float(line.rsplit(" ", 1)[1])
        return None

    def saved_rv(self):
        try:
            return walmod.read_state_file(self.state_file)["resourceVersion"]
        except OSError:
            return -1

    def load(self, n, start=0, ns="default"):
        for lo in range(start, start + n, 2000):
            results = self.client.bulk([{"verb": "create", "data": make_pod(i, ns)}
                                        for i in range(lo, min(lo + 2000, start + n))])
            assert all(r.get("status") == "ok" for r in results)

    def freeze_child(self, provoke):
        """A snapshot child of the daemon, stopped before it is done, so
        that what follows falls in mid-save whatever the machine's pace;
        ``provoke`` makes the next save due when one got away."""
        while True:
            pid, = wait_for(self.children, 30, "a snapshot child")
            with contextlib.suppress(ProcessLookupError):
                os.kill(pid, signal.SIGSTOP)
                if pid in self.children():
                    return pid
            provoke()

    def children(self):
        """Live children of the daemon: the snapshot child, when one is."""
        out = []
        for name in os.listdir("/proc"):
            if name.isdigit():
                with contextlib.suppress(OSError, IndexError, ValueError):
                    with open(f"/proc/{name}/stat") as f:
                        fields = f.read().rsplit(")", 1)[1].split()
                    if int(fields[1]) == self.proc.pid and fields[0] != "Z":
                        out.append(int(name))
        return out

    def reap(self):
        if self.proc is not None:
            with contextlib.suppress(ProcessLookupError):
                os.killpg(self.proc.pid, signal.SIGKILL)
            self.proc.wait(timeout=20)


@pytest.fixture
def daemon(tmp_path):
    made = []

    def make(*extra, **kw):
        made.append(Daemon(tmp_path, *extra, **kw))
        return made[-1].start()

    yield make
    for d in made:
        d.reap()


@limit(120)
def test_an_unwritable_directory_skips_the_save_and_keeps_the_log_whole(tmp_path):
    """The state file's directory is taken away under the running daemon
    (a mode refuses root nothing, so it is moved): the child cannot create
    its file, the save is skipped with today's line and counted as failed,
    the previous snapshot is the newest one and the WAL still covers every
    acknowledged write; with the directory back the next save goes through."""
    files, away = tmp_path / "files", tmp_path / "away"
    files.mkdir()
    d = Daemon(tmp_path, state_dir=files)
    try:
        d.start()
        d.load(50)
        wait_for(lambda: d.saved_rv() >= 50, 30, "the first 50 are in a snapshot")
        first = d.saved_rv()
        os.rename(files, away)
        d.load(50, start=50)
        wait_for(lambda: "snapshot save skipped: [Errno 2]" in d.log(), 30, "the skipped line")
        assert d.metric('kwok_apiserver_save_children_total{outcome="failed"}') >= 1
        assert walmod.read_state_file(str(away / "state.json"))["resourceVersion"] == first
        assert max(rv for rv, _p in PitrArchive(d.pitr).snapshots()) == first
        covered = {rv for r in walmod.scan(d.wal_file).records for rv in walmod.record_rvs(r)}
        assert covered >= set(range(first + 1, 101))
        assert temporaries(str(away), d.pitr) == []

        os.rename(away, files)
        wait_for(lambda: d.saved_rv() >= 100, 30, "a save after the directory came back")
        # the parent renames the state file, then the archive's copy: a
        # reader between the two sees the first alone for a moment
        wait_for(lambda: max(rv for rv, _p in PitrArchive(d.pitr).snapshots()) == d.saved_rv(),
                 10, "the archive's snapshot of that save")
        assert temporaries(str(files), d.pitr) == []
        assert len(d.client.list("Pod")[0]) == 100
    finally:
        d.reap()


@limit(180)
def test_a_kill_with_a_child_alive_loses_nothing_and_the_orphans_file_is_swept(daemon):
    """SIGKILL of the apiserver alone, as the benchmark's crash does it,
    while a snapshot child is alive: the orphan finishes temporaries nobody
    renames; the restarted daemon has every acknowledged create from the
    last whole snapshot and the WAL, and sweeps before its first save."""
    d = daemon(save_interval="0.3")
    d.load(6000)
    wait_for(lambda: d.saved_rv() >= 6000, 60, "a whole snapshot of the 6,000")
    created = [f"pod-{i}" for i in range(6060)]

    def one_more():
        created.append(f"pod-more-{len(created)}")
        return int(d.client.create(make_pod(created[-1][4:]))["metadata"]["resourceVersion"])

    d.load(60, start=6000)  # acknowledged, in the WAL alone when the kill falls
    orphan = d.freeze_child(one_more)
    os.kill(d.proc.pid, signal.SIGKILL)
    d.proc.wait(timeout=20)
    os.kill(orphan, signal.SIGCONT)
    wait_for(lambda: not os.path.exists(f"/proc/{orphan}"), 60, "the orphan to end")
    left = temporaries(d.home, d.pitr)
    assert len(left) == 2 and all(p.endswith(f".tmp.{orphan}") for p in left)
    assert 6000 <= d.saved_rv() < 6060  # the orphan renamed nothing

    d.start()
    assert {p["metadata"]["name"] for p in d.client.list("Pod")[0]} == set(created)
    last = one_more()
    wait_for(lambda: d.saved_rv() >= last, 60, "the restarted daemon's first save")
    assert temporaries(d.home, d.pitr) == []
    assert "snapshot save skipped" not in d.log()


@limit(180)
def test_sigterm_in_mid_save_ends_in_one_whole_snapshot_at_the_last_rv(daemon):
    d = daemon(save_interval="0.3")
    d.load(6000)
    wait_for(lambda: d.saved_rv() >= 6000, 60, "a whole snapshot of the 6,000")
    created = [f"pod-{i}" for i in range(6000)]

    def one_more():
        created.append(f"pod-more-{len(created)}")
        return int(d.client.create(make_pod(created[-1][4:]))["metadata"]["resourceVersion"])

    one_more()
    child = d.freeze_child(one_more)
    last = one_more()  # after that save's cut: the shutdown save's to write
    d.proc.send_signal(signal.SIGTERM)
    os.kill(child, signal.SIGCONT)
    assert d.proc.wait(timeout=60) == 0
    assert temporaries(d.home, d.pitr) == []
    state = walmod.read_state_file(d.state_file)
    assert state["resourceVersion"] == last
    assert {o["metadata"]["name"] for o in state["objects"] if o["kind"] == "Pod"} == set(created)
    rv, newest = PitrArchive(d.pitr).snapshots()[-1]
    assert rv == last
    with open(d.state_file, "rb") as a, open(newest, "rb") as b:
        assert a.read() == b.read()
    assert "snapshot save skipped" not in d.log()


@limit(180)
def test_the_serving_process_spends_under_a_third_of_a_save(daemon):
    """20,000 objects: the sum of what the saves cost the serving
    interpreter (cut, fork, renames, compaction, prune) against the sum of
    the whole saves, both of one process; a ratio of counts of seconds, not
    a speed.  Every save went through a child and none failed."""
    d = daemon(save_interval="0.5")
    d.load(20000)
    wait_for(lambda: d.saved_rv() >= 20000, 120, "a whole snapshot of the 20,000")
    ok = 'kwok_apiserver_save_children_total{outcome="ok"}'
    saves = wait_for(lambda: d.metric("kwok_apiserver_save_seconds_count") == d.metric(ok)
                     and d.metric(ok), 30, "the last save to be counted whole")
    assert d.metric("kwok_apiserver_save_inprocess_seconds_count") == saves
    assert d.metric('kwok_apiserver_save_children_total{outcome="failed"}') == 0
    whole = d.metric("kwok_apiserver_save_seconds_sum")
    inprocess = d.metric("kwok_apiserver_save_inprocess_seconds_sum")
    assert 0 < inprocess < whole / 3, (inprocess, whole)


@limit(120)
def test_a_sharded_stores_save_takes_the_same_helper(daemon):
    """``--store-shards 2``: a child a shard and a save, each shard's state
    file and archive copy whole, their union what the daemon lists."""
    d = daemon(shards=2)
    ns_b = next(f"ns-{i}" for i in range(64)
                if shard_of(True, "Pod", f"ns-{i}", 2) != shard_of(True, "Pod", "default", 2))
    d.client.create({"apiVersion": "v1", "kind": "Namespace", "metadata": {"name": ns_b}})
    d.load(40)
    d.load(40, ns=ns_b)
    last = max(int(p["metadata"]["resourceVersion"]) for p in d.client.list("Pod")[0])

    def both_saved():
        with contextlib.suppress(OSError):
            return all(walmod.read_state_file(shard_state_path(d.home, i))["resourceVersion"]
                       >= last for i in range(2))

    wait_for(both_saved, 60, "both shards' snapshots")
    assert d.metric('kwok_apiserver_save_children_total{outcome="ok"}') >= 2
    assert d.metric('kwok_apiserver_save_children_total{outcome="failed"}') == 0
    pods = set()
    for i in range(2):
        state = walmod.read_state_file(shard_state_path(d.home, i))
        mine = {(o["metadata"]["namespace"], o["metadata"]["name"])
                for o in state["objects"] if o["kind"] == "Pod"}
        assert len(mine) == 40
        pods |= mine
        rv, path = PitrArchive(shard_pitr_dir(d.home, i)).snapshots()[-1]
        assert walmod.read_state_file(path)["resourceVersion"] == rv
        assert temporaries(os.path.dirname(shard_state_path(d.home, i)),
                           shard_pitr_dir(d.home, i)) == []
    assert pods == {(p["metadata"]["namespace"], p["metadata"]["name"])
                    for p in d.client.list("Pod")[0]}


@limit(60)
def test_prune_takes_the_logs_word_for_the_segments_it_archived(durable, monkeypatch):
    """Compaction hands the archive the highest rv it wrote into each
    segment it moved there, and prune decides by it without decoding the
    segment in the save loop's process; an archive opened anew (a restart)
    has no such word and reads the segment as before."""
    import kwok_tpu.snapshot.pitr as pitr

    store, log, archive, state_file = durable
    words = {}
    for upto in (10, 20, 30):
        for i in range(upto - 10, upto):
            store.create(make_pod(i))
        save_in_child(store.dump_state(copy=False), state_file, archive)
        store.compact_wal(upto)
        word = log.take_archived()
        assert list(word.values()) == [upto] and log.take_archived() == {}
        words.update(word)
    assert sorted(words) == archive.segments()

    scanned = []
    monkeypatch.setattr(pitr, "scan_files", lambda files: scanned.append(files) or real(files))
    real = walmod.scan_files
    assert archive.prune(keep_snapshots=2, sealed=words) == {"snapshots": 1, "segments": 2}
    assert scanned == [] and [rv for rv, _p in archive.snapshots()] == [20, 30]
    (kept,) = archive.segments()
    assert words[kept] == 30

    anew = PitrArchive(archive.root)
    assert anew.prune(keep_snapshots=1) == {"snapshots": 1, "segments": 1}
    assert scanned == [[kept]] and anew.segments() == []
