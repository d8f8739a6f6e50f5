"""A watch line is encoded once (ISSUE 34): the apiserver builds the NDJSON
line of an event when the first stream delivers it and keeps it on the
event, and every other stream that carries the event (the live streams of
the kind, a field-selected one, one resumed from a resourceVersion) writes
those bytes.  ``kwok_watch_lines_encoded`` counts what a stream had to
encode itself and ``kwok_watch_lines_total`` what it wrote; with a tracer
armed the envelope carries the delivery's ``ctx`` and nothing is kept.
Both event types (the C slot event and the dataclass it stands in for)
take the line without its becoming part of what the event is."""

import json
import socket
import sys
import threading
import time
from urllib.parse import urlsplit

import pytest

from kwok_tpu.cluster import apiserver as api_mod
from kwok_tpu.cluster import store as store_mod
from kwok_tpu.cluster.apiserver import APIServer
from kwok_tpu.cluster.sharding import build_sharded_store, shard_of
from kwok_tpu.cluster.store import ResourceStore
from kwok_tpu.utils.trace import Tracer, set_global

STREAMS = 5  # what a default cluster's Pods have, the benchmark's watcher included


@pytest.fixture(
    autouse=True,
    params=[
        pytest.param(
            "native",
            marks=pytest.mark.skipif(
                store_mod._FAST is None, reason="native fastdrain unavailable"
            ),
        ),
        "python",
    ],
)
def event_type(request, monkeypatch):
    """The store's events as the C unit allocates them, and as the
    dataclass where the unit is absent."""
    if request.param == "python":
        monkeypatch.setattr(store_mod, "_FAST", None)
        monkeypatch.setattr(store_mod, "WatchEvent", store_mod._PyWatchEvent)
    return store_mod.WatchEvent


@pytest.fixture
def one_turn_a_burst():
    """No forced thread switches: a stream then encodes its burst in one
    turn of the interpreter, so no two streams race for one event and the
    counts below are exact (the race is benign and is counted as two
    encodes; ``test_racing_streams`` below lets it happen)."""
    was = sys.getswitchinterval()
    sys.setswitchinterval(1000.0)
    try:
        yield
    finally:
        sys.setswitchinterval(was)


def make_pod(name, node="node-0", ns="default"):
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": name, "namespace": ns, "finalizers": ["kwok.x-k8s.io/fake"]},
        "spec": {"nodeName": node, "containers": [{"name": "app", "image": "x"}]},
        "status": {},
    }


class Stream:
    """One raw watch connection: the bytes as the server wrote them."""

    def __init__(self, url, query=""):
        u = urlsplit(url)
        self.sock = socket.create_connection((u.hostname, u.port), timeout=30)
        self.sock.sendall(
            f"GET /r/pods?watch=1{query} HTTP/1.1\r\nHost: {u.hostname}\r\n\r\n".encode())
        self.fp = self.sock.makefile("rb")
        assert self.fp.readline().split()[1] == b"200"
        while self.fp.readline() not in (b"\r\n", b""):
            pass
        self.lines = []
        self.eof = False
        self._thread = threading.Thread(target=self._read, daemon=True)
        self._thread.start()

    def _read(self):
        try:
            for line in self.fp:
                self.lines.append(line)
        except OSError:
            pass
        self.eof = True

    def wait(self, n, timeout=10.0):
        deadline = time.monotonic() + timeout
        while len(self.lines) < n and time.monotonic() < deadline:
            time.sleep(0.005)
        assert len(self.lines) >= n, f"{len(self.lines)} of {n} lines"
        return list(self.lines)

    def close(self):
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass  # the server hung up first
        self._thread.join(timeout=5)
        assert not self._thread.is_alive()
        self.fp.close()
        self.sock.close()


def watchers(store):
    """The Pod watchers registered with the store (with each of its shards)."""
    shards = [store.shard_lane(i) for i in range(getattr(store, "shard_count", 0))] or [store]
    return sum(len(sh._state("Pod").watchers) for sh in shards)


def open_streams(srv, store, n, query=""):
    """``n`` live streams, each registered with the store before it returns."""
    want = watchers(store) + n * (getattr(store, "shard_count", 0) or 1)
    streams = [Stream(srv.url, query) for _ in range(n)]
    deadline = time.monotonic() + 10
    while watchers(store) < want and time.monotonic() < deadline:
        time.sleep(0.005)
    assert watchers(store) == want
    return streams


class Counts:
    """What the three series gained since the last look."""

    def __init__(self):
        self.at = self._now()

    @staticmethod
    def _now():
        enc = store_mod._H_LINES_ENCODED.snapshot().get(("Pod",), {"sum": 0.0, "count": 0})
        sec = store_mod._H_ENCODE.snapshot().get(("Pod",), {"sum": 0.0, "count": 0})
        return {
            "encoded": enc["sum"],
            "bursts": enc["count"],
            "written": store_mod._C_LINES.snapshot().get(("Pod",), 0),
            "timed": sec["count"],
        }

    def gained(self):
        now = self._now()
        got = {k: now[k] - self.at[k] for k in now}
        self.at = now
        return got


def settle(counts_of, want_written, timeout=10.0):
    """The series are observed after the flush, so a reader can hold its
    lines a moment before the count moves: wait for the count."""
    deadline = time.monotonic() + timeout
    got = {"encoded": 0, "bursts": 0, "written": 0, "timed": 0}
    while time.monotonic() < deadline:
        for k, v in counts_of.gained().items():
            got[k] += v
        if got["written"] >= want_written:
            break
        time.sleep(0.01)
    return got


def three_kinds_of_commit(store, n=12):
    """A bulk of creates, a status batch, the client's deletes (MODIFIED,
    for the finalizer) and a delete batch: 4 events a pod."""
    results = store.bulk([{"verb": "create", "data": make_pod(f"p{i}", node=f"node-{i % 2}")}
                          for i in range(n)])
    assert all(r["status"] == "ok" for r in results)
    at = {o["metadata"]["name"]: o["metadata"]["resourceVersion"] for o in store.list("Pod")[0]}
    done = store.apply_status_batch(
        "Pod", [("default", f"p{i}", {"phase": "Running", "podIP": f"10.0.0.{i}"}, at[f"p{i}"])
                for i in range(n)])
    assert all(r and r[0] > 0 for r in done)
    for i in range(n):
        store.delete("Pod", f"p{i}", namespace="default")
    at = {o["metadata"]["name"]: o["metadata"]["resourceVersion"] for o in store.list("Pod")[0]}
    gone = store.apply_delete_batch(
        "Pod", [("default", f"p{i}", at[f"p{i}"]) for i in range(n)])
    assert all(r and r > 0 for r in gone)
    return 4 * n


def test_every_stream_writes_the_bytes_the_first_one_encoded(one_turn_a_burst):
    store = ResourceStore()
    with APIServer(store) as srv:
        streams = open_streams(srv, store, STREAMS)
        counts = Counts()
        try:
            events = three_kinds_of_commit(store)
            got = [s.wait(events) for s in streams]
            tally = settle(counts, STREAMS * events)
        finally:
            for s in streams:
                s.close()
    assert all(lines == got[0] for lines in got[1:])
    # the bytes are the envelope round the object's compact JSON (the
    # WAL's style since PR 37), and parse to what they did before
    history = list(store._state("Pod").history)
    assert len(history) == events
    assert got[0] == [
        b'{"type": "%s", "object": %s, "rv": %d}\n'
        % (e.type.encode(), json.dumps(e.object, separators=(",", ":")).encode(), e.rv)
        for e in history]
    assert [json.loads(ln) for ln in got[0]] == [
        {"type": e.type, "object": e.object, "rv": e.rv} for e in history]
    assert [json.loads(ln)["type"] for ln in got[0]] == (
        ["ADDED"] * 12 + ["MODIFIED"] * 24 + ["DELETED"] * 12)
    # each event in order, once; and the line stays on the event
    rvs = [json.loads(ln)["rv"] for ln in got[0]]
    assert rvs == sorted(set(rvs))
    assert [e.line for e in history] == got[0]
    # json.dumps ran once an event; every stream wrote every line; a
    # burst is one observation of each series
    assert tally["encoded"] == events
    assert tally["written"] == STREAMS * events
    assert tally["bursts"] == tally["timed"] >= STREAMS


def test_streams_over_a_sharded_store_share_lines_too(one_turn_a_burst):
    """``MergedWatcher`` hands on the shards' own instances."""
    store = build_sharded_store(4)
    home = shard_of(True, "Pod", "default", 4)
    other = next(f"ns-{i}" for i in range(64) if shard_of(True, "Pod", f"ns-{i}", 4) != home)
    with APIServer(store) as srv:
        streams = open_streams(srv, store, 3)
        counts = Counts()
        try:
            for i in range(10):
                store.create(make_pod(f"p{i}", ns="default" if i % 2 else other))
            done = store.apply_status_batch(
                "Pod", [("default" if i % 2 else other, f"p{i}", {"phase": "Running"})
                        for i in range(10)])
            assert all(r and r[0] > 0 for r in done)
            got = [s.wait(20) for s in streams]
            tally = settle(counts, 60)
        finally:
            for s in streams:
                s.close()
    assert got[0] == got[1] == got[2]
    assert {json.loads(ln)["object"]["metadata"]["namespace"] for ln in got[0]} == {"default", other}
    assert (tally["encoded"], tally["written"]) == (20, 60)


def test_a_selected_and_a_resumed_stream_write_the_kept_bytes(one_turn_a_burst):
    store = ResourceStore()
    with APIServer(store) as srv:
        first = open_streams(srv, store, 1)[0]
        counts = Counts()
        selected = resumed = None
        try:
            events = three_kinds_of_commit(store)
            whole = first.wait(events)
            assert settle(counts, events)["encoded"] == events
            # the store's selected watcher carries a subset of the same
            # instances, the resume replays them from the history ring:
            # both find every line encoded
            selected = Stream(srv.url, "&resourceVersion=0&fieldSelector=spec.nodeName%3Dnode-1")
            mine = [ln for ln in whole
                    if json.loads(ln)["object"]["spec"]["nodeName"] == "node-1"]
            assert len(mine) == events // 2
            assert selected.wait(len(mine)) == mine
            half = json.loads(whole[events // 2 - 1])["rv"]
            resumed = Stream(srv.url, f"&resourceVersion={half}")
            assert resumed.wait(events // 2) == whole[events // 2:]
            tally = settle(counts, len(mine) + events // 2)
        finally:
            for s in (first, selected, resumed):
                if s is not None:
                    s.close()
    assert tally["encoded"] == 0
    assert tally["written"] == len(mine) + events // 2


def test_an_armed_tracer_gets_its_ctx_and_nothing_is_kept(one_turn_a_burst):
    tracer = Tracer("t", endpoint="http://127.0.0.1:9/v1/traces")
    set_global(tracer)
    try:
        store = ResourceStore()
        with APIServer(store) as srv:
            streams = open_streams(srv, store, 2)
            counts = Counts()
            try:
                with tracer.span("writer") as sp:
                    for i in range(6):
                        store.create(make_pod(f"p{i}"))
                got = [s.wait(6) for s in streams]
                tally = settle(counts, 12)
            finally:
                for s in streams:
                    s.close()
    finally:
        set_global(None)
        tracer.stop()
    assert got[0] == got[1]
    for ln in got[0]:
        assert json.loads(ln)["ctx"][0] == sp.trace_id
    assert [e.line for e in store._state("Pod").history] == [None] * 6
    assert tally["encoded"] == tally["written"] == 12


def test_an_evicted_stream_ends_as_before_and_resumes_on_kept_bytes(one_turn_a_burst):
    """Backpressure's farewell is a clean end of the stream after the last
    whole line, no line of its own; the consumer resumes from its last
    resourceVersion and is written the bytes the ring's events carry."""
    store = ResourceStore(watch_high_water=10)
    with APIServer(store) as srv:
        streams = open_streams(srv, store, 2)
        again = []
        try:
            for i in range(5):
                store.create(make_pod(f"p{i}"))
            before = [s.wait(5) for s in streams]
            at = {o["metadata"]["name"]: o["metadata"]["resourceVersion"]
                  for o in store.list("Pod")[0]}
            for i in range(5, 30):
                store.create(make_pod(f"p{i}"))
            # one atomic batch past the mark evicts both, whatever they read
            store.apply_status_batch(
                "Pod", [("default", f"p{i}", {"phase": "Running"}) for i in range(30)])
            deadline = time.monotonic() + 10
            while not all(s.eof for s in streams) and time.monotonic() < deadline:
                time.sleep(0.01)
            assert all(s.eof for s in streams)
            assert store.watch_evictions == 2
            assert srv.flow is None or sum(
                lvl["evicted_watchers"] for lvl in srv.flow.snapshot().values()) == 2
            for s, had in zip(streams, before):
                assert s.lines[:5] == had and all(ln.endswith(b"}\n") for ln in s.lines)
                assert {json.loads(ln)["type"] for ln in s.lines} <= {"ADDED", "MODIFIED"}
            counts = Counts()
            last = json.loads(before[0][-1])["rv"]
            assert str(last) == at["p4"]
            again = [Stream(srv.url, f"&resourceVersion={last}") for _ in range(2)]
            replay = [s.wait(55) for s in again]
            tally = settle(counts, 110)
        finally:
            for s in streams + again:
                s.close()
    assert replay[0] == replay[1]
    assert [e.line for e in list(store._state("Pod").history)[5:]] == replay[0]
    # whatever the evicted streams had encoded of these 55 before they
    # were cut, nobody encodes twice
    assert tally["written"] == 110 and tally["encoded"] <= 55


def test_racing_streams_write_the_same_bytes():
    """More streams than cores and a switch every few bytecodes: streams
    do race for an event here.  Each still writes every line once, in
    order, byte for byte what the others write, and no stream encodes what
    it found encoded."""
    store = ResourceStore()
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with APIServer(store) as srv:
            streams = open_streams(srv, store, 8)
            counts = Counts()
            try:
                events = 0
                for _ in range(5):
                    events += three_kinds_of_commit(store, n=25)
                got = [s.wait(events, timeout=60) for s in streams]
                tally = settle(counts, 8 * events, timeout=60)
            finally:
                for s in streams:
                    s.close()
    finally:
        sys.setswitchinterval(was)
    assert all(lines == got[0] for lines in got[1:])
    rvs = [json.loads(ln)["rv"] for ln in got[0]]
    assert len(rvs) == events and rvs == sorted(set(rvs))
    assert tally["written"] == 8 * events
    assert events <= tally["encoded"] <= 8 * events


def test_the_line_is_no_part_of_what_an_event_is(event_type):
    obj = {"metadata": {"name": "p"}}
    bare, kept = event_type("ADDED", obj, 7), event_type("ADDED", obj, 7)
    assert bare.line is None and kept.line is None
    kept.line = b'{"type":"ADDED"}\n'
    assert bare == kept and not (bare != kept)
    assert kept.line == b'{"type":"ADDED"}\n' and bare.line is None
    assert event_type(type="ADDED", object=obj, rv=7, line=b"x") == bare
    assert bare != event_type("ADDED", obj, 8) and bare != event_type("DELETED", obj, 7)
    if event_type is store_mod._PyWatchEvent:
        assert "line" not in repr(kept)
    # the two types hold the same four slots
    twin = store_mod._PyWatchEvent("ADDED", obj, 7)
    assert (twin.type, twin.object, twin.rv, twin.line) == (
        bare.type, bare.object, bare.rv, bare.line)


def test_a_status_batch_allocates_events_without_a_line(event_type):
    """``_FAST.status_commit`` builds its events slot by slot, past
    ``__new__``: the fourth slot is None there too, and a commit with
    nobody watching encodes nothing."""
    store = ResourceStore()
    store.bulk([{"verb": "create", "data": make_pod(f"p{i}")} for i in range(4)])
    counts = Counts()
    store.apply_status_batch(
        "Pod", [("default", f"p{i}", {"phase": "Running"}) for i in range(4)])
    history = list(store._state("Pod").history)
    assert len(history) == 8 and all(isinstance(e, event_type) for e in history)
    assert [e.line for e in history] == [None] * 8
    assert counts.gained() == {"encoded": 0, "bursts": 0, "written": 0, "timed": 0}
