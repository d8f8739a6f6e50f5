"""An object is serialised once a resourceVersion (ISSUE 37): where the
store has a WAL the committing thread encodes the object for its ``ev``
record, and the event's ``/r/`` watch line, the Kubernetes frame cut from
it and the op's entry in a ``/bulk`` or ``/txn`` answer are envelopes round
those bytes.  What each writer writes parses to what the parent's code
wrote; the WAL frame is the parent's byte for byte;
``kwok_object_json_total{kind,source}`` counts one ``encoded`` and the rest
``reused``; a store with no WAL and no watcher encodes nothing.  Both event
types (the C slot event and the dataclass it stands in for) behave alike."""

import http.client
import json
import time
from urllib.parse import urlsplit

import pytest

from kwok_tpu.cluster import store as store_mod
from kwok_tpu.cluster import wal as wal_mod
from kwok_tpu.cluster.apiserver import APIServer
from kwok_tpu.cluster.sharding import build_sharded_store
from kwok_tpu.cluster.store import (
    ResourceStore,
    k8s_frame,
    object_json,
    results_body,
    watch_line,
)
from kwok_tpu.cluster.wal import WriteAheadLog, _parse_frame

from test_watch_line_once import event_type, open_streams  # noqa: F401 (autouse fixture)

FINALIZER = "kwok.x-k8s.io/fake"


def pod(name, finalizer=True, ns="default", **extra):
    obj = {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": name, "namespace": ns, "labels": {"app": "roll-1"}},
        "spec": {"nodeName": "node-0", "containers": [{"name": "app", "image": "x"}]},
        "status": {},
    }
    if finalizer:
        obj["metadata"]["finalizers"] = [FINALIZER]
    obj.update(extra)
    return obj


def create(name, **kw):
    return {"verb": "create", "data": pod(name, **kw)}


def patch(name, data, **more):
    return {"verb": "patch", "kind": "Pod", "name": name, "namespace": "default",
            "data": data, **more}


def delete(name):
    return {"verb": "delete", "kind": "Pod", "name": name, "namespace": "default"}


#: the four ways a bulk op commits an object, with what has to be there first
COMMITS = {
    "create": ([], create("p"), "ADDED"),
    "patch": ([create("p")], patch("p", {"status": {"phase": "Running"}}), "MODIFIED"),
    "delete": ([create("p")], delete("p"), "MODIFIED"),  # graceful: it holds a finalizer
    "reap": ([create("p"), delete("p")],
             patch("p", {"metadata": {"finalizers": None}}), "DELETED"),
}

#: objects whose JSON is awkward to put an envelope round and to cut out of
#: one: what a cut in the wrong place or a second escaping would show
CORPUS = {
    "the-envelopes-own-text": {
        "a": 1, "rv": 5, "type": "ADDED", "object": {"rv": 6},
        "s": ', "rv": 7}', "t": '{"type": "DELETED", "object": {}, "rv": 8}\n',
        "u": '"object":', "o": '"o":{}}', "list": [', "rv": ', {"rv": 9}, '"object": ']},
    "non-ascii-and-escapes": {
        "s": "naïve ☃ 日本語 \U0001f600", "q": 'a "quoted" \\ back\\slash, "rv": 3',
        "ctl": "tab\t nl\n cr\r nul\x00 del\x7f", "html": "</script><!--", "ü-key": "ü"},
    "nested-empty-and-numbers": {
        "a": {}, "b": [], "c": [[], {}, [{}]], "t": True, "n": None,
        "f": 0.1, "g": 1e-9, "i": 2**63 + 1, "j": -0.0, "l": -(2**70), "m": 3.0},
}


def logged_store(tmp_path, name="wal.jsonl"):
    store = ResourceStore()
    store.attach_wal(WriteAheadLog(str(tmp_path / name), fsync="off"))
    return store


def frames(path):
    """(framed line, record) of the log's ``ev`` frames, in file order."""
    with open(path, encoding="utf-8") as f:
        lines = f.read().splitlines()
    out = [(ln, _parse_frame(ln)) for ln in lines]
    return [(ln, seq, rec) for ln, (seq, rec, _legacy) in out if rec.get("t") == "ev"]


class Uses:
    """What ``kwok_object_json_total`` and its histogram gained for Pods."""

    def __init__(self):
        self.at = self._now()

    @staticmethod
    def _now():
        c = store_mod._C_OBJECT_JSON.snapshot()
        h = store_mod._H_OBJECT_JSON_REUSED.snapshot().get(("Pod",), {"sum": 0.0})
        return {"encoded": c.get(("Pod", "encoded"), 0), "reused": c.get(("Pod", "reused"), 0),
                "reused_sum": h["sum"]}

    def gained(self):
        now = self._now()
        got = {k: now[k] - self.at[k] for k in now}
        self.at = now
        return got


@pytest.fixture
def counting_dumps(monkeypatch):
    """How often the store's module ran ``json.dumps`` over a whole Pod."""
    calls = []
    real = json.dumps

    def counted(obj, **kw):
        if isinstance(obj, dict) and obj.get("kind") == "Pod":
            calls.append(obj)
        return real(obj, **kw)

    class Json:
        loads = staticmethod(json.loads)
        dumps = staticmethod(counted)

    monkeypatch.setattr(store_mod, "json", Json)
    return calls


# ------------------------------------------------ (a), (b): four writers, one encode


@pytest.mark.parametrize("case", list(COMMITS))
def test_the_four_writers_of_a_bulk_op_write_one_encode(case, tmp_path, counting_dumps):
    before, op, etype = COMMITS[case]
    store = logged_store(tmp_path)
    watcher = store.watch("Pod")
    assert all(r["status"] == "ok" for r in store.bulk(before))
    watcher.drain()
    seen, uses = len(counting_dumps), Uses()

    (entry,) = store.bulk([op], encoded=True)

    (ev,) = watcher.drain()
    assert ev.type == etype and ev.rv == store.resource_version
    obj = ev.object
    # one json.dumps of the object, on the committing thread, for the record
    assert len(counting_dumps) - seen == 1
    # the record: the parent's frame byte for byte (its json.dumps of the dict)
    line, seq, rec = frames(tmp_path / "wal.jsonl")[-1]
    want = {"t": "ev", "rv": ev.rv, "u": store._uid, "e": etype, "o": obj}
    assert rec == want
    assert line + "\n" == wal_mod.encode_record(seq, want)
    # the /r/ line and the Kubernetes frame: the line was there at the commit
    assert ev.line is not None
    wire, fresh = watch_line(ev)
    assert fresh == 0 and wire is ev.line and wire.endswith(b"}\n")
    assert json.loads(wire) == {"type": etype, "object": obj, "rv": ev.rv}
    assert json.loads(k8s_frame(wire)) == {"type": etype, "object": obj}
    # the answer entry: the same bytes in its envelope
    assert json.loads(entry) == {"status": "ok", "object": obj}
    cut = object_json(etype, wire)
    assert json.loads(cut) == obj
    assert entry == b'{"status": "ok", "object": ' + cut + b"}"
    assert cut.decode() in line and cut in k8s_frame(wire)
    assert len(counting_dumps) - seen == 1  # still the one
    # (b) one encoded (the record), the rest reused (the line, the entry)
    assert uses.gained() == {"encoded": 1, "reused": 2, "reused_sum": 2}


def test_in_process_callers_get_objects_of_their_own_as_before(tmp_path):
    store = logged_store(tmp_path)
    (res,) = store.bulk([create("p")])
    assert res["status"] == "ok"
    stored = store._state("Pod").objects[("default", "p")]
    assert res["object"] == stored and res["object"] is not stored
    (ref,) = store.bulk([patch("p", {"status": {"phase": "Running"}})], copy_results=False)
    assert ref["object"] is store._state("Pod").objects[("default", "p")]
    (out,) = store.transact([patch("p", {"status": {"phase": "Failed"}})])
    assert out == store.get("Pod", "p") and out is not store._state("Pod").objects[("default", "p")]


def test_an_op_that_commits_nothing_is_encoded_for_its_answer(tmp_path):
    store = logged_store(tmp_path)
    store.bulk([create("p"), delete("p")])
    uses = Uses()
    again, gone, missing = store.bulk(
        [delete("p"), patch("p", {"metadata": {"finalizers": None}}), delete("p")], encoded=True)
    # a delete that found the pod terminating commits nothing and answers the pod
    assert json.loads(again)["object"]["metadata"]["deletionTimestamp"]
    assert json.loads(gone)["object"]["metadata"]["name"] == "p"
    assert json.loads(missing) == {
        "status": "error", "reason": "NotFound", "error": "'Pod default/p not found'"}
    assert uses.gained() == {"encoded": 2, "reused": 2, "reused_sum": 2}
    store.bulk([create("q", finalizer=False)])
    assert store.bulk([delete("q")], encoded=True) == [b'{"status": "ok", "object": null}']


def test_a_transaction_answers_from_its_events_and_logs_one_frame(tmp_path):
    store = logged_store(tmp_path)
    store.bulk([create("a"), create("gone", finalizer=False)])
    watcher = store.watch("Pod")
    uses = Uses()
    answer = store.transact(
        [create("b"), patch("a", {"status": {"phase": "Running"}}), delete("gone")], encoded=True)
    evs = watcher.drain()
    assert [e.type for e in evs] == ["ADDED", "MODIFIED", "DELETED"]
    assert [json.loads(a) for a in answer] == [evs[0].object, evs[1].object, None]
    assert json.loads(results_body(answer)) == {"results": [evs[0].object, evs[1].object, None]}
    assert uses.gained() == {"encoded": 3, "reused": 5, "reused_sum": 5}
    # one txn frame, as the parent's json.dumps of the same record gives it
    with open(tmp_path / "wal.jsonl", encoding="utf-8") as f:
        last = f.read().splitlines()[-1]
    seq, rec, _ = _parse_frame(last)
    want = {"t": "txn", "rv": evs[-1].rv,
            "recs": [{"t": "ev", "rv": e.rv, "u": store._uid, "e": e.type, "o": e.object}
                     for e in evs]}
    assert rec == want
    assert last + "\n" == wal_mod.encode_record(seq, want)


# ------------------------------------------------ (c): killed after the acknowledgement


def test_a_store_killed_after_the_ack_replays_to_the_same_objects(tmp_path):
    wal_path, state = str(tmp_path / "wal.jsonl"), str(tmp_path / "state.json")
    store = logged_store(tmp_path)
    store.bulk([create(f"s{i}") for i in range(3)])
    store.save_file(state)  # the snapshot; what follows is in the log alone
    acked = store.bulk(
        [create("p"), patch("s0", {"status": {"phase": "Running"}}), delete("s1"),
         patch("s1", {"metadata": {"finalizers": None}}), create("q", finalizer=False)],
        encoded=True)
    acked += store.transact([create("t"), delete("q")], encoded=True)
    assert [json.loads(a)["status"] for a in acked[:5]] == ["ok"] * 5
    assert json.loads(acked[5])["metadata"]["name"] == "t" and acked[6] == b"null"
    # killed: nothing of the process is kept but its files
    back = ResourceStore()
    back.load_file(state)
    assert back.replay_wal(wal_path) == 6  # five ev frames and the txn's one
    assert back.resource_version == store.resource_version
    mine, theirs = store.list("Pod")[0], back.list("Pod")[0]
    assert theirs == mine
    assert sorted(o["metadata"]["name"] for o in theirs) == ["p", "s0", "s2", "t"]
    # and it goes on logging where the dead one stopped
    back.attach_wal(WriteAheadLog(wal_path, fsync="off"))
    back.bulk([patch("p", {"status": {"phase": "Running"}})], encoded=True)
    again = ResourceStore()
    again.load_file(state)
    assert again.replay_wal(wal_path) == 7
    assert again.list("Pod")[0] == back.list("Pod")[0]


# ------------------------------------------------ (d): awkward objects still cut right


@pytest.mark.parametrize("with_log", [True, False], ids=["log", "no-log"])
@pytest.mark.parametrize("name", list(CORPUS))
def test_awkward_text_still_cuts_into_a_correct_line_frame_and_entry(name, with_log, tmp_path):
    store = logged_store(tmp_path) if with_log else ResourceStore()
    watcher = store.watch("Pod")
    body = pod("c", spec=CORPUS[name])
    body["metadata"]["annotations"] = {', "rv": ': '"object": é', "é": ', "rv": 1}\n'}
    (entry,) = store.bulk([{"verb": "create", "data": body}], encoded=True)
    (ev,) = watcher.drain()
    assert ev.object["spec"] == CORPUS[name]
    line, fresh = watch_line(ev)
    assert fresh == 0  # the commit's with a log, the answer's without one
    assert line.count(b"\n") == 1 and line.isascii()
    assert json.loads(line) == {"type": "ADDED", "object": ev.object, "rv": ev.rv}
    frame = k8s_frame(line)
    assert frame.count(b"\n") == 1
    assert json.loads(frame) == {"type": "ADDED", "object": ev.object}
    assert json.loads(object_json("ADDED", line)) == ev.object
    assert json.loads(entry) == {"status": "ok", "object": ev.object}
    if with_log:
        assert frames(tmp_path / "wal.jsonl")[-1][2]["o"] == ev.object


# ------------------------------------------------ (e): nobody reads, nothing is encoded


def test_a_store_with_no_log_and_no_watcher_encodes_nothing(counting_dumps):
    store = ResourceStore()
    uses = Uses()
    ops = [create("p"), patch("p", {"status": {"phase": "Running"}}), delete("p"),
           patch("p", {"metadata": {"finalizers": None}})]
    assert all(r["status"] == "ok" for r in store.bulk(ops, copy_results=False))
    store.transact([create("t")])
    store.create(pod("single"))
    assert counting_dumps == []
    assert all(ev.line is None for ev in store._state("Pod").history)
    assert uses.gained() == {"encoded": 0, "reused": 0, "reused_sum": 0}


def test_without_a_log_the_first_reader_encodes_and_the_others_reuse(counting_dumps):
    store = ResourceStore()
    watcher = store.watch("Pod")
    uses = Uses()
    (entry,) = store.bulk([create("p")], encoded=True)  # the answer comes first
    (ev,) = watcher.drain()
    assert ev.line is not None and watch_line(ev) == (ev.line, 0)
    assert json.loads(entry)["object"] == ev.object
    assert len(counting_dumps) == 1
    assert uses.gained() == {"encoded": 1, "reused": 0, "reused_sum": 0}
    store.bulk([patch("p", {"status": {"phase": "Running"}})])
    (ev,) = watcher.drain()
    assert ev.line is None  # in process, nobody asked yet
    assert watch_line(ev)[1] == 1 and watch_line(ev)[1] == 0  # the first stream, the second


# ------------------------------------------------ (f): over HTTP


def post(srv, path, body, headers=None):
    u = urlsplit(srv.url)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=30)
    try:
        conn.request("POST", path, json.dumps(body).encode(),
                     {"Content-Type": "application/json", **(headers or {})})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def mixed_ops():
    return [
        patch("nope", {"status": {"phase": "Running"}}),  # NotFound
        patch("p", {"status": {"phase": "Failed"}}, expect={"status.phase": "Running"}),  # Conflict
        patch("p", {"status": {"phase": "Running"}}),  # ok
        {"verb": "frobnicate", "kind": "Pod", "name": "p"},  # Invalid
        delete("q"),  # ok, a completed delete: null
        create("r"),  # ok
    ]


@pytest.mark.parametrize("with_log", [True, False], ids=["log", "no-log"])
def test_an_http_bulk_answers_entries_aligned_with_its_ops(with_log, tmp_path):
    def twin(name):
        s = logged_store(tmp_path, name) if with_log else ResourceStore()
        s._now_string = lambda: "2026-01-01T00:00:00Z"
        s.bulk([create("p"), create("q", finalizer=False)])
        return s

    store, same = twin("a.jsonl"), twin("b.jsonl")
    with APIServer(same):  # it seeds what a served store holds
        want = same.bulk(mixed_ops())  # what the parent's route serialised
    assert [r["status"] for r in want] == ["error", "error", "ok", "error", "ok", "ok"]
    assert [r.get("reason") for r in want[:2]] == ["NotFound", "Conflict"]
    with APIServer(store) as srv:
        streams = open_streams(srv, store, 2)
        try:
            code, body = post(srv, "/bulk", {"ops": mixed_ops()})
            lines = streams[0].wait(3)
            assert streams[1].wait(3) == lines
        finally:
            for s in streams:
                s.close()
    assert code == 200
    assert json.loads(body) == {"results": want}
    # the watch lines of the ops that answer an object hold their entries' bytes
    # (the completed delete's event has a line and its entry is null)
    got = json.loads(body)["results"]
    assert [json.loads(ln)["type"] for ln in lines] == ["MODIFIED", "DELETED", "ADDED"]
    for ln, entry in ((lines[0], got[2]), (lines[2], got[5])):
        assert json.loads(ln)["object"] == entry["object"]
        assert object_json(json.loads(ln)["type"], ln) in body


def test_the_bulk_routes_cpu_series_counts_requests_and_ops(tmp_path):
    from kwok_tpu.cluster import apiserver as api_mod

    def now():
        h = api_mod._H_BULK_CPU.snapshot().get((), {"count": 0, "sum": 0.0})
        return h["count"], h["sum"], api_mod._C_BULK_OPS.snapshot().get((), 0)

    store = logged_store(tmp_path)
    with APIServer(store) as srv:
        before = now()
        assert post(srv, "/bulk", {"ops": [create("a"), create("b"), delete("nope")]})[0] == 200
        assert post(srv, "/bulk", {"ops": []})[0] == 200
        assert post(srv, "/txn", {"ops": [create("c")]})[0] == 200  # not a /bulk
        after = now()
        text = http_get(srv, "/metrics")
    assert after[0] - before[0] == 2 and after[2] - before[2] == 3
    assert after[1] > before[1]
    for series in ("kwok_bulk_cpu_seconds_sum", "kwok_bulk_ops_total",
                   'kwok_object_json_total{kind="Pod",source="encoded"}',
                   'kwok_object_json_total{kind="Pod",source="reused"}',
                   'kwok_object_json_reused_sum{kind="Pod"}'):
        assert series in text


def http_get(srv, path):
    u = urlsplit(srv.url)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=30)
    try:
        conn.request("GET", path)
        return conn.getresponse().read().decode()
    finally:
        conn.close()


def test_an_http_txn_answers_objects_and_an_abort_answers_409(tmp_path):
    store = logged_store(tmp_path)
    store.bulk([create("p"), create("q", finalizer=False)])
    with APIServer(store) as srv:
        code, body = post(srv, "/txn", {"ops": [
            patch("p", {"status": {"phase": "Running"}}), delete("q"), create("r")]})
        assert code == 200
        got = json.loads(body)["results"]
        assert got == [store.get("Pod", "p"), None, store.get("Pod", "r")]
        rv = store.resource_version
        code, body = post(srv, "/txn", {"ops": [create("s"), delete("nope")]})
        assert code == 409 and json.loads(body)["reason"]
        assert store.resource_version == rv


def test_a_sharded_stores_routes_merge_their_shards_entries_in_the_ops_order(tmp_path):
    store = build_sharded_store(2)
    for i in range(2):
        store.shard_lane(i).attach_wal(WriteAheadLog(str(tmp_path / f"w{i}.jsonl"), fsync="off"))
    by_shard = {}
    for n in range(64):
        by_shard.setdefault(store.shard_for("Pod", f"ns-{n}"), f"ns-{n}")
    ns_a, ns_b = by_shard[0], by_shard[1]
    ops = [create("a0", ns=ns_a), create("b0", ns=ns_b), create("a1", ns=ns_a),
           {"verb": "patch", "kind": "Pod", "name": "nope", "namespace": ns_b, "data": {}},
           create("b1", ns=ns_b)]
    with APIServer(store) as srv:
        code, body = post(srv, "/bulk", {"ops": ops})
        assert code == 200
        got = json.loads(body)["results"]
        assert [r["status"] for r in got] == ["ok", "ok", "ok", "error", "ok"]
        assert [r["object"]["metadata"]["name"] for r in got if r["status"] == "ok"] == [
            "a0", "b0", "a1", "b1"]
        assert all(r["object"] == store.get("Pod", r["object"]["metadata"]["name"],
                                            namespace=r["object"]["metadata"]["namespace"])
                   for r in got if r["status"] == "ok")
        # a shard's own lane: the op of the other shard is refused, the rest land
        code, body = post(srv, "/shards/1/bulk", {"ops": [
            create("mis", ns=ns_a), create("b2", ns=ns_b)]})
        assert code == 200
        lane = json.loads(body)["results"]
        assert lane[0]["status"] == "error" and lane[0]["reason"] == "Misrouted"
        assert lane[1] == {"status": "ok", "object": store.get("Pod", "b2", namespace=ns_b)}
        code, body = post(srv, "/shards/1/txn", {"ops": [create("b3", ns=ns_b)]})
        assert code == 200
        assert json.loads(body) == {"results": [store.get("Pod", "b3", namespace=ns_b)]}


def test_streams_of_both_dialects_write_the_commits_bytes_and_encode_none(tmp_path):
    """With a log the lines of a ``/bulk``'s events are no stream's to encode."""
    store = logged_store(tmp_path)
    with APIServer(store) as srv:
        streams = open_streams(srv, store, 3)
        enc = store_mod._H_LINES_ENCODED.snapshot().get(("Pod",), {"sum": 0.0})["sum"]
        try:
            code, body = post(srv, "/bulk", {"ops": [create(f"p{i}") for i in range(8)]})
            got = [s.wait(8) for s in streams]
        finally:
            for s in streams:
                s.close()
        deadline = time.monotonic() + 5
        while (store_mod._C_LINES.snapshot().get(("Pod",), 0) < 24
               and time.monotonic() < deadline):
            time.sleep(0.01)
    assert code == 200 and got[0] == got[1] == got[2]
    assert got[0] == [e.line for e in store._state("Pod").history]
    assert store_mod._H_LINES_ENCODED.snapshot()[("Pod",)]["sum"] == enc
    entries = json.loads(body)["results"]
    assert [json.loads(ln)["object"] for ln in got[0]] == [r["object"] for r in entries]
