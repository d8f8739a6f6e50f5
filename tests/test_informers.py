"""The users' own controllers (ISSUE 35): client-go-shaped informers on the
Kubernetes wire.  The pages of one LIST are one snapshot on every route that
pages (the store, both HTTP dialects, a sharded router, a tenant's view); a
continue token whose snapshot is gone, or that nobody gave out, answers 410;
N Kubernetes-wire streams write one ``json.dumps`` an event; a commit's
locked pass asks no scoped watcher that does not select its object, and what
each watcher is delivered is what ``match_label_selector`` selected before;
the benchmark's plain reference informer, fed LIST + WATCH over HTTP while a
writer churns, ends equal to the store, also after it was cut for being
slow."""

import base64
import http.client
import json
import random
import socket
import sys
import threading
import time
from urllib.parse import quote, urlsplit

import pytest

from benchmarks.generators import informed_churn
from benchmarks.references import informer_general_stages as reference
from kwok_tpu.cluster import store as store_mod
from kwok_tpu.cluster.apiserver import APIServer
from kwok_tpu.cluster.client import ClusterClient
from kwok_tpu.cluster.sharding import build_sharded_store
from kwok_tpu.cluster.store import Expired, ListSnapshots, ResourceStore
from kwok_tpu.fleet.tenant import TenantStore
from kwok_tpu.utils.trace import Tracer, set_global

K8S_PODS = "/api/v1/namespaces/default/pods"


def make_pod(name, labels=None, ns="default"):
    meta = {"name": name, "namespace": ns}
    if labels:
        meta["labels"] = dict(labels)
    return {"apiVersion": "v1", "kind": "Pod", "metadata": meta,
            "spec": {"nodeName": "node-0", "containers": [{"name": "app", "image": "x"}]}}


def get_json(url, path):
    u = urlsplit(url)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=30)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


# ------------------------------------------------------------- the paged LIST


class StorePager:
    """``list_page`` called in process."""

    def __init__(self, store, srv=None):
        self.store = store

    def page(self, limit, token):
        return self.store.list_page("Pod", namespace="default", limit=limit, continue_from=token)


class LegacyPager:
    def __init__(self, store, srv):
        self.url = srv.url

    def page(self, limit, token):
        q = f"/r/pods?namespace=default&limit={limit}" + (f"&continue={token}" if token else "")
        status, body = get_json(self.url, q)
        if status == 410:
            raise Expired(body["error"])
        assert status == 200, body
        return body["items"], int(body["resourceVersion"]), body.get("continue")


class K8sPager:
    def __init__(self, store, srv):
        self.url = srv.url

    def page(self, limit, token):
        q = f"{K8S_PODS}?limit={limit}" + (f"&continue={token}" if token else "")
        status, body = get_json(self.url, q)
        if status == 410:
            assert body["kind"] == "Status" and body["reason"] == "Expired"
            raise Expired(body["message"])
        assert status == 200, body
        assert body["kind"] == "PodList"
        return (body["items"], int(body["metadata"]["resourceVersion"]),
                body["metadata"].get("continue"))


PAGERS = {"store": StorePager, "legacy": LegacyPager, "k8s": K8sPager}


def churn_between_pages(store, rng, round_no):
    """Creates, deletes and status writes, some on either side of any cursor."""
    names = sorted(o["metadata"]["name"] for o in store.list("Pod", copy=False)[0])
    for name in rng.sample(names, 5):
        store.delete("Pod", name, namespace="default")
    for k in range(6):
        store.create(make_pod(f"p{rng.randrange(1000):04d}-new{round_no}-{k}"))
    left = sorted(o["metadata"]["name"] for o in store.list("Pod", copy=False)[0])
    done = store.apply_status_batch(
        "Pod", [("default", n, {"phase": "Running", "round": round_no})
                for n in rng.sample(left, 20)])
    assert all(done)


@pytest.mark.parametrize("route", sorted(PAGERS))
def test_the_pages_of_one_list_are_the_unpaged_list_at_the_first_pages_rv(route):
    rng = random.Random(3500000001)
    store = ResourceStore()
    store.bulk([{"verb": "create", "data": make_pod(f"p{i:04d}")} for i in range(0, 1000, 5)])
    with APIServer(store) as srv:
        pager = PAGERS[route](store, srv)
        want, want_rv = store.list("Pod", namespace="default")
        pages, rvs, token, round_no = [], [], None, 0
        while True:
            items, rv, token = pager.page(17, token)
            pages.append(items)
            rvs.append(rv)
            if token is None:
                break
            round_no += 1
            churn_between_pages(store, rng, round_no)
    assert len(pages) == 12 and round_no == 11
    # every page carries the first page's resourceVersion, which is the
    # one the unpaged LIST was read at
    assert rvs == [want_rv] * len(pages)
    got = [o for items in pages for o in items]
    # each object that existed then, once, as it was then; none created later
    assert got == want
    assert store.resource_version > want_rv + 300
    # a LIST that fits one page pins nothing; this one's snapshot went with
    # its last page
    assert not store._snapshots._snaps


@pytest.mark.parametrize("route", sorted(PAGERS))
def test_a_forged_or_expired_token_answers_410_and_never_a_fresh_read(route, monkeypatch):
    store = ResourceStore()
    store.bulk([{"verb": "create", "data": make_pod(f"p{i:03d}")} for i in range(40)])
    store.create({"apiVersion": "v1", "kind": "ConfigMap",
                  "metadata": {"name": "c", "namespace": "default"}})
    with APIServer(store) as srv:
        pager = PAGERS[route](store, srv)

        def encoded(token):
            if route == "store":
                return token
            return base64.urlsafe_b64encode(json.dumps(list(token)).encode()).decode()

        _items, _rv, token = pager.page(10, None)
        assert token is not None
        sid, pos = token if route == "store" else json.loads(base64.urlsafe_b64decode(token))
        assert pos == 10
        # nobody gave these out: another snapshot, a position outside this one
        for forged in ((sid + 1000, 10), (sid, 0), (sid, 41), (sid, "x"), ("pods", 10)):
            with pytest.raises(Expired):
                pager.page(10, encoded(forged))
        # the real one still serves, from where it stood
        items, _rv, token = pager.page(10, encoded((sid, pos)))
        assert [o["metadata"]["name"] for o in items] == [f"p{i:03d}" for i in range(10, 20)]
        # a token of one kind's snapshot is none of another kind's
        if route == "store":
            with pytest.raises(Expired):
                store.list_page("ConfigMap", limit=10, continue_from=token)
        # aged out
        monkeypatch.setattr(ListSnapshots, "TTL_S", 0.0)
        with pytest.raises(Expired):
            pager.page(10, token)


def test_what_a_paged_list_pins_is_bounded_by_count_and_by_age(monkeypatch):
    store = ResourceStore()
    store.bulk([{"verb": "create", "data": make_pod(f"p{i:03d}")} for i in range(30)])
    monkeypatch.setattr(ListSnapshots, "MAX", 4)
    expired = lambda: store_mod._C_LIST_SNAPSHOTS.snapshot().get(("expired",), 0)  # noqa: E731
    opened = lambda: store_mod._C_LIST_SNAPSHOTS.snapshot().get(("opened",), 0)  # noqa: E731
    was, was_opened = expired(), opened()
    tokens = [store.list_page("Pod", limit=10)[2] for _ in range(6)]
    assert opened() - was_opened == 6
    assert len(store._snapshots._snaps) == 4
    for tok in tokens[:2]:  # the two oldest were pushed out
        with pytest.raises(Expired):
            store.list_page("Pod", limit=10, continue_from=tok)
    assert expired() - was == 2
    # an unpaged LIST, and one whose first page is its last, pin nothing
    store.list("Pod")
    assert store.list_page("Pod", limit=30)[2] is None
    assert len(store._snapshots._snaps) == 4
    # served to the end: the snapshot goes
    items, _rv, tok = store.list_page("Pod", limit=10, continue_from=tokens[2])
    items2, _rv, end = store.list_page("Pod", limit=10, continue_from=tok)
    assert end is None and len(items) == len(items2) == 10
    assert len(store._snapshots._snaps) == 3
    # a snapshot pins the objects it was cut with, not copies of them
    kept = store._snapshots._snaps[tokens[3][0]][1]
    assert all(obj is store._state("Pod").objects.get(key) for key, obj in kept)
    store.patch("Pod", "p000", {"metadata": {"labels": {"a": "b"}}}, "merge",
                namespace="default")
    assert kept[0][1] is not store._state("Pod").objects[("default", "p000")]
    assert "labels" not in kept[0][1]["metadata"]
    # too old: dropped as the next one opens
    monkeypatch.setattr(ListSnapshots, "TTL_S", 0.0)
    time.sleep(0.01)
    store.list_page("Pod", limit=10)
    assert len(store._snapshots._snaps) == 1


def test_a_list_across_shards_and_a_tenants_view_page_over_one_snapshot():
    sharded = build_sharded_store(3)
    for i in range(60):
        sharded.create(make_pod(f"p{i:03d}", ns=f"ns-{i % 7}"))
    want, _rv = sharded.list("Pod")
    got, token, rvs = [], None, set()
    while True:
        items, rv, token = sharded.list_page("Pod", limit=8, continue_from=token)
        got += items
        rvs.add(rv)
        if token is None:
            break
        sharded.create(make_pod(f"late-{len(got)}", ns="ns-0"))
        sharded.delete("Pod", got[-1]["metadata"]["name"],
                       namespace=got[-1]["metadata"]["namespace"])
    key = lambda o: (o["metadata"]["namespace"], o["metadata"]["name"])  # noqa: E731
    assert sorted(got, key=key) == sorted(want, key=key) and len(rvs) == 1
    assert [key(o) for o in got] == sorted(map(key, got))
    # within one namespace the walk is one shard's own
    one, token = [], None
    while True:
        items, _rv, token = sharded.list_page("Pod", namespace="ns-3", limit=2,
                                              continue_from=token)
        one += items
        if token is None:
            break
    assert one == sharded.list("Pod", namespace="ns-3")[0] and 5 <= len(one) <= 9
    # a tenant's pages pass the store's token through and see its own objects
    store = ResourceStore()
    for tenant in ("a", "b"):
        view = TenantStore(store, tenant)
        for i in range(9):
            view.create(make_pod(f"{tenant}{i}"))
    view = TenantStore(store, "a")
    names, token, pages = [], None, 0
    while True:
        items, _rv, token = view.list_page("Pod", namespace="default", limit=4,
                                           continue_from=token, copy=False)
        names += [(o["metadata"]["namespace"], o["metadata"]["name"]) for o in items]
        pages += 1
        if token is None:
            break
        view.create(make_pod(f"a-late-{pages}"))
    assert names == [("default", f"a{i}") for i in range(9)] and pages == 5


def test_the_client_pages_through_one_snapshot_and_raises_expired(monkeypatch):
    store = ResourceStore()
    store.bulk([{"verb": "create", "data": make_pod(f"p{i:03d}")} for i in range(50)])
    with APIServer(store) as srv:
        client = ClusterClient(srv.url)
        real = client._request
        calls = []

        def racing(method, path, *a, **kw):
            out = real(method, path, *a, **kw)
            if "limit=" in path:
                calls.append(path)
                store.create(make_pod(f"late-{len(calls)}"))
                store.delete("Pod", f"p{len(calls):03d}", namespace="default")
            return out

        monkeypatch.setattr(client, "_request", racing)
        items, rv = client.list_paged("Pod", namespace="default", page_size=7)
        assert len(calls) == 8
        assert [o["metadata"]["name"] for o in items] == [f"p{i:03d}" for i in range(50)]
        # a watch from the LIST's resourceVersion hears of everything since
        w = client.watch("Pod", namespace="default", since_rv=rv)
        seen = []
        while len(seen) < 16:
            ev = w.next(timeout=5)
            assert ev is not None
            seen.append((ev.type, ev.object["metadata"]["name"]))
        w.stop()
        assert sorted(seen) == sorted(
            [("ADDED", f"late-{i}") for i in range(1, 9)]
            + [("DELETED", f"p{i:03d}") for i in range(1, 9)])
        monkeypatch.setattr(ListSnapshots, "TTL_S", -1.0)
        with pytest.raises(Expired):
            client.list_paged("Pod", namespace="default", page_size=7)


# ----------------------------------------------------- one encode an event


class RawStream:
    """One raw watch connection: the bytes as the server wrote them."""

    def __init__(self, url, path, headers=()):
        u = urlsplit(url)
        self.sock = socket.create_connection((u.hostname, u.port), timeout=30)
        head = "".join(f"{k}: {v}\r\n" for k, v in headers)
        self.sock.sendall(f"GET {path} HTTP/1.1\r\nHost: {u.hostname}\r\n{head}\r\n".encode())
        self.fp = self.sock.makefile("rb")
        assert self.fp.readline().split()[1] == b"200"
        while self.fp.readline() not in (b"\r\n", b""):
            pass
        self.lines = []
        self._thread = threading.Thread(target=self._read, daemon=True)
        self._thread.start()

    def _read(self):
        try:
            for line in self.fp:
                self.lines.append(line)
        except OSError:
            pass

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


def pod_watchers(store):
    return len(store._state("Pod").watchers)


def open_raw(srv, store, paths, headers=()):
    want = pod_watchers(store) + len(paths)
    streams = [RawStream(srv.url, p, headers) for p in paths]
    deadline = time.monotonic() + 10
    while pod_watchers(store) < want and time.monotonic() < deadline:
        time.sleep(0.005)
    assert pod_watchers(store) == want
    return streams


def series():
    enc = store_mod._H_LINES_ENCODED.snapshot().get(("Pod",), {"sum": 0.0, "count": 0})
    return {"encoded": enc["sum"], "bursts": enc["count"],
            "written": store_mod._C_LINES.snapshot().get(("Pod",), 0),
            "timed": store_mod._H_ENCODE.snapshot().get(("Pod",), {"count": 0})["count"]}


def gained(before, want_written, timeout=10.0):
    deadline = time.monotonic() + timeout
    while True:
        now = series()
        got = {k: now[k] - before[k] for k in now}
        if got["written"] >= want_written or time.monotonic() >= deadline:
            return got
        time.sleep(0.01)


@pytest.fixture
def one_turn_a_burst():
    """No forced thread switches, so no two streams race for one event and
    the counts are exact (a race is benign and counts as two encodes)."""
    was = sys.getswitchinterval()
    sys.setswitchinterval(1000.0)
    try:
        yield
    finally:
        sys.setswitchinterval(was)


def commits(store, n=12):
    """A bulk of creates, a status batch, deletes one by one: 3 events a pod."""
    store.bulk([{"verb": "create", "data": make_pod(f"w{i}", {"app": f"roll-{i % 3}"})}
                for i in range(n)])
    done = store.apply_status_batch(
        "Pod", [("default", f"w{i}", {"phase": "Running"}) for i in range(n)])
    assert all(done)
    for i in range(n):
        store.delete("Pod", f"w{i}", namespace="default")
    return 3 * n


def test_n_kubernetes_wire_streams_write_the_same_bytes_for_one_encode(one_turn_a_burst):
    store = ResourceStore()
    watch = f"{K8S_PODS}?watch=true&allowWatchBookmarks=true&resourceVersion="
    with APIServer(store) as srv:
        rv = str(store.resource_version)
        k8s = open_raw(srv, store, [watch + rv] * 4)
        scoped = open_raw(srv, store, [watch + rv + "&labelSelector=" + quote("app=roll-1")])
        legacy = open_raw(srv, store, ["/r/pods?watch=1"])
        before = series()
        try:
            events = commits(store)
            got = [s.wait(events) for s in k8s]
            mine = scoped[0].wait(events // 3)
            old = legacy[0].wait(events)
            tally = gained(before, 5 * events + events // 3)
        finally:
            for s in k8s + scoped + legacy:
                s.close()
    history = list(store._state("Pod").history)
    assert len(history) == events
    # the frame is the Kubernetes envelope round the object's compact JSON
    # (the WAL's style since PR 37), and parses to what it did before
    assert got[0] == [
        b'{"type": "%s", "object": %s}\n'
        % (e.type.encode(), json.dumps(e.object, separators=(",", ":")).encode())
        for e in history]
    assert [json.loads(f) for f in got[0]] == [
        {"type": e.type, "object": e.object} for e in history]
    assert all(lines == got[0] for lines in got[1:])
    # a scoped stream writes its share of the same bytes, the other dialect
    # the line they were cut from
    assert mine == [ln for ln, e in zip(got[0], history)
                    if e.object["metadata"]["labels"]["app"] == "roll-1"]
    assert old == [e.line for e in history]
    assert [store_mod.k8s_frame(ln) for ln in old] == got[0]
    # six streams, one json.dumps an event; the Kubernetes-wire streams are
    # counted in the three series
    assert tally["encoded"] == events
    assert tally["written"] == 5 * events + events // 3
    assert tally["bursts"] == tally["timed"] >= 6


def test_table_and_traced_streams_encode_for_themselves(one_turn_a_burst):
    store = ResourceStore()
    watch = f"{K8S_PODS}?watch=true&resourceVersion="
    table = ("Accept", "application/json;as=Table;v=v1;g=meta.k8s.io,application/json")
    with APIServer(store) as srv:
        rv = str(store.resource_version)
        plain = open_raw(srv, store, [watch + rv] * 2)
        tabled = open_raw(srv, store, [watch + rv], headers=(table,))
        before = series()
        try:
            events = commits(store, n=4)
            frames = [s.wait(events) for s in plain]
            rows = tabled[0].wait(events)
            tally = gained(before, 3 * events)
        finally:
            for s in plain + tabled:
                s.close()
        assert frames[0] == frames[1]
        assert all(json.loads(ln)["object"]["kind"] == "Table" for ln in rows)
        # the Table stream encoded every frame it wrote, the two others shared
        assert tally["encoded"] == 2 * events and tally["written"] == 3 * events
    # traced: the envelope carries the delivery's ctx, so nothing is shared
    store = ResourceStore()
    tracer = Tracer("t", endpoint="http://127.0.0.1:9/v1/traces")
    set_global(tracer)
    try:
        with APIServer(store) as srv:
            traced = open_raw(srv, store, [watch + str(store.resource_version)] * 2)
            before = series()
            try:
                with tracer.span("write"):
                    store.create(make_pod("traced"))
                lines = [s.wait(1) for s in traced]
                tally = gained(before, 2)
            finally:
                for s in traced:
                    s.close()
    finally:
        set_global(None)
        tracer.stop()
    assert all("ctx" in json.loads(ln[0]) for ln in lines)
    assert tally["encoded"] == tally["written"] == 2
    assert store._state("Pod").history[-1].line is None


# ------------------------------------------- scoped watchers and the fan-out


def old_match_label_selector(obj, sel):
    """``match_label_selector`` as it stood before the selector was parsed
    once (PR 34's ``store.py``), the oracle of what a watcher was delivered."""
    def split(s):
        parts, cur, depth = [], [], 0
        for ch in s:
            depth += (ch == "(") - (ch == ")")
            if ch == "," and depth == 0:
                parts.append("".join(cur))
                cur = []
            else:
                cur.append(ch)
        return parts + ["".join(cur)]

    def values(raw):
        return [v.strip() for v in raw.strip().strip("()").split(",") if v.strip()]

    labels = (obj.get("metadata") or {}).get("labels") or {}
    for part in split(sel or ""):
        part = part.strip()
        if not part:
            continue
        low = f" {part} "
        if " notin " in low:
            k, v = low.split(" notin ", 1)
            if labels.get(k.strip()) in values(v):
                return False
        elif " in " in low:
            k, v = low.split(" in ", 1)
            if k.strip() not in labels or labels[k.strip()] not in values(v):
                return False
        elif "!=" in part:
            k, v = part.split("!=", 1)
            if labels.get(k.strip()) == v.strip():
                return False
        elif "=" in part:
            k, v = part.split("==", 1) if "==" in part else part.split("=", 1)
            if labels.get(k.strip()) != v.strip():
                return False
        elif part.startswith("!"):
            if part[1:].strip() in labels:
                return False
        elif part not in labels:
            return False
    return True


def random_selector(rng):
    reqs = []
    for _ in range(rng.randint(1, 3)):
        key = rng.choice(["app", "tier", "zone"])
        vals = rng.sample(["a", "b", "c", "d"], rng.randint(1, 3))
        reqs.append(rng.choice([
            f"{key}={vals[0]}", f"{key}=={vals[0]}", f"{key}!={vals[0]}", key, f"!{key}",
            f"{key} in ({','.join(vals)})", f"{key} notin ({', '.join(vals)})"]))
    return ",".join(reqs)


@pytest.mark.parametrize("seed", [3500000011, 3500000012, 3500000013])
def test_the_fan_out_delivers_what_match_label_selector_selected(seed):
    rng = random.Random(seed)
    store = ResourceStore(watch_high_water=0)
    asked = [(rng.choice([None, "default", "other"]), random_selector(rng)) for _ in range(40)]
    asked += [(None, None), ("default", None), ("other", None), (None, "app=a"),
              ("default", "app=a,tier!=b")]
    watchers = [store.watch("Pod", namespace=ns, label_selector=sel) for ns, sel in asked]
    routes = {w._route[0] for w in watchers}
    assert routes == {"every", "namespace", "label", "scan"}

    def labels():
        return {k: rng.choice(["a", "b", "c", "d"]) for k in ("app", "tier", "zone")
                if rng.random() < 0.6}

    alive, events = [], 0
    while events < 5000:
        op = rng.random()
        if op < 0.25 or len(alive) < 20:
            ns = rng.choice(["default", "other"])
            name = f"p{events}"
            store.create(make_pod(name, labels(), ns=ns))
            alive.append((ns, name))
            events += 1
        elif op < 0.45:
            ns, name = rng.choice(alive)
            store.patch("Pod", name, {"metadata": {"labels": None}}, "merge", namespace=ns)
            store.patch("Pod", name, {"metadata": {"labels": labels()}}, "merge", namespace=ns)
            events += 2
        elif op < 0.75:
            picked = rng.sample(alive, min(len(alive), rng.randint(1, 40)))
            done = store.apply_status_batch(
                "Pod", [(ns, name, {"phase": "Running", "n": events}) for ns, name in picked])
            events += sum(1 for d in done if d)
        elif op < 0.85:
            picked = rng.sample(alive, min(len(alive), rng.randint(1, 10)))
            objs = store._state("Pod").objects
            gone = store.apply_delete_batch(
                "Pod", [(ns, name, objs[(ns, name)]["metadata"]["resourceVersion"])
                        for ns, name in picked])
            assert all(gone)
            alive = [k for k in alive if k not in picked]
            events += len(picked)
        else:
            ns, name = alive.pop(rng.randrange(len(alive)))
            store.bulk([{"verb": "delete", "kind": "Pod", "name": name, "namespace": ns}])
            events += 1
    history = list(store._state("Pod").history)
    assert len(history) == events >= 5000
    selected = 0
    for (ns, sel), w in zip(asked, watchers):
        want = [(e.type, e.rv) for e in history
                if (ns is None or e.object["metadata"]["namespace"] == ns)
                and old_match_label_selector(e.object, sel)]
        assert [(e.type, e.rv) for e in w.drain()] == want, (ns, sel)
        selected += len(want)
        # and a resume replays the same from the ring
        again = store.watch("Pod", namespace=ns, label_selector=sel, since_rv=0)
        assert [(e.type, e.rv) for e in again.drain()] == want
        again.stop()
    assert selected > 5000
    for w in watchers:
        w.stop()
    routes = store._state("Pod").routes
    assert not routes.homes and not routes.label_keys


def test_a_commit_asks_no_scoped_watcher_that_does_not_select_its_object():
    store = ResourceStore()
    asked = []

    def counting(w):
        real = w._filter

        def filt(obj):
            asked.append(w)
            return real(obj)

        w._filter = filt

    scoped = [store.watch("Pod", namespace="default", label_selector=f"app=roll-{j}")
              for j in range(200)]
    wide = [store.watch("Pod", namespace="default") for _ in range(10)]
    for w in scoped + wide:
        counting(w)
    store.create(make_pod("a", {"app": "roll-7"}))
    store.create(make_pod("b"))
    store.apply_status_batch("Pod", [("default", "a", {"phase": "Running"}),
                                     ("default", "b", {"phase": "Running"})])
    # the one watcher whose equality the object carries was asked, twice;
    # a namespace-only watcher is never asked: its list is its answer
    assert asked == [scoped[7], scoped[7]]
    assert [len(w.drain()) for w in wide] == [4] * 10
    assert [len(w.drain()) for w in scoped] == [2 if j == 7 else 0 for j in range(200)]
    # the selector was parsed when the watch was opened, and not since
    info = store_mod._parse_selector_string.cache_info()
    store.create(make_pod("c", {"app": "roll-9"}))
    assert store_mod._parse_selector_string.cache_info().misses == info.misses
    assert store_mod._parse_selector_string.cache_info().hits == info.hits


def test_the_new_series_are_observed_one_a_page_and_one_a_commit():
    store = ResourceStore()
    w = store.watch("Pod", namespace="default", label_selector="app=x")

    def count(family, label):
        return family.snapshot().get((label,), {"count": 0})["count"]

    f0 = count(store_mod._H_WATCH_FILTER, "Pod")
    store.create(make_pod("a", {"app": "x"}))
    assert count(store_mod._H_WATCH_FILTER, "Pod") == f0 + 1
    store.bulk([{"verb": "create", "data": make_pod(f"b{i}", {"app": "x"})} for i in range(30)])
    assert count(store_mod._H_WATCH_FILTER, "Pod") == f0 + 2  # a bulk's commits: one
    store.apply_status_batch("Pod", [("default", f"b{i}", {"phase": "Running"})
                                     for i in range(30)])
    assert count(store_mod._H_WATCH_FILTER, "Pod") == f0 + 3
    assert len(w.drain()) == 61
    p0 = count(store_mod._H_LIST_PAGE, "Pod")
    objects0 = store_mod._H_LIST_OBJECTS.snapshot().get(("Pod",), {"sum": 0.0})["sum"]
    token, pages = None, 0
    while True:
        _items, _rv, token = store.list_page("Pod", limit=8, continue_from=token)
        pages += 1
        if token is None:
            break
    assert pages == 4 and count(store_mod._H_LIST_PAGE, "Pod") == p0 + 4
    assert store_mod._H_LIST_OBJECTS.snapshot()[("Pod",)]["sum"] == objects0 + 31
    # the three outcomes are there from the start, at 0 or more, for a reader of deltas
    with APIServer(store) as srv:
        u = urlsplit(srv.url)
        conn = http.client.HTTPConnection(u.hostname, u.port, timeout=30)
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        conn.close()
    for outcome in ("opened", "served", "expired"):
        assert f'kwok_list_snapshots{{outcome="{outcome}"}}' in text
    for name in ("kwok_list_page_seconds_sum", "kwok_list_page_objects_sum",
                 "kwok_watch_filter_seconds_sum"):
        assert name + '{kind="Pod"}' in text


# ------------------------------ the reference informer against the served path


class Writer(threading.Thread):
    """Creates, status batches and deletes over labelled and unlabelled pods
    until told to stop; leaves some alive."""

    def __init__(self, store, seed):
        super().__init__(daemon=True)
        self.store = store
        self.rng = random.Random(seed)
        self.halt = threading.Event()
        self.n = 0

    def run(self):
        alive = []
        while not self.halt.is_set():
            batch = [make_pod(f"r{self.n + k}", {"app": f"roll-{(self.n + k) % 4}"})
                     for k in range(10)]
            batch.append(make_pod(f"plain{self.n}"))
            self.n += 10
            self.store.bulk([{"verb": "create", "data": p} for p in batch])
            alive += [p["metadata"]["name"] for p in batch]
            self.store.apply_status_batch(
                "Pod", [("default", n, {"phase": "Running"})
                        for n in self.rng.sample(alive, min(len(alive), 15))])
            self.rng.shuffle(alive)
            for _ in range(min(len(alive) - 40, 12)):
                self.store.delete("Pod", alive.pop(), namespace="default")
            time.sleep(0.002)


def final_of(store, selector):
    label = None if selector is None else "=".join(selector)
    return {o["metadata"]["name"]: int(o["metadata"]["resourceVersion"])
            for o in store.list("Pod", namespace="default", label_selector=label,
                                copy=False)[0]}


def wait_equal(informer, final, timeout=15.0):
    deadline = time.monotonic() + timeout
    while informer.cache.items != final and time.monotonic() < deadline:
        time.sleep(0.02)
    return reference.disagreements(informer.cache.items, final)


def test_the_reference_informer_over_http_ends_equal_to_the_store():
    store = ResourceStore()
    store.bulk([{"verb": "create", "data": make_pod(f"standing{i:03d}")} for i in range(150)])
    with APIServer(store) as srv:
        writer = Writer(store, 3500000021)
        writer.start()
        informers = [informed_churn.Informer(srv.url, j, sel, 20, random.Random(j))
                     for j, sel in enumerate([None, None, ("app", "roll-1"), ("app", "roll-3")])]
        for inf in informers:
            inf.start()
        assert all(inf.synced.wait(20) for inf in informers)
        for k in range(6):  # restarts under the churn: a paged LIST between writes
            time.sleep(0.25)
            informers[k % 4].restart()
        time.sleep(0.3)
        writer.halt.set()
        writer.join(timeout=10)
        assert not writer.is_alive() and writer.n > 200
        for inf in informers:
            assert wait_equal(inf, final_of(store, inf.cache.selector)) == []
            assert inf.cache.findings == [] and inf.is_alive()
        assert informers[0].counts["lists"] >= 2 and informers[0].counts["pages"] >= 16
        assert informers[0].cache.events > 400
        assert len(informers[2].cache.items) < len(informers[0].cache.items)
        assert sum(inf.counts["gone_410"] for inf in informers) == 0
    # what the reference makes of it for the check: nothing to count
    reports = [{"name": str(inf.j), "cache": inf.cache.items, "findings": inf.cache.findings,
                "final": final_of(store, inf.cache.selector)} for inf in informers]
    assert reference.informers(reports, window=["r10"]) == []
    assert reference.pod_mismatch.__module__ == reference.__name__


def test_an_evicted_kubernetes_wire_watcher_gets_the_410_frame_and_is_whole_after_its_relist():
    store = ResourceStore(watch_high_water=64)
    store.bulk([{"verb": "create", "data": make_pod(f"p{i:03d}")} for i in range(300)])
    with APIServer(store) as srv:
        inf = informed_churn.Informer(srv.url, 0, None, 50, random.Random(0))
        inf.start()
        assert inf.synced.wait(20)
        deadline = time.monotonic() + 10
        while pod_watchers(store) < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        evicted = store.watch_evictions
        # one commit of more events than the high-water mark: the backlog
        # passes it before the stream's thread can take any
        done = store.apply_status_batch(
            "Pod", [("default", f"p{i:03d}", {"phase": "Running"}) for i in range(300)])
        assert all(done)
        assert store.watch_evictions == evicted + 1
        assert wait_equal(inf, final_of(store, None)) == []
        assert inf.counts["gone_410"] == 1 and inf.counts["lists"] == 2
        # whole again, and hearing of what comes after
        store.delete("Pod", "p000", namespace="default")
        store.create(make_pod("after"))
        assert wait_equal(inf, final_of(store, None)) == []
        assert "after" in inf.cache.items and "p000" not in inf.cache.items
        assert inf.cache.findings == []


def test_the_plain_reference_counts_what_an_informer_got_wrong():
    cache = reference.Cache(("app", "roll-1"))
    page = lambda rv, *objs: {"metadata": {"resourceVersion": str(rv)}, "items": list(objs)}  # noqa: E731
    obj = lambda name, rv, app="roll-1": {"metadata": {  # noqa: E731
        "name": name, "resourceVersion": str(rv), "labels": {"app": app}}}
    cache.replace([page(10, obj("a", 3), obj("b", 7)), page(10, obj("c", 9))])
    assert cache.items == {"a": 3, "b": 7, "c": 9} and cache.rv == 10 and not cache.findings
    cache.apply("MODIFIED", obj("a", 11))
    cache.apply("DELETED", obj("b", 12))
    cache.apply("BOOKMARK", {"metadata": {"resourceVersion": "20"}})
    cache.apply("ADDED", obj("d", 21))
    assert cache.items == {"a": 11, "c": 9, "d": 21} and cache.rv == 21 and not cache.findings
    # g2: an event that does not pass the last one, an object not selected
    cache.apply("MODIFIED", obj("a", 21))
    cache.apply("ADDED", obj("e", 22, app="roll-2"))
    # g1: a page at another resourceVersion, a key listed twice
    other = reference.Cache()
    other.replace([page(10, obj("a", 3)), page(12, obj("a", 11))])
    assert [n for n, _ in cache.findings] == ["a", "e"]
    assert [n for n, _ in other.findings] == ["", "a"]
    # g3, and where each finding goes
    final = {"a": 21, "c": 10, "z": 5}
    assert sorted(n for n, _ in reference.disagreements(cache.items, final)) == \
        ["c", "d", "e", "z"]
    loose = reference.informers(
        [{"name": "7", "cache": cache.items, "final": final, "findings": cache.findings},
         {"name": "8", "cache": other.items, "final": other.items, "findings": other.findings}],
        window=["a", "c", "d"])
    assert sorted(loose) == sorted([
        "e: informer 7: ADDED delivered though the selector does not select it",
        "e: informer 7: still in the informer's store, gone from the final LIST",
        "z: informer 7: absent from the informer's store, in the final LIST",
        "no pod: informer 8: a page carries resourceVersion 12, the LIST's first page 10"])
    sent = {"metadata": {"name": "c"}, "spec": {"containers": [{"name": "app", "image": "x"}]}}
    import benchmarks.references.general_stages as stages

    good = {**stages.merge(stages.pod_create(sent, "10.0.0.1"), stages.pod_ready(sent)),
            "podIP": "10.0.0.9"}
    assert stages.pod_mismatch(sent, good, "10.0.0.1") is None
    assert reference.pod_mismatch(sent, good, "10.0.0.1") == (
        "informer 7: at resourceVersion 9 in the informer's store, 10 in the final LIST")
    sent["metadata"]["name"] = "untouched"
    assert reference.pod_mismatch(sent, good, "10.0.0.1") is None
    # the reference imports nothing of the program
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(reference))
    imported = [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)] + \
        [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    assert not [m for m in imported if "kwok_tpu" in m]
    reference.informers([], window=[])


def test_the_generator_refuses_a_program_whose_pages_are_no_snapshot(monkeypatch):
    from benchmarks.harness.cluster import Failed

    store = ResourceStore()
    store.bulk([{"verb": "create", "data": make_pod(f"p{i}")} for i in range(5)])
    with APIServer(store) as srv:
        informed_churn.require_snapshot_lists(srv.url)  # 410 for a token nobody gave out
        # a program that reads on from whatever the token says
        monkeypatch.setattr(ListSnapshots, "page", lambda self, kind, token, limit, cut: (
            cut()[0][:limit], cut()[1], None))
        with pytest.raises(Failed, match="answered 200, not 410"):
            informed_churn.require_snapshot_lists(srv.url)
