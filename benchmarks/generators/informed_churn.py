"""``informed_churn``: ``churn``'s traffic under the eyes of the users' own
controllers: a population of client-go-shaped Pod informers on the
Kubernetes wire.

The writes are ``churn.py``'s, imported and not copied: the same standing
pods and crash-loopers, the same closed loop over ``rolling_pods`` (``Stream``,
subclassed so that rolling pod ``i`` also carries the label ``app=roll-<i mod
scoped>``), the same ``run`` and ``settle``.  With ``informers`` 0 it sends,
seed for seed, what ``churn`` sends but for that label.

The readers are ``informers`` = ``cluster_wide`` + ``scoped`` informers of
``/api/v1/namespaces/default/pods``, run as ``PROCESSES`` child processes of
the benchmark (so that their decoding does not share the harness's
interpreter), each informer one thread doing what client-go's
``Reflector.ListAndWatch`` does: a LIST in pages of ``page_size`` (``limit``,
``continue``), then ``watch=true&allowWatchBookmarks=true`` from the LIST's
resourceVersion with a ``timeoutSeconds`` drawn from 300-600 s, every frame
decoded with ``json.loads`` and applied to the plain reference's store
(``references/informer_general_stages.py::Cache``: key -> resourceVersion); a
``410 Gone``, as a frame of the WATCH or as the answer to a ``continue``, is
counted and answered with a new LIST at once; a stream that ends without one
resumes from the last resourceVersion seen.  Informer ``j`` below
``cluster_wide`` has no selector; informer ``cluster_wide + j`` asks for
``labelSelector=app=roll-<j>``.  All are started, and waited for until each
has listed and opened its WATCH, after the standing population is Running
and before the warm loop.

In the window restart ``k`` happens at ``t0 + k * restart_every_s``, as long
as a whole period is left before the close (10 restarts at 51 s and 5 s): an
informer drops its connection and lists and watches again, as a restarted
controller does.  It is a cluster-wide informer where ``k mod 3 == 0`` and a
scoped one otherwise, each class in an order the seed draws.

``settle`` is ``churn``'s, then the judgement: one final LIST through each
selector, every informer waited for (a quarter of a minute) until its store
equals it, and every store, final LIST and finding handed to the reference
(``informers``), which counts a window pod an informer got wrong under
``status_mismatch``.  (``warm`` refuses at once a program that serves a continue
token nobody gave out: its pages are no snapshot, and the cell is not for it.)  A finding that names no pod of the window (a standing
pod, a page at another resourceVersion) can reach no number of the
comparison, so ``settle`` then raises and the run prints no result."""

from __future__ import annotations

import base64
import http.client
import json
import os
import random
import subprocess
import sys
import threading
import time
import urllib.parse

if __name__ == "__main__":  # a child: the package is two directories up
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.generators import churn  # noqa: E402
from benchmarks.harness.cluster import Failed  # noqa: E402
from benchmarks.references import informer_general_stages as reference  # noqa: E402

PATH = "/api/v1/namespaces/default/pods"
LABEL = "app"
#: child processes the informers are spread over, round-robin
PROCESSES = 3
#: the name an informer sends for APF (``X-Kwok-Client``): the ``workload:``
#: prefix is the default flow schema's level for what is no system component
CLIENT = "workload:informer-{}"
#: seconds all informers may take to list and open their WATCH at the start
SYNC_S = 180.0
#: seconds an informer may take after the traffic settled to equal the final LIST
CATCH_UP_S = 15.0


def selector_of(j: int, params: dict):
    """Informer ``j``'s ``(label, value)``, None for a cluster-wide one."""
    if j < params["cluster_wide"]:
        return None
    return (LABEL, f"roll-{j - params['cluster_wide']}")


class Informer(threading.Thread):
    """One reflector: LIST, WATCH, again."""

    def __init__(self, server: str, j: int, selector, page_size: int, rng: random.Random):
        super().__init__(daemon=True, name=f"informer-{j}")
        self.j = j
        self.hostport = server.split("://", 1)[1]
        self.cache = reference.Cache(selector)
        self.page_size = page_size
        self.rng = rng
        self.headers = {"X-Kwok-Client": CLIENT.format(j), "Accept": "application/json"}
        self.synced = threading.Event()
        self.counts = {"lists": 0, "pages": 0, "listed": 0, "gone_410": 0, "resumed": 0,
                       "shed_429": 0}
        #: the socket of the request under way, for restart() to cut (a
        #: response that will close takes the connection's socket with it)
        self._sock = None
        #: restart() counts up; the thread lists again when it sees a new count
        self._gen = 0

    def _query(self, **more) -> str:
        q = dict(more)
        if self.cache.selector is not None:
            q["labelSelector"] = "=".join(self.cache.selector)
        return PATH + "?" + urllib.parse.urlencode(q)

    def _get(self, url: str):
        conn = http.client.HTTPConnection(self.hostport, timeout=120)
        conn.connect()
        self._sock = conn.sock
        conn.request("GET", url, headers=self.headers)
        return conn.getresponse()

    def list(self) -> None:
        """The pager: every page of one LIST (one request where
        ``page_size`` is 0), from the start again where a ``continue`` is
        answered 410."""
        while True:
            pages, cont = [], None
            self.counts["lists"] += 1
            while True:
                more = {"limit": self.page_size} if self.page_size else {}
                if cont:
                    more["continue"] = cont
                resp = self._get(self._query(**more))
                body = resp.read()
                resp.close()
                if resp.status == 429:
                    self.counts["shed_429"] += 1
                    time.sleep(float(resp.getheader("Retry-After") or 1))
                    continue
                if resp.status == 410:
                    self.counts["gone_410"] += 1
                    break
                if resp.status != 200:
                    raise RuntimeError(f"LIST answered {resp.status}: {body[:200]!r}")
                page = json.loads(body)
                pages.append(page)
                self.counts["pages"] += 1
                self.counts["listed"] += len(page["items"])
                cont = page["metadata"].get("continue")
                if not cont:
                    self.cache.replace(pages)
                    return

    def _watch(self, gen: int) -> bool:
        """Frames until the stream ends; True where a LIST is due: the
        stream ended in a 410, or restart() cut it."""
        resp = self._get(self._query(
            watch="true", allowWatchBookmarks="true", resourceVersion=self.cache.rv,
            timeoutSeconds=self.rng.randint(300, 600)))
        if resp.status != 200:
            raise RuntimeError(f"WATCH answered {resp.status}")
        if self._gen != gen:  # restarted before this connection was there to cut
            return True
        self.synced.set()
        for line in resp:
            frame = json.loads(line)
            if frame["type"] == "ERROR":
                if frame["object"].get("code") != 410:
                    raise RuntimeError(f"WATCH error frame {frame['object']}")
                self.counts["gone_410"] += 1
                return True
            self.cache.apply(frame["type"], frame["object"])
        return self._gen != gen

    def run(self) -> None:
        relist = True
        while True:
            gen = self._gen
            try:
                if relist:
                    self.list()
                    relist = False
                else:
                    self.counts["resumed"] += 1
                relist = self._watch(gen)
            except (OSError, http.client.HTTPException, ValueError):
                # the connection was cut: by restart(), or under us
                if self._gen != gen:
                    relist = True
                else:
                    time.sleep(0.05)

    def restart(self) -> None:
        """What a restarted controller does: the connection dropped, a LIST,
        a WATCH from its resourceVersion."""
        self._gen += 1
        sock = self._sock
        if sock is not None:
            try:
                sock.shutdown(2)
            except OSError:
                pass

    def report(self, final: dict, deadline: float) -> dict:
        while self.cache.items != final and time.monotonic() < deadline:
            time.sleep(0.05)
        return {"name": str(self.j), "cache": dict(self.cache.items),
                "findings": list(self.cache.findings), "events": self.cache.events,
                "alive": self.is_alive(), **self.counts}


def child_main(argv) -> int:
    """``--child <server> <page_size> <seed> <cluster_wide> <j>...``: the
    informers ``j``, driven by one JSON command a line on stdin, one JSON
    answer a line on stdout; the end of stdin ends the process."""
    server, page_size, seed, cluster_wide = argv[0], int(argv[1]), int(argv[2]), int(argv[3])
    params = {"cluster_wide": cluster_wide}
    mine = {int(j): Informer(server, int(j), selector_of(int(j), params), page_size,
                             random.Random(seed * 1000 + int(j))) for j in argv[4:]}
    for inf in mine.values():
        inf.start()
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["op"] == "synced":
            out = all(inf.synced.wait(cmd["wait_s"]) for inf in mine.values())
        elif cmd["op"] == "restart":
            mine[cmd["informer"]].restart()
            out = True
        elif cmd["op"] == "finish":
            deadline = time.monotonic() + cmd["wait_s"]
            out = [inf.report(cmd["final"][str(j)], deadline) for j, inf in mine.items()]
        else:
            raise ValueError(f"unknown command {cmd['op']!r}")
        sys.stdout.write(json.dumps(out) + "\n")
        sys.stdout.flush()
    return 0


def server_url() -> str:
    """The apiserver of the one cluster under ``KWOK_TPU_HOME`` (the harness
    exports it), from the cluster's own configuration."""
    from kwok_tpu.ctl.runtime import BinaryRuntime

    (name,) = os.listdir(os.path.join(os.environ["KWOK_TPU_HOME"], "clusters"))
    return BinaryRuntime(name).load_config()["serverURL"]


def require_snapshot_lists(server: str) -> None:
    """Refuse, before any pod is created, a program that cannot give g1: it
    has to answer 410 to a continue token that no first page ever gave out
    (a program whose pages are independent reads takes it for a place to
    read on from)."""
    forged = base64.urlsafe_b64encode(json.dumps([0, 1]).encode()).decode()
    conn = http.client.HTTPConnection(server.split("://", 1)[1], timeout=60)
    try:
        conn.request("GET", f"{PATH}?limit=1&continue={forged}",
                     headers={"X-Kwok-Client": "kwok-client"})
        status = conn.getresponse().status
    finally:
        conn.close()
    if status != 410:
        raise Failed(f"a continue token nobody gave out is answered {status}, not 410: this "
                     "program's paged LIST is not one snapshot (guarantee g1), so it cannot "
                     "run the deployment")


class Informers:
    """The parent's side: the children, and who runs which informer."""

    def __init__(self, load, server: str):
        self.load = load
        self.server = server
        p = load.params
        if p["informers"] != p["cluster_wide"] + p["scoped"]:
            raise Failed("informers is not cluster_wide + scoped")
        self.count = p["informers"]
        self.children = []
        #: informer -> its child
        self.home = {}
        self.restarts = 0
        self._lock = threading.Lock()

    def start(self, seed: int) -> None:
        p = self.load.params
        for c in range(min(PROCESSES, self.count)):
            mine = list(range(c, self.count, PROCESSES))
            proc = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--child", self.server,
                 str(p["page_size"]), str(seed), str(p["cluster_wide"])] + list(map(str, mine)),
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
            self.children.append(proc)
            self.home.update((j, proc) for j in mine)
        t = time.monotonic()
        if not all(self._ask(proc, {"op": "synced", "wait_s": SYNC_S}) for proc in self.children):
            raise Failed(f"the informers did not all list and watch in {SYNC_S:.0f} s")
        self.load.log(f"{self.count} informers listed and watching after "
                      f"{time.monotonic() - t:.1f} s, in {len(self.children)} processes")

    def _ask(self, proc, cmd: dict):
        with self._lock:
            try:
                proc.stdin.write(json.dumps(cmd) + "\n")
                proc.stdin.flush()
                line = proc.stdout.readline()
            except OSError as exc:
                raise Failed(f"an informer process is gone: {exc}") from exc
        if not line:
            raise Failed(f"an informer process exited {proc.poll()} and left no answer")
        return json.loads(line)

    def restart(self, j: int) -> None:
        self._ask(self.home[j], {"op": "restart", "informer": j})
        self.restarts += 1

    def restart_on_schedule(self, order: list, t0: float, t1: float, every: float) -> None:
        """Thread body: restart ``order[k]`` at ``t0 + k * every`` while a
        whole period is left before ``t1``."""
        k = 0
        while t0 + (k + 1) * every <= t1 and k < len(order):
            time.sleep(max(t0 + k * every - time.monotonic(), 0.0))
            self.restart(order[k])
            k += 1

    def finish(self, final: dict) -> list:
        """Every informer's report, once its store equals ``final[j]`` or
        ``CATCH_UP_S`` have passed; the children are ended."""
        try:
            reports = []
            for proc in self.children:
                mine = {str(j): final[j] for j, home in self.home.items() if home is proc}
                reports += self._ask(proc, {"op": "finish", "final": mine, "wait_s": CATCH_UP_S})
            for r in reports:
                r["final"] = final[int(r["name"])]
            return reports
        finally:
            self.stop()

    def stop(self) -> None:
        for proc in self.children:
            proc.stdin.close()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
        self.children = []


def restart_order(rng: random.Random, params: dict, count: int) -> list:
    """Who restart ``k`` takes, for ``k`` below ``count``: a cluster-wide
    informer where ``k mod 3 == 0``, a scoped one otherwise, each class in
    an order ``rng`` draws (and again from its start when used up)."""
    wide = list(range(params["cluster_wide"]))
    scoped = list(range(params["cluster_wide"], params["informers"]))
    rng.shuffle(wide)
    rng.shuffle(scoped)
    order, taken = [], {True: 0, False: 0}
    for k in range(count):
        pick = wide if (k % 3 == 0 and wide) or not scoped else scoped
        order.append(pick[taken[pick is wide] % len(pick)])
        taken[pick is wide] += 1
    return order


class Stream(churn.Stream):
    """``churn``'s stream, each pod with the label a scoped informer selects."""

    def create(self, count: int, in_window: bool) -> None:
        pods = []
        for i in range(self.next, self.next + count):
            p = churn.rolling_pod(i, self.order)
            p["metadata"]["labels"] = {LABEL: f"roll-{i % self.load.params['scoped']}"}
            pods.append(p)
        self.next += count
        for bulk in churn._bulks(self.load, pods):
            self.load.bulk_create(bulk, in_window)
        self.alive.update(q["metadata"]["name"] for q in pods
                          if q["metadata"]["name"] in self.load.created)


def warm(load) -> None:
    """``churn.warm`` with the informers started between the wait for
    Running and the warm loop."""
    if load.params.get("clients", 1) != 1:
        raise Failed("churn has one client: one loop keeps the population")
    load.informers = None
    if load.params["informers"]:
        load.informers = Informers(load, server_url())
        require_snapshot_lists(load.informers.server)
    nodes = churn._nodes(load)
    before = [churn.pod(f"standing-{i}", nodes[i % len(nodes)])
              for i in range(load.params["standing_pods"])]
    before += [churn.crashloop_pod(i, nodes) for i in range(load.params["crashloop_pods"])]
    for bulk in churn._bulks(load, before):
        load.bulk_create(bulk, in_window=False)
    if not load.watcher.wait_running(list(load.created), 300, poll=churn.POLL_WAIT_S):
        raise Failed("standing and crash-looping pods did not all reach Running in 300 s")
    load.stream = stream = Stream(load)
    if load.informers is not None:
        # drawn after the stream's own draw, which is churn's
        load.informers.start(load.rng.randrange(2 ** 31))
    stream.loop(time.monotonic() + load.params["warm_s"], in_window=False)


def run(load, t0: float, t1: float) -> None:
    restarter = None
    if load.informers is not None:
        every = load.params["restart_every_s"]
        order = restart_order(load.rng, load.params, int((t1 - t0) // every))
        restarter = threading.Thread(target=load.informers.restart_on_schedule, daemon=True,
                                     args=(order, t0, t1, every), name="informer-restarts")
        restarter.start()
    churn.run(load, t0, t1)
    if restarter is not None:
        restarter.join(timeout=30)
        load.log(f"informers restarted in the window: {load.informers.restarts}")


def final_list(server: str, selector) -> dict:
    """key -> resourceVersion of one unpaged LIST through ``selector``, by
    the route the informers take and under the harness's own name."""
    probe = Informer(server, -1, selector, 0, random.Random(0))
    probe.headers["X-Kwok-Client"] = "kwok-client"
    probe.list()
    return probe.cache.items


def settle(load, t1: float) -> None:
    try:
        churn.settle(load, t1)
        if load.informers is None:
            return
        infs, p = load.informers, load.params
        lists = {}
        final = {}
        for j in range(infs.count):
            sel = selector_of(j, p)
            if sel not in lists:
                lists[sel] = final_list(infs.server, sel)
            final[j] = lists[sel]
        reports = infs.finish(final)
    finally:
        if load.informers is not None:
            load.informers.stop()
    total = {k: sum(r[k] for r in reports) for k in
             ("events", "lists", "pages", "listed", "gone_410", "resumed", "shed_429")}
    dead = [r["name"] for r in reports if not r["alive"]]
    load.log(f"informers: {total}; dead threads: {dead}")
    loose = reference.informers(reports, load.in_window)
    wrong = len(reference.by_pod)
    load.log(f"informers: {wrong} window pods an informer got wrong, "
             f"{len(loose)} findings that name no window pod")
    if dead:
        raise Failed(f"informer threads {dead} died")
    if loose:
        raise Failed(f"{len(loose)} findings of the informers name no pod of the window, so no "
                     f"number of the comparison carries them ({wrong} window pods would count "
                     f"under status_mismatch); the first: {loose[:3]}")


if __name__ == "__main__":
    if sys.argv[1:2] != ["--child"]:
        sys.exit("informed_churn.py is a generator; run.py starts its children")
    sys.exit(child_main(sys.argv[2:]))
