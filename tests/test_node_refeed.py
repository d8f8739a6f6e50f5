"""A node's re-feed costs what the node holds, not what the cluster holds.

Every node the kwok daemon comes to own re-feeds each device player
(``Controller._on_node_owned`` → ``DeviceStagePlayer.sync_node``): the Node
player LISTs Nodes by ``metadata.name``, the Pod player LISTs Pods by
``spec.nodeName``.  The store answers the first from the object's key and the
second from its ``spec.nodeName`` index, on the native wire in process and
over HTTP alike, so the objects a wave makes the store look at grow with the
wave (a full scan a node would make them grow with its square)."""

from types import SimpleNamespace

import pytest

from kwok_tpu.cluster import store as store_mod
from kwok_tpu.cluster.apiserver import APIServer
from kwok_tpu.cluster.client import ClusterClient
from kwok_tpu.cluster.informer import Informer
from kwok_tpu.cluster.store import ResourceStore
from kwok_tpu.controllers.device_player import DeviceStagePlayer
from kwok_tpu.utils.queue import Queue


def node(i: int) -> dict:
    return {"apiVersion": "v1", "kind": "Node", "metadata": {"name": f"node-{i}"}}


def pod(i: int) -> dict:
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": f"pod-{i}", "namespace": "default"},
            "spec": {"nodeName": f"node-{i}",
                     "containers": [{"name": "c", "image": "i"}]}}


def wave_work(n: int, wire: str, monkeypatch) -> dict:
    """Objects the store examined and the objects re-fed, over a wave of
    ``n`` nodes owned one after another, each holding one pod."""
    store = ResourceStore()
    for i in range(n):
        store.create(node(i))
        store.create(pod(i))
    examined = [0]
    real = store_mod.match_field_selector

    def counting(obj, sel):
        examined[0] += 1
        return real(obj, sel)

    monkeypatch.setattr(store_mod, "match_field_selector", counting)
    srv = APIServer(store).start() if wire == "http" else None
    try:
        view = ClusterClient(srv.url) if srv else store
        players = [SimpleNamespace(kind=kind, _predicate=None, _informer=Informer(view, kind),
                                   events=Queue()) for kind in ("Node", "Pod")]
        for i in range(n):
            for p in players:
                DeviceStagePlayer.sync_node(p, f"node-{i}")
    finally:
        if srv is not None:
            srv.stop()
        monkeypatch.setattr(store_mod, "match_field_selector", real)
    refed = sum(len(p.events) for p in players)
    return {"examined": examined[0], "refed": refed}


@pytest.mark.parametrize("wire", ["store", "http"])
def test_a_waves_refeed_grows_linearly(wire, monkeypatch):
    work = {n: wave_work(n, wire, monkeypatch) for n in (50, 100, 200)}
    for n, w in work.items():
        # one Node and one Pod a node, and nothing looked at beside them
        assert w == {"examined": 0, "refed": 2 * n}, (n, w)


def test_a_name_selector_in_a_namespace_is_answered_from_the_key():
    store = ResourceStore()
    for i in range(20):
        store.create(pod(i))
    got, _ = store.list("Pod", namespace="default", field_selector={"metadata.name": "pod-7"})
    assert [p["metadata"]["name"] for p in got] == ["pod-7"]
    assert store.list("Pod", namespace="other", field_selector="metadata.name=pod-7")[0] == []
    assert store.list("Node", field_selector="metadata.name=node-3")[0] == []
    # across namespaces the name names no one key: a scan, as before
    got, _ = store.list("Pod", field_selector="metadata.name=pod-7")
    assert [p["metadata"]["name"] for p in got] == ["pod-7"]
    # a label selector still applies to what the key found
    got, _ = store.list("Pod", namespace="default", label_selector="a=b",
                        field_selector="metadata.name=pod-7")
    assert got == []
