"""Store-boundary analyzer: keep non-cluster code on the duck-typed
store surface.

``ClusterClient`` is duck-typed to ``ResourceStore`` (CLAUDE.md:49-51):
anything taking a store must keep working when handed the REST client,
so code outside ``kwok_tpu/cluster/`` must never reach into store
internals — the moment a controller touches ``store._mut`` or
``store._types``, it silently stops working over HTTP (the reference
never has this problem because its only store *is* the remote
kube-apiserver, reachable only through client-go's public surface).

Detection is lexical on the receiver: an attribute access ``X._name``
(single leading underscore, not a dunder) is flagged when ``X`` is an
identifier whose terminal name looks store-like — ``store``,
``_store``, ``client``, ``_client``, or any ``*store``/``*client``
suffix.  Optional-capability *probes* stay legal: ``hasattr(store,
"bulk")``-style feature tests never name a private attribute.

Shard internals are stricter: any ``X._shards`` / ``X._shard_*``
access (the :class:`~kwok_tpu.cluster.sharding.router.ShardedStore`
private family) is flagged REGARDLESS of the receiver's name.  Shard placement is an implementation detail of
cluster/ — code above it that reaches for a shard list stops working
over the REST client AND breaks the single-store composition, so the
lexical net is cast receiver-wide.
"""

from __future__ import annotations

import ast
from typing import Iterable, List

from kwok_tpu.analysis import Finding, SourceFile, terminal_name

RULE = "store-boundary"

#: files under this prefix own the store internals and are exempt
EXEMPT_PREFIX = "kwok_tpu/cluster/"


def _storeish(name: str) -> bool:
    low = name.lower()
    return low.endswith("store") or low.endswith("client")


def analyze(files: Iterable[SourceFile], config) -> List[Finding]:
    findings: List[Finding] = []
    for sf in files:
        if sf.path.startswith(EXEMPT_PREFIX) or not sf.path.startswith("kwok_tpu/"):
            continue
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Attribute):
                continue
            attr = node.attr
            if not attr.startswith("_") or attr.startswith("__"):
                continue
            recv = terminal_name(node.value)
            if attr in ("_shard", "_shards") or attr.startswith("_shard_"):
                findings.append(
                    Finding(
                        rule=RULE,
                        path=sf.path,
                        line=node.lineno,
                        message=(
                            f"shard-internal access '{recv}.{attr}' "
                            "outside kwok_tpu/cluster/ — shard placement "
                            "is a cluster/ implementation detail; use "
                            "the duck-typed store surface (shard_lane/"
                            "shard_for/shard_topology are the public "
                            "seams)"
                        ),
                    )
                )
                continue
            if not _storeish(recv):
                continue
            findings.append(
                Finding(
                    rule=RULE,
                    path=sf.path,
                    line=node.lineno,
                    message=(
                        f"private store attribute access '{recv}.{attr}' "
                        "outside kwok_tpu/cluster/ — use the "
                        "ClusterClient-compatible surface (CLAUDE.md: "
                        "anything taking a store must keep working over "
                        "the REST client)"
                    ),
                )
            )
    return findings
