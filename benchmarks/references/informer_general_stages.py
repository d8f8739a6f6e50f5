"""Plain reference of the deployment ``informers-1k-100k``: upstream's
realistic pod lifecycle (``general_stages.py``, imported, not copied) seen by
a population of client-go-shaped informers.  Nothing of the program is
imported.

An informer (kubernetes/client-go ``tools/cache/reflector.go``,
``ListAndWatch``) keeps a store of the objects its selector selects: a LIST
replaces the store and gives the resourceVersion the WATCH starts from;
``ADDED`` and ``MODIFIED`` put the event's object under its key, ``DELETED``
takes the key out, ``BOOKMARK`` moves the resume point alone; told ``410
Gone`` it lists again.  What it holds of an object here is its
``metadata.resourceVersion``, which names one state of one object: two
stores agree where they hold the same keys at the same resourceVersions.

The configuration's guarantees, as this module judges them:

- g1, the pages of one LIST are one snapshot: ``Cache.replace`` is handed
  every page and notes pages that carry another resourceVersion than the
  first and keys that came twice.
- g2, what a stream delivers: ``Cache.apply`` notes an event whose
  resourceVersion is not above the last one's (or the LIST's) and an object
  the selector does not select.
- g3, after the traffic has settled the store equals a final LIST through
  the same selector: ``disagreements``.

``harness/check.py`` asks a reference two things, ``pod_mismatch`` and
``duplicate_ips``.  The generator's ``settle`` hands what every informer
ended with to ``informers`` below, which keeps the findings by pod;
``pod_mismatch`` then refuses a window pod the stage set got right where an
informer that selects it got it wrong, so such a pod counts under
``status_mismatch``.  ``informers`` returns the findings that name no pod of
the window: the caller has to refuse the run for them, since no number of
the comparison would carry them."""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

from .general_stages import duplicate_ips, pod_mismatch as _stage_mismatch  # noqa: F401

#: a finding: (pod name, or "" where it names none; what is wrong)
Finding = Tuple[str, str]


def _meta(obj: dict) -> dict:
    return obj.get("metadata") or {}


class Cache:
    """One informer's store, key -> resourceVersion, and what it saw that
    the guarantees forbid.  ``selector`` is one ``(label, value)`` equality
    or None for every object."""

    def __init__(self, selector: Optional[Tuple[str, str]] = None):
        self.selector = selector
        self.items: Dict[str, int] = {}
        #: where a WATCH resumes: the LIST's, then the last event's or bookmark's
        self.rv = 0
        #: the last LIST's or delivered event's, which the next event has to pass
        self._last = 0
        self.findings: List[Finding] = []
        self.events = 0

    def selects(self, obj: dict) -> bool:
        if self.selector is None:
            return True
        label, value = self.selector
        return (_meta(obj).get("labels") or {}).get(label) == value

    def replace(self, pages: Iterable[dict]) -> None:
        """A LIST, page by page as it was served."""
        items: Dict[str, int] = {}
        first = None
        for page in pages:
            rv = int(_meta(page).get("resourceVersion") or 0)
            if first is None:
                first = rv
            elif rv != first:
                self.findings.append(("", f"a page carries resourceVersion {rv}, "
                                          f"the LIST's first page {first}"))
            for obj in page.get("items") or []:
                name = _meta(obj)["name"]
                if name in items:
                    self.findings.append((name, "listed twice in one LIST"))
                if not self.selects(obj):
                    self.findings.append((name, "listed though the selector does not select it"))
                items[name] = int(_meta(obj)["resourceVersion"])
        self.items = items
        self.rv = self._last = first or 0

    def apply(self, etype: str, obj: dict) -> None:
        """One frame of the WATCH."""
        rv = int(_meta(obj).get("resourceVersion") or 0)
        if etype == "BOOKMARK":
            self.rv = max(self.rv, rv)
            return
        self.events += 1
        name = _meta(obj)["name"]
        if rv <= self._last:
            self.findings.append((name, f"{etype} at resourceVersion {rv} after {self._last}"))
        if not self.selects(obj):
            self.findings.append((name, f"{etype} delivered though the selector does not "
                                        "select it"))
        if etype == "DELETED":
            self.items.pop(name, None)
        else:
            self.items[name] = rv
        self.rv = self._last = max(self._last, rv)


def disagreements(cache: Dict[str, int], final: Dict[str, int]) -> List[Finding]:
    """g3: where an informer's store differs from a final LIST through its
    selector."""
    out: List[Finding] = []
    for name, rv in final.items():
        if name not in cache:
            out.append((name, "absent from the informer's store, in the final LIST"))
        elif cache[name] != rv:
            out.append((name, f"at resourceVersion {cache[name]} in the informer's store, "
                              f"{rv} in the final LIST"))
    out.extend((name, "still in the informer's store, gone from the final LIST")
               for name in cache if name not in final)
    return out


#: window pod -> the first finding about it, of the run ``informers`` was last handed
by_pod: Dict[str, str] = {}


def informers(reports: List[dict], window: Iterable[str]) -> List[str]:
    """What every informer ended with: ``{"name", "cache", "final",
    "findings"}``, a store, the final LIST through the same selector (both
    key -> resourceVersion) and the ``Cache.findings`` of its whole life.
    Keeps the first finding about each pod for ``pod_mismatch`` and returns,
    in words, those about no pod of ``window``."""
    by_pod.clear()
    window = set(window)
    loose: List[str] = []
    for r in reports:
        found = [tuple(f) for f in r["findings"]] + disagreements(r["cache"], r["final"])
        for name, what in found:
            text = f"informer {r['name']}: {what}"
            if name in window:
                by_pod.setdefault(name, text)
            else:
                loose.append(f"{name or 'no pod'}: {text}")
    return loose


def pod_mismatch(pod_sent: dict, status: Optional[dict], node_ip: str) -> Optional[str]:
    """None when the stage set gives ``pod_sent`` this status and every
    informer that selects the pod agrees with the final LIST about it."""
    why = _stage_mismatch(pod_sent, status, node_ip)
    if why is not None:
        return why
    return by_pod.get(_meta(pod_sent).get("name"))
