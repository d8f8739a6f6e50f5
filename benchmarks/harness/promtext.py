"""Prometheus text format: the parser (copied from
``kwok_tpu/utils/promtext.py`` so the yardstick does not move with the
program) and the delta readers the ``prom_delta`` per-layer metrics use."""

from __future__ import annotations

import re
from typing import Dict, Iterator, List, Optional, Tuple

_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
_ESCAPES = {"\\": "\\", '"': '"', "n": "\n"}

Sample = Tuple[str, Dict[str, str], float]


def _unescape(value: str) -> str:
    if "\\" not in value:
        return value
    out = []
    i = 0
    while i < len(value):
        c = value[i]
        if c == "\\" and i + 1 < len(value):
            out.append(_ESCAPES.get(value[i + 1], "\\" + value[i + 1]))
            i += 2
        else:
            out.append(c)
            i += 1
    return "".join(out)


def iter_samples(text: str) -> Iterator[Sample]:
    """Yield (metric_name, labels, value) for each sample line."""
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        series, _, val = line.rpartition(" ")
        if not series:
            continue
        try:
            fval = float(val)
        except ValueError:
            continue
        labels: Dict[str, str] = {}
        name = series
        if "{" in series:
            name, _, lbl = series.partition("{")
            labels = {k: _unescape(v) for k, v in _LABEL_RE.findall(lbl)}
        yield name.strip(), labels, fval


def total(samples: List[Sample], name: str, labels: Dict[str, str]) -> Optional[float]:
    """Sum of the series of ``name`` that carry ``labels``; None if none
    does (a series that does not exist is not a 0)."""
    vals = [v for n, ls, v in samples if n == name and labels.items() <= ls.items()]
    return sum(vals) if vals else None


def delta(before: List[Sample], after: List[Sample], name: str,
          labels: Dict[str, str]) -> Optional[float]:
    """Growth of a counter over the window.  A series that appeared
    inside the window started at 0."""
    b = total(after, name, labels)
    if b is None:
        return None
    return b - (total(before, name, labels) or 0.0)


def read(spec: dict, before: dict, after: dict) -> Optional[float]:
    """One ``prom_delta`` per-layer metric from two scrapes
    (``Cluster.scrape``).  ``spec`` is the metric file's ``reader``:

    - ``sum_over_count``: Δ``<series>_sum`` / Δ``<series>_count``
    - ``sum_over_window``: Δ``<series>_sum`` (or Δ``<series>`` for a plain
      counter) / seconds between the scrapes
    - ``count_delta``: Δ``<series>``
    - ``sum_over_other_count``: Δ``<series>_sum`` summed over
      ``label_sets`` / Δ``other.series``
    - ``gauge_at_end``: the series' value in the second scrape

    times ``scale`` (unit conversion).  None where the series is absent
    or the divisor is 0: nothing to read."""
    comp = spec["component"]
    b, a = before[comp], after[comp]
    series = spec["series"]
    sets = spec.get("label_sets") or [spec.get("labels") or {}]
    how = spec["how"]
    scale = float(spec.get("scale", 1.0))

    def summed(suffix: str) -> Optional[float]:
        vals = [delta(b, a, series + suffix, ls) for ls in sets]
        vals = [v for v in vals if v is not None]
        return sum(vals) if vals else None

    if how == "count_delta":
        num, den = summed(""), 1.0
    elif how == "gauge_at_end":
        num, den = total(a, series, sets[0]), 1.0
    elif how == "sum_over_count":
        num, den = summed("_sum"), summed("_count")
    elif how == "sum_over_window":
        num = summed("_sum")
        if num is None:
            num = summed("")
        den = after["t"] - before["t"]
    elif how == "sum_over_other_count":
        other = spec["other"]
        num = summed("_sum")
        den = delta(b, a, other["series"], other.get("labels") or {})
    else:
        raise ValueError(f"unknown prom_delta reduction {how!r}")
    if num is None or not den:
        return None
    return num / den * scale
