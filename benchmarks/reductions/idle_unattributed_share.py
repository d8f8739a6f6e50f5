"""``idle_unattributed_share``: per cent of the device's idle time, between
the first and the last span of the Pod player in the traced interval, that
lies under no ``kwok/Pod/*`` span at all.  The Pod player's tick thread runs
every stage of its loop inside one, so this reads near 0 while the
instrumentation is whole and grows when work is added to the loop outside a
span.  Nothing to read without a device plane or without spans of the Pod
player."""

from . import kwok_spans


def reduce(trace, ctx):
    under = kwok_spans.idle_share(trace, "Pod")
    return None if under is None else 100.0 - under
