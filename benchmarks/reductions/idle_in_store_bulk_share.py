"""``idle_in_store_bulk_share``: per cent of the device's idle time, between
the first and the last span of the Pod player in the traced interval, that
lies under a ``kwok/Pod/store_bulk`` span of the kwok daemon (the Pod player
waiting for the apiserver to answer a bulk of status patches).  Nothing to
read without a device plane or without spans of the Pod player."""

from . import kwok_spans


def reduce(trace, ctx):
    return kwok_spans.idle_share(trace, "Pod", "store_bulk")
