"""Median time a request waited in the service's queue before a flush
took it (``queued_ms`` of ``request`` spans begun between the window's
start and its cut-off), in ms.  One reader for every
``queue_wait_ms.<kind>`` metric: each cell's traffic sends one kind of
request (point reads in ``read-attr``, explicit-values requests in
``whatif``), and the suffix names it.  Silent where the program stamps no
queue wait."""
from statistics import median


def read(ctx):
    lo, cut = ctx["window"][0], ctx["cut"]
    waits = [s["args"]["queued_ms"] for s in ctx["spans"]
             if s["name"] == "request" and lo <= s["start"] <= cut
             and "queued_ms" in s["args"]]
    return median(waits) if waits else None
