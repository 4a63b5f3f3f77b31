"""Roofline share of the segment kernels in whole-group refreshes, in %:
the least time of the segment work of the window's refreshes, over the
summed device time of every ``segment_sum_tiled`` and
``segment_minmax_tiled`` call in the traced window, however many pages
(Pallas calls) a reduce took.

A refresh is an unbatched ``query.group`` span (as ``refresh_ms`` counts
them): one row through both passes of the 2-pass (DBIndex) plan.  The
trace's kernel time is clipped to the window, so a refresh counts by the
share of its span inside the window: one that straddles an edge counts
only the part whose kernels the denominator holds.  Its
least work comes from the served index's sizes: pass 1 over the block
members into the blocks, pass 2 over the owner links into the vertices;
the sum channels by ``roofline.segment_sum_work`` (pass 1 carries the
values, pass 2 the values and the counts), the min and max channels by
:func:`segment_minmax_work`.  Padding rows and the kernels' extra work
count as time, not as work.
"""
from bench import roofline
from bench.metrics._spans import channels

KERNELS = ("segment_sum_tiled", "segment_minmax_tiled")


def segment_minmax_work(rows: int, channels: int, segments: int):
    """``(flops, bytes)`` of a segment min or max of ``rows`` gathered rows
    of ``channels`` values into ``segments`` outputs: one compare a value;
    each value and segment id read once, each output written once."""
    flops = rows * channels
    nbytes = roofline.WORD * (rows * channels + rows + segments * channels)
    return flops, nbytes


def kernel_seconds(ops) -> float:
    """Device seconds of the segment kernels' custom calls (the trace's
    per-operation names, ``%segment_sum_tiled.8 custom-call``)."""
    return sum(v["seconds"] for name, v in ops.items()
               if name.endswith("custom-call")
               and any(k in name for k in KERNELS))


def share_inside(span, lo: float, hi: float) -> float:
    """Share of ``span``'s time inside ``[lo, hi]`` (an instant: 1 or 0)."""
    a, b = span["start"], span["start"] + span["seconds"]
    if b <= a:
        return float(lo <= a <= hi)
    return max(0.0, min(b, hi) - max(a, lo)) / (b - a)


def read(ctx):
    t, c = ctx["trace"], ctx["counts"]
    if t is None or c["kind"] != "dbindex":
        return None
    lo, hi = ctx["window"]
    refreshes = sum(share_inside(s, lo, hi) for s in ctx["spans"]
                    if s["name"] == "query.group"
                    and not s["args"].get("batched"))
    seconds = kernel_seconds(t["ops"])
    if not refreshes or seconds <= 0:
        return None
    value, ones, minmax = channels(ctx["config"]["aggregates"])
    kind = ctx["device_kind"]
    work = [
        roofline.segment_sum_work(c["members"], int(value), c["blocks"]),
        roofline.segment_sum_work(c["links"], int(value) + int(ones), c["n"]),
    ]
    if minmax:
        work += [segment_minmax_work(c["members"], minmax, c["blocks"]),
                 segment_minmax_work(c["links"], minmax, c["n"])]
    least = sum(roofline.least_time_s(f, b, kind) for f, b in work)
    return 100.0 * refreshes * least / seconds
