"""Roofline share of the Pallas segment-sum kernel in the 2-pass (DBIndex)
executor, in %: the least time of the work its events did, over their
summed device time.

Each row of a launch runs the kernel twice: pass 1 sums member values into
blocks, pass 2 sums block partials (the sum channels) into owners.  The
least work of each comes from the served index's sizes
(``roofline.segment_sum_work``), so padding and the one-hot matmul count
as time, not as work.
"""
from bench import roofline
from bench.metrics._spans import channels

KERNEL = "segment_sum"


def read(ctx):
    t, c = ctx["trace"], ctx["counts"]
    if t is None or c["kind"] != "dbindex":
        return None
    events = [v for v in t["ops"].values() if KERNEL in v["text"]]
    count = sum(v["count"] for v in events)
    seconds = sum(v["seconds"] for v in events)
    if not count or count % 2 or seconds <= 0:
        return None
    value, ones, _ = channels(ctx["config"]["aggregates"])
    kind = ctx["device_kind"]
    p1 = roofline.least_time_s(
        *roofline.segment_sum_work(c["members"], int(value), c["blocks"]),
        kind)
    p2 = roofline.least_time_s(
        *roofline.segment_sum_work(c["links"], int(value) + int(ones),
                                   c["n"]), kind)
    return 100.0 * (count // 2) * (p1 + p2) / seconds
