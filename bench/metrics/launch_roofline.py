"""Roofline share of a batched explicit-values launch, in %: the least
time for the bytes a launch cannot avoid (the index arrays once, one value
per window member per row, every output), from the served index's sizes,
over the device's busy time per launch in the traced window."""
from bench import roofline
from bench.metrics._spans import channels, delta


def read(ctx):
    t, c = ctx["trace"], ctx["counts"]
    launches = delta(ctx, "batched_launches")
    if t is None or not launches or t["busy_s"] <= 0:
        return None
    value, ones, minmax = channels(ctx["config"]["aggregates"])
    nbytes = roofline.launch_bytes(c["index_words"], c["members"], c["n"],
                                   ctx["bucket"], value + ones + minmax)
    least = roofline.least_time_s(0, nbytes, ctx["device_kind"])
    return 100.0 * least * launches / t["busy_s"]
