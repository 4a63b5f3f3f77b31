"""Mean time of a whole-group refresh (``query.group`` span of an
unbatched read: the fused executor and the host finalize), in ms."""
from bench.metrics._spans import in_window, mean_ms


def read(ctx):
    lo, hi = ctx["window"]
    return mean_ms([s["seconds"] for s in ctx["spans"]
                    if s["name"] == "query.group" and lo <= s["start"] <= hi
                    and not s["args"].get("batched")])
