"""Helpers shared by the readers."""


def in_window(ctx, name, **match):
    """Seconds of the program's ``name`` spans that start inside the
    measured window and whose arguments include ``match``."""
    lo, hi = ctx["window"]
    return [s["seconds"] for s in ctx["spans"]
            if s["name"] == name and lo <= s["start"] <= hi
            and all(s["args"].get(k) == v for k, v in match.items())]


def mean_ms(values):
    return 1e3 * sum(values) / len(values) if values else None


def channels(aggregates):
    """``(value, ones, minmax)``: whether the fused plan carries the sum of
    values, the count, and how many min/max channels it carries."""
    value = any(a in ("sum", "avg") for a in aggregates)
    ones = any(a in ("count", "avg") for a in aggregates)
    return value, ones, sum(a in ("min", "max") for a in aggregates)


def delta(ctx, key):
    return ctx["stats1"][key] - ctx["stats0"][key]
