"""The readers of the refreshes and writes on hand-made spans (CPU):
``refresh_roofline`` counts a refresh by the share of it inside the
window, whose kernel time the trace clips; ``invalidated_share`` averages
the owners a write invalidated over the vertices."""

import pytest

from bench import roofline
from bench.cell import load_reader
from bench.metrics import refresh_roofline

COUNTS = {"kind": "dbindex", "n": 1000, "members": 50_000, "blocks": 700,
          "links": 9_000}
KIND = "TPU v5 lite"


def _refresh(start, seconds, **args):
    return {"name": "query.group", "start": start, "seconds": seconds,
            "args": {"group": 0, **args}}


def _ctx(spans, kernel_s=2.0):
    ops = {"%segment_sum_tiled.3 custom-call": {"seconds": kernel_s / 2},
           "%segment_minmax_tiled.4 custom-call": {"seconds": kernel_s / 2},
           "%fusion.2": {"seconds": 5.0}}
    return {"trace": {"ops": ops}, "counts": COUNTS, "window": (10.0, 40.0),
            "spans": spans, "device_kind": KIND,
            "config": {"aggregates": ["sum", "count", "avg", "min", "max"]}}


def _least():
    work = [roofline.segment_sum_work(50_000, 1, 700),
            roofline.segment_sum_work(9_000, 2, 1000),
            refresh_roofline.segment_minmax_work(50_000, 2, 700),
            refresh_roofline.segment_minmax_work(9_000, 2, 1000)]
    return sum(roofline.least_time_s(f, b, KIND) for f, b in work)


def test_refresh_roofline_counts_the_share_inside_the_window():
    spans = [_refresh(8.0, 1.0),  # before the window: no kernel time in it
             _refresh(12.0, 2.8),  # inside
             _refresh(39.0, 4.0),  # a quarter of it inside
             _refresh(20.0, 1.0, batched=True)]  # a launch, not a refresh
    got = load_reader("refresh_roofline")(_ctx(spans))
    assert got == pytest.approx(100.0 * 1.25 * _least() / 2.0)


def test_refresh_roofline_is_silent_without_refreshes_or_trace():
    read = load_reader("refresh_roofline")
    assert read(_ctx([_refresh(1.0, 2.0)])) is None
    assert read(dict(_ctx([_refresh(12.0, 2.0)]), trace=None)) is None


@pytest.mark.parametrize("span,share", [
    ((12.0, 2.0), 1.0), ((9.0, 2.0), 0.5), ((39.0, 2.0), 0.5),
    ((5.0, 40.0), 0.75), ((41.0, 1.0), 0.0), ((20.0, 0.0), 1.0)])
def test_share_inside(span, share):
    start, seconds = span
    got = refresh_roofline.share_inside(
        {"start": start, "seconds": seconds}, 10.0, 40.0)
    assert got == pytest.approx(share)


def test_invalidated_share_averages_owners_over_vertices():
    def update(start, **args):
        return {"name": "service.update", "start": start, "seconds": 0.1,
                "args": args}

    ctx = {"window": (10.0, 40.0), "config": {"graph": {"n": 1000}},
           "spans": [update(5.0, owners=999), update(12.0, owners=100),
                     update(30.0, owners=300), update(31.0)]}
    read = load_reader("invalidated_share")
    assert read(ctx) == pytest.approx(20.0)
    assert read(dict(ctx, spans=ctx["spans"][-1:])) is None
