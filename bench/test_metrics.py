"""Readers of per-layer metrics on hand-made spans and counters (CPU)."""

from bench.cell import load_reader


def _launch(start, rows, filled):
    return {"name": "launch", "start": start, "seconds": 1.0,
            "args": {"group": 0, "rows": rows, "filled": filled}}


def test_launch_fill_leaves_out_launches_after_the_cut():
    ctx = {"window": (10.0, 40.0), "cut": 30.0,
           "spans": [_launch(9.0, 8, 1), _launch(12.0, 8, 8),
                     _launch(20.0, 8, 6), _launch(31.0, 8, 2)]}
    assert load_reader("launch_fill")(ctx) == 100.0 * 14 / 16


def test_launch_fill_is_silent_without_launches():
    assert load_reader("launch_fill")({"window": (0, 1), "cut": 1,
                                       "spans": []}) is None


def test_idle_share_metrics_share_one_reader():
    ctx = {"trace": {"idle_share": 0.25}}
    assert load_reader("idle_share.read")(ctx) == 25.0
    assert load_reader("idle_share.whatif")(ctx) == 25.0
    assert load_reader("idle_share.read")({"trace": None}) is None
