"""The comparison that decides ``correct`` fails when the timed path is
broken underneath, and fails for the control (the reference in bfloat16 in
place of the served answers).  Tiny sizes on the CPU; each fault is
planted in the program for the length of one run."""

import numpy as np
import pytest

from bench.test_cells import run_cell


def _write_ignored(monkeypatch):
    """A write that bumps the version but leaves the state unchanged."""
    from repro.core import updates

    monkeypatch.setattr(updates, "apply_batch", lambda g, batch: g)


def _half_batch(monkeypatch):
    """Half of each batched launch left out: its rows repeat the others."""
    from repro.core.api import SessionView

    orig = SessionView.run_group_many

    def run_group_many(self, gi, values_batch):
        out = orig(self, gi, values_batch)
        half = len(values_batch) // 2
        return {a: np.concatenate([v[:len(v) - half], v[:half]])
                for a, v in out.items()}

    monkeypatch.setattr(SessionView, "run_group_many", run_group_many)


def _answer_altered(monkeypatch):
    """Every answer altered where it is produced: point reads as they are
    served, explicit-values rows as the launch returns them."""
    from repro.core.api import SessionView
    from repro.serve.window_service import WindowService

    serve, many = WindowService._serve_snapshot, SessionView.run_group_many

    def serve_snapshot(self, *a, **k):
        value, hit = serve(self, *a, **k)
        return value + np.float32(1), hit

    def run_group_many(self, gi, values_batch):
        return {a: v + np.float32(1)
                for a, v in many(self, gi, values_batch).items()}

    monkeypatch.setattr(WindowService, "_serve_snapshot", serve_snapshot)
    monkeypatch.setattr(SessionView, "run_group_many", run_group_many)


FAULTS = [
    ("khop2-lj.read-attr", _write_ignored),
    ("khop2-lj.whatif", _half_batch),
    ("topo-cit.whatif", _half_batch),
    ("khop2-lj.read-attr", _answer_altered),
    ("khop2-lj.whatif", _answer_altered),
    ("topo-cit.whatif", _answer_altered),
]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__.strip('_')}"
                              for c, f in FAULTS])
def test_planted_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    res = run_cell(cell, seconds=2.5)
    assert res["correct"] is False
    assert res["checks"]["mismatched"]["value"] > 0


@pytest.mark.parametrize("cell", ["khop2-lj.whatif", "khop2-lj.read-attr",
                                  "topo-cit.whatif"])
def test_bfloat16_control_is_not_correct(cell):
    res = run_cell(cell, control="bfloat16")
    assert res["correct"] is False
    assert res["checks"]["mismatched"]["value"] > 0
