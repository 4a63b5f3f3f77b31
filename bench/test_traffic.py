"""The load generator gives every seed the same work in another order
(CPU)."""

import json
from pathlib import Path

import numpy as np

from bench.traffic import Traffic

ROOT = Path(__file__).resolve().parent.parent
CONFIG = json.loads((ROOT / "bench/configs/khop2-lj.json").read_text())
MIX = json.loads((ROOT / "bench/mixes/read-attr.json").read_text())


def test_every_seed_reads_and_writes_the_same_vertices():
    a, b = Traffic(MIX, CONFIG, 7), Traffic(MIX, CONFIG, 3_000_000_011)
    (_, va), (_, vb) = a.window_reads(500), b.window_reads(500)
    assert not (va == vb).all()  # another order
    np.testing.assert_array_equal(np.sort(va), np.sort(vb))
    assert [a.write(j)[1] for j in range(20)] == \
        [b.write(j)[1] for j in range(20)]


def test_every_seed_has_the_same_schedule_in_another_order():
    a, b = Traffic(MIX, CONFIG, 7), Traffic(MIX, CONFIG, 3_000_000_011)
    (da, ka), (db, kb) = a.open_schedule(30.0), b.open_schedule(30.0)
    assert da.size == db.size and (np.bincount(ka) == np.bincount(kb)).all()
    assert not (ka == kb).all()
    assert 0 == da[0] == db[0] and max(da[-1], db[-1]) < 30.0
