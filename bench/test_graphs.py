"""The configurations' graphs: one fixed draw each, with the skew their
sources have (CPU, small sizes)."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import graphs

ROOT = Path(__file__).resolve().parent.parent
CONFIGS = [json.loads((ROOT / c["file"]).read_text())
           for c in json.loads((ROOT / "BENCHMARK.json").read_text())
           ["configs"]]


@pytest.mark.parametrize("cfg", CONFIGS, ids=[c["name"] for c in CONFIGS])
def test_every_run_serves_the_same_graph(cfg):
    spec = dict(cfg["graph"], n=1024)
    a, b = graphs.make_graph(spec), graphs.make_graph(spec)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


def test_rmat_has_the_mean_degree_and_hubs():
    n = 4096
    src, dst = graphs.rmat(np.random.default_rng(0), n, 17.35,
                           (0.57, 0.19, 0.19, 0.05))
    assert 2 * src.size == round(n * 17.35)
    assert (src != dst).all()
    key = np.minimum(src, dst).astype(np.int64) * n + np.maximum(src, dst)
    assert np.unique(key).size == src.size  # no edge twice, either way
    deg = np.bincount(np.concatenate([src, dst]), minlength=n)
    assert deg.max() > 20 * deg.mean()  # hubs


def test_rmat_refuses_other_sizes():
    with pytest.raises(ValueError):
        graphs.rmat(np.random.default_rng(0), 1000, 4.0, (0.57, 0.19, 0.19,
                                                          0.05))


def test_price_dag_is_acyclic_with_cumulative_advantage():
    n = 5000
    src, dst = graphs.price_dag(np.random.default_rng(0), n, 4.38)
    assert abs(src.size / n - 4.38) < 0.1
    # Kahn's algorithm removes every paper: no cycle
    indeg = np.bincount(dst, minlength=n)
    order = np.argsort(src, kind="stable")
    starts = np.searchsorted(src[order], np.arange(n + 1))
    ready = list(np.flatnonzero(indeg == 0))
    seen = 0
    while ready:
        u = ready.pop()
        seen += 1
        for v in dst[order][starts[u]:starts[u + 1]]:
            indeg[v] -= 1
            if indeg[v] == 0:
                ready.append(v)
    assert seen == n
    cited = np.bincount(dst, minlength=n)
    assert cited.max() > 20 * cited.mean()  # the most cited papers
