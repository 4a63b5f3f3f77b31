"""The benchmark's plain reference against the program's set-evaluation
oracle, on tiny graphs (CPU)."""

import numpy as np
import pytest

from bench import graphs, oracle

AGGS = oracle.AGGREGATES


def _program_graph(n, src, dst, directed, values):
    from repro.core.graph import Graph

    return Graph(n=n, src=src, dst=dst, directed=directed).with_attr(
        "val", values)


@pytest.mark.parametrize("directed", [False, True])
@pytest.mark.parametrize("k", [1, 2])
def test_khop_reference_matches_brute_force(directed, k):
    from repro.core.query import brute_force
    from repro.core.windows import KHopWindow

    rng = np.random.default_rng(7)
    n = 128
    src, dst = graphs.rmat(rng, n, 5.0, (0.57, 0.19, 0.19, 0.05), directed)
    values = rng.integers(0, 100, n).astype(np.float64)
    g = _program_graph(n, src, dst, directed, values)
    ref = oracle.RefGraph(n, src, dst, directed)
    indptr, members = oracle.khop_windows(ref, k)
    for agg in AGGS:
        want = brute_force(g, KHopWindow(k), values, agg, dtype=np.float32)
        got = oracle.reduce(values, indptr, members, agg)
        np.testing.assert_array_equal(got, want)


def test_topological_reference_matches_brute_force():
    from repro.core.query import brute_force
    from repro.core.windows import TopologicalWindow

    rng = np.random.default_rng(8)
    n = 200
    src, dst = graphs.price_dag(rng, n, 3.0)
    values = rng.integers(0, 100, n).astype(np.float64)
    g = _program_graph(n, src, dst, True, values)
    ref = oracle.RefGraph(n, src, dst, True)
    verts = np.arange(n)
    indptr, members = oracle.windows(ref, {"kind": "topological"}, verts)
    for agg in AGGS:
        want = brute_force(g, TopologicalWindow(), values, agg,
                           dtype=np.float32)
        np.testing.assert_array_equal(
            oracle.reduce(values, indptr, members, agg), want)


def test_bfloat16_reduce_departs_from_float32():
    """The control: the same reduce in bfloat16 gets sums, counts and means
    of a few hundred values wrong, so it cannot pass the exact check."""
    rng = np.random.default_rng(9)
    values = rng.integers(0, 100, 1000).astype(np.float64)
    indptr = np.array([0, 301, 700, 1000])  # 301 and 399 are not bf16
    members = rng.permutation(1000)
    for agg, differs in [("sum", True), ("avg", True), ("count", True),
                         ("min", False), ("max", False)]:
        f32 = oracle.reduce(values, indptr, members, agg, np.float32)
        b16 = oracle.reduce(values, indptr, members, agg,
                            oracle.DTYPES["bfloat16"])
        assert (f32 != b16).any() == differs, agg


def test_reduce_refuses_an_empty_window():
    with pytest.raises(ValueError):
        oracle.reduce(np.ones(3), np.array([0, 0, 3]), np.arange(3), "sum")
