"""Filter-predicate maintenance + drained-index robustness (ISSUE 6).

Satellite 1 — a truthiness flip of a Filter predicate attribute changes
only the flipped vertices' *own* membership in any composite window
(k-hop/topological expansion exists only at the leaves, below every
Filter), so the maintenance path may rebuild just the blocks containing
flipped vertices (``DBIndex.owners_of_members`` + a reverse-reachability
sweep for gains) instead of the whole index.  These tests differentially
pin the bounded path against a from-scratch rebuild and the
set-evaluation oracle, and assert the bounded path actually runs.

Satellite 3 — delete-everything streams: ``garbage_block_fraction`` and
pass-1 compaction must tolerate empty and zero-block indices (no division
by zero, no spurious reorganize), across compaction configs.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.query import brute_force  # noqa: E402
from repro.core.streaming import StalenessPolicy, StreamingEngine  # noqa: E402
from repro.core.updates import UpdateBatch  # noqa: E402
from repro.core.windows import (  # noqa: E402
    Diff,
    Filter,
    Intersect,
    KHop,
    KHopWindow,
    Union,
)
from repro.graphs.generators import erdos_renyi  # noqa: E402


def masked_graph(n=300, deg=2.0, seed=3, attrs=("mask",)):
    g = erdos_renyi(n, deg, directed=False, seed=seed)
    rng = np.random.default_rng(seed + 1)
    g = g.with_attr("val", rng.integers(0, 50, n).astype(np.float64))
    for a in attrs:
        g = g.with_attr(a, (rng.random(n) < 0.7).astype(np.float64))
    return g


def flip_batch(g, rng, attr, n_loss, n_gain):
    """Attr-edit batch flipping truthiness: n_loss truthy->0, n_gain 0->1
    (clipped to availability)."""
    vals = np.asarray(g.attrs[attr])
    on, off = np.flatnonzero(vals != 0), np.flatnonzero(vals == 0)
    loss = rng.choice(on, min(n_loss, on.size), replace=False)
    gain = rng.choice(off, min(n_gain, off.size), replace=False)
    verts = np.concatenate([loss, gain])
    new = np.concatenate([np.zeros(loss.size), np.ones(gain.size)])
    return UpdateBatch.attr_set(attr, verts.astype(np.int64), new)


EXPRS = [
    pytest.param(Filter(KHopWindow(2), "mask"), ("mask",), id="filter-khop2"),
    pytest.param(Union(Filter(KHop(1), "mask"), KHopWindow(1)), ("mask",),
                 id="union-filter"),
    pytest.param(Diff(KHopWindow(2), Filter(KHopWindow(1), "mask")),
                 ("mask",), id="diff-filter"),
    pytest.param(
        Intersect(Filter(KHopWindow(2), "mask"),
                  Union(KHopWindow(1), Filter(KHop(1, "in"), "mask2"))),
        ("mask", "mask2"), id="intersect-two-attrs"),
]

FLIP_MIXES = [("loss-only", 3, 0), ("gain-only", 0, 3), ("mixed", 2, 2)]


@pytest.mark.parametrize("expr,attrs", EXPRS)
@pytest.mark.parametrize("mix,n_loss,n_gain",
                         FLIP_MIXES, ids=[m[0] for m in FLIP_MIXES])
def test_bounded_refilter_differential(expr, attrs, mix, n_loss, n_gain):
    """Bounded predicate-flip maintenance is bit-identical to both a full
    rebuild and the set-evaluation oracle, for every flip direction."""
    g = masked_graph(attrs=attrs)
    eng = StreamingEngine(g, expr, device=True, use_pallas=False)
    rng = np.random.default_rng(11)
    bounded = 0
    for step in range(6):
        attr = attrs[step % len(attrs)]
        b = flip_batch(eng.graph, rng, attr, n_loss, n_gain)
        report = eng.apply(b)
        assert report["batch_size"] == 0
        if report["refiltered"]:
            bounded += 1
            assert report["affected"] <= eng.graph.n // 2
        fresh = StreamingEngine(eng.graph, expr, device=True,
                                use_pallas=False)
        vals = np.asarray(eng.graph.attrs["val"], np.float64)
        for agg in ("sum", "count", "min"):
            got = np.asarray(eng.query(agg))
            assert np.array_equal(got, np.asarray(fresh.query(agg))), \
                f"{mix} step {step}: bounded refilter != full rebuild ({agg})"
            assert np.array_equal(
                got, brute_force(eng.graph, expr, vals, agg,
                                 dtype=np.float32)), \
                f"{mix} step {step}: engine != oracle ({agg})"
    assert bounded >= 1, \
        "bounded refilter never ran — the test is exercising only rebuilds"


def test_loss_only_flip_uses_reverse_map_bound():
    """Loss-only flips on a Diff-free expression: the changed owners are
    exactly the flipped vertices' block owners (monotone shrink), so the
    affected count reported must not exceed that bound."""
    g = masked_graph(seed=5)
    expr = Filter(KHopWindow(2), "mask")
    eng = StreamingEngine(g, expr, device=True, use_pallas=False)
    rng = np.random.default_rng(13)
    for _ in range(4):
        vals = np.asarray(eng.graph.attrs["mask"])
        on = np.flatnonzero(vals != 0)
        flipped = rng.choice(on, 2, replace=False)
        bound = eng.index.owners_of_members(flipped.astype(np.int64))
        report = eng.apply(UpdateBatch.attr_set(
            "mask", flipped.astype(np.int64), np.zeros(2)))
        if report["refiltered"]:
            assert report["affected"] <= bound.size
            assert np.isin(report["affected_owners"], bound).all()
        v = np.asarray(eng.graph.attrs["val"], np.float64)
        assert np.array_equal(
            np.asarray(eng.query("sum")),
            brute_force(eng.graph, expr, v, "sum", dtype=np.float32))


def test_noop_truthiness_edit_skips_maintenance():
    """Editing a predicate attr without changing truthiness (3.0 -> 7.0)
    must not rebuild or refilter anything."""
    g = masked_graph(seed=7)
    expr = Filter(KHopWindow(2), "mask")
    eng = StreamingEngine(g, expr, device=True, use_pallas=False)
    on = np.flatnonzero(np.asarray(g.attrs["mask"]) != 0)[:4]
    pv = eng.plan_version
    report = eng.apply(UpdateBatch.attr_set("mask", on.astype(np.int64),
                                            np.full(4, 7.0)))
    assert report["affected"] == 0
    assert not report["reorganized"] and not report["refiltered"]
    assert eng.plan_version == pv
    v = np.asarray(eng.graph.attrs["val"], np.float64)
    assert np.array_equal(
        np.asarray(eng.query("sum")),
        brute_force(eng.graph, expr, v, "sum", dtype=np.float32))


# ---------------------------------------------------------------------- #
#  Delete-everything streams (drained / zero-block indices)
# ---------------------------------------------------------------------- #
def _delete_all_in_batches(eng, per_batch=13):
    """Drain every edge of the engine's graph, checking after each batch."""
    expr, steps = eng.window, 0
    while eng.graph.n_edges > 0:
        src, dst = eng.graph.src[:per_batch], eng.graph.dst[:per_batch]
        eng.apply(UpdateBatch.deletes(src, dst))
        steps += 1
        v = np.asarray(eng.graph.attrs["val"], np.float64)
        for agg in ("sum", "count"):
            assert np.array_equal(
                np.asarray(eng.query(agg)),
                brute_force(eng.graph, expr, v, agg, dtype=np.float32)), \
                f"drain step {steps} ({agg})"
        assert steps < 1000
    return steps


DRAIN_CONFIGS = [
    pytest.param({}, id="default"),
    pytest.param({"compact_garbage": 0.0}, id="compact-every-patch"),
    pytest.param({"policy": StalenessPolicy(max_link_ratio=1.05,
                                            max_block_ratio=1.05,
                                            max_garbage_ratio=0.05)},
                 id="aggressive-policy"),
]


@pytest.mark.parametrize("kw", DRAIN_CONFIGS)
def test_delete_everything_stream(kw):
    g = masked_graph(n=120, deg=2.5, seed=9)
    eng = StreamingEngine(g, KHopWindow(2), device=True, use_pallas=False,
                          **kw)
    _delete_all_in_batches(eng)
    assert eng.graph.n_edges == 0
    # drained index: staleness must be well-defined, never reorganizing
    assert eng.index.garbage_block_fraction() >= 0.0
    assert not eng.policy.should_reorganize(
        eng.index, eng._base_links, eng._base_blocks, 5) \
        or eng.index.num_blocks > 0
    # and it keeps accepting traffic: re-insert and stay oracle-correct
    eng.apply(UpdateBatch.inserts([0, 1, 2], [1, 2, 3]))
    v = np.asarray(eng.graph.attrs["val"], np.float64)
    assert np.array_equal(
        np.asarray(eng.query("sum")),
        brute_force(eng.graph, KHopWindow(2), v, "sum", dtype=np.float32))


def test_zero_block_filter_index_is_safe():
    """An all-false predicate can yield an index with no blocks at all:
    staleness, patching, and queries must all survive it."""
    g = masked_graph(n=60, deg=2.0, seed=15)
    g = g.with_attr("mask", np.zeros(g.n))
    expr = Filter(KHopWindow(1), "mask")
    eng = StreamingEngine(g, expr, device=True, use_pallas=False)
    v = np.asarray(g.attrs["val"], np.float64)
    assert np.array_equal(
        np.asarray(eng.query("sum")),
        brute_force(g, expr, v, "sum", dtype=np.float32))
    assert eng.index.garbage_block_fraction() == 0.0
    assert not StalenessPolicy().should_reorganize(eng.index, 0, 0, 5)
    # flip some vertices on: gains on a drained index must still work
    rng = np.random.default_rng(16)
    on = rng.choice(g.n, 5, replace=False)
    eng.apply(UpdateBatch.attr_set("mask", on.astype(np.int64), np.ones(5)))
    v = np.asarray(eng.graph.attrs["val"], np.float64)
    for agg in ("sum", "count"):
        assert np.array_equal(
            np.asarray(eng.query(agg)),
            brute_force(eng.graph, expr, v, agg, dtype=np.float32))
