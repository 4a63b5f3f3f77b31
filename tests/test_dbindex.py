"""DBIndex: exact cover invariants, MC/EMC/mc_paper equality, updates."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline container: use the local shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import updates
from repro.core.dbindex import build_dbindex
from repro.core.query import GraphWindowQuery, brute_force
from repro.core.windows import KHopWindow, TopologicalWindow, khop_window_single
from repro.graphs.generators import erdos_renyi, random_dag, with_random_attrs


@pytest.mark.parametrize("method", ["mc", "emc", "mc_paper"])
@pytest.mark.parametrize("k", [1, 2, 3])
def test_query_matches_bruteforce(small_undirected, method, k):
    g = small_undirected
    w = KHopWindow(k)
    ref = brute_force(g, w, g.attrs["val"], "sum")
    idx = build_dbindex(g, w, method=method)
    assert np.allclose(idx.query(g.attrs["val"], "sum"), ref)


@pytest.mark.parametrize("agg", ["sum", "count", "min", "max", "avg"])
def test_all_aggregates(small_undirected, agg):
    g = small_undirected
    w = KHopWindow(2)
    idx = build_dbindex(g, w, method="emc")
    ref = brute_force(g, w, g.attrs["val"], agg)
    assert np.allclose(idx.query(g.attrs["val"], agg), ref)


def test_directed_windows(small_directed):
    g = small_directed
    w = KHopWindow(2)
    idx = build_dbindex(g, w, method="emc")
    ref = brute_force(g, w, g.attrs["val"], "sum")
    assert np.allclose(idx.query(g.attrs["val"], "sum"), ref)


def test_topological_dbindex(small_dag):
    g = small_dag
    w = TopologicalWindow()
    idx = build_dbindex(g, w, method="mc")
    ref = brute_force(g, w, g.attrs["val"], "sum")
    assert np.allclose(idx.query(g.attrs["val"], "sum"), ref)


def test_cover_invariant(small_undirected):
    """Every window is exactly covered by disjoint linked blocks."""
    g = small_undirected
    idx = build_dbindex(g, KHopWindow(2), method="emc")
    for v in range(0, g.n, 11):
        reconstructed = idx.window_of(v)
        assert np.array_equal(reconstructed, khop_window_single(g, 2, v)), v
        # disjointness: reconstruction has no duplicates
        assert np.unique(reconstructed).size == reconstructed.size


def test_emc_vs_mc_same_results_different_cost(small_undirected):
    g = small_undirected
    w = KHopWindow(3)
    i_mc = build_dbindex(g, w, method="mc_paper")
    i_emc = build_dbindex(g, w, method="emc")
    v = g.attrs["val"]
    assert np.allclose(i_mc.query(v, "sum"), i_emc.query(v, "sum"))


def test_paper_example_dense_blocks(paper_social_graph):
    """The paper's running example (Fig. 1 + §3 windows).

    The text gives W(B)={A,B,D,F} and W(E)={A,C,E} explicitly; with the
    Posts column (A..F = 12,15,28,23,26,14) the 1-hop sums are B=64, E=66.
    The full vector is derived from the adjacency the text implies.
    """
    g = paper_social_graph
    idx = build_dbindex(g, KHopWindow(1), method="mc", num_hashes=1)
    got = idx.query(g.attrs["val"], "sum")
    expect = np.array([81, 64, 103, 80, 66, 80], dtype=np.float64)
    assert np.allclose(got, expect)
    # dense block {A, D, F} (shared by W(B), W(C)) must exist (paper §4)
    found = any(
        set(idx.block(b).tolist()) == {0, 3, 5} for b in range(idx.num_blocks)
    ) or idx.stats["num_dense_blocks"] > 0
    assert found


def test_index_stats_sane(small_undirected):
    idx = build_dbindex(small_undirected, KHopWindow(2), method="emc")
    st_ = idx.stats
    assert st_["num_blocks"] == idx.num_blocks
    assert st_["num_members"] == idx.block_members.size
    assert idx.size_bytes() > 0


def test_update_insert_edge(small_undirected):
    g = small_undirected
    w = KHopWindow(2)
    idx = build_dbindex(g, w, method="emc")
    g2 = updates.insert_edge(g, 7, 123)
    idx2 = updates.update_dbindex(idx, g2, w, 7, 123)
    ref = brute_force(g2, w, g2.attrs["val"], "sum")
    assert np.allclose(idx2.query(g2.attrs["val"], "sum"), ref)


def test_update_delete_edge(small_undirected):
    g = small_undirected
    w = KHopWindow(2)
    idx = build_dbindex(g, w, method="emc")
    s, t = int(g.src[0]), int(g.dst[0])
    g2 = updates.delete_edge(g, s, t)
    idx2 = updates.update_dbindex(idx, g2, w, s, t)
    ref = brute_force(g2, w, g2.attrs["val"], "sum")
    assert np.allclose(idx2.query(g2.attrs["val"], "sum"), ref)


def test_update_then_reorganize(small_undirected):
    g = small_undirected
    w = KHopWindow(1)
    idx = build_dbindex(g, w, method="emc")
    for i in range(5):  # a burst of updates, then phase-2 reorganization
        g = updates.insert_edge(g, i, (i * 37 + 11) % g.n)
        idx = updates.update_dbindex(idx, g, w, i, (i * 37 + 11) % g.n)
    ref = brute_force(g, w, g.attrs["val"], "sum")
    assert np.allclose(idx.query(g.attrs["val"], "sum"), ref)
    reorg = updates.reorganize(g, w)
    assert np.allclose(reorg.query(g.attrs["val"], "sum"), ref)
    # reorganized index is at least as shared (not more links than incremental)
    assert reorg.stats["num_links"] <= idx.stats["num_links"] + g.n


def test_attribute_updates_dont_touch_index(small_undirected):
    """§4.3: attribute changes require no index maintenance."""
    g = small_undirected
    idx = build_dbindex(g, KHopWindow(2), method="emc")
    vals2 = g.attrs["val"] * 3 + 1
    ref = brute_force(g, KHopWindow(2), vals2, "sum")
    assert np.allclose(idx.query(vals2, "sum"), ref)


@settings(max_examples=15, deadline=None)
@given(st.integers(20, 100), st.integers(2, 8), st.integers(0, 99999),
       st.sampled_from(["mc", "emc"]), st.integers(1, 3))
def test_property_dbindex_equals_bruteforce(n, deg, seed, method, k):
    g = with_random_attrs(erdos_renyi(n, float(deg), seed=seed), seed=seed + 1)
    w = KHopWindow(k)
    idx = build_dbindex(g, w, method=method)
    ref = brute_force(g, w, g.attrs["val"], "sum")
    assert np.allclose(idx.query(g.attrs["val"], "sum"), ref)


@settings(max_examples=10, deadline=None)
@given(st.integers(20, 80), st.integers(1, 4), st.integers(0, 99999))
def test_property_topo_dbindex(n, deg, seed):
    g = with_random_attrs(random_dag(n, float(deg), seed=seed), seed=seed + 1)
    w = TopologicalWindow()
    idx = build_dbindex(g, w)
    ref = brute_force(g, w, g.attrs["val"], "sum")
    assert np.allclose(idx.query(g.attrs["val"], "sum"), ref)


# ------------------------ memoized link scan ------------------------- #
def _fresh_garbage(idx):
    linked = np.zeros(idx.num_blocks, dtype=bool)
    linked[idx.link_block] = True
    return 1.0 - int(np.count_nonzero(linked)) / idx.num_blocks


def test_every_new_index_gets_its_own_link_scan(small_undirected):
    """The scan is memoized per index object: an index made by
    ``dataclasses.replace``, ``update_dbindex_batch`` or ``_merge_affected``
    carries none of its parent's, and each answers as a fresh scan."""
    import dataclasses

    from repro.core.dbindex import link_scans
    from repro.core.updates import UpdateBatch, apply_batch, _merge_affected

    g, w = small_undirected, KHopWindow(1)
    idx = build_dbindex(g, w, method="emc")
    before = link_scans()
    assert idx.garbage_block_fraction() == _fresh_garbage(idx) == 0.0
    assert idx.linked_blocks_mask() is idx.linked_blocks_mask()
    assert link_scans() == before + 1

    cut = dataclasses.replace(idx, link_block=idx.link_block[:1])
    assert cut.garbage_block_fraction() == _fresh_garbage(cut) > 0.0
    assert cut.linked_blocks_mask().sum() == 1

    rng = np.random.default_rng(44)
    for step in range(3):
        ei = rng.choice(g.n_edges, 60, replace=False)
        batch = UpdateBatch.deletes(g.src[ei], g.dst[ei])
        g = apply_batch(g, batch)
        prev = idx
        idx, owners = updates.update_dbindex_batch(prev, g, w, batch)
        assert idx.garbage_block_fraction() == _fresh_garbage(idx), step
        assert prev.garbage_block_fraction() == _fresh_garbage(prev), step
    assert idx.garbage_block_fraction() > 0.0, "stream produced no garbage"

    wins = [khop_window_single(g, int(v), 1) for v in owners[:5]]
    merged = _merge_affected(idx, owners[:5], wins)
    assert merged.garbage_block_fraction() == _fresh_garbage(merged)
    assert merged.linked_blocks_mask() is not idx.linked_blocks_mask()
    assert link_scans() == before + 6


def test_memoized_link_mask_is_read_only(small_undirected):
    idx = build_dbindex(small_undirected, KHopWindow(1), method="emc")
    mask = idx.linked_blocks_mask()
    assert not mask.flags.writeable
    with pytest.raises(ValueError):
        mask[0] = False
    assert idx.garbage_block_fraction() == 0.0


def test_policy_doubles_pass_through_garbage_block_fraction():
    """Duck-typed doubles with ``num_blocks``/``link_block`` only (or
    without ``link_block``, or without blocks) still read through the
    module-level ``garbage_block_fraction``, once per double."""
    from repro.core.dbindex import link_scans
    from repro.core.streaming import garbage_block_fraction

    class ShrunkIdx:
        num_blocks = 10
        stats = {"num_links": 50}
        link_block = np.array([0, 1, 2], np.int32)

    class NoLinks:
        num_blocks = 100
        stats = {"num_links": 200}

    class NoBlocks:
        num_blocks = 0
        link_block = np.empty(0, np.int32)

    d = ShrunkIdx()
    before = link_scans()
    assert garbage_block_fraction(d) == pytest.approx(0.7)
    assert garbage_block_fraction(d) == garbage_block_fraction(d)
    assert link_scans() == before + 1
    assert garbage_block_fraction(NoLinks()) == 0.0
    assert garbage_block_fraction(NoBlocks()) == 0.0
