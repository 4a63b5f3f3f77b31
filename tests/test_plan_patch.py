"""Incremental device-plan maintenance: patched plans == fresh plans.

Regression suite for ``patch_tile_plan`` / ``patch_plan_dbindex`` /
``patch_plan_iindex``: after every batch of a random edit stream, a query
on the incrementally patched plan must match a fresh ``plan_from_*`` build
bit-for-bit (same f32 arithmetic on both paths) and the host brute-force
oracle approximately.  Runs on CPU (XLA fallback for the sweep, one Pallas
interpret-mode case to pin the kernel path).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import engine_jax as ej  # noqa: E402
from repro.core import updates as U  # noqa: E402
from repro.core.dbindex import build_dbindex  # noqa: E402
from repro.core.iindex import build_iindex  # noqa: E402
from repro.core.query import brute_force  # noqa: E402
from repro.core.streaming import StalenessPolicy, StreamingEngine  # noqa: E402
from repro.core.windows import KHopWindow, TopologicalWindow  # noqa: E402
from repro.graphs.generators import erdos_renyi, random_dag, with_random_attrs  # noqa: E402
from repro.kernels.segment_reduce.ops import (  # noqa: E402
    build_tile_plan,
    patch_tile_plan,
    segment_sum,
)

from test_updates import mixed  # noqa: E402  (stream helpers)


# ------------------------- patch_tile_plan unit ----------------------- #
@pytest.mark.parametrize("tm,ts", [(64, 64), (128, 32)])
def test_patch_tile_plan_matches_rebuild(tm, ts):
    rng = np.random.default_rng(0)
    n, m, s = 500, 3000, 400
    vals = rng.normal(size=n).astype(np.float32)
    seg = np.sort(rng.integers(0, s, m)).astype(np.int64)
    gidx = rng.integers(0, n, m).astype(np.int32)
    plan = build_tile_plan(gidx, seg, s, tm, ts)
    # mutate a sparse set of segments: drop their rows, add new ones
    changed = rng.choice(s, 25, replace=False)
    keep = ~np.isin(seg, changed)
    add_seg = np.repeat(changed, 3)
    add_gidx = rng.integers(0, n, add_seg.size).astype(np.int32)
    seg2 = np.concatenate([seg[keep], add_seg])
    gidx2 = np.concatenate([gidx[keep], add_gidx])
    order = np.argsort(seg2, kind="stable")
    seg2, gidx2 = seg2[order], gidx2[order]
    patched = patch_tile_plan(plan, gidx2, seg2, s, changed)
    fresh = build_tile_plan(gidx2, seg2, s, tm, ts)
    out_p = np.asarray(segment_sum(patched, jnp.asarray(vals), use_pallas=False))
    out_f = np.asarray(segment_sum(fresh, jnp.asarray(vals), use_pallas=False))
    assert np.array_equal(out_p, out_f)


def test_patch_tile_plan_grows_segments():
    rng = np.random.default_rng(1)
    n, m, s = 200, 800, 100
    seg = np.sort(rng.integers(0, s, m)).astype(np.int64)
    gidx = rng.integers(0, n, m).astype(np.int32)
    plan = build_tile_plan(gidx, seg, s, 64, 64)
    # append rows for brand-new segment ids beyond the old num_segments
    s2 = 150
    add_seg = np.sort(rng.integers(s, s2, 120)).astype(np.int64)
    add_gidx = rng.integers(0, n, add_seg.size).astype(np.int32)
    seg2 = np.concatenate([seg, add_seg])
    gidx2 = np.concatenate([gidx, add_gidx])
    patched = patch_tile_plan(plan, gidx2, seg2, s2, np.arange(s, s2))
    fresh = build_tile_plan(gidx2, seg2, s2, 64, 64)
    vals = rng.normal(size=n).astype(np.float32)
    out_p = np.asarray(segment_sum(patched, jnp.asarray(vals), use_pallas=False))
    out_f = np.asarray(segment_sum(fresh, jnp.asarray(vals), use_pallas=False))
    assert np.array_equal(out_p, out_f)


def test_patch_tile_plan_scatter_no_recompile():
    """Shape-stable patches scatter changed tile groups into the live device
    arrays — the static parts are reused verbatim and jitted consumers never
    retrace (asserted via the jit compile counter)."""
    rng = np.random.default_rng(5)
    n, m, s = 300, 2000, 256
    vals = jnp.asarray(rng.normal(size=n).astype(np.float32))
    seg = np.sort(rng.integers(0, s, m)).astype(np.int64)
    gidx = rng.integers(0, n, m).astype(np.int32)
    plan = build_tile_plan(gidx, seg, s, 64, 64)
    np.asarray(segment_sum(plan, vals, use_pallas=False))  # warm the cache
    cache0 = segment_sum._cache_size()
    outs, rows = [], []
    for step in range(3):
        changed = rng.choice(s, 12, replace=False)
        keep = ~np.isin(seg, changed)
        add_seg = np.repeat(changed, 2)
        add_gidx = rng.integers(0, n, add_seg.size).astype(np.int32)
        seg2 = np.concatenate([seg[keep], add_seg])
        gidx2 = np.concatenate([gidx[keep], add_gidx])
        order = np.argsort(seg2, kind="stable")
        seg, gidx = seg2[order], gidx2[order]
        patched = patch_tile_plan(plan, gidx, seg, s, changed)
        # static parts are the same device arrays, not re-uploads
        assert patched.m2out is plan.m2out and patched.first_visit is plan.first_visit
        outs.append(np.asarray(segment_sum(patched, vals, use_pallas=False)))
        rows.append((gidx.copy(), seg.copy()))
        plan = patched
    assert segment_sum._cache_size() == cache0  # scatter path: no retrace
    for (gi, si), out_p in zip(rows, outs):  # rebuild oracle, after the count
        fresh = build_tile_plan(gi, si, s, 64, 64)
        out_f = np.asarray(segment_sum(fresh, vals, use_pallas=False))
        assert np.array_equal(out_p, out_f)


def test_patch_tile_plan_stable_shapes_when_rows_fit():
    """Steady-state streams must not change static shapes (no recompiles)."""
    rng = np.random.default_rng(2)
    n, m, s = 300, 2000, 256
    seg = np.sort(rng.integers(0, s, m)).astype(np.int64)
    gidx = rng.integers(0, n, m).astype(np.int32)
    plan = build_tile_plan(gidx, seg, s, 64, 64)
    # shrink a few segments (rows certainly still fit the old capacity)
    changed = rng.choice(s, 10, replace=False)
    keep = ~np.isin(seg, changed)
    patched = patch_tile_plan(plan, gidx[keep], seg[keep], s, changed)
    assert patched.gather_padded.shape == plan.gather_padded.shape
    assert patched.seg_tiles.shape == plan.seg_tiles.shape
    assert np.array_equal(np.asarray(patched.m2out), np.asarray(plan.m2out))


# --------------------- DBIndex plan parity over streams --------------- #
@pytest.mark.parametrize("k,directed", [(1, False), (2, False), (2, True)])
def test_dbindex_patched_plan_parity(k, directed):
    rng = np.random.default_rng(100 + k)
    g = with_random_attrs(
        erdos_renyi(220, 4.0, directed=directed, seed=k), seed=k + 1
    )
    w = KHopWindow(k)
    idx = build_dbindex(g, w, method="emc")
    plan = ej.plan_from_dbindex(idx, tm=64, ts=64)
    for step in range(3):
        b = mixed(g, rng, 15, 6)
        g = U.apply_batch(g, b)
        idx, owners = U.update_dbindex_batch(idx, g, w, b)
        plan = ej.patch_plan_dbindex(plan, idx, owners)
        fresh = ej.plan_from_dbindex(idx, tm=64, ts=64,
                                     like=plan)
        for agg in ("sum", "count", "avg"):
            got = np.asarray(ej.query_dbindex(plan, g.attrs["val"], agg,
                                              use_pallas=False))
            ref_plan = np.asarray(ej.query_dbindex(fresh, g.attrs["val"], agg,
                                                   use_pallas=False))
            assert np.array_equal(got, ref_plan), (step, agg)  # bit-for-bit
            oracle = brute_force(g, w, g.attrs["val"], agg)
            assert np.allclose(got, oracle, rtol=1e-5, atol=1e-3), (step, agg)


def test_dbindex_patched_plan_parity_pallas_interpret():
    """One case through the Pallas kernel in interpret mode (CPU-safe)."""
    rng = np.random.default_rng(7)
    g = with_random_attrs(erdos_renyi(150, 3.0, directed=False, seed=7), seed=8)
    w = KHopWindow(1)
    idx = build_dbindex(g, w, method="emc")
    plan = ej.plan_from_dbindex(idx, tm=64, ts=64)
    b = mixed(g, rng, 10, 4)
    g = U.apply_batch(g, b)
    idx, owners = U.update_dbindex_batch(idx, g, w, b)
    plan = ej.patch_plan_dbindex(plan, idx, owners)
    got = np.asarray(ej.query_dbindex(plan, g.attrs["val"], "sum",
                                      use_pallas=True, interpret=True))
    oracle = brute_force(g, w, g.attrs["val"], "sum")
    assert np.allclose(got, oracle, rtol=1e-5, atol=1e-3)


def test_dbindex_plan_capacity_growth_is_pow2():
    rng = np.random.default_rng(8)
    g = with_random_attrs(erdos_renyi(200, 4.0, directed=False, seed=9), seed=10)
    w = KHopWindow(1)
    idx = build_dbindex(g, w, method="emc")
    plan = ej.plan_from_dbindex(idx, tm=64, ts=64)
    caps = [plan.block_capacity]
    for _ in range(4):
        b = mixed(g, rng, 20, 0)
        g = U.apply_batch(g, b)
        idx, owners = U.update_dbindex_batch(idx, g, w, b)
        plan = ej.patch_plan_dbindex(plan, idx, owners)
        caps.append(plan.block_capacity)
        assert plan.block_capacity >= idx.num_blocks
    grown = [c for a, c in zip(caps, caps[1:]) if c != a]
    assert all(c & (c - 1) == 0 for c in grown)  # powers of two only


def test_patch_plan_dbindex_compacts_garbage_blocks():
    """A delete-heavy stream strands zero-link blocks whose member rows
    still occupy pass-1 tiles; crossing ``compact_garbage`` re-lays pass 1
    without them — smaller plan, identical answers."""
    from repro.core.streaming import garbage_block_fraction
    from test_updates import random_delete_batch

    rng = np.random.default_rng(44)
    g = with_random_attrs(erdos_renyi(160, 6.0, directed=False, seed=27), seed=28)
    w = KHopWindow(1)
    idx = build_dbindex(g, w, method="emc")
    plan = ej.plan_from_dbindex(idx, tm=64, ts=64)
    for _ in range(3):
        b = random_delete_batch(g, rng, 40)
        g = U.apply_batch(g, b)
        idx, owners = U.update_dbindex_batch(idx, g, w, b)
    assert garbage_block_fraction(idx) > 0.05, "stream produced no garbage"
    lazy = ej.patch_plan_dbindex(plan, idx, owners, compact_garbage=1.1)
    compacted = ej.patch_plan_dbindex(plan, idx, owners, compact_garbage=0.05)
    assert (compacted.pass1.seg_tiles.size < lazy.pass1.seg_tiles.size)
    for agg in ("sum", "count", "avg", "min"):
        out_c = np.asarray(ej.query_dbindex(compacted, g.attrs["val"], agg,
                                            use_pallas=False))
        out_l = np.asarray(ej.query_dbindex(lazy, g.attrs["val"], agg,
                                            use_pallas=False))
        assert np.array_equal(out_c, out_l), agg  # garbage contributes nothing
        oracle = brute_force(g, w, g.attrs["val"], agg)
        assert np.allclose(out_c, oracle, rtol=1e-5, atol=1e-3), agg


# --------------------- I-Index plan parity over streams --------------- #
@pytest.mark.parametrize("schedule", ["level", "doubling"])
def test_iindex_patched_plan_parity(schedule):
    rng = np.random.default_rng(9)
    g = with_random_attrs(random_dag(180, 2.5, seed=17), seed=18)
    ii = build_iindex(g)
    plan = ej.plan_from_iindex(ii, tm=64, ts=64)
    for step in range(3):
        b = mixed(g, rng, 10, 4, dag=True)
        g = U.apply_batch(g, b)
        ii, cone = U.update_iindex_batch(ii, g, b)
        plan = ej.patch_plan_iindex(plan, ii, cone)
        fresh = ej.plan_from_iindex(ii, tm=64, ts=64)
        got = np.asarray(ej.query_iindex(plan, g.attrs["val"], schedule=schedule,
                                         use_pallas=False))
        ref_plan = np.asarray(ej.query_iindex(fresh, g.attrs["val"],
                                              schedule=schedule, use_pallas=False))
        assert np.array_equal(got, ref_plan), step  # bit-for-bit
        oracle = brute_force(g, TopologicalWindow(), g.attrs["val"], "sum")
        assert np.allclose(got, oracle, rtol=1e-5, atol=1e-3), step


def test_dbindex_large_affected_set_falls_back_and_plan_stays_valid():
    """When >n/2 owners are affected the updater rebuilds outright; the
    appended-prefix invariant then does NOT hold, and patch_plan_dbindex
    must rebuild the plan instead of splicing stale tiles."""
    # chain DAG: descendants of vertex 2 are the whole tail (> n/2)
    from repro.core.graph import Graph

    n = 100
    g = Graph(n=n, src=np.arange(n - 1, dtype=np.int32),
              dst=np.arange(1, n, dtype=np.int32), directed=True)
    g = with_random_attrs(g, seed=34)
    w = TopologicalWindow()
    idx = build_dbindex(g, w, method="mc")
    plan = ej.plan_from_dbindex(idx, tm=64, ts=64)
    b = U.UpdateBatch.inserts([0], [2])  # cone = descendants(2) = n-2 > n/2
    g2 = U.apply_batch(g, b)
    idx2, owners = U.update_dbindex_batch(idx, g2, w, b)
    assert idx2.stats.get("last_full_rebuild") is True
    assert owners.size == g.n
    plan2 = ej.patch_plan_dbindex(plan, idx2, owners)
    got = np.asarray(ej.query_dbindex(plan2, g2.attrs["val"], "sum",
                                      use_pallas=False))
    fresh = np.asarray(ej.query_dbindex(
        ej.plan_from_dbindex(idx2, tm=64, ts=64,
                             like=plan2),
        g2.attrs["val"], "sum", use_pallas=False))
    assert np.array_equal(got, fresh)
    oracle = brute_force(g2, w, g2.attrs["val"], "sum")
    assert np.allclose(got, oracle, rtol=1e-5, atol=1e-3)
    # and the next (small) batch clears the flag so splicing resumes
    rng = np.random.default_rng(35)
    b2 = mixed(g2, rng, 2, 0, dag=True)
    g3 = U.apply_batch(g2, b2)
    idx3, owners3 = U.update_dbindex_batch(idx2, g3, w, b2)
    if not idx3.stats.get("last_full_rebuild"):
        plan3 = ej.patch_plan_dbindex(plan2, idx3, owners3)
        got3 = np.asarray(ej.query_dbindex(plan3, g3.attrs["val"], "sum",
                                           use_pallas=False))
        assert np.allclose(got3, brute_force(g3, w, g3.attrs["val"], "sum"),
                           rtol=1e-5, atol=1e-3)


# --------------------- engine with device plans ----------------------- #
def test_streaming_engine_device_stream():
    rng = np.random.default_rng(19)
    g = with_random_attrs(erdos_renyi(160, 4.0, directed=False, seed=21), seed=22)
    eng = StreamingEngine(
        g, KHopWindow(1), use_pallas=False,
        policy=StalenessPolicy(max_link_ratio=1.3, min_batches=2),
    )
    for step in range(5):
        b = mixed(eng.graph, rng, 12, 5)
        eng.apply(b)
        ref = brute_force(eng.graph, eng.window, eng.graph.attrs["val"], "sum")
        assert np.allclose(eng.query("sum"), ref, rtol=1e-5, atol=1e-3), step


def test_streaming_engine_device_iindex():
    rng = np.random.default_rng(23)
    g = with_random_attrs(random_dag(140, 2.0, seed=25), seed=26)
    eng = StreamingEngine(g, TopologicalWindow(), index_kind="iindex",
                          use_pallas=False)
    for step in range(3):
        b = mixed(eng.graph, rng, 8, 3, dag=True)
        eng.apply(b)
        ref = brute_force(eng.graph, TopologicalWindow(),
                          eng.graph.attrs["val"], "sum")
        assert np.allclose(eng.query("sum"), ref, rtol=1e-5, atol=1e-3), step
