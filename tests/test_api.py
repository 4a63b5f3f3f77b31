"""Unified window-analytics API: registry, fused compiler, Session.

Differential suite for :mod:`repro.core.api`:

* every registered engine × every aggregate × both window types against
  the per-vertex ``brute_force`` oracle (one fused runner call per engine
  — the registry interface is multi-aggregate);
* fused multi-aggregate device plans against per-aggregate
  ``query_dbindex`` answers bit-for-bit;
* capability selection + the explicit ``UnsupportedQueryError`` contract;
* ``Session`` update→query round-trips: 20 streamed ``UpdateBatch``es with
  oracle-correct answers and zero recompiles of the fused plan.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import engine_jax as ej  # noqa: E402
from repro.core.api import (  # noqa: E402
    DEFAULT_REGISTRY,
    QuerySpec,
    Session,
    UnsupportedQueryError,
    compile_queries,
    recompile_count,
)
from repro.core.dbindex import build_dbindex  # noqa: E402
from repro.core.iindex import build_iindex  # noqa: E402
from repro.core.query import brute_force  # noqa: E402
from repro.core.streaming import StreamingEngine  # noqa: E402
from repro.core.windows import KHopWindow, TopologicalWindow  # noqa: E402
from repro.graphs.generators import (  # noqa: E402
    barabasi_albert,
    erdos_renyi,
    random_dag,
    with_random_attrs,
)

from test_updates import mixed  # noqa: E402  (stream helpers)

ALL_AGGS = ("sum", "count", "min", "max", "avg")
KHOP_ENGINES = ("nonindex", "bitset", "eagr", "dbindex", "jax")
TOPO_ENGINES = ("nonindex", "bitset", "eagr", "dbindex", "iindex", "jax",
                "jax-iindex")


@pytest.fixture(scope="module")
def khop_case():
    g = with_random_attrs(erdos_renyi(90, 3.0, directed=False, seed=7), seed=8)
    w = KHopWindow(2)
    refs = {a: brute_force(g, w, g.attrs["val"], a) for a in ALL_AGGS}
    return g, w, refs


@pytest.fixture(scope="module")
def topo_case():
    g = with_random_attrs(random_dag(90, 2.0, seed=9), seed=10)
    w = TopologicalWindow()
    refs = {a: brute_force(g, w, g.attrs["val"], a) for a in ALL_AGGS}
    return g, w, refs


# ----------------------- engine × aggregate sweep --------------------- #
@pytest.mark.parametrize("engine", KHOP_ENGINES)
def test_every_engine_every_agg_khop(engine, khop_case):
    g, w, refs = khop_case
    out = DEFAULT_REGISTRY.run(engine, g, w, g.attrs["val"], ALL_AGGS,
                               use_pallas=False)
    for a in ALL_AGGS:
        assert np.allclose(out[a], refs[a], rtol=1e-5, atol=1e-3), (engine, a)


@pytest.mark.parametrize("engine", TOPO_ENGINES)
def test_every_engine_every_agg_topological(engine, topo_case):
    g, w, refs = topo_case
    out = DEFAULT_REGISTRY.run(engine, g, w, g.attrs["val"], ALL_AGGS,
                               use_pallas=False)
    for a in ALL_AGGS:
        assert np.allclose(out[a], refs[a], rtol=1e-5, atol=1e-3), (engine, a)


# --------------------------- capability model ------------------------- #
def test_registry_selection_by_capability():
    w2, wt = KHopWindow(2), TopologicalWindow()
    assert DEFAULT_REGISTRY.select(w2, ("sum", "avg")) == "jax"
    assert DEFAULT_REGISTRY.select(wt, ("min",), device=True) == "jax-iindex"
    assert DEFAULT_REGISTRY.select(w2, ("sum",), device=False) == "dbindex"
    assert DEFAULT_REGISTRY.select(w2, ("sum",), sharded=True) == "jax-sharded"
    # the stacked-channel sharded executor serves every monoid aggregate
    # (the old SUM-only capability row is gone)
    assert DEFAULT_REGISTRY.select(w2, ("min", "avg", "count"),
                                   sharded=True) == "jax-sharded"
    # explicit pins are validated against the declared capability
    assert DEFAULT_REGISTRY.select(wt, ("max",), engine="iindex") == "iindex"


def test_registry_unsupported_is_explicit():
    w2 = KHopWindow(2)
    with pytest.raises(UnsupportedQueryError, match="iindex"):
        DEFAULT_REGISTRY.select(w2, ("sum",), engine="iindex")
    # no sharded engine is non-incremental: must fail loudly, and the
    # capability table must carry the device/sharded/incremental flags so
    # planner failures are self-explaining
    with pytest.raises(UnsupportedQueryError,
                       match=r"sharded=True.*sharded=True, incremental=True"):
        DEFAULT_REGISTRY.select(w2, ("sum",), sharded=True, incremental=False)
    # pin-mismatch errors carry the engine's full capability row too
    with pytest.raises(UnsupportedQueryError, match="device=False"):
        DEFAULT_REGISTRY.select(w2, ("sum",), engine="iindex")
    with pytest.raises(UnsupportedQueryError, match="unknown engine"):
        DEFAULT_REGISTRY.select(w2, ("sum",), engine="nope")


def test_compile_queries_dedups_and_fuses():
    specs = [
        QuerySpec(("khop", 2), "sum"),
        QuerySpec(("khop", 2), "avg"),
        QuerySpec(("khop", 2), "sum"),  # duplicate collapses
        QuerySpec("topological", "min"),
        QuerySpec(("khop", 2), "count", engine="bitset"),
    ]
    cq = compile_queries(specs, device=True)
    assert [g.aggs for g in cq.groups] == [("sum", "avg"), ("min",), ("count",)]
    assert [g.engine for g in cq.groups] == ["jax", "jax-iindex", "bitset"]
    # spec back-pointers: duplicate sum shares the first slot
    assert cq.spec_slots[0] == cq.spec_slots[2]


# ------------------- fused multi-channel device plans ------------------ #
def test_fused_dbindex_multi_bit_identical_to_per_agg(khop_case):
    g, w, refs = khop_case
    idx = build_dbindex(g, w, method="emc")
    plan = ej.plan_from_dbindex(idx, tm=64, ts=64)
    fused = ej.query_dbindex_multi(plan, g.attrs["val"], ALL_AGGS,
                                   use_pallas=False)
    for a, got in zip(ALL_AGGS, fused):
        single = np.asarray(ej.query_dbindex(plan, g.attrs["val"], a,
                                             use_pallas=False))
        assert np.array_equal(np.asarray(got), single), a  # bit-for-bit
        assert np.allclose(np.asarray(got), refs[a], rtol=1e-5, atol=1e-3), a


def test_fused_dbindex_multi_pallas_interpret(khop_case):
    g, w, refs = khop_case
    idx = build_dbindex(g, w, method="emc")
    plan = ej.plan_from_dbindex(idx, tm=64, ts=64)
    fused = ej.query_dbindex_multi(plan, g.attrs["val"], ("sum", "avg"),
                                   use_pallas=True, interpret=True)
    for a, got in zip(("sum", "avg"), fused):
        assert np.allclose(np.asarray(got), refs[a], rtol=1e-5, atol=1e-3), a


def _hub_tree_plan():
    """A Barabási–Albert tree: its hubs make blocks too wide for the ELL
    layout, so min/max reduce over the tile layout."""
    g = with_random_attrs(barabasi_albert(500, 1, seed=0), seed=14)
    plan = ej.plan_from_dbindex(build_dbindex(g, KHopWindow(2)),
                                tm=128, ts=128)
    return g, plan


def test_fused_dbindex_multi_minmax_tiled_matches_xla():
    g, plan = _hub_tree_plan()
    assert plan.p1_ell is None
    assert ej.minmax_route(plan, True) == "tiled"
    assert ej.minmax_route(plan, False) == "xla"
    v = g.attrs["val"]
    tiled = ej.query_dbindex_multi(plan, v, ALL_AGGS, use_pallas=True,
                                   interpret=True)
    xla = ej.query_dbindex_multi(plan, v, ALL_AGGS, use_pallas=False)
    for a, got, want in zip(ALL_AGGS, tiled, xla):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want), a)
    for a in ("min", "max"):
        single = ej.query_dbindex(plan, v, a, use_pallas=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(single),
                                      brute_force(g, KHopWindow(2), v, a))


def test_fused_iindex_multi_minmax_tiled_matches_xla():
    g = with_random_attrs(random_dag(400, 2.0, seed=15), seed=16)
    plan = ej.plan_from_iindex(build_iindex(g), tm=128, ts=128)
    assert ej.minmax_route(plan, True) == "tiled"
    v = g.attrs["val"]
    tiled = ej.query_iindex_multi(plan, v, ALL_AGGS, use_pallas=True,
                                  interpret=True)
    xla = ej.query_iindex_multi(plan, v, ALL_AGGS, use_pallas=False)
    for a, got, want in zip(ALL_AGGS, tiled, xla):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want), a)
    for a in ("min", "max"):
        np.testing.assert_array_equal(
            np.asarray(tiled[ALL_AGGS.index(a)]),
            brute_force(g, TopologicalWindow(), v, a))


def test_spans_name_the_minmax_route():
    """``query.group`` and ``executor.device`` carry ``minmax``, the route
    the group's min/max channels take; a group without one carries none."""
    from repro.obs.tracing import Tracer

    g, _ = _hub_tree_plan()
    specs = [QuerySpec(("khop", 2), "min"), QuerySpec(("khop", 1), "max"),
             QuerySpec(("khop", 2), "sum", engine="dbindex")]
    tr = Tracer()
    sess = Session(g, specs, device=True, use_pallas=False, tracer=tr)
    sess.run()
    sess.snapshot().run_many(np.stack([g.attrs["val"]] * 2))
    routes = {}
    for e in tr.events():
        if e["name"] in ("query.group", "executor.device"):
            routes.setdefault(e["name"], []).append(e["args"].get("minmax"))
    assert routes["query.group"] == ["xla", "ell", None] * 2
    assert routes["executor.device"] == ["xla", "ell"]


def test_spans_count_the_kernel_pages(monkeypatch):
    """``query.group`` and ``executor.device`` carry ``pages``, the Pallas
    calls each segment kernel takes over the plan's largest pass: 1 under
    ``PAGE_TILES``, more once the plan has more tiles; absent on the XLA
    route.  Paged answers equal the one-call answers."""
    from repro.kernels.segment_reduce import segment_reduce as sr
    from repro.obs.tracing import Tracer

    g, _ = _hub_tree_plan()
    vb = np.stack([g.attrs["val"]] * 2)

    def pages_of(use_pallas, page_tiles=None):
        if page_tiles is not None:
            monkeypatch.setattr(sr, "PAGE_TILES", page_tiles)
        jax.clear_caches()
        tr = Tracer()
        sess = Session(g, [QuerySpec(("khop", 2), "max")], device=True,
                       use_pallas=use_pallas, tracer=tr)
        out = sess.run(), sess.snapshot().run_many(vb)
        spans = {e["name"]: e["args"].get("pages") for e in tr.events()
                 if e["name"] in ("query.group", "executor.device")}
        return spans, out, sess.snapshot().artifacts[0][0][1]

    try:
        one, ref, plan = pages_of(True)
        assert one == {"query.group": 1, "executor.device": 1}
        assert pages_of(False)[0] == {"query.group": None,
                                      "executor.device": None}
        paged, got, _ = pages_of(True, page_tiles=2)
        want = -(-max(plan.pass1.seg_tiles.shape[0],
                      plan.pass2.seg_tiles.shape[0]) // 2)
        assert paged == {"query.group": want, "executor.device": want} \
            and want > 1
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            np.testing.assert_array_equal(a, b)
    finally:
        jax.clear_caches()


@pytest.mark.parametrize("schedule", ["level", "doubling"])
def test_fused_iindex_multi_all_monoids(schedule, topo_case):
    g, w, refs = topo_case
    ii = build_iindex(g)
    plan = ej.plan_from_iindex(ii, tm=64, ts=64)
    fused = ej.query_iindex_multi(plan, g.attrs["val"], ALL_AGGS,
                                  schedule=schedule, use_pallas=False)
    for a, got in zip(ALL_AGGS, fused):
        assert np.allclose(np.asarray(got), refs[a], rtol=1e-5, atol=1e-3), (
            schedule, a)
    # sum channel is bit-identical to the dedicated SUM kernel path
    s = np.asarray(ej.query_iindex(plan, g.attrs["val"], schedule=schedule,
                                   use_pallas=False))
    assert np.array_equal(np.asarray(fused[0]), s)


def test_streaming_engine_device_iindex_minmax_no_assert(topo_case):
    """The old device I-Index path asserted SUM-only; the registry now
    routes min/max/count/avg through per-monoid level inheritance."""
    g, w, refs = topo_case
    eng = StreamingEngine(g, w, index_kind="iindex", use_pallas=False)
    for a in ALL_AGGS:
        assert np.allclose(eng.query(a), refs[a], rtol=1e-5, atol=1e-3), a
    outs = eng.query_multi(("min", "max", "avg"))
    for a, o in zip(("min", "max", "avg"), outs):
        assert np.allclose(o, refs[a], rtol=1e-5, atol=1e-3), a


# ------------------------------ Session ------------------------------- #
def test_session_update_query_roundtrip_no_recompile():
    """Oracle-correct across >= 20 streamed batches, zero retraces of the
    fused device query (plan patching keeps static shapes stable)."""
    g = with_random_attrs(erdos_renyi(600, 4.0, directed=False, seed=11),
                          seed=12)
    specs = [QuerySpec(("khop", 1), a) for a in ("sum", "count", "min", "avg")]
    sess = Session(g, specs, device=True, use_pallas=False, plan_headroom=1.0)
    sess.run()
    # unified counter spanning every fused executor's jit cache (the old
    # per-executor probe stays as a cross-check that the union attributes
    # a regression to the right executor)
    cache0 = recompile_count()
    dbcache0 = ej.query_dbindex_multi._cache_size()
    rng = np.random.default_rng(13)
    for step in range(20):
        sess.update(mixed(sess.graph, rng, 4, 2))
        res = sess.run()
        vals = sess.graph.attrs["val"]
        for s, r in zip(specs, res):
            ref = brute_force(sess.graph, s.window, vals, s.agg)
            assert np.allclose(r, ref, rtol=1e-5, atol=1e-3), (step, s.agg)
    assert recompile_count() == cache0  # no recompiles, any executor
    assert ej.query_dbindex_multi._cache_size() == dbcache0
    assert sess.updates_applied == 20


@pytest.mark.parametrize("sharded", [False, True])
def test_session_reorganize_keeps_plan_shapes(sharded):
    """2-hop batches trip the staleness policy (the merged secondary blocks
    share little), so the stream reorganizes; each rebuilt plan keeps the
    shapes of the one it replaces and the fused query never retraces."""
    g = with_random_attrs(erdos_renyi(1500, 6.0, directed=False, seed=21),
                          seed=22)
    specs = [QuerySpec(("khop", 2), a) for a in ("sum", "min")]
    mesh = jax.make_mesh((1,), ("data",)) if sharded else None
    sess = Session(g, specs, device=True, use_pallas=False, mesh=mesh)
    sess.run()
    cache0 = recompile_count()
    rng = np.random.default_rng(23)
    reorganized = 0
    for _ in range(4):
        reports = sess.update(mixed(sess.graph, rng, 20, 10))
        reorganized += any(r["reorganized"] for r in reports.values())
        vals = sess.graph.attrs["val"]
        for s, r in zip(specs, sess.run()):
            ref = brute_force(sess.graph, s.window, vals, s.agg,
                              dtype=np.float32)
            assert np.array_equal(r, np.asarray(ref, r.dtype)), s.agg
    assert reorganized >= 2
    assert recompile_count() == cache0


@pytest.mark.parametrize("sharded", [False, True])
def test_session_reorganize_shrinks_plan_when_widest_block_shrinks(sharded):
    """A hub's wide block sets the ELL width R1; once the hub's edges are
    deleted, the reorganized index's own R1 is a quarter of it.  Keeping
    the old width would pad the min/max layout past ``keep_shape``'s
    bound, so the rebuild takes its own widths (one retrace) and pads no
    more than a fresh plan of the same index."""
    from repro.core.streaming import StalenessPolicy
    from repro.core.updates import UpdateBatch, apply_batch
    from repro.kernels.segment_reduce.ops import KEEP_SHAPE_MAX_PAD

    g = with_random_attrs(erdos_renyi(600, 3.0, directed=False, seed=5),
                          seed=6)
    hub_s, hub_d = np.zeros(50, np.int32), np.arange(1, 51, dtype=np.int32)
    fresh = ~g.contains_edges(hub_s, hub_d)
    hub = UpdateBatch.inserts(hub_s[fresh], hub_d[fresh])
    g = apply_batch(g, hub)
    specs = [QuerySpec(("khop", 1), a) for a in ("sum", "min")]
    mesh = jax.make_mesh((1,), ("data",)) if sharded else None
    sess = Session(g, specs, device=True, use_pallas=False, mesh=mesh,
                   policy=StalenessPolicy(max_link_ratio=0.0))

    def widths_and_padding():
        (index, plan), = sess._group_artifacts(0)
        r1, r2 = plan.ell_widths
        return (r1, r2), plan.block_capacity * r1 / index.block_members.size

    (wide_r1, _), _ = widths_and_padding()
    reports = sess.update(UpdateBatch.deletes(hub.src, hub.dst))
    assert all(r["reorganized"] for r in reports.values())
    (index, _), = sess._group_artifacts(0)
    own = ej.plan_from_dbindex(index, headroom=0.5)
    own_padding = own.block_capacity * own.ell_widths[0] \
        / index.block_members.size
    widths, padding = widths_and_padding()
    assert wide_r1 > KEEP_SHAPE_MAX_PAD * own.ell_widths[0]  # the premise
    assert widths == own.ell_widths
    assert padding <= KEEP_SHAPE_MAX_PAD * own_padding
    vals = sess.graph.attrs["val"]
    for s, r in zip(specs, sess.run()):
        ref = brute_force(sess.graph, s.window, vals, s.agg, dtype=np.float32)
        assert np.array_equal(r, np.asarray(ref, r.dtype)), s.agg


def test_session_mixed_windows_and_attrs(topo_case):
    g, w, refs = topo_case
    g = g.with_attr("weight", np.arange(g.n, dtype=np.float64))
    specs = [
        QuerySpec("topological", "sum"),
        QuerySpec(("khop", 1), "max", attr="weight"),
        QuerySpec("topological", "avg"),
    ]
    sess = Session(g, specs, device=True, use_pallas=False)
    res = sess.run()
    for s, r in zip(specs, res):
        ref = brute_force(g, s.window, g.attrs[s.attr], s.agg)
        assert np.allclose(r, ref, rtol=1e-5, atol=1e-3), s
    # one stateful index per distinct (window, kind), shared across groups
    assert len(sess._states) == 2


def test_session_run_many_matches_per_row():
    g = with_random_attrs(erdos_renyi(120, 3.0, directed=False, seed=14),
                          seed=15)
    specs = [QuerySpec(("khop", 1), a) for a in ("sum", "min", "avg")]
    sess = Session(g, specs, device=True, use_pallas=False)
    vb = np.random.default_rng(16).normal(size=(3, g.n))
    outs = sess.run_many(vb)
    for s, o in zip(specs, outs):
        assert o.shape == (3, g.n)
        for b in range(vb.shape[0]):
            ref = brute_force(g, s.window, vb[b], s.agg)
            assert np.allclose(o[b], ref, rtol=1e-5, atol=1e-3), (s.agg, b)


def test_session_shared_state_keeps_device_plan(khop_case):
    """A host-pinned group sharing a window with a device group must not
    strip the compiled plan (state device flag is the OR over groups)."""
    g, w, refs = khop_case
    specs = [
        QuerySpec(w, "sum", engine="dbindex"),  # host
        QuerySpec(w, "avg", engine="jax"),      # device, same window
    ]
    sess = Session(g, specs, use_pallas=False)
    assert sess._states[(w, "dbindex")].plan is not None
    s, avg = sess.run()
    assert np.allclose(s, refs["sum"], rtol=1e-5, atol=1e-3)
    assert np.allclose(avg, refs["avg"], rtol=1e-5, atol=1e-3)


def test_session_update_reports_distinct_windows():
    g = with_random_attrs(erdos_renyi(80, 3.0, directed=False, seed=31), seed=32)
    sess = Session(g, [QuerySpec(("khop", 1), "sum"), QuerySpec(("khop", 2), "sum")],
                   device=True, use_pallas=False)
    from repro.core.updates import UpdateBatch

    reports = sess.update(UpdateBatch.inserts([0, 1], [5, 6]))
    assert set(reports) == {"khop[1]/dbindex", "khop[2]/dbindex"}


def test_registry_rejects_unknown_options(khop_case):
    g, w, refs = khop_case
    with pytest.raises(TypeError, match="unknown engine option"):
        DEFAULT_REGISTRY.run("dbindex", g, w, g.attrs["val"], ("sum",),
                             metod="mc")  # typo must not silently default


def test_legacy_graph_window_query_shim(khop_case):
    from repro.core.query import GraphWindowQuery

    g, w, refs = khop_case
    for engine in ("dbindex", "bitset"):
        got = GraphWindowQuery(w, agg="avg").run(g, engine=engine)
        assert np.allclose(got, refs["avg"], rtol=1e-5, atol=1e-3), engine
    with pytest.raises(UnsupportedQueryError):
        GraphWindowQuery(w, agg="sum").run(g, engine="iindex")


# ------------------- sharded runtime (single-device mesh) -------------- #
# The real multi-device coverage lives in tests/test_sharded_stream.py (own
# CI job, subprocess-forced device count); a 1-device mesh exercises the
# whole sharded code path — layout, shard_map, collectives, patching — in
# tier-1 without the device-count dance.
def test_sharded_multi_single_device_mesh_bit_identical(khop_case):
    g, w, refs = khop_case
    mesh = jax.make_mesh((1,), ("data",))
    idx = build_dbindex(g, w, method="emc")
    plan = ej.plan_from_dbindex(idx, tm=64, ts=64)
    fused = ej.query_dbindex_multi(plan, g.attrs["val"], ALL_AGGS,
                                   use_pallas=False)
    sharded = ej.query_dbindex_sharded_multi(plan, g.attrs["val"], ALL_AGGS,
                                             mesh)
    for a, r, o in zip(ALL_AGGS, fused, sharded):
        assert np.array_equal(np.asarray(r), np.asarray(o)), a


def test_session_mesh_kwarg_builds_sharded_session():
    from repro.distributed.window_runtime import ShardedSession

    # big enough that a small batch stays on the incremental patch path
    # (tiny dense graphs trip the affected>n/2 rebuild / staleness policy)
    g = with_random_attrs(erdos_renyi(300, 3.0, directed=False, seed=21),
                          seed=22)
    w = KHopWindow(1)
    mesh = jax.make_mesh((1,), ("data",))
    sess = Session(g, [QuerySpec(w, "sum"), QuerySpec(w, "min")], mesh=mesh,
                   plan_headroom=1.0)
    assert isinstance(sess, ShardedSession)
    s, mn = sess.run()
    vals = g.attrs["val"]
    assert np.allclose(s, brute_force(g, w, vals, "sum"), rtol=1e-5, atol=1e-3)
    assert np.allclose(mn, brute_force(g, w, vals, "min"), rtol=1e-5, atol=1e-3)
    # streamed update keeps the sharded plan fresh (patch, not re-upload)
    rng = np.random.default_rng(23)
    reports = sess.update(mixed(sess.graph, rng, 4, 2))
    rep = next(iter(reports.values()))
    assert not rep["reorganized"]
    assert 0 < rep["patch_bytes"] < rep["full_plan_bytes"]
    s2, _ = sess.run()
    ref2 = brute_force(sess.graph, w, sess.graph.attrs["val"], "sum")
    assert np.allclose(s2, ref2, rtol=1e-5, atol=1e-3)


def test_sharded_session_mixed_pin_single_host_device_group():
    """A pinned non-sharded device group sharing a window with a sharded
    group must not be handed the ShardedDBPlan (regression: jit crashed on
    the non-array plan) — it gets the shared index and builds its own
    host plan per call."""
    from repro.distributed.window_runtime import ShardedSession

    g = with_random_attrs(erdos_renyi(120, 3.0, directed=False, seed=24),
                          seed=25)
    w = KHopWindow(1)
    mesh = jax.make_mesh((1,), ("data",))
    sess = Session(g, [QuerySpec(w, "sum"), QuerySpec(w, "min", engine="jax")],
                   mesh=mesh, use_pallas=False)
    assert isinstance(sess, ShardedSession)
    s, mn = sess.run()
    vals = g.attrs["val"]
    assert np.allclose(s, brute_force(g, w, vals, "sum"), rtol=1e-5, atol=1e-3)
    assert np.allclose(mn, brute_force(g, w, vals, "min"), rtol=1e-5, atol=1e-3)
