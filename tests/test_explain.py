"""EXPLAIN/ANALYZE for window plans + serving flight recorder (ISSUE 8).

Tentpole contracts:

* **byte-exact memory accounting** — ``plan_nbytes()`` equals the sum of
  the actual ``.nbytes`` of every array the plan holds, for host DBIndex
  plans, I-Index plans, and sharded plans (checked array-by-array, not
  just in total);
* **EXPLAIN without execution** — engine resolution with per-candidate
  rejection reasons, the lowering choice per (expression, monoid set)
  with rejected alternatives, and plan anatomy, all stable across >= 10
  streamed ``UpdateBatch``es (static shapes ⇒ constant footprint);
* **ANALYZE attribution** — one profiled execution attributes >= 95% of
  wall time to named phases without touching the tracked jit caches;
* **flight recorder** — bounded ring of serving events, auto-dumped into
  ``last_flight_record`` when a ticket fails, surfaced (with padding
  waste and the plan footprint) by ``WindowService.debug_report()``.
"""

import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.api import (  # noqa: E402
    QuerySpec,
    Session,
    recompile_count,
)
from repro.core.windows import KHop, KHopWindow, Union  # noqa: E402
from repro.graphs.generators import (  # noqa: E402
    erdos_renyi,
    random_dag,
    with_random_attrs,
)
from repro.serve import FlightRecorder, WindowService  # noqa: E402
from repro.serve.flight import EVENT_TYPES  # noqa: E402

from test_updates import mixed  # noqa: E402  (stream helpers)


# ---------------------------------------------------------------------- #
#  Byte-exact plan memory accounting
# ---------------------------------------------------------------------- #
def _tileplan_actual(tp):
    return {"gather_padded": tp.gather_padded.nbytes,
            "seg_tiles": tp.seg_tiles.nbytes,
            "m2out": tp.m2out.nbytes,
            "first_visit": tp.first_visit.nbytes}


def test_dbindex_plan_nbytes_byte_exact():
    g = with_random_attrs(erdos_renyi(300, 4.0, directed=False, seed=1),
                          seed=2)
    sess = Session(g, [QuerySpec(("khop", 1), "sum")], device=True,
                   use_pallas=False)
    plan = next(iter(sess._states.values())).plan
    assert type(plan).__name__ == "DBIndexPlan"
    actual = {}
    for k, v in _tileplan_actual(plan.pass1).items():
        actual[f"pass1.{k}"] = v
    for k, v in _tileplan_actual(plan.pass2).items():
        actual[f"pass2.{k}"] = v
    actual["block_sizes"] = plan.block_sizes.nbytes
    actual["link_counts"] = plan.link_counts.nbytes
    if plan.p1_ell is not None:
        actual["p1_ell"] = plan.p1_ell.nbytes
    if plan.p2_ell is not None:
        actual["p2_ell"] = plan.p2_ell.nbytes
    assert plan.array_nbytes() == actual  # array-by-array, not just total
    assert plan.plan_nbytes() == sum(actual.values())
    # and EXPLAIN carries the same number per term
    rep = sess.explain()
    assert rep.groups[0].terms[0].plan_nbytes == plan.plan_nbytes()
    assert rep.total_plan_nbytes == plan.plan_nbytes()


def test_iindex_plan_nbytes_byte_exact():
    g = with_random_attrs(random_dag(300, 2.5, seed=5), seed=6)
    sess = Session(g, [QuerySpec("topological", "sum")], device=True,
                   use_pallas=False)
    plan = next(iter(sess._states.values())).plan
    assert type(plan).__name__ == "IIndexPlan"
    actual = {f"wd_plan.{k}": v
              for k, v in _tileplan_actual(plan.wd_plan).items()}
    actual["pid"] = plan.pid.nbytes
    actual["level"] = plan.level.nbytes
    assert plan.array_nbytes() == actual
    assert plan.plan_nbytes() == sum(actual.values())
    assert sess.explain().total_plan_nbytes == plan.plan_nbytes()


def test_sharded_plan_nbytes_byte_exact():
    # 1-device CPU mesh: exercises the full sharded code path in tier-1
    mesh = jax.make_mesh((1,), ("data",))
    g = with_random_attrs(erdos_renyi(200, 4.0, seed=1), seed=2)
    sess = Session(g, [QuerySpec(("khop", 1), "sum")], mesh=mesh,
                   use_pallas=False)
    plan = next(iter(sess._states.values())).plan
    assert type(plan).__name__ == "ShardedDBPlan"
    actual = {"p1_gather": plan.p1_gather.nbytes,
              "p1_seg": plan.p1_seg.nbytes,
              "p2_gather": plan.p2_gather.nbytes,
              "p2_seg": plan.p2_seg.nbytes,
              "block_sizes": plan.block_sizes.nbytes}
    if plan.has_ell:
        actual.update(e1=plan.e1.nbytes, e1_ids=plan.e1_ids.nbytes,
                      e2=plan.e2.nbytes, e2_ids=plan.e2_ids.nbytes)
    assert plan.array_nbytes() == actual
    assert plan.plan_nbytes() == sum(actual.values())
    rep = sess.explain()
    assert rep.sharded
    term = rep.groups[0].terms[0]
    assert term.plan_nbytes == plan.plan_nbytes()
    bal = term.plan["shard_balance"]
    assert bal["pass1"]["rows_per_shard"] == [term.plan["rows1_per_shard"]]
    assert bal["pass1"]["balance"] == 1.0  # one shard is trivially balanced


# ---------------------------------------------------------------------- #
#  EXPLAIN: candidates, lowering, stability under streaming
# ---------------------------------------------------------------------- #
def test_explain_candidates_carry_rejection_reasons():
    g = with_random_attrs(erdos_renyi(200, 4.0, directed=False, seed=1),
                          seed=2)
    sess = Session(g, [QuerySpec(("khop", 1), "sum")], device=True,
                   use_pallas=False)
    grp = sess.explain().groups[0]
    assert grp.engine == "jax"
    by_name = {c["name"]: c for c in grp.candidates}
    assert by_name["jax"]["selected"]
    # every non-selected candidate explains itself
    for name, c in by_name.items():
        if not c["selected"]:
            assert c["reason"], name
    assert "priority" in by_name["dbindex"]["reason"]
    assert "not served" in by_name["iindex"]["reason"]
    assert "mesh" in by_name["jax-sharded"]["reason"]


def test_explain_does_not_execute_or_recompile():
    g = with_random_attrs(erdos_renyi(200, 4.0, directed=False, seed=1),
                          seed=2)
    sess = Session(g, [QuerySpec(("khop", 1), "sum")], device=True,
                   use_pallas=False)
    c0 = recompile_count()
    rep = sess.explain()
    assert recompile_count() == c0  # no jitted executor was entered
    json.loads(rep.to_json())  # fully serializable
    assert "engine: jax" in rep.text()


def test_explain_stable_across_streamed_batches():
    g = with_random_attrs(erdos_renyi(400, 4.0, directed=False, seed=11),
                          seed=12)
    specs = [QuerySpec(("khop", 1), a) for a in ("sum", "min", "avg")]
    sess = Session(g, specs, device=True, use_pallas=False,
                   plan_headroom=1.0)
    sess.run()
    first = sess.explain()
    lowering0 = first.groups[0].lowering["choice"]
    nbytes0 = first.total_plan_nbytes
    rng = np.random.default_rng(13)
    for step in range(10):
        sess.update(mixed(sess.graph, rng, 4, 2))
        rep = sess.explain()
        assert rep.groups[0].lowering["choice"] == lowering0
        assert rep.groups[0].engine == first.groups[0].engine
        # static shapes: plan patching never changes the footprint
        assert rep.total_plan_nbytes == nbytes0, step
        assert rep.version == step + 1


def test_composite_lowering_choices():
    g = with_random_attrs(erdos_renyi(250, 4.0, directed=True, seed=3),
                          seed=4)
    u = Union(KHop(2, "in"), KHopWindow(2))
    # same window, one session each: aggs on one window fuse into one group
    s_min = Session(g, [QuerySpec(u, "min")], device=True, use_pallas=False)
    s_sum = Session(g, [QuerySpec(u, "sum")], device=True, use_pallas=False)
    lo_min = s_min.explain().groups[0].lowering
    assert lo_min["choice"] == "idempotent-combine"
    assert len(lo_min["terms"]) == 2  # no intersection term needed
    lo_sum = s_sum.explain().groups[0].lowering
    assert lo_sum["choice"] == "inclusion-exclusion"
    assert len(lo_sum["terms"]) == 3  # A, B, A∩B
    assert sorted(lo_sum["sum_coefs"]) == [-1, 1, 1]
    assert any(r["choice"] == "idempotent-combine"
               for r in lo_sum["rejected"])


@pytest.mark.parametrize("use_pallas", [True, False])
def test_explain_names_the_minmax_route(use_pallas):
    """EXPLAIN names how each term's min/max reduce: ``ell`` on a DBIndex
    plan with ELL layouts, else ``tiled`` (Pallas) or ``xla``; the I-Index
    never has ELL layouts.  The text view shows it too."""
    from repro.graphs.generators import barabasi_albert

    tiled = "tiled" if use_pallas else "xla"
    cases = [
        (erdos_renyi(200, 4.0, directed=False, seed=1), ("khop", 1), "ell"),
        # a hub tree: its blocks are too wide for the ELL layout
        (barabasi_albert(500, 1, seed=0), ("khop", 2), tiled),
        (random_dag(150, 2.0, seed=2), "topological", tiled),
    ]
    for g, window, route in cases:
        sess = Session(with_random_attrs(g, seed=5),
                       [QuerySpec(window, "max")], device=True,
                       use_pallas=use_pallas)
        rep = sess.explain()
        assert rep.groups[0].terms[0].plan["minmax_route"] == route, window
        assert f"plan.minmax_route: {route}" in rep.text()


@pytest.mark.parametrize("page_tiles,use_pallas,pages",
                         [(None, True, 1), (2, True, None), (None, False, None)])
def test_explain_shows_the_kernel_pages(page_tiles, use_pallas, pages,
                                        monkeypatch):
    """EXPLAIN shows ``plan.pages``: the Pallas calls each segment kernel
    takes over the plan's largest pass (1 under ``PAGE_TILES``; None where
    the XLA segment ops run)."""
    from repro.kernels.segment_reduce import segment_reduce as sr

    if page_tiles is not None:
        monkeypatch.setattr(sr, "PAGE_TILES", page_tiles)
    g = with_random_attrs(random_dag(150, 2.0, seed=2), seed=5)
    sess = Session(g, [QuerySpec("topological", "sum")], device=True,
                   use_pallas=use_pallas)
    rep = sess.explain()
    plan = sess.snapshot().artifacts[0][0][1]
    want = pages if pages is not None or not use_pallas \
        else -(-plan.wd_plan.seg_tiles.shape[0] // page_tiles)
    assert rep.groups[0].terms[0].plan["pages"] == want
    assert f"plan.pages: {want}" in rep.text()


def test_explain_spec_filter_selects_one_group():
    g = with_random_attrs(erdos_renyi(200, 4.0, directed=False, seed=1),
                          seed=2)
    specs = [QuerySpec(("khop", 1), "sum"), QuerySpec(("khop", 2), "min")]
    sess = Session(g, specs, device=True, use_pallas=False)
    assert len(sess.explain().groups) == 2
    only = sess.explain(specs[1])
    assert len(only.groups) == 1
    assert only.groups[0].window == "khop[2]"
    with pytest.raises(KeyError):
        sess.explain(QuerySpec(("khop", 3), "sum"))


# ---------------------------------------------------------------------- #
#  ANALYZE: phase attribution
# ---------------------------------------------------------------------- #
def test_analyze_attributes_wall_time_and_keeps_caches_cold():
    # big enough that device phases dominate the fixed Python glue; the
    # attribution contract targets real workloads, not microbenchmarks
    g = with_random_attrs(erdos_renyi(2000, 8.0, directed=False, seed=21),
                          seed=22)
    specs = [QuerySpec(("khop", 1), a) for a in ("sum", "min", "avg")]
    sess = Session(g, specs, device=True, use_pallas=False)
    sess.run()
    c0 = recompile_count()
    sess.analyze()  # warm the eager op-by-op dispatch path
    rep = sess.analyze()
    assert rep.attribution >= 0.95, rep.attribution
    assert recompile_count() == c0  # eager mirror, tracked jits untouched
    phases = {p["phase"] for p in rep.phases}
    assert {"pass1_reduce", "pass2_gather", "pass2_reduce",
            "finalize"} <= phases
    txt = rep.text()
    for name in sorted(phases):
        assert name in txt
    json.loads(rep.to_json())


def test_analyze_iindex_and_composite_phases():
    gd = with_random_attrs(random_dag(300, 2.5, seed=5), seed=6)
    s_topo = Session(gd, [QuerySpec("topological", "sum"),
                          QuerySpec("topological", "min")],
                     device=True, use_pallas=False)
    s_topo.run()
    s_topo.analyze()
    rep = s_topo.analyze()
    assert rep.attribution >= 0.95, rep.attribution
    assert {"gather", "wd_reduce", "inherit",
            "finalize"} <= {p["phase"] for p in rep.phases}

    g = with_random_attrs(erdos_renyi(600, 5.0, directed=True, seed=3),
                          seed=4)
    u = Union(KHop(2, "in"), KHopWindow(2))
    s_u = Session(g, [QuerySpec(u, "sum")], device=True, use_pallas=False)
    s_u.run()
    s_u.analyze()
    rep = max((s_u.analyze() for _ in range(2)),
              key=lambda r: r.attribution)
    assert rep.attribution >= 0.95, rep.attribution
    # three dbindex terms (A, B, A∩B) plus the host-side recombination
    assert "host_combine" in {p["phase"] for p in rep.phases}
    assert len({p["term"] for p in rep.phases}) >= 3


# ---------------------------------------------------------------------- #
#  Flight recorder + debug_report
# ---------------------------------------------------------------------- #
def test_flight_recorder_ring_bounds_and_dump(tmp_path):
    fr = FlightRecorder(capacity=4)
    for i in range(10):
        fr.record("admit", rid=i)
    assert len(fr) == 4 and fr.capacity == 4
    assert fr.dropped == 6
    evs = fr.dump()
    assert [e["rid"] for e in evs] == [6, 7, 8, 9]  # oldest evicted first
    assert [e["seq"] for e in evs] == [7, 8, 9, 10]
    assert all(e["event"] == "admit" for e in evs)
    assert fr.tail(2) == evs[-2:]
    path = fr.dump_json(tmp_path / "flight.json")
    loaded = json.loads(open(path).read())
    assert loaded["dropped"] == 6 and len(loaded["events"]) == 4


def _int_service(n=200, seed=7, bucket=4):
    g = erdos_renyi(n, 4.0, directed=False, seed=seed)
    vals = np.random.default_rng(seed + 1).integers(0, 50, g.n)
    g = g.with_attr("val", vals.astype(np.float64))
    sess = Session(g, [QuerySpec(("khop", 1), "sum")], device=True,
                   use_pallas=False)
    return WindowService(sess, bucket=bucket)


def test_service_flight_events_follow_taxonomy():
    svc = _int_service()
    for v in (3, 5, 9, 11):
        svc.submit(0, v)
    svc.flush()
    rng = np.random.default_rng(9)
    svc.update(mixed(svc.session.graph, rng, 4, 2))
    svc.submit(0, 2)
    svc.flush()
    events = [e["event"] for e in svc.flight.dump()]
    assert set(events) <= set(EVENT_TYPES)
    assert events.count("admit") == 5
    assert "flush" in events and "patch" in events and "flip" in events
    # ordering: the patch lands before the flip that publishes it
    assert events.index("patch") < events.index("flip")
    flush_ev = next(e for e in svc.flight.dump() if e["event"] == "flush")
    assert flush_ev["served"] == 4 and flush_ev["failed"] == 0


def test_ticket_failure_auto_dumps_flight_record():
    svc = _int_service()
    svc.submit(0, 3)
    svc.flush()
    assert svc.last_flight_record is None  # healthy serving: no dump
    # explicit values bypass the result cache: the launch path must run
    vb = np.arange(svc.session.graph.n, dtype=np.float64)
    t = svc.submit(0, 7, values=vb)

    def boom(*a, **k):
        raise RuntimeError("injected failure")

    object.__setattr__(svc._active, "run_group", boom)
    object.__setattr__(svc._active, "run_group_many", boom)
    svc.flush()
    assert isinstance(t.error, RuntimeError)
    rec = svc.last_flight_record
    assert rec is not None
    fails = [e for e in rec if e["event"] == "failure"]
    assert len(fails) == 1
    assert fails[0]["error"] == "RuntimeError"
    assert "injected failure" in fails[0]["detail"]
    # the record carries the causal history, not just the failure
    assert [e["event"] for e in rec][0] == "admit"
    json.dumps(rec)  # CI artifact hook serializes this as-is


def test_debug_report_shape_and_padding_accounting():
    svc = _int_service(bucket=4)
    rng = np.random.default_rng(31)
    # explicit-values requests force batched run_many launches (padding)
    vb = rng.integers(0, 50, svc.session.graph.n).astype(np.float64)
    for _ in range(3):
        svc.submit(0, values=vb)
    svc.flush()
    rep = svc.debug_report()
    assert set(rep) >= {"stats", "padding", "staleness",
                        "plan_footprint_bytes", "flight",
                        "last_flight_record"}
    pad = rep["padding"]
    assert pad["bucket"] == 4
    assert pad["batched_launches"] == 1
    assert pad["padded_rows"] == 1  # 3 requests pad to one bucket of 4
    assert pad["waste_fraction"] == 0.25
    assert rep["plan_footprint_bytes"] == int(
        svc.session.explain().total_plan_nbytes)
    assert rep["flight"]["capacity"] == svc.flight.capacity
    json.dumps(rep["flight"])
