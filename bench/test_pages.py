"""The serving path over plans the segment kernels reduce in several pages
(Pallas calls), on the CPU: ``PAGE_TILES`` is cut to a few tiles, so a
small Kronecker graph at Graph500's edge factor takes as many pages as the
``graph500-kron`` plan does on the chip.  The jit caches are cleared
around each test, since a cached trace does not see the constant change."""

import time

import numpy as np
import pytest

from bench import graphs, oracle

PAGE = 2  # tiles a page in these tests


@pytest.fixture
def small_pages(monkeypatch):
    import jax

    from repro.kernels.segment_reduce import segment_reduce as sr

    jax.clear_caches()
    monkeypatch.setattr(sr, "PAGE_TILES", PAGE)
    yield PAGE
    jax.clear_caches()


def _spans(tracer, name):
    return [e for e in tracer.events()
            if e.get("ph") == "X" and e["name"] == name]


def test_service_on_paged_plans_is_exact_through_writes(small_pages):
    """``Session(device=True)`` behind ``AsyncWindowService`` on a 2-hop
    plan of several pages: every point read after each value write equals
    ``bench/oracle.py`` at that write's values, and the spans carry
    ``pages`` > 1 and the invalidated ``owners``."""
    from repro.core.api import QuerySpec, Session
    from repro.core.graph import Graph
    from repro.core.updates import UpdateBatch
    from repro.obs.tracing import Tracer
    from repro.serve import AsyncWindowService

    n, aggs = 512, ("sum", "count", "avg", "min", "max")
    src, dst = graphs.rmat(np.random.default_rng(0), n, 16,
                           [0.57, 0.19, 0.19, 0.05])
    rng = np.random.default_rng(1)
    vals = rng.integers(0, 100, n).astype(np.float64)
    g = Graph(n=n, src=src, dst=dst, directed=False).with_attr("val", vals)
    tracer = Tracer()
    sess = Session(g, [QuerySpec(("khop", 2), a, attr="val", engine="jax")
                       for a in aggs], device=True, tracer=tracer)
    svc = AsyncWindowService(sess, bucket=8, auto_flip=True,
                             tracer=tracer).start()
    ref_graph = oracle.RefGraph(n, src, dst, False)
    probe = np.unique(np.concatenate([rng.integers(0, n, 24),
                                      np.argsort(-np.bincount(src, None, n))[:4]]))
    indptr, members = oracle.windows(ref_graph, {"kind": "khop", "k": 2},
                                     probe)
    try:
        for step in range(3):
            if step:
                v, x = int(probe[step]), float(rng.integers(0, 100))
                svc.update(UpdateBatch.attr_set("val", [v], [x]))
                vals[v] = x
            tickets = [(a, i, svc.submit(a, vertex=int(v)))
                       for a in range(len(aggs)) for i, v in enumerate(probe)]
            for a, i, t in tickets:
                want = oracle.reduce(vals, indptr, members, aggs[a])[i]
                assert np.float32(t.get(timeout=120)) == want, (step, a, i)
    finally:
        svc.stop(drain=True)
    plan = sess.snapshot().artifacts[0][0][1]
    assert plan.pass1.pages > 1
    groups = _spans(tracer, "query.group")
    assert groups and all(e["args"]["pages"] == plan.pass1.pages
                          for e in groups)
    owners = [e["args"]["owners"] for e in _spans(tracer, "service.update")]
    assert len(owners) == 2 and all(0 < o <= n for o in owners)


def test_kron_cell_runs_paged_end_to_end(small_pages, tmp_path):
    """``graph500-kron.read-attr`` at a tiny size with pages of a few
    tiles, for long enough to hold a write: ``correct``, no compile in the
    window, ``pages`` > 1 on every refresh."""
    from bench.cell import Cell, Run

    from bench.test_cells import N, ROOT

    cell = Cell.find("graph500-kron.read-attr", ROOT)
    run = Run(cell, 4_000_000_019, 7.0, False, t_start=time.perf_counter(),
              n=N, compile_cache=False, trace_dir=tmp_path / "trace")
    run.setup()
    run.run()
    run.end_to_end()
    checks = run.check()
    assert all(c["value"] <= c["limit"] for c in checks.values()), checks
    assert run.notes["compared"] > 0 and run.notes["writes"] > 0
    assert run.notes["compiles_in_window"] == 0
    assert run.notes["executor_retraces"] == 0
    pages = {e["args"].get("pages") for e in _spans(run.tracer,
                                                    "query.group")}
    assert pages and min(pages) > 1, pages
