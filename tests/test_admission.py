"""Admission control reads the index's staleness once per index object:
the link scan behind ``pressure()`` is memoized on the immutable DBIndex,
and what admission decides from it is exactly what a fresh scan gives."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.api import QuerySpec, Session  # noqa: E402
from repro.core.dbindex import link_scans  # noqa: E402
from repro.core.query import brute_force  # noqa: E402
from repro.core.streaming import StalenessPolicy  # noqa: E402
from repro.core.updates import UpdateBatch  # noqa: E402
from repro.core.windows import KHopWindow  # noqa: E402
from repro.obs import Tracer  # noqa: E402
from repro.serve import AsyncWindowService  # noqa: E402

from test_async_service import int_graph  # noqa: E402
from test_updates import random_delete_batch, random_insert_batch  # noqa: E402

# growth ratios never trip: only the garbage ratio reorganizes
GARBAGE_POLICY = StalenessPolicy(max_link_ratio=100.0, max_block_ratio=100.0,
                                 max_garbage_ratio=0.25, min_batches=1)


def fresh_garbage(index) -> float:
    """The zero-link block fraction by a scan of its own."""
    if index.num_blocks == 0:
        return 0.0
    linked = np.zeros(index.num_blocks, dtype=bool)
    linked[index.link_block] = True
    return 1.0 - int(np.count_nonzero(linked)) / index.num_blocks


def fresh_pressure(session, policy) -> float:
    """``AsyncWindowService.pressure`` recomputed from fresh scans."""
    p = 0.0
    for eng in session._states.values():
        p = max(
            p,
            (eng.staleness["link_ratio"] - 1.0) / (policy.max_link_ratio - 1.0),
            (eng.staleness["block_ratio"] - 1.0)
            / (policy.max_block_ratio - 1.0),
            fresh_garbage(eng.index) / policy.max_garbage_ratio,
        )
    return float(min(max(p, 0.0), 1.0))


def make_service(seed, tracer=None):
    g = int_graph(80, 2.5, seed)
    specs = [QuerySpec(KHopWindow(2), "sum")]
    sess = Session(g, specs, use_pallas=False, policy=GARBAGE_POLICY,
                   tracer=tracer)
    svc = AsyncWindowService(sess, bucket=4, policy=GARBAGE_POLICY,
                             tracer=tracer)
    return g, sess, svc


def test_point_reads_between_value_writes_scan_the_index_once():
    """Value writes leave the index object as it was, so N admissions and
    N writes cost one link scan, counted in ``staleness_scans``; a
    structural update brings exactly one more (its new index)."""
    tr = Tracer()
    g, sess, svc = make_service(seed=61, tracer=tr)
    (eng,) = sess._states.values()
    index0 = eng.index
    rng = np.random.default_rng(62)
    n_reads = 12
    with svc:
        for i in range(n_reads):
            v = int(rng.integers(g.n))
            got = svc.submit(0, vertex=v).get(timeout=10.0)
            vals = np.asarray(svc.session.graph.attrs["val"], np.float64)
            want = brute_force(svc.session.graph, KHopWindow(2), vals, "sum",
                               dtype=np.float32)[v]
            assert got == want
            svc.update(UpdateBatch.attr_set("val", [v], [float(i)]))
        assert eng.index is index0
        assert svc.staleness_scans == 1
        assert svc.stats["staleness_scans"] == 1

        svc.update(random_insert_batch(svc.session.graph, rng, 3))
        assert eng.index is not index0
        svc.submit(0, vertex=0).get(timeout=10.0)
        assert svc.staleness_scans == 2
    admits = [e["args"]["admit_ms"] for e in tr.events()
              if e["ph"] == "X" and e["name"] == "request"]
    assert len(admits) == n_reads + 1
    assert all(a >= 0.0 for a in admits)


def test_pressure_equals_a_fresh_scan_through_garbage_and_reorganize():
    """A delete-dominated edge stream grows garbage blocks until the
    garbage ratio reorganizes; after every batch ``Session.staleness`` and
    ``pressure()`` equal what fresh, unmemoized scans give."""
    g, sess, svc = make_service(seed=63)
    (eng,) = sess._states.values()
    rng = np.random.default_rng(64)
    saw_garbage = False
    for step in range(8):
        svc.update(random_delete_batch(svc.session.graph, rng, 12))
        (st,) = sess.staleness.values()
        assert st["garbage_ratio"] == fresh_garbage(eng.index), step
        assert svc.pressure() == fresh_pressure(sess, GARBAGE_POLICY), step
        # asked again, the memo answers the same
        assert svc.pressure() == fresh_pressure(sess, GARBAGE_POLICY), step
        assert svc.effective_max_pending() == int(
            svc.bucket + (svc.max_pending - svc.bucket)
            * (1.0 - fresh_pressure(sess, GARBAGE_POLICY)))
        saw_garbage |= st["garbage_ratio"] > 0.0
    assert saw_garbage, "delete stream never produced garbage blocks"
    assert eng.reorg_count >= 1, "garbage never tripped the reorganize"
    svc.close()


def test_admission_scans_nothing_once_the_index_is_scanned():
    """With the index scanned, an admission check runs no link scan on
    the submitting thread."""
    g, sess, svc = make_service(seed=65)
    svc.pressure()
    before = link_scans()
    for v in range(5):
        svc.submit(0, vertex=v)
        svc.effective_max_pending()
    assert link_scans() == before
    svc.flush()
    assert svc.staleness_scans == 1
