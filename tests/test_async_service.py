"""AsyncWindowService: deadline flushing, load shedding, backpressure,
and the exception-safe request lifecycle (ISSUE 6).

Threaded tests are structured so the flusher is either *provably idle*
(deadlines far in the future) or *deliberately blocked* (the test holds
``_flush_lock``), never raced: assertions are on ticket completion events
and monotonic counters, not on sleeps.
"""

import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import api  # noqa: E402
from repro.core.api import QuerySpec, Session  # noqa: E402
from repro.core.query import brute_force  # noqa: E402
from repro.core.updates import UpdateBatch  # noqa: E402
from repro.core.windows import KHopWindow  # noqa: E402
from repro.graphs.generators import erdos_renyi  # noqa: E402
from repro.serve import (  # noqa: E402
    AsyncWindowService,
    DEFAULT_REQUEST_CLASSES,
    LoadShedError,
    RequestClass,
    WindowService,
)

from test_updates import mixed  # noqa: E402


def int_graph(n, deg, seed):
    g = erdos_renyi(n, deg, directed=False, seed=seed)
    vals = np.random.default_rng(seed + 1).integers(0, 50, g.n)
    return g.with_attr("val", vals.astype(np.float64))


def make_session(seed=7, n=80):
    g = int_graph(n, 2.5, seed)
    specs = [QuerySpec(KHopWindow(2), "sum"), QuerySpec(KHopWindow(2), "min")]
    return g, specs, Session(g, specs, use_pallas=False)


# a class whose deadline can never fire within a test run: flushes happen
# only on fill (or explicit stop/flush)
NEVER = RequestClass("never", max_delay_ms=600_000.0, priority=5,
                     sheddable=True)
NEVER_POINT = RequestClass("never-point", max_delay_ms=600_000.0,
                           priority=100, sheddable=False)


# ---------------------------------------------------------------------- #
#  Deadline-driven flushing
# ---------------------------------------------------------------------- #
def test_deadline_flush_serves_sub_bucket_request():
    """A single point read in an otherwise idle service must be served by
    its class deadline, not wait for the bucket to fill."""
    g, specs, sess = make_session()
    with AsyncWindowService(sess, bucket=64) as svc:
        t = svc.submit(0, vertex=3)  # point class: 2 ms deadline
        got = t.get(timeout=10.0)
        assert svc.deadline_flushes >= 1
        assert svc.fill_flushes == 0
    oracle = brute_force(g, KHopWindow(2),
                         np.asarray(g.attrs["val"], np.float64), "sum",
                         dtype=np.float32)
    assert got == oracle[3]
    assert t.latency_s is not None and t.request_class.name == "point"


def test_deadline_flush_full_scan_and_classes():
    g, specs, sess = make_session(seed=9)
    with AsyncWindowService(sess, bucket=64) as svc:
        t0 = svc.submit(0)  # default full-scan class: interactive, 5 ms
        t1 = svc.submit(1, request_class="batch")
        a, b = t0.get(timeout=10.0), t1.get(timeout=10.0)
        assert t0.request_class is DEFAULT_REQUEST_CLASSES["interactive"]
        assert t1.request_class is DEFAULT_REQUEST_CLASSES["batch"]
    vals = np.asarray(g.attrs["val"], np.float64)
    assert np.array_equal(
        a, brute_force(g, KHopWindow(2), vals, "sum", dtype=np.float32))
    assert np.array_equal(
        b, brute_force(g, KHopWindow(2), vals, "min", dtype=np.float32))


def test_fill_flush_at_bucket():
    """With deadlines effectively infinite, the bucket filling is the only
    trigger — the flusher must launch on the fill edge."""
    g, specs, sess = make_session(seed=11)
    vals = np.asarray(g.attrs["val"], np.float64)
    oracle = brute_force(g, KHopWindow(2), vals, "sum", dtype=np.float32)
    with AsyncWindowService(sess, bucket=4, classes={"never": NEVER}) as svc:
        tickets = [svc.submit(0, vertex=i, request_class="never")
                   for i in range(4)]
        for i, t in enumerate(tickets):
            assert t.get(timeout=10.0) == oracle[i]
        assert svc.fill_flushes >= 1
        assert svc.deadline_flushes == 0


def test_explicit_values_through_async_path():
    g, specs, sess = make_session(seed=13)
    rng = np.random.default_rng(14)
    with AsyncWindowService(sess, bucket=4) as svc:
        vecs = [rng.integers(0, 9, g.n).astype(np.float64) for _ in range(3)]
        tickets = [svc.submit(0, values=v) for v in vecs]
        for t, v in zip(tickets, vecs):
            got = t.get(timeout=10.0)
            want = brute_force(g, KHopWindow(2), v, "sum", dtype=np.float32)
            assert np.array_equal(got, want)


def test_updates_interleaved_with_async_reads():
    """Reads always see a complete published version while the write head
    advances underneath."""
    g, specs, sess = make_session(seed=15)
    rng = np.random.default_rng(16)
    with AsyncWindowService(sess, bucket=64) as svc:
        for _ in range(4):
            svc.update(mixed(svc.session.graph, rng, 3, 1))
            got = svc.submit(0).get(timeout=10.0)
            gg = svc.session.graph
            want = brute_force(gg, KHopWindow(2),
                               np.asarray(gg.attrs["val"], np.float64),
                               "sum", dtype=np.float32)
            assert np.array_equal(got, want)


# ---------------------------------------------------------------------- #
#  Load shedding + backpressure
# ---------------------------------------------------------------------- #
def test_shed_evicts_lowest_priority_scan_never_point_reads():
    g, specs, sess = make_session(seed=17)
    svc = AsyncWindowService(
        sess, bucket=4, max_pending=8,
        classes={"never": NEVER, "never-point": NEVER_POINT},
        default_class="never",
    )
    # block the flusher so the queue holds still while we assert on it
    svc._flush_lock.acquire()
    try:
        svc.start()
        low = [svc.submit(0, request_class="batch") for _ in range(2)]
        high = [svc.submit(0, request_class="never") for _ in range(6)]
        # queue is now at max_pending=8; a point read must evict the
        # NEWEST lowest-priority sheddable scan, never another point read
        pt = svc.submit(0, vertex=1, request_class="never-point")
        victim = low[1]
        assert victim.done and victim.failed
        assert isinstance(victim.error, LoadShedError)
        with pytest.raises(LoadShedError):
            victim.get(timeout=0)
        assert not low[0].done and not pt.done
        assert svc.shed == 1

        # an incoming request that is itself the lowest-priority sheddable
        # scan is rejected at admission
        with pytest.raises(LoadShedError):
            svc.submit(0, request_class="batch")
        assert svc.shed == 2

        # a higher-priority scan instead evicts the remaining batch ticket
        t2 = svc.submit(0, request_class="never")
        assert low[0].done and isinstance(low[0].error, LoadShedError)
        assert svc.shed == 3

        # queue again full, all sheddable scans outrank "batch": sheds
        # drain down the priority ladder, eventually hitting "never" scans
        t3 = svc.submit(0, vertex=2, request_class="never-point")
        assert svc.shed == 4
        survivors = [t for t in high + [t2, t3, pt] if not t.done]
        assert pt in survivors and t3 in survivors
    finally:
        svc._flush_lock.release()
    # unblocked flusher serves every survivor
    for t in [pt, t3]:
        assert t.get(timeout=10.0) is not None
    svc.stop()
    assert svc.stats["failed"] == svc.shed == 4


def test_backpressure_waits_when_nothing_sheddable():
    """All-point-read queue: nothing is sheddable, so an over-admission
    submit must *wait* for the flusher to drain, then succeed."""
    g, specs, sess = make_session(seed=19)
    svc = AsyncWindowService(
        sess, bucket=4, max_pending=4,
        classes={"never-point": NEVER_POINT}, default_class="never-point",
    )
    svc._flush_lock.acquire()
    release_at = None
    try:
        svc.start()
        pts = [svc.submit(0, vertex=i, request_class="never-point")
               for i in range(4)]
        assert len(svc._pending) == 4
        # free the flusher shortly; the submit below must block until then
        release_at = threading.Timer(0.1, svc._flush_lock.release)
        release_at.start()
        # default "point" class: once admitted, its 2 ms deadline flushes it
        t = svc.submit(0, vertex=9)
        assert svc.backpressure_waits >= 1
        for p in pts + [t]:
            assert p.get(timeout=10.0) is not None
    finally:
        if release_at is None:
            svc._flush_lock.release()
    svc.stop()
    assert svc.shed == 0 and svc.stats["failed"] == 0


def test_pressure_and_effective_window():
    g, specs, sess = make_session(seed=21)
    svc = AsyncWindowService(sess, bucket=4, max_pending=64)
    assert 0.0 <= svc.pressure() <= 1.0
    assert svc.pressure() == 0.0  # fresh index is its own baseline
    assert svc.effective_max_pending() == 64
    rng = np.random.default_rng(22)
    for _ in range(6):
        svc.update(mixed(svc.session.graph, rng, 6, 4))
    p = svc.pressure()
    assert 0.0 <= p <= 1.0
    eff = svc.effective_max_pending()
    assert svc.bucket <= eff <= svc.max_pending
    assert eff == int(4 + 60 * (1.0 - p))
    svc.close()


# ---------------------------------------------------------------------- #
#  Exception-safe flush (satellite: sync WindowService lifecycle)
# ---------------------------------------------------------------------- #
def test_flush_failure_isolated_to_affected_tickets(monkeypatch):
    """A raise mid-flush fails only the tickets whose launch raised; every
    other ticket in the same flush is served, the queue ends empty, and
    the next flush works."""
    g, specs, sess = make_session(seed=23)
    svc = WindowService(sess, bucket=4)
    vals = np.asarray(g.attrs["val"], np.float64)
    oracle = brute_force(g, KHopWindow(2), vals, "sum", dtype=np.float32)

    boom = RuntimeError("injected launch failure")
    real = api.SessionView.run_group_many
    monkeypatch.setattr(api.SessionView, "run_group_many",
                        lambda self, gi, vb: (_ for _ in ()).throw(boom))
    bad = [svc.submit(0, values=vals) for _ in range(2)]
    good = [svc.submit(0, vertex=5), svc.submit(1)]
    served = svc.flush()
    assert len(served) == 4 and len(svc._pending) == 0
    for t in bad:
        assert t.done and t.error is boom
        with pytest.raises(RuntimeError, match="injected"):
            t.get(timeout=0)
    assert good[0].error is None and good[0].result == oracle[5]
    assert good[1].error is None
    assert svc.stats["failed"] == 2 and svc.stats["served"] == 2

    # recovery: the very next flush serves the same shape of request
    monkeypatch.setattr(api.SessionView, "run_group_many", real)
    t = svc.submit(0, values=vals)
    svc.flush()
    assert np.array_equal(t.get(timeout=0), oracle)
    assert svc.stats["failed"] == 2  # no lingering poison


def test_snapshot_launch_failure_poisons_memo_not_queue(monkeypatch):
    """A failing cached-read launch fails every same-group ticket in that
    flush via the memo (one launch attempt, not N), leaves other groups
    served, and clears on the next flush."""
    g, specs, sess = make_session(seed=25)
    svc = WindowService(sess, bucket=4, use_cache=False)
    calls = {"n": 0}
    real = api.SessionView.run_group

    def failing(self, gi, values=None):
        calls["n"] += 1
        raise RuntimeError("injected snapshot failure")

    monkeypatch.setattr(api.SessionView, "run_group", failing)
    tickets = [svc.submit(0, vertex=i) for i in range(3)]
    svc.flush()
    assert calls["n"] == 1, "poisoned memo must prevent repeat launches"
    for t in tickets:
        assert isinstance(t.error, RuntimeError)
    monkeypatch.setattr(api.SessionView, "run_group", real)
    assert svc.query(0, vertex=0) is not None  # clean next flush


def test_malformed_request_fails_at_submit_not_flush():
    g, specs, sess = make_session(seed=27)
    svc = WindowService(sess, bucket=4)
    with pytest.raises(IndexError):
        svc.submit(0, vertex=g.n + 5)
    with pytest.raises(ValueError):
        svc.submit(0, values=np.zeros(g.n - 1))
    with pytest.raises((KeyError, IndexError, TypeError)):
        svc.submit(99)
    assert len(svc._pending) == 0  # nothing half-enqueued
    assert svc.query(0, vertex=0) is not None


def test_ticket_get_timeout_and_error_contract():
    g, specs, sess = make_session(seed=29)
    svc = WindowService(sess, bucket=64)
    t = svc.submit(0, vertex=0)
    assert not t.done
    with pytest.raises(TimeoutError):
        t.get(timeout=0.01)
    svc.flush()
    assert t.done and t.get(timeout=0) is not None


# ---------------------------------------------------------------------- #
#  Lifecycle
# ---------------------------------------------------------------------- #
def test_stop_drain_serves_leftovers():
    g, specs, sess = make_session(seed=31)
    svc = AsyncWindowService(sess, bucket=64, classes={"never": NEVER},
                             default_class="never").start()
    tickets = [svc.submit(0, request_class="never") for _ in range(3)]
    svc.stop(drain=True)
    for t in tickets:
        assert t.done and t.error is None


def test_stop_without_drain_fails_leftovers():
    g, specs, sess = make_session(seed=33)
    svc = AsyncWindowService(sess, bucket=64, classes={"never": NEVER},
                             default_class="never").start()
    tickets = [svc.submit(0, request_class="never") for _ in range(3)]
    svc.stop(drain=False)
    for t in tickets:
        assert t.done and isinstance(t.error, LoadShedError)
    assert svc.stats["failed"] == 3


def test_unstarted_service_degrades_to_synchronous():
    g, specs, sess = make_session(seed=35)
    svc = AsyncWindowService(sess, bucket=2)
    assert not svc.running
    t0 = svc.submit(0, vertex=0)
    t1 = svc.submit(0, vertex=1)  # fill edge: synchronous flush
    assert t0.done and t1.done
    vals = np.asarray(g.attrs["val"], np.float64)
    oracle = brute_force(g, KHopWindow(2), vals, "sum", dtype=np.float32)
    assert t0.get(timeout=0) == oracle[0] and t1.get(timeout=0) == oracle[1]


# ---------------------------------------------------------------------- #
#  Deterministic deadline scheduling (ISSUE 7: injected clock)
# ---------------------------------------------------------------------- #
class FakeClock:
    """A manually advanced monotonic clock injected via ``now_fn`` — the
    scheduling decision (:meth:`AsyncWindowService._due_reason`) runs on
    it, so deadline behavior is asserted exactly, no sleeps or jitter."""

    def __init__(self, t=100.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def test_deadline_fires_exactly_on_fake_clock():
    """Sub-bucket queue: not due one tick before the class deadline, due
    exactly at it — and the trigger is recorded as a deadline flush."""
    g, specs, sess = make_session(seed=39)
    clk = FakeClock()
    svc = AsyncWindowService(sess, bucket=64, now_fn=clk)
    # unstarted service: submit runs flush_if_due synchronously, which on
    # the frozen clock is "not due" — the ticket must still be pending
    t = svc.submit(0, vertex=3)  # point class: 2 ms deadline
    assert not t.done and len(svc._pending) == 1
    reason, dl = svc._due_reason()
    assert reason is None and dl == pytest.approx(clk.t + 0.002)

    clk.advance(0.002 - 1e-6)
    assert svc.flush_if_due() == [] and not t.done
    assert svc.deadline_flushes == 0

    clk.advance(1e-6)  # exactly at the deadline: now >= dl
    served = svc.flush_if_due()
    assert [s.rid for s in served] == [t.rid]
    assert t.done and t.error is None
    assert svc.deadline_flushes == 1 and svc.fill_flushes == 0
    # latency is measured on the same injected clock
    assert t.latency_s == pytest.approx(0.002)


def test_earliest_deadline_wins_across_classes():
    g, specs, sess = make_session(seed=43)
    clk = FakeClock()
    svc = AsyncWindowService(sess, bucket=64, classes={"never": NEVER},
                             now_fn=clk)
    svc.submit(0, request_class="never")     # +600 s deadline
    reason, dl = svc._due_reason()
    assert reason is None and dl == pytest.approx(clk.t + 600.0)
    svc.submit(0, vertex=1)                  # point: +2 ms — new earliest
    reason, dl = svc._due_reason()
    assert reason is None and dl == pytest.approx(clk.t + 0.002)
    clk.advance(0.002)
    served = svc.flush_if_due()
    # a deadline flush serves the WHOLE queue, not just the due ticket
    assert len(served) == 2 and svc.deadline_flushes == 1


def test_fill_beats_deadline_on_fake_clock():
    """At the fill edge the trigger is 'fill' even when deadlines have
    also expired — fill is checked first (it never needs the clock)."""
    g, specs, sess = make_session(seed=45)
    clk = FakeClock()
    svc = AsyncWindowService(sess, bucket=2, now_fn=clk)
    svc._pending.append(svc._make_ticket(0, None, None,
                                         svc.classes["interactive"]))
    clk.advance(60.0)  # way past every deadline
    svc._pending.append(svc._make_ticket(0, None, None,
                                         svc.classes["interactive"]))
    reason, _ = svc._due_reason()
    assert reason == "fill"
    assert len(svc.flush_if_due()) == 2
    assert svc.fill_flushes == 1 and svc.deadline_flushes == 0
    assert svc._due_reason() == (None, None)  # empty queue: nothing due


def test_flusher_survives_flush_exception(monkeypatch):
    """An injected failure inside a background flush must not kill the
    flusher thread — the next request is still served."""
    g, specs, sess = make_session(seed=37)
    with AsyncWindowService(sess, bucket=64) as svc:
        monkeypatch.setattr(
            api.SessionView, "run_group",
            lambda self, gi, values=None:
                (_ for _ in ()).throw(RuntimeError("boom")))
        bad = svc.submit(0, vertex=0)
        with pytest.raises(RuntimeError):
            bad.get(timeout=10.0)
        monkeypatch.undo()
        assert svc.running
        ok = svc.submit(0, vertex=0)
        assert ok.get(timeout=10.0) is not None


def test_request_span_stamps_its_queue_wait_and_flush():
    """A point read submitted at 0 and served by the deadline flush at
    2 ms waited 2 ms in the queue, on the injected clock; its request span
    says so, names the flush's reason, and hangs under the flush span."""
    from repro.obs import Tracer

    g, specs, sess = make_session(seed=47)
    clk = FakeClock(0.0)
    tr = Tracer()
    svc = AsyncWindowService(sess, bucket=64, now_fn=clk, tracer=tr)
    t = svc.submit(0, vertex=3)  # point class: 2 ms deadline
    clk.advance(0.002)
    assert [s.rid for s in svc.flush_if_due()] == [t.rid]
    spans = {e["name"]: e for e in tr.events() if e["ph"] == "X"}
    req, flush = spans["request"]["args"], spans["flush"]["args"]
    assert req["queued_ms"] == 2.0 and req["reason"] == "deadline"
    assert req["parent_id"] == flush["span_id"]
    assert t.detached_s == 0.002


def test_flusher_wait_span_carries_the_reason_it_woke():
    """The background flusher's wait with a ticket pending is one
    ``flush.wait`` span, tagged with the trigger that ended it."""
    from repro.obs import Tracer

    g, specs, sess = make_session(seed=49)
    tr = Tracer()
    with AsyncWindowService(sess, bucket=64, tracer=tr) as svc:
        svc.submit(0, vertex=1).get(timeout=10.0)  # 2 ms deadline
    waits = [e for e in tr.events() if e["name"] == "flush.wait"]
    assert waits and waits[0]["args"]["reason"] == "deadline"
    assert waits[0]["dur"] > 0


# ---------------------------------------------------------------------- #
#  Refreshes apart from the flusher
# ---------------------------------------------------------------------- #
def _after_one_write(seed):
    """A started service with its cache filled, then one value write: the
    write's version, a vertex it invalidated and one it left valid, and
    the sum oracle after the write."""
    g, specs, sess = make_session(seed=seed)
    svc = AsyncWindowService(sess, bucket=64).start()
    svc.submit(0, vertex=0).get(timeout=10.0)  # fills the cache
    vals = np.asarray(g.attrs["val"], np.float64).copy()
    hub = int(np.argmax(np.bincount(g.src, minlength=g.n)))
    vals[hub] += 7
    svc.update(UpdateBatch.attr_set("val", [hub], [vals[hub]]))
    valid = svc.cache._entries[0]["valid"]
    stale, fresh = int(np.flatnonzero(~valid)[0]), int(np.flatnonzero(valid)[0])
    oracle = brute_force(g, KHopWindow(2), vals, "sum", dtype=np.float32)
    return svc, svc.version, stale, fresh, oracle


def test_cache_hits_do_not_wait_for_a_refresh(monkeypatch):
    """While the refresher is held inside a whole-group refresh, a read the
    cache answers is served; the read that missed gets the refreshed
    answer, exact at the write's version or later."""
    svc, version, stale, fresh, oracle = _after_one_write(51)
    entered, release = threading.Event(), threading.Event()
    real = api.SessionView.run_group

    def held(self, gi, values=None):
        entered.set()
        assert release.wait(10.0)
        return real(self, gi, values)

    monkeypatch.setattr(api.SessionView, "run_group", held)
    try:
        miss = svc.submit(0, vertex=stale)
        assert entered.wait(10.0)
        hit = svc.submit(0, vertex=fresh)
        assert hit.get(timeout=10.0) == oracle[fresh] and hit.cache_hit
        assert not miss.done
    finally:
        release.set()
    assert miss.get(timeout=10.0) == oracle[stale] and not miss.cache_hit
    assert miss.version >= version and hit.version == version
    svc.stop()
    assert svc.stats["served"] == 3 and svc.stats["failed"] == 0


def test_stop_serves_reads_waiting_for_a_refresh(monkeypatch):
    """Reads a flush handed to the refresher are served by ``stop``, with
    or without drain, and a failed refresh fails exactly them."""
    for drain in (True, False):
        svc, _, stale, _, oracle = _after_one_write(53)
        entered, release = threading.Event(), threading.Event()
        real = api.SessionView.run_group

        def held(self, gi, values=None):
            entered.set()
            assert release.wait(10.0)
            return real(self, gi, values)

        monkeypatch.setattr(api.SessionView, "run_group", held)
        t = svc.submit(0, vertex=stale)
        assert entered.wait(10.0)  # the refresher holds it
        threading.Timer(0.2, release.set).start()
        svc.stop(drain=drain)
        assert t.done and t.result == oracle[stale]
        monkeypatch.undo()
    svc, _, stale, fresh, _ = _after_one_write(55)
    monkeypatch.setattr(
        api.SessionView, "run_group",
        lambda self, gi, values=None:
            (_ for _ in ()).throw(RuntimeError("boom")))
    bad = svc.submit(0, vertex=stale)
    with pytest.raises(RuntimeError):
        bad.get(timeout=10.0)
    assert svc.submit(0, vertex=fresh).get(timeout=10.0) is not None
    svc.stop()
    assert svc.stats["failed"] == 1
