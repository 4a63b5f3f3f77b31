"""Observability subsystem (ISSUE 7): metrics registry thread safety,
histogram bucket semantics, Null compile-out guarantees, span nesting and
Chrome-trace export, and the differential guarantee that enabling obs
never changes served results.
"""

import json
import threading

import numpy as np
import pytest

from repro import obs
from repro.obs import (
    DEFAULT_LATENCY_BUCKETS_S,
    Histogram,
    MetricsRegistry,
    NullRegistry,
    NullTracer,
    Tracer,
)
from repro.obs.slo import SLOTracker


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts and ends with the global obs layer disabled."""
    obs.disable()
    yield
    obs.disable()


# ---------------------------------------------------------------------- #
#  Registry + instruments
# ---------------------------------------------------------------------- #
def test_counter_concurrent_writers_lose_nothing():
    """Per-thread shard cells: N writers x M incs must merge to exactly
    N*M — no lost updates, no locks on the write path."""
    reg = MetricsRegistry()
    c = reg.counter("repro_test_total", "t")
    h = reg.histogram("repro_test_seconds", "t", buckets=(0.1, 1.0))
    lab = reg.counter("repro_test_labeled_total", "t", labels=("who",))
    n_threads, n_incs = 8, 10_000
    start = threading.Barrier(n_threads)

    def work(i):
        mine = lab.labels(f"w{i % 2}")
        start.wait()
        for _ in range(n_incs):
            c.inc()
            h.observe(0.05)
            mine.inc(2)

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_incs
    assert h.count == n_threads * n_incs
    per_label = n_threads // 2 * n_incs * 2
    assert lab.labels("w0").value == per_label
    assert lab.labels("w1").value == per_label


def test_registry_declarations_idempotent_and_clash_checked():
    reg = MetricsRegistry()
    a = reg.counter("repro_x_total", "first")
    b = reg.counter("repro_x_total", "redeclared")
    assert a is b  # same family object: instruments are process-wide names
    with pytest.raises(ValueError):
        reg.gauge("repro_x_total")  # kind clash
    with pytest.raises(ValueError):
        reg.counter("repro_x_total", labels=("cls",))  # labelnames clash


def test_gauge_set_inc_dec():
    reg = MetricsRegistry()
    g = reg.gauge("repro_depth")
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.value == 6


def test_histogram_bucket_edges_and_quantiles():
    """Bucket bounds are inclusive upper edges; quantiles interpolate
    linearly inside the landing bucket and clamp at overflow."""
    h = Histogram(buckets=(1.0, 2.0, 4.0))
    # bisect_left on inclusive upper bounds: 1.0 lands IN the first bucket
    for x in (0.5, 1.0):
        h.observe(x)
    h.observe(3.0)   # third bucket (2, 4]
    h.observe(100.0)  # overflow
    counts, total, n = h.merged()
    assert counts == [2, 0, 1, 1]
    assert n == 4 and total == pytest.approx(104.5)
    # overflow clamps to the last finite bound
    assert h.quantile(1.0) == 4.0
    # q=0.5 -> target 2.0 falls exactly at the end of bucket 0: edge-exact
    assert h.quantile(0.5) == pytest.approx(1.0)
    empty = Histogram(buckets=(1.0,))
    assert empty.quantile(0.99) == 0.0
    with pytest.raises(AssertionError):
        Histogram(buckets=(2.0, 1.0))  # must be strictly increasing


def test_snapshot_and_prometheus_shapes():
    reg = MetricsRegistry()
    reg.counter("repro_reqs_total", "requests", labels=("cls",)
                ).labels("fast").inc(3)
    reg.gauge("repro_lag").set(7)
    reg.histogram("repro_lat_seconds", "latency",
                  buckets=(0.1, 1.0)).observe(0.05)
    snap = reg.snapshot()
    assert snap["repro_reqs_total"]["values"][0] == {
        "labels": {"cls": "fast"}, "value": 3.0}
    assert snap["repro_lag"]["values"][0]["value"] == 7.0
    hist = snap["repro_lat_seconds"]["values"][0]
    assert hist["count"] == 1 and "p99" in hist
    text = reg.prometheus()
    assert '# TYPE repro_reqs_total counter' in text
    assert 'repro_reqs_total{cls="fast"} 3' in text
    # prometheus histograms are cumulative with a +Inf bucket
    assert 'repro_lat_seconds_bucket{le="+Inf"} 1' in text
    assert 'repro_lat_seconds_count 1' in text


def test_null_registry_is_inert():
    reg = NullRegistry()
    assert not reg.enabled
    c = reg.counter("repro_anything_total", labels=("a", "b"))
    # every operation is a no-op returning the singleton
    c.inc()
    c.labels("x", "y").inc(5)
    assert c.labels("x", "y") is c.labels("p", "q")
    assert c.value == 0.0
    h = reg.histogram("repro_h_seconds")
    h.observe(1.0)
    assert h.count == 0 and h.quantile(0.99) == 0.0
    g = reg.gauge("repro_g")
    g.set(9)
    g.dec()
    assert g.value == 0.0
    assert reg.snapshot() == {}
    assert reg.prometheus() == ""


def test_global_enable_disable_swaps_registries():
    assert isinstance(obs.get_registry(), NullRegistry)
    reg, tr = obs.enable()
    assert obs.get_registry() is reg and obs.get_tracer() is tr
    assert reg.enabled and tr.enabled
    reg.counter("repro_t_total").inc()
    obs.disable()
    assert isinstance(obs.get_registry(), NullRegistry)
    assert isinstance(obs.get_tracer(), NullTracer)
    # a fresh enable starts clean: no user metrics carry over — only the
    # built-in collect-on-scrape families are pre-declared
    reg2, _ = obs.enable()
    snap = reg2.snapshot()
    assert "repro_t_total" not in snap
    assert set(snap) <= {"repro_recompiles",
                         "repro_trace_spans_dropped_total"}


# ---------------------------------------------------------------------- #
#  Tracing
# ---------------------------------------------------------------------- #
def test_span_nesting_parents_and_export_round_trip(tmp_path):
    tr = Tracer()
    with tr.span("outer", cat="t", a=1) as outer:
        with tr.span("mid", cat="t"):
            with tr.span("inner", cat="t") as inner:
                inner.set(rows=4)
    detached = tr.start_span("ticket", cat="t", parent=outer.id)
    detached.finish()
    evs = {e["name"]: e for e in tr.events()}
    assert evs["mid"]["args"]["parent_id"] == evs["outer"]["args"]["span_id"]
    assert evs["inner"]["args"]["parent_id"] == evs["mid"]["args"]["span_id"]
    assert evs["ticket"]["args"]["parent_id"] == evs["outer"]["args"]["span_id"]
    assert evs["inner"]["args"]["rows"] == 4
    assert tr.max_depth() == 3
    for e in evs.values():
        assert e["dur"] >= 0

    path = tmp_path / "trace.json"
    tr.dump(path)
    doc = json.loads(path.read_text())
    names = {e.get("name") for e in doc["traceEvents"]}
    assert {"outer", "mid", "inner", "ticket"} <= names
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert all({"ts", "dur", "pid", "tid"} <= set(e) for e in xs)


def test_span_exit_records_error_and_ring_buffer_caps():
    tr = Tracer(capacity=8)
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    # a garbage collection may record its own span after it
    boom = [e for e in tr.events() if e["name"] == "boom"]
    assert boom[-1]["args"]["error"] == "RuntimeError"
    for i in range(20):
        with tr.span(f"s{i}"):
            pass
    assert len(tr) == 8  # oldest spans fell off the ring
    null = NullTracer()
    with null.span("n") as sp:
        sp.set(a=1)
    assert null.events() == [] and null.max_depth() == 0


# ---------------------------------------------------------------------- #
#  SLO accounting
# ---------------------------------------------------------------------- #
def test_slo_tracker_attainment_and_outcomes():
    reg = MetricsRegistry()
    slo = SLOTracker(reg)
    for lat in (0.001, 0.002, 0.050):
        slo.observe("interactive", lat, target_s=0.005)
    slo.observe("interactive", 0.1, target_s=0.005, outcome="error")
    slo.observe("interactive", 0.0, target_s=0.005, outcome="shed")
    rep = slo.report()["interactive"]
    assert rep["target_ms"] == pytest.approx(5.0)
    assert rep["ok"] == 3 and rep["error"] == 1 and rep["shed"] == 1
    assert rep["attainment"] == pytest.approx(2 / 3)
    assert rep["p50_ms"] > 0
    slo.observe("batch", 1.0)  # no target: attainment undefined
    assert slo.report()["batch"]["attainment"] is None


# ---------------------------------------------------------------------- #
#  Differential: obs on/off must not change results
# ---------------------------------------------------------------------- #
def test_enabling_obs_does_not_change_results_bitwise():
    jax = pytest.importorskip("jax")  # noqa: F841
    from repro.core.api import QuerySpec, Session
    from repro.graphs.generators import erdos_renyi
    from repro.serve import WindowService
    from test_updates import mixed

    def run(enabled):
        if enabled:
            obs.enable()
        else:
            obs.disable()
        g = erdos_renyi(120, 3.0, directed=False, seed=41)
        vals = np.random.default_rng(42).integers(0, 50, g.n)
        g = g.with_attr("val", vals.astype(np.float64))
        sess = Session(g, [QuerySpec(("khop", 2), "sum"),
                           QuerySpec(("khop", 1), "min")],
                       use_pallas=False)
        svc = WindowService(sess, bucket=4)
        rng = np.random.default_rng(43)
        outs = []
        for _ in range(3):
            svc.update(mixed(svc.session.graph, rng, 5, 2))
            tickets = [svc.submit(0), svc.submit(1), svc.submit(0, vertex=7)]
            svc.flush()
            outs.append([np.asarray(t.get(timeout=0)) for t in tickets])
        return outs

    base, instrumented = run(False), run(True)
    snap = obs.get_registry().snapshot()
    assert snap["repro_flushes_total"]["values"], "obs really was on"
    for a, b in zip(base, instrumented):
        for x, y in zip(a, b):
            assert np.array_equal(x, y)


# ---------------------------------------------------------------------- #
#  Label escaping, collect-on-scrape, trace-drop exposure (ISSUE 8)
# ---------------------------------------------------------------------- #
def test_prometheus_hostile_label_value_round_trips():
    """A label value carrying backslashes, quotes, and newlines must stay
    on one exposition line and invert exactly through the escaper."""
    import re

    from repro.obs.metrics import _escape_label_value, _unescape_label_value

    hostile = 'a\\b"c\nd{},= \\" \n\\ e'
    reg = MetricsRegistry()
    reg.counter("repro_hostile_total", "t", labels=("who",)
                ).labels(hostile).inc(3)
    text = reg.prometheus()
    lines = [ln for ln in text.splitlines()
             if ln.startswith("repro_hostile_total{")]
    assert len(lines) == 1, "newline in the value must not split the line"
    line = lines[0]
    m = re.search(r'who="((?:[^"\\]|\\.)*)"', line)
    assert m, line
    assert _unescape_label_value(m.group(1)) == hostile
    assert line.endswith(" 3")
    # escape/unescape is a bijection on every metacharacter alone too
    for v in ("\\", '"', "\n", "", "plain", '\\n'):
        assert _unescape_label_value(_escape_label_value(v)) == v


def test_collectors_run_on_scrape_and_dedupe_by_name():
    reg = MetricsRegistry()
    calls = []

    def fill(r):
        calls.append(1)
        r.gauge("repro_scraped").set(len(calls))

    reg.collect(fill, name="fill")
    reg.collect(fill, name="fill")  # same name: replaces, no double-run
    snap = reg.snapshot()
    assert len(calls) == 1
    assert snap["repro_scraped"]["values"][0]["value"] == 1.0
    reg.prometheus()
    assert len(calls) == 2  # fresh on every scrape

    def broken(r):
        raise RuntimeError("collector bug")

    reg.collect(broken, name="broken")
    reg.snapshot()  # a broken collector must not poison the scrape


def test_recompile_gauge_is_collected_fresh():
    reg, _ = obs.enable()
    from repro.core.api import recompile_count

    snap = reg.snapshot()
    assert snap["repro_recompiles"]["values"][0]["value"] == float(
        recompile_count())
    assert "repro_recompiles" in reg.prometheus()


def test_trace_drop_counter_exposed_and_monotonic():
    tr = Tracer(capacity=4)
    reg, _ = obs.enable(tracer=tr)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    snap = reg.snapshot()
    fam = snap["repro_trace_spans_dropped_total"]
    dropped = fam["values"][0]["value"]
    assert dropped == float(tr.dropped_hint) and dropped > 0
    # monotonic across scrapes: delta-folded, not re-added
    snap2 = reg.snapshot()
    assert snap2["repro_trace_spans_dropped_total"]["values"][0][
        "value"] == dropped
    tr.instant("one-more")  # ring is full: this drops another event
    for _ in range(3):
        with tr.span("x"):
            pass
    snap3 = reg.snapshot()
    assert snap3["repro_trace_spans_dropped_total"]["values"][0][
        "value"] == float(tr.dropped_hint) > dropped
    assert "repro_trace_spans_dropped_total" in reg.prometheus()


def test_reenable_same_registry_does_not_double_count_drops():
    """ISSUE 9 satellite: obs.enable(registry=r, tracer=t) called twice
    must be idempotent — re-running _install_collectors used to reset the
    drop-delta seen-state, folding the whole historical drop count in
    again on the next scrape (double counting)."""
    tr = Tracer(capacity=4)
    reg = MetricsRegistry()
    obs.enable(registry=reg, tracer=tr)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    dropped = reg.snapshot()[
        "repro_trace_spans_dropped_total"]["values"][0]["value"]
    assert dropped == float(tr.dropped_hint) > 0
    # re-enable with the SAME registry + tracer (e.g. a test harness
    # round-tripping enable/disable): nothing may be re-counted
    obs.enable(registry=reg, tracer=tr)
    again = reg.snapshot()[
        "repro_trace_spans_dropped_total"]["values"][0]["value"]
    assert again == dropped
    # and the collector did not stack either: one more drop folds once
    tr.instant("overflow")
    for _ in range(2):
        with tr.span("x"):
            pass
    final = reg.snapshot()[
        "repro_trace_spans_dropped_total"]["values"][0]["value"]
    assert final == float(tr.dropped_hint)


def test_name_thread_metadata_survives_thread_exit():
    """ISSUE 9 satellite: worker threads self-register display names; the
    Chrome export carries `"ph": "M"` thread_name rows for them even after
    the thread has exited (threading.enumerate() no longer sees it)."""
    tr = Tracer()

    def worker():
        tr.name_thread()  # registers "audit-worker-x" by ident
        with tr.span("work"):
            pass

    th = threading.Thread(target=worker, name="audit-worker-x")
    th.start()
    th.join()
    evs = tr.chrome_trace()["traceEvents"]
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "audit-worker-x" in names
    # one process_name row anchors the whole pid in Perfetto
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)
    # explicit-name form wins over the Thread name
    tr.name_thread("custom-role")
    evs = tr.chrome_trace()["traceEvents"]
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "custom-role" in names
    # NullTracer compiles the call out
    NullTracer().name_thread("whatever")


def test_flight_recorder_wall_clock_anchor(tmp_path):
    """ISSUE 9 satellite: dump_json carries anchor_unix_s so the
    perf_counter-relative t_s stamps correlate with wall-clock metric and
    trace timestamps."""
    import time as _time

    from repro.serve.flight import FlightRecorder

    before = _time.time()
    fr = FlightRecorder(capacity=8)
    after = _time.time()
    assert before <= fr.anchor_unix_s <= after
    fr.record("flip", version=1)
    out = json.loads(open(fr.dump_json(tmp_path / "f.json")).read())
    assert out["anchor_unix_s"] == fr.anchor_unix_s
    assert out["events"][0]["t_s"] >= 0.0


# ---------------------------------------------------------------------- #
#  One clock with the device trace; garbage collections; named scopes
# ---------------------------------------------------------------------- #
def test_span_is_mirrored_into_the_profiler_trace(tmp_path):
    """Under a profiler session a stack span appears in the xplane as
    ``repro.<name>``, within 1 ms of the Tracer's own record mapped onto
    the profiler's clock through the Tracer's epoch."""
    import glob
    import time as _time

    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("executor.finalize"):
            _time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(path[0])
    base = int(dict(data.find_plane_with_name("Task Environment").stats)
               ["profile_start_time"])
    got = [(base + e.start_ns, base + e.end_ns) for p in data.planes
           for line in p.lines for e in line.events
           if e.name == "repro.executor.finalize"]
    rec = [e for e in tr.events() if e["name"] == "executor.finalize"][0]
    start = tr.epoch_unix_ns + rec["ts"] * 1e3
    assert len(got) == 1
    assert abs(got[0][0] - start) < 1e6
    assert abs(got[0][1] - (start + rec["dur"] * 1e3)) < 1e6


def test_gc_collect_is_one_gc_span():
    """A live tracer records a garbage collection as a root ``gc`` span
    with its generation and the objects it collected."""
    import gc

    tr = Tracer()
    for _ in range(50):  # cycles for the collector to find
        a = []
        a.append(a)
    del a
    collected = gc.collect()
    full = [e for e in tr.events()
            if e["name"] == "gc" and e["args"]["generation"] == 2]
    assert len(full) == 1
    args = full[0]["args"]
    assert args["collected"] >= 50 and args["collected"] <= collected
    assert "parent_id" not in args and full[0]["cat"] == "gc"


def test_batched_channel_cores_name_their_phases():
    """Both batched channel cores run each phase under its named scope;
    the scopes reach the compiled operations' op_name."""
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core import api
    from repro.core.engine_jax import plan_from_dbindex, plan_from_iindex
    from repro.core.dbindex import build_dbindex
    from repro.core.iindex import build_iindex
    from repro.core.windows import KHopWindow
    from repro.graphs.generators import erdos_renyi, random_dag

    aggs = ("sum", "count", "avg", "min", "max")
    g = erdos_renyi(64, 3.0, directed=False, seed=3)
    dag = random_dag(64, 2.0, seed=3)
    cases = {
        "jax": (plan_from_dbindex(build_dbindex(g, KHopWindow(2))),
                ("pass1.sum", "pass1.minmax", "pass2.sum", "pass2.minmax")),
        "jax-iindex": (plan_from_iindex(build_iindex(dag)),
                       ("wd.sum", "wd.minmax", "inherit.sum",
                        "inherit.minmax")),
    }
    for engine, (plan, scopes) in cases.items():
        lowered = api._get_vmany(engine).lower(
            plan, jnp.zeros((2, 64), jnp.float32), aggs, False, None)
        text = lowered.as_text(debug_info=True)
        compiled = lowered.compile().as_text()
        for scope in scopes:
            assert f'"{scope}/' in text, (engine, scope)  # MLIR locations
            assert f"/{scope}/" in compiled, (engine, scope)  # op_name


def test_gc_spans_under_thread_churn_lose_no_span():
    """Threads build tracers, record spans and collect garbage at once,
    with the interpreter switching threads as often as it can: every span
    is recorded, every gc span is closed, no stack is left open."""
    import gc
    import sys

    n_threads, n_spans = 16, 200
    tracers, errors = [Tracer() for _ in range(n_threads)], []

    def work(tr):
        try:
            for i in range(n_spans):
                with tr.span("work", i=i):
                    if i % 50 == 0:
                        gc.collect(0)
                    Tracer()  # registers, then dies: the WeakSet churns
            assert tr._stack() == []
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(tr,))
                   for tr in tracers]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert errors == []
    for tr in tracers:
        evs = tr.events()
        assert sum(e["name"] == "work" for e in evs) == n_spans
        assert all("collected" in e["args"] for e in evs
                   if e["name"] == "gc")
