"""Observability end to end: metrics, request tracing, SLO accounting.

`repro.obs` is a zero-dependency observability layer threaded through the
whole serving stack — `Session` maintenance, the `WindowService`
schedulers, the WAL, replicas, and the sharded runtime.  It is off by
default: every instrumented class falls back to a process-global
`NullRegistry`/`NullTracer` whose operations are no-ops, so the hot path
pays one attribute call per event.  `obs.enable()` swaps in live
implementations; call it BEFORE constructing sessions/services (classes
capture the registry at construction).

This demo drives an `AsyncWindowService` with a concurrent update stream
while three request classes compete, then reads everything back out:

* per-class SLO attainment (fraction of ok requests within their class
  `max_delay_ms`), p50/p95/p99 latency from fixed-bucket histograms;
* the affected-owner cache hit rate and invalidation traffic;
* the unified recompile counter — flat across the whole streamed run;
* a Prometheus text exposition;
* a Chrome `trace_event` JSON (load it at chrome://tracing or
  https://ui.perfetto.dev) with the full span hierarchy:
  flush > launch > query.group > executor.device/executor.finalize on
  the read path (query.term sits between the last two only for the terms
  of a composite window) and service.update > session.update > maintain
  > index.update/plan.patch on the write path, plus one detached
  "request" span per ticket (its queue wait in ``queued_ms``, linked to
  the flush that served it), the flusher's ``flush.wait`` and one root
  ``gc`` span per garbage collection.

Reading the metrics
-------------------
Every instrument is prefixed ``repro_`` and follows the Prometheus
conventions: counters end in ``_total``, durations are ``_seconds``
histograms, sizes are ``_bytes``/``_records``, and gauges are bare nouns.
Label keys are closed vocabularies:

* ``cls``     — request class name (``interactive``, ``point``, ...);
* ``outcome`` — ``ok`` | ``error`` | ``shed`` (on ``repro_requests_total``);
* ``reason``  — ``fill`` | ``deadline`` | ``manual`` (on
  ``repro_flushes_total``: what triggered the launch);
* ``event``   — ``hit`` | ``miss`` | ``invalidate`` | ``drop`` (on
  ``repro_cache_events_total``);
* ``kind`` / ``action`` — index kind and maintenance action
  (``attr_only`` | ``refilter`` | ``patch`` | ``reorganize``) on
  ``repro_maintenance_total``.

The ones to alert on: ``repro_slo_within_target_total / ok`` per class
(attainment), ``repro_recompiles`` (a moving value means retraces in
steady state — the one thing this stack promises never happens),
``repro_wal_fsync_seconds`` p99 (durability stalls), and
``repro_replica_lag_bytes`` (follower health).

Run:  PYTHONPATH=src python examples/observability.py
"""

import json
import os
import tempfile
import threading
import time

import numpy as np

from repro import obs

# enable FIRST: instrumented classes bind the registry at construction
registry, tracer = obs.enable()

from repro.core.api import QuerySpec, Session, recompile_count  # noqa: E402
from repro.core.updates import UpdateBatch  # noqa: E402
from repro.graphs.generators import erdos_renyi  # noqa: E402
from repro.serve import AsyncWindowService  # noqa: E402

rng = np.random.default_rng(0)
g = erdos_renyi(1_500, 5.0, seed=4)
g = g.with_attr("val", rng.integers(0, 100, g.n).astype(np.float64))
specs = [QuerySpec(("khop", 1), "sum"), QuerySpec(("khop", 1), "min")]
out_dir = tempfile.mkdtemp(prefix="repro_obs_")

sess = Session(g, specs, device=True, use_pallas=False, plan_headroom=1.0)

with AsyncWindowService(sess, bucket=8,
                        wal=os.path.join(out_dir, "service.wal")) as svc:
    # ---- warmup: compile every executor shape the run will use -------- #
    svc.submit(0).get(timeout=60)
    svc.submit(0, vertex=0).get(timeout=60)
    svc.update(UpdateBatch.inserts(np.array([1], np.int32),
                                   np.array([2], np.int32)))
    svc.submit(1).get(timeout=60)
    warm = recompile_count()

    # ---- concurrent update stream ------------------------------------- #
    stop = threading.Event()

    def writer():
        r = np.random.default_rng(7)
        while not stop.is_set():
            s = r.integers(0, g.n, 4).astype(np.int32)
            d = r.integers(0, g.n, 4).astype(np.int32)
            ok = (s != d) & ~svc.session.graph.contains_edges(s, d)
            if ok.any():
                svc.update(UpdateBatch.inserts(s[ok], d[ok]))
            time.sleep(0.002)

    th = threading.Thread(target=writer, name="update-stream")
    th.start()

    # ---- mixed request classes under load ----------------------------- #
    tickets = []
    for i in range(96):
        if i % 3 == 0:
            tickets.append(svc.submit(0, vertex=int(rng.integers(g.n))))
        elif i % 3 == 1:
            tickets.append(svc.submit(i % 2, request_class="interactive"))
        else:
            tickets.append(svc.submit(i % 2, request_class="batch"))
    served = sum(1 for t in tickets if t.get(timeout=60.0) is not None)
    stop.set()
    th.join()

    stats = svc.stats

# ---- the one invariant dashboards page on: zero recompiles ------------- #
assert recompile_count() == warm, "steady-state stream must never retrace"
print(f"{served}/96 requests served under a concurrent update stream; "
      f"recompiles after warmup: {recompile_count() - warm}")

# ---- SLO attainment per request class ---------------------------------- #
print("\nSLO report (per request class):")
for cls, rep in sorted(stats["slo"].items()):
    att = ("n/a" if rep["attainment"] is None
           else f"{rep['attainment'] * 100:.1f}%")
    tgt = "-" if rep["target_ms"] is None else f"{rep['target_ms']:.0f} ms"
    print(f"  {cls:<12} target {tgt:>7}  attainment {att:>6}  "
          f"ok/err/shed {rep['ok']}/{rep['error']}/{rep['shed']}  "
          f"p50 {rep['p50_ms']:.1f} ms  p95 {rep['p95_ms']:.1f} ms  "
          f"p99 {rep['p99_ms']:.1f} ms")

# ---- cache + WAL + maintenance counters from the snapshot -------------- #
snap = registry.snapshot()


def fam(name, **labels):
    for row in snap.get(name, {}).get("values", []):
        if all(row["labels"].get(k) == v for k, v in labels.items()):
            return row["value"]
    return 0.0


hits = fam("repro_cache_events_total", event="hit")
misses = fam("repro_cache_events_total", event="miss")
rate = hits / max(hits + misses, 1)
print(f"\naffected-owner cache: {hits:.0f} hits / {misses:.0f} misses "
      f"({rate * 100:.1f}% hit rate), "
      f"{fam('repro_cache_events_total', event='invalidate'):.0f} owner "
      f"invalidations")
print(f"flush triggers: {fam('repro_flushes_total', reason='fill'):.0f} fill "
      f"/ {fam('repro_flushes_total', reason='deadline'):.0f} deadline "
      f"/ {fam('repro_flushes_total', reason='manual'):.0f} manual; "
      f"wal appends: {fam('repro_wal_appends_total'):.0f}")
maint = snap["repro_maintenance_total"]["values"]
print("maintenance:", ", ".join(
    f"{r['labels']['kind']}/{r['labels']['action']}={r['value']:.0f}"
    for r in maint))

# ---- exporters --------------------------------------------------------- #
prom_path = os.path.join(out_dir, "metrics.prom")
with open(prom_path, "w") as f:
    f.write(registry.prometheus())
trace_path = tracer.dump(os.path.join(out_dir, "trace.json"))

with open(trace_path) as f:
    doc = json.load(f)
depth = tracer.max_depth()
assert depth >= 4, f"expected >= 4 span levels, got {depth}"
print(f"\nwrote {prom_path} ({sum(1 for _ in open(prom_path))} lines) and "
      f"{trace_path} ({len(doc['traceEvents'])} events, span depth {depth})"
      f" — load the trace at chrome://tracing")

obs.disable()
