#!/usr/bin/env python3
"""Drive the Session -> WindowService path once on a TPU and check it.

Run from the repository root on a machine with a TPU:

    python3 chip_smoke.py              # one chip: k-hop and topological phases
    python3 chip_smoke.py --chips 4    # four chips: the sharded Session only

One chip: a 2-hop ``Session(device=True)`` over an undirected Erdos-Renyi
graph (n=100,000, average degree 8) with sum/count/avg/min/max, behind a
``WindowService``, streams ``BATCHES`` update batches (one of them large
enough to route the affected-owner BFS through the ``bitset_expand``
kernel) and serves point reads, full scans and explicit-values requests
between them.  Then a ``jax-iindex`` topological Session over a 20,000-vertex
random DAG serves the same request mix.  Every answer at the sampled
vertices is compared exactly against the ``brute_force`` set-evaluation
oracle (integer attributes in [0, 100), so f32 sums are exact in any
order), and the fused executors must not recompile after warm-up.

``--chips 4`` serves the same 2-hop spec set from ``Session(mesh=...)`` on a
four-device mesh and compares it bit-for-bit with a one-device Session in
the same process and with the oracle.

Earlier lines report each phase's wall-clock time on the chip host; the
last line is one JSON object ``{"ok": true, "device": {...}}``.  The script
exits non-zero, and prints no such line, when JAX finds no TPU, when a
kernel would run in interpret mode, or when any check fails.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

AGGS = ("sum", "count", "avg", "min", "max")
SAMPLE = 256  # oracle-checked vertices per check
BUCKET = 8
BATCHES = 5  # update batches streamed through the service
DAG_N = 20_000  # vertices of the topological phase's DAG


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _report(phase: str, **fields) -> None:
    print(f"{phase}: {json.dumps(fields)}", flush=True)


# ---------------------------------------------------------------------- #
#  Traffic
# ---------------------------------------------------------------------- #
def _update_batch(g, rng, n_ins: int, n_del: int):
    """``n_ins`` fresh edges plus ``n_del`` deletes of existing ones."""
    from repro.core.updates import UpdateBatch

    s = rng.integers(0, g.n, n_ins * 4).astype(np.int32)
    d = rng.integers(0, g.n, n_ins * 4).astype(np.int32)
    ok = (s != d) & ~g.contains_edges(s, d)
    _, first = np.unique(g.edge_keys(s, d), return_index=True)
    pick = np.intersect1d(np.flatnonzero(ok), first)[:n_ins]
    ei = rng.choice(g.n_edges, min(n_del, g.n_edges), replace=False)
    return UpdateBatch.concat([UpdateBatch.inserts(s[pick], d[pick]),
                               UpdateBatch.deletes(g.src[ei], g.dst[ei])])


def _big_batch(g, rng):
    """A batch whose k-hop seed set reaches the device-BFS threshold."""
    from repro.core.updates import DEVICE_BFS_MIN_SEEDS, _khop_seeds

    batch = _update_batch(g, rng, DEVICE_BFS_MIN_SEEDS * 3 // 5, 16)
    seeds = np.unique(_khop_seeds(g, batch)).size
    _require(seeds >= DEVICE_BFS_MIN_SEEDS,
             f"large batch has {seeds} seeds < {DEVICE_BFS_MIN_SEEDS}")
    return batch, seeds


def _sample(n: int, rng, affected=None) -> np.ndarray:
    """Half uniform vertices, half owners the last batch touched."""
    picks = [rng.choice(n, SAMPLE, replace=False)]
    if affected is not None and np.size(affected):
        picks.append(rng.choice(np.asarray(affected), SAMPLE // 2))
    return np.unique(np.concatenate(picks))


def _submit_mix(svc, vertices, explicit):
    """Point reads at ``vertices`` for every spec, one full scan per spec
    and one explicit-values request per (spec, vector)."""
    points = {(si, int(v)): svc.submit(si, vertex=int(v))
              for si in range(len(AGGS)) for v in vertices}
    scans = {si: svc.submit(si) for si in range(len(AGGS))}
    explicit_t = {(si, k): svc.submit(si, values=vals)
                  for si in range(len(AGGS)) for k, vals in enumerate(explicit)}
    return points, scans, explicit_t


def _check_served(svc, window, vertices, explicit, tickets) -> int:
    """Compare every ticket of one flush with the oracle; returns the
    number of values compared."""
    from repro.core.query import brute_force

    points, scans, explicit_t = tickets
    everything = [*points.values(), *scans.values(), *explicit_t.values()]
    failed = [t for t in everything if not t.done or t.failed]
    if failed:
        raise SmokeFailure(f"{len(failed)} tickets failed or unserved, "
                           f"first error: {failed[0].error!r}")
    g = svc.session.graph
    checked, wrong = 0, []

    def compare(what, got, ref):
        got = np.asarray(got)
        ref = np.asarray(ref, got.dtype)
        bad = got != ref
        if bad.any():
            wrong.append(f"{what}: {int(bad.sum())}/{bad.size} differ, "
                         f"max |diff| {float(np.abs(got - ref).max())!r}")
        return got.size

    for si, agg in enumerate(AGGS):
        ref = brute_force(g, window, g.attrs["val"], agg, dtype=np.float32,
                          vertices=vertices)
        checked += compare(f"{agg} point reads", [
            points[(si, int(v))].result for v in vertices], ref)
        checked += compare(f"{agg} full scan",
                           np.asarray(scans[si].result)[vertices], ref)
        for k, vals in enumerate(explicit):
            ref = brute_force(g, window, vals, agg, dtype=np.float32,
                              vertices=vertices)
            checked += compare(f"{agg} explicit values {k}", np.asarray(
                explicit_t[(si, k)].result)[vertices], ref)
    _require(not wrong, "answers differ from the oracle: " + "; ".join(wrong))
    return checked


def _explicit_values(n: int, rng, count: int):
    return [rng.integers(0, 100, n).astype(np.float32) for _ in range(count)]


def _span_seconds(tracer, name: str) -> float:
    return sum(e["dur"] for e in tracer.events() if e["name"] == name) / 1e6


def _serve_and_check(svc, window, rng, affected, explicit_count: int):
    """One flush of the request mix, timed and oracle-checked."""
    n = svc.session.graph.n
    vertices = _sample(n, rng, affected)
    explicit = _explicit_values(n, rng, explicit_count)
    tickets = _submit_mix(svc, vertices, explicit)
    t0 = time.perf_counter()
    svc.flush()
    flush_s = time.perf_counter() - t0
    return flush_s, _check_served(svc, window, vertices, explicit, tickets)


# ---------------------------------------------------------------------- #
#  Phases
# ---------------------------------------------------------------------- #
def _pallas_in_batched_executor(sess, engine: str) -> bool:
    """Whether the batched serving executor of group 0 lowers to a Pallas
    ``tpu_custom_call`` (and not to XLA's segment ops)."""
    import jax

    from repro.core.api import _get_vmany

    (_, plan), = sess._group_artifacts(0)
    vb = jax.ShapeDtypeStruct((BUCKET, sess.graph.n), np.float32)
    text = _get_vmany(engine).lower(plan, vb, AGGS, True, False).as_text()
    return "tpu_custom_call" in text


def khop_phase(n: int, seed: int) -> None:
    from repro.core.api import QuerySpec, Session, recompile_count
    from repro.core.updates import affected_owners
    from repro.graphs.generators import erdos_renyi, with_random_attrs
    from repro.kernels.bitset_expand.ops import bitset_expand
    from repro.obs.tracing import Tracer
    from repro.serve import WindowService

    rng = np.random.default_rng(seed)
    g = with_random_attrs(erdos_renyi(n, 8.0, directed=False, seed=seed),
                          seed=seed + 1)
    specs = [QuerySpec(("khop", 2), a) for a in AGGS]
    tracer = Tracer()
    sess = Session(g, specs, device=True, tracer=tracer)
    svc = WindowService(sess, bucket=BUCKET)
    window = sess.compiled.groups[0].window
    _require(_pallas_in_batched_executor(sess, "jax"),
             "the batched k-hop executor does not run the Pallas kernel")
    _report("khop.setup", n=n, edges=int(g.n_edges),
            index_build_s=_span_seconds(tracer, "index.build"),
            plan_upload_s=_span_seconds(tracer, "plan.upload"))

    first_s, checked = _serve_and_check(svc, window, rng, None, 2)
    c0 = recompile_count()
    _report("khop.first_flush", compile_and_flush_s=first_s, checked=checked)

    flushes, updates, big_at = [], [], 2
    for b in range(BATCHES):
        if b == big_at:
            batch, seeds = _big_batch(sess.graph, rng)
        else:
            batch = _update_batch(sess.graph, rng, 200, 100)
            seeds = None
        t0 = time.perf_counter()
        reports = svc.update(batch)
        update_s = time.perf_counter() - t0
        affected = np.concatenate(
            [np.asarray(r["affected_owners"]) for r in reports.values()])
        if seeds is not None:
            # the kernel scatters 16-bit halves through the MXU: exact only
            # if the f32 matmul is, so check its BFS against the host's
            dev, host = (affected_owners(sess.graph, window, batch,
                                         use_device=d) for d in (True, False))
            _require(np.array_equal(dev, host),
                     "bitset_expand's affected owners differ from the host BFS")
        flush_s, checked = _serve_and_check(svc, window, rng, affected, 2)
        flushes.append(flush_s)
        updates.append(update_s)
        _report(f"khop.batch{b}", edits=int(batch.size), seeds=seeds,
                affected=int(affected.size), update_s=update_s,
                flush_s=flush_s, checked=checked, correct=True,
                reorganized=any(r.get("reorganized")
                                for r in reports.values()))
    recompiles = recompile_count() - c0
    _require(bitset_expand._cache_size() > 0,
             "the large batch did not run the bitset_expand kernel")
    _require(recompiles == 0, f"{recompiles} recompiles after warm-up")
    _require(svc.stats["failed"] == 0, f"{svc.stats['failed']} failed tickets")
    _report("khop.steady", flushes=len(flushes),
            flush_median_s=float(np.median(flushes)),
            flush_max_s=max(flushes),
            update_median_s=float(np.median(updates)),
            recompiles=recompiles, failed_tickets=svc.stats["failed"],
            correct=True)


def topo_phase(n: int, seed: int) -> None:
    from repro.core.api import QuerySpec, Session, recompile_count
    from repro.core.updates import UpdateBatch
    from repro.graphs.generators import random_dag, with_random_attrs
    from repro.obs.tracing import Tracer
    from repro.serve import WindowService

    rng = np.random.default_rng(seed + 10)
    g = with_random_attrs(random_dag(n, 3.0, seed=seed + 2), seed=seed + 3)
    specs = [QuerySpec("topological", a, engine="jax-iindex") for a in AGGS]
    tracer = Tracer()
    sess = Session(g, specs, device=True, tracer=tracer)
    svc = WindowService(sess, bucket=BUCKET)
    window = sess.compiled.groups[0].window
    _report("topo.setup", n=n, edges=int(g.n_edges),
            index_build_s=_span_seconds(tracer, "index.build"),
            plan_upload_s=_span_seconds(tracer, "plan.upload"))
    first_s, checked = _serve_and_check(svc, window, rng, None, 1)
    c0 = recompile_count()
    _report("topo.first_flush", compile_and_flush_s=first_s, checked=checked)
    # attribute edits: the I-Index plan stays, every cached owner whose
    # window holds an edited vertex is invalidated
    verts = rng.choice(n, 512, replace=False)
    batch = UpdateBatch.attr_set("val", verts, rng.integers(0, 100, verts.size))
    t0 = time.perf_counter()
    svc.update(batch)
    update_s = time.perf_counter() - t0
    flush_s, checked = _serve_and_check(svc, window, rng, verts, 1)
    recompiles = recompile_count() - c0
    _require(recompiles == 0, f"{recompiles} recompiles after warm-up")
    _require(svc.stats["failed"] == 0, f"{svc.stats['failed']} failed tickets")
    _report("topo.steady", update_s=update_s, flush_s=flush_s,
            checked=checked, recompiles=recompiles,
            failed_tickets=svc.stats["failed"], correct=True)


def mesh_phase(n: int, seed: int, chips: int) -> None:
    """The sharded Session on a ``chips``-device mesh against a one-device
    Session and the oracle, under a stream of update batches."""
    import jax

    from repro.core.api import QuerySpec, Session, recompile_count
    from repro.graphs.generators import erdos_renyi, with_random_attrs
    from repro.serve import WindowService

    _require(len(jax.devices()) == chips,
             f"{len(jax.devices())} devices, expected {chips}")
    rng = np.random.default_rng(seed)
    g = with_random_attrs(erdos_renyi(n, 8.0, directed=False, seed=seed),
                          seed=seed + 1)
    specs = [QuerySpec(("khop", 2), a) for a in AGGS]
    t0 = time.perf_counter()
    sess = Session(g, specs, device=True,
                   mesh=jax.make_mesh((chips,), ("data",)))
    build_s = time.perf_counter() - t0
    single = Session(g, specs, device=True)
    svc = WindowService(sess, bucket=BUCKET)
    window = sess.compiled.groups[0].window
    _report("mesh.setup", n=n, chips=chips, session_build_s=build_s)

    def serve_and_compare(affected):
        vertices = _sample(n, rng, affected)
        explicit = _explicit_values(n, rng, 1)
        tickets = _submit_mix(svc, vertices, explicit)
        t0 = time.perf_counter()
        svc.flush()
        flush_s = time.perf_counter() - t0
        checked = _check_served(svc, window, vertices, explicit, tickets)
        _, scans, explicit_t = tickets
        ref = single.run()
        ref_x = single.run(values=explicit[0])
        for si, agg in enumerate(AGGS):
            _require(np.array_equal(np.asarray(scans[si].result),
                                    np.asarray(ref[si])),
                     f"{agg}: sharded full scan != one-device Session")
            _require(np.array_equal(np.asarray(explicit_t[(si, 0)].result),
                                    np.asarray(ref_x[si])),
                     f"{agg}: sharded explicit values != one-device Session")
        return flush_s, checked

    first_s, checked = serve_and_compare(None)
    c0 = recompile_count()
    _report("mesh.first_flush", compile_and_flush_s=first_s, checked=checked)
    flushes, big_at = [], 2
    for b in range(BATCHES):
        if b == big_at:
            batch, seeds = _big_batch(sess.graph, rng)
        else:
            batch, seeds = _update_batch(sess.graph, rng, 200, 100), None
        t0 = time.perf_counter()
        reports = svc.update(batch)
        update_s = time.perf_counter() - t0
        single.update(batch)
        affected = np.concatenate(
            [np.asarray(r["affected_owners"]) for r in reports.values()])
        flush_s, checked = serve_and_compare(affected)
        flushes.append(flush_s)
        _report(f"mesh.batch{b}", edits=int(batch.size), seeds=seeds,
                update_s=update_s, flush_s=flush_s, checked=checked,
                bit_identical_to_one_device=True, correct=True)
    recompiles = recompile_count() - c0
    _require(recompiles == 0, f"{recompiles} recompiles after warm-up")
    _require(svc.stats["failed"] == 0, f"{svc.stats['failed']} failed tickets")
    _report("mesh.steady", flushes=len(flushes),
            flush_median_s=float(np.median(flushes)), recompiles=recompiles,
            failed_tickets=svc.stats["failed"], correct=True)


# ---------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded Session on a 4-chip mesh")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=100_000,
                    help="vertices of the k-hop graph")
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX backend is {jax.default_backend()!r}, "
              "not 'tpu'; nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache
    from repro.kernels.compat import default_interpret

    if default_interpret():
        print("chip_smoke: Pallas kernels would run in interpret mode",
              file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    _report("device", platform=dev.platform, kind=dev.device_kind,
            count=len(jax.devices()),
            compile_cache=enable_compile_cache(),
            timings="wall clock on the chip host")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            mesh_phase(args.n, args.seed, 4)
        else:
            khop_phase(args.n, args.seed)
            topo_phase(DAG_N, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    _report("total", seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
