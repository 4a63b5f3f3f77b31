"""Streaming-update benchmark: batched maintenance vs full rebuild.

Acceptance target (ISSUE 1): a batched 1k-edge update on a >=100k-vertex
Erdős–Rényi graph — graph edit + ``update_dbindex_batch`` + incremental
``patch_plan_dbindex`` — must beat a full ``build_dbindex`` +
``plan_from_dbindex`` by >= 5x.  Results land in ``BENCH_updates.json``
(via :func:`benchmarks.common.emit_json`) plus the usual CSV rows.

A secondary section measures localized I-Index maintenance on a
pathway-shaped DAG (bounded edge span keeps windows, and thus the
rebuild, tractable at bench scale).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit, emit_json
from repro.core import engine_jax as ej
from repro.core import updates as U
from repro.core.dbindex import build_dbindex
from repro.core.iindex import build_iindex
from repro.core.updates import UpdateBatch
from repro.core.windows import KHopWindow
from repro.graphs.generators import erdos_renyi, random_dag, with_random_attrs


def _fresh_edge_batch(g, rng, size: int) -> UpdateBatch:
    s = rng.integers(0, g.n, size * 3).astype(np.int32)
    d = rng.integers(0, g.n, size * 3).astype(np.int32)
    ok = (s != d) & ~g.contains_edges(s, d)
    _, first = np.unique(g.edge_keys(s, d), return_index=True)
    pick = np.intersect1d(np.flatnonzero(ok), first)[:size]
    return UpdateBatch.inserts(s[pick], d[pick])


def _t(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def run(n: int = 100_000, deg: float = 8.0, k: int = 1, batch_edges: int = 1000,
        json_path: str = "BENCH_updates.json") -> dict:
    rng = np.random.default_rng(0)
    g = with_random_attrs(erdos_renyi(n, deg, directed=False, seed=0), seed=1)
    w = KHopWindow(k)

    idx, t_build0 = _t(lambda: build_dbindex(g, w, method="emc"))
    plan, t_plan0 = _t(lambda: ej.plan_from_dbindex(idx))
    emit(f"updates/initial_build/n{n}", t_build0 * 1e6, f"k={k},deg={deg}")
    emit(f"updates/initial_plan/n{n}", t_plan0 * 1e6, "")

    batch = _fresh_edge_batch(g, rng, batch_edges)
    g2, t_apply = _t(lambda: U.apply_batch(g, batch))
    (idx2, owners), t_update = _t(lambda: U.update_dbindex_batch(idx, g2, w, batch))
    plan2, t_patch = _t(lambda: ej.patch_plan_dbindex(plan, idx2, owners))
    batched_s = t_apply + t_update + t_patch

    idx_f, t_rebuild = _t(lambda: build_dbindex(g2, w, method="emc"))
    plan_f, t_replan = _t(lambda: ej.plan_from_dbindex(idx_f))
    rebuild_s = t_rebuild + t_replan
    speedup = rebuild_s / max(batched_s, 1e-12)

    emit(f"updates/batched_{batch.size}edges/n{n}", batched_s * 1e6,
         f"affected={owners.size}")
    emit(f"updates/full_rebuild/n{n}", rebuild_s * 1e6, "")
    emit(f"updates/speedup/n{n}", speedup, "x_batched_vs_rebuild")

    # sanity: both paths answer identically on device (XLA path, CPU-safe)
    got = np.asarray(ej.query_dbindex(plan2, g2.attrs["val"], "sum", use_pallas=False))
    ref = np.asarray(ej.query_dbindex(
        ej.plan_from_dbindex(idx2, like=plan2),
        g2.attrs["val"], "sum", use_pallas=False))
    assert np.array_equal(got, ref), "patched plan diverged from fresh plan"

    # ---------------- I-Index localized maintenance ------------------- #
    n_dag = max(n // 5, 2000)
    gd = with_random_attrs(random_dag(n_dag, 2.0, seed=2, locality=64), seed=3)
    ii, t_ibuild = _t(lambda: build_iindex(gd))
    iplan, t_iplan = _t(lambda: ej.plan_from_iindex(ii))
    order = gd.topological_order()
    rank = np.empty(gd.n, np.int64)
    rank[order] = np.arange(gd.n)
    # edits land in the last decile of the topological order so the
    # descendant cones stay localized (random heads on a connected DAG
    # union to ~the whole graph, which just measures the rebuild fallback)
    s = order[rng.integers(int(gd.n * 0.9), gd.n - 1, batch_edges // 10)]
    span = rng.integers(1, 64, s.size)
    hi = order[np.minimum(rank[s] + span, gd.n - 1)].astype(np.int32)
    ok = (rank[s] < rank[hi]) & ~gd.contains_edges(s, hi)
    ib = UpdateBatch.inserts(s[ok].astype(np.int32), hi[ok])
    gd2, t_iapply = _t(lambda: U.apply_batch(gd, ib))
    (ii2, cone), t_iupdate = _t(lambda: U.update_iindex_batch(ii, gd2, ib))
    _, t_ipatch = _t(lambda: ej.patch_plan_iindex(iplan, ii2, cone))
    i_batched = t_iapply + t_iupdate + t_ipatch
    i_rebuild = _t(lambda: build_iindex(gd2))[1] + _t(lambda: ej.plan_from_iindex(ii2))[1]
    emit(f"updates/iindex_batched/n{n_dag}", i_batched * 1e6, f"cone={cone.size}")
    emit(f"updates/iindex_rebuild/n{n_dag}", i_rebuild * 1e6, "")
    emit(f"updates/iindex_speedup/n{n_dag}", i_rebuild / max(i_batched, 1e-12), "x")

    payload = {
        "config": {"n": n, "avg_degree": deg, "k": k,
                   "batch_edges": int(batch.size), "method": "emc"},
        "dbindex": {
            "initial_build_s": t_build0,
            "initial_plan_s": t_plan0,
            "batch_apply_s": t_apply,
            "batch_update_index_s": t_update,
            "batch_patch_plan_s": t_patch,
            "batched_total_s": batched_s,
            "full_rebuild_s": t_rebuild,
            "full_replan_s": t_replan,
            "full_rebuild_total_s": rebuild_s,
            "speedup_batched_vs_rebuild": speedup,
            "affected_owners": int(owners.size),
            "secondary_blocks": int(idx2.stats.get("last_secondary_blocks", 0)),
        },
        "iindex": {
            "n": n_dag,
            "batch_edges": int(ib.size),
            "cone_size": int(cone.size),
            "batched_total_s": i_batched,
            "full_rebuild_total_s": i_rebuild,
            "speedup_batched_vs_rebuild": i_rebuild / max(i_batched, 1e-12),
        },
    }
    emit_json(json_path, payload)
    return payload


if __name__ == "__main__":
    run()
