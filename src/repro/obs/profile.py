"""ANALYZE for compiled window plans: one profiled execution, per-phase.

``analyze_session(session)`` (surfaced as :meth:`Session.analyze`) runs
the session's compiled groups **once** under a phase-decomposed scope and
returns an :class:`AnalyzeReport` attributing wall time to named phases:

* device DBIndex terms decompose into ``pass1_gather`` →
  ``pass1_reduce`` → ``pass2_gather`` → ``pass2_reduce`` → ``finalize``
  (the same math as the fused jitted core, evaluated eagerly with a
  device sync after each phase so the timings are real, not dispatch
  shadows);
* device I-Index terms decompose into ``gather`` → ``wd_reduce`` →
  ``inherit`` → ``finalize``;
* host, stateless, and sharded terms run as one ``materialize`` phase
  (their internal phases live on the other side of a runner/shard_map
  boundary);
* algebraic programs add a ``host_combine`` phase;
* input staging (artifact lookup, dtype cast + device put) is charged to
  an explicit ``host_prep`` phase rather than hiding in the residue.

Because every phase blocks on its device results before the clock stops,
the sum of phase times accounts for (>= 95% of) the profiled wall time by
construction — the residue is Python glue between phases.  The eager
evaluation never touches the tracked jitted executors, so ANALYZE cannot
perturb the zero-recompile counters it is often run next to.  Spans are
also emitted on the session's tracer (one ``analyze.phase`` span per
phase) so a Chrome trace shows the same decomposition.

Cache-hit attribution (when a result cache is attached to the session)
and serving-bucket padding waste (via
:meth:`WindowService.debug_report`) complete the picture.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Dict, List, Optional

import numpy as np

__all__ = ["AnalyzeReport", "analyze_session"]


@dataclasses.dataclass
class AnalyzeReport:
    """One profiled run: phases, totals, and attribution quality."""

    wall_s: float
    phases: List[Dict]  # [{group, term, phase, seconds}]
    attributed_s: float
    attribution: float  # attributed_s / wall_s
    phase_totals: Dict  # phase name -> seconds summed across terms
    cache: Dict  # result-cache attribution (empty if none attached)
    version: int

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)

    def to_json(self, **kw) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True, **kw)

    def text(self) -> str:
        L = [f"ANALYZE: wall={self.wall_s * 1e3:.3f} ms, "
             f"attributed={self.attributed_s * 1e3:.3f} ms "
             f"({self.attribution * 100:.1f}%), version={self.version}"]
        width = max((len(p) for p in self.phase_totals), default=10)
        for name, sec in sorted(self.phase_totals.items(),
                                key=lambda kv: -kv[1]):
            share = sec / self.wall_s if self.wall_s else 0.0
            L.append(f"  {name:<{width}}  {sec * 1e3:9.3f} ms  "
                     f"{share * 100:5.1f}%")
        for p in self.phases:
            L.append(f"    group {p['group']} term {p['term']} "
                     f"{p['phase']}: {p['seconds'] * 1e3:.3f} ms")
        if self.cache:
            L.append(f"  cache: {self.cache}")
        return "\n".join(L)


class _PhaseClock:
    """Collects (group, term, phase) -> seconds; blocks device results
    inside the timed region so a phase owns its own compute."""

    def __init__(self, tracer):
        self.rows: List[Dict] = []
        self._tracer = tracer

    def timed(self, group: int, term: str, phase: str, fn):
        import jax

        with self._tracer.span("analyze.phase", cat="analyze",
                               phase=phase, term=term):
            t0 = time.perf_counter()
            out = fn()
            out = jax.block_until_ready(out)
            dt = time.perf_counter() - t0
        self.rows.append({"group": group, "term": term, "phase": phase,
                          "seconds": dt})
        return out


# ---------------------------------------------------------------------- #
#  Phase-decomposed eager executions (mirror the fused jitted cores)
# ---------------------------------------------------------------------- #
def _analyze_dbindex_term(clock: _PhaseClock, gi: int, tname: str, plan,
                          values, aggs, opts) -> Dict:
    import jax.numpy as jnp

    from repro.core.aggregates import pack_channels
    from repro.core.engine_jax import _minmax_pass1, _minmax_pass2
    from repro.kernels.segment_reduce.ops import segment_sum_gathered

    use_pallas = opts.get("use_pallas", True)
    interpret = opts.get("interpret")
    pack = pack_channels(tuple(aggs))
    # device put + dtype cast is real work — charge it to its own phase
    values = clock.timed(gi, tname, "host_prep",
                         lambda: jnp.asarray(values, jnp.float32))
    sum_cols = pack.channels_of("sum")
    minmax_cols = [(ci, m, s) for ci, (m, s) in enumerate(pack.channels)
                   if m != "sum"]

    need_g1 = any(pack.channels[ci][1] in ("value", "square")
                  for ci in sum_cols) or (plan.p1_ell is None and minmax_cols)
    g1 = None
    if need_g1:
        g1 = clock.timed(gi, tname, "pass1_gather",
                         lambda: jnp.take(values, plan.pass1.gather_padded))

    def _pass1():
        t_cols = {}
        for ci in sum_cols:
            src = pack.channels[ci][1]
            if src == "ones":
                t_cols[ci] = plan.block_sizes
            else:
                t_cols[ci] = segment_sum_gathered(
                    plan.pass1, g1 if src == "value" else g1 * g1,
                    use_pallas=use_pallas, interpret=interpret)
        for ci, mname, src in minmax_cols:
            vsrc = values if src == "value" else values * values
            gsrc = g1 if (g1 is None or src == "value") else g1 * g1
            t_cols[ci] = _minmax_pass1(plan, vsrc, mname, gathered=gsrc)
        return t_cols

    t_cols = clock.timed(gi, tname, "pass1_reduce", _pass1)

    outs = {}
    if sum_cols:
        g2 = clock.timed(
            gi, tname, "pass2_gather",
            lambda: jnp.take(
                jnp.stack([t_cols[ci] for ci in sum_cols], axis=1),
                plan.pass2.gather_padded, axis=0))

        def _pass2():
            reduced = segment_sum_gathered(
                plan.pass2, g2, use_pallas=use_pallas, interpret=interpret)
            if reduced.ndim == 1:
                reduced = reduced[:, None]
            return {ci: reduced[:, j] for j, ci in enumerate(sum_cols)}

        outs.update(clock.timed(gi, tname, "pass2_reduce", _pass2))
    if minmax_cols:
        def _pass2_minmax():
            return {ci: _minmax_pass2(plan, t_cols[ci], mname)
                    for ci, mname, _ in minmax_cols}

        outs.update(clock.timed(gi, tname, "pass2_reduce", _pass2_minmax))

    chans = tuple(outs[ci] for ci in range(len(pack.channels)))
    return clock.timed(
        gi, tname, "finalize",
        lambda: dict(zip(aggs, pack.finalize(chans))))


def _analyze_iindex_term(clock: _PhaseClock, gi: int, tname: str, plan,
                         values, aggs, opts) -> Dict:
    import jax.numpy as jnp

    from repro.core.aggregates import pack_channels
    from repro.core.engine_jax import (
        _inherit_scan,
        _segment_minmax_gathered,
    )
    from repro.kernels.segment_reduce.ops import segment_sum_gathered

    use_pallas = opts.get("use_pallas", True)
    interpret = opts.get("interpret")
    schedule = opts.get("schedule", "level")
    pack = pack_channels(tuple(aggs))
    values = clock.timed(gi, tname, "host_prep",
                         lambda: jnp.asarray(values, jnp.float32))
    n = plan.n

    def _gather():
        ones = jnp.ones(n, jnp.float32)
        srcs = {"value": values, "ones": ones, "square": values * values}
        cols = jnp.stack([srcs[src] for _, src in pack.channels], axis=1)
        return jnp.take(cols, plan.wd_plan.gather_padded, axis=0)

    g = clock.timed(gi, tname, "gather", _gather)
    chans = [None] * len(pack.channels)
    sum_cols = pack.channels_of("sum")

    def _wd_reduce():
        parts = {}
        if sum_cols:
            wdp = segment_sum_gathered(plan.wd_plan, g[:, list(sum_cols)],
                                       use_pallas=use_pallas,
                                       interpret=interpret)
            parts["sum"] = wdp[:, None] if wdp.ndim == 1 else wdp
        for mname in ("min", "max"):
            for ci in pack.channels_of(mname):
                # string key: pytree dict flatten sorts keys, so mixing
                # str and tuple keys would break block_until_ready
                parts[f"{mname}:{ci}"] = _segment_minmax_gathered(
                    plan.wd_plan, g[:, ci], n, mname)
        return parts

    parts = clock.timed(gi, tname, "wd_reduce", _wd_reduce)

    def _inherit():
        if sum_cols:
            done = _inherit_scan(parts["sum"], plan.pid, plan.level,
                                 plan.max_level, n, "sum", schedule)
            for j, ci in enumerate(sum_cols):
                chans[ci] = done[:, j]
        for mname in ("min", "max"):
            for ci in pack.channels_of(mname):
                chans[ci] = _inherit_scan(parts[f"{mname}:{ci}"], plan.pid,
                                          plan.level, plan.max_level, n,
                                          mname, schedule)
        return [c for c in chans if c is not None]

    clock.timed(gi, tname, "inherit", _inherit)
    return clock.timed(
        gi, tname, "finalize",
        lambda: dict(zip(aggs, pack.finalize(chans))))


# ---------------------------------------------------------------------- #
def analyze_session(session, spec=None, values=None) -> AnalyzeReport:
    """Execute the selected groups once, phase-profiled (see module doc).

    ``spec`` filters like :func:`~repro.obs.explain.explain_session`;
    ``values`` overrides the graph attribute(s) as in ``Session.run``.
    """
    from repro.obs.explain import _match_groups

    clock = _PhaseClock(session.tracer)
    cache_before = _cache_stats(session)
    t_start = time.perf_counter()
    for gi in _match_groups(session, spec):
        grp = session.compiled.groups[gi]
        prog = session._programs[gi]

        def _prep(gi=gi, grp=grp):
            return (session._group_artifacts(gi),
                    session._values_for(grp, values))

        arts, vals = clock.timed(gi, "-", "host_prep", _prep)
        aggs = prog.term_aggs if prog is not None else grp.aggs
        term_outs = []
        for term, (index, plan) in zip(session._group_terms(gi), arts):
            tname = term.name()
            cls = type(plan).__name__ if plan is not None else None
            if cls == "DBIndexPlan":
                out = _analyze_dbindex_term(clock, gi, tname, plan, vals,
                                            aggs, session._opts)
            elif cls == "IIndexPlan":
                out = _analyze_iindex_term(clock, gi, tname, plan, vals,
                                           aggs, session._opts)
            else:
                # host / stateless / sharded: the runner is the phase —
                # its internals live behind a runner or shard_map boundary
                out = clock.timed(
                    gi, tname, "materialize",
                    lambda term=term, index=index, plan=plan:
                        session._exec_term(grp, term, index, plan, vals,
                                           session.graph, aggs))
            term_outs.append(out)
        if prog is not None:
            from repro.core.api import _combine_program

            clock.timed(gi, "-", "host_combine",
                        lambda: _combine_program(prog, grp.aggs, term_outs))
    wall = time.perf_counter() - t_start

    attributed = sum(p["seconds"] for p in clock.rows)
    totals: Dict[str, float] = {}
    for p in clock.rows:
        totals[p["phase"]] = totals.get(p["phase"], 0.0) + p["seconds"]
    return AnalyzeReport(
        wall_s=wall,
        phases=clock.rows,
        attributed_s=attributed,
        attribution=(attributed / wall) if wall > 0 else 1.0,
        phase_totals=totals,
        cache=_cache_delta(cache_before, _cache_stats(session)),
        version=int(session.version),
    )


def _cache_stats(session) -> Dict:
    cache = getattr(session, "_result_cache", None)
    if cache is None:
        return {}
    out = {}
    for k in ("hits", "misses", "invalidations", "evictions"):
        v = getattr(cache, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _cache_delta(before: Dict, after: Dict) -> Dict:
    if not after:
        return {}
    out = {k: after[k] for k in after}
    hits = after.get("hits", 0)
    misses = after.get("misses", 0)
    out["hit_rate"] = hits / max(hits + misses, 1)
    out["during_run"] = {k: after[k] - before.get(k, 0) for k in after}
    return out
