"""Sharded streaming runtime: distributed window queries + update propagation.

This subsystem makes every prior layer — fused multi-channel queries,
incremental plan patching, capability planning — multi-device at once:

* :class:`ShardedDBPlan` — a DBIndex device plan laid out as *per-shard tile
  groups*.  The single-host plan already groups rows (members→blocks links,
  links→owners) by output tile group; here whole groups are assigned to mesh
  shards (greedy balance over padded rows), so no segment ever straddles a
  shard.  That alignment is what buys **bit-identity** with the single-host
  fused path: each segment's partial is produced by exactly one shard in the
  same row order, and the cross-shard ``psum`` only ever adds exact zeros
  (``pmin``/``pmax`` add exact identities) from the non-owning shards.

* :func:`query_sharded_multi` — the stacked-channel matrix form of
  ``query_dbindex_sharded``: fused SUM/COUNT/AVG channels ride one ``psum``
  per pass, MIN/MAX ride ``pmin``/``pmax`` over sharded ELL row layouts
  (fall back to the masked tile layout when the plan carries no ELL).
  Collective footprint per query: ``|T|·C + |n|·C`` floats, independent of
  window sizes — the paper's sharing structure keeps the wire format tiny.
  :func:`query_sharded_many` batches a whole [B, n] ``run_many`` bucket
  through the same shard-local fn in ONE launch (trailing values axis).

* :func:`patch_sharded_plan` — streamed update propagation.  The changed
  tile groups are the wire format: after a batched index update only the
  groups holding appended secondary blocks (pass 1) and the affected
  owners' link groups (pass 2) are re-laid-out and scattered into the
  device-resident shards via ``jax.Array.at[...].set`` (the same
  shape-stable splice contract as
  :func:`repro.kernels.segment_reduce.ops.patch_tile_plan`), so a batch
  ships a few KB of patches instead of re-uploading the full plan, and the
  jitted sharded query never retraces.

* :class:`ShardedSession` — ``Session(mesh=...)``: owns per-shard plans,
  shards the affected-owner BFS over the data axis (each shard traverses
  only its slice of the batch's touched endpoints), streams batches with
  zero recompiles, and serves ``run`` / ``run_many`` across the mesh.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import obs as _obs
from repro.core.dbindex import DBIndex, build_dbindex
from repro.core.graph import Graph
from repro.core.streaming import StalenessPolicy
from repro.core.updates import (
    UpdateBatch,
    sharded_affected_owners,
    update_dbindex_batch,
)


def _axes_tuple(axis) -> Tuple[str, ...]:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def _mesh_ndev(mesh, axes: Tuple[str, ...]) -> int:
    return int(np.prod([mesh.shape[a] for a in axes]))


# ---------------------------------------------------------------------- #
#  Shard-aligned plan layout
# ---------------------------------------------------------------------- #
def _group_layout(tile_plan) -> Tuple[np.ndarray, np.ndarray]:
    """(tiles_per_group, flat row starts) of a group-aligned tile layout."""
    m2out = np.asarray(tile_plan.m2out)
    tiles = np.bincount(m2out, minlength=tile_plan.num_out_tiles).astype(np.int64)
    starts = np.zeros(tile_plan.num_out_tiles + 1, np.int64)
    np.cumsum(tiles * tile_plan.tm, out=starts[1:])
    return tiles, starts


def _assign_groups(rows_per_group: np.ndarray, ndev: int):
    """Greedy balanced assignment of whole tile groups to shards.

    Groups are placed largest-first on the least-loaded shard (first shard
    wins ties) — deterministic, and within ~1 group of optimal for the
    near-uniform group sizes the headroom-floored layouts produce.  Returns
    ``(shard_of_group, offset_in_shard, rows_per_shard)``; every shard's row
    span is padded to the max load so ``shard_map`` sees equal shards.
    """
    order = np.argsort(-rows_per_group, kind="stable")
    shard_of = np.zeros(rows_per_group.size, np.int64)
    offset = np.zeros(rows_per_group.size, np.int64)
    load = np.zeros(ndev, np.int64)
    for g in order:
        s = int(np.argmin(load))
        shard_of[g] = s
        offset[g] = load[s]
        load[s] += rows_per_group[g]
    return shard_of, offset, max(int(load.max()), 1)


def _shard_tiles(tile_plan, ndev: int, prev_rows: Optional[int]):
    """(tiles per group, rows per shard) of one pass.  A rebuild replacing
    a plan of ``prev_rows`` rows per shard keeps that count where
    :func:`keep_shape` keeps it; when the layout's own tiles do not fit in
    it, each group takes only the tiles its rows fill (the headroom gives
    way).  A group's rows lead its span, so packing the shorter spans drops
    padding rows only."""
    from repro.kernels.segment_reduce.ops import keep_shape

    tiles = _group_layout(tile_plan)[0]
    rows = _assign_groups(tiles * tile_plan.tm, ndev)[2]
    if prev_rows is None:
        return tiles, rows
    filled = (np.asarray(tile_plan.seg_tiles) >= 0).sum(axis=1)
    group_rows = np.bincount(np.asarray(tile_plan.m2out), weights=filled,
                             minlength=tiles.size).astype(np.int64)
    tight = np.maximum(1, -(-group_rows // tile_plan.tm))
    need = _assign_groups(tight * tile_plan.tm, ndev)[2]
    kept = keep_shape(prev_rows, need, rows)
    return (tight if kept < rows else tiles), kept


def _pack_shards(src_seg, src_gather, starts, rows_per_group, shard_of, offset,
                 rows_cap: int, ndev: int):
    """Scatter group row spans into equal per-shard flat arrays (pad -1/0)."""
    seg = np.full(ndev * rows_cap, -1, np.int32)
    gather = np.zeros(ndev * rows_cap, np.int32)
    for g in range(rows_per_group.size):
        span = int(rows_per_group[g])
        if span == 0:
            continue
        lo = int(shard_of[g]) * rows_cap + int(offset[g])
        s0 = int(starts[g])
        seg[lo : lo + span] = src_seg[s0 : s0 + span]
        gather[lo : lo + span] = src_gather[s0 : s0 + span]
    return seg, gather


@dataclasses.dataclass(frozen=True)
class ShardedDBPlan:
    """Device-resident DBIndex plan shards plus the host metadata needed to
    route tile-group patches to the shard that owns them.

    Tile rows (pass 1/2) are sharded at whole-group granularity by the
    greedy assignment; ELL rows are sharded by contiguous id chunks (block
    ids for pass 1, owner ids for pass 2) with an explicit per-row id array
    so the local reduce scatters its rows into an identity-filled full
    vector before the ``pmin``/``pmax`` combine.
    """

    mesh: object
    axes: Tuple[str, ...]
    ndev: int
    n: int
    num_blocks: int
    block_capacity: int
    tm: int
    ts: int
    headroom: float
    nb_seg: int  # padded pass-1 segment space (num_out_tiles1 * ts)
    n_seg: int  # padded pass-2 segment space (num_out_tiles2 * ts)
    rows1: int  # per-shard pass-1 rows
    rows2: int  # per-shard pass-2 rows
    # device arrays ([ndev*rows] flats sharded over `axes`; sizes replicated)
    p1_gather: object
    p1_seg: object
    p2_gather: object
    p2_seg: object
    block_sizes: object  # f32 [block_capacity], replicated
    e1: Optional[object] = None  # i32 [ndev*ell_rows1, R1] member ids
    e1_ids: Optional[object] = None  # i32 [ndev*ell_rows1] block id / -1
    e2: Optional[object] = None  # i32 [ndev*ell_rows2, R2] block ids
    e2_ids: Optional[object] = None  # i32 [ndev*ell_rows2] owner id / -1
    # host metadata (patch routing)
    group_shard1: Optional[np.ndarray] = None
    group_off1: Optional[np.ndarray] = None
    group_tiles1: Optional[np.ndarray] = None
    group_shard2: Optional[np.ndarray] = None
    group_off2: Optional[np.ndarray] = None
    group_tiles2: Optional[np.ndarray] = None
    stats: Dict = dataclasses.field(default_factory=dict, repr=False)

    @property
    def has_ell(self) -> bool:
        return self.e1 is not None

    @property
    def ell_widths(self) -> Optional[Tuple[int, int]]:
        """(R1, R2) of the ELL layouts, or None when the plan has none."""
        return (self.e1.shape[1], self.e2.shape[1]) if self.has_ell else None

    def array_nbytes(self) -> Dict:
        """Exact per-array device bytes — the same accounting surface as
        ``DBIndexPlan.array_nbytes`` / ``IIndexPlan.array_nbytes``, so
        EXPLAIN reports one schema across host/device/sharded plans."""
        out = {
            "p1_gather": int(self.p1_gather.nbytes),
            "p1_seg": int(self.p1_seg.nbytes),
            "p2_gather": int(self.p2_gather.nbytes),
            "p2_seg": int(self.p2_seg.nbytes),
            "block_sizes": int(self.block_sizes.nbytes),
        }
        if self.has_ell:
            out["e1"] = int(self.e1.nbytes)
            out["e1_ids"] = int(self.e1_ids.nbytes)
            out["e2"] = int(self.e2.nbytes)
            out["e2_ids"] = int(self.e2_ids.nbytes)
        return out

    def plan_nbytes(self) -> int:
        """Total device bytes held by this plan."""
        return sum(self.array_nbytes().values())

    def size_bytes(self) -> int:
        # kept for pre-existing callers (wire ledger, benches)
        return self.plan_nbytes()

    def shard_row_loads(self) -> Dict:
        """Per-shard real (unpadded) row loads for both passes, from the
        patch-routing metadata — EXPLAIN's shard-balance view.  Empty dict
        when routing metadata was dropped (plans restored without it)."""
        out: Dict = {}
        for name, shard_of, tiles, rows_cap in (
            ("pass1", self.group_shard1, self.group_tiles1, self.rows1),
            ("pass2", self.group_shard2, self.group_tiles2, self.rows2),
        ):
            if shard_of is None or tiles is None:
                continue
            loads = np.zeros(self.ndev, np.int64)
            np.add.at(loads, np.asarray(shard_of, np.int64),
                      np.asarray(tiles, np.int64) * self.tm)
            out[name] = {
                "rows_per_shard": [int(x) for x in loads],
                "rows_capacity": int(rows_cap),
                "balance": (float(loads.min() / loads.max())
                            if loads.max() else 1.0),
            }
        return out


def _shard_put(mesh, axes, arr, sharded: bool):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    # one entry per dimension: a scatter's output spec is spelled that
    # way, and a jitted query sees P() and P(None,) as different arguments
    rest = (None,) * (np.ndim(arr) - 1)
    spec = P(axes, *rest) if sharded else P(None, *rest)
    return jax.device_put(arr, NamedSharding(mesh, spec))


def _ell_shards(rows_np: np.ndarray, num_ids: int, ndev: int):
    """Pad an [num_ids, R] ELL matrix to equal contiguous id chunks."""
    from repro.core.engine_jax import _ELL_SENTINEL

    per = max(-(-num_ids // ndev), 1)
    pad = per * ndev - num_ids
    if pad:
        rows_np = np.concatenate(
            [rows_np, np.full((pad, rows_np.shape[1]), _ELL_SENTINEL, np.int32)]
        )
    ids = np.full(per * ndev, -1, np.int32)
    ids[:num_ids] = np.arange(num_ids, dtype=np.int32)
    return rows_np, ids


def build_sharded_plan(plan, mesh, axis="data", headroom: float = 0.0,
                       stats: Optional[Dict] = None,
                       like: Optional[ShardedDBPlan] = None) -> ShardedDBPlan:
    """Lay a single-host :class:`~repro.core.engine_jax.DBIndexPlan` out as
    device-resident shards (see :class:`ShardedDBPlan`).  ``headroom`` is
    recorded so rebuilds keep the same streaming slack; ``stats`` carries
    counters forward across rebuilds; ``like``, the plan being replaced,
    lends its per-shard row counts (see :func:`_shard_tiles`)."""
    axes = _axes_tuple(axis)
    ndev = _mesh_ndev(mesh, axes)

    tiles1, rows1 = _shard_tiles(plan.pass1, ndev,
                                 like.rows1 if like is not None else None)
    tiles2, rows2 = _shard_tiles(plan.pass2, ndev,
                                 like.rows2 if like is not None else None)
    starts1, starts2 = _group_layout(plan.pass1)[1], _group_layout(plan.pass2)[1]
    rows_g1, rows_g2 = tiles1 * plan.pass1.tm, tiles2 * plan.pass2.tm
    shard1, off1, _ = _assign_groups(rows_g1, ndev)
    shard2, off2, _ = _assign_groups(rows_g2, ndev)
    p1_seg, p1_gather = _pack_shards(
        np.asarray(plan.pass1.seg_tiles).reshape(-1),
        np.asarray(plan.pass1.gather_padded),
        starts1, rows_g1, shard1, off1, rows1, ndev,
    )
    p2_seg, p2_gather = _pack_shards(
        np.asarray(plan.pass2.seg_tiles).reshape(-1),
        np.asarray(plan.pass2.gather_padded),
        starts2, rows_g2, shard2, off2, rows2, ndev,
    )
    e1 = e1_ids = e2 = e2_ids = None
    if plan.p1_ell is not None:
        e1_np, e1_ids_np = _ell_shards(np.asarray(plan.p1_ell),
                                       plan.block_capacity, ndev)
        e2_np, e2_ids_np = _ell_shards(np.asarray(plan.p2_ell), plan.n, ndev)
        e1 = _shard_put(mesh, axes, e1_np, True)
        e1_ids = _shard_put(mesh, axes, e1_ids_np, True)
        e2 = _shard_put(mesh, axes, e2_np, True)
        e2_ids = _shard_put(mesh, axes, e2_ids_np, True)
    base_stats = dict(stats or {})
    base_stats.setdefault("patched_bytes_total", 0)
    base_stats.setdefault("rebuilds", 0)
    base_stats.setdefault("version", 0)
    # a fresh layout lays out every member row the index holds — any
    # previously device-compacted garbage rows are back, so the ledger
    # the patcher keeps must restart empty
    base_stats.pop("p1_compacted_ids", None)
    splan = ShardedDBPlan(
        mesh=mesh, axes=axes, ndev=ndev,
        n=plan.n, num_blocks=plan.num_blocks,
        block_capacity=plan.block_capacity,
        tm=plan.pass1.tm, ts=plan.pass1.ts,
        headroom=headroom,
        nb_seg=plan.pass1.num_out_tiles * plan.pass1.ts,
        n_seg=plan.pass2.num_out_tiles * plan.pass2.ts,
        rows1=rows1, rows2=rows2,
        p1_gather=_shard_put(mesh, axes, p1_gather, True),
        p1_seg=_shard_put(mesh, axes, p1_seg, True),
        p2_gather=_shard_put(mesh, axes, p2_gather, True),
        p2_seg=_shard_put(mesh, axes, p2_seg, True),
        block_sizes=_shard_put(
            mesh, axes, np.asarray(plan.block_sizes, np.float32), False
        ),
        e1=e1, e1_ids=e1_ids, e2=e2, e2_ids=e2_ids,
        group_shard1=shard1, group_off1=off1, group_tiles1=tiles1,
        group_shard2=shard2, group_off2=off2, group_tiles2=tiles2,
        stats=base_stats,
    )
    base_stats["full_bytes"] = splan.size_bytes()
    return splan


# ---------------------------------------------------------------------- #
#  Sharded fused multi-aggregate query
# ---------------------------------------------------------------------- #
def _sharded_query_impl(sharded, repl, values, mesh, axes, aggs, cfg):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.aggregates import pack_channels
    from repro.core.engine_jax import _ell_reduce

    n, cap, nb_seg, n_seg, has_ell = cfg
    pack = pack_channels(aggs)
    sum_cols = pack.channels_of("sum")
    minmax_cols = [
        (ci, m, s) for ci, (m, s) in enumerate(pack.channels) if m != "sum"
    ]
    _SEG = {"min": jax.ops.segment_min, "max": jax.ops.segment_max}
    _COMB = {"min": jax.lax.pmin, "max": jax.lax.pmax}
    _FILL = {"min": jnp.inf, "max": -jnp.inf}

    def local(shard_args, repl_args, vals):
        if has_ell:
            p1g, p1s, p2g, p2s, e1, e1i, e2, e2i = shard_args
        else:
            p1g, p1s, p2g, p2s = shard_args
        (bsz,) = repl_args
        # ``vals`` is [n] (one query) or [n, B] (a run_many bucket riding a
        # trailing batched values axis through the same shard-local fn —
        # gathers/segment reduces/collectives all carry the extra axis, so
        # a whole [B, n] batch is ONE launch instead of B replays)
        bat = vals.ndim == 2

        def col(mask):  # broadcast a row mask over the batch axis
            return mask[:, None] if bat else mask

        # ---- pass 1: block partials, one psum for the stacked channels --- #
        # "square" channels (registered derived aggregates) square the
        # gathered rows — take(v², idx) == take(v, idx)², so no extra gather
        t_cols = {}

        def sum_pass1(rows):
            ok1 = p1s >= 0
            part = jax.ops.segment_sum(
                jnp.where(col(ok1), rows, 0.0),
                jnp.where(ok1, p1s, nb_seg),
                num_segments=nb_seg + 1,
            )[:nb_seg]
            return jax.lax.psum(part, axes)[:cap]

        srcs_needed = {pack.channels[ci][1] for ci in sum_cols} - {"ones"}
        if srcs_needed:
            rows1 = jnp.take(vals, p1g, axis=0)
            t_src = {s: sum_pass1(rows1 if s == "value" else rows1 * rows1)
                     for s in srcs_needed}
        for ci in sum_cols:
            # block cardinalities are host-exact replicated metadata
            if pack.channels[ci][1] == "ones":
                t_cols[ci] = (
                    jnp.broadcast_to(bsz[:, None], (bsz.shape[0],) + vals.shape[1:])
                    if bat else bsz
                )
            else:
                t_cols[ci] = t_src[pack.channels[ci][1]]
        for ci, m, s in minmax_cols:
            v_in = vals if s == "value" else vals * vals
            if has_ell:
                red = _ell_reduce(e1, v_in, m)  # [rows/shard(, B)]
                part = _SEG[m](red, jnp.where(e1i >= 0, e1i, cap),
                               num_segments=cap + 1)[:cap]
                t_cols[ci] = _COMB[m](part, axes)
            else:
                ok1 = p1s >= 0
                part = _SEG[m](
                    jnp.where(col(ok1), jnp.take(v_in, p1g, axis=0), _FILL[m]),
                    jnp.where(ok1, p1s, nb_seg),
                    num_segments=nb_seg + 1,
                )[:nb_seg]
                t_cols[ci] = _COMB[m](part, axes)[:cap]

        # ---- pass 2: one gather of the stacked matrix + one psum --------- #
        outs = {}
        if sum_cols:
            t_mat = jnp.stack([t_cols[ci] for ci in sum_cols], axis=1)
            ok2 = p2s >= 0
            g2 = jnp.take(t_mat, p2g, axis=0)
            part = jax.ops.segment_sum(
                jnp.where(ok2[:, None, None] if bat else ok2[:, None], g2, 0.0),
                jnp.where(ok2, p2s, n_seg),
                num_segments=n_seg + 1,
            )[:n_seg]
            red = jax.lax.psum(part, axes)[:n]
            for j, ci in enumerate(sum_cols):
                outs[ci] = red[:, j]
        for ci, m, _ in minmax_cols:
            if has_ell:
                red = _ell_reduce(e2, t_cols[ci], m)
                part = _SEG[m](red, jnp.where(e2i >= 0, e2i, n),
                               num_segments=n + 1)[:n]
                outs[ci] = _COMB[m](part, axes)
            else:
                ok2 = p2s >= 0
                part = _SEG[m](
                    jnp.where(col(ok2), jnp.take(t_cols[ci], p2g, axis=0),
                              _FILL[m]),
                    jnp.where(ok2, p2s, n_seg),
                    num_segments=n_seg + 1,
                )[:n_seg]
                outs[ci] = _COMB[m](part, axes)[:n]
        return tuple(outs[ci] for ci in range(len(pack.channels)))

    sh = P(axes)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(tuple(sh for _ in sharded), (P(),), P()),
        out_specs=tuple(P() for _ in pack.channels),
        check_vma=False,
    )
    # channel results only — finalizers run on the host in the public
    # wrappers (XLA fusion may FMA-contract a finalizer and re-round, and
    # the TPU's division is not correctly rounded; NumPy matches the oracle)
    return fn(sharded, repl, values)


_sharded_query = None  # jitted lazily (keeps module import JAX-light)


def _get_sharded_query():
    global _sharded_query
    if _sharded_query is None:
        import functools
        import jax

        _sharded_query = functools.partial(jax.jit, static_argnames=(
            "mesh", "axes", "aggs", "cfg"))(_sharded_query_impl)
    return _sharded_query


def query_cache_size() -> int:
    """Jit cache entries of the sharded fused query (recompile counter)."""
    return _get_sharded_query()._cache_size() if _sharded_query else 0


def _splan_call_args(splan: ShardedDBPlan):
    sharded = (splan.p1_gather, splan.p1_seg, splan.p2_gather, splan.p2_seg)
    if splan.has_ell:
        sharded = sharded + (splan.e1, splan.e1_ids, splan.e2, splan.e2_ids)
    cfg = (splan.n, splan.block_capacity, splan.nb_seg, splan.n_seg,
           splan.has_ell)
    return sharded, cfg


def _finalize_chans(aggs: tuple, chans):
    from repro.core.aggregates import pack_channels

    return pack_channels(aggs).finalize(chans)


def query_sharded_multi(splan: ShardedDBPlan, values, aggs: Sequence[str]):
    """Fused multi-aggregate sharded query; returns one array per aggregate,
    bit-identical to the single-host ``query_dbindex_multi`` results."""
    import jax.numpy as jnp

    values = jnp.asarray(values, jnp.float32)
    sharded, cfg = _splan_call_args(splan)
    _obs.get_registry().counter(
        "repro_shard_launches_total",
        "per-device launches of the sharded fused query").inc(splan.ndev)
    chans = _get_sharded_query()(
        sharded, (splan.block_sizes,), values,
        mesh=splan.mesh, axes=splan.axes, aggs=tuple(aggs), cfg=cfg,
    )
    return _finalize_chans(tuple(aggs), chans)


def query_sharded_many(splan: ShardedDBPlan, values_batch,
                       aggs: Sequence[str]):
    """[B, n] serving traffic in ONE sharded launch.

    The shard-local fn carries a trailing batched values axis, so
    ``ShardedSession.run_many`` no longer replays the compiled executable
    per batch row (the old ROADMAP open item) — one launch computes every
    row, and the collective footprint stays one ``psum``/``pmin``/``pmax``
    per pass with a ``B``-wide payload.  Returns one [B, n] array per
    aggregate.
    """
    import jax.numpy as jnp

    vb = jnp.asarray(values_batch, jnp.float32)
    assert vb.ndim == 2, "values_batch must be [B, n]"
    sharded, cfg = _splan_call_args(splan)
    _obs.get_registry().counter(
        "repro_shard_launches_total",
        "per-device launches of the sharded fused query").inc(splan.ndev)
    chans = _get_sharded_query()(
        sharded, (splan.block_sizes,), vb.T,
        mesh=splan.mesh, axes=splan.axes, aggs=tuple(aggs), cfg=cfg,
    )
    return tuple(o.T for o in _finalize_chans(tuple(aggs), chans))


# ---------------------------------------------------------------------- #
#  Streamed update propagation: per-shard tile-group patches
# ---------------------------------------------------------------------- #
def _group_rows(sorted_seg: np.ndarray, gather_src: np.ndarray, g: int,
                ts: int, span: int):
    """Padded (seg, gather) rows of one output tile group from the full new
    arrays, or None when the group's rows no longer fit its capacity."""
    lo, hi = np.searchsorted(sorted_seg, (g * ts, (g + 1) * ts))
    if hi - lo > span:
        return None
    seg = np.full(span, -1, np.int32)
    gather = np.zeros(span, np.int32)
    seg[: hi - lo] = sorted_seg[lo:hi]
    gather[: hi - lo] = gather_src[lo:hi]
    return seg, gather


def patch_sharded_plan(
    splan: ShardedDBPlan, index: DBIndex, changed_owners: np.ndarray,
    compact_garbage: float = 0.25, wire: Optional[list] = None,
) -> ShardedDBPlan:
    """Propagate one streamed batch into the device-resident plan shards.

    The wire format is *changed tile groups*: pass 1 ships only the groups
    holding appended secondary block ids, pass 2 only the groups containing
    ``changed_owners``; each patch is scattered into the owning shard's flat
    rows via ``at[...].set`` (shapes never change in steady state, so jitted
    queries never retrace).  ELL rows are row-addressed (block id / owner
    id) and patched the same way.  Falls back to a full rebuild — a
    recompile-sized event, like capacity growth — when the updater rebuilt
    outright, capacity is exceeded, or a group/row no longer fits.

    Delete-dominated streams accumulate *garbage blocks* (zero-link blocks
    whose member rows still occupy pass-1 tiles).  When the garbage
    fraction crosses ``compact_garbage``, pass 1 is re-packed **per shard,
    in place**: every pass-1 group whose block range holds a garbage or
    appended block is re-laid-out from the index with the garbage blocks'
    member rows dropped and scattered into its owning shard's existing
    flat rows (groups without either are bit-identical and ship nothing).
    Shapes never change (no retrace, unlike the single-host compaction
    which rebuilds pass 1), garbage partials simply become identities
    nobody gathers — correctness is untouched because a garbage block by
    definition has no pass-2 link — and the freed tile slots keep future
    appends below the rebuild threshold.

    ``wire``, when a list, receives one serializable *replication message*
    describing exactly what this call shipped to the shards: the changed
    tile groups' flat positions and rows, the appended block sizes and ELL
    rows (kind ``"patch"``), or the full index on a rebuild (kind
    ``"resync"``).  A follower holding the same pre-patch plan replays the
    message with :func:`apply_wire_message` and lands on a bit-identical
    plan — the patch stream *is* the replication stream.
    """
    ts = splan.ts
    stats = dict(splan.stats)
    stats["version"] = stats.get("version", 0) + 1

    def rebuild():
        stats["rebuilds"] = stats.get("rebuilds", 0) + 1
        _obs.get_registry().counter(
            "repro_plan_rebuilds_total",
            "sharded plan full rebuilds (recompile-sized events)").inc()
        stats["last_patch_groups"] = -1
        stats["last_compaction"] = False
        out = _rebuild(index, splan, stats)
        out.stats["last_patch_bytes"] = out.size_bytes()
        if wire is not None:
            from repro.obs.audit import plan_crc

            # stamp the post-apply content digest: a follower replaying
            # this message self-checks against it (apply_wire_message)
            wire.append({"kind": "resync", "index": index,
                         "plan_crc": plan_crc(out)})
        return out

    if (index.stats.get("last_full_rebuild")
            or index.num_blocks > splan.block_capacity):
        return rebuild()

    owners = np.unique(np.asarray(changed_owners, np.int64))
    new_blocks = np.arange(splan.num_blocks, index.num_blocks, dtype=np.int64)
    if splan.has_ell:
        # width overflow is a rebuild-sized event — detect it before any
        # device scatter is staged (same early-out as the single-host
        # ``_patch_ell``), not after the tile-group work is already done
        r1, r2 = splan.e1.shape[1], splan.e2.shape[1]
        if new_blocks.size and int(
                np.diff(index.block_offsets)[new_blocks].max()) > r1:
            return rebuild()
        if owners.size and int(
                np.diff(index.link_owner_offsets)[owners].max()) > r2:
            return rebuild()
    member_block = np.asarray(index.member_block_ids, np.int64)
    link_owner = np.asarray(index.link_owner_ids, np.int64)

    # per-shard pass-1 garbage compaction.  Only groups whose block range
    # holds *fresh* garbage (rows to drop that are still on device) or an
    # appended block differ from the device content — everything else is
    # bit-identical and ships nothing, so the changed-tile-groups wire
    # format survives compaction.  ``p1_compacted_ids`` records which
    # garbage blocks' rows are already gone from the device shards: the
    # index keeps its garbage until a rebuild, so without the ledger every
    # later batch would re-ship the same compacted groups; it also keeps
    # pass-1 patches on the garbage-free row set once any compaction
    # happened (a plain re-lay-out would resurrect the dropped rows).
    linked = index.linked_blocks_mask()
    garbage = np.flatnonzero(~linked[: index.num_blocks]).astype(np.int64)
    already = np.asarray(stats.get("p1_compacted_ids", []), np.int64)
    fresh_garbage = np.setdiff1d(garbage, already)
    # same threshold semantics as the single-host ``patch_plan_dbindex``:
    # fraction >= threshold compacts (0.0 = compact whenever garbage exists);
    # zero-block indices never compact (nothing to drop, and the fraction
    # is defined as 0.0 for them)
    over = (index.num_blocks > 0
            and index.garbage_block_fraction() >= compact_garbage)
    compacting = over and fresh_garbage.size > 0
    filter_garbage = compacting or already.size > 0
    if filter_garbage:
        keep = linked[member_block]
        p1_seg_src = member_block[keep]
        p1_gather_src = index.block_members[keep]
    else:
        p1_seg_src, p1_gather_src = member_block, index.block_members
    dirty = (
        np.concatenate([fresh_garbage, new_blocks]) if compacting
        else new_blocks
    )
    p1_groups = np.unique(dirty // ts)
    if filter_garbage and p1_groups.size:
        shipped = garbage[np.isin(garbage // ts, p1_groups)]
        stats["p1_compacted_ids"] = np.union1d(already, shipped).tolist()
    if compacting:
        stats["p1_compactions"] = stats.get("p1_compactions", 0) + 1
    stats["last_compaction"] = bool(compacting)

    per_shard = np.zeros(splan.ndev, np.int64)
    patches: List[Tuple] = []  # (pass_name, flat positions, seg, gather)
    groups_patched = 0
    for pass_id, groups, seg_src, gather_src in (
        (1, p1_groups, p1_seg_src, p1_gather_src),
        (2, np.unique(owners // ts), link_owner, index.link_block),
    ):
        if groups.size == 0:
            continue
        tiles = splan.group_tiles1 if pass_id == 1 else splan.group_tiles2
        shard_of = splan.group_shard1 if pass_id == 1 else splan.group_shard2
        offset = splan.group_off1 if pass_id == 1 else splan.group_off2
        rows_cap = splan.rows1 if pass_id == 1 else splan.rows2
        tm = splan.tm
        pos_chunks, seg_chunks, gather_chunks = [], [], []
        for g in groups:
            span = int(tiles[g]) * tm
            rows = _group_rows(seg_src, gather_src, int(g), ts, span)
            if rows is None:  # group outgrew its tile capacity
                return rebuild()
            lo = int(shard_of[g]) * rows_cap + int(offset[g])
            pos_chunks.append(np.arange(lo, lo + span, dtype=np.int64))
            seg_chunks.append(rows[0])
            gather_chunks.append(rows[1])
            per_shard[int(shard_of[g])] += span * 8  # seg + gather, i32 each
            groups_patched += 1
        patches.append((f"p{pass_id}", np.concatenate(pos_chunks),
                        np.concatenate(seg_chunks),
                        np.concatenate(gather_chunks)))

    sizes = np.empty(0, np.float32)
    if new_blocks.size:
        sizes = np.diff(index.block_offsets)[new_blocks].astype(np.float32)
        per_shard += (new_blocks.size * 4) // splan.ndev  # replicated bcast
    e1_rows = e2_rows = None
    if splan.has_ell:  # widths already validated before the tile scatters
        from repro.core.engine_jax import (
            _ell_rows_for_new_blocks,
            _ell_rows_for_owners,
        )

        if new_blocks.size:
            e1_rows = _ell_rows_for_new_blocks(index, splan.num_blocks, r1)
            rs1 = splan.e1.shape[0] // splan.ndev
            np.add.at(per_shard, (new_blocks // rs1).astype(np.int64),
                      r1 * 4)
        if owners.size:
            e2_rows = _ell_rows_for_owners(index, owners, r2)
            rs2 = splan.e2.shape[0] // splan.ndev
            np.add.at(per_shard, (owners // rs2).astype(np.int64), r2 * 4)

    msg = {
        "kind": "patch",
        "num_blocks": int(index.num_blocks),
        "patches": patches,
        "block_ids": new_blocks,
        "block_sizes": sizes,
        "e1_ids": new_blocks if e1_rows is not None
        else np.empty(0, np.int64),
        "e1_rows": e1_rows,
        "e2_ids": owners if e2_rows is not None
        else np.empty(0, np.int64),
        "e2_rows": e2_rows,
    }

    patch_bytes = int(per_shard.sum())
    _obs.get_registry().counter(
        "repro_patch_bytes_total",
        "bytes of tile-group patches shipped to plan shards").inc(patch_bytes)
    stats.update(
        last_patch_bytes=patch_bytes,
        last_patch_groups=groups_patched,
        last_patch_per_shard=per_shard.tolist(),
        patched_bytes_total=stats.get("patched_bytes_total", 0) + patch_bytes,
    )
    out = _apply_patch(splan, msg, stats)
    if wire is not None:
        from repro.obs.audit import plan_crc

        # post-apply content digest of the plan this message produces —
        # a follower replaying it self-checks (apply_wire_message)
        msg["plan_crc"] = plan_crc(out)
        wire.append(msg)
    return out


def _rebuild(index: DBIndex, splan: ShardedDBPlan,
             stats: Dict) -> ShardedDBPlan:
    """A fresh sharded plan of ``index`` in place of ``splan``, keeping its
    shapes where the rebuild allows — the leader's rebuild and a
    follower's resync lay the plan out exactly alike."""
    from repro.core.engine_jax import plan_from_dbindex

    base = plan_from_dbindex(index, splan.tm, splan.ts,
                             headroom=splan.headroom, like=splan)
    return build_sharded_plan(base, splan.mesh, splan.axes,
                              headroom=splan.headroom, stats=stats, like=splan)


def _apply_patch(splan: ShardedDBPlan, msg: Dict,
                 stats: Dict) -> ShardedDBPlan:
    """Scatter one ``"patch"`` message into the device-resident shards —
    the leader's :func:`patch_sharded_plan` and a follower's
    :func:`apply_wire_message` run exactly these device updates."""
    from repro.kernels.segment_reduce.ops import set_rows

    arrays = {"p1_seg": splan.p1_seg, "p1_gather": splan.p1_gather,
              "p2_seg": splan.p2_seg, "p2_gather": splan.p2_gather}
    for name, pos_np, seg_np, gather_np in msg["patches"]:
        arrays[f"{name}_seg"] = set_rows(arrays[f"{name}_seg"], pos_np,
                                         seg_np)
        arrays[f"{name}_gather"] = set_rows(arrays[f"{name}_gather"],
                                            pos_np, gather_np)
    block_sizes = splan.block_sizes
    if msg["block_ids"].size:
        block_sizes = set_rows(block_sizes, msg["block_ids"],
                               msg["block_sizes"])
    e1, e2 = splan.e1, splan.e2
    if msg["e1_rows"] is not None and msg["e1_ids"].size:
        e1 = set_rows(e1, msg["e1_ids"], msg["e1_rows"])
    if msg["e2_rows"] is not None and msg["e2_ids"].size:
        e2 = set_rows(e2, msg["e2_ids"], msg["e2_rows"])
    return dataclasses.replace(
        splan, num_blocks=int(msg["num_blocks"]), block_sizes=block_sizes,
        e1=e1, e2=e2, stats=stats, **arrays,
    )


# ---------------------------------------------------------------------- #
#  Replication messages (the patch stream on the wire)
# ---------------------------------------------------------------------- #
class WireDivergenceError(RuntimeError):
    """A replayed wire message produced a plan whose content digest does
    not match the leader's ``plan_crc`` stamp (the follower held different
    pre-patch state, or the message was corrupted in transit)."""


def apply_wire_message(splan: ShardedDBPlan, msg: Dict,
                       verify: bool = True) -> ShardedDBPlan:
    """Replay one :func:`patch_sharded_plan` wire message on a follower's
    plan.  The follower must hold the same plan state the leader held
    before the message was produced (apply the stream in order, no gaps);
    positions and row ids in a ``"patch"`` message are absolute, so the
    replay is exactly the leader's device scatters.  A ``"resync"``
    message (leader rebuilt) carries the full index and rebuilds the
    follower the same deterministic way.

    When the message carries the leader's post-apply ``plan_crc`` stamp
    and ``verify`` is on, the follower recomputes its own plan digest and
    raises :class:`WireDivergenceError` on mismatch — silent follower
    drift is converted into an immediate, attributed failure."""
    if msg["kind"] == "resync":
        # the leader rebuilt in place of the plan this follower holds
        stats = dict(splan.stats)
        stats["version"] = stats.get("version", 0) + 1
        stats["rebuilds"] = stats.get("rebuilds", 0) + 1
        out = _rebuild(msg["index"], splan, stats)
        return _verify_wire_crc(out, msg, verify)

    assert msg["kind"] == "patch", msg["kind"]
    stats = dict(splan.stats)
    stats["version"] = stats.get("version", 0) + 1
    out = _apply_patch(splan, msg, stats)
    return _verify_wire_crc(out, msg, verify)


def _verify_wire_crc(out: ShardedDBPlan, msg: Dict,
                     verify: bool) -> ShardedDBPlan:
    expect = msg.get("plan_crc")
    if verify and expect is not None:
        from repro.obs.audit import plan_crc

        got = plan_crc(out)
        if got != int(expect):
            _obs.get_registry().counter(
                "repro_wire_divergence_total",
                "wire-replayed plans failing the leader's plan_crc").inc()
            raise WireDivergenceError(
                f"{msg['kind']} replay digest mismatch: "
                f"leader={int(expect):#010x} follower={got:#010x}")
    return out


def encode_wire_message(msg: Dict) -> bytes:
    """Serialize one replication message to bytes (``np.savez``-framed;
    no pickling — index stats ride as JSON)."""
    import io
    import json

    arrays: Dict[str, np.ndarray] = {}
    meta: Dict = {"kind": msg["kind"]}
    if msg.get("plan_crc") is not None:
        meta["plan_crc"] = int(msg["plan_crc"])
    if msg["kind"] == "resync":
        idx = msg["index"]
        meta["n"] = int(idx.n)
        meta["num_blocks"] = int(idx.num_blocks)
        meta["stats"] = {k: v for k, v in idx.stats.items()
                         if isinstance(v, (int, float, bool, str))}
        arrays["block_members"] = np.asarray(idx.block_members)
        arrays["block_offsets"] = np.asarray(idx.block_offsets)
        arrays["link_block"] = np.asarray(idx.link_block)
        arrays["link_owner_offsets"] = np.asarray(idx.link_owner_offsets)
    else:
        meta["num_blocks"] = int(msg["num_blocks"])
        meta["patch_names"] = [name for name, *_ in msg["patches"]]
        for i, (name, pos, seg, gather) in enumerate(msg["patches"]):
            arrays[f"patch{i}_pos"] = pos
            arrays[f"patch{i}_seg"] = seg
            arrays[f"patch{i}_gather"] = gather
        arrays["block_ids"] = msg["block_ids"]
        arrays["block_sizes"] = msg["block_sizes"]
        for key in ("e1", "e2"):
            rows = msg[f"{key}_rows"]
            meta[f"has_{key}"] = rows is not None
            arrays[f"{key}_ids"] = np.asarray(msg[f"{key}_ids"])
            if rows is not None:
                arrays[f"{key}_rows"] = rows
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    payload = buf.getvalue()
    header = json.dumps(meta).encode()
    return (len(header).to_bytes(4, "little") + header + payload)


def decode_wire_message(data: bytes) -> Dict:
    """Inverse of :func:`encode_wire_message`."""
    import io
    import json

    hlen = int.from_bytes(data[:4], "little")
    meta = json.loads(data[4: 4 + hlen].decode())
    arrays = dict(np.load(io.BytesIO(data[4 + hlen:]), allow_pickle=False))
    if meta["kind"] == "resync":
        index = DBIndex(
            n=int(meta["n"]),
            num_blocks=int(meta["num_blocks"]),
            block_members=arrays["block_members"],
            block_offsets=arrays["block_offsets"],
            link_block=arrays["link_block"],
            link_owner_offsets=arrays["link_owner_offsets"],
            stats=dict(meta["stats"]),
        )
        out = {"kind": "resync", "index": index}
        if "plan_crc" in meta:
            out["plan_crc"] = int(meta["plan_crc"])
        return out
    msg: Dict = {
        "kind": "patch",
        "num_blocks": int(meta["num_blocks"]),
        "patches": [
            (name, arrays[f"patch{i}_pos"], arrays[f"patch{i}_seg"],
             arrays[f"patch{i}_gather"])
            for i, name in enumerate(meta["patch_names"])
        ],
        "block_ids": arrays["block_ids"],
        "block_sizes": arrays["block_sizes"],
    }
    for key in ("e1", "e2"):
        msg[f"{key}_ids"] = arrays[f"{key}_ids"]
        msg[f"{key}_rows"] = arrays[f"{key}_rows"] if meta[f"has_{key}"] else None
    if "plan_crc" in meta:
        msg["plan_crc"] = int(meta["plan_crc"])
    return msg


# ---------------------------------------------------------------------- #
#  Sharded streaming state (graph + index + plan shards under updates)
# ---------------------------------------------------------------------- #
class ShardedStreamState:
    """Per-window streaming state with device-resident plan shards.

    Mirrors :class:`repro.core.streaming.StreamingEngine` (``apply`` /
    ``index`` / ``plan`` / ``staleness``) so :class:`repro.core.api.Session`
    machinery drives both interchangeably, but the plan is a
    :class:`ShardedDBPlan` and update propagation is distributed: the
    affected-owner BFS is sharded over the data axis (one seed slice per
    shard) and only the dirty tile groups are shipped to the shard owning
    them.
    """

    def __init__(
        self,
        g: Graph,
        window,
        mesh,
        axis="data",
        *,
        method: str = "emc",
        policy: Optional[StalenessPolicy] = None,
        tm: int = 512,
        ts: int = 512,
        plan_headroom: float = 0.5,
        # below StalenessPolicy.max_garbage_ratio (0.5) on purpose: the
        # in-place sharded compaction is shape-stable (no retrace), so it
        # should fire well before a policy rebuild is due
        compact_garbage: float = 0.25,
        use_device_bfs: Optional[bool] = None,
        capture_wire: bool = False,
        obs=None,
        tracer=None,
    ):
        from repro.core.windows import TopologicalWindow

        if isinstance(window, TopologicalWindow) and method == "emc":
            method = "mc"  # EMC is k-hop only (paper §4.2.2)
        #: replication stream: one message per applied batch when enabled
        #: (``patch_sharded_plan``'s wire format — see ``apply_wire_message``)
        self.wire_log: Optional[list] = [] if capture_wire else None
        self.graph = g
        self.window = window
        self.mesh, self.axes = mesh, _axes_tuple(axis)
        self.method = method
        self.policy = policy or StalenessPolicy()
        self.tm, self.ts = tm, ts
        self.plan_headroom = plan_headroom
        self.compact_garbage = compact_garbage
        self.use_device_bfs = use_device_bfs
        self.index_kind = "dbindex"
        self.batches_applied = 0
        self.reorg_count = 0
        self.batches_since_reorg = 0
        self.obs = obs if obs is not None else _obs.get_registry()
        self.tracer = tracer if tracer is not None else _obs.get_tracer()
        # same families as StreamingEngine so single-host and sharded
        # maintenance land in one place, split by the kind/action labels
        self._m_maint = self.obs.counter(
            "repro_maintenance_total", "index maintenance operations",
            labels=("kind", "action"))
        self._m_t_index = self.obs.histogram(
            "repro_index_update_seconds", "incremental index update latency",
            labels=("kind",))
        self._m_t_plan = self.obs.histogram(
            "repro_plan_patch_seconds", "device plan patch latency",
            labels=("kind",))
        self._build(initial=True)

    def _build(self, initial: bool = False) -> None:
        import jax

        from repro.core import engine_jax as ej

        with self.tracer.span("index.build", cat="build",
                              kind=self.index_kind, sharded=True):
            self.index = build_dbindex(self.graph, self.window,
                                       method=self.method)
        self._base_links = int(self.index.stats.get("num_links", 0))
        self._base_blocks = int(self.index.num_blocks)
        prev = None if initial else self.plan
        with self.tracer.span("plan.upload", cat="build",
                              kind=self.index_kind, sharded=True):
            base = ej.plan_from_dbindex(self.index, self.tm, self.ts,
                                        headroom=self.plan_headroom, like=prev)
            self.plan = build_sharded_plan(
                base, self.mesh, self.axes, headroom=self.plan_headroom,
                stats=prev.stats if prev is not None else None, like=prev,
            )
            jax.block_until_ready(self.plan)
        if prev is not None:
            # a reorganize re-uploads the whole plan: the patch telemetry
            # must say so, not echo the previous batch's few-KB patch
            self.plan.stats.update(
                last_patch_bytes=self.plan.size_bytes(),
                last_patch_groups=-1,
                last_patch_per_shard=[],
                rebuilds=self.plan.stats.get("rebuilds", 0) + 1,
                version=self.plan.stats.get("version", 0) + 1,
            )
        self.batches_since_reorg = 0
        if not initial:
            self.reorg_count += 1
            if self.wire_log is not None:
                from repro.obs.audit import plan_crc

                self.wire_log.append({
                    "kind": "resync", "index": self.index,
                    "plan_crc": plan_crc(self.plan)})

    # ------------------------------------------------------------------ #
    def _refilter(self, owners: np.ndarray) -> bool:
        """Sharded analogue of :meth:`StreamingEngine._refilter`: phase-1
        merge the flipped owners' re-filtered windows, then ship only the
        changed tile groups to the shards that own them.  Returns True when
        the merge tripped the staleness policy and the state rebuilt."""
        from repro.core.updates import _merge_affected
        from repro.core.windows import expr_windows

        wins = expr_windows(self.graph, self.window, owners)
        self.index = _merge_affected(self.index, owners, wins)
        self.batches_applied += 1
        self.batches_since_reorg += 1
        if self.policy.should_reorganize(
            self.index, self._base_links, self._base_blocks,
            self.batches_since_reorg,
        ):
            self._build()
            return True
        self.plan = patch_sharded_plan(self.plan, self.index, owners,
                                       compact_garbage=self.compact_garbage,
                                       wire=self.wire_log)
        return False

    # ------------------------------------------------------------------ #
    def apply(self, batch: UpdateBatch, graph: Optional[Graph] = None) -> Dict:
        """Apply one batch; the affected-owner BFS runs one seed shard per
        mesh shard, and only changed tile groups ship to the plan shards."""
        from repro.core.streaming import _attr_only_report
        from repro.core.updates import apply_batch

        t0 = time.perf_counter()
        g2 = apply_batch(self.graph, batch) if graph is None else graph
        fast = _attr_only_report(self, batch, g2, t0)
        if fast is not None:
            refiltered = fast.get("refiltered", False)
            fast.update(
                affected_per_shard=[],
                compacted=bool(self.plan.stats.get("last_compaction", False))
                if refiltered else False,
                patch_bytes=int(self.plan.stats.get("last_patch_bytes", 0))
                if refiltered else 0,
                patch_bytes_per_shard=self.plan.stats.get(
                    "last_patch_per_shard", []) if refiltered else [],
                full_plan_bytes=int(self.plan.stats.get("full_bytes", 0)),
                plan_rebuilt=fast["reorganized"],
            )
            return fast
        with self.tracer.span("index.update", cat="update",
                              kind=self.index_kind, size=batch.size,
                              sharded=True):
            owners, per_shard_owners = sharded_affected_owners(
                g2, self.window, batch, self.plan.ndev,
                use_device=self.use_device_bfs,
            )
            idx2, changed = update_dbindex_batch(self.index, g2, self.window,
                                                 batch, owners=owners)
        self.graph, self.index = g2, idx2
        t_index = time.perf_counter() - t0
        self._m_t_index.labels(self.index_kind).observe(t_index)
        self.batches_applied += 1
        self.batches_since_reorg += 1

        reorganized = False
        if idx2.stats.get("last_full_rebuild"):
            self._base_links = int(idx2.stats.get("num_links", 0))
            self._base_blocks = int(idx2.num_blocks)
            self.batches_since_reorg = 0
        t1 = time.perf_counter()
        if self.policy.should_reorganize(
            idx2, self._base_links, self._base_blocks, self.batches_since_reorg
        ):
            with self.tracer.span("plan.patch", cat="update",
                                  kind=self.index_kind, action="reorganize",
                                  rows=int(np.size(changed))):
                self._build()
            reorganized = True
        else:
            with self.tracer.span("plan.patch", cat="update",
                                  kind=self.index_kind, action="patch",
                                  rows=int(np.size(changed))):
                self.plan = patch_sharded_plan(
                    self.plan, idx2, changed,
                    compact_garbage=self.compact_garbage,
                    wire=self.wire_log)
        t_plan = time.perf_counter() - t1
        self._m_t_plan.labels(self.index_kind).observe(t_plan)
        self._m_maint.labels(
            self.index_kind, "reorganize" if reorganized else "patch").inc()
        # the patcher itself may have rebuilt (updater full rebuild, capacity
        # or ELL-width overflow) — that is a full-plan re-upload too, and
        # consumers asserting patch < full must see it flagged
        plan_rebuilt = self.plan.stats.get("last_patch_groups") == -1
        return {
            "batch_size": batch.size,
            "affected": int(np.asarray(changed).size),
            # the exact owner set the serving-layer cache invalidates
            "affected_owners": np.asarray(changed, np.int32),
            "plan_version": int(self.plan.stats.get("version", 0)),
            "compacted": bool(self.plan.stats.get("last_compaction", False)),
            "affected_per_shard": [int(o.size) for o in per_shard_owners],
            "patch_bytes": int(self.plan.stats.get("last_patch_bytes", 0)),
            "patch_bytes_per_shard": self.plan.stats.get(
                "last_patch_per_shard", []),
            "full_plan_bytes": int(self.plan.stats.get("full_bytes", 0)),
            "t_index_s": t_index,
            "t_plan_s": t_plan,
            "reorganized": reorganized or plan_rebuilt,
            "plan_rebuilt": plan_rebuilt,
        }

    # ------------------------------------------------------------------ #
    def query_multi(self, aggs: Sequence[str], values=None) -> list:
        if values is None:
            values = self.graph.attrs["val"]
        outs = query_sharded_multi(self.plan, values, tuple(aggs))
        return [np.asarray(o) for o in outs]

    def query(self, agg: str = "sum", values=None) -> np.ndarray:
        return self.query_multi((agg,), values)[0]

    @property
    def staleness(self) -> Dict:
        from repro.core.streaming import garbage_block_fraction

        return {
            "link_ratio": int(self.index.stats.get("num_links", 0))
            / max(self._base_links, 1),
            "block_ratio": self.index.num_blocks / max(self._base_blocks, 1),
            "garbage_ratio": garbage_block_fraction(self.index),
        }


# ---------------------------------------------------------------------- #
#  ShardedSession — Session(mesh=...) across the mesh
# ---------------------------------------------------------------------- #
from repro.core.api import Session  # noqa: E402  (api never imports us eagerly)


class ShardedSession(Session):
    """A :class:`~repro.core.api.Session` whose device groups run across a
    mesh: query planning selects sharded capabilities, every distinct window
    gets per-shard device plans, and streamed ``UpdateBatch``es propagate as
    per-shard tile-group patches.  Construct directly or via
    ``Session(g, specs, mesh=mesh)`` — all Session kwargs (policy, headroom,
    method, pins, ``compact_garbage``, ...) keep their meaning; on
    delete-dominated streams the patcher re-packs pass-1 shards in place
    once the garbage-block fraction crosses ``compact_garbage`` (shapes
    stable — no retrace, no rebuild), so streams stay patch-only until a
    :class:`~repro.core.streaming.StalenessPolicy` rebuild is truly due.
    """

    _sharded = True

    def __init__(self, g: Graph, specs, *, mesh, axis="data", **kw):
        assert mesh is not None, "ShardedSession needs a mesh"
        self.axes = _axes_tuple(axis)
        super().__init__(g, specs, mesh=mesh, axis=axis, **kw)

    # ------------------------------------------------------------------ #
    def _make_state(self, window, kind: str, device: bool, sharded: bool):
        if not sharded:  # e.g. explicitly pinned host / iindex groups
            return super()._make_state(window, kind, device, sharded)
        cfg = self._state_cfg
        cg = cfg["compact_garbage"]
        return ShardedStreamState(
            self.graph, window, self.mesh, cfg["axis"],
            method=cfg["method"], policy=cfg["policy"],
            tm=cfg["tm"], ts=cfg["ts"],
            plan_headroom=cfg["plan_headroom"],
            compact_garbage=0.25 if cg is None else cg,
            use_device_bfs=cfg["use_device_bfs"],
            obs=self.obs, tracer=self.tracer,
        )

    def _group_artifacts(self, gi):
        """A (window, kind) state shared between a sharded group and a
        pinned non-sharded device group holds a :class:`ShardedDBPlan`,
        which single-host executors cannot consume — hand those groups the
        index only (their runner builds a host plan per call)."""
        arts = super()._group_artifacts(gi)
        cap = self.registry.capability(self.compiled.groups[gi].engine)
        if not cap.sharded:
            arts = tuple(
                (index, None if isinstance(plan, ShardedDBPlan) else plan)
                for index, plan in arts
            )
        return arts

    # ------------------------------------------------------------------ #
    def _exec_term_many(self, grp, window, index, plan, vb, g, aggs):
        """Serving traffic across the mesh: sharded plans ride the batched
        values axis of the shard-local fn — one launch for the whole
        [B, n] bucket instead of one executable replay per row."""
        if isinstance(plan, ShardedDBPlan):
            outs = query_sharded_many(plan, vb, tuple(aggs))
            return {a: np.asarray(o) for a, o in zip(aggs, outs)}
        return super()._exec_term_many(grp, window, index, plan, vb, g, aggs)
