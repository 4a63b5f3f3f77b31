"""Jit'd wrapper + host tile-plan builder for the segment-reduce kernel.

``build_tile_plan`` is run once at *index build time* (host, NumPy): it
renumbers nothing (ids are already dense) but groups rows by output tile and
pads so the Pallas kernel sees a tile-aligned layout.  The returned plan is
a pytree of device arrays with static shapes — exactly what pjit wants.

``segment_sum(plan, values)`` = gather + Pallas tiled segment sum (a
one-hot matmul on the MXU).  ``segment_sum_gathered`` and
``segment_minmax_gathered`` (Pallas tiled segment min/max, a masked VPU
reduce over the same plan) take rows already gathered, so the fused
queries share one gather between channels; ``use_pallas=False`` picks the
XLA segment ops over the same tile-aligned inputs.  ``segment_reduce(...)``
is the general entry point (min/max through the pure-jnp oracle).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.compat import default_interpret as _default_interpret
from repro.kernels.segment_reduce.segment_reduce import (
    DEFAULT_TM,
    DEFAULT_TS,
    segment_minmax_tiled,
    segment_sum_tiled,
)


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """Static-shape device plan for one sorted segment reduction."""

    gather_padded: jnp.ndarray  # int32 [Mpad] index into values rows (0 on pad)
    seg_tiles: jnp.ndarray  # int32 [nm, TM]; -1 on padding rows
    m2out: jnp.ndarray  # int32 [nm]
    first_visit: jnp.ndarray  # int32 [nm]
    num_segments: int
    num_out_tiles: int
    tm: int
    ts: int

    def tree_flatten(self):
        return (
            (self.gather_padded, self.seg_tiles, self.m2out, self.first_visit),
            (self.num_segments, self.num_out_tiles, self.tm, self.ts),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def array_nbytes(self) -> "dict":
        """Per-array device bytes held by this plan (exact ``.nbytes``)."""
        return {
            "gather_padded": int(self.gather_padded.nbytes),
            "seg_tiles": int(self.seg_tiles.nbytes),
            "m2out": int(self.m2out.nbytes),
            "first_visit": int(self.first_visit.nbytes),
        }

    def plan_nbytes(self) -> int:
        return sum(self.array_nbytes().values())


jax.tree_util.register_pytree_node(
    TilePlan, TilePlan.tree_flatten, TilePlan.tree_unflatten
)

# A rebuilt plan keeps each static size of the plan it replaces, so the
# jitted queries do not retrace, unless that size would pad the rebuild to
# more than this many times its own; it then takes its own size and the
# queries retrace once.
KEEP_SHAPE_MAX_PAD = 2


def keep_shape(prev: Optional[int], need: int, own: int) -> int:
    """One static size of a rebuilt plan: ``prev``, the replaced plan's,
    when the rebuild's content (``need``) fits in it and it is at most
    ``KEEP_SHAPE_MAX_PAD`` times the rebuild's own size ``own``; else
    ``own``.  The single-host and the sharded rebuilds size every kept
    dimension (block capacity, tiles, ELL widths, rows per shard) here."""
    if prev is not None and need <= prev <= KEEP_SHAPE_MAX_PAD * own:
        return int(prev)
    return int(own)


@jax.jit
def set_rows(arr, ids, rows):
    """``arr.at[ids].set(rows)`` for an incremental plan patch, keeping
    ``arr``'s sharding (a scatter into an explicitly sharded array must
    name its output's).  Traced under the ``plan.patch`` named scope, so
    the device trace files the patch's scatters under it: a scope reaches
    only operations traced inside a jit, never eager ones."""
    with jax.named_scope("plan.patch"):
        return arr.at[ids].set(rows, out_sharding=jax.typeof(arr).sharding)


def build_tile_plan(
    gather_idx: np.ndarray,
    segment_ids: np.ndarray,
    num_segments: int,
    tm: int = DEFAULT_TM,
    ts: int = DEFAULT_TS,
    headroom: float = 0.0,
    group_min_tiles: "Optional[np.ndarray]" = None,
    num_tiles: Optional[int] = None,
) -> TilePlan:
    """Host-side plan: rows (sorted by segment id) -> tile-aligned layout.

    ``headroom`` > 0 over-allocates every tile group by an even share of
    ``total_rows * headroom`` extra row capacity.  Streamed updates append
    rows into a few hot groups (e.g. secondary blocks land in the capacity
    tail); the spread keeps :func:`patch_tile_plan` shape-stable — hence
    recompile-free — until the cumulative growth exceeds the slack.
    ``group_min_tiles`` optionally floors individual groups' tile counts —
    the caller's way to concentrate slack where appends will land.
    ``num_tiles``, the input tile count of a plan this one replaces, is
    kept where :func:`keep_shape` keeps it (slack first gives way, then
    spare tiles are spread over the groups), so the jitted consumers do
    not retrace.
    """
    gather_idx = np.asarray(gather_idx, np.int32)
    segment_ids = np.asarray(segment_ids, np.int64)
    assert gather_idx.shape == segment_ids.shape
    if segment_ids.size:
        assert (np.diff(segment_ids) >= 0).all(), "segment_ids must be sorted"
    sizes = np.bincount(segment_ids, minlength=num_segments).astype(np.int64)
    n_out_tiles = max(1, -(-num_segments // ts))
    group_rows = np.add.reduceat(sizes, np.arange(0, num_segments, ts)) if num_segments else np.zeros(1, np.int64)
    if group_rows.size < n_out_tiles:
        group_rows = np.pad(group_rows, (0, n_out_tiles - group_rows.size))
    # >=1 input tile per output tile so every output block gets initialized
    needed = np.maximum(1, -(-group_rows // tm))
    tiles_per_group = needed
    if headroom > 0:
        extra = max(1, -(-int(group_rows.sum() * headroom) // (n_out_tiles * tm)))
        tiles_per_group = tiles_per_group + extra
    if group_min_tiles is not None:
        tiles_per_group = np.maximum(
            tiles_per_group, group_min_tiles[:n_out_tiles].astype(np.int64)
        )
    if num_tiles is not None:
        num_tiles = keep_shape(num_tiles, int(needed.sum()),
                               int(tiles_per_group.sum()))
        if tiles_per_group.sum() > num_tiles:
            tiles_per_group = needed  # the rows fit, the slack does not
        spare = num_tiles - int(tiles_per_group.sum())
        if spare > 0:  # spread evenly, as headroom is
            tiles_per_group = tiles_per_group + spare // n_out_tiles
            tiles_per_group[-1] += spare % n_out_tiles
    padded_rows = tiles_per_group * tm
    total_pad = int(padded_rows.sum())
    nm = int(tiles_per_group.sum())
    # scatter original rows into the padded layout
    src_group_start = np.zeros(n_out_tiles + 1, np.int64)
    np.cumsum(group_rows, out=src_group_start[1:])
    dst_group_start = np.zeros(n_out_tiles + 1, np.int64)
    np.cumsum(padded_rows, out=dst_group_start[1:])
    row_map = np.full(total_pad, -1, dtype=np.int64)
    if segment_ids.size:
        within = np.arange(segment_ids.size) - np.repeat(
            src_group_start[:-1], group_rows
        )
        dst = np.repeat(dst_group_start[:-1], group_rows) + within
        row_map[dst] = np.arange(segment_ids.size)
    seg_padded = np.full(total_pad, -1, dtype=np.int32)
    valid = row_map >= 0
    seg_padded[valid] = segment_ids[row_map[valid]]
    gather_padded = np.zeros(total_pad, dtype=np.int32)
    gather_padded[valid] = gather_idx[row_map[valid]]
    m2out = np.repeat(np.arange(n_out_tiles, dtype=np.int32), tiles_per_group)
    first_visit = np.empty(nm, dtype=np.int32)
    first_visit[0] = 1
    first_visit[1:] = (np.diff(m2out) != 0).astype(np.int32)
    return TilePlan(
        gather_padded=jnp.asarray(gather_padded),
        seg_tiles=jnp.asarray(seg_padded.reshape(nm, tm)),
        m2out=jnp.asarray(m2out),
        first_visit=jnp.asarray(first_visit),
        num_segments=int(num_segments),
        num_out_tiles=n_out_tiles,
        tm=tm,
        ts=ts,
    )


def patch_tile_plan(
    plan: TilePlan,
    gather_idx: np.ndarray,
    segment_ids: np.ndarray,
    num_segments: int,
    changed_segments: np.ndarray,
) -> TilePlan:
    """Incrementally rebuild a tile plan after a sparse segment change.

    ``gather_idx``/``segment_ids`` are the FULL new row arrays (sorted by
    segment id, same contract as :func:`build_tile_plan`); the caller
    guarantees that every segment whose row set changed is listed in
    ``changed_segments``.  Only output-tile groups containing a changed
    segment are re-laid-out; untouched groups reuse their existing padded
    rows verbatim.  A changed group keeps its old tile capacity when the
    new rows still fit (extra tiles are all-padding rows the kernel masks),
    so steady-state streams produce plans with *identical static shapes* —
    no XLA recompilation of the jitted query.  ``num_segments`` may grow
    (e.g. appended secondary blocks); new groups are appended at the end.
    """
    gather_idx = np.asarray(gather_idx, np.int32)
    segment_ids = np.asarray(segment_ids, np.int64)
    assert gather_idx.shape == segment_ids.shape
    if segment_ids.size:
        assert (np.diff(segment_ids) >= 0).all(), "segment_ids must be sorted"
    tm, ts = plan.tm, plan.ts
    n_out_old = plan.num_out_tiles
    n_out_new = max(1, -(-num_segments // ts))
    if n_out_new < n_out_old:  # shrinking segment space: no reuse story
        return build_tile_plan(gather_idx, segment_ids, num_segments, tm, ts)

    old_m2out = np.asarray(plan.m2out)
    old_tiles = np.bincount(old_m2out, minlength=n_out_old).astype(np.int64)
    old_starts = np.zeros(n_out_old + 1, np.int64)
    np.cumsum(old_tiles * tm, out=old_starts[1:])

    changed_mask = np.zeros(n_out_new, dtype=bool)
    cs = np.asarray(changed_segments, np.int64)
    changed_mask[np.unique(cs[cs < num_segments]) // ts] = True
    changed_mask[n_out_old:] = True  # appended groups are always new

    # per-group row ranges in the new arrays
    bounds = np.searchsorted(
        segment_ids, np.arange(n_out_new + 1, dtype=np.int64) * ts
    )
    rows_per_group = np.diff(bounds)
    tiles_needed = np.maximum(1, -(-rows_per_group // tm))
    old_tiles_ext = np.zeros(n_out_new, np.int64)
    old_tiles_ext[:n_out_old] = old_tiles
    tiles_new = np.where(
        changed_mask, np.maximum(tiles_needed, old_tiles_ext), old_tiles_ext
    )
    new_starts = np.zeros(n_out_new + 1, np.int64)
    np.cumsum(tiles_new * tm, out=new_starts[1:])
    total_pad = int(new_starts[-1])
    nm = int(tiles_new.sum())

    if n_out_new == n_out_old and np.array_equal(tiles_new, old_tiles):
        # Shape-stable steady state: scatter only the changed tile groups
        # into the live device arrays (`jax.Array.at[...].set`) instead of
        # round-tripping the whole plan through host memory and re-uploading
        # it.  Everything static (m2out, first_visit, shapes) is reused, so
        # jitted consumers never retrace.
        pos_chunks, seg_chunks, gather_chunks = [], [], []
        for g in np.flatnonzero(changed_mask):
            lo, span = int(new_starts[g]), int(tiles_new[g]) * tm
            r0, r1 = int(bounds[g]), int(bounds[g + 1])
            seg_rows = np.full(span, -1, dtype=np.int32)
            gather_rows = np.zeros(span, dtype=np.int32)
            seg_rows[: r1 - r0] = segment_ids[r0:r1]
            gather_rows[: r1 - r0] = gather_idx[r0:r1]
            pos_chunks.append(np.arange(lo, lo + span, dtype=np.int64))
            seg_chunks.append(seg_rows)
            gather_chunks.append(gather_rows)
        seg_flat = plan.seg_tiles.reshape(-1)
        gather_flat = plan.gather_padded
        if pos_chunks:
            pos = jnp.asarray(np.concatenate(pos_chunks))
            seg_flat = set_rows(seg_flat, pos, np.concatenate(seg_chunks))
            gather_flat = set_rows(gather_flat, pos,
                                   np.concatenate(gather_chunks))
        return TilePlan(
            gather_padded=gather_flat,
            seg_tiles=seg_flat.reshape(nm, tm),
            m2out=plan.m2out,
            first_visit=plan.first_visit,
            num_segments=int(num_segments),
            num_out_tiles=n_out_new,
            tm=tm,
            ts=ts,
        )

    old_seg = np.asarray(plan.seg_tiles).reshape(-1)
    old_gather = np.asarray(plan.gather_padded)
    seg_padded = np.full(total_pad, -1, dtype=np.int32)
    gather_padded = np.zeros(total_pad, dtype=np.int32)
    for g in range(n_out_new):
        lo = int(new_starts[g])
        if changed_mask[g]:
            r0, r1 = int(bounds[g]), int(bounds[g + 1])
            seg_padded[lo : lo + (r1 - r0)] = segment_ids[r0:r1]
            gather_padded[lo : lo + (r1 - r0)] = gather_idx[r0:r1]
        else:
            o0 = int(old_starts[g])
            span = int(old_tiles[g]) * tm
            seg_padded[lo : lo + span] = old_seg[o0 : o0 + span]
            gather_padded[lo : lo + span] = old_gather[o0 : o0 + span]
    m2out = np.repeat(np.arange(n_out_new, dtype=np.int32), tiles_new)
    first_visit = np.empty(nm, dtype=np.int32)
    first_visit[0] = 1
    first_visit[1:] = (np.diff(m2out) != 0).astype(np.int32)
    return TilePlan(
        gather_padded=jnp.asarray(gather_padded),
        seg_tiles=jnp.asarray(seg_padded.reshape(nm, tm)),
        m2out=jnp.asarray(m2out),
        first_visit=jnp.asarray(first_visit),
        num_segments=int(num_segments),
        num_out_tiles=n_out_new,
        tm=tm,
        ts=ts,
    )


def segment_sum_gathered(
    plan: TilePlan,
    gathered: jnp.ndarray,
    interpret: Optional[bool] = None,
    use_pallas: bool = True,
):
    """Tiled segment sum over pre-gathered rows ([Mpad] or [Mpad, D]).

    Traceable (no jit of its own): fused multi-channel queries call this
    after a single shared ``jnp.take`` so k aggregates pay for one gather.
    """
    interpret = _default_interpret() if interpret is None else interpret
    squeeze = gathered.ndim == 1
    v = gathered[:, None] if squeeze else gathered
    if use_pallas:
        # the kernel takes channels-major rows ([D, Mpad]: rows on lanes)
        out = segment_sum_tiled(
            v.astype(jnp.float32).T,
            plan.seg_tiles,
            plan.m2out,
            plan.first_visit,
            num_out_tiles=plan.num_out_tiles,
            tm=plan.tm,
            ts=plan.ts,
            interpret=interpret,
        ).T
    else:  # XLA fallback (same tile-aligned inputs)
        sid = plan.seg_tiles.reshape(-1)
        ok = sid >= 0
        out = jax.ops.segment_sum(
            jnp.where(ok[:, None], v, 0).astype(jnp.float32),
            jnp.where(ok, sid, plan.num_out_tiles * plan.ts),
            num_segments=plan.num_out_tiles * plan.ts + 1,
        )[:-1]
    out = out[: plan.num_segments]
    return out[:, 0] if squeeze else out


@functools.partial(jax.jit, static_argnames=("interpret", "use_pallas"))
def segment_sum(
    plan: TilePlan,
    values: jnp.ndarray,
    interpret: Optional[bool] = None,
    use_pallas: bool = True,
):
    """Fused gather + tiled segment sum.  values: [N] or [N, D] -> [S(, D)]."""
    gathered = jnp.take(values, plan.gather_padded, axis=0)
    return segment_sum_gathered(plan, gathered, interpret, use_pallas)


def segment_minmax_gathered(
    plan: TilePlan,
    gathered: jnp.ndarray,
    op: str,
    interpret: Optional[bool] = None,
    use_pallas: bool = True,
):
    """Tiled segment min or max over pre-gathered rows ([Mpad] or
    [Mpad, D]) -> [S(, D)] f32; a segment with no rows holds the identity
    (+inf for min, -inf for max), as ``jax.ops.segment_min``/``max`` give.

    Traceable, like :func:`segment_sum_gathered`.  Both paths are exact:
    min and max do not depend on the order of the rows."""
    interpret = _default_interpret() if interpret is None else interpret
    squeeze = gathered.ndim == 1
    v = (gathered[:, None] if squeeze else gathered).astype(jnp.float32)
    if use_pallas:
        out = segment_minmax_tiled(
            v.T,
            plan.seg_tiles,
            plan.m2out,
            plan.first_visit,
            op=op,
            num_out_tiles=plan.num_out_tiles,
            tm=plan.tm,
            ts=plan.ts,
            interpret=interpret,
        ).T[: plan.num_segments]
    else:  # XLA fallback: masked scatter-min/max over the same layout
        sid = plan.seg_tiles.reshape(-1)
        ok = sid >= 0
        fill = jnp.inf if op == "min" else -jnp.inf
        seg_op = jax.ops.segment_min if op == "min" else jax.ops.segment_max
        out = seg_op(
            jnp.where(ok[:, None], v, fill),
            jnp.where(ok, sid, plan.num_segments),
            num_segments=plan.num_segments + 1,
        )[:-1]
    return out[:, 0] if squeeze else out


def segment_reduce(
    values, gather_idx, segment_ids, num_segments, op="add",
    plan: Optional[TilePlan] = None, interpret: Optional[bool] = None,
    use_pallas: bool = True,
):
    """General entry point.  SUM goes through the Pallas MXU path (plan
    required or built eagerly); min/max use the XLA segment lowering."""
    if op == "add":
        if plan is None:
            plan = build_tile_plan(
                np.asarray(gather_idx), np.asarray(segment_ids), num_segments
            )
        return segment_sum(plan, values, interpret=interpret, use_pallas=use_pallas)
    from repro.kernels.segment_reduce.ref import segment_reduce_ref

    return segment_reduce_ref(values, gather_idx, segment_ids, num_segments, op)
