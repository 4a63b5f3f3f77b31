"""Device (JAX/TPU) query data plane for DBIndex and I-Index.

The host-built indices become static *plans* of device arrays:

* DBIndex: two chained tile plans — members→blocks, then links→owners —
  each one fused gather + Pallas segment-sum (DESIGN.md §2), and for
  min/max the Pallas tiled segment min/max over the same tiles.
* I-Index: one tile plan for the window-difference partials plus the PID
  forest; the inheritance scan is either level-scheduled (``depth`` gathers)
  or pointer-doubled (``log2(depth)`` gathers, the §Perf variant).

``query_dbindex_multi`` / ``query_iindex_multi`` are the fused
multi-aggregate executors behind :mod:`repro.core.api`: one gather per
pass feeds every monoid channel (sum channels stack into a matrix reduce;
min/max reduce over dense ELL layouts where the plan has them, else over
the tile layout, then per-monoid inheritance on the I-Index), so k
aggregates over one window cost roughly one query instead of k.
:func:`minmax_route` names which of those min/max reduces a plan takes,
and :func:`plan_pages` how many kernel calls its largest pass takes.

``query_dbindex_sharded`` distributes the query under ``shard_map``:
pass 1 is sharded over *blocks*, the (small) block-partial vector ``T`` is
all-gathered over the data axis, and pass 2 is sharded over *owners* —
the collective footprint is ``|T|`` floats, independent of window sizes,
which is what makes the paper's sharing structure attractive on a pod.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dbindex import DBIndex
from repro.core.iindex import IIndex
from repro.kernels.segment_reduce.ops import (
    TilePlan,
    build_tile_plan,
    keep_shape,
    patch_tile_plan,
    segment_minmax_gathered,
    segment_sum,
    segment_sum_gathered,
    set_rows,
)


# ---------------------------------------------------------------------- #
#  DBIndex plan
# ---------------------------------------------------------------------- #
_ELL_SENTINEL = np.int32(np.iinfo(np.int32).max)  # jnp.take clips -> last row


@dataclasses.dataclass(frozen=True)
class DBIndexPlan:
    """Device plan.  ``block_capacity >= num_blocks`` pads the block-partial
    vector ``T`` so that streamed updates appending secondary blocks keep
    static shapes (capacity grows by powers of two → O(log) recompiles over
    a stream instead of one per batch).

    ``num_blocks`` is a pytree *child* (not aux data): it changes on every
    streamed batch, and jitted queries must not retrace for it — device code
    sizes everything by ``block_capacity`` instead.

    ``p1_ell`` / ``p2_ell`` are padded per-segment row layouts (ELL style)
    for the idempotent monoids: blocks and owner link lists have tiny
    bounded fan-in, so min/max evaluate as one dense gather + axis reduce
    instead of a segment reduce over the tile layout.  min/max are
    order-insensitive, so the formulation is bit-exact against any other
    evaluation order.  Pad slots hold ``_ELL_SENTINEL``; ``jnp.take`` clips
    it to the last row of the value vector, which the query extends with
    the monoid identity."""

    n: int
    num_blocks: int
    block_capacity: int
    pass1: TilePlan  # members -> block partials
    pass2: TilePlan  # block partials -> owner windows
    block_sizes: jnp.ndarray  # f32 [block_capacity] (for count/avg)
    link_counts: jnp.ndarray  # f32 [n]
    p1_ell: Optional[jnp.ndarray] = None  # i32 [block_capacity, R1] member ids
    p2_ell: Optional[jnp.ndarray] = None  # i32 [n, R2] block ids

    def tree_flatten(self):
        return (
            (self.num_blocks, self.pass1, self.pass2, self.block_sizes,
             self.link_counts, self.p1_ell, self.p2_ell),
            (self.n, self.block_capacity),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        nb, p1, p2, bs, lc, e1, e2 = children
        return cls(aux[0], nb, aux[1], p1, p2, bs, lc, e1, e2)

    @property
    def ell_widths(self) -> Optional[Tuple[int, int]]:
        """(R1, R2) of the ELL layouts, or None when the plan has none."""
        if self.p1_ell is None:
            return None
        return self.p1_ell.shape[1], self.p2_ell.shape[1]

    def array_nbytes(self) -> dict:
        """Exact per-array device bytes, keyed ``pass1.<name>`` /
        ``pass2.<name>`` / top-level array name.  The EXPLAIN footprint
        accounting (and ROADMAP direction 2's spill planning) reads this."""
        out = {}
        for prefix, tp in (("pass1", self.pass1), ("pass2", self.pass2)):
            for k, v in tp.array_nbytes().items():
                out[f"{prefix}.{k}"] = v
        out["block_sizes"] = int(self.block_sizes.nbytes)
        out["link_counts"] = int(self.link_counts.nbytes)
        if self.p1_ell is not None:
            out["p1_ell"] = int(self.p1_ell.nbytes)
        if self.p2_ell is not None:
            out["p2_ell"] = int(self.p2_ell.nbytes)
        return out

    def plan_nbytes(self) -> int:
        """Total device bytes held by this plan (sum of per-array sizes)."""
        return sum(self.array_nbytes().values())


jax.tree_util.register_pytree_node(
    DBIndexPlan, DBIndexPlan.tree_flatten, DBIndexPlan.tree_unflatten
)


def _block_sizes_padded(index: DBIndex, capacity: int) -> np.ndarray:
    sizes = np.zeros(capacity, np.float32)
    sizes[: index.num_blocks] = np.diff(index.block_offsets)
    return sizes


def _pow2(x: int) -> int:
    return 1 << (max(int(x), 1) - 1).bit_length()


def _ell_rows(offsets: np.ndarray, items: np.ndarray, num_rows: int,
              width: int) -> np.ndarray:
    """Padded per-segment item matrix [num_rows, width], sentinel-padded."""
    out = np.full((num_rows, width), _ELL_SENTINEL, np.int32)
    sizes = np.diff(offsets).astype(np.int64)
    row = np.repeat(np.arange(sizes.size, dtype=np.int64), sizes)
    pos = np.arange(items.size) - np.repeat(offsets[:-1], sizes)
    out[row, pos] = items
    return out


def _ell_from_index(index: DBIndex, cap: int, prev_widths=None):
    """(p1_ell, p2_ell) for the min/max fast path, or (None, None) when a
    degenerate fan-in distribution would blow the padded layout up (min/max
    then reduce over the tile layout — exact either way).
    ``prev_widths``, the (R1, R2) of a plan being replaced, are kept where
    :func:`keep_shape` keeps them and the padding rule still holds."""
    max_block = int(np.diff(index.block_offsets).max()) if index.num_blocks else 1
    max_links = int(np.diff(index.link_owner_offsets).max()) if index.n else 1
    own = _pow2(max_block), _pow2(max_links)

    def fits(r1, r2):
        # the dense reduce was sized against the XLA scatter (~9 ns per
        # padded row on a v5e), which it beats until padding inflates the
        # row count by roughly an order of magnitude; skewed fan-in
        # distributions (one huge block, one hub owner linking thousands
        # of blocks) reduce over the tile layout instead
        return (cap * r1 <= max(16 * index.block_members.size, 1 << 16)
                and index.n * r2 <= max(16 * index.link_block.size, 1 << 16))

    widths = own
    if prev_widths is not None:
        widths = tuple(keep_shape(p, r, r) for p, r in zip(prev_widths, own))
        if not fits(*widths):
            widths = own  # the kept widths pad too far: one retrace
    if not fits(*widths):
        return None, None
    r1, r2 = widths
    p1 = _ell_rows(index.block_offsets, index.block_members, cap, r1)
    p2 = _ell_rows(index.link_owner_offsets, index.link_block, index.n, r2)
    return jnp.asarray(p1), jnp.asarray(p2)


def plan_from_dbindex(
    index: DBIndex, tm: int = 512, ts: int = 512,
    headroom: float = 0.0, like=None,
) -> DBIndexPlan:
    """Device plan of ``index``.

    ``like`` is the plan this one replaces (a reorganize, or a rebuild the
    patcher fell back to), single-host or sharded: its block capacity, tile
    counts and ELL widths are kept where :func:`keep_shape` keeps them, so
    a rebuild on a stream does not retrace the jitted queries.
    """
    cap = max(index.num_blocks, 1)
    floors = None
    if headroom > 0:
        # pre-pad the block id space to the next power of two past the
        # headroom so streamed secondary-block appends don't change the
        # capacity (and hence the static shapes) on the first few batches
        cap = _pow2(int(cap * (1 + headroom)))
    if like is not None:
        cap = keep_shape(like.block_capacity, index.num_blocks, cap)
    if headroom > 0:
        # appended secondary blocks take consecutive ids just past
        # num_blocks, so the growth lands in a handful of specific tile
        # groups — floor those at the expected rows of a full group of
        # average-sized blocks instead of spreading slack uniformly
        n_groups = max(1, -(-cap // ts))
        avg_block = index.block_members.size / max(index.num_blocks, 1)
        boost = -(-int(ts * avg_block * (1 + headroom)) // tm)
        floors = np.ones(n_groups, np.int64)
        g0 = index.num_blocks // ts
        floors[g0: g0 + 4] = max(boost, 1)
    tiles = (None, None)
    if isinstance(like, DBIndexPlan):
        tiles = (like.pass1.seg_tiles.shape[0], like.pass2.seg_tiles.shape[0])
    member_block = np.asarray(index.member_block_ids, np.int64)
    pass1 = build_tile_plan(index.block_members, member_block, cap, tm, ts,
                            headroom=headroom, group_min_tiles=floors,
                            num_tiles=tiles[0])
    owner_ids = np.asarray(index.link_owner_ids, np.int64)
    pass2 = build_tile_plan(index.link_block, owner_ids, index.n, tm, ts,
                            headroom=headroom, num_tiles=tiles[1])
    links = np.diff(index.link_owner_offsets).astype(np.float32)
    p1_ell, p2_ell = _ell_from_index(
        index, cap, like.ell_widths if like is not None else None)
    return DBIndexPlan(
        n=index.n,
        num_blocks=index.num_blocks,
        block_capacity=cap,
        pass1=pass1,
        pass2=pass2,
        block_sizes=jnp.asarray(_block_sizes_padded(index, cap)),
        link_counts=jnp.asarray(links),
        p1_ell=p1_ell,
        p2_ell=p2_ell,
    )


def patch_plan_dbindex(
    plan: DBIndexPlan, index: DBIndex, changed_owners: np.ndarray,
    compact_garbage: float = 0.5, headroom: float = 0.0,
) -> DBIndexPlan:
    """Incremental plan maintenance after ``update_dbindex_batch``.

    The merged index keeps the primary block prefix intact and appends
    secondary blocks, so pass 1 only re-lays-out the tile groups holding
    appended block ids; pass 2 re-lays-out the groups containing
    ``changed_owners`` (the batch's affected owner set).  Everything else
    is spliced from the live plan.

    Delete-heavy streams accumulate *garbage blocks* — blocks no owner
    links to any more, whose member rows still occupy pass-1 tiles.  When
    the garbage fraction crosses ``compact_garbage``, pass 1 is re-laid-out
    without the garbage blocks' member rows (block ids are untouched, so
    pass 2 and the jitted query are unaffected beyond the shape change).

    When the updater fell back to a full rebuild (``last_full_rebuild``
    stat), the appended-prefix invariant does not hold and splicing would
    silently reuse stale tiles — build a fresh plan instead.
    """
    if index.stats.get("last_full_rebuild"):
        return plan_from_dbindex(index, plan.pass1.tm, plan.pass1.ts,
                                 headroom=headroom, like=plan)
    cap = plan.block_capacity
    if index.num_blocks > cap:
        cap = _pow2(index.num_blocks)
    member_block = np.asarray(index.member_block_ids, np.int64)
    # require actual garbage, not just fraction >= threshold: an empty or
    # garbage-free index with compact_garbage == 0.0 would otherwise take
    # the full pass-1 re-layout every batch (a spurious compaction that
    # drops nothing — the delete-everything / zero-block degenerate cases)
    garbage = index.garbage_block_fraction()
    if garbage > 0.0 and garbage >= compact_garbage:
        keep = index.linked_blocks_mask()[member_block]
        pass1 = build_tile_plan(
            index.block_members[keep], member_block[keep], cap,
            plan.pass1.tm, plan.pass1.ts, headroom=headroom,
        )
    else:
        new_blocks = np.arange(plan.num_blocks, index.num_blocks, dtype=np.int64)
        pass1 = patch_tile_plan(
            plan.pass1,
            index.block_members,
            member_block,
            cap,
            new_blocks,
        )
    pass2 = patch_tile_plan(
        plan.pass2,
        index.link_block,
        np.asarray(index.link_owner_ids, np.int64),
        index.n,
        np.asarray(changed_owners, np.int64),
    )
    links = np.diff(index.link_owner_offsets).astype(np.float32)
    p1_ell, p2_ell = _patch_ell(plan, index, cap, changed_owners)
    return DBIndexPlan(
        n=index.n,
        num_blocks=index.num_blocks,
        block_capacity=cap,
        pass1=pass1,
        pass2=pass2,
        block_sizes=jnp.asarray(_block_sizes_padded(index, cap)),
        link_counts=jnp.asarray(links),
        p1_ell=p1_ell,
        p2_ell=p2_ell,
    )


def _ell_rows_for_new_blocks(index: DBIndex, old_num_blocks: int,
                             width: int) -> np.ndarray:
    """Padded ELL rows for the blocks appended past ``old_num_blocks``
    (relies on the appended-prefix invariant of phase-1 merges).  Shared by
    the single-host and sharded ELL patchers."""
    off = index.block_offsets[old_num_blocks:]
    return _ell_rows(off - off[0], index.block_members[off[0]:],
                     off.size - 1, width)


def _ell_rows_for_owners(index: DBIndex, owners: np.ndarray,
                         width: int) -> np.ndarray:
    """Padded ELL rows of the given owners' link lists (vectorized
    multi-slice gather).  Shared by the single-host and sharded patchers."""
    counts = np.diff(index.link_owner_offsets)[owners]
    starts = index.link_owner_offsets[owners]
    off = np.zeros(owners.size + 1, np.int64)
    np.cumsum(counts, out=off[1:])
    items = index.link_block[
        np.repeat(starts, counts)
        + (np.arange(off[-1]) - np.repeat(off[:-1], counts))
    ]
    return _ell_rows(off, items, owners.size, width)


def _patch_ell(plan: DBIndexPlan, index: DBIndex, cap: int,
               changed_owners: np.ndarray):
    """Incremental maintenance of the min/max ELL layouts: scatter-set only
    the appended blocks' rows and the changed owners' rows; rebuild (a
    recompile-sized event, like capacity growth) only when a row no longer
    fits its padded width."""
    if plan.p1_ell is None:
        return None, None
    block_sizes = np.diff(index.block_offsets)
    new_sizes = block_sizes[plan.num_blocks:]
    link_sizes = np.diff(index.link_owner_offsets)
    owners = np.asarray(changed_owners, np.int64)
    r1, r2 = plan.p1_ell.shape[1], plan.p2_ell.shape[1]
    if (cap != plan.block_capacity
            or (new_sizes.size and int(new_sizes.max()) > r1)
            or (owners.size and int(link_sizes[owners].max()) > r2)):
        return _ell_from_index(index, cap)
    p1_ell = plan.p1_ell
    if new_sizes.size:
        rows = _ell_rows_for_new_blocks(index, plan.num_blocks, r1)
        ids = np.arange(plan.num_blocks, index.num_blocks)
        p1_ell = set_rows(p1_ell, ids, rows)
    p2_ell = plan.p2_ell
    if owners.size:
        rows = _ell_rows_for_owners(index, owners, r2)
        p2_ell = set_rows(p2_ell, owners, rows)
    return p1_ell, p2_ell


@functools.partial(jax.jit, static_argnames=("agg", "use_pallas", "interpret"))
def query_dbindex(plan: DBIndexPlan, values, agg: str = "sum",
                  use_pallas: bool = True, interpret: Optional[bool] = None):
    """values: [n] (or [n, D]) vertex attribute -> [n(, D)] window aggregates."""
    values = jnp.asarray(values, jnp.float32)
    if agg in ("sum", "count", "avg"):
        chans = []
        if agg in ("sum", "avg"):
            t = segment_sum(plan.pass1, values, use_pallas=use_pallas, interpret=interpret)
            chans.append(segment_sum(plan.pass2, t, use_pallas=use_pallas, interpret=interpret))
        if agg in ("count", "avg"):
            cnt = segment_sum(plan.pass2, plan.block_sizes, use_pallas=use_pallas,
                              interpret=interpret)
            chans.append(cnt)
        if agg == "sum":
            return chans[0]
        if agg == "count":
            return chans[0]
        return chans[0] / jnp.maximum(chans[1], 1e-30)
    if agg in ("min", "max"):
        t = _minmax_pass1(plan, values, agg, use_pallas=use_pallas,
                          interpret=interpret)
        return _minmax_pass2(plan, t, agg, use_pallas, interpret)
    raise ValueError(agg)


def _ell_reduce(ell, vec, op: str):
    """Dense padded reduce: one gather + axis reduce, no scatter.  The
    sentinel pad index clips to the appended identity row of ``vec``.
    ``vec`` may be [S] or [S, C] (stacked channels of one monoid)."""
    ident = {"min": jnp.inf, "max": -jnp.inf, "sum": 0.0}[op]
    pad = jnp.full((1,) + vec.shape[1:], ident, vec.dtype)
    ext = jnp.concatenate([vec, pad])
    rows = jnp.take(ext, ell, axis=0, mode="clip")  # sentinel -> identity row
    red = {"min": jnp.min, "max": jnp.max, "sum": jnp.sum}[op]
    return red(rows, axis=1)


def minmax_route(plan, use_pallas: bool) -> Optional[str]:
    """How ``plan``'s min/max channels reduce: ``"ell"`` (dense padded
    layouts), ``"tiled"`` (the Pallas tiled segment min/max) or ``"xla"``
    (masked XLA scatter-min/max), as the query functions below decide it;
    None for a plan that is neither a DBIndex nor an I-Index plan."""
    if isinstance(plan, DBIndexPlan) and plan.p1_ell is not None:
        return "ell"
    if isinstance(plan, (DBIndexPlan, IIndexPlan)):
        return "tiled" if use_pallas else "xla"
    return None


def plan_pages(plan) -> Optional[int]:
    """Pallas calls each segment kernel takes over ``plan``'s largest pass
    (:attr:`TilePlan.pages`: 1 up to ``segment_reduce.PAGE_TILES`` tiles);
    None for a plan that is neither a DBIndex nor an I-Index plan."""
    if isinstance(plan, DBIndexPlan):
        return max(plan.pass1.pages, plan.pass2.pages)
    if isinstance(plan, IIndexPlan):
        return plan.wd_plan.pages
    return None


def _minmax_pass1(plan: DBIndexPlan, values, op: str, gathered=None,
                  use_pallas: bool = True, interpret: Optional[bool] = None):
    """Block partials for an idempotent monoid: ELL fast path when the plan
    carries one, else the tiled segment min/max over the pass-1 layout
    (sized by block_capacity — static under streamed updates)."""
    if plan.p1_ell is not None:
        return _ell_reduce(plan.p1_ell, values, op)
    if gathered is None:
        gathered = jnp.take(values, plan.pass1.gather_padded)
    return segment_minmax_gathered(plan.pass1, gathered, op,
                                   interpret=interpret, use_pallas=use_pallas)


def _minmax_pass2(plan: DBIndexPlan, t, op: str, use_pallas: bool = True,
                  interpret: Optional[bool] = None):
    if plan.p2_ell is not None:
        return _ell_reduce(plan.p2_ell, t, op)
    gathered = jnp.take(t, plan.pass2.gather_padded)
    return segment_minmax_gathered(plan.pass2, gathered, op,
                                   interpret=interpret, use_pallas=use_pallas)


@functools.partial(jax.jit, static_argnames=("aggs", "use_pallas", "interpret"))
def _query_dbindex_multi_channels(plan: DBIndexPlan, values, aggs: tuple,
                                  use_pallas: bool = True,
                                  interpret: Optional[bool] = None):
    """Jitted channel core of :func:`query_dbindex_multi`: returns the
    deduped monoid channel results (finalizers run on the host in the
    wrapper — XLA fusion may contract a finalizer's multiply-add into an
    FMA, which re-rounds, and the TPU's division is not correctly rounded;
    NumPy keeps registered aggregates bit-identical to the oracle)."""
    from repro.core.aggregates import pack_channels

    pack = pack_channels(aggs)
    values = jnp.asarray(values, jnp.float32)
    sum_cols = pack.channels_of("sum")
    minmax_cols = [
        (ci, m, s) for ci, (m, s) in enumerate(pack.channels) if m != "sum"
    ]

    # ---- pass 1: one shared gather of the attribute vector -------------- #
    # registered derived aggregates add "square" channels; they reuse the
    # same gather (take(v², idx) == take(v, idx)² elementwise).  Each phase
    # runs under a named scope (metadata only) that the device trace shows
    # as its operations' op_name; the shared gather counts to the sums
    # unless only the masked min/max need it.
    need_sum_g1 = any(
        pack.channels[ci][1] in ("value", "square") for ci in sum_cols)
    need_g1 = need_sum_g1 or (plan.p1_ell is None and minmax_cols)
    g1 = None
    if need_g1:
        with jax.named_scope("pass1.sum" if need_sum_g1 else "pass1.minmax"):
            g1 = jnp.take(values, plan.pass1.gather_padded)
    t_cols = {}
    with jax.named_scope("pass1.sum"):
        for ci in sum_cols:
            src = pack.channels[ci][1]
            if src == "ones":
                # block cardinalities are host-exact plan metadata: the
                # count channel skips pass 1 entirely (same as the per-agg
                # path)
                t_cols[ci] = plan.block_sizes
            else:
                t_cols[ci] = segment_sum_gathered(
                    plan.pass1, g1 if src == "value" else g1 * g1,
                    use_pallas=use_pallas, interpret=interpret)
    with jax.named_scope("pass1.minmax"):
        for ci, mname, src in minmax_cols:
            vsrc = values if src == "value" else values * values
            gsrc = g1 if (g1 is None or src == "value") else g1 * g1
            t_cols[ci] = _minmax_pass1(plan, vsrc, mname, gathered=gsrc,
                                       use_pallas=use_pallas,
                                       interpret=interpret)

    # ---- pass 2: one gather of the stacked sum-channel matrix; min/max
    # reduce per channel, as in pass 1 (order-insensitive, so exact) ----- #
    outs = {}
    if sum_cols:
        with jax.named_scope("pass2.sum"):
            t_mat = jnp.stack([t_cols[ci] for ci in sum_cols], axis=1)
            g2 = jnp.take(t_mat, plan.pass2.gather_padded, axis=0)  # [Lpad, C]
            reduced = segment_sum_gathered(
                plan.pass2, g2, use_pallas=use_pallas, interpret=interpret,
            )
            if reduced.ndim == 1:
                reduced = reduced[:, None]
            for j, ci in enumerate(sum_cols):
                outs[ci] = reduced[:, j]
    with jax.named_scope("pass2.minmax"):
        for ci, mname, _ in minmax_cols:
            outs[ci] = _minmax_pass2(plan, t_cols[ci], mname, use_pallas,
                                     interpret)
    return tuple(outs[ci] for ci in range(len(pack.channels)))


def query_dbindex_multi(plan: DBIndexPlan, values, aggs: tuple,
                        use_pallas: bool = True,
                        interpret: Optional[bool] = None):
    """Fused multi-aggregate DBIndex query: one gather per pass feeds every
    monoid channel (the Cao et al. multi-window-function sharing, applied to
    graph windows).

    ``aggs`` is a static tuple of aggregate names sharing one window; the
    channels are deduped (``sum``/``avg`` share the value channel, ``count``/
    ``avg`` the cardinality channel, registered derived aggregates ride
    extra ``square`` channels), pass 1 runs once over the deduped value
    channels, and pass 2 gathers one stacked ``[block_capacity, C]`` matrix
    feeding k per-monoid segment reduces.  Returns one array per aggregate,
    in ``aggs`` order, bit-identical to the per-aggregate ``query_dbindex``
    results.
    """
    from repro.core.aggregates import pack_channels

    aggs = tuple(aggs)
    chans = _query_dbindex_multi_channels(plan, values, aggs,
                                          use_pallas=use_pallas,
                                          interpret=interpret)
    return pack_channels(aggs).finalize(chans)


# the recompile counter the streaming/serving tests assert on lives on the
# jitted channel core (the wrapper itself is plain Python)
query_dbindex_multi._cache_size = _query_dbindex_multi_channels._cache_size


def query_dbindex_sharded_multi(plan: DBIndexPlan, values, aggs: tuple,
                                mesh, axis="data"):
    """Fused multi-aggregate distributed query (stacked-channel matrix form).

    Tile rows are sharded over ``axis`` at whole-tile-group granularity
    (:mod:`repro.distributed.window_runtime`), so every segment's partial is
    produced by exactly one shard: the stacked SUM/COUNT/AVG channels ride
    one ``psum`` per pass, MIN/MAX ride ``pmin``/``pmax`` over sharded ELL
    layouts, and every aggregate is **bit-identical** to the single-host
    fused ``query_dbindex_multi`` answers (non-owning shards only ever
    contribute exact monoid identities).  Collective footprint: ``|T|·C +
    |n|·C`` floats per query, independent of window sizes.

    One-shot convenience — lays the plan out per call.  Streaming callers
    hold a :class:`~repro.distributed.window_runtime.ShardedDBPlan` (via
    ``Session(mesh=...)``) so the layout uploads once and streamed updates
    ship only changed tile groups.
    """
    from repro.distributed.window_runtime import (
        build_sharded_plan,
        query_sharded_multi,
    )

    splan = build_sharded_plan(plan, mesh, axis)
    return query_sharded_multi(splan, values, tuple(aggs))


def query_dbindex_sharded(plan: DBIndexPlan, values, mesh, axis="data"):
    """Single-aggregate (SUM) wrapper over the stacked-channel sharded
    query, kept for compatibility with the pre-multi-channel API."""
    return query_dbindex_sharded_multi(plan, values, ("sum",), mesh, axis)[0][: plan.n]


# ---------------------------------------------------------------------- #
#  I-Index plan
# ---------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class IIndexPlan:
    n: int
    max_level: int
    wd_plan: TilePlan  # wd members -> per-vertex difference partials
    pid: jnp.ndarray  # int32 [n], -1 roots
    level: jnp.ndarray  # int32 [n]

    def tree_flatten(self):
        return ((self.wd_plan, self.pid, self.level), (self.n, self.max_level))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(aux[0], aux[1], *children)

    def array_nbytes(self) -> dict:
        """Exact per-array device bytes (see :meth:`DBIndexPlan.array_nbytes`)."""
        out = {f"wd_plan.{k}": v for k, v in self.wd_plan.array_nbytes().items()}
        out["pid"] = int(self.pid.nbytes)
        out["level"] = int(self.level.nbytes)
        return out

    def plan_nbytes(self) -> int:
        """Total device bytes held by this plan."""
        return sum(self.array_nbytes().values())


jax.tree_util.register_pytree_node(
    IIndexPlan, IIndexPlan.tree_flatten, IIndexPlan.tree_unflatten
)


def plan_from_iindex(index: IIndex, tm: int = 512, ts: int = 512) -> IIndexPlan:
    sizes = np.diff(index.wd_offsets)
    owner = np.repeat(np.arange(index.n, dtype=np.int64), sizes)
    wd_plan = build_tile_plan(index.wd_members, owner, index.n, tm, ts)
    return IIndexPlan(
        n=index.n,
        max_level=int(index.level.max()) if index.n else 0,
        wd_plan=wd_plan,
        pid=jnp.asarray(index.pid),
        level=jnp.asarray(index.level),
    )


def patch_plan_iindex(
    plan: IIndexPlan, index: IIndex, changed_owners: np.ndarray
) -> IIndexPlan:
    """Incremental plan maintenance after ``update_iindex_batch``: only the
    WD tile groups holding cone vertices are re-laid-out; the PID forest and
    levels are small [n] arrays and are simply re-uploaded."""
    sizes = np.diff(index.wd_offsets)
    owner = np.repeat(np.arange(index.n, dtype=np.int64), sizes)
    wd_plan = patch_tile_plan(
        plan.wd_plan,
        index.wd_members,
        owner,
        index.n,
        np.asarray(changed_owners, np.int64),
    )
    return IIndexPlan(
        n=index.n,
        max_level=int(index.level.max()) if index.n else 0,
        wd_plan=wd_plan,
        pid=jnp.asarray(index.pid),
        level=jnp.asarray(index.level),
    )


@functools.partial(jax.jit, static_argnames=("schedule", "use_pallas", "interpret"))
def query_iindex(plan: IIndexPlan, values, schedule: str = "level",
                 use_pallas: bool = True, interpret: Optional[bool] = None):
    """Topological window SUM via inheritance (paper Algorithm 5 on device).

    schedule="level":   depth sequential steps, each one masked gather.
    schedule="doubling": pointer doubling, ceil(log2(depth+1)) gathers —
    the beyond-paper parallelization (§Perf).
    """
    values = jnp.asarray(values, jnp.float32)
    wdp = segment_sum(plan.wd_plan, values, use_pallas=use_pallas, interpret=interpret)
    return _inherit_scan(wdp, plan.pid, plan.level, plan.max_level, plan.n,
                         "sum", schedule)


_COMBINE = {"sum": (jnp.add, 0.0), "min": (jnp.minimum, jnp.inf),
            "max": (jnp.maximum, -jnp.inf)}


def _inherit_scan(wdp, pid, level, max_level: int, n: int, monoid: str,
                  schedule: str):
    """Per-monoid inheritance along the PID forest (Algorithm 5 generalized).

    ``wdp`` holds the window-difference partials, [n] or [n, C] (stacked
    channels of the same monoid).  Works for any commutative monoid — the
    level schedule combines each vertex with its parent's *finished*
    aggregate, the doubling schedule is an exact pointer-chain prefix
    combine — which is what lifts the device I-Index path beyond SUM.
    """
    combine, ident = _COMBINE[monoid]
    mat = wdp.ndim == 2
    if schedule == "level":
        def body(i, ans):
            parent = jnp.take(ans, jnp.clip(pid, 0, n - 1), axis=0)
            mask = pid >= 0
            parent = jnp.where(mask[:, None] if mat else mask, parent, ident)
            cond = level == i
            return jnp.where(cond[:, None] if mat else cond,
                             combine(wdp, parent), ans)

        return jax.lax.fori_loop(1, max_level + 1, body, wdp)
    if schedule == "doubling":
        rounds = max(1, int(np.ceil(np.log2(max_level + 1)))) if max_level else 0

        def body(_, carry):
            val, ptr = carry
            pv = jnp.take(val, jnp.clip(ptr, 0, n - 1), axis=0)
            mask = ptr >= 0
            pv = jnp.where(mask[:, None] if mat else mask, pv, ident)
            val = combine(val, pv)
            pp = jnp.take(ptr, jnp.clip(ptr, 0, n - 1))
            ptr = jnp.where(mask, pp, -1)
            return val, ptr

        val, _ = jax.lax.fori_loop(0, rounds, body, (wdp, pid))
        return val
    raise ValueError(schedule)


@functools.partial(jax.jit,
                   static_argnames=("aggs", "schedule", "use_pallas", "interpret"))
def _query_iindex_multi_channels(plan: IIndexPlan, values, aggs: tuple,
                                 schedule: str = "level",
                                 use_pallas: bool = True,
                                 interpret: Optional[bool] = None):
    """Jitted channel core of :func:`query_iindex_multi` (finalizers run
    on the host in the wrapper — see ``_query_dbindex_multi_channels``)."""
    from repro.core.aggregates import pack_channels

    pack = pack_channels(aggs)
    values = jnp.asarray(values, jnp.float32)
    n = plan.n
    sum_cols = pack.channels_of("sum")
    # named scopes as in _query_dbindex_multi_channels; the shared gather
    # counts to the sums unless only min/max channels need it
    with jax.named_scope("wd.sum" if sum_cols else "wd.minmax"):
        ones = jnp.ones(n, jnp.float32)
        srcs = {"value": values, "ones": ones, "square": values * values}
        cols = jnp.stack([srcs[src] for _, src in pack.channels],
                         axis=1)  # [n, C]
        g = jnp.take(cols, plan.wd_plan.gather_padded, axis=0)  # one gather
    chans = [None] * len(pack.channels)
    if sum_cols:
        with jax.named_scope("wd.sum"):
            wdp = segment_sum_gathered(plan.wd_plan, g[:, list(sum_cols)],
                                       use_pallas=use_pallas,
                                       interpret=interpret)
            if wdp.ndim == 1:
                wdp = wdp[:, None]
        with jax.named_scope("inherit.sum"):
            done = _inherit_scan(wdp, plan.pid, plan.level, plan.max_level,
                                 n, "sum", schedule)
            for j, ci in enumerate(sum_cols):
                chans[ci] = done[:, j]
    for mname in ("min", "max"):
        for ci in pack.channels_of(mname):
            with jax.named_scope("wd.minmax"):
                wdp = segment_minmax_gathered(plan.wd_plan, g[:, ci], mname,
                                              interpret=interpret,
                                              use_pallas=use_pallas)
            with jax.named_scope("inherit.minmax"):
                chans[ci] = _inherit_scan(wdp, plan.pid, plan.level,
                                          plan.max_level, n, mname, schedule)
    return tuple(chans)


def query_iindex_multi(plan: IIndexPlan, values, aggs: tuple,
                       schedule: str = "level", use_pallas: bool = True,
                       interpret: Optional[bool] = None):
    """Fused multi-aggregate topological query via inheritance.

    One gather of the stacked channel matrix feeds every monoid's
    window-difference reduce (the Pallas segment sum, and the tiled segment
    min/max per min/max channel); the inheritance scan then runs once per
    monoid (sum channels stacked into a single scan).  min/max ride the
    per-monoid level inheritance — containment (Theorem 5.1) makes the
    parent's finished aggregate a valid partial for *any* monoid, not just
    SUM.  Returns one array per aggregate, in ``aggs`` order.
    """
    from repro.core.aggregates import pack_channels

    aggs = tuple(aggs)
    chans = _query_iindex_multi_channels(plan, values, aggs,
                                         schedule=schedule,
                                         use_pallas=use_pallas,
                                         interpret=interpret)
    return pack_channels(aggs).finalize(chans)


query_iindex_multi._cache_size = _query_iindex_multi_channels._cache_size
