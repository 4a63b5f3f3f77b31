"""Pallas TPU kernels: tiled segment sum and min/max over sorted,
tile-aligned segments.

TPU-native rethink of the paper's shared-aggregation data plane (DESIGN.md
§2).  The host plan (:func:`repro.kernels.segment_reduce.ops.build_tile_plan`)
renumbers segments and pads rows so that

* rows are grouped by segment, segments by output tile of ``TS`` ids,
* every input tile of ``TM`` rows touches exactly **one** output tile,
* all tiles visiting one output tile are consecutive in the grid.

Inside the kernel, the per-tile reduction becomes a one-hot matmul on the
MXU: ``partial[D, TS] = vals[D, TM] @ one_hot(seg - ts0)`` — the scatter
that a GPU implementation would do with atomics is a systolic matrix product
here.  Rows run along the lanes (channels-major ``[D, M]`` operands), so a
one- or two-channel query streams ``D`` sublanes per row instead of padding
every row out to 128 lanes in HBM.
Revisit accumulation relies on Pallas TPU semantics: an output block whose
index_map repeats across *consecutive* grid steps stays resident in VMEM, so
``out += partial`` accumulates without ever round-tripping HBM.

VMEM budget per grid step (defaults ``TM=512, TS=512``, f32): vals
``D``·512·4 B, one-hot 512·512·4 = 1 MiB, out ``D``·512·4 B — well under
the ~16 MiB/core budget, MXU-aligned (multiples of 128).

``segment_minmax_tiled`` reduces the same layout with min or max, which no
matmul expresses.  Its tile is laid out the other way round: rows on
sublanes, segment ids on lanes, so the masked ``[TM, TS]`` tile reduces
over sublanes with elementwise VPU min/max (a cross-lane reduce would go
through the XLU for every segment).  Same grid, same scalar-prefetch
tables, same revisit accumulation into the resident ``[D, TS]`` block.

Pages.  Both kernels prefetch the plan's two int32 tile tables
(``m2out``, ``first_visit``) into scalar memory, 8 bytes a tile, and
v5e's SMEM is 1 MiB a core: one call over a whole plan stops compiling
near 131,000 tiles.  A plan of more than :data:`PAGE_TILES` tiles is
therefore reduced in *pages*: static pieces of ``PAGE_TILES`` tiles, cut
at multiples of ``PAGE_TILES`` (the tile count is known at trace time, so
a patched plan keeps its compile), each one ``pallas_call`` over its own
slices of the two tables.  The output runs from page to page through
``input_output_aliases``; a page whose first tile continues an output
tile begun on the page before (``first_visit == 0`` at its tile 0) reads
that block back through an extra input mapped like the output and
combines (``+``, ``min`` or ``max``) before it goes on, so sums
accumulate in exactly the order of one call and every result is
bit-identical.  The last page is padded to ``PAGE_TILES`` tiles marked
``SKIP_TILE`` (no work, block index held), so every page has one shape
and one Mosaic body; the rows and seg ids are never copied, each page
indexes them at its own offset (a third, one-word prefetched table).  A
plan of at most ``PAGE_TILES`` tiles takes one page: the one call above,
lowered exactly as before paging.  (The paged body over such a plan, as
one page, is bit-identical but 3.5% slower for the sum and 4.4% for min
and max at 57,859 tiles on a TPU v5e: its carry input and skip test cost
in every grid step.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_TM = 512  # rows per input tile
DEFAULT_TS = 512  # segment ids per output tile

# Tiles whose tables one pallas_call prefetches into scalar memory.  v5e
# has 1 MiB of SMEM a core; the two int32 tables take 8 bytes a tile, so
# 2**16 tiles hold 512 KiB of tables, half of SMEM, and leave the other
# half to the compiler's own scalars.  (60,038 tiles, 480 KB, compile on
# the chip; 180,956 tiles, 1.38 MB, do not.)  A constant, not a knob.
PAGE_TILES = 1 << 16
# first_visit value of a padding tile on the last page: no work
SKIP_TILE = 2


def num_pages(num_m_tiles: int) -> int:
    """Pages (pallas_calls) a plan of ``num_m_tiles`` tiles is reduced in
    by each segment kernel: 1 up to :data:`PAGE_TILES` tiles."""
    return max(1, -(-int(num_m_tiles) // PAGE_TILES))


def _sum_partial(rel, vals, ts: int):
    """[D, TS] sums of one tile: ``rel`` [1, TM] seg ids relative to the
    output tile (padding rows < 0), ``vals`` [D, TM]."""
    tm = rel.shape[1]
    # transposed one-hot [TS, TM] built on the fly from the seg-id row: a
    # padding row or a row outside this output tile matches no iota value
    iota = jax.lax.broadcasted_iota(jnp.int32, (ts, tm), 0)
    oh_t = (iota == rel).astype(vals.dtype)
    # HIGHEST keeps the f32 values exact through the MXU (a one-pass bf16
    # matmul would round them to 8 mantissa bits)
    return jax.lax.dot_general(
        vals,
        oh_t,
        (((1,), (1,)), ((), ())),  # contract over TM: [D, TS]
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _seg_sum_kernel(m2out_ref, first_ref, seg_ref, vals_ref, out_ref, *, ts: int):
    mi = pl.program_id(0)
    seg = seg_ref[...]  # [1, TM] int32 (padding rows carry -1)
    vals = vals_ref[...]  # [D, TM]
    partial = _sum_partial(seg - m2out_ref[mi] * ts, vals, ts)

    @pl.when(first_ref[mi] == 1)
    def _init():
        out_ref[...] = partial.astype(out_ref.dtype)

    @pl.when(first_ref[mi] == 0)
    def _acc():
        out_ref[...] = out_ref[...] + partial.astype(out_ref.dtype)


def _compiler_params():
    return pltpu.CompilerParams(dimension_semantics=(pltpu.ARBITRARY,))


def _one_call(kernel, vals, seg_ids, m2out, first_visit, num_out_tiles: int,
              tm: int, ts: int, interpret: bool):
    """One pallas_call over a whole plan of at most PAGE_TILES tiles."""
    num_m_tiles = seg_ids.shape[0]
    d = vals.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # m2out, first_visit
        grid=(num_m_tiles,),
        in_specs=[
            # one [1, TM] seg-id row per tile: the last two block dims equal
            # the array's, as the TPU lowering requires
            pl.BlockSpec((None, 1, tm), lambda mi, m2out, first: (mi, 0, 0)),
            pl.BlockSpec((d, tm), lambda mi, m2out, first: (0, mi)),
        ],
        out_specs=pl.BlockSpec((d, ts), lambda mi, m2out, first: (0, m2out[mi])),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((d, num_out_tiles * ts), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(m2out, first_visit, seg_ids.reshape(num_m_tiles, 1, tm), vals)


def _page_kernel(base_ref, m2out_ref, first_ref, seg_ref, vals_ref, prev_ref,
                 out_ref, *, ts: int, partial_fn, combine):
    """One page of a paged reduce: the one-call kernel's init and revisit
    accumulation, plus the carry of an output tile begun on the page
    before (``prev_ref``, that block as the page before left it)."""
    del base_ref  # read by the index maps only
    mi = pl.program_id(0)
    first = first_ref[mi]

    @pl.when(first != SKIP_TILE)
    def _tile():
        partial = partial_fn(seg_ref[...] - m2out_ref[mi] * ts, vals_ref[...])

        @pl.when(first == 1)
        def _init():
            out_ref[...] = partial

        @pl.when((first == 0) & (mi == 0))
        def _carry():
            out_ref[...] = combine(prev_ref[...], partial)

        @pl.when((first == 0) & (mi > 0))
        def _acc():
            out_ref[...] = combine(out_ref[...], partial)


def _paged_calls(partial_fn, combine, vals, seg_ids, m2out, first_visit,
                 num_out_tiles: int, tm: int, ts: int, interpret: bool):
    """``num_pages`` pallas_calls of PAGE_TILES tiles each, cut at
    multiples of PAGE_TILES, the output aliased from one to the next."""
    num_m_tiles = seg_ids.shape[0]
    d = vals.shape[0]
    page = PAGE_TILES
    pad = num_pages(num_m_tiles) * page - num_m_tiles
    # padding tiles hold the last output block and do no work
    m2out = jnp.concatenate([m2out, jnp.broadcast_to(m2out[-1:], (pad,))])
    first_visit = jnp.concatenate(
        [first_visit, jnp.full((pad,), SKIP_TILE, first_visit.dtype)])
    last = num_m_tiles - 1

    def tile(mi, base):  # the plan's tile at the page's step mi
        return jnp.minimum(base[0] + mi, last)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # the page's first tile, m2out, first_visit
        grid=(page,),
        in_specs=[
            pl.BlockSpec((None, 1, tm),
                         lambda mi, base, m2o, fv: (tile(mi, base), 0, 0)),
            pl.BlockSpec((d, tm),
                         lambda mi, base, m2o, fv: (0, tile(mi, base))),
            # the output block the page starts in: read once, at step 0
            pl.BlockSpec((d, ts), lambda mi, base, m2o, fv: (0, m2o[0])),
        ],
        out_specs=pl.BlockSpec((d, ts), lambda mi, base, m2o, fv: (0, m2o[mi])),
    )
    call = pl.pallas_call(
        functools.partial(_page_kernel, ts=ts, partial_fn=partial_fn,
                          combine=combine),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((d, num_out_tiles * ts), jnp.float32),
        input_output_aliases={5: 0},  # prev -> out
        compiler_params=_compiler_params(),
        interpret=interpret,
    )
    seg_ids = seg_ids.reshape(num_m_tiles, 1, tm)
    out = jnp.zeros((d, num_out_tiles * ts), jnp.float32)
    for lo in range(0, num_m_tiles, page):
        out = call(jnp.full((1,), lo, jnp.int32), m2out[lo:lo + page],
                   first_visit[lo:lo + page], seg_ids, vals, out)
    return out


@functools.partial(
    jax.jit, static_argnames=("num_out_tiles", "tm", "ts", "interpret")
)
def segment_sum_tiled(
    vals,  # [D, M_pad] pre-gathered rows (channels-major), grouped by segment
    seg_ids,  # [num_m_tiles, TM] int32, -1 on padding rows
    m2out,  # [num_m_tiles] int32: output tile per input tile (non-decreasing)
    first_visit,  # [num_m_tiles] int32 {0,1}
    *,
    num_out_tiles: int,
    tm: int = DEFAULT_TM,
    ts: int = DEFAULT_TS,
    interpret: bool = False,
):
    """Returns [D, num_out_tiles * TS] f32 segment sums: one pallas_call up
    to PAGE_TILES tiles, else ``num_pages`` calls of PAGE_TILES tiles (the
    module docstring), bit-identical to one call."""
    num_m_tiles = seg_ids.shape[0]
    assert vals.shape[1] == num_m_tiles * tm, (vals.shape, num_m_tiles, tm)
    args = (vals, seg_ids, m2out, first_visit, num_out_tiles, tm, ts,
            interpret)
    if num_m_tiles <= PAGE_TILES:
        return _one_call(functools.partial(_seg_sum_kernel, ts=ts), *args)
    return _paged_calls(functools.partial(_sum_partial, ts=ts), jnp.add,
                        *args)


def _minmax_ops(op: str):
    """``(identity, reduce, combine)`` of ``op``."""
    if op == "min":
        return jnp.inf, jnp.min, jnp.minimum
    return -jnp.inf, jnp.max, jnp.maximum


def _minmax_partial(rel, vals, ts: int, op: str):
    """[D, TS] minima or maxima of one tile (a segment with no row in it
    holds the identity): ``rel`` [1, TM] seg ids relative to the output
    tile (padding rows < 0), ``vals`` [D, TM]."""
    d, tm = vals.shape
    # rows onto sublanes: Mosaic transposes whole (8, 128) tiles, so the
    # seg-id row is broadcast to 8 sublanes and the channels are padded to
    # a multiple of 8 first
    rel_col = jnp.transpose(jnp.broadcast_to(rel, (8, tm)))[:, :1]  # [TM, 1]
    dp = -(-d // 8) * 8
    if dp != d:
        vals = jnp.concatenate([vals, jnp.zeros((dp - d, tm), vals.dtype)])
    rows = jnp.transpose(vals)  # [TM, dp]
    # a padding row or a row outside this output tile matches no lane
    hit = jax.lax.broadcasted_iota(jnp.int32, (tm, ts), 1) == rel_col
    ident, reduce, _ = _minmax_ops(op)
    return jnp.concatenate([
        reduce(jnp.where(hit, rows[:, c:c + 1], ident), axis=0, keepdims=True)
        for c in range(d)
    ])


def _seg_minmax_kernel(m2out_ref, first_ref, seg_ref, vals_ref, out_ref, *,
                       ts: int, op: str):
    mi = pl.program_id(0)
    rel = seg_ref[...] - m2out_ref[mi] * ts  # [1, TM], padding rows < 0
    partial = _minmax_partial(rel, vals_ref[...], ts, op)
    combine = _minmax_ops(op)[2]

    @pl.when(first_ref[mi] == 1)
    def _init():
        out_ref[...] = partial

    @pl.when(first_ref[mi] == 0)
    def _acc():
        out_ref[...] = combine(out_ref[...], partial)


@functools.partial(
    jax.jit, static_argnames=("op", "num_out_tiles", "tm", "ts", "interpret")
)
def segment_minmax_tiled(
    vals,  # [D, M_pad] f32 pre-gathered rows (channels-major), by segment
    seg_ids,  # [num_m_tiles, TM] int32, -1 on padding rows
    m2out,  # [num_m_tiles] int32: output tile per input tile (non-decreasing)
    first_visit,  # [num_m_tiles] int32 {0,1}
    *,
    op: str,
    num_out_tiles: int,
    tm: int = DEFAULT_TM,
    ts: int = DEFAULT_TS,
    interpret: bool = False,
):
    """Returns [D, num_out_tiles * TS] f32 segment minima (``op="min"``) or
    maxima (``op="max"``); a segment with no rows holds +inf / -inf.  One
    pallas_call up to PAGE_TILES tiles, else ``num_pages`` calls of
    PAGE_TILES tiles, as :func:`segment_sum_tiled`."""
    if op not in ("min", "max"):
        raise ValueError(f"op must be 'min' or 'max', not {op!r}")
    num_m_tiles = seg_ids.shape[0]
    assert vals.shape[1] == num_m_tiles * tm, (vals.shape, num_m_tiles, tm)
    args = (vals, seg_ids, m2out, first_visit, num_out_tiles, tm, ts,
            interpret)
    if num_m_tiles <= PAGE_TILES:
        return _one_call(functools.partial(_seg_minmax_kernel, ts=ts, op=op),
                         *args)
    return _paged_calls(functools.partial(_minmax_partial, ts=ts, op=op),
                        _minmax_ops(op)[2], *args)
