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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_TM = 512  # rows per input tile
DEFAULT_TS = 512  # segment ids per output tile


def _seg_sum_kernel(m2out_ref, first_ref, seg_ref, vals_ref, out_ref, *, ts: int):
    mi = pl.program_id(0)
    seg = seg_ref[...]  # [1, TM] int32 (padding rows carry -1)
    vals = vals_ref[...]  # [D, TM]
    tm = seg.shape[1]
    rel = seg - m2out_ref[mi] * ts
    # transposed one-hot [TS, TM] built on the fly from the seg-id row: a
    # padding row or a row outside this output tile matches no iota value
    iota = jax.lax.broadcasted_iota(jnp.int32, (ts, tm), 0)
    oh_t = (iota == rel).astype(vals.dtype)
    # HIGHEST keeps the f32 values exact through the MXU (a one-pass bf16
    # matmul would round them to 8 mantissa bits)
    partial = jax.lax.dot_general(
        vals,
        oh_t,
        (((1,), (1,)), ((), ())),  # contract over TM: [D, TS]
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when(first_ref[mi] == 1)
    def _init():
        out_ref[...] = partial.astype(out_ref.dtype)

    @pl.when(first_ref[mi] == 0)
    def _acc():
        out_ref[...] = out_ref[...] + partial.astype(out_ref.dtype)


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
    """Returns [D, num_out_tiles * TS] f32 segment sums."""
    num_m_tiles = seg_ids.shape[0]
    d = vals.shape[0]
    assert vals.shape[1] == num_m_tiles * tm, (vals.shape, num_m_tiles, tm)
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
        functools.partial(_seg_sum_kernel, ts=ts),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((d, num_out_tiles * ts), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY,)
        ),
        interpret=interpret,
    )(m2out, first_visit, seg_ids.reshape(num_m_tiles, 1, tm), vals)


def _seg_minmax_kernel(m2out_ref, first_ref, seg_ref, vals_ref, out_ref, *,
                       ts: int, op: str):
    mi = pl.program_id(0)
    rel = seg_ref[...] - m2out_ref[mi] * ts  # [1, TM], padding rows < 0
    vals = vals_ref[...]  # [D, TM]
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
    if op == "min":
        ident, reduce, combine = jnp.inf, jnp.min, jnp.minimum
    else:
        ident, reduce, combine = -jnp.inf, jnp.max, jnp.maximum
    partial = jnp.concatenate([
        reduce(jnp.where(hit, rows[:, c:c + 1], ident), axis=0, keepdims=True)
        for c in range(d)
    ])  # [D, TS]; a segment with no row in this tile holds the identity

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
    maxima (``op="max"``); a segment with no rows holds +inf / -inf."""
    if op not in ("min", "max"):
        raise ValueError(f"op must be 'min' or 'max', not {op!r}")
    num_m_tiles = seg_ids.shape[0]
    d = vals.shape[0]
    assert vals.shape[1] == num_m_tiles * tm, (vals.shape, num_m_tiles, tm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # m2out, first_visit
        grid=(num_m_tiles,),
        in_specs=[
            pl.BlockSpec((None, 1, tm), lambda mi, m2out, first: (mi, 0, 0)),
            pl.BlockSpec((d, tm), lambda mi, m2out, first: (0, mi)),
        ],
        out_specs=pl.BlockSpec((d, ts), lambda mi, m2out, first: (0, m2out[mi])),
    )
    return pl.pallas_call(
        functools.partial(_seg_minmax_kernel, ts=ts, op=op),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((d, num_out_tiles * ts), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY,)
        ),
        interpret=interpret,
    )(m2out, first_visit, seg_ids.reshape(num_m_tiles, 1, tm), vals)
