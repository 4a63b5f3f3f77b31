"""Pallas TPU kernel: one k-hop BFS expansion step over packed bitsets.

TPU adaptation of the paper's window-computation primitive (DESIGN.md §2):
multi-source reachability is a scatter-OR of ``uint32``-packed source rows
into destination rows over a dst-sorted edge list — i.e. a segment-OR with
the same tile-aligned plan as the segment-sum kernel.

OR is not a matmul monoid, so the kernel uses the two-step TPU idiom:

1. **Segmented Hillis–Steele OR-scan** over the row tile (log2(TM) vector
   steps on the VPU; rows of different segments masked out of each shift),
   after which the *last* row of every segment holds the tile-local OR.
2. **Boundary extraction via 16-bit split one-hot matmul**: each output row
   receives exactly one boundary contribution per tile, so splitting words
   into exact-in-f32 16-bit halves makes the MXU scatter the boundary rows
   (sum of one term == the value), recombined as ``lo | hi << 16``.

Cross-tile continuation of a segment is handled by OR-idempotent revisit
accumulation on the resident output block (same consecutive-revisit
guarantee as segment_sum).  Lane count W = 128 uint32 words = 4096 BFS
sources per sweep.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_TM = 256
DEFAULT_TS = 256


def _expand_kernel(m2out_ref, first_ref, seg_ref, rows_ref, base_ref, out_ref, *, ts: int):
    mi = pl.program_id(0)
    seg_row = seg_ref[...]  # [1, TM] int32, -1 padding
    vals = rows_ref[...].astype(jnp.uint32)  # [TM, W] gathered reach[src]
    tm, w = vals.shape
    # seg id of each row replicated across the W lanes, so every row mask
    # of the scan is a plain [TM, W] elementwise operand
    seg = jnp.broadcast_to(seg_row, (w, tm)).T
    row = jax.lax.broadcasted_iota(jnp.int32, (tm, w), 0)
    vals = jnp.where(seg >= 0, vals, jnp.uint32(0))
    # segmented inclusive OR-scan down the rows
    shift = 1
    while shift < tm:
        rolled = pltpu.roll(vals, shift, 0)
        seg_rolled = pltpu.roll(seg, shift, 0)
        same = (row >= shift) & (seg_rolled == seg)
        vals = vals | jnp.where(same, rolled, jnp.uint32(0))
        shift *= 2
    # boundary = last row of each segment within the tile, found on the row
    nxt = pltpu.roll(seg_row, tm - 1, 1)  # nxt[i] = seg[i+1 mod tm]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tm), 1)
    boundary = (seg_row >= 0) & ((nxt != seg_row) | (lane == tm - 1))
    # transposed one-hot [TS, TM]: non-boundary rows and rows outside this
    # output tile match no iota value
    rel = jnp.where(boundary, seg_row - m2out_ref[mi] * ts, -1)
    oh_t = (jax.lax.broadcasted_iota(jnp.int32, (ts, tm), 0) == rel).astype(
        jnp.float32)
    # the TPU has no direct uint32 <-> f32 cast; halves < 2^16 fit int32.
    # HIGHEST keeps the halves exact through the MXU (a one-pass bf16
    # matmul would round any half above 256)
    lo = (vals & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    hi = (vals >> jnp.uint32(16)).astype(jnp.int32).astype(jnp.float32)
    dims = (((1,), (0,)), ((), ()))
    plo = jax.lax.dot_general(oh_t, lo, dims, precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    phi = jax.lax.dot_general(oh_t, hi, dims, precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    partial = (plo.astype(jnp.int32).astype(jnp.uint32)
               | (phi.astype(jnp.int32).astype(jnp.uint32) << jnp.uint32(16)))

    @pl.when(first_ref[mi] == 1)
    def _init():
        out_ref[...] = partial | base_ref[...]

    @pl.when(first_ref[mi] == 0)
    def _acc():
        out_ref[...] = out_ref[...] | partial


@functools.partial(jax.jit, static_argnames=("num_out_tiles", "tm", "ts", "interpret"))
def bitset_expand_tiled(
    gathered_rows,  # [Mpad, W] uint32 = reach[edge_src] tile-aligned
    base,  # [num_out_tiles*TS, W] uint32 = current reach (self OR)
    seg_ids,  # [nm, TM] int32 (-1 padding)
    m2out,
    first_visit,
    *,
    num_out_tiles: int,
    tm: int = DEFAULT_TM,
    ts: int = DEFAULT_TS,
    interpret: bool = False,
):
    num_m_tiles = seg_ids.shape[0]
    w = gathered_rows.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_m_tiles,),
        in_specs=[
            # one [1, TM] seg-id row per tile: the last two block dims equal
            # the array's, as the TPU lowering requires
            pl.BlockSpec((None, 1, tm), lambda mi, m2out, first: (mi, 0, 0)),
            pl.BlockSpec((tm, w), lambda mi, m2out, first: (mi, 0)),
            pl.BlockSpec((ts, w), lambda mi, m2out, first: (m2out[mi], 0)),
        ],
        out_specs=pl.BlockSpec((ts, w), lambda mi, m2out, first: (m2out[mi], 0)),
    )
    return pl.pallas_call(
        functools.partial(_expand_kernel, ts=ts),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_out_tiles * ts, w), jnp.uint32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(pltpu.ARBITRARY,)),
        interpret=interpret,
    )(m2out, first_visit, seg_ids.reshape(num_m_tiles, 1, tm), gathered_rows, base)
