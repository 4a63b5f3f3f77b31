"""Pallas TPU kernel: fused FM second-order interaction.

Tiles the batch (rows) and keeps the full [F, K] field block per example in
VMEM; computes the sum-square factorization in one pass so the [B, F, K]
embedding tensor is read exactly once from HBM (the op is purely
memory-bound: 3 flops/float).  Lane layout: K padded to 128; F on sublanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_TB = 256


def _fm_kernel(emb_ref, out_ref):
    emb = emb_ref[...]  # [TB, F, Kp]
    s = jnp.sum(emb, axis=1)  # [TB, Kp]
    ss = jnp.sum(emb * emb, axis=1)
    out_ref[...] = 0.5 * jnp.sum(s * s - ss, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))
def fm_interaction(emb, tb: int = DEFAULT_TB, interpret: bool = False):
    """emb: [B, F, K] f32 -> [B] f32.  B padded to a TB multiple."""
    b, f, k = emb.shape
    kp = (-k) % 128
    bp = (-b) % tb
    if kp or bp:
        emb = jnp.pad(emb, ((0, bp), (0, 0), (0, kp)))
    bb = emb.shape[0]
    out = pl.pallas_call(
        _fm_kernel,
        grid=(bb // tb,),
        in_specs=[pl.BlockSpec((tb, f, emb.shape[2]), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((tb, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bb, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(pltpu.PARALLEL,)),
        interpret=interpret,
    )(emb.astype(jnp.float32))
    return out[:b, 0]
