"""Pallas TPU kernel: causal GQA flash attention (prefill hot spot).

Standard streaming-softmax tiling: grid (batch*kv_head, q_group, q_block,
kv_block) with the kv_block dimension innermost/sequential; running
(max, sum, acc) live in VMEM scratch and are rescaled per kv tile.  Causal
tiles beyond the diagonal are skipped via ``pl.when`` (they still appear in
the grid, but do no work — Mosaic elides the DMA for untouched blocks).

Block sizes default to (BQ=512, BK=512) with D = head_dim on lanes; VMEM
per step ~ q 512·128·4 + k/v 2·512·128·4 + scores 512·512·4 ≈ 2.3 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BQ = 512
DEFAULT_BK = 512
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, bq: int, bk: int, causal: bool, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _reset():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (not causal) or (ki * bk <= qi * bq + bq - 1)

    @pl.when(run)
    def _work():
        q = q_ref[0, 0]  # [BQ, D]
        k = k_ref[0, 0]  # [BK, D]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [BQ, BK]
        if causal:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_ref[...]  # [BQ, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # [BQ, BK]
        alpha = jnp.exp(m_prev - m_new)  # [BQ, 1]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "interpret")
)
def flash_attention(
    q, k, v, *, causal: bool = True,
    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK, interpret: bool = False,
):
    """q: [B, Hq, S, D]; k, v: [B, Hkv, S, D] -> [B, Hq, S, D].

    GQA folding: q heads are grouped so each kv head serves Hq/Hkv query
    groups; grid axis 1 walks the groups (k/v index map ignores it).
    """
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0
    group = hq // hkv
    scale = 1.0 / (d ** 0.5)
    qr = q.reshape(b * hkv, group, s, d)
    kr = k.reshape(b * hkv, 1, s, d)
    vr = v.reshape(b * hkv, 1, s, d)
    grid = (b * hkv, group, s // bq, s // bk)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, causal=causal, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda h, g, qi, ki: (h, g, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda h, g, qi, ki: (h, 0, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda h, g, qi, ki: (h, 0, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda h, g, qi, ki: (h, g, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, group, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                pltpu.PARALLEL, pltpu.PARALLEL, pltpu.PARALLEL, pltpu.ARBITRARY,
            )
        ),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hq, s, d)
