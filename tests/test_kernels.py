"""Pallas kernel sweeps vs ref.py oracles (interpret mode on CPU).

Shapes are swept to cover the boundary cases the tile plans create:
segments straddling tile edges, empty segments, singleton blocks, D not a
lane multiple, empty inputs.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels.segment_reduce.ops import build_tile_plan, segment_sum  # noqa: E402
from repro.kernels.segment_reduce.ref import segment_reduce_ref  # noqa: E402


RNG = np.random.default_rng(0)


@pytest.mark.parametrize(
    "n,m,s,d",
    [
        (50, 200, 17, 1),
        (100, 1000, 100, 4),
        (1000, 5000, 600, 8),  # multiple output tiles
        (300, 700, 513, 3),  # segments straddle the TS=512 boundary
        (64, 0, 10, 4),  # empty input
        (128, 512, 1, 2),  # single segment
        (2000, 3000, 1200, 130),  # D > 128 lanes
    ],
)
def test_segment_sum_sweep(n, m, s, d):
    vals = RNG.normal(size=(n, d)).astype(np.float32)
    seg = np.sort(RNG.integers(0, s, m)).astype(np.int32)
    gidx = RNG.integers(0, n, m).astype(np.int32)
    plan = build_tile_plan(gidx, seg, s)
    out = segment_sum(plan, jnp.asarray(vals))
    ref = segment_reduce_ref(jnp.asarray(vals), jnp.asarray(gidx),
                             jnp.asarray(seg), s, "add")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32])
def test_segment_sum_dtypes(dtype):
    vals = (RNG.normal(size=(100, 4)) * 10).astype(dtype)
    seg = np.sort(RNG.integers(0, 30, 400)).astype(np.int32)
    gidx = RNG.integers(0, 100, 400).astype(np.int32)
    plan = build_tile_plan(gidx, seg, 30)
    out = segment_sum(plan, jnp.asarray(vals))
    ref = segment_reduce_ref(
        jnp.asarray(vals, jnp.float32), jnp.asarray(gidx), jnp.asarray(seg), 30, "add"
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_segment_sum_empty_segments_are_identity():
    # segments 3..9 get no rows -> exact zeros
    seg = np.array([0, 0, 1, 2, 10, 10], np.int32)
    gidx = np.arange(6, dtype=np.int32)
    vals = np.ones((6, 2), np.float32)
    plan = build_tile_plan(gidx, seg, 12)
    out = np.asarray(segment_sum(plan, jnp.asarray(vals)))
    assert np.allclose(out[3:10], 0)
    assert np.allclose(out[0], 2) and np.allclose(out[10], 2)


def test_segment_min_max_fallback():
    from repro.kernels.segment_reduce.ops import segment_reduce

    vals = RNG.normal(size=(80, 3)).astype(np.float32)
    seg = np.sort(RNG.integers(0, 20, 200)).astype(np.int32)
    gidx = RNG.integers(0, 80, 200).astype(np.int32)
    for op in ("min", "max"):
        out = segment_reduce(jnp.asarray(vals), jnp.asarray(gidx),
                             jnp.asarray(seg), 20, op=op)
        ref = segment_reduce_ref(jnp.asarray(vals), jnp.asarray(gidx),
                                 jnp.asarray(seg), 20, op)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref))


def _minmax_case(case):
    """(plan, vals, gather_idx, segment_ids, num_segments, d) for one
    tiled segment min/max case: rows sorted by segment, as the index
    builders give them."""
    from repro.kernels.segment_reduce.ops import patch_tile_plan

    rng = np.random.default_rng(len(case))
    d = 3 if case in ("empty", "headroom", "spanning_d3") else 1
    s = 1100  # three output tiles of 512 segment ids
    if case.startswith("spanning"):
        # segment 7 alone fills three 512-row input tiles of output tile 0
        seg = np.sort(np.concatenate([np.full(1400, 7),
                                      rng.integers(0, s, 900)]))
    elif case == "empty":
        # output tile 1 (ids 512-1023) and most single ids get no row
        seg = np.sort(np.concatenate([rng.integers(0, 40, 300),
                                      rng.integers(1024, s, 200)]))
    else:
        seg = np.sort(rng.integers(0, s, 2500))
    n = 400
    vals = rng.integers(-50, 50, (n, d)).astype(np.float32)
    if case == "inf":
        vals[rng.integers(0, n, 40), 0] = np.inf
        vals[rng.integers(0, n, 40), 0] = -np.inf
    gidx = rng.integers(0, n, seg.size).astype(np.int32)
    plan = build_tile_plan(gidx, seg, s,
                           headroom=0.5 if case == "headroom" else 0.0)
    if case.startswith("patched"):
        # rows of segments 3 and 900 change (and, when grown, ids past the
        # old space appear): the patcher re-lays-out only their groups
        keep = (seg != 3) & (seg != 900)
        grow = 1700 if case == "patched_grown" else s
        extra = np.concatenate([np.full(5, 3), np.full(9, 900),
                                np.arange(s, grow)])
        seg = np.concatenate([seg[keep], extra])
        order = np.argsort(seg, kind="stable")
        seg = seg[order]
        gidx = np.concatenate([gidx[keep],
                               rng.integers(0, n, extra.size)])[order]
        gidx = gidx.astype(np.int32)
        changed = np.concatenate([[3, 900], np.arange(s, grow)])
        plan = patch_tile_plan(plan, gidx, seg, grow, changed)
        s = grow
    return plan, vals, gidx, seg, s, d


@pytest.mark.parametrize("case", [
    "spanning", "spanning_d3", "empty", "inf", "headroom", "patched",
    "patched_grown",
])
@pytest.mark.parametrize("op", ["min", "max"])
def test_segment_minmax_tiled_matches_xla(op, case):
    """The Pallas tiled segment min/max equals the masked XLA
    scatter-min/max on the same plan, element for element, and both equal
    the oracle: empty segments hold the identity (+inf / -inf)."""
    from repro.kernels.segment_reduce.ops import segment_minmax_gathered

    plan, vals, gidx, seg, s, d = _minmax_case(case)
    g = jnp.take(jnp.asarray(vals), plan.gather_padded, axis=0)
    if d == 1:
        g = g[:, 0]
    tiled = segment_minmax_gathered(plan, g, op, interpret=True)
    xla = segment_minmax_gathered(plan, g, op, use_pallas=False)
    assert tiled.shape == xla.shape == ((s, d) if d > 1 else (s,))
    np.testing.assert_array_equal(np.asarray(tiled), np.asarray(xla))
    ref = segment_reduce_ref(jnp.asarray(vals), jnp.asarray(gidx),
                             jnp.asarray(seg, jnp.int32), s, op)
    np.testing.assert_array_equal(np.asarray(tiled).reshape(s, d),
                                  np.asarray(ref))
    empty = np.bincount(seg, minlength=s) == 0
    assert empty.any() or case.startswith("spanning")
    ident = np.inf if op == "min" else -np.inf
    assert (np.asarray(tiled).reshape(s, d)[empty] == ident).all()


# ------------------------------ bitset ------------------------------- #
@pytest.mark.parametrize("n,deg,k", [(200, 4.0, 1), (300, 6.0, 2), (150, 3.0, 3)])
def test_bitset_expand_sweep(n, deg, k):
    from repro.graphs.generators import erdos_renyi
    from repro.kernels.bitset_expand.ops import build_expand_plan, khop_reach
    from repro.kernels.bitset_expand.ref import khop_reach_ref

    g = erdos_renyi(n, deg, seed=int(n + k))
    src = np.concatenate([g.src, g.dst])
    dst = np.concatenate([g.dst, g.src])
    order = np.argsort(dst, kind="stable")
    es, ed = src[order], dst[order]
    plan = build_expand_plan(es, ed, n, tm=256, ts=256)
    sources = np.arange(min(96, n), dtype=np.int32)
    got = np.asarray(khop_reach(plan, n, sources, k))
    reach0 = np.zeros((n, 128), dtype=np.uint32)
    cols = np.arange(sources.size)
    reach0[sources, cols // 32] |= np.uint32(1) << (cols % 32).astype(np.uint32)
    ref = khop_reach_ref(reach0, es, ed, n, k)
    assert np.array_equal(got, ref)


def test_bitset_matches_host_bfs():
    from repro.core.windows import khop_window_single
    from repro.graphs.generators import erdos_renyi
    from repro.kernels.bitset_expand.ops import build_expand_plan, khop_reach

    g = erdos_renyi(250, 5.0, seed=42)
    src = np.concatenate([g.src, g.dst])
    dst = np.concatenate([g.dst, g.src])
    order = np.argsort(dst, kind="stable")
    plan = build_expand_plan(src[order], dst[order], g.n, tm=256, ts=256)
    got = np.asarray(khop_reach(plan, g.n, np.arange(64, dtype=np.int32), 2))
    for v in (0, 17, 63):
        members = np.flatnonzero((got[:, v // 32] >> np.uint32(v % 32)) & 1)
        assert np.array_equal(members, khop_window_single(g, 2, v))


# -------------------------------- fm --------------------------------- #
@pytest.mark.parametrize("b,f,k", [(64, 39, 10), (100, 8, 16), (256, 5, 3)])
def test_fm_interaction_sweep(b, f, k):
    from repro.kernels.fm_interaction.fm_interaction import fm_interaction
    from repro.kernels.fm_interaction.ref import fm_interaction_ref

    emb = jnp.asarray(RNG.normal(size=(b, f, k)), jnp.float32)
    out = fm_interaction(emb, interpret=True)
    ref = fm_interaction_ref(emb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_fm_equals_explicit_pairwise():
    """sum-square trick == O(F^2) pairwise dots (Rendle's identity)."""
    from repro.kernels.fm_interaction.ref import fm_interaction_ref

    emb = RNG.normal(size=(10, 6, 4)).astype(np.float32)
    ref = np.asarray(fm_interaction_ref(jnp.asarray(emb)))
    explicit = np.zeros(10)
    for i in range(6):
        for j in range(i + 1, 6):
            explicit += np.sum(emb[:, i] * emb[:, j], axis=-1)
    np.testing.assert_allclose(ref, explicit, rtol=1e-4, atol=1e-4)


# ---------------------------- attention ------------------------------ #
@pytest.mark.parametrize(
    "b,hq,hkv,s,d,bq,bk",
    [(1, 4, 2, 256, 64, 128, 128), (2, 2, 1, 128, 128, 64, 64),
     (1, 8, 8, 128, 32, 64, 64)],
)
def test_flash_attention_sweep(b, hq, hkv, s, d, bq, bk):
    from repro.kernels.flash_attention.flash_attention import flash_attention
    from repro.kernels.flash_attention.ref import mha_ref

    q = jnp.asarray(RNG.normal(size=(b, hq, s, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, bq=bq, bk=bk, interpret=True)
    ref = mha_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_flash_jnp_matches_naive():
    from repro.kernels.flash_attention.ref import mha_ref
    from repro.models.attention import flash_jnp

    q = jnp.asarray(RNG.normal(size=(2, 4, 256, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 2, 256, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 2, 256, 32)), jnp.float32)
    out = flash_jnp(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
    ref = mha_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_decode_matches_full_attention():
    from repro.kernels.flash_attention.ref import decode_ref, mha_ref

    b, hq, hkv, s, d = 2, 6, 2, 32, 16
    q = jnp.asarray(RNG.normal(size=(b, hq, s, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), jnp.float32)
    full = mha_ref(q, k, v, causal=True)
    dec = decode_ref(q[:, :, -1], k, v, s)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full[:, :, -1]),
                               rtol=2e-3, atol=2e-3)


def test_sliding_window_decode():
    from repro.kernels.flash_attention.ref import decode_ref, mha_ref

    b, h, s, d = 1, 2, 64, 16
    q = jnp.asarray(RNG.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, h, s, d)), jnp.float32)
    full = mha_ref(q, k, v, causal=True, local_window=16)
    dec = decode_ref(q[:, :, -1], k, v, s, window=16)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full[:, :, -1]),
                               rtol=2e-3, atol=2e-3)
