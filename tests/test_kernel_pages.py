"""Paged segment kernels: a plan of more than ``PAGE_TILES`` tiles is
reduced in several Pallas calls, each prefetching the tables of at most
``PAGE_TILES`` tiles, bit-identical to one call over the whole plan.

``PAGE_TILES`` is monkeypatched to a few tiles so that small plans take
several pages; the jit caches are cleared around each such test, since a
cached trace does not see the constant change.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels.segment_reduce import segment_reduce as sr  # noqa: E402
from repro.kernels.segment_reduce.ops import (  # noqa: E402
    build_tile_plan,
    segment_minmax_gathered,
    segment_sum_gathered,
)
from repro.kernels.segment_reduce.ref import segment_reduce_ref  # noqa: E402

PAGE = 4  # tiles a page in these tests
TILE = 128


@pytest.fixture
def small_pages(monkeypatch):
    jax.clear_caches()
    monkeypatch.setattr(sr, "PAGE_TILES", PAGE)
    yield PAGE
    jax.clear_caches()


def _case(d, seed=0, rows=3000):
    """(plan, gathered rows, gather idx, seg ids, segments, values): 1100
    segments in 9 output tiles of 128 ids; segment 7 alone fills six input
    tiles, so output tile 0 spans the first two pages, and the tile count
    is no multiple of ``PAGE`` (a padded last page)."""
    rng = np.random.default_rng(seed)
    s, n = 1100, 500
    seg = np.sort(rng.integers(0, s, rows))
    seg = np.sort(np.concatenate([seg, np.full(700, 7)]))
    gidx = rng.integers(0, n, seg.size)
    plan = build_tile_plan(gidx, seg, s, tm=TILE, ts=TILE, headroom=0.2)
    vals = rng.integers(0, 100, (n, d)).astype(np.float32)
    g = jnp.take(jnp.asarray(vals), plan.gather_padded, axis=0)
    return plan, g, gidx, seg, s, vals


def _reduce(plan, g, op):
    if op == "sum":
        return segment_sum_gathered(plan, g, interpret=True)
    return segment_minmax_gathered(plan, g, op, interpret=True)


def _ref(vals, gidx, seg, s, op):
    """The pure-jnp oracle (``ops.segment_reduce``'s min/max route)."""
    return np.asarray(segment_reduce_ref(
        jnp.asarray(vals), jnp.asarray(gidx), jnp.asarray(seg), s,
        "add" if op == "sum" else op))


def test_case_straddles_pages():
    plan, *_ = _case(1)
    nm = plan.seg_tiles.shape[0]
    first = np.asarray(plan.first_visit)
    assert nm % PAGE and nm > 3 * PAGE
    # some page begins inside an output tile begun on the page before
    assert (first[PAGE::PAGE] == 0).any()


@pytest.mark.parametrize("d", [1, 2, 5])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_paged_equals_one_call_and_oracle(op, d, monkeypatch):
    plan, g, gidx, seg, s, vals = _case(d, seed=d)
    jax.clear_caches()
    one = np.asarray(_reduce(plan, g, op))
    monkeypatch.setattr(sr, "PAGE_TILES", PAGE)
    jax.clear_caches()
    try:
        assert plan.pages == -(-plan.seg_tiles.shape[0] // PAGE) > 1
        paged = np.asarray(_reduce(plan, g, op))
    finally:
        jax.clear_caches()
    np.testing.assert_array_equal(paged, one)
    np.testing.assert_array_equal(paged, _ref(vals, gidx, seg, s, op))


@pytest.mark.parametrize("op", ["sum", "max"])
def test_paged_without_a_padded_page(op, monkeypatch):
    """A tile count that is a multiple of ``PAGE`` leaves the last page
    full: no padding tiles."""
    plan, g, *_ = _case(2, seed=2, rows=3500)
    assert plan.seg_tiles.shape[0] % PAGE == 0
    jax.clear_caches()
    one = np.asarray(_reduce(plan, g, op))
    monkeypatch.setattr(sr, "PAGE_TILES", PAGE)
    jax.clear_caches()
    try:
        np.testing.assert_array_equal(np.asarray(_reduce(plan, g, op)), one)
    finally:
        jax.clear_caches()


@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("batch", ["vmap", "lax.map"])
def test_paged_batched_rows(op, batch, small_pages):
    """B = 8 rows through the paged kernels under ``vmap`` and
    ``lax.map`` equal the rows reduced one at a time."""
    plan, _, gidx, seg, s, _ = _case(2, seed=11)
    rng = np.random.default_rng(12)
    vb = rng.integers(0, 100, (8, 500, 2)).astype(np.float32)
    rows = jnp.take(jnp.asarray(vb), plan.gather_padded, axis=1)

    def one(g):
        return _reduce(plan, g, op)

    got = np.asarray(jax.vmap(one)(rows) if batch == "vmap"
                     else jax.lax.map(one, rows))
    for b in range(8):
        np.testing.assert_array_equal(got[b], np.asarray(one(rows[b])))
        np.testing.assert_array_equal(got[b], _ref(vb[b], gidx, seg, s, op))


def test_page_constant_fits_scalar_memory():
    """Two int32 tables a tile: a page's tables take half of v5e's 1 MiB
    of SMEM."""
    assert sr.PAGE_TILES * 2 * 4 == 512 * 1024
    assert sr.num_pages(sr.PAGE_TILES) == 1
    assert sr.num_pages(sr.PAGE_TILES + 1) == 2
    assert sr.num_pages(261_247) == 4
