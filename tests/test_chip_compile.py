"""Compile the main path's kernels for a described TPU v5e (no chip needed).

The TPU compiler is installed with JAX and compiles for a chip that is only
described, so these tests catch what interpret mode cannot: block shapes the
Mosaic lowering refuses, casts the chip lacks, programs that do not
partition.  Nothing runs, so they say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (  # noqa: E402
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

AGGS = ("sum", "count", "avg", "min", "max")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _seg_sum_args(one_chip, d, nm=16, tm=512, lead=()):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    return (s(lead + (d, nm * tm), jnp.float32), s((nm, tm), jnp.int32),
            s((nm,), jnp.int32), s((nm,), jnp.int32))


def test_segment_sum_tiled_compiles_for_v5e(one_chip):
    from repro.kernels.segment_reduce.segment_reduce import segment_sum_tiled

    def f(v, s, m, fv):
        return segment_sum_tiled(v, s, m, fv, num_out_tiles=4, tm=512, ts=512)

    assert "tpu_custom_call" in _compile_text(f, *_seg_sum_args(one_chip, 128))


def _has_minmax_kernel(text: str) -> bool:
    """The tiled segment min/max's custom call is in compiled HLO text."""
    return re.search(r"%segment_minmax_tiled\S* = .*tpu_custom_call",
                     text) is not None


@pytest.mark.parametrize("op,d", [("min", 1), ("max", 3)])
def test_segment_minmax_tiled_compiles_for_v5e(op, d, one_chip):
    from repro.kernels.segment_reduce.segment_reduce import (
        segment_minmax_tiled,
    )

    def f(v, s, m, fv):
        return segment_minmax_tiled(v, s, m, fv, op=op, num_out_tiles=4,
                                    tm=512, ts=512)

    assert _has_minmax_kernel(_compile_text(f, *_seg_sum_args(one_chip, d)))


def test_vmapped_segment_sum_compiles_for_v5e(one_chip):
    from repro.kernels.segment_reduce.segment_reduce import segment_sum_tiled

    f = jax.vmap(
        lambda v, s, m, fv: segment_sum_tiled(v, s, m, fv, num_out_tiles=4,
                                              tm=512, ts=512),
        in_axes=(0, None, None, None))
    args = _seg_sum_args(one_chip, 2, lead=(8,))
    assert "tpu_custom_call" in _compile_text(f, *args)


def test_bitset_expand_tiled_compiles_for_v5e(one_chip):
    from repro.kernels.bitset_expand.bitset_expand import bitset_expand_tiled

    nm, nout, tm, w = 16, 4, 256, 128

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def f(g, b, seg, m, fv):
        return bitset_expand_tiled(g, b, seg, m, fv, num_out_tiles=nout,
                                   tm=tm, ts=tm)

    text = _compile_text(
        f, s((nm * tm, w), jnp.uint32), s((nout * tm, w), jnp.uint32),
        s((nm, tm), jnp.int32), s((nm,), jnp.int32), s((nm,), jnp.int32))
    assert "tpu_custom_call" in text


def _small_plan(engine):
    from repro.core import engine_jax as ej
    from repro.core.dbindex import build_dbindex
    from repro.core.iindex import build_iindex
    from repro.core.windows import KHopWindow
    from repro.graphs.generators import barabasi_albert, erdos_renyi, random_dag

    if engine == "jax":
        g = erdos_renyi(600, 6.0, directed=False, seed=0)
        return g.n, ej.plan_from_dbindex(build_dbindex(g, KHopWindow(2)),
                                         headroom=0.5)
    if engine == "jax-no-ell":  # a hub tree: blocks too wide for ELL
        g = barabasi_albert(500, 1, seed=0)
        return g.n, ej.plan_from_dbindex(build_dbindex(g, KHopWindow(2)))
    g = random_dag(600, 3.0, seed=0)
    return g.n, ej.plan_from_iindex(build_iindex(g))


def _batched_executor_text(engine, plan, n, one_chip) -> str:
    from repro.core.api import _get_vmany

    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                       sharding=one_chip), plan)
    vb = jax.ShapeDtypeStruct((8, n), jnp.float32, sharding=one_chip)
    return _get_vmany(engine).lower(shapes, vb, AGGS, True, False) \
        .compile().as_text()


@pytest.mark.parametrize("engine", ["jax", "jax-iindex"])
def test_batched_serving_executor_runs_pallas_on_v5e(engine, one_chip):
    """The WindowService's [bucket, n] launch, with the Session's default
    ``use_pallas=True``, compiles with the segment-sum kernel in it (and
    on the I-Index, the tiled segment min/max)."""
    n, plan = _small_plan(engine)
    text = _batched_executor_text(engine, plan, n, one_chip)
    assert "tpu_custom_call" in text
    assert _has_minmax_kernel(text) == (engine == "jax-iindex")


def test_batched_executor_without_ell_runs_tiled_minmax_on_v5e(one_chip):
    """On a DBIndex plan without ELL layouts, the batched launch's min/max
    compile to the tiled segment min/max kernel."""
    n, plan = _small_plan("jax-no-ell")
    assert plan.p1_ell is None
    assert _has_minmax_kernel(_batched_executor_text("jax", plan, n, one_chip))


def _prefetch_sizes(fn, *args):
    """Per pallas_call in ``fn``'s jaxpr (nested ones included), the sizes
    of its scalar-prefetch operands."""
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                k = eqn.params["grid_mapping"].num_index_operands
                out.append([int(np.prod(v.aval.shape))
                            for v in eqn.invars[:k]])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


def _abstract_dbplan(one_chip, tiles1, tiles2, n=32768, cap=262144):
    """A DBIndex plan of shapes only: ``tiles1`` pass-1 and ``tiles2``
    pass-2 tiles of 512 rows, no ELL layout."""
    from repro.core import engine_jax as ej
    from repro.kernels.segment_reduce.ops import TilePlan

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def tiles(nm, segments):
        i32 = jnp.int32
        return TilePlan(s((nm * 512,), i32), s((nm, 512), i32), s((nm,), i32),
                        s((nm,), i32), segments, -(-segments // 512), 512, 512)

    return ej.DBIndexPlan(
        n=n, num_blocks=s((), jnp.int32), block_capacity=cap,
        pass1=tiles(tiles1, cap), pass2=tiles(tiles2, n),
        block_sizes=s((cap,), jnp.float32), link_counts=s((n,), jnp.float32))


@pytest.mark.parametrize("tiles1", [60_038, 261_247])
def test_batched_executor_pages_large_plans_on_v5e(tiles1, one_chip):
    """The [8, n] launch over a plan of more than ``PAGE_TILES`` tiles (the
    ``graph500-kron`` plan's 261,247 pass-1 tiles) compiles for v5e, and no
    pallas_call in it prefetches the tables of more than ``PAGE_TILES``
    tiles; a plan of at most ``PAGE_TILES`` tiles (the ``khop2-lj``
    plan's 60,038) takes one call per kernel use, as before paging."""
    from repro.core.api import _get_vmany
    from repro.kernels.segment_reduce.segment_reduce import PAGE_TILES

    plan = _abstract_dbplan(one_chip, tiles1, 41_990)
    vb = jax.ShapeDtypeStruct((8, plan.n), jnp.float32, sharding=one_chip)
    fn = _get_vmany("jax")
    sizes = _prefetch_sizes(
        lambda p, v: fn(p, v, AGGS, True, False), plan, vb)
    pages = plan.pass1.pages
    # pass 1: the value sum, min and max; pass 2: the stacked sums, min, max
    assert len(sizes) == 3 * pages + 3
    assert max(max(s) for s in sizes) <= PAGE_TILES
    if tiles1 <= PAGE_TILES:
        assert pages == 1
        assert sorted(sizes) == [[41_990] * 2] * 3 + [[tiles1] * 2] * 3
    else:
        assert pages == 4
    text = fn.lower(plan, vb, AGGS, True, False).compile().as_text()
    calls = re.findall(r"%(segment_\w+?)\.\d+ = .*tpu_custom_call", text)
    assert sorted(set(calls)) == ["segment_minmax_tiled", "segment_sum_tiled"]
    assert len(calls) == len(sizes)


def test_sharded_query_compiles_on_a_4_chip_mesh(topo):
    """The sharded fused query (XLA segment ops under ``jax.shard_map``,
    one collective per pass) partitions over four described chips."""
    from repro.distributed.window_runtime import _get_sharded_query

    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    ndev, n, cap, r1, r2, w1, w2 = 4, 4096, 8192, 16384, 8192, 64, 16

    def sharded(shape, dt):
        spec = P("data", *(None,) * (len(shape) - 1))
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))

    def replicated(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, P(None)))

    i32 = jnp.int32
    shards = (
        sharded((ndev * r1,), i32), sharded((ndev * r1,), i32),
        sharded((ndev * r2,), i32), sharded((ndev * r2,), i32),
        sharded((cap, w1), i32), sharded((cap,), i32),
        sharded((n, w2), i32), sharded((n,), i32),
    )
    cfg = (n, cap, cap, n, True)
    compiled = _get_sharded_query().lower(
        shards, (replicated((cap,), jnp.float32),),
        replicated((n,), jnp.float32),
        mesh=mesh, axes=("data",), aggs=AGGS, cfg=cfg,
    ).compile()
    assert "all-reduce" in compiled.as_text()
