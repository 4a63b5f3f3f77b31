"""The trace reduction, on synthetic intervals and on a tiny trace recorded
on the CPU."""

import time

import pytest

from bench import trace as xtrace


def test_union_gaps_and_labels():
    busy = xtrace.union([(0, 10), (5, 20), (30, 40), (40, 45)])
    assert busy == [(0, 20), (30, 45)]
    assert xtrace.gaps(busy, -5, 50) == [(-5, 0), (20, 30), (45, 50)]
    spans = [("bench.window", -5, 50), ("bench.update", 18, 35),
             ("bench.await", 24, 26)]
    assert xtrace.label((20, 30), spans) == "bench.update+bench.await"
    assert xtrace.label((45, 50), spans) == "none"
    assert xtrace.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_short_names_of_hlo_ops():
    assert xtrace.short_name(
        "%fusion.74 = s32[24]{0:T(1024)S(1)} fusion(s32[24]{0} %p), "
        "kind=kLoop") == "%fusion.74 fusion"
    assert xtrace.short_name(
        "%while.6 = (s32[]{:T(128)}, f32[5]{0}) while((s32[], f32[5]) %t), "
        "body=%b") == "%while.6 while"
    assert xtrace.short_name("copy_start") == "copy_start"


def test_reduce_of_a_synthetic_trace():
    t = xtrace.Trace(
        {"/device:TPU:0": [("a", 0e9, 2e9), ("b", 1e9, 3e9), ("a", 6e9, 7e9)]},
        [("bench.window", 0.0, 10e9), ("bench.update", 3e9, 6e9)])
    s = xtrace.reduce(t)
    assert s["busy_s"] == pytest.approx(4.0)
    assert s["window_s"] == pytest.approx(10.0)
    assert s["idle_share"] == pytest.approx(0.6)
    assert s["ops"]["a"] == {"count": 2, "seconds": pytest.approx(3.0),
                             "text": "a"}
    assert s["breakdown"]["device_ops"][0] == ["a", pytest.approx(3.0)]
    assert s["breakdown"]["idle_gaps"] == [
        ["bench.update", pytest.approx(3.0)], ["none", pytest.approx(3.0)]]


def _cpu_op_line(plane: str, line: str) -> bool:
    # the CPU backend runs its ops on client threads of the host plane
    return plane == "/host:CPU" and line.startswith("tf_XLAPjRtCpuClient")


def test_reduce_of_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.await"):
                time.sleep(0.05)
    finally:
        jax.profiler.stop_trace()
    t = xtrace.Trace.load(xtrace.find_xplane(str(tmp_path)), _cpu_op_line)
    lo, hi = t.window()
    assert hi - lo >= 0.05e9
    ops = [o for evs in t.device_ops.values() for o in evs]
    assert ops, "no op of the jitted function in the trace"
    s = xtrace.reduce(t)
    busy = sum(e - s_ for s_, e in xtrace.union(
        xtrace.clip([(a, b) for _, a, b in ops], lo, hi)))
    assert s["busy_s"] * len(t.device_ops) == pytest.approx(busy / 1e9)
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["idle_share"] == pytest.approx(1 - s["busy_s"] / s["window_s"])
    # the longest idle gap is the sleep, and it is labelled so
    label, seconds = s["breakdown"]["idle_gaps"][0]
    assert label == "bench.await" and seconds >= 0.04


def test_find_xplane_without_a_trace(tmp_path):
    with pytest.raises(FileNotFoundError):
        xtrace.find_xplane(str(tmp_path))
