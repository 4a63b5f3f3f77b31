"""The program's spans and named scopes in a trace, and the readers of the
metrics built on them, on hand-made data and on a trace recorded on the
CPU."""

import time

import pytest

from bench import program_trace as pt
from bench import trace as xtrace
from bench.cell import load_reader

XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000000 }
    events { metadata_id: 2 offset_ps: 3000000000 duration_ps: 1000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8] fusion()"
    stats { metadata_id: 1 str_value: "jit(f)/while/body/pass2.minmax/min" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[8] fusion()"
    stats { metadata_id: 1 ref_value: 2 } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "jit(f)/pass1.sum/gather" } }
}
planes { id: 2 name: "/host:CPU" }
"""


def _ops(*entries):
    """``reduce(...)["ops"]`` of (short name, seconds) and their op_names."""
    ops = {name: {"count": 1, "seconds": sec, "text": f"{name} = ..."}
           for name, _, sec in entries}
    return ops, {f"{name} = ...": op for name, op, _ in entries if op}


def _write_trace(directory):
    from jax.profiler import ProfileData

    directory.mkdir(parents=True, exist_ok=True)
    (directory / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return {"%fusion.1 fusion": {"count": 1, "seconds": 3.0,
                                 "text": "%fusion.1 = f32[8] fusion()"},
            "%fusion.2 fusion": {"count": 1, "seconds": 1.0,
                                 "text": "%fusion.2 = f32[8] fusion()"},
            "%while.1 while": {"count": 1, "seconds": 4.0,
                               "text": "%while.1 = s32[] while()"}}


def test_label_adds_the_innermost_program_span_of_each_thread():
    host = [("bench.window", 0, 100), ("bench.submit", 10, 30)]
    spans = [("repro.launch", 0, 50, "flusher"),
             ("repro.executor.finalize", 15, 25, "flusher"),
             ("repro.gc", 18, 22, "client"),
             ("repro.flush.wait", 60, 90, "flusher")]
    assert pt.label((19, 21), host, spans) == \
        "bench.submit+repro.executor.finalize+repro.gc"
    assert pt.label((30, 40), host, spans) == "repro.launch"
    assert pt.label((95, 99), host, spans) == "none"


def test_recorded_cpu_trace_labels_a_gap_by_the_program_span(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.obs import Tracer

    tracer = Tracer()
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            f(x).block_until_ready()
            with tracer.span("executor.finalize"):
                time.sleep(0.05)
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = xtrace.find_xplane(str(tmp_path))
    trace = xtrace.Trace.load(path, lambda plane, line: (
        plane == "/host:CPU" and line.startswith("tf_XLAPjRtCpuClient")))
    label, seconds = pt.labelled_gaps(trace, pt.program_spans(path))[0]
    assert label.endswith("repro.executor.finalize") and seconds >= 0.04


def test_device_scopes_file_time_under_the_innermost_scope():
    ops, names = _ops(
        ("%fusion.1 fusion", "jit(f)/while/body/pass2.minmax/min:", 3.0),
        ("%fusion.2 fusion", "jit(f)/pass1.sum/jit(_take)/gather:", 1.0),
        ("%while.1 while", "jit(f)/while:", 9.0),
        ("%copy.1 copy", None, 0.5))
    assert pt.device_scopes(ops, names) == {
        "pass2.minmax": 3.0, "pass1.sum": 1.0, "none": 0.5}
    assert pt.scope_of("jit(f)/plan.patch:") == "plan.patch"
    assert pt.scope_of("jit(f)/while/body/add:") is None


def test_op_names_from_the_event_metadata_of_a_device_plane(tmp_path):
    ops = _write_trace(tmp_path)
    names = pt.op_names(tmp_path)
    assert names == {"%fusion.1 = f32[8] fusion()":
                     "jit(f)/while/body/pass2.minmax/min",
                     "%fusion.2 = f32[8] fusion()": "jit(f)/pass1.sum/gather"}
    assert pt.device_scopes(ops, names) == {"pass2.minmax": 3.0,
                                            "pass1.sum": 1.0}
    assert pt.op_names(tmp_path / "missing") == {}


def _request(start, **args):
    return {"name": "request", "start": start, "seconds": 0.01,
            "args": args}


def test_queue_wait_reader():
    ctx = {"window": (10.0, 40.0), "cut": 30.0,
           "spans": [_request(9.0, queued_ms=50.0),
                     _request(11.0, queued_ms=1.0, point=True),
                     _request(12.0, queued_ms=3.0, point=True),
                     _request(13.0, queued_ms=2.5, point=True),
                     _request(14.0, shed=True),
                     _request(31.0, queued_ms=70.0)]}
    assert load_reader("queue_wait_ms.read")(ctx) == 2.5
    assert load_reader("queue_wait_ms.whatif")(ctx) == 2.5
    ctx["spans"] = [_request(11.0, ok=True)]  # no queue wait stamped
    assert load_reader("queue_wait_ms.read")(ctx) is None


def test_finalize_reader():
    span = {"name": "executor.finalize", "args": {}}
    ctx = {"window": (10.0, 40.0),
           "spans": [dict(span, start=5.0, seconds=1.0),
                     dict(span, start=12.0, seconds=0.010),
                     dict(span, start=20.0, seconds=0.020)]}
    assert load_reader("finalize_ms")(ctx) == pytest.approx(15.0)
    ctx["spans"] = []
    assert load_reader("finalize_ms")(ctx) is None


def test_minmax_share_reader(tmp_path):
    ops = _write_trace(tmp_path / "trace")
    read = load_reader("minmax_share")
    ctx = {"trace": {"busy_s": 8.0, "ops": ops},
           "trace_dir": tmp_path / "trace"}
    assert read(ctx) == pytest.approx(100.0 * 3.0 / 8.0)
    # no operation under a named scope (a program without them): silent
    ctx["trace_dir"] = tmp_path / "none"
    assert read(ctx) is None
    assert read({"trace": None}) is None
