#!/usr/bin/env python3
"""The program's own spans and named scopes in a profiler trace.

The program mirrors each stack span of a live ``repro.obs.Tracer`` as a
profiler annotation named ``repro.<name>``, and runs the phases of its
fused executors under named scopes (``pass1.sum``, ``pass2.minmax``,
``wd.sum``, ``inherit.minmax``, ``plan.patch`` and the like), which the
compiler keeps as each operation's ``op_name``.  ``bench/trace.py`` reads
the device's operations and the benchmark's own ``bench.*`` spans; this
module adds the program's side of the same trace:

- ``label``: an idle gap's ``bench.*`` label, followed by the innermost
  ``repro.*`` span each host thread had open at the gap's middle;
- ``device_scopes``: device seconds per named scope, container operations
  (``while``, ``call``, ``conditional``) left out, as their bodies' own
  operations carry the time.

    python3 bench/program_trace.py [trace_dir]

prints, for the newest trace under ``trace_dir`` (the benchmark's
``bench/_out/trace`` by default), the ten longest idle gaps so labelled and
``device_scopes``, as one JSON object.
"""

from __future__ import annotations

import json
import re
import sys
from collections import defaultdict
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import trace as xtrace  # noqa: E402

PROGRAM_PREFIX = "repro."
TRACE_DIR = Path(__file__).resolve().parent / "_out" / "trace"
SCOPES = ("pass1.sum", "pass1.minmax", "pass2.sum", "pass2.minmax",
          "wd.sum", "wd.minmax", "inherit.sum", "inherit.minmax",
          "plan.patch")
CONTAINERS = ("while", "call", "conditional")
UNSCOPED = "none"


# ---------------------------------------------------------------------- #
#  Program spans and gap labels
# ---------------------------------------------------------------------- #
def program_spans(path: str) -> list:
    """The trace's ``repro.*`` host events as ``(name, start_ns, end_ns,
    thread)``; ``thread`` tells the host threads' lines apart."""
    from jax.profiler import ProfileData

    out = []
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    start = float(ev.start_ns)
                    out.append((ev.name, start, start + float(ev.duration_ns),
                                (p, i)))
    return out


def label(gap, host_spans, spans) -> str:
    """``bench/trace.py``'s label of ``gap``, then the innermost
    ``repro.*`` span open at its middle on each thread (the latest begun)."""
    mid = (gap[0] + gap[1]) / 2
    inner = {}
    for name, s, e, thread in spans:
        if s <= mid <= e and (thread not in inner or s > inner[thread][0]):
            inner[thread] = (s, name)
    names = [name for _, name in sorted(inner.values())]
    base = xtrace.label(gap, host_spans)
    return "+".join(([] if base == "none" else [base]) + names) or "none"


def labelled_gaps(trace: xtrace.Trace, spans, top: int = 10) -> list:
    """The ``top`` longest idle gaps of the window, as ``reduce`` lists
    them, each ``[label, seconds]``."""
    lo, hi = trace.window()
    found = []
    for ops in trace.device_ops.values():
        busy = xtrace.union(xtrace.clip([(s, e) for _, s, e in ops], lo, hi))
        found += xtrace.gaps(busy, lo, hi)
    found.sort(key=lambda g: g[0] - g[1])
    return [[label(g, trace.host_spans, spans), (g[1] - g[0]) / 1e9]
            for g in found[:top]]


# ---------------------------------------------------------------------- #
#  Named scopes of device operations
# ---------------------------------------------------------------------- #
def scope_of(op_name: str):
    """The innermost named scope of ``SCOPES`` on an ``op_name`` path
    (``jit(f)/while/body/pass1.sum/jit(_take)/gather:``)."""
    for part in reversed(re.split(r"[/:]", op_name)):
        if part in SCOPES:
            return part
    return None


def op_names(trace_dir) -> dict:
    """``{device operation's name: op_name}`` from the newest trace under
    ``trace_dir``, ``{}`` where there is none.  The TPU's profiler keeps an
    operation's op_name in the ``tf_op`` stat of its event metadata, not
    in the HLO text it names the operation by; ``ProfileData`` does not
    expose those stats, so they are read from the file itself."""
    try:
        path = xtrace.find_xplane(str(trace_dir))
    except FileNotFoundError:
        return {}
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num == 1:  # XSpace.planes
            out.update(_plane_tf_ops(buf, *plane))
    return out


def device_scopes(ops: dict, names: dict) -> dict:
    """Device seconds per named scope (``UNSCOPED`` for the rest) of the
    non-container operations of ``reduce(...)["ops"]``."""
    out = defaultdict(float)
    for name, v in ops.items():
        if name.rsplit(" ", 1)[-1] in CONTAINERS:
            continue
        out[scope_of(names.get(v["text"], "")) or UNSCOPED] += v["seconds"]
    return dict(out)


def _varint(buf, pos: int) -> tuple:
    shift = result = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, pos
        shift += 7


def _fields(buf, pos: int, end: int):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, ``(start, end)`` for a length-delimited field."""
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = (pos, pos + size), pos + size
        elif wire in (1, 5):
            value, pos = None, pos + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def _plane_tf_ops(buf, start: int, end: int):
    """``(event name, tf_op)`` of a device XPlane's event metadata.  Field
    numbers of ``xplane.proto``: XPlane name 2, event_metadata 4,
    stat_metadata 5 (maps: key 1, value 2); XEventMetadata name 2,
    display_name 4, stats 5; XStat metadata_id 1, str_value 5, ref_value 7
    (the id of a stat metadata whose name is the string); XStatMetadata
    id 1, name 2."""
    text = lambda s: bytes(buf[s[0]:s[1]]).decode("utf-8", "replace")  # noqa: E731
    events, stat_names = [], {}
    for num, value in _fields(buf, start, end):
        if num == 2 and not text(value).startswith("/device:"):
            return
        if num in (4, 5):
            entry = dict(_fields(buf, *value)).get(2, (0, 0))
            if num == 4:
                events.append(entry)
            else:
                meta = dict(_fields(buf, *entry))
                stat_names[meta.get(1)] = text(meta[2]) if 2 in meta else ""
    for value in events:
        keys, op_name = [], None
        for k, x in _fields(buf, *value):
            if k in (2, 4):
                keys.append(text(x))
            elif k == 5:
                stat = dict(_fields(buf, *x))
                if stat_names.get(stat.get(1)) != "tf_op":
                    continue
                op_name = (text(stat[5]) if 5 in stat
                           else stat_names.get(stat.get(7), ""))
        if op_name:
            for key in keys:
                if key:
                    yield key, op_name


# ---------------------------------------------------------------------- #
def summary(trace_dir=TRACE_DIR, top: int = 10) -> dict:
    path = xtrace.find_xplane(str(trace_dir))
    trace = xtrace.Trace.load(path)
    reduced = xtrace.reduce(trace, top)
    return {
        "idle_gaps": labelled_gaps(trace, program_spans(path), top),
        "busy_s": reduced["busy_s"],
        "window_s": reduced["window_s"],
        "device_scopes": device_scopes(reduced["ops"], op_names(trace_dir)),
    }


if __name__ == "__main__":
    print(json.dumps(summary(sys.argv[1] if len(sys.argv) > 1
                             else TRACE_DIR)))
