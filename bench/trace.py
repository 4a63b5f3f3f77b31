"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Busy time is the union of the intervals in which an operation ran on a
device, clipped to the traced window; the idle share is one minus busy over
the window.  Idle gaps are labelled by the benchmark's own host spans
(``jax.profiler.TraceAnnotation`` names starting with ``bench.``) that were
open at the middle of the gap: what the host was doing while the device
waited.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def device_op_line(plane_name: str, line_name: str) -> bool:
    """The per-operation line of an accelerator's plane."""
    return plane_name.startswith("/device:") and line_name == "XLA Ops"


def short_name(text: str) -> str:
    """An operation's HLO instruction and opcode (``%fusion.74 fusion``)
    from the full instruction text the profiler records as its name."""
    if " = " not in text:
        return text[:200]
    instr, rest = text.split(" = ", 1)
    m = re.search(r"\s([a-z][\w\-]*)\(", " " + rest)
    return f"{instr} {m.group(1)}" if m else instr


def find_xplane(directory: str) -> str:
    """The newest trace file the profiler wrote under ``directory``."""
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(found, key=os.path.getmtime)


class Trace:
    """Device operations per device, and the benchmark's host spans, as
    ``(name, start_ns, end_ns)``."""

    def __init__(self, device_ops: dict, host_spans: list):
        self.device_ops = device_ops
        self.host_spans = host_spans

    @classmethod
    def load(cls, path: str, is_device_line=device_op_line) -> "Trace":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        device_ops = defaultdict(list)
        host_spans = []
        for plane in data.planes:
            for line in plane.lines:
                dev = is_device_line(plane.name, line.name)
                for ev in line.events:
                    start = float(ev.start_ns)
                    end = start + float(ev.duration_ns)
                    if dev and end > start:
                        device_ops[plane.name].append((ev.name, start, end))
                    elif ev.name.startswith(HOST_PREFIX):
                        host_spans.append((ev.name, start, end))
        return cls(dict(device_ops), host_spans)

    def window(self):
        """``(start_ns, end_ns)`` of the benchmark's traced window."""
        spans = [(s, e) for name, s, e in self.host_spans
                 if name == WINDOW_SPAN]
        if len(spans) != 1:
            raise ValueError(f"{len(spans)} {WINDOW_SPAN} spans in the trace")
        return spans[0]


def clip(intervals, lo: float, hi: float):
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals):
    """Disjoint sorted intervals covering ``intervals``."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy, lo: float, hi: float):
    """Intervals of ``[lo, hi]`` that ``busy`` (disjoint, sorted) leaves."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def label(gap, host_spans) -> str:
    """The benchmark spans open at the middle of ``gap``, innermost last."""
    mid = (gap[0] + gap[1]) / 2
    open_ = sorted((s, name) for name, s, e in host_spans
                   if s <= mid <= e and name != WINDOW_SPAN)
    return "+".join(dict.fromkeys(name for _, name in open_)) or "none"


def reduce(trace: Trace, top: int = 10) -> dict:
    """Busy and window seconds, per-name device time and the breakdown of
    the traced window.  ``busy_s`` is averaged over the devices traced."""
    lo, hi = trace.window()
    per_name = defaultdict(lambda: [0, 0.0, ""])
    busy_total, gap_list = 0.0, []
    for ops in trace.device_ops.values():
        ivs = clip([(s, e) for _, s, e in ops], lo, hi)
        busy = union(ivs)
        busy_total += sum(e - s for s, e in busy)
        gap_list += gaps(busy, lo, hi)
        for text, s, e in ops:
            c = clip([(s, e)], lo, hi)
            if c:
                entry = per_name[short_name(text)]
                entry[0] += 1
                entry[1] += (c[0][1] - c[0][0]) / 1e9
                entry[2] = entry[2] or text
    devices = len(trace.device_ops)
    busy_s = busy_total / 1e9 / devices if devices else 0.0
    window_s = (hi - lo) / 1e9
    ops_sorted = sorted(per_name.items(), key=lambda kv: -kv[1][1])
    gap_sorted = sorted(gap_list, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        # no device in the trace: nothing to read, not an idle device
        "idle_share": (1.0 - busy_s / window_s
                       if devices and window_s > 0 else None),
        # per operation: events, device seconds, and its full HLO text
        "ops": {name: {"count": c, "seconds": sec, "text": text}
                for name, (c, sec, text) in per_name.items()},
        "breakdown": {
            "device_ops": [[name, sec]
                           for name, (_, sec, _) in ops_sorted[:top]],
            "idle_gaps": [[label(g, trace.host_spans), (g[1] - g[0]) / 1e9]
                          for g in gap_sorted],
        },
    }
