"""Share of the device's busy time spent in the min/max phases of the
fused executors, in %: device seconds of the operations (containers left
out) whose op_name lies under a ``*.minmax`` named scope
(``pass1.minmax``, ``pass2.minmax``, ``wd.minmax``, ``inherit.minmax``),
over ``busy_s``.  The op_names come from the run's trace, in the
benchmark's trace directory.  Silent where no operation carries a named
scope."""
from bench import program_trace


def read(ctx):
    t = ctx["trace"]
    if t is None or not t["busy_s"] > 0:
        return None
    names = program_trace.op_names(
        ctx.get("trace_dir", program_trace.TRACE_DIR))
    seconds = program_trace.device_scopes(t["ops"], names)
    if set(seconds) <= {program_trace.UNSCOPED}:
        return None
    return 100.0 * sum(v for k, v in seconds.items()
                       if k.endswith(".minmax")) / t["busy_s"]
