"""Mean device time of one execution of a compiled program, by the
program's name on the trace's modules line."""

from harness import trace as trace_mod


def read(ctx, pattern):
    trace = ctx.get("trace")
    if trace is None:
        return None
    seconds, calls = trace_mod.module_seconds(trace, pattern)
    return 1e3 * seconds / calls if calls else None
