"""Time in all-gather / reduce-scatter / all-reduce during which no
compute ran on that device, over the traced window."""


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or trace["chips"] < 2:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
