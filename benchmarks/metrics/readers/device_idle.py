"""1 - (union of the intervals in which an operation ran on the device)
over the traced window, averaged over the chips."""


def read(ctx):
    trace = ctx.get("trace")
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
