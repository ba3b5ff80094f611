"""A number from the program's own host spans in the cell's newest
trace (``tracing.span``: ``TraceAnnotation`` events of the host plane,
on the device trace's clock): by default the mean duration in
milliseconds of the named spans; ``stat`` ``median`` for the median;
with ``per``, the summed duration of the named spans over the count of
``per`` spans (host time per decode tick over several spans of one
tick); with ``arg``, the statistic of that argument of the spans in
place of their duration. Spans the capture's edges cut are left out.
Prints how many spans it read on a line of its own; returns None where
the trace holds none (a program from before the spans)."""

import json
import statistics

from harness import spans as spans_mod


def read(ctx, spans, stat="mean", per=None, arg=None):
    loaded = spans_mod.for_cell(ctx)
    host = spans_mod.whole(loaded["host"], loaded["window_ns"])
    hits = spans_mod.named(host, spans)
    if not hits:
        return None
    if arg is not None:
        values = [float(s[4][arg]) for s in hits if arg in s[4]]
    else:
        values = [s[2] / 1e6 for s in hits]
    if per is not None:
        ticks = len(spans_mod.named(host, per))
        value = sum(values) / ticks if ticks else None
    elif not values:
        value = None
    else:
        value = {"mean": statistics.fmean,
                 "median": statistics.median}[stat](values)
    print(json.dumps({"host_span": {"spans": spans, "count": len(hits),
                                    "value": value}}), flush=True)
    return value
