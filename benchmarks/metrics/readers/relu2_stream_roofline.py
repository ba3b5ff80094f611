"""The un-gated (relu2) routed experts' grouped matmuls' share of their
(bandwidth) roofline in the decode ticks: the bytes of the held experts'
weights a tick HAS to read (the (layer, expert) cells that received a
token, from ``experts_touched_pct`` of the ``serve.decode.deliver`` spans,
each expert's TWO matrices once at the published width, over the layers
the configuration's pattern marks ``E``: ``harness/hybridbytes.py``) over
the chip's HBM bandwidth, over the device time of the operations matching
``pattern`` that ran inside an execution of the decode program
(``module``). The shared expert and the router are left out on both
sides. Returns None where the trace holds no such span or operation, or
the configuration has no such pattern (a program without these layers)."""

import json
import re
import statistics

from harness import hybridbytes, spans as spans_mod


def read(ctx, pattern, module):
    trace, peaks = ctx.get("trace"), ctx["peaks"]
    cfg = ctx["cell"].config
    if trace is None or peaks is None or (
            "hybrid_override_pattern" not in cfg):
        return None
    loaded = spans_mod.for_cell(ctx)
    host = spans_mod.whole(loaded["host"], loaded["window_ns"])
    touched = [float(s[4]["experts_touched_pct"])
               for s in spans_mod.named(host, "serve.decode.deliver")
               if "experts_touched_pct" in s[4]]
    ticks = sorted((start, start + dur) for name, start, dur
                   in trace["modules"] if re.search(module, name))
    if not touched or not ticks:
        return None
    rx = re.compile(pattern)
    took, at = 0.0, 0
    for name, _, start, dur in sorted(trace["rows"], key=lambda r: r[2]):
        while at < len(ticks) and ticks[at][1] <= start:
            at += 1
        if at < len(ticks) and ticks[at][0] <= start and rx.search(name):
            took += dur / 1e9
    if not took:
        return None
    per_tick = hybridbytes.touched_expert_bytes(cfg, statistics.fmean(touched))
    ideal = len(ticks) * per_tick / peaks["hbm_bytes_per_s"]
    print(json.dumps({"relu2_expert_stream_roofline": {
        "ticks": len(ticks), "spans": len(touched), "bytes_per_tick": per_tick,
        "ideal_s": ideal, "took_s": took}}), flush=True)
    return 100.0 * ideal / took
