"""The paged decode kernel's share of its (bandwidth) roofline: the K/V
bytes of the blocks the kernel HAS to read (``live_blocks_pct`` of the
``serve.decode.prepare`` spans times the layer-blocks the slots' tables
span: every live block of a full layer, the blocks that meet the window
of a window layer; keys and values) over the chip's HBM bandwidth, over
the device time of the kernel's calls. Returns None where the trace holds
no such span or call."""

import json
import statistics

from harness import moebytes, spans as spans_mod, trace as trace_mod


def read(ctx, pattern):
    trace, peaks = ctx.get("trace"), ctx["peaks"]
    if trace is None or peaks is None:
        return None
    took, calls = trace_mod.matching_seconds(trace, pattern)
    loaded = spans_mod.for_cell(ctx)
    host = spans_mod.whole(loaded["host"], loaded["window_ns"])
    live = [float(s[4]["live_blocks_pct"])
            for s in spans_mod.named(host, "serve.decode.prepare")
            if "live_blocks_pct" in s[4]]
    if not calls or not live:
        return None
    cell = ctx["cell"]
    cfg, engine = cell.config, cell.spec["engine"]
    layers = cfg["num_hidden_layers"]
    ticks = calls / layers  # one call a layer a tick
    blocks = statistics.fmean(live) / 100.0 * moebytes.kv_tabled_blocks(
        cfg, engine)
    ideal = ticks * blocks * moebytes.kv_block_bytes(
        cfg, engine["block_size"]) / peaks["hbm_bytes_per_s"]
    print(json.dumps({"paged_decode_roofline": {
        "calls": calls, "live_blocks_a_tick": blocks, "ideal_s": ideal,
        "took_s": took}}), flush=True)
    return 100.0 * ideal / took
