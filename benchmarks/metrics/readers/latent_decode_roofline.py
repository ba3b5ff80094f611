"""The paged latent decode kernel's share of its roofline: the least time
the chip could take for the ticks the trace shows (the larger of the
latent bytes of the blocks the kernel HAS to read, ``live_blocks_pct`` of
the ``serve.decode.prepare`` spans times the layer-blocks the slots'
tables span, over the chip's HBM bandwidth, and the absorbed attention's
operations over the live positions, ``context_tokens`` of the same spans,
over its peak FLOP/s) over the device time of the kernel's calls. Says
which of the two bounds it on a line of its own. Returns None where the
trace holds no such span or call (a program without latent attention)."""

import json
import statistics

from harness import mlabytes, spans as spans_mod, trace as trace_mod


def read(ctx, pattern):
    trace, peaks = ctx.get("trace"), ctx["peaks"]
    if trace is None or peaks is None:
        return None
    took, calls = trace_mod.matching_seconds(trace, pattern)
    loaded = spans_mod.for_cell(ctx)
    host = spans_mod.whole(loaded["host"], loaded["window_ns"])
    prepared = [s[4] for s in spans_mod.named(host, "serve.decode.prepare")
                if "live_blocks_pct" in s[4] and "context_tokens" in s[4]]
    if not calls or not prepared:
        return None
    cell = ctx["cell"]
    cfg, engine = cell.config, cell.spec["engine"]
    ticks = calls / cfg["num_hidden_layers"]  # one call a layer a tick
    blocks = statistics.fmean(
        float(a["live_blocks_pct"]) for a in prepared
    ) / 100.0 * mlabytes.latent_tabled_blocks(cfg, engine)
    context = statistics.fmean(float(a["context_tokens"]) for a in prepared)
    memory = blocks * mlabytes.latent_block_bytes(
        cfg, engine["block_size"]) / peaks["hbm_bytes_per_s"]
    compute = mlabytes.latent_decode_flops(cfg, context) / peaks["flops_bf16"]
    ideal = ticks * max(memory, compute)
    print(json.dumps({"latent_decode_roofline": {
        "calls": calls, "live_blocks_a_tick": blocks,
        "context_tokens_a_tick": context, "memory_s_a_tick": memory,
        "compute_s_a_tick": compute,
        "bound": "memory" if memory >= compute else "compute",
        "ideal_s": ideal, "took_s": took}}), flush=True)
    return 100.0 * ideal / took
