"""The state-update kernel's share of its (bandwidth) roofline: the bytes
of the recurrent states the kernel HAS to move in the ticks the trace
shows (the live sequences a tick, ``live_states_pct`` of the
``serve.decode.prepare`` spans times the states the engine's pool holds,
each state read once and written once a Mamba layer) over the chip's HBM
bandwidth, over the device time of the kernel's calls. Returns None where
the trace holds no such span or call (a program without state layers)."""

import json
import statistics

from harness import spans as spans_mod, ssmbytes, trace as trace_mod


def read(ctx, pattern):
    trace, peaks = ctx.get("trace"), ctx["peaks"]
    if trace is None or peaks is None:
        return None
    took, calls = trace_mod.matching_seconds(trace, pattern)
    loaded = spans_mod.for_cell(ctx)
    host = spans_mod.whole(loaded["host"], loaded["window_ns"])
    live = [float(s[4]["live_states_pct"])
            for s in spans_mod.named(host, "serve.decode.prepare")
            if "live_states_pct" in s[4]]
    if not calls or not live:
        return None
    cell = ctx["cell"]
    cfg, engine = cell.config, cell.spec["engine"]
    ticks = calls / ssmbytes.mamba_layers(cfg)  # one call a layer a tick
    states = statistics.fmean(live) / 100.0 * engine["slots"]
    ideal = ticks * ssmbytes.state_update_bytes(
        cfg, states) / peaks["hbm_bytes_per_s"]
    print(json.dumps({"ssm_update_roofline": {
        "calls": calls, "live_states_a_tick": states, "ideal_s": ideal,
        "took_s": took}}), flush=True)
    return 100.0 * ideal / took
