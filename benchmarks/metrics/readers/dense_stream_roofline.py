"""The dense matrices' share of their (bandwidth) roofline in the decode
ticks: the bytes of every matrix a tick HAS to read once (each layer's
two mixers' projections, its MLP, and the head, from the configuration's
keys: ``harness/densebytes.py``) over the chip's HBM bandwidth, over the
device time of the decode program's executions (``module``) LESS the time
of the operations matching ``kernels`` inside them (the state update and
the paged decode attention, which read the sequences' states and K/V and
have rooflines of their own). What is left holds more than the matrix
products (norms, the convolution, rotary, the K/V writes, the argmax), so
the share reads low and cannot pass 100. Returns None where the trace
holds no such execution, or the configuration is not such a model (no
``mamba_d_ssm``: a program without this block)."""

import json
import re

from harness import densebytes


def read(ctx, module, kernels):
    trace, peaks = ctx.get("trace"), ctx["peaks"]
    cfg = ctx["cell"].config
    if trace is None or peaks is None or "mamba_d_ssm" not in cfg:
        return None
    ticks = sorted((start, start + dur) for name, start, dur
                   in trace["modules"] if re.search(module, name))
    if not ticks:
        return None
    rx = re.compile(kernels)
    inside, at = 0.0, 0
    for name, detail, start, dur in sorted(trace["rows"], key=lambda r: r[2]):
        while at < len(ticks) and ticks[at][1] <= start:
            at += 1
        if at < len(ticks) and ticks[at][0] <= start and (
                rx.search(name) or rx.search(detail)):
            inside += dur / 1e9
    took = sum(end - start for start, end in ticks) / 1e9 - inside
    if took <= 0:
        return None
    per_tick = densebytes.tick_weight_bytes(cfg)
    ideal = len(ticks) * per_tick / peaks["hbm_bytes_per_s"]
    print(json.dumps({"dense_weight_stream_roofline": {
        "ticks": len(ticks), "bytes_per_tick": per_tick, "ideal_s": ideal,
        "kernels_s": inside, "took_s": took}}), flush=True)
    return 100.0 * ideal / took
