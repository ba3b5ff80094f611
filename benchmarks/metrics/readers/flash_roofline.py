"""The flash-attention kernels' share of their roofline: the least time
the chip could take for the calls the trace shows (the larger of
operations over peak FLOP/s and bytes over peak bytes/s, causal half
only) over the device time those calls took."""

from harness import opsbytes, trace as trace_mod


def read(ctx, kernels):
    trace, peaks = ctx.get("trace"), ctx["peaks"]
    if trace is None or peaks is None:
        return None
    cell = ctx["cell"]
    cfg, data = cell.config, cell.spec["data"]
    ideal = took = 0.0
    for kind, pattern in kernels.items():
        seconds, calls = trace_mod.matching_seconds(trace, pattern)
        if not calls:
            return None
        cost = opsbytes.flash_kernel_cost(
            kind, rows=data["rows_per_step"] // cell.chips,
            heads=cfg["n_head"], seq_len=data["seq_len"],
            head_dim=cfg["n_embd"] // cfg["n_head"],
        )
        ideal += calls * opsbytes.roofline_seconds(cost, peaks)[0]
        took += seconds
    return 100.0 * ideal / took
