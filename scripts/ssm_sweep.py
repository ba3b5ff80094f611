"""The state-space kernels alone, on the chip: the decode tick's in-place
update of the live slots' states and convolution tails
(``ops/ssm.ssm_state_update``, ms a call and GB/s of state moved at
several counts of live slots) and the prefill's chunked scan
(``ops/ssm.ssd_chunk_scan``, ms a call and TFLOP/s at the prompt
buckets), at granite-4.0-h-small's widths. JSON rows.

    python scripts/ssm_sweep.py --out chiprun_out/ssm.json
    python scripts/ssm_sweep.py --tiny   # the CPU: a rehearsal, never a time
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time(fn, *args, reps: int):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    start = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / reps, out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--out")
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--reps", type=int, default=20)
    args = parser.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from fluxmpi_tpu.ops import ssm

    if args.tiny:
        slots, layers, heads, head_dim, d_state, chunk = 4, 2, 8, 16, 128, 8
        lives, buckets, reps, conv_dim = (0, 2, 4), (16, 24), 1, 160
    else:
        slots, layers, heads, head_dim = 128, 9, 128, 64
        d_state, chunk, conv_dim = 128, 256, 8448
        lives, buckets, reps = (0, 1, 16, 32, 64, 96, 128), (
            256, 512, 1024, 2048), args.reps
    rows = []
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    inner = heads * head_dim
    # The pools are ARGUMENTS, donated and handed back: closed over they
    # would be constants of the program.
    tail = jax.random.normal(keys[4], (slots, 3, conv_dim)).astype(
        jnp.bfloat16)
    pools = (
        jnp.zeros((layers, slots + 1, d_state, inner), jnp.float32),
        jnp.zeros((layers, slots + 1,
                   *ssm.tail_to_pool_layout(tail).shape[1:]), jnp.bfloat16),
    )
    x = jax.random.normal(keys[0], (slots, heads, head_dim))
    step = jax.nn.softplus(jax.random.normal(keys[1], (slots, heads)) - 3.0)
    decay = jnp.exp(-step * 4.0)
    b = jax.random.normal(keys[2], (slots, d_state))
    c = jax.random.normal(keys[3], (slots, d_state))

    def update(pools, entries, tail, x, step, decay, b, c):
        live = ssm.live_entries(entries)
        y = 0.0
        for layer in range(layers):  # one tick: every layer's update
            out, *pools = ssm.ssm_state_update(
                *pools, entries, tail, x, step, decay, b, c, layer=layer,
                live=live, interpret=True if args.tiny else None)
            y = y + out
        return y, tuple(pools)

    tick = jax.jit(update, donate_argnums=(0,))
    state_bytes = heads * head_dim * d_state * 4
    for live in lives:
        entries = jnp.where(jnp.arange(slots) < live,
                            jnp.arange(1, slots + 1), 0).astype(jnp.int32)
        _, pools = tick(pools, entries, tail, x, step, decay, b, c)
        jax.block_until_ready(pools)
        start = time.perf_counter()
        for _ in range(reps):
            _, pools = tick(pools, entries, tail, x, step, decay, b, c)
        jax.block_until_ready(pools)
        took = (time.perf_counter() - start) / reps
        rows.append({
            "kernel": "ssm_state_update", "live": live, "slots": slots,
            "layers": layers, "ms_a_tick": 1e3 * took,
            "us_a_state": 1e6 * took / max(live * layers, 1),
            "gb_per_s": live * layers * 2 * state_bytes / took / 1e9,
        })
        print(json.dumps(rows[-1]), flush=True)
    del pools
    a_rate = -jnp.exp(jnp.linspace(0.0, 2.7, heads))
    scan = jax.jit(lambda x, dt, b, c: ssm.ssd_chunk_scan(
        x, dt, a_rate, b, c, chunk=chunk))
    for seq in buckets:
        xs = jax.random.normal(keys[4], (1, seq, heads, head_dim)).astype(
            jnp.bfloat16)
        dts = jax.nn.softplus(jax.random.normal(keys[5], (1, seq, heads)) - 3)
        bs = jax.random.normal(keys[6], (1, seq, d_state))
        cs = jax.random.normal(keys[7], (1, seq, d_state))
        took, _ = _time(scan, xs, dts, bs, cs, reps=reps)
        # A chunk: C B^T, the masked product with x, C H and x^T B a head.
        flops = seq * heads * (
            2 * chunk * head_dim + 4 * head_dim * d_state
        ) + seq * 2 * chunk * d_state
        rows.append({"kernel": "ssd_chunk_scan", "seq": seq,
                     "ms_a_call": 1e3 * took,
                     "tflop_per_s": flops / took / 1e12})
        print(json.dumps(rows[-1]), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump({"device": str(jax.devices()[0]), "rows": rows}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
