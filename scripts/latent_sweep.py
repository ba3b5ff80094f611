"""Time the latent-attention kernels alone at the served model's widths:
the paged latent decode kernel of ``ops/paged_attention.py`` and the
flash forward with keys of 192 and values of 128.

    python scripts/latent_sweep.py --out chiprun_out/latent.json

``decode``: 48 slots x 64 heads against a pool of latent rows ``[1,
blocks, block_size, 640]`` (rank 512 + rotary 64, padded to whole lane
tiles) at block sizes 512 / 1,024 / 2,048 (``--blocks``), ``--live`` of
the slots holding ``--context`` positions each: ``ms`` a call (a jitted
``fori_loop`` chains ``--reps`` calls through the result; host clock
around the loop, best of three), ``gbps`` (the live rows' 1,152 B over
``ms``), ``tflops`` (live positions x 64 x (576 + 512) x 2 over ``ms``),
``max_err`` against the plain reference. ``prefill``: one causal flash
forward over ``s`` tokens (``--lengths``), 64 heads, q / k 192, v 128, at
the module's own tiles and at ``--tiles`` (``block_q,block_k;...``, each
worked whole): ``ms``, ``tflops`` over the causal half. A tile the chip's
compiler refuses is reported as ``error``.

Kernels ALONE: confirm a choice by a traced run of the cell. On the CPU
(``JAX_PLATFORMS=cpu``) pass ``--tiny`` (interpret mode: a rehearsal of
the control flow, never a time).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _best(fn, *args):
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def decode_rows(args, jax, jnp, np):
    from fluxmpi_tpu.ops.paged_attention import (
        paged_latent_decode_attention as kernel,
        paged_latent_decode_reference as reference,
    )

    heads, rank, rope, width = args.heads, args.rank, args.rope, args.width
    rng = np.random.default_rng(args.seed)
    for block in args.blocks:
        per_slot = -(-args.max_len // block)
        blocks = 1 + args.slots * per_slot
        pool = jax.random.normal(
            jax.random.PRNGKey(0), (1, blocks, block, width), jnp.bfloat16)
        pool = pool.at[..., rank + rope:].set(0)
        tables = np.zeros((args.slots, per_slot), np.int32)
        lengths = np.zeros((args.slots,), np.int32)
        ids = rng.permutation(np.arange(1, blocks))
        for s in range(args.live):
            lengths[s] = args.context
            need = -(-args.context // block)
            tables[s, :need] = ids[s * per_slot:s * per_slot + need]
        q_abs = jax.random.normal(
            jax.random.PRNGKey(1), (args.slots, heads, rank), jnp.bfloat16)
        q_rope = jax.random.normal(
            jax.random.PRNGKey(2), (args.slots, heads, rope), jnp.bfloat16)
        tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
        row = {"kernel": "paged_latent_decode", "block_size": block,
               "live_slots": args.live, "context": args.context}
        try:
            @jax.jit
            def chain(q_abs, q_rope, pool, tables, lengths):
                def body(_, q):
                    out = kernel(q, q_rope, pool, tables, lengths)
                    return (q + 1e-3 * out).astype(q.dtype)
                return jax.lax.fori_loop(0, args.reps, body, q_abs)

            seconds = _best(chain, q_abs, q_rope, pool, tables, lengths)
            ms = 1e3 * seconds / args.reps
            tokens = args.live * args.context
            got = kernel(q_abs * 0.05, q_rope * 0.05, pool, tables, lengths)
            want = reference(q_abs * 0.05, q_rope * 0.05, pool, tables,
                             lengths)
            row.update(
                ms=ms, gbps=tokens * (rank + rope) * 2 / ms / 1e6,
                tflops=tokens * heads * (2 * rank + rope) * 2 / ms / 1e9,
                max_err=float(jnp.max(jnp.abs(
                    got.astype(jnp.float32) - want.astype(jnp.float32)))),
            )
        except Exception as exc:  # the chip's compiler refused the shape
            row["error"] = repr(exc)[:300]
        yield row


def prefill_rows(args, jax, jnp, np):
    from fluxmpi_tpu.ops.flash_attention import flash_attention

    heads, dk, dv = args.heads, args.nope + args.rope, args.vdim
    for s in args.lengths:
        keys = jax.random.split(jax.random.PRNGKey(s), 3)
        q = jax.random.normal(keys[0], (1, s, heads, dk), jnp.bfloat16)
        k = jax.random.normal(keys[1], (1, s, heads, dk), jnp.bfloat16)
        v = jax.random.normal(keys[2], (1, s, heads, dv), jnp.bfloat16)
        for tiles in [None, *args.tiles]:
            row = {"kernel": "flash_fwd", "s": s,
                   "tiles": "rule" if tiles is None else list(tiles)}
            kw = {} if tiles is None else {
                "block_q": tiles[0], "block_k": tiles[1]}
            try:
                @jax.jit
                def chain(q, k, v):
                    def body(_, q):
                        out = flash_attention(q, k, v, causal=True, **kw)
                        return q.at[..., :dv].add(1e-3 * out)
                    return jax.lax.fori_loop(0, args.reps, body, q)

                seconds = _best(chain, q, k, v)
                ms = 1e3 * seconds / args.reps
                flops = heads * s * (s + 1) / 2 * (dk + dv) * 2
                row.update(ms=ms, tflops=flops / ms / 1e9)
            except Exception as exc:
                row["error"] = repr(exc)[:300]
            yield row


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default=None)
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--reps", type=int, default=10)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--live", type=int, default=40)
    parser.add_argument("--context", type=int, default=10000)
    parser.add_argument("--blocks", default="512,1024,2048")
    parser.add_argument("--lengths", default="4096,16384")
    parser.add_argument("--tiles", default="512,512;1024,1024;512,2048")
    args = parser.parse_args(argv)
    import numpy as np

    import jax
    import jax.numpy as jnp

    args.blocks = [int(b) for b in args.blocks.split(",")]
    args.lengths = [int(s) for s in args.lengths.split(",")]
    args.tiles = [tuple(int(t) for t in pair.split(","))
                  for pair in args.tiles.split(";") if pair]
    args.heads, args.rank, args.rope, args.width = 64, 512, 64, 640
    args.nope, args.vdim, args.slots, args.max_len = 128, 128, 48, 17408
    if args.tiny:
        args.heads, args.rank, args.rope, args.width = 8, 128, 64, 256
        args.nope, args.vdim, args.slots, args.max_len = 64, 64, 4, 64
        args.live, args.context, args.reps = 3, 40, 1
        args.blocks, args.lengths, args.tiles = [16], [128], [(64, 64)]
    rows = []
    for row in (*decode_rows(args, jax, jnp, np),
                *prefill_rows(args, jax, jnp, np)):
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump({"device": jax.devices()[0].device_kind, "rows": rows},
                      f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
