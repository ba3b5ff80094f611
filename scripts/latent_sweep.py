"""Time the paged decode kernels alone at the serve cells' shapes, and the
latent-attention kernels at the served model's widths: the two decode
kernels of ``ops/paged_attention.py`` and the flash forward with keys of
192 and values of 128.

    python scripts/latent_sweep.py --out chiprun_out/latent.json
    python scripts/latent_sweep.py --phases cells --live 1,10,25,100 \
        [--cells sarvam,gpt2m] [--tree <another checkout>] --out ...

``cells``: kernel time against LIVE blocks, without a serve run. Each of
``--cells`` (the six serve cells' ``(slots, table width, block_size,
lanes)``; ``trinity`` is its full layer, ``trinity-ring`` a window
layer's ring) at each ``--live`` share (percent of the ``slots x width``
table entries that hold a live block: ``slots * sqrt(share)`` slots at
scattered ids share them evenly, a half-full last block each, full
tables at 100): ``ms`` a call as below, ``us_per_live_block``, ``gbps``
over the live blocks' bytes, ``walk_ms`` (the list a tick makes once for
its layers, :func:`live_block_walk`; a tree without one walks its
static grid) and ``max_err`` against the plain reference. ``--tree DIR``
times another checkout's kernels (one tree a process).

``decode``: 48 slots x 64 heads against a pool of latent rows ``[1,
blocks, block_size, 640]`` (rank 512 + rotary 64, padded to whole lane
tiles) at block sizes 512 / 1,024 / 2,048 (``--blocks``), ``--live-slots``
of the slots holding ``--context`` positions each: ``ms`` a call (a jitted
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
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The serve cells' decode attention: slots, table entries, block size,
# query heads, K/V heads and head_dim (a latent cell: rank, rotary lanes
# and the pool's padded width), the window of a ring.
CELLS = {
    "gpt2m": dict(slots=32, entries=8, block=128, heads=16, kv_heads=16,
                  head_dim=64),
    "trinity": dict(slots=64, entries=17, block=512, heads=32, kv_heads=4,
                    head_dim=128),
    "trinity-ring": dict(slots=64, entries=5, block=512, heads=32,
                         kv_heads=4, head_dim=128, window=2048),
    "sarvam": dict(slots=48, entries=17, block=1024, heads=64, rank=512,
                   rope=64, width=640),
    "granite": dict(slots=128, entries=10, block=256, heads=32, kv_heads=8,
                    head_dim=128),
    "nemotron": dict(slots=192, entries=24, block=256, heads=32, kv_heads=2,
                     head_dim=128),
    "falcon": dict(slots=96, entries=6, block=512, heads=20, kv_heads=4,
                   head_dim=128),
    "tiny": dict(slots=4, entries=3, block=16, heads=4, kv_heads=2,
                 head_dim=64),
    "tiny-ring": dict(slots=4, entries=3, block=16, heads=4, kv_heads=2,
                      head_dim=64, window=24),
    "tiny-latent": dict(slots=4, entries=3, block=16, heads=8, rank=128,
                        rope=64, width=256),
}


def _best(fn, *args):
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _live_tables(cell, share, rng, np):
    """Tables and lengths with ``share`` percent of the entries live (see
    the module docstring); blocks handed out in scrambled order."""
    slots, entries, block = cell["slots"], cell["entries"], cell["block"]
    total = max(1, round(share / 100.0 * slots * entries))
    live = max(1, min(slots, round(slots * (share / 100.0) ** 0.5)))
    live = max(live, -(-total // entries))
    tables = np.zeros((slots, entries), np.int32)
    lengths = np.zeros((slots,), np.int32)
    ids = iter(rng.permutation(np.arange(1, 1 + slots * entries)))
    for n, slot in enumerate(sorted(rng.permutation(slots)[:live])):
        held = total // live + (n < total % live)
        if held == entries and cell.get("window"):
            # A ring that has wrapped, the window cutting its first block.
            lengths[slot] = 2 * entries * block + block // 2 + 1
        elif share >= 100:
            lengths[slot] = held * block
        else:
            lengths[slot] = (held - 1) * block + block // 2 + 1
        tables[slot, :held] = [next(ids) for _ in range(held)]
    return tables, lengths, total


def cell_rows(args, jax, jnp, np):
    from fluxmpi_tpu.ops import paged_attention as ops

    rng = np.random.default_rng(args.seed)
    make_walk = getattr(ops, "live_block_walk", None)
    for name in args.cells:
        cell = CELLS[name]
        slots, entries, block = cell["slots"], cell["entries"], cell["block"]
        latent = "rank" in cell
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
        if latent:
            width = cell["width"]
            live_lanes = cell["rank"] + cell["rope"]
            pools = (jax.random.normal(
                keys[0], (1, 1 + slots * entries, block, width),
                jnp.bfloat16).at[..., live_lanes:].set(0),)
            qs = (0.05 * jax.random.normal(
                      keys[1], (slots, cell["heads"], cell["rank"]),
                      jnp.bfloat16),
                  0.05 * jax.random.normal(
                      keys[2], (slots, cell["heads"], cell["rope"]),
                      jnp.bfloat16))
            kernel = ops.paged_latent_decode_attention
            reference = ops.paged_latent_decode_reference
            kw = {}
            block_bytes = block * live_lanes * 2
        else:
            width = cell["kv_heads"] * cell["head_dim"]
            shape = (1, 1 + slots * entries, block, width)
            pools = (jax.random.normal(keys[0], shape, jnp.bfloat16),
                     jax.random.normal(keys[1], shape, jnp.bfloat16))
            qs = (jax.random.normal(
                keys[2], (slots, cell["heads"], cell["head_dim"]),
                jnp.bfloat16),)
            kernel = ops.paged_decode_attention
            reference = ops.paged_decode_reference
            kw = {"window": cell.get("window")}
            block_bytes = 2 * block * width * 2
        for share in args.live:
            tables, lengths, total = _live_tables(cell, share, rng, np)
            tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
            row = {"kernel": "paged_latent_decode" if latent
                   else "paged_decode_attention", "cell": name,
                   "live_pct": share, "live_blocks": total,
                   "live_slots": int((lengths > 0).sum())}
            try:
                walk_kw = {}
                if make_walk is not None:
                    walk_fn = functools.partial(
                        make_walk, block_size=block, **kw)
                    walk_kw = {"walk": jax.jit(walk_fn)(tables, lengths)}

                    @jax.jit
                    def walks(tables, lengths):
                        def body(_, lengths):
                            _, blocks, _, count = walk_fn(tables, lengths)
                            # The same lengths, through the list.
                            return jnp.minimum(
                                lengths, lengths + blocks[0] + count[0])
                        return jax.lax.fori_loop(0, args.reps, body, lengths)

                    row["walk_ms"] = 1e3 * _best(
                        walks, tables, lengths) / args.reps

                @jax.jit
                def chain(qs, pools, tables, lengths, walk_kw):
                    def body(_, q):
                        out = kernel(q, *qs[1:], *pools, tables, lengths,
                                     **kw, **walk_kw)
                        return (q + 1e-3 * out).astype(q.dtype)
                    return jax.lax.fori_loop(0, args.reps, body, qs[0])

                seconds = _best(chain, qs, pools, tables, lengths, walk_kw)
                ms = 1e3 * seconds / args.reps
                got = kernel(*qs, *pools, tables, lengths, **kw, **walk_kw)
                want = reference(*qs, *pools, tables, lengths, **kw)
                dead = np.asarray(lengths) == 0
                row.update(
                    ms=ms, us_per_live_block=1e3 * ms / total,
                    gbps=total * block_bytes / ms / 1e6,
                    max_err=float(jnp.max(jnp.abs(
                        got.astype(jnp.float32) - want.astype(jnp.float32)))),
                    dead_rows_zero=bool(
                        (np.asarray(got, np.float32)[dead] == 0).all()),
                )
            except Exception as exc:  # the chip's compiler refused it
                row["error"] = repr(exc)[:300]
            yield row


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
        for s in range(args.live_slots):
            lengths[s] = args.context
            need = -(-args.context // block)
            tables[s, :need] = ids[s * per_slot:s * per_slot + need]
        q_abs = jax.random.normal(
            jax.random.PRNGKey(1), (args.slots, heads, rank), jnp.bfloat16)
        q_rope = jax.random.normal(
            jax.random.PRNGKey(2), (args.slots, heads, rope), jnp.bfloat16)
        tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
        row = {"kernel": "paged_latent_decode", "block_size": block,
               "live_slots": args.live_slots, "context": args.context}
        try:
            @jax.jit
            def chain(q_abs, q_rope, pool, tables, lengths):
                def body(_, q):
                    out = kernel(q, q_rope, pool, tables, lengths)
                    return (q + 1e-3 * out).astype(q.dtype)
                return jax.lax.fori_loop(0, args.reps, body, q_abs)

            seconds = _best(chain, q_abs, q_rope, pool, tables, lengths)
            ms = 1e3 * seconds / args.reps
            tokens = args.live_slots * args.context
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
    parser.add_argument("--phases", default="cells,decode,prefill")
    parser.add_argument("--cells", default=(
        "gpt2m,trinity,trinity-ring,sarvam,granite,nemotron,falcon"))
    parser.add_argument("--live", default="1,10,25,100",
                        help="percent of the tables' entries live (cells)")
    parser.add_argument("--tree", default=None,
                        help="time this checkout's kernels (default: here)")
    parser.add_argument("--live-slots", type=int, default=40)
    parser.add_argument("--context", type=int, default=10000)
    parser.add_argument("--blocks", default="512,1024,2048")
    parser.add_argument("--lengths", default="4096,16384")
    parser.add_argument("--tiles", default="512,512;1024,1024;512,2048")
    args = parser.parse_args(argv)
    tree = os.path.abspath(args.tree or HERE)
    sys.path.insert(0, tree)
    import numpy as np

    import jax
    import jax.numpy as jnp

    args.cells = args.cells.split(",")
    args.live = [float(p) for p in args.live.split(",")]
    args.blocks = [int(b) for b in args.blocks.split(",")]
    args.lengths = [int(s) for s in args.lengths.split(",")]
    args.tiles = [tuple(int(t) for t in pair.split(","))
                  for pair in args.tiles.split(";") if pair]
    args.heads, args.rank, args.rope, args.width = 64, 512, 64, 640
    args.nope, args.vdim, args.slots, args.max_len = 128, 128, 48, 17408
    if args.tiny:
        args.heads, args.rank, args.rope, args.width = 8, 128, 64, 256
        args.nope, args.vdim, args.slots, args.max_len = 64, 64, 4, 64
        args.live_slots, args.context, args.reps = 3, 40, 1
        args.blocks, args.lengths, args.tiles = [16], [128], [(64, 64)]
        args.cells, args.live = ["tiny", "tiny-ring", "tiny-latent"], [25, 100]
    phases = {"cells": cell_rows, "decode": decode_rows,
              "prefill": prefill_rows}
    rows = []
    for phase in args.phases.split(","):
        for row in phases[phase](args, jax, jnp, np):
            row["tree"] = os.path.relpath(tree, HERE)
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
