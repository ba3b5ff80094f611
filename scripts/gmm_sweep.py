"""Time the routed experts' grouped matmul alone: the Pallas kernel of
``ops/grouped_matmul.py`` at candidate tiles against ``jax.lax.ragged_dot``.

    python scripts/gmm_sweep.py --shapes decode,prefill --out chiprun_out/gmm.json
    python scripts/gmm_sweep.py --shapes decode --tiles "512,128,1024;512,32,512"

Per shape (``decode``: 64 slots x top-8 = 512 rows of which 48 slots'
are live; ``prefill``: every bucket of 512 up to 8,704 tokens x 8; both
over 128 experts, ``up`` = ``[2048, 1024]`` and ``down`` = ``[1024,
2048]``, routing drawn uniformly without repeats from ``--seed``) and
per candidate, as JSON rows: ``ms``, the device time of a call (a jitted
``fori_loop`` chains ``--reps`` calls through one element of the result;
host clock around the loop, best of three), ``lower_s`` (what
``jit(call).lower()`` takes in Python, paid at every start of a program
that holds the call), ``gbps`` (the touched experts' bytes over ``ms``),
``visits`` and ``touched`` (``weight_visits``), ``max_err`` against
``ragged_dot`` on the same operands over the live rows (the rows past
the last group are unspecified: the kernel leaves a row tile past the
groups unwritten, XLA's op what it finds there). ``--tiles`` is
``tile_rows,sub_rows,tile_n;...``; without it the module's own rule. A tile the chip's
compiler refuses is reported as ``error``. The rule's constants in
``ops/grouped_matmul.py`` are read off such sweeps.

A kernel ALONE: a candidate from here is confirmed by a traced run of
the cell. On the CPU (``JAX_PLATFORMS=cpu``) only ``--shapes tiny``
(interpret mode: a rehearsal of the control flow, never a time).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GROUPS, TOP_K, HIDDEN, WIDTH = 128, 8, 2048, 1024
# name -> [(label, rows, live rows)]
SHAPES = {
    "decode": [("decode", 64 * TOP_K, 48 * TOP_K)],
    "prefill": [(f"prefill_{b}", b * TOP_K, b * TOP_K)
                for b in range(512, 8705, 512)],
    "prefill-few": [(f"prefill_{b}", b * TOP_K, b * TOP_K)
                    for b in (512, 1024, 2560, 4096, 8704)],
    "tiny": [("tiny", 64, 40)],
}


def _sizes(np, rng, live, groups, top_k):
    """Each of ``live // top_k`` tokens picks ``top_k`` distinct groups."""
    counts = np.zeros((groups,), np.int32)
    for _ in range(live // top_k):
        counts[rng.choice(groups, size=top_k, replace=False)] += 1
    return counts


def _time(jax, jnp, fn, x, w, sizes, reps):
    """(seconds a call on the device, seconds ``lower()`` took, the last
    call's result). The operands are ARGUMENTS of the loop: closed over,
    half a gigabyte of weights becomes a constant of the program and
    compiling it takes minutes."""
    start = time.perf_counter()
    jax.jit(fn).lower(x, w, sizes)
    lower_s = time.perf_counter() - start

    def body(_, carry):
        x, w, sizes, _ = carry
        out = fn(x, w, sizes)
        sizes = sizes + jnp.where(out[0, 0] > 1e30, 1, 0).astype(sizes.dtype)
        return x, w, sizes, out

    @jax.jit
    def loop(x, w, sizes):
        out = jnp.zeros((x.shape[0], w.shape[2]), jnp.float32)
        return jax.lax.fori_loop(0, reps, body, (x, w, sizes, out))[3]

    got = jax.block_until_ready(loop(x, w, sizes))
    best = float("inf")
    for _ in range(3):
        start = time.perf_counter()
        jax.block_until_ready(loop(x, w, sizes))
        best = min(best, time.perf_counter() - start)
    return best / reps, lower_s, got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="decode")
    ap.add_argument("--sides", default="up,down")
    ap.add_argument("--tiles", default=None)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from fluxmpi_tpu.ops import grouped_matmul as G

    on_tpu = jax.default_backend() == "tpu"
    tiny = args.shapes == "tiny"
    if not on_tpu and not tiny:
        raise SystemExit("no TPU: only --shapes tiny runs here")
    groups, top_k = (4, 2) if tiny else (GROUPS, TOP_K)
    hidden, width = (128, 256) if tiny else (HIDDEN, WIDTH)
    sides = {"up": (hidden, width), "down": (width, hidden)}
    rng = np.random.default_rng(args.seed)
    rows_out = []
    for shape in args.shapes.split(","):
        for label, rows, live in SHAPES[shape]:
            counts = _sizes(np, rng, live, groups, top_k)
            sizes = jnp.asarray(counts)
            for side in args.sides.split(","):
                k, n = sides[side]
                key = jax.random.PRNGKey(args.seed)
                x = jax.random.normal(key, (rows, k), jnp.bfloat16)
                w = jax.random.normal(key, (groups, k, n), jnp.bfloat16) * 0.02
                want = jax.lax.ragged_dot(
                    x, w, sizes, preferred_element_type=jnp.float32)
                touched = int(np.count_nonzero(counts))
                if args.tiles:
                    tiles = [tuple(int(v) for v in t.split(","))
                             for t in args.tiles.split(";")]
                else:
                    padded = G._padded(rows)
                    tiles = [G._tile_rule(padded, k, n, 2)]
                cands = [("ragged_dot", None, lambda x, w, s: jax.lax.ragged_dot(
                    x, w, s, preferred_element_type=jnp.float32))]
                for t in tiles:
                    if rows % t[0] or n % t[2]:
                        continue
                    cands.append(("kernel", t, lambda x, w, s, t=t: G._gmm(
                        x, w, s, tiles=t, interpret=not on_tpu)))
                for name, t, fn in cands:
                    row = {"shape": label, "side": side, "rows": rows,
                           "what": name, "tiles": t, "touched": touched}
                    try:
                        ms, lower_s, got = _time(
                            jax, jnp, fn, x, w, sizes,
                            1 if tiny else args.reps)
                        live_mask = (jnp.arange(rows) < live)[:, None]
                        row.update(
                            ms=ms * 1e3, lower_s=lower_s,
                            gbps=touched * k * n * 2 / ms / 1e9,
                            max_err=float(jnp.max(jnp.abs(
                                jnp.where(live_mask, got - want, 0.0)))),
                        )
                        if name == "kernel":
                            row["visits"] = G.weight_visits(counts, t[0])
                    except Exception as exc:  # the compiler's refusal
                        row["error"] = repr(exc)[:300]
                    print(json.dumps(row), flush=True)
                    rows_out.append(row)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(rows_out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
