"""Time the flash-attention kernels of a checkout, one kernel at a time.

    python scripts/flash_sweep.py --shapes train,trinity --out chiprun_out/sweep.json
    python scripts/flash_sweep.py --tree .proof/parent --shapes train ...

Per shape, length and kernel (``fwd``, ``dq``, ``dkv``), as JSON rows:
``ms``, the time of a call on the device (a jitted ``fori_loop`` chains
``--reps`` calls, each output feeding the next call's same-shaped input,
so nothing but the kernel and the head folds of a row-major
``[b, s, h, d]`` carry runs; host clock around the loop, best of three),
``ms_in_program``, the call WITH the layout copies its wrapper implies
where a model calls it: the same loop around the call jitted between a
projection-shaped producer (``x @ W`` for Q, K, V, ``g @ Wo^T`` for dO)
and consumer (``out @ Wo``; the weight-gradient matmuls ``x^T @ dq``),
LESS the same loop with the call replaced by a stand-in that moves
nothing (so the matmuls' own time cancels and XLA is free to hand each
operand over in the layout its matmul writes: what is left is the kernel
and every copy the wrapper's layout forces; ``--in-program 0`` skips it),
``layout``, which layout each operand crosses HBM in (the checkout's own
``_LAYOUTS``; a checkout from before PR 44 folds everything row-major but
V and the output of the forward), ``lower_s``, what ``jit(call).lower()``
takes in Python (tracing the kernel and lowering it from Pallas: paid at
EVERY start of a program that holds the kernel, before its compile-cache
key exists), and ``pairs_worked_pct``, the pairs the mask keeps over the
pairs the kernel's walk multiplies (``_visited_pairs``: counted from the
shapes, not measured; the most of its roofline the kernel can reach). A
tile the chip's compiler refuses is reported as ``error``.

The tile is the checkout's own (its ``_prepare``) unless ``--tiles`` gives
candidates: ``block_q,block_k`` for any checkout, or
``block_q,block_k,sub_q,sub_k`` (all three kernels) for one whose kernels
work a block in sub-tiles. ``--tree DIR`` times another checkout's
kernels (one from before the sub-tiles too), for a before and after on one
chip; ``--strips 1,2,4`` times a cut sub-tile worked in that many strips
(1: the generic masked body), by setting this script's copy of the
module's ``_STRIPS``, which the package itself offers no option for;
both go through ``_fwd_pallas`` / ``_bwd_pallas`` on 4-D operands,
and dq and dkv are told apart by which gradient is kept (XLA drops the
other kernel). The tile rule's table in ``ops/flash_attention.py`` is
read off such sweeps.

What this reads is a kernel under the causal mask (and a window) and
nothing else: a program that hands the kernels segment ids or
dropout runs other bodies (until PR 42 ``TransformerLM`` handed them its
causal mask as one segment id a token, and gpt2m-train's kernels read
1.1 to 1.5 times these: PERF.md §6), and until PR 44 ``ms`` left out the
seven head-fold copies a layer the program paid around the kernels (a
tenth of gpt2m-train's step), so a candidate from here is read in
``ms_in_program`` and confirmed by a traced run of the cell. On the CPU
(``JAX_PLATFORMS=cpu``) the same loops run in interpret
mode at ``--shapes tiny``: a rehearsal of the control flow, never a time.
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import itertools
import json
import os
import sys
import time

# name -> (batch, heads, kv heads, head_dim, window, lengths, kernels[,
# query length: one row against each length, not causal])
SHAPES = {
    # gpt2m-train: GPT-2 medium, 8 x 1,024 tokens, forward and backward.
    "train": (8, 16, 16, 64, None, (1024,), ("fwd", "dq", "dkv")),
    # trinity-mini-serve's prefill, window layers: 32 query over 4 K/V
    # heads of 128, a window of 2,048, every bucket of 512 up to 8,192.
    "trinity": (1, 32, 4, 128, 2048, tuple(range(512, 8193, 512)),
                ("fwd",)),
    # The same cell's full-attention layers (every 4th).
    "trinity-full": (1, 32, 4, 128, None, tuple(range(512, 8193, 512)),
                     ("fwd",)),
    # gpt2m-serve's prefill: its six buckets of 128.
    "gpt2-serve": (1, 16, 16, 64, None, (128, 256, 384, 512, 640, 768),
                   ("fwd",)),
    # generate()'s decode attend (GPT-2 small, batch 8): one query row
    # against the contiguous cache.
    "decode": (8, 12, 12, 64, None, (1024,), ("fwd",), 1),
    "tiny": (1, 2, 1, 32, 48, (256,), ("fwd", "dq", "dkv")),
    "tiny-causal": (1, 2, 1, 32, None, (256,), ("fwd", "dq", "dkv")),
}


def _inputs(jax, jnp, b, s, h, h_kv, d, dtype, q_len=None):
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    sq = q_len or s
    q, do = (jax.random.normal(k, (b, sq, h, d), jnp.float32).astype(dtype)
             for k in keys[:2])
    k, v = (jax.random.normal(k, (b, s, h_kv, d), jnp.float32).astype(dtype)
            for k in keys[2:])
    lse = jnp.full((b, h, sq), 7.0, jnp.float32)  # ~ log(s): p stays small
    return q, k, v, do, lse


def _call(fa, kind, tile_args, window, interpret, causal=True):
    """``carry -> carry`` for one kernel of the checkout ``fa``."""
    def run(q, k, v, do, lse):
        if kind == "fwd":
            out, _ = fa._fwd_pallas(q, k, v, None, None, None, causal, window,
                                    *tile_args["fwd"], interpret, 0.0)
            return out, k, v, do, lse
        dq, dk, dv = fa._bwd_pallas(
            q, k, v, None, None, None, q, lse, do, lse * 0.0, causal, window,
            *tile_args["bwd"], interpret, 0.0)
        if kind == "dq":
            return dq, k, v, do, lse
        return q, dk, dv, do, lse
    return run


# What a checkout from before PR 44 (no ``_LAYOUTS``) hands its kernels.
_LAYOUTS_BEFORE = {
    "fwd": "q k [bh,s,d]; v out [bh,d,s]",
    "dq": "q k v do dq [bh,s,d]",
    "dkv": "q k v do dk dv [bh,s,d]",
}


def _in_program(fa, jnp, kind, tile_args, window, interpret, causal, kernel):
    """``carry -> carry`` for one kernel of the checkout ``fa`` between a
    projection-shaped producer and consumer; with ``kernel`` False, the
    same with a stand-in for the call that moves nothing. The carry is
    ``(x, g, wq, wk, wv, wo)``: activations ``[b, s, h * d]`` and weights
    ``[h * d, heads, d]`` (``wo``: ``[h, d, h * d]``)."""
    def project(x, w):
        return jnp.einsum("bsm,mhd->bshd", x, w).astype(x.dtype)

    def run(x, g, wq, wk, wv, wo):
        q, k, v = project(x, wq), project(x, wk), project(x, wv)
        if kind == "fwd":
            out = q
            if kernel:
                out, _ = fa._fwd_pallas(q, k, v, None, None, None, causal,
                                        window, *tile_args["fwd"], interpret,
                                        0.0)
            y = jnp.einsum("bshd,hdm->bsm", out, wo).astype(x.dtype)
            return y, g, wq, wk, wv, wo
        do = jnp.einsum("bsm,hdm->bshd", g, wo).astype(x.dtype)
        dq, dk, dv = do, k, v
        if kernel:
            b, s, h, _ = q.shape
            lse = jnp.full((b, h, s), 7.0, jnp.float32)
            dq, dk, dv = fa._bwd_pallas(
                q, k, v, None, None, None, q, lse, do, lse * 0.0, causal,
                window, *tile_args["bwd"], interpret, 0.0)

        def weight_grad(dy):
            return jnp.einsum("bsm,bshd->mhd", x, dy).astype(x.dtype)

        if kind == "dq":
            return x, g, weight_grad(dq), wk, wv, wo
        return x, g, wq, weight_grad(dk), weight_grad(dv), wo
    return run


def _program_inputs(jax, jnp, b, s, h, h_kv, d, dtype):
    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    m = h * d

    def normal(key, shape, scale):
        return (scale * jax.random.normal(key, shape, jnp.float32)).astype(
            dtype)

    x, g = (normal(key, (b, s, m), 1.0) for key in keys[:2])
    wq = normal(keys[2], (m, h, d), m ** -0.5)
    wk, wv = (normal(key, (m, h_kv, d), m ** -0.5) for key in keys[3:5])
    return x, g, wq, wk, wv, normal(keys[5], (h, d, m), m ** -0.5)


def _tile_args(fa, data, tile, interpret):
    """How this checkout's ``_fwd_pallas`` / ``_bwd_pallas`` take a tile,
    and the tile as a row reports it."""
    subtiled = "tiles" in inspect.signature(fa._fwd_pallas).parameters
    if tile is None:
        auto = fa._prepare(*data[:3], None, None, interpret)[:-1]
        if subtiled:
            (tiles,) = auto
            return {"fwd": (tiles[0],), "bwd": (tiles,)}, {
                "fwd": tiles[0], "dq": tiles[1], "dkv": tiles[2]}
        return {"fwd": auto, "bwd": auto}, dict.fromkeys(
            ("fwd", "dq", "dkv"), tuple(auto))
    if subtiled:
        tile = tuple(tile) if len(tile) == 4 else (*tile, *tile)
        return {"fwd": (tile,), "bwd": ((tile,) * 3,)}, dict.fromkeys(
            ("fwd", "dq", "dkv"), tile)
    if len(tile) != 2:
        raise SystemExit("this checkout's kernels take block_q,block_k only")
    return {"fwd": tuple(tile), "bwd": tuple(tile)}, dict.fromkeys(
        ("fwd", "dq", "dkv"), tuple(tile))


def _time(jax, fn, args, reps, lower=True):
    """(seconds a call on the device, seconds ``lower()`` took)."""
    lower_s = None
    if lower:
        start = time.perf_counter()
        jax.jit(fn).lower(*args)
        lower_s = time.perf_counter() - start
    loop = jax.jit(lambda *a: jax.lax.fori_loop(
        0, reps, lambda _, carry: fn(*carry), a))
    jax.block_until_ready(loop(*args))  # compile, warm up
    best = float("inf")
    for _ in range(3):
        start = time.perf_counter()
        jax.block_until_ready(loop(*args))
        best = min(best, time.perf_counter() - start)
    return best / reps, lower_s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="train")
    ap.add_argument("--lengths", default=None,
                    help="only these of a shape's lengths, comma-separated")
    ap.add_argument("--kernels", default="fwd,dq,dkv")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--tiles", default=None,
                    help="candidates, ';'-separated: bq,bk or bq,bk,sq,sk; "
                    "0 stands for the whole length")
    ap.add_argument("--strips", default=None,
                    help="strip counts of a cut sub-tile, comma-separated "
                    "(default: the checkout's own)")
    ap.add_argument("--in-program", type=int, default=1,
                    help="0: skip ms_in_program (two more compiles a row)")
    ap.add_argument("--tree", default=None,
                    help="time this checkout's kernels (default: this one's)")
    ap.add_argument("--out", default="chiprun_out/flash_sweep.json")
    args = ap.parse_args(argv)

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tree = os.path.abspath(args.tree or here)
    sys.path.insert(0, tree)
    import jax
    import jax.numpy as jnp

    # ``fluxmpi_tpu.ops.flash_attention`` the attribute is the function.
    fa = importlib.import_module("fluxmpi_tpu.ops.flash_attention")
    interpret = jax.default_backend() != "tpu"
    device = jax.devices()[0]
    print(f"device: {device.platform} {device.device_kind}; tree: {tree}",
          flush=True)
    candidates = [None] if args.tiles is None else [
        tuple(int(x) for x in t.split(",")) for t in args.tiles.split(";")]
    only = args.lengths and {int(x) for x in args.lengths.split(",")}
    layouts = getattr(fa, "_LAYOUTS", _LAYOUTS_BEFORE)
    strip_counts = [getattr(fa, "_STRIPS", None)] if args.strips is None else [
        int(x) for x in args.strips.split(",")]
    rows = []
    for name in args.shapes.split(","):
        b, h, h_kv, d, window, lengths, kinds, *q_len = SHAPES[name]
        for s in lengths:
            if only and s not in only:
                continue
            data = _inputs(jax, jnp, b, s, h, h_kv, d, jnp.dtype(args.dtype),
                           *q_len)
            # One query row against a cache has no projection of its own
            # length to sit between.
            between = None if q_len or not args.in_program else (
                _program_inputs(jax, jnp, b, s, h, h_kv, d,
                                jnp.dtype(args.dtype)))
            for tile in candidates:
                if tile is not None:
                    tile = tuple(x or s for x in tile)  # 0: the length
                    if any(n % x for n, x in zip((data[0].shape[1], s),
                                                 tile)) or any(
                            blk % sub for blk, sub in zip(tile, tile[2:])):
                        continue
                tile_args, shown = _tile_args(fa, data, tile, interpret)
                for kind, strips in itertools.product(kinds, strip_counts):
                    if kind not in args.kernels.split(","):
                        continue
                    row = dict(shape=name, dtype=args.dtype, seq=s,
                               kernel=kind, tile=list(shown[kind]),
                               layout=layouts[kind],
                               tree=os.path.relpath(tree, here))
                    if strips is not None:
                        # Read while a kernel is traced: each row's
                        # ``jit`` is new, so each traces its own.
                        fa._STRIPS = row["strips"] = strips
                        kept, worked = fa._visited_pairs(
                            shown[kind], data[0].shape[1], s, not q_len,
                            window)
                        row["pairs_worked_pct"] = 100.0 * kept / worked
                    try:
                        sec, row["lower_s"] = _time(
                            jax, _call(fa, kind, tile_args, window,
                                       interpret, causal=not q_len),
                            data, args.reps)
                        row["ms"] = 1e3 * sec
                        if between is not None:
                            with_call, without = (_time(
                                jax, _in_program(fa, jnp, kind, tile_args,
                                                 window, interpret, True,
                                                 kernel),
                                between, args.reps, lower=False)[0]
                                for kernel in (True, False))
                            row["ms_in_program"] = 1e3 * (with_call - without)
                    except Exception as exc:  # the compiler's refusal
                        row["error"] = f"{type(exc).__name__}: " + str(
                            exc).strip().splitlines()[0][:160]
                    rows.append(row)
                    print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump({"device": device.device_kind, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
