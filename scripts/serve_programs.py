#!/usr/bin/env python
"""Are the serving engine's programs the same in two checkouts?

For every ``tiny-*-serve`` cell under ``benchmarks/workloads`` (read, not
edited) this builds the engine the way the benchmark's driver does (the
cell's program file, its ``engine`` geometry, its ``attention``), lowers
the decode program and EVERY prefill bucket for the CPU (nothing runs,
weights are shapes), and prints one JSON row a program: the histogram of
its lowered text (operation -> count) and the text's SHA-256. Run it on
two trees and compare:

    JAX_PLATFORMS=cpu python scripts/serve_programs.py --out a.json
    JAX_PLATFORMS=cpu python scripts/serve_programs.py \\
        --tree /path/to/the/other/checkout --out b.json
    python scripts/serve_programs.py --compare a.json b.json

Equal hashes: the same operations in the same order on the same operands.
Equal histograms alone: the same operations, traced in another order. It
leans on nothing of the engine but its two jitted steps and the cache's
``kinds`` / ``k_pools`` / ``v_pools``, so it runs on a tree from before a
refactor of what lies between.
"""

from __future__ import annotations

import argparse
import collections
import glob
import hashlib
import json
import os
import re
import sys

_OPERATION = re.compile(r'(?:=\s|^\s*)"?([a-z_]+\.[a-z_0-9.]+)"?[ (<]', re.M)


def lowered_programs(tree: str, cells: list[str], modes: list[str]):
    """``{"<cell>/<mode>/<program>": lowered text}`` for the engine of
    every cell in ``cells``, built from ``tree``."""
    sys.path[:0] = [tree, os.path.join(tree, "benchmarks")]
    import jax
    import jax.numpy as jnp

    from fluxmpi_tpu.serving import InferenceEngine
    from harness import manifest

    def zeros(*shape):
        return jnp.zeros(shape, jnp.int32)

    programs = {}
    for name in cells:
        cell = manifest.Cell(name)
        cfg, spec = cell.config, cell.spec
        params = jax.eval_shape(
            lambda key: cell.program.to_program(
                cell.reference.make_weights(cfg, key), cfg)[0],
            jax.random.PRNGKey(0))
        for mode in modes:
            engine = InferenceEngine(
                cell.program.build_model(cfg, "naive"), params,
                attention=mode, check_memory=False, **spec["engine"])
            try:
                cache, slots = engine.cache, engine.slots
                pools = (params, cache.k_pools, cache.v_pools)
                prev = jax.eval_shape(
                    engine._decode_step, *pools,
                    tuple(zeros(slots, k.entries) for k in cache.kinds),
                    zeros(slots), zeros(slots), zeros(slots),
                    jnp.zeros((slots,), bool))[0]
                lowered = {"decode": engine._decode_step.lower(
                    *pools,
                    tuple(zeros(slots, k.entries) for k in cache.kinds),
                    zeros(slots), zeros(slots), zeros(*prev.shape),
                    jnp.zeros((slots,), bool))}
                for bucket in range(engine.block_size, engine.max_len + 1,
                                    engine.block_size):
                    lowered[f"prefill_{bucket}"] = engine._prefill_step(
                        bucket).lower(
                        *pools, zeros(bucket), jnp.int32(1),
                        tuple(zeros(k.entries) for k in cache.kinds))
                for program, low in lowered.items():
                    programs[f"{name}/{mode}/{program}"] = low.as_text()
            finally:
                engine.close()
    return programs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tree", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--cells", default="",
                    help="comma-separated (default: every tiny-*-serve)")
    ap.add_argument("--modes", default="flash,naive")
    ap.add_argument("--out", default="-")
    ap.add_argument("--compare", nargs=2, metavar=("A", "B"))
    args = ap.parse_args()
    if args.compare:
        a, b = (json.load(open(path)) for path in args.compare)
        differ = sorted(k for k in a.keys() | b.keys()
                        if a.get(k, {}).get("ops") != b.get(k, {}).get("ops"))
        reordered = sorted(k for k in a.keys() & b.keys() if k not in differ
                           and a[k]["sha256"] != b[k]["sha256"])
        print(json.dumps({
            "programs": len(a.keys() | b.keys()),
            "histograms_differ": differ, "same_ops_other_text": reordered,
        }, indent=1))
        return 1 if differ else 0
    tree = os.path.abspath(args.tree)
    cells = [c for c in args.cells.split(",") if c] or sorted(
        os.path.basename(path)[:-len(".json")] for path in glob.glob(
            os.path.join(tree, "benchmarks", "workloads", "tiny-*-serve.json")))
    rows = {
        name: {"ops": dict(sorted(collections.Counter(
                   _OPERATION.findall(text)).items())),
               "sha256": hashlib.sha256(text.encode()).hexdigest()}
        for name, text in lowered_programs(
            tree, cells, args.modes.split(",")).items()
    }
    out = json.dumps(rows, indent=1)
    if args.out == "-":
        print(out)
    else:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(out)
        print(f"{len(rows)} programs -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
