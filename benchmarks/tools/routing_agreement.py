"""How often the served model and the plain reference choose the same
experts, and what the paged cache costs beside the program's own dense
forward: two readings for PERF.md, not limits.

Serves a few requests of a cell's mix through the engine (inline, no
clock), then for each finished request

- runs the PROGRAM's plain forward (no cache, the same dtype) over the
  prompt with its served tokens and reads, at a few served positions,
  the gap by which the served token's logit lies below that forward's
  best (``dense_gap``: what prefill-then-decode through the paged pool
  differs by from one dense pass in the same precision), and the experts
  every token chose in every expert layer;
- asks the reference (float32) for the experts it chooses for the same
  tokens, and prints the share of (token, layer) pairs whose chosen sets
  are the same (``same_set_share``) and the mean count of experts that
  differ in a pair that is not.

    python benchmarks/tools/routing_agreement.py --workload \
        trinity-mini-serve --seed 5 --requests 4 --positions 8
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=5)
    parser.add_argument("--requests", type=int, default=4)
    parser.add_argument("--positions", type=int, default=8)
    args = parser.parse_args(argv)
    import jax
    import jax.numpy as jnp

    import fluxmpi_tpu as fm
    import run as bench_run
    from drivers.train import seed_key
    from harness import manifest

    bench_run.configure_compile_cache()
    cell = manifest.Cell(args.workload)
    cfg, driver = cell.config, cell.driver
    fm.init(devices=jax.devices()[:1], compileplane=True)
    key = seed_key(args.seed)
    engine = driver.build_engine(cell, key, bench_run.Phases(time.perf_counter()))
    window = [r for phase, _, reqs in driver.make_plan(cell, args.seed, 30.0)
              if phase == "window" for r in reqs]
    # The longest request and a spread of the others.
    window.sort(key=lambda r: len(r["prompt"]))
    picks = [window[-1]] + window[:: max(1, len(window) // (args.requests - 1))][
        : args.requests - 1]
    handles = [engine.submit(r["prompt"], r["max_new_tokens"]) for r in picks]
    engine.run()
    model, params = engine.model, engine.params
    engine.close()
    del engine

    expert_layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    # The engine's model runs the paged path through attention_fn; here
    # its own attention (the flash kernels on the chip) runs over the
    # whole sequence.
    dense = jax.jit(
        lambda p, t, at: model.apply(
            p, t[None], head_at=at[None],
            capture_intermediates=lambda _, method: method == "route",
            mutable=["intermediates"],
        )
    )
    rows = []
    for req in handles:
        plen = len(req.prompt)
        full = np.concatenate([req.prompt, req.tokens]).astype(np.int32)
        block = cell.spec["engine"]["block_size"]
        padded = np.pad(full, (0, (-len(full)) % block))
        served = np.linspace(0, len(req.tokens) - 1, args.positions).astype(int)
        gaps, routes = [], None
        for i in sorted(set(served.tolist())):
            logits, state = dense(params, jnp.asarray(padded),
                                  jnp.int32(plen - 1 + i))
            logits = np.asarray(logits[0])
            gaps.append(float(logits.max() - logits[req.tokens[i]]))
            if routes is None:
                leaves = jax.tree_util.tree_flatten_with_path(
                    state["intermediates"])[0]
                picks_by_layer = sorted(
                    (jax.tree_util.keystr(path), np.asarray(leaf))
                    for path, leaf in leaves
                    if "route" in jax.tree_util.keystr(path)
                    and leaf.dtype == jnp.int32
                )
                routes = [r[: len(full)] for _, r in picks_by_layer]
        rows.append({"full": full, "dense_gap": gaps, "routes": routes,
                     "prompt_tokens": plen})
    del params, dense
    jax.clear_caches()

    same = differ = pairs = 0
    for row in rows:
        chosen = np.asarray(cell.reference.expert_choices(cfg, key, row["full"]))
        assert chosen.shape[0] == expert_layers == len(row["routes"])
        for layer, picked in enumerate(row["routes"]):
            mine = np.zeros_like(chosen[layer])
            np.put_along_axis(mine, picked, True, axis=1)
            off = (mine != chosen[layer]).sum(axis=1) // 2
            same += int((off == 0).sum())
            differ += int(off.sum())
            pairs += off.size
    gaps = [g for row in rows for g in row["dense_gap"]]
    print(json.dumps({
        "requests": [{"prompt_tokens": r["prompt_tokens"],
                      "tokens": int(len(r["full"]))} for r in rows],
        "same_set_share": same / pairs,
        "experts_off_in_a_differing_pair": differ / max(1, pairs - same),
        "token_layer_pairs": pairs,
        "dense_gap_mean": float(np.mean(gaps)),
        "dense_gap_widest": float(np.max(gaps)),
        "dense_positions": len(gaps),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
