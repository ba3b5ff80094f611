"""Find the knee of a serving cell once, by a sweep on the chip: the
highest offered rate with no growing backlog. One process, one engine,
several rates; the cell's file then fixes its rate at four fifths of it.

    python benchmarks/tools/sweep_knee.py --workload gpt2m-serve \
        --rates 2,3,4,5,6 --seconds 20 --seed 77
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--seed", type=int, default=77)
    args = parser.parse_args(argv)
    import jax

    import fluxmpi_tpu as fm
    import run as bench_run
    from harness import manifest

    bench_run.configure_compile_cache()
    cell = manifest.Cell(args.workload)
    driver = cell.driver
    rates = [float(r) for r in args.rates.split(",")]
    fm.init(devices=jax.devices()[:1], compileplane=True)
    phases = bench_run.Phases(time.perf_counter())
    from drivers.train import seed_key

    engine = driver.build_engine(cell, seed_key(args.seed), phases)
    plans = {}
    for rate in rates:
        cell.spec["traffic"]["rate_per_s"] = rate
        plans[rate] = driver.make_plan(cell, args.seed, args.seconds)
    engine.warmup(prompt_lengths=tuple(sorted(
        {len(r["prompt"]) for plan in plans.values()
         for _, _, reqs in plan for r in reqs}
    )))
    phases.mark("compile_or_cache_load")
    print("setup", json.dumps(phases.spans), flush=True)
    for rate in rates:
        cell.spec["traffic"]["rate_per_s"] = rate
        out = driver._drive(cell, engine, plans[rate], args.seconds, False,
                            bench_run.Phases(time.perf_counter()), None)
        backlog = engine.queue_depth
        active = engine.active_count
        v = out["values"]
        print("RATE " + json.dumps({
            "rate_per_s": rate, "queue_at_stop": backlog,
            "active_at_stop": active,
            "serve_tokens_per_s": v["serve_tokens_per_s"],
            "ttft_p50_ms": v["ttft_p50_ms"], "ttft_p90_ms": v["ttft_p90_ms"],
            "itl_p50_ms": v["itl_p50_ms"], "itl_p95_ms": v["itl_p95_ms"],
            "queue_wait_p90_ms": v["queue_wait_p90_ms"],
            "occupancy_pct": v["decode_occupancy_pct"],
            "failed": out["failed"], "due": out["attempted"],
        }), flush=True)
        engine.run()  # drain what is left before the next rate
    engine.close()
    print("peak_bytes", max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:1]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
