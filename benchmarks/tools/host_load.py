"""How far does a busy host move a training cell's rate? One process,
one compiled step: for each fused-window width, short timed calls of
``train_loop`` alone and beside N busy processes (which never touch
JAX). A cell whose host blocks at every window boundary loses the host's
share of each window when the host's cores are shared; a wider window
has fewer boundaries.

    python benchmarks/tools/host_load.py --workload gpt2m-train \
        --widths 6,3 --hogs 0,13,26,0 --steps 48 --seed 5
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--widths", required=True)
    parser.add_argument("--hogs", default="0,13,26,0")
    parser.add_argument("--steps", type=int, default=48)
    parser.add_argument("--seed", type=int, default=5)
    args = parser.parse_args(argv)
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import fluxmpi_tpu as fm
    import run as bench_run
    from drivers.train import seed_key
    from fluxmpi_tpu.data import (
        ArrayDataset,
        DistributedDataContainer,
        DistributedDataLoader,
    )
    from fluxmpi_tpu.parallel import TrainState, make_train_step, train_loop
    from harness import manifest

    bench_run.configure_compile_cache()
    cell = manifest.Cell(args.workload)
    spec, cfg, prog = cell.spec, cell.config, cell.program
    fm.init(devices=jax.devices()[:1], compileplane=True)
    loader = DistributedDataLoader(
        DistributedDataContainer(ArrayDataset(
            prog.make_dataset(cfg, spec["data"], args.seed))),
        spec["data"]["rows_per_step"], **spec.get("loader", {}),
    )
    model = prog.build_model(cfg, spec.get("attention", "flash"))
    optimizer = prog.make_optimizer(spec["optimizer"])

    def make_state(k):
        variables, model_state = prog.to_program(
            cell.reference.make_weights(cfg, k), cfg)
        return TrainState.create(variables, optimizer, model_state)

    state = jax.jit(make_state, out_shardings=NamedSharding(
        fm.global_mesh(), P()))(seed_key(args.seed))
    step = make_train_step(prog.make_loss(model), optimizer)
    items = spec["data"]["rows_per_step"] * prog.items_per_row(spec["data"])
    for width in (int(w) for w in args.widths.split(",")):
        t0 = time.perf_counter()
        state, _ = train_loop(step, state, loader, steps=width,
                              flush_every=width)
        print(f"width {width}: first call {time.perf_counter() - t0:.1f} s",
              flush=True)
        for n in (int(h) for h in args.hogs.split(",")):
            hogs = [subprocess.Popen([sys.executable, "-c", "while True: pass"])
                    for _ in range(n)]
            try:
                time.sleep(1.0 if n else 0.0)
                t0 = time.perf_counter()
                state, summary = train_loop(step, state, loader,
                                            steps=args.steps,
                                            flush_every=width)
                elapsed = time.perf_counter() - t0
            finally:
                for h in hogs:
                    h.kill()
                for h in hogs:
                    h.wait()
            print("LOAD " + json.dumps({
                "width": width, "hogs": n, "updates": summary["updates"],
                "dispatches": summary["dispatches"], "seconds": elapsed,
                "items_per_s": summary["updates"] * items / elapsed,
            }), flush=True)
    print("cores", os.cpu_count(), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
