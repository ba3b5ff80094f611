"""Read, over several seeds in one process, the numbers a cell's
``correct`` compares: the program's against the reference, and the
control's (the reference computed in a lower precision, put in the
program's place). A limit is set from these two columns and never from
a guess.

    python benchmarks/tools/readings.py --workload <cell> --seeds 1,2,3 \
        [--control fp8] [--seconds 6] [--skip-program]
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
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control", default=None)
    parser.add_argument("--seconds", type=float, default=3.0)
    args = parser.parse_args(argv)
    import run as bench_run
    from harness import manifest

    bench_run.configure_compile_cache()
    cell = manifest.Cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        result, _ = bench_run.run_cell(
            cell, seed=seed, seconds=args.seconds, trace=False,
            phases=bench_run.Phases(t0), control=args.control,
        )
        row = {"seed": seed, "device": result["device"]["kind"],
               "program": {k: v["value"] for k, v in result["compared"].items()}}
        if "control" in result:
            row["control"] = {k: v["value"]
                              for k, v in result["control"].items()}
        row["seconds"] = time.perf_counter() - t0
        print("READING " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
