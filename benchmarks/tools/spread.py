"""Measure a cell's spreads as the driver's check sees them: one
machine, fresh processes, a warm compile cache.

    python benchmarks/tools/spread.py --workload <cell> [--sets 2 --runs 6]

One run first fills the compile cache and is left out. Then ``sets``
sets of ``runs`` runs, each a new process of the cell's own command at
the manifest's ``run_seconds``, the same seeds in every set. For each
end-to-end metric and set: the median and the spread (the distance
between the first and third quartile, ``statistics.quantiles(n=4)``,
as a share of the median). This process never touches JAX: the chip
belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one_run(command, cell, seed, seconds, trace=0):
    proc = subprocess.run(
        [*command, "--workload", cell, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True, check=False,
    )
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        sys.stderr.write(proc.stderr[-4000:])
        raise RuntimeError(f"run failed: rc={proc.returncode}")
    return json.loads(lines[-1]), lines[:-1]


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--seed0", type=int, default=2_147_500_000)
    parser.add_argument("--seconds", type=float)
    parser.add_argument("--out")
    args = parser.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    command = manifest["command"]
    seconds = args.seconds or manifest["run_seconds"]
    first, _ = one_run(command, args.workload, args.seed0 - 1, seconds)
    print("cache-filling run (left out):", json.dumps(first["metrics"]),
          flush=True)
    seeds = [args.seed0 + 7919 * i for i in range(args.runs)]
    report = {"workload": args.workload, "seconds": seconds, "seeds": seeds,
              "first_run": first, "sets": []}
    for s in range(args.sets):
        runs = []
        for seed in seeds:
            result, earlier = one_run(command, args.workload, seed, seconds)
            runs.append(result)
            flat = {k: v["value"] for k, v in result["metrics"].items()}
            print(f"set {s} seed {seed} correct={result['correct']} "
                  f"{json.dumps(flat)} {json.dumps(result['compared'])} "
                  f"{earlier[-1] if earlier else ''}", flush=True)
        summary = {}
        for name in runs[0]["metrics"]:
            values = [r["metrics"][name]["value"] for r in runs]
            summary[name] = {"median": statistics.median(values),
                             "spread": spread(values), "values": values}
        report["sets"].append({"runs": runs, "summary": summary})
    print(f"\n{'metric':28s} " + " ".join(
        f"{'median' + str(i):>14s} {'spread' + str(i):>9s}"
        for i in range(args.sets)))
    for name in report["sets"][0]["summary"]:
        print(f"{name:28s} " + " ".join(
            f"{st['summary'][name]['median']:14.4f} "
            f"{100 * st['summary'][name]['spread']:8.3f}%"
            for st in report["sets"]))
    print("all correct:", all(r["correct"] for st in report["sets"]
                              for r in st["runs"]))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(report, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
