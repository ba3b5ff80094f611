"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell on the machine it is started on (never the CPU: without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result; a rehearsal cell under ``JAX_PLATFORMS=cpu`` is the
one exception and reports device ``cpu`` and no device metric). Prints
where set-up went on an earlier line and, as the last line of standard
output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and last the
numbers compared, each beside its limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for path in (ROOT, BENCH_DIR):
    if path not in sys.path:
        sys.path.insert(0, path)


class Phases:
    """Where set-up went: seconds between marks, from process start."""

    def __init__(self, start: float):
        self.start = start
        self.last = start
        self.spans: dict[str, float] = {}

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.spans[name] = self.spans.get(name, 0.0) + now - self.last
        self.last = now

    def since_start(self) -> float:
        return time.perf_counter() - self.start


def configure_compile_cache() -> None:
    """The program places the cache (``JAX_COMPILATION_CACHE_DIR`` where
    the machine sets it, else ``<checkout>/.jax_cache``); every program
    goes in, however quickly it compiled."""
    import jax

    from fluxmpi_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def run_cell(cell, *, seed: int, seconds: float, trace: bool,
             phases: Phases, **driver_args):
    """Everything of a run after the look for a chip: drive the cell,
    read its metrics, decide ``correct``. Returns the result object and
    the comparison behind its ``correct``."""
    from harness import device as device_mod
    from harness import manifest, trace as trace_mod

    out = cell.driver.run(cell, seed=seed, seconds=seconds, trace=trace,
                          phases=phases, **driver_args)
    print(json.dumps({"setup_breakdown_s": phases.spans,
                      "reference_s": out["reference_s"]}), flush=True)
    values, device = out["values"], dict(out["device"])
    metrics: dict = {}
    if trace:
        ctx = dict(out)
        ctx["peaks"] = (device_mod.peaks(device["kind"])
                        if device["platform"] == "tpu" else None)
        for metric in cell.per_layer():
            value = manifest.read_per_layer(metric, ctx)
            if value is not None:
                metrics[metric["name"]] = {"value": float(value),
                                           "unit": metric["unit"]}
    else:
        for metric in cell.end_to_end():
            metrics[metric["name"]] = {"value": float(values[metric["name"]]),
                                       "unit": metric["unit"]}
    if device["platform"] != "tpu":
        # A number from a CPU run never goes under a device metric's name.
        metrics = {f"cpu_rehearsal.{k}": v for k, v in metrics.items()}
    result = {
        "correct": out["comparison"].correct,
        "attempted": out["attempted"], "failed": out["failed"],
        "metrics": metrics, "device": device,
    }
    reduced = out.get("trace")
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = trace_mod.breakdown(reduced)
    result["compared"] = out["comparison"].as_dict()
    if "control" in out:
        result["control"] = out["control"].as_dict()
    return result, out["comparison"]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    phases = Phases(T_START)

    from harness import manifest

    cell = manifest.Cell(args.workload)
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    rehearsal = (cell.rehearsal and platform == "cpu"
                 and os.environ.get("JAX_PLATFORMS") == "cpu")
    if platform != "tpu" and not rehearsal:
        print(f"no accelerator: jax.devices()[0].platform == {platform!r}",
              file=sys.stderr)
        return 3
    if cell.rehearsal and not rehearsal:
        print("a rehearsal cell runs only under JAX_PLATFORMS=cpu",
              file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"cell {cell.name} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 3

    configure_compile_cache()
    phases.mark("import")

    result, comparison = run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        phases=phases,
    )
    sys.stdout.flush()
    comparison.print_last_lines()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
