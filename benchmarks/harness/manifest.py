"""Find a cell, its configuration and its metrics by name.

Everything that belongs to one cell, one configuration or one per-layer
metric sits in a file of its own, found by the name ``BENCHMARK.json``
(or, for a rehearsal cell, the cell's file) gives it. Adding a cell, a
configuration or a metric adds files and manifest entries and edits no
file that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(path: str):
    """Import the Python file at ``path`` (relative to the checkout)
    under a name of its own; file names may hold dots and dashes."""
    full = path if os.path.isabs(path) else os.path.join(ROOT, path)
    name = "bench_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(full, ROOT)
    )
    spec = importlib.util.spec_from_file_location(name, full)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(full)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_manifest() -> dict:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"))


class Cell:
    """One cell: its file, its configuration's file and the two Python
    files the configuration names (``program``: how the system under
    test is built through the program's entry points; ``reference``:
    the plain reference and the seeded weights)."""

    def __init__(self, name: str):
        self.name = name
        self.spec = _load_json(
            os.path.join(BENCH_DIR, "workloads", f"{name}.json")
        )
        self.rehearsal = bool(self.spec.get("rehearsal", False))
        # A cell that is not (yet) in the manifest reports the metrics of
        # the cell it stands for: rehearsal cells, and a cell whose files
        # wait for the PR that proves it.
        manifest = load_manifest()
        in_manifest = any(w["name"] == name for w in manifest["workloads"])
        self.manifest_name = (
            name if in_manifest else self.spec.get("stands_for", name)
        )
        entry = next(
            (w for w in manifest["workloads"]
             if w["name"] == self.manifest_name), None,
        )
        if entry is None:
            raise KeyError(
                f"{self.manifest_name!r} is not a workload of BENCHMARK.json"
            )
        self.chips = int(self.spec.get("chips", entry["chips"]))
        config_name = self.spec.get("config", entry["config"])
        config_file = next(
            (c["file"] for c in manifest["configs"]
             if c["name"] == config_name),
            os.path.join("benchmarks", "configs", f"{config_name}.json"),
        )
        self.config_name = config_name
        self.config = _load_json(os.path.join(ROOT, config_file))
        self.program = load_module(self.config["program"])
        self.reference = load_module(self.config["reference"])
        self.driver = load_module(
            os.path.join("benchmarks", "drivers", f"{self.spec['driver']}.py")
        )
        self._manifest = manifest

    def _reported(self, metric: dict) -> bool:
        cells = metric.get("workloads")
        return cells is None or self.manifest_name in cells

    def end_to_end(self) -> list[dict]:
        return [m for m in self._manifest["end_to_end"] if self._reported(m)]

    def per_layer(self) -> list[dict]:
        return [m for m in self._manifest["per_layer"] if self._reported(m)]


def read_per_layer(metric: dict, ctx: dict):
    """Run the metric's reader (``benchmarks/metrics/<name>.json`` names
    it and its arguments). A reader that finds nothing to read returns
    None and the metric is left out of the line."""
    spec = _load_json(
        os.path.join(BENCH_DIR, "metrics", f"{metric['name']}.json")
    )
    reader = load_module(
        os.path.join("benchmarks", "metrics", "readers",
                     f"{spec['reader']}.py")
    )
    return reader.read(ctx, **spec.get("args", {}))
