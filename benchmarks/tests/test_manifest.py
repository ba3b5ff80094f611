"""The manifest is consistent with the files the harness finds by name,
and the command refuses to measure without a chip."""

import json
import os
import re
import subprocess
import sys

from harness import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_name_resolves_to_files():
    m = manifest.load_manifest()
    configs = {c["name"] for c in m["configs"]}
    for c in m["configs"]:
        assert NAME.match(c["name"]) and os.path.isfile(
            os.path.join(manifest.ROOT, c["file"]))
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            cfg = json.load(f)
        for key in ("program", "reference"):
            assert os.path.isfile(os.path.join(manifest.ROOT, cfg[key]))
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    cells = {w["name"] for w in m["workloads"]}
    for w in m["workloads"]:
        assert w["config"] in configs and NAME.match(w["traffic"])
        assert os.path.isfile(os.path.join(
            manifest.BENCH_DIR, "workloads", f"{w['name']}.json"))
    e2e = {x["name"] for x in m["end_to_end"]}
    assert "setup_s" in e2e
    for metric in m["per_layer"]:
        assert metric["moves"] in e2e and set(metric["workloads"]) <= cells
        with open(os.path.join(manifest.BENCH_DIR, "metrics",
                               f"{metric['name']}.json")) as f:
            spec = json.load(f)
        assert os.path.isfile(os.path.join(
            manifest.BENCH_DIR, "metrics", "readers", f"{spec['reader']}.py"))
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(m["workloads"]) // 4)


def test_a_real_cell_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", "gpt2m-train", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
