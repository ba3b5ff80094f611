"""``correct`` is shown to fail: the control (the reference computed in
the precision below the configuration's) comes out as not correct, and
so does a run whose timed path is broken underneath. Rehearsal sizes on
the CPU; the chip-sized readings are in PERF.md."""

import time

import pytest

import run as bench_run
from harness import manifest


def _run(name, seed=2_147_483_777, **driver_args):
    cell = manifest.Cell(name)
    result, _ = bench_run.run_cell(
        cell, seed=seed, seconds=1.5, trace=False,
        phases=bench_run.Phases(time.perf_counter()), **driver_args,
    )
    return result


@pytest.mark.parametrize("name,control,seed", [
    # The training rehearsals state float32: the control is bfloat16.
    ("tiny-lm-train", "bf16", 2_147_483_777),
    ("tiny-resnet-train", "bf16", 2_147_483_777),
    # The serving rehearsal states bfloat16, as the real cell: fp8. With
    # so few served tokens not every seed holds a near-tie that the
    # lower precision flips; this one does.
    ("tiny-lm-serve", "fp8", 78),
])
def test_sound_run_is_correct_and_control_is_not(name, control, seed):
    result = _run(name, seed=seed, control=control)
    assert result["correct"] is True, result["compared"]
    control = result["control"]
    assert any(row["value"] > row["limit"] for row in control.values()), control
    assert result["device"]["platform"] == "cpu"
    assert all(k.startswith("cpu_rehearsal.") for k in result["metrics"])


@pytest.mark.parametrize("name,broken", [
    ("tiny-lm-train", "state_unchanged"),
    ("tiny-resnet-train", "state_unchanged"),
    ("tiny-lm-serve", "token_altered"),
])
def test_broken_timed_path_is_not_correct(name, broken):
    result = _run(name, broken=broken)
    assert result["correct"] is False, result["compared"]


def test_four_virtual_chips_sharded_state_is_correct():
    result = _run("tiny-lm-fsdp4-train")
    assert result["correct"] is True, result["compared"]
    assert result["device"]["count"] == 4
