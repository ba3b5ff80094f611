"""chip_smoke.py off the chip: the script must refuse the CPU, and its
phase functions — which take their model configuration as an argument —
must pass at tiny sizes on the 8-device CPU mesh (kernels interpreted),
so that a chip call is never spent on a wrong path, argument or
assertion. The script itself has no small-size or CPU switch."""

import json
import os
import subprocess
import sys

import jax

import chip_smoke

# Two layers at d_model 64 (GPT-2's shape, not its size).
TINY_LM = {"vocab_size": 256, "max_len": 32, "num_layers": 2,
           "d_model": 64, "num_heads": 4, "d_ff": 128, "ln_eps": 1e-5}
# The smallest ResNet of the zoo, at 32x32: a ResNet-50 compile on
# XLA:CPU takes minutes.
TINY_RESNET = {"model": "ResNet18", "image": 32, "classes": 10, "batch": 16}


def test_script_refuses_the_cpu():
    here = os.path.dirname(os.path.abspath(chip_smoke.__file__))
    proc = subprocess.run(
        [sys.executable, os.path.join(here, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=here,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "no TPU" in last["reason"]


def test_failed_phase_ends_nonzero(monkeypatch, capsys):
    """Past the platform check, a phase that raises ends the run with
    ``"ok": false`` and exit code 1 — never caught and dropped."""
    monkeypatch.setattr(
        chip_smoke, "_device_report",
        lambda: {"platform": "tpu", "kind": "fake", "count": 1},
    )

    def boom(devices=None):
        raise AssertionError("deliberate")

    monkeypatch.setattr(chip_smoke, "phase_device", boom)
    assert chip_smoke.main(["--phase", "device"]) == 1
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False
    assert "phase device failed: AssertionError: deliberate" in last["reason"]


def test_phase_device(world, own_runtime):
    with own_runtime():
        rec = chip_smoke.phase_device(jax.devices()[:1])
    assert rec["platform"] == "cpu" and rec["device_count"] == 8
    assert rec["mesh"] == {"dp": 1}
    assert rec["versions"]["jax"] == jax.__version__
    assert isinstance(rec["native_loader"], bool)


def test_phase_kernels_interpreted(world):
    rec = chip_smoke.phase_kernels(interpret=True, quick=True)
    assert rec["cases"] == 1 and len(rec["asserted"]) == 1


def test_phase_train_lm(world, own_runtime):
    with own_runtime():
        rec = chip_smoke.phase_train_lm(TINY_LM, seed=0, compiled=False)
    assert rec["updates"] == 8
    assert rec["fused_window"] == 2 and rec["device_gather"] is True
    assert rec["tpu_custom_calls"] == 0  # interpreted off the chip
    assert rec["last_loss"] < rec["first_loss"]
    assert any("zero compiles" in a for a in rec["asserted"])


def test_phase_serve_lm(world, own_runtime):
    with own_runtime():
        rec = chip_smoke.phase_serve_lm(
            TINY_LM, seed=0, prompt_lengths=(5, 20, 5, 20),
            new_tokens=8, late=2, head_start=3, compiled=False,
        )
    assert rec["requests"] == 4 and rec["tokens"] == 32
    # Exact at this size on the CPU: the documented contract.
    assert rec["exact_vs_generate"] == 4 and rec["near_tie_requests"] == 0


def test_phase_train_resnet(world, own_runtime):
    with own_runtime():
        rec = chip_smoke.phase_train_resnet(TINY_RESNET, seed=0)
    assert rec["updates"] == 8 and rec["fused_window"] == 2
    assert any("batch_stats" in a for a in rec["asserted"])


def test_phase_multichip(world, own_runtime):
    with own_runtime():
        rec = chip_smoke.phase_multichip(
            TINY_LM, devices=jax.devices()[:4], seed=0, compiled=False
        )
    layouts = rec["layouts"]
    assert set(layouts) == {"one_device", "dp4", "fsdp4"}
    assert layouts["dp4"]["mesh"] == {"dp": 4}
    assert layouts["fsdp4"]["partitioned_leaves"] > 0
    assert layouts["dp4"]["partitioned_leaves"] == 0
    for name in ("dp4", "fsdp4"):
        assert layouts[name]["max_loss_diff"] <= chip_smoke.LOSS_TOL
    assert any("fm.allreduce" in a for a in rec["asserted"])
    assert any("fm.synchronize" in a for a in rec["asserted"])
