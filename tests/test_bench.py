"""Unit tests for bench.py's measurement machinery (VERDICT r2 weak #2:
the MFU path must not be cold code that first executes on the TPU run)."""

import json

import numpy as np
import pytest

import bench


def test_chip_peak_flops_lookup():
    assert bench._chip_peak_flops("TPU v5 lite") == 197e12
    assert bench._chip_peak_flops("TPU v5e") == 197e12
    assert bench._chip_peak_flops("TPU v4") == 275e12
    assert bench._chip_peak_flops("TPU v6 lite") == 918e12
    assert bench._chip_peak_flops("cpu") is None


def test_mfu_math():
    # 1e12 FLOPs/step at 98.5 steps/s on one v5e (197e12 peak) = 50%.
    assert bench._mfu(1e12, 98.5, 1, "TPU v5 lite") == 0.5
    # Per-chip normalization.
    assert bench._mfu(2e12, 98.5, 2, "TPU v5 lite") == 0.5
    # Unknown chip or missing FLOPs → None.
    assert bench._mfu(1e12, 10.0, 1, "cpu") is None
    assert bench._mfu(None, 10.0, 1, "TPU v5 lite") is None
    assert bench._mfu(0.0, 10.0, 1, "TPU v5 lite") is None


def test_mfu_discards_impossible_values():
    # MFU > 1 means a broken clock or FLOPs estimate (round 2's first TPU
    # number was 6.33): must be dropped, never reported.
    assert bench._mfu(1e12, 1000.0, 1, "TPU v5 lite") is None


def test_scaling_efficiency_math():
    assert bench._scaling_efficiency(100.0, 85.0) == 0.85
    assert bench._scaling_efficiency(0.0, 50.0) == 0.0


def test_device_fingerprint_keys_cpu_by_core_count():
    # ADVICE r2 #3: anchors from another machine must not be compared.
    import os

    assert bench._device_fingerprint("tpu", "TPU v5 lite") == "TPU v5 lite"
    assert bench._device_fingerprint("cpu", "cpu") == f"cpu{os.cpu_count()}"


def test_parse_json_line_takes_last_valid():
    out = "garbage\n{\"a\": 1}\nnoise {\nfinal\n" + json.dumps(
        {"metric": "m", "value": 2.0}
    )
    parsed = bench._parse_json_line(out)
    assert parsed == {"metric": "m", "value": 2.0}
    assert bench._parse_json_line("no json here") is None


def test_steps_per_sec_slope_cancels_fixed_overhead():
    # Synthetic step with a large fixed per-sync cost: the two-point slope
    # must recover the true per-step rate.
    class FakeClock:
        def __init__(self):
            self.t = 0.0

    clock = FakeClock()
    step_cost, sync_cost = 0.01, 0.5

    def fake_step(state, data):
        clock.t += step_cost
        return state, None

    real_sync = bench._sync
    real_counter = bench.time.perf_counter
    real_each = bench._sync_each_step
    bench._sync = lambda x: setattr(clock, "t", clock.t + sync_cost)
    bench.time.perf_counter = lambda: clock.t
    # Model the TPU regime (one sync per measurement, async dispatch) —
    # that is where the fixed cost must cancel; the CPU regime syncs every
    # step to serialize collective launches.
    bench._sync_each_step = lambda: False
    try:
        rate, _ = bench._steps_per_sec(fake_step, None, None, warmup=1, steps=20)
    finally:
        bench._sync = real_sync
        bench.time.perf_counter = real_counter
        bench._sync_each_step = real_each
    assert rate == pytest.approx(1.0 / step_cost, rel=1e-6)


def test_anchor_table_keyed_by_fingerprint():
    key = ("resnet50_images_per_sec_per_chip", "tpu", "TPU v5 lite")
    assert key in bench._ANCHORS
    # No bare (metric, platform) keys left (every anchor carries a device
    # fingerprint).
    assert all(len(k) == 3 for k in bench._ANCHORS)


def test_run_scaling_config_selection(monkeypatch):
    # On a real multi-chip TPU the scaling mode must run the headline
    # resnet50 workload with stable mode "accelerator" + backend "tpu";
    # elsewhere the mlp plumbing proxy on the cpu-virtual mesh
    # (VERDICT r3 next #7; mode/backend split per ADVICE r4).
    calls = []

    def fake_run_child(config, timeout, platform, extra_env=None):
        calls.append((config, platform, dict(extra_env or {})))
        return {"metric": "x", "value": 100.0, "unit": "u",
                "vs_baseline": 1.0, "n_chips": 1}

    monkeypatch.setattr(bench, "_run_child", fake_run_child)

    out = bench._run_scaling(
        3000.0, {"platform": "tpu", "n_devices": 4}, None
    )
    assert out["mode"] == "accelerator"
    assert out["backend"] == "tpu"
    assert out["config"] == "resnet50"
    assert [c[0] for c in calls] == ["resnet50", "resnet50"]
    assert calls[0][2]["FLUXMPI_TPU_BENCH_DEVICES"] == "1"
    assert calls[1][2]["FLUXMPI_TPU_BENCH_DEVICES"] == "4"

    calls.clear()
    out = bench._run_scaling(3000.0, None, None)
    assert out["mode"] == "cpu-virtual"
    # The cpu-virtual legs run the REAL driver (train_loop fuse="window"
    # under a ParallelConfig), not the synthetic-step mlp child.
    assert out["config"] == "train_loop"
    assert [c[0] for c in calls] == ["train_loop", "train_loop"]

    # Env override wins.
    monkeypatch.setenv("FLUXMPI_TPU_BENCH_SCALING_CONFIG", "cnn")
    calls.clear()
    out = bench._run_scaling(
        3000.0, {"platform": "tpu", "n_devices": 8}, None
    )
    assert out["config"] == "cnn"


def test_run_scaling_single_chip_falls_back(monkeypatch):
    # One visible chip → cpu-virtual plumbing proof, never a fake "tpu"
    # scaling number.
    def fake_run_child(config, timeout, platform, extra_env=None):
        return {"metric": "x", "value": 10.0, "unit": "u",
                "vs_baseline": 1.0, "n_chips": 1}

    monkeypatch.setattr(bench, "_run_child", fake_run_child)
    out = bench._run_scaling(
        3000.0, {"platform": "tpu", "n_devices": 1}, None
    )
    assert out["mode"] == "cpu-virtual"


def test_peak_table_orders_v5p_before_v5_lite():
    # Substring lookup: "TPU v5p" must hit the v5p row, not "v5 lite"/v5e.
    assert bench._chip_peak_flops("TPU v5p") == 459e12
    assert bench._chip_peak_flops("TPU v5 lite") == 197e12


def test_parse_json_line_rejects_non_dict():
    assert bench._parse_json_line("[1, 2]\n") is None


@pytest.mark.parametrize(
    "env, probe, child, want_config",
    [
        # Not told to use the CPU, and the one check finds no TPU: the
        # run ends non-zero, never in a CPU number.
        ({}, {"ok": True, "platform": "cpu", "n_devices": 1}, None, "probe"),
        ({}, {"ok": False, "error": "timed out after 120s"}, None, "probe"),
        # The same for a forced config: no TPU, no child.
        ({"FLUXMPI_TPU_BENCH_CONFIG": "mlp"},
         {"ok": True, "platform": "cpu", "n_devices": 1}, None, "mlp"),
        # An explicit CPU run never consults the check, and a config
        # that produces no metric exits non-zero too.
        ({"FLUXMPI_TPU_BENCH_CONFIG": "mlp",
          "FLUXMPI_TPU_BENCH_PLATFORM": "cpu"}, None, "no-metric", "mlp"),
        ({"JAX_PLATFORMS": "cpu"}, None, "no-metric", "cnn"),
    ],
)
def test_no_tpu_or_no_metric_exits_nonzero(
    monkeypatch, capsys, env, probe, child, want_config
):
    for var in ("FLUXMPI_TPU_BENCH_SMOKE", "FLUXMPI_TPU_BENCH_CONFIG",
                "FLUXMPI_TPU_BENCH_PLATFORM", "FLUXMPI_TPU_BENCH_JSONL",
                "JAX_PLATFORMS"):
        monkeypatch.delenv(var, raising=False)
    for var, val in env.items():
        monkeypatch.setenv(var, val)

    def fake_probe(timeout, platform):
        assert probe is not None, "an explicit CPU run must not probe"
        return dict(probe)

    def fake_child(config, timeout, platform, extra_env=None):
        assert child is not None, "no TPU: no workload child may start"
        return None

    monkeypatch.setattr(bench, "_run_probe", fake_probe)
    monkeypatch.setattr(bench, "_run_child", fake_child)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 1
    result = bench._parse_json_line(capsys.readouterr().out)
    assert result["metric"] == "bench_failed"
    assert result["config"] == want_config


def test_leg_breakdown_lifts_diagnostics():
    rec = {
        "metric": "mlp_quickstart_samples_per_sec_per_chip",
        "value": 100.0,
        "loader_fed_mlp_quickstart_samples_per_sec_per_chip": 80.0,
        "loader_fed_path": "device_gather",
        "assembly_samples_per_sec": 900.0,
        "dispatch": {"per_dispatch_us": 12.5, "n_dev": 8},
        "scan_steps": 8,
    }
    out = bench._leg_breakdown(rec)
    assert out == {
        "synthetic": 100.0,
        "loader_fed": 80.0,
        "loader_path": "device_gather",
        "assembly": 900.0,
        "dispatch_us": 12.5,
        "scan_steps": 8,
    }
    # Minimal record: only the synthetic rate.
    assert bench._leg_breakdown({"value": 5.0}) == {"synthetic": 5.0}


def test_leg_breakdown_lifts_fused_window():
    rec = {
        "value": 100.0,
        "fused_window": {
            "window": 8,
            "pipelined": {"samples_per_sec_per_chip": 4000.0,
                          "dispatches_per_update": 1.0},
            "fused": {"samples_per_sec_per_chip": 20000.0,
                      "dispatches_per_update": 0.125},
            "dispatch_reduction": 8.0,
            "speedup": 5.0,
        },
    }
    out = bench._leg_breakdown(rec)
    assert out["fused_window"] == {
        "window": 8,
        "pipelined_dispatches_per_update": 1.0,
        "fused_dispatches_per_update": 0.125,
        "dispatch_reduction": 8.0,
        "speedup": 5.0,
    }


def test_leg_breakdown_lifts_attention_ab():
    rec = {
        "value": 100.0,
        "attention_ab": {
            "train": {"speedup": 1.4, "hbm_temp_saved_bytes": 1995872.0},
            "decode": {"speedup": 1.1},
        },
    }
    out = bench._leg_breakdown(rec)
    assert out["attention_ab"] == {
        "train_speedup": 1.4,
        "decode_speedup": 1.1,
        "hbm_temp_saved_bytes": 1995872.0,
    }


def test_run_scaling_includes_breakdown(monkeypatch):
    def fake_run_child(config, timeout, platform, extra_env=None):
        n = extra_env.get("FLUXMPI_TPU_BENCH_DEVICES", "1")
        return {
            "metric": "x", "value": 100.0 / int(n), "unit": "u",
            "vs_baseline": 1.0, "n_chips": int(n),
            "dispatch": {"per_dispatch_us": 10.0 * int(n), "n_dev": int(n)},
            "assembly_samples_per_sec": 1000.0,
        }

    monkeypatch.setattr(bench, "_run_child", fake_run_child)
    out = bench._run_scaling(3000.0, None, None)
    assert set(out["breakdown"]) == {"dp1", "dpN"}
    assert out["breakdown"]["dp1"]["dispatch_us"] == 10.0
    assert out["breakdown"]["dpN"]["dispatch_us"] == 80.0
    assert out["breakdown"]["dpN"]["assembly"] == 1000.0


def test_dispatch_probe_on_test_mesh(world):
    # The null-step probe must produce a sane per-dispatch cost on the
    # 8-device CPU mesh (the number the scaling breakdown attributes
    # dispatch overhead with).
    out = bench._dispatch_probe(world)
    assert out is not None
    assert out["n_dev"] == 8
    assert out["per_dispatch_us"] > 0


def test_bench_smoke_mode_emits_schema_valid_json(tmp_path):
    """The FLUXMPI_TPU_BENCH_SMOKE=1 contract: one real child spawn on
    CPU with capped steps, stdout JSON + JSONL sink both validating
    against scripts/check_metrics_schema.py. (The scaling pair is
    exercised by the slow-marked variant below — this one must stay
    cheap enough for tier-1.)"""
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(bench.__file__))
    jsonl = tmp_path / "smoke.jsonl"
    env = {
        **os.environ,
        "FLUXMPI_TPU_BENCH_SMOKE": "1",
        "FLUXMPI_TPU_BENCH_SMOKE_SCALING": "0",
        "FLUXMPI_TPU_BENCH_STEPS": "4",
        "FLUXMPI_TPU_BENCH_MLP_BATCH": "128",
        "FLUXMPI_TPU_BENCH_JSONL": str(jsonl),
    }
    proc = subprocess.run(
        [sys.executable, os.path.join(here, "bench.py")],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=here,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = bench._parse_json_line(proc.stdout)
    assert result is not None and result["metric"] != "bench_failed", (
        proc.stderr[-2000:]
    )
    assert result.get("smoke") == 1
    assert "dispatch" in result
    # Fused-window leg (PR 11): the one-dispatch-per-window claim is
    # asserted in the record itself — dispatches per update reduced >=5x
    # vs the pipelined path.
    fused = result.get("fused_window")
    assert fused, "mlp child must carry the fused A/B leg"
    assert fused["fused"]["dispatches_per_update"] == pytest.approx(
        1.0 / fused["window"]
    )
    assert fused["pipelined"]["dispatches_per_update"] == 1.0
    assert fused["dispatch_reduction"] >= 5.0
    json_path = tmp_path / "smoke.json"
    json_path.write_text(json.dumps(result))
    check = subprocess.run(
        [
            sys.executable,
            os.path.join(here, "scripts", "check_metrics_schema.py"),
            str(json_path),
            str(jsonl),
        ],
        capture_output=True, text=True, timeout=60,
    )
    assert check.returncode == 0, check.stdout + check.stderr


def test_bench_serving_ab_smoke(tmp_path):
    """The serving child's tier-1 smoke (FLUXMPI_TPU_BENCH_SMOKE=1 +
    _CONFIG=serving): static-batch vs continuous-batch A/B on the
    mixed-length workload. The acceptance claims are asserted in the
    record itself — continuous batching serves the SAME token count in
    fewer decode steps than static (the wall-clock ratio is printed, not
    asserted: it is load-dependent), and mid-flight joins cost zero
    steady-state retraces."""
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(bench.__file__))
    env = {
        **os.environ,
        "FLUXMPI_TPU_BENCH_SMOKE": "1",
        "FLUXMPI_TPU_BENCH_CONFIG": "serving",
    }
    proc = subprocess.run(
        [sys.executable, os.path.join(here, "bench.py")],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=here,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = bench._parse_json_line(proc.stdout)
    assert result is not None and result["metric"] == "serving_tokens_per_sec", (
        proc.stderr[-2000:]
    )
    assert result.get("smoke") == 1
    ab = result["serving"]
    assert ab["static"]["tokens"] == ab["continuous"]["tokens"] > 0
    # The claim in its deterministic form: the same tokens in fewer decode
    # steps. The ratio of the two wall-clock times says the same on a
    # quiet host and swings under six test workers, so it is a reading.
    assert ab["continuous"]["decode_steps"] < ab["static"]["decode_steps"]
    print(f"continuous over static, wall clock: {ab['speedup']:.2f}x")
    assert ab["steady_retraces"] == 0
    json_path = tmp_path / "serving.json"
    json_path.write_text(json.dumps(result))
    check = subprocess.run(
        [
            sys.executable,
            os.path.join(here, "scripts", "check_metrics_schema.py"),
            str(json_path),
        ],
        capture_output=True, text=True, timeout=60,
    )
    assert check.returncode == 0, check.stdout + check.stderr


def test_bench_attention_ab_smoke(tmp_path):
    """The kernel-plane A/B's tier-1 smoke (FLUXMPI_TPU_BENCH_SMOKE=1 +
    _CONFIG=attention_ab): flash vs naive through the model switch on
    both hot paths. The acceptance claims asserted from the record:
    zero steady-state retraces on every leg (training AND paged decode
    with mid-flight joins), the same decoded token count in both modes
    (the kernel swap changes no scheduling), and a strictly smaller
    compiled temp footprint for flash — the dense attend materializes
    [s, s] scores, flash streams tiles. Throughput speedups are NOT
    asserted here: on CPU the flash legs run in pallas interpret mode
    (emulation, not a fast path)."""
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(bench.__file__))
    env = {
        **os.environ,
        "FLUXMPI_TPU_BENCH_SMOKE": "1",
        "FLUXMPI_TPU_BENCH_CONFIG": "attention_ab",
    }
    proc = subprocess.run(
        [sys.executable, os.path.join(here, "bench.py")],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=here,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = bench._parse_json_line(proc.stdout)
    assert result is not None and result["metric"] == "attention_ab_tokens_per_sec", (
        proc.stderr[-2000:]
    )
    assert result.get("smoke") == 1
    ab = result["attention_ab"]
    for path in ("train", "decode"):
        for mode in ("naive", "flash"):
            assert ab[path][mode]["steady_retraces"] == 0, (path, mode, ab)
    assert ab["decode"]["naive"]["tokens"] == ab["decode"]["flash"]["tokens"] > 0
    naive_hbm = ab["train"]["naive"]["compiled_hbm"]
    flash_hbm = ab["train"]["flash"]["compiled_hbm"]
    assert flash_hbm["temp_bytes"] < naive_hbm["temp_bytes"], ab
    assert ab["train"]["hbm_temp_saved_bytes"] > 0
    json_path = tmp_path / "attention_ab.json"
    json_path.write_text(json.dumps(result))
    check = subprocess.run(
        [
            sys.executable,
            os.path.join(here, "scripts", "check_metrics_schema.py"),
            str(json_path),
        ],
        capture_output=True, text=True, timeout=60,
    )
    assert check.returncode == 0, check.stdout + check.stderr


def test_parse_parallel_env(monkeypatch):
    monkeypatch.delenv("FLUXMPI_TPU_BENCH_PARALLEL", raising=False)
    assert bench._parse_parallel_env() == {"dp": -1}
    monkeypatch.setenv("FLUXMPI_TPU_BENCH_PARALLEL", "dp=4,fsdp=2")
    assert bench._parse_parallel_env() == {"dp": 4, "fsdp": 2}
    # Env typos degrade to the default (warn-and-default convention).
    for bad in ("dp=four", "dp=4,", "dp4"):
        monkeypatch.setenv("FLUXMPI_TPU_BENCH_PARALLEL", bad)
        assert bench._parse_parallel_env() == {"dp": -1}


def test_run_axis_bench_composes_legs(monkeypatch):
    calls = []

    def fake_run_child(config, timeout, platform, extra_env=None):
        calls.append((config, platform, dict(extra_env or {})))
        return {
            "metric": "train_loop_tokens_per_sec_per_chip", "value": 50.0,
            "unit": "tokens/sec/chip", "vs_baseline": 1.0, "n_chips": 8,
            "parallel": {"axes": {"dp": 4}, "data_parallel_size": 4,
                         "dispatches_per_update": 0.125,
                         "sharded_param_leaves": 3, "rule_hits": {}},
        }

    monkeypatch.setattr(bench, "_run_child", fake_run_child)
    out = bench._run_axis_bench(3000.0)
    assert set(out) == {"dp", "dp_fsdp", "dp_tp"}
    specs = [c[2]["FLUXMPI_TPU_BENCH_PARALLEL"] for c in calls]
    assert specs == ["dp=8", "dp=4,fsdp=2", "dp=4,tp=2"]
    assert all(c[0] == "train_loop" for c in calls)
    assert all(
        "--xla_force_host_platform_device_count=8" in c[2]["XLA_FLAGS"]
        for c in calls
    )
    assert out["dp"]["dispatches_per_update"] == 0.125
    # No budget → no legs, not a crash.
    assert bench._run_axis_bench(30.0) is None


def test_bench_train_loop_dp_fsdp_leg_smoke(tmp_path):
    """The smoke dp×fsdp composition leg (tier-1): the train_loop child
    forced through smoke mode under FLUXMPI_TPU_BENCH_PARALLEL=dp=4,fsdp=2
    — the scaling legs' real-driver contract, asserted in the record:
    fused windows engaged (dispatches_per_update == 1/window) under the
    plan-derived sharding (sharded parameter leaves > 0), schema-valid."""
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(bench.__file__))
    env = {
        **os.environ,
        "FLUXMPI_TPU_BENCH_SMOKE": "1",
        "FLUXMPI_TPU_BENCH_CONFIG": "train_loop",
        "FLUXMPI_TPU_BENCH_PARALLEL": "dp=4,fsdp=2",
        "FLUXMPI_TPU_BENCH_STEPS": "16",
    }
    proc = subprocess.run(
        [sys.executable, os.path.join(here, "bench.py")],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=here,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = bench._parse_json_line(proc.stdout)
    assert result is not None, proc.stderr[-2000:]
    assert result["metric"] == "train_loop_tokens_per_sec_per_chip", result
    assert result.get("smoke") == 1
    par = result["parallel"]
    assert par["axes"] == {"dp": 4, "fsdp": 2}
    assert par["data_parallel_size"] == 8
    assert par["sharded_param_leaves"] > 0
    assert par["dispatches_per_update"] == pytest.approx(
        1.0 / par["fused_window"]
    )
    json_path = tmp_path / "train_loop.json"
    json_path.write_text(json.dumps(result))
    check = subprocess.run(
        [
            sys.executable,
            os.path.join(here, "scripts", "check_metrics_schema.py"),
            str(json_path),
        ],
        capture_output=True, text=True, timeout=60,
    )
    assert check.returncode == 0, check.stdout + check.stderr


@pytest.mark.slow
def test_bench_smoke_mode_full_with_scaling(tmp_path):
    """Full smoke including the dp1/dpN scaling pair + breakdown."""
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(bench.__file__))
    env = {
        **os.environ,
        "FLUXMPI_TPU_BENCH_SMOKE": "1",
        "FLUXMPI_TPU_BENCH_STEPS": "4",
        "FLUXMPI_TPU_BENCH_MLP_BATCH": "128",
    }
    proc = subprocess.run(
        [sys.executable, os.path.join(here, "bench.py")],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=here,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = bench._parse_json_line(proc.stdout)
    assert result is not None
    scaling = result.get("scaling")
    assert scaling and "breakdown" in scaling
    assert scaling["breakdown"]["dpN"]["synthetic"] == scaling[
        "per_chip_at_dpN"
    ]
    # The scaling legs ride the real fused driver now: the train_loop
    # child's dispatch accounting is in the breakdown.
    assert scaling["config"] == "train_loop"
    assert scaling["breakdown"]["dpN"].get("dispatches_per_update") is not None
    # And the smoke dp×fsdp composition leg banked alongside.
    axes = result.get("parallel_axes")
    assert axes and "dp_fsdp" in axes
    assert axes["dp_fsdp"]["sharded_param_leaves"] > 0
