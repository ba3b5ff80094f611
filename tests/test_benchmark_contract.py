"""The package still offers what ``benchmarks/`` calls.

The benchmark (``BENCHMARK.json``, ``benchmarks/run.py``) imports the
program only through its public entry points, with the arguments each
cell's file under ``benchmarks/workloads/`` names. Nothing else on a CPU
notices when a change to the package takes one of them away: on the chip
that is a run that cannot start. Every cell file is read through the
benchmark's own loader (``benchmarks/harness/manifest.py``) and each call
a driver makes is checked against the package's signature; one tiny
training cell and one tiny serving cell then make the calls, the way
``benchmarks/drivers/train.py`` and ``serve_open_loop.py`` make them, and
are held to the keys the drivers read. Nothing under ``benchmarks/`` is
edited, and nothing large is built.
"""

import functools
import glob
import inspect
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "benchmarks")
# The drivers import ``harness`` and ``drivers`` the way run.py lets them.
for _path in (_REPO, _BENCH):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from harness import manifest  # noqa: E402

CELLS = sorted(
    os.path.basename(p)[: -len(".json")]
    for p in glob.glob(os.path.join(_BENCH, "workloads", "*.json"))
)


@functools.cache
def _cell(name):
    return manifest.Cell(name)


def _cells(driver=None, having=None):
    out = []
    for name in CELLS:
        with open(os.path.join(_BENCH, "workloads", f"{name}.json")) as f:
            spec = json.load(f)
        if driver is not None and spec["driver"] != driver:
            continue
        if having is not None and having not in spec:
            continue
        out.append(name)
    return out


TRAIN_CELLS = _cells(driver="train")
SERVE_CELLS = _cells(driver="serve_open_loop")


def test_every_cell_file_is_covered():
    # The four cells of BENCHMARK.json, their tiny twins and the cell
    # whose files wait for its PR: a driver this file does not know would
    # go unchecked.
    listed = {w["name"] for w in manifest.load_manifest()["workloads"]}
    assert listed <= set(CELLS)
    assert set(TRAIN_CELLS) | set(SERVE_CELLS) == set(CELLS)


# ---------------------------------------------------------------------------
# Signatures: what each cell's file passes, bound and not called
# ---------------------------------------------------------------------------


def test_run_py_compile_cache_entry_point():
    from fluxmpi_tpu.runtime import enable_compile_cache

    inspect.signature(enable_compile_cache).bind()


@pytest.mark.parametrize("name", CELLS)
def test_init_binds_what_the_drivers_pass(name):
    import fluxmpi_tpu as fm

    cell = _cell(name)
    inspect.signature(fm.init).bind(
        devices=jax.devices()[: cell.chips], compileplane=True, parallel=None
    )
    for entry in ("shutdown", "global_plan", "global_mesh"):
        assert callable(getattr(fm, entry))


@pytest.mark.parametrize("name", _cells(having="parallel"))
def test_parallel_config_constructs(name):
    from fluxmpi_tpu import ParallelConfig

    ParallelConfig(**_cell(name).spec["parallel"])


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_loader_binds_the_cells_keys(name):
    from fluxmpi_tpu.data import (
        ArrayDataset,
        DistributedDataContainer,
        DistributedDataLoader,
    )

    spec = _cell(name).spec
    assert callable(ArrayDataset) and callable(DistributedDataContainer)
    inspect.signature(DistributedDataLoader).bind(
        object(), spec["data"]["rows_per_step"], **spec.get("loader", {})
    )
    assert callable(DistributedDataLoader.load_state_dict)


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_train_entry_points_bind(name):
    import flax.linen as nn

    from fluxmpi_tpu.parallel import TrainState, make_train_step, train_loop

    cell = _cell(name)
    spec, prog = cell.spec, cell.program
    model = prog.build_model(cell.config, spec.get("attention", "flash"))
    assert isinstance(model, nn.Module)
    loss_fn = prog.make_loss(model)
    optimizer = prog.make_optimizer(spec["optimizer"])
    inspect.signature(make_train_step).bind(loss_fn, optimizer, parallel=None)
    inspect.signature(TrainState.create).bind(object(), optimizer, None)
    inspect.signature(train_loop).bind(
        object(), object(), object(), steps=3,
        flush_every=spec["loop"]["flush_every"], metrics=[].append,
    )
    for entry in ("to_program", "from_program", "grad_state",
                  "make_dataset", "items_per_row"):
        assert callable(getattr(prog, entry))


@pytest.mark.parametrize("name", SERVE_CELLS)
def test_engine_binds_the_cells_keys(name):
    import flax.linen as nn

    from fluxmpi_tpu.serving import InferenceEngine

    cell = _cell(name)
    model = cell.program.build_model(cell.config, "naive")
    assert isinstance(model, nn.Module)
    inspect.signature(InferenceEngine).bind(
        model, object(), attention=cell.spec["attention"],
        **cell.spec["engine"],
    )
    inspect.signature(InferenceEngine.submit).bind(
        object(), np.zeros(8, np.int32), 4, on_token=print
    )
    inspect.signature(InferenceEngine.warmup).bind(
        object(), prompt_lengths=(8,)
    )
    for entry in ("start", "stop", "close", "run", "stats"):
        assert callable(getattr(InferenceEngine, entry))
    assert callable(cell.program.to_program)


# ---------------------------------------------------------------------------
# One tiny cell of each driver makes the calls
# ---------------------------------------------------------------------------


def test_tiny_train_cell_summary_and_record_keys(world, own_runtime):
    import fluxmpi_tpu as fm
    from fluxmpi_tpu.data import (
        ArrayDataset,
        DistributedDataContainer,
        DistributedDataLoader,
    )
    from fluxmpi_tpu.parallel import TrainState, make_train_step, train_loop
    from fluxmpi_tpu.telemetry.compileplane import get_compile_monitor

    cell = _cell("tiny-lm-train")
    spec, cfg, prog = cell.spec, cell.config, cell.program
    flush_every = spec["loop"]["flush_every"]
    with own_runtime():
        fm.init(devices=jax.devices()[: cell.chips], compileplane=True,
                parallel=None)
        assert fm.global_plan() is None and fm.global_mesh() is not None
        arrays = prog.make_dataset(cfg, spec["data"], 3)
        loader = DistributedDataLoader(
            DistributedDataContainer(ArrayDataset(arrays)),
            spec["data"]["rows_per_step"], **spec.get("loader", {}),
        )
        model = prog.build_model(cfg, spec["attention"])
        optimizer = prog.make_optimizer(spec["optimizer"])
        variables, model_state = prog.to_program(
            cell.reference.make_weights(cfg, jax.random.PRNGKey(3)), cfg
        )
        state = TrainState.create(variables, optimizer, model_state)
        step = make_train_step(prog.make_loss(model), optimizer)
        records: list[dict] = []
        state, summary = train_loop(
            step, state, loader, steps=flush_every, flush_every=flush_every,
            metrics=records.append,
        )
        events = get_compile_monitor().events
    for key in ("loss", "fused_window", "examples", "dispatches", "updates"):
        assert key in summary, key
    assert summary["updates"] == flush_every
    assert summary["examples"] == flush_every * spec["data"]["rows_per_step"]
    # The cell's feed is device-gathered, so the loop fuses the window:
    # one dispatch, and the record the driver reads its losses from.
    assert summary["fused_window"] == flush_every
    assert summary["dispatches"] == 1
    assert {"loss_window_mean", "loss_window_max"} <= set(records[-1])
    assert np.isfinite(summary["loss"])
    assert isinstance(events, int) and events > 0
    assert prog.grad_state(state.opt_state) is not None
    loader.load_state_dict(
        {"epoch": 0, "cursor": flush_every, "seed": loader.seed}
    )


# The counters PERF.md §3 names as the public twin of what the serve
# driver reads.
_ENGINE_COUNTERS = (
    "decode_steps", "tokens", "slot_steps_active", "admissions", "evictions",
    "kv_blocks_live", "kv_blocks_tabled", "kv_kernel_steps", "context_tokens",
    "kv_blocks_full",
    "kv_blocks_window", "kv_blocks_uniform", "expert_tokens",
    "experts_touched", "expert_slots", "expert_weight_visits",
    "expert_row_tiles_worked", "expert_row_tiles", "decode_steps_overlapped", "tokens_discarded", "state_entries",
    "state_entries_used", "state_bytes", "gaps", "gaps_stalled",
    "gap_seconds", "gap_stalled_seconds", "kv_sublayers", "state_sublayers",
)


def test_tiny_serve_cell_engine_surface(world, own_runtime):
    import fluxmpi_tpu as fm
    from fluxmpi_tpu.serving import InferenceEngine
    from fluxmpi_tpu.telemetry.compileplane import get_compile_monitor

    cell = _cell("tiny-lm-serve")
    spec, cfg, prog = cell.spec, cell.config, cell.program
    with own_runtime():
        fm.init(devices=jax.devices()[: cell.chips], compileplane=True)
        params = prog.to_program(
            cell.reference.make_weights(cfg, jax.random.PRNGKey(3)), cfg
        )[0]
        engine = InferenceEngine(
            prog.build_model(cfg, "naive"), params,
            attention=spec["attention"], **spec["engine"],
        )
        try:
            engine.warmup(prompt_lengths=(8,))
            mon = get_compile_monitor()
            warm = mon.events
            assert isinstance(warm, int) and warm > 0
            seen: list[int] = []
            engine.start()
            prompt = np.arange(8, dtype=np.int32) % cfg["vocab_size"]
            req = engine.submit(prompt, 4, on_token=seen.append)
            assert req.wait(timeout=120.0)
            assert engine.stop()
            assert engine.serve_error is None
            stats = engine.stats()
            steps, slots = engine._decode_steps, engine.slots
            # What benchmarks/tools/ read besides.
            assert engine.queue_depth == 0 and engine.active_count == 0
            assert engine.model is not None and engine.params is params
            # Warmed up: serving the request compiled nothing.
            assert mon.events == warm
        finally:
            engine.close()
    assert req.status == "finished" and len(seen) == 4
    assert req.admitted_t is not None and req.submitted_t <= req.admitted_t
    assert set(_ENGINE_COUNTERS) <= set(stats)
    assert stats["decode_steps"] == steps > 0
    assert stats["tokens"] == 4 and stats["admissions"] == 1
    # One request alone: three gaps, none of them behind a prefill.
    assert stats["gaps"] == 3 and stats["gaps_stalled"] == 0
    assert stats["gap_seconds"] > 0.0 == stats["gap_stalled_seconds"]
    assert slots == spec["engine"]["slots"]


# ---------------------------------------------------------------------------
# A traced run of the serve rehearsal cells: the span readers read
# ---------------------------------------------------------------------------

# What a traced run's last line carries (under ``cpu_rehearsal.``): the
# span readers the serve cells share, the expert layer's and the state
# kind's. On a CPU
# the grouped matmul is XLA's ``ragged_dot``, which makes no weight visits
# to count: ``expert_weight_visits_per_touched`` is then absent, and its
# reader must say so without raising.
_TRACED = {
    "tiny-lm-serve": ("decode_host_ms", "decode_active_slots",
                      "kv_blocks_read_pct", "decode_ticks_in_flight"),
    "tiny-trinity-serve": ("decode_host_ms", "decode_active_slots",
                           "kv_blocks_read_pct", "experts_touched_pct",
                           "expert_row_tiles_worked_pct",
                           "decode_ticks_in_flight"),
    # Latent attention: the new span argument, the held experts' counters.
    "tiny-sarvam-serve": ("decode_host_ms", "decode_active_slots",
                          "kv_blocks_read_pct", "experts_touched_pct",
                          "expert_load_max_over_mean",
                          "expert_row_tiles_worked_pct",
                          "decode_context_tokens", "decode_ticks_in_flight"),
    # Mamba-2 layers: the state kind's span argument beside the K/V's.
    "tiny-granite-serve": ("decode_host_ms", "decode_active_slots",
                           "kv_blocks_read_pct", "experts_touched_pct",
                           "expert_row_tiles_worked_pct",
                           "decode_context_tokens", "decode_ticks_in_flight",
                           "ssm_states_read_pct"),
    # Layers of one sublayer: the expert layers' arguments (counted over
    # the layers that have experts) and the state kind's, which
    # ``ssm_update_roofline`` reads, beside the K/V's.
    "tiny-nemotron-serve": ("decode_host_ms", "decode_active_slots",
                            "kv_blocks_read_pct", "experts_touched_pct",
                            "expert_load_max_over_mean",
                            "expert_row_tiles_worked_pct",
                            "decode_context_tokens", "decode_ticks_in_flight",
                            "ssm_states_read_pct"),
    # Two mixers a layer: every layer's state AND its K/V blocks, which
    # ``ssm_update_roofline`` and ``paged_decode_roofline`` read; no
    # expert layer, so none of their arguments.
    "tiny-falcon-h1-serve": ("decode_host_ms", "decode_active_slots",
                             "kv_blocks_read_pct", "decode_context_tokens",
                             "decode_ticks_in_flight", "ssm_states_read_pct"),
}


@pytest.mark.parametrize("name", ["tiny-sarvam-serve", "tiny-granite-serve",
                                  "tiny-nemotron-serve",
                                  "tiny-falcon-h1-serve"])
def test_untraced_rehearsal_reports_its_end_to_end_metrics(name, tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", HOME=str(tmp_path), TMPDIR=str(tmp_path))
    done = subprocess.run(
        [sys.executable, os.path.join(_BENCH, "run.py"), "--workload",
         name, "--seed", "2147483777", "--seconds", "3", "--trace", "0"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-4000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {
        "cpu_rehearsal.serve_tokens_per_s", "cpu_rehearsal.itl_p95_ms",
        "cpu_rehearsal.setup_s"}


# The gap ledger's and the upload's metrics (PR 39) read the spans of
# every serve cell; the three idle shares read the device's plane, which
# a CPU run has not: absent, and their reader must not raise.
_GAP_METRICS = ("stalled_gap_pct", "stalled_gap_p50_ms", "clean_gap_p95_ms",
                "decode_upload_ms")
_IDLE_METRICS = ("idle_engine_empty_pct", "idle_admit_pct",
                 "idle_tick_host_pct")


@pytest.mark.parametrize("name", sorted(_TRACED))
def test_traced_rehearsal_run_exits_0_and_reads_its_spans(name, tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", HOME=str(tmp_path), TMPDIR=str(tmp_path))
    done = subprocess.run(
        [sys.executable, os.path.join(_BENCH, "run.py"), "--workload", name,
         "--seed", "5", "--seconds", "3", "--trace", "1"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-4000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    metrics = result["metrics"]
    for metric in _TRACED[name] + _GAP_METRICS:
        assert f"cpu_rehearsal.{metric}" in metrics, (metric, sorted(metrics))
    for metric in _GAP_METRICS:
        value = metrics[f"cpu_rehearsal.{metric}"]["value"]
        assert isinstance(value, float) and value >= 0.0, (metric, value)
    assert 0.0 <= metrics["cpu_rehearsal.stalled_gap_pct"]["value"] <= 100.0
    assert metrics["cpu_rehearsal.clean_gap_p95_ms"]["value"] > 0.0
    for metric in _IDLE_METRICS:
        assert f"cpu_rehearsal.{metric}" not in metrics
    if name == "tiny-nemotron-serve":
        # The cell it stands for reports the un-gated experts' roofline:
        # its reader ran and, on a CPU's trace, found no kernel to read.
        reported = {m["name"] for m in manifest.Cell(name).per_layer()}
        assert {"relu2_expert_stream_roofline", "ssm_update_roofline",
                "moe_device_pct"} <= reported
        assert "moe_weight_stream_roofline" not in reported
        assert "cpu_rehearsal.relu2_expert_stream_roofline" not in metrics
    if name == "tiny-falcon-h1-serve":
        # The cell it stands for reports three rooflines off the device's
        # trace: their readers ran and, on a CPU's trace, found no kernel
        # and no decode program to read; their inputs from the spans (the
        # live states and the live blocks of the keeping sublayers) are
        # there.
        reported = {m["name"] for m in manifest.Cell(name).per_layer()}
        rooflines = {"dense_weight_stream_roofline", "ssm_update_roofline",
                     "paged_decode_roofline"}
        assert rooflines | {"ssm_update_device_pct"} <= reported
        assert not any(m.startswith(("moe_", "expert", "relu2_", "latent_"))
                       for m in reported)
        for metric in rooflines:
            assert f"cpu_rehearsal.{metric}" not in metrics
        assert 0.0 < metrics["cpu_rehearsal.ssm_states_read_pct"][
            "value"] <= 100.0
        assert 0.0 < metrics["cpu_rehearsal.kv_blocks_read_pct"][
            "value"] <= 100.0
    visits = metrics.get("cpu_rehearsal.expert_weight_visits_per_touched")
    assert visits is None or visits["value"] >= 1.0
    # Every expert held: every row tile worked; a model without expert
    # layers has no such span argument to read.
    worked = metrics.get("cpu_rehearsal.expert_row_tiles_worked_pct")
    if name in ("tiny-lm-serve", "tiny-falcon-h1-serve"):
        assert worked is None
    elif name == "tiny-trinity-serve":
        assert worked["value"] == 100.0
    else:
        assert 0.0 <= worked["value"] <= 100.0
