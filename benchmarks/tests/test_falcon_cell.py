"""The Falcon-H1 cell's part of the yardstick: the configuration and the
cell's letter against ISSUE 43, the rehearsal cell deciding ``correct``
both ways, the byte counts against hand-worked numbers AND the engine's
own pool shapes, the new roofline reader on a hand-made trace, and the
reference's layer-by-layer form against its full forward."""

import json
import os
import time

import pytest

import run as bench_run
from harness import densebytes, manifest, moebytes, spans, ssmbytes

MS = 1_000_000
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "falcon-h1-34b-serve"
NEW = "dense_weight_stream_roofline"


def _config(name):
    with open(os.path.join(manifest.BENCH_DIR, "configs", f"{name}.json")) as f:
        return json.load(f)


def _reader(name):
    return manifest.load_module(
        os.path.join("benchmarks", "metrics", "readers", f"{name}.py")
    )


# ---------------------------------------------------------------------------
# The configuration and the cell
# ---------------------------------------------------------------------------

PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256,
    "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_key_value_heads": 4, "num_logits_to_keep": 1,
    "projectors_bias": False, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120,
}


def test_falcon_keeps_every_published_width_and_multiplier():
    cfg = _config("falcon-h1-34b")
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    # The cut is depth alone: no head, channel or row of the vocabulary.
    assert sorted(cfg["reduced"]) == ["max_position_embeddings",
                                      "num_hidden_layers"]
    assert (cfg["num_hidden_layers"], cfg["max_position_embeddings"]) == (
        4, 3072)
    assert cfg["published"] == {"num_hidden_layers": 72,
                                "max_position_embeddings": 262144}
    # What the benchmark's byte counts read, under their own names.
    assert cfg["layer_types"] == ["mamba"] * 4
    assert (cfg["compute_dtype"], cfg["param_dtype"], cfg["state_dtype"]) == (
        "bfloat16", "bfloat16", "float32")
    for key in ("layer_types", "block", "in_proj_order", "key_multiplier",
                "rope_layout", "mamba_inner", "gate_before_norm",
                "time_step_limit", "state_dtype", "mamba_init", "init_std",
                "branch_magnitudes"):
        assert key in cfg["assumed"], key
    assert set(cfg["init_std"]) == {"embed", "head", "wq", "wk", "wv", "wo",
                                    "w_in", "w_out", "w1", "w3", "w2"}
    assert "Pipeline stages of WHOLE layers" in cfg["deployment"]
    assert "about twice its share of a tick" in cfg["deployment"]
    assert "fewer layers make the host's turn" in cfg["deployment"]
    assert "8.789 GB" in cfg["parameters"]["held_here"]
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "falcon-h1-34b")
    assert entry["file"] == "benchmarks/configs/falcon-h1-34b.json"
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/"
        "config.json")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    # The rehearsal twin: the same keys, every multiplier away from 1.
    tiny = _config("tiny-falcon-h1")
    assert set(PUBLISHED) <= set(tiny)
    scalars = [tiny[k] for k in PUBLISHED if k.endswith("_multiplier")]
    assert all(m != 1 for m in (
        *scalars, *tiny["mlp_multipliers"], *tiny["ssm_multipliers"]))


def test_the_cell_serves_the_issues_traffic():
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and cell.config_name == "falcon-h1-34b"
    entry = next(w for w in manifest.load_manifest()["workloads"]
                 if w["name"] == CELL)
    assert entry["traffic"] == "assistant_open_loop_0p8knee"
    assert len(entry["why"]) <= 200
    # 96 slots: 13.28 GB with the longest prefill's temporaries, compiled
    # for a described v5e (tests/test_tpu_compile.py).
    assert cell.spec["engine"] == {"slots": 96, "block_size": 512,
                                   "max_len": 3072, "max_queue": 4096}
    mix = cell.spec["traffic"]
    assert mix["prompt"] == {"median": 256, "sigma": 0.9, "min": 32,
                             "max": 2048}
    assert mix["answer"] == {"median": 512, "sigma": 0.6, "min": 64,
                             "max": 1536}
    assert (mix["max_total"], mix["burst"], mix["preroll_s"],
            mix["postroll_s"]) == (3072, 1, 20.0, 4.0)
    assert cell.spec["trace"] == {"at_s": 5.0, "seconds": 3.0}
    assert cell.spec["reference"] == {"sample": 8}
    reported = {m["name"] for m in cell.end_to_end()}
    assert reported == {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    layers = {m["name"] for m in cell.per_layer()}
    assert {NEW, "ssm_update_roofline", "ssm_update_device_pct",
            "ssm_states_read_pct", "paged_decode_roofline",
            "kv_blocks_read_pct", "decode_context_tokens",
            "decode_ticks_in_flight", "decode_step_device_ms",
            "prefill_device_ms", "peak_hbm_gb.serve",
            "compiles_in_window.serve", "stalled_gap_pct",
            "device_idle_pct.serve"} <= layers
    # What cannot count this configuration: no expert, no latent row, no
    # window, no gathered K/V.
    assert not any(name.startswith(("moe_", "expert", "relu2_", "latent_"))
                   or name in ("kv_window_blocks_pct", "kv_gather_device_pct")
                   for name in layers)
    by_name = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    assert by_name[NEW]["workloads"] == [CELL]
    assert by_name[NEW]["moves"] == "itl_p95_ms"
    assert by_name[NEW]["layer"] == by_name["decode_step_device_ms"]["layer"]


def test_the_prompts_fall_into_four_buckets_most_into_the_first():
    from harness import traffic

    cell = manifest.Cell(CELL)
    pairs = traffic.length_pairs(cell.spec["traffic"], 1000)
    prompts, answers = pairs[:, 0], pairs[:, 1]
    buckets = {int(-(-p // 512) * 512) for p in prompts}
    assert buckets == {512, 1024, 1536, 2048}
    assert 0.74 < (prompts <= 512).mean() < 0.82
    assert prompts.min() == 32 and prompts.max() == 2048
    assert answers.min() >= 64 and answers.max() <= 1536
    assert (prompts + answers).max() <= 3072
    assert 560 < answers.mean() < 640


def test_the_windows_edges_fall_in_lulls_of_the_schedule():
    """``tools/edge_exposure.py`` replays what the driver sends, and the
    file's ``schedule_seed`` is the realisation it found least exposed:
    a pause of the host moves fewer tokens across the window's edges
    than under seed 7, which the other serve cells use (PERF.md §6)."""
    import importlib

    from drivers import serve_open_loop

    tool = importlib.import_module("tools.edge_exposure")
    cell = manifest.Cell(CELL)
    mix = dict(cell.spec["traffic"])
    assert mix["schedule_seed"] == 32 and mix["rate_per_s"] == 6.6
    sent = sorted(
        (base + r["due"], len(r["prompt"]), r["max_new_tokens"])
        for _, base, reqs in serve_open_loop.make_plan(cell, 5, 30.0)
        for r in reqs
    )
    assert tool.schedule(mix, 30.0) == sent and len(sent) == 132 + 198 + 26
    model = dict(slots=96, block=512, tick_ms=(9.2, 0.061),
                 prefill_ms=(2.5, 15.0))

    def moved(seed, at):
        mix["schedule_seed"] = seed
        requests = tool.schedule(mix, 30.0)
        base, live = tool.replay(requests, mix, 30.0, **model)
        paused, _ = tool.replay(requests, mix, 30.0, **model,
                                pause=(at, 0.4))
        assert 50 < live < 70 and 3700 < base / 30.0 < 4100
        return 100.0 * (paused - base) / base

    # Before the window a pause pushes tokens into it, before its end out
    # of it; either way by less at this cell's seed.
    assert 0 < moved(32, 19.0) < moved(7, 19.0)
    assert moved(7, 49.0) < moved(32, 49.0) < 0


def _run(name, **driver_args):
    result, _ = bench_run.run_cell(
        manifest.Cell(name), seed=2_147_483_777, seconds=1.5, trace=False,
        phases=bench_run.Phases(time.perf_counter()), **driver_args,
    )
    return result


@pytest.mark.parametrize("broken,correct", [(None, True),
                                            ("token_altered", False)])
def test_rehearsal_cell_decides_correct_both_ways(broken, correct):
    result = _run("tiny-falcon-h1-serve", broken=broken)
    assert result["correct"] is correct, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert all(k.startswith("cpu_rehearsal.") for k in result["metrics"])


def test_rehearsal_cell_fails_the_reference_in_fp8():
    """The configuration states bfloat16 compute over a float32 state:
    the control is the reference computed in fp8, its state held so too,
    and it comes out as not correct."""
    result = _run("tiny-falcon-h1-serve", control="fp8")
    assert result["correct"] is True, result["compared"]
    row = result["control"]["served_logit_gap_mean"]
    assert row["value"] > row["limit"], row


# ---------------------------------------------------------------------------
# Bytes that have to move: by hand, and against the engine's own pools
# ---------------------------------------------------------------------------


def test_weight_state_and_kv_bytes_by_hand():
    cfg = _config("falcon-h1-34b")
    assert densebytes.attention_params(cfg) == (
        5120 * (2560 + 512 + 512) + 2560 * 5120) == 31_457_280
    assert densebytes.mamba_params(cfg) == (
        5120 * 9248 + 4096 * 5120) == 68_321_280
    assert densebytes.mlp_params(cfg) == 3 * 5120 * 21504 == 330_301_440
    assert densebytes.head_params(cfg) == 5120 * 261120 == 1_336_934_400
    # Four layers and the head, bfloat16, once a tick: 6.11 GB.
    assert densebytes.tick_weight_bytes(cfg) == 2 * (
        4 * 430_080_000 + 1_336_934_400) == 6_114_508_800
    # One sequence, one layer: 32 x 128 x 256 float32 = 4.19 MB of state
    # whatever the context, and 4 x 128 x 2 x 2 = 2,048 B a TOKEN of K/V.
    assert ssmbytes.state_bytes(cfg) == 32 * 128 * 256 * 4 == 4_194_304
    assert ssmbytes.mamba_layers(cfg) == 4
    assert moebytes.kv_block_bytes(cfg, 1) == 2048
    assert moebytes.kv_block_bytes(cfg, 512) == 2 * 512 * 512 * 2 == 2**20
    assert ssmbytes.state_update_bytes(cfg, 64) == 64 * 4 * 2 * 4_194_304


def test_the_byte_counts_count_what_the_engine_holds():
    """Every reader of the file's ``layer_types`` against the engine's
    own count: 4 state sublayers and 4 full K/V sublayers, the pools'
    shapes, the blocks its tables span."""
    import jax

    from fluxmpi_tpu.serving import InferenceEngine

    cell = manifest.Cell(CELL)
    cfg, geometry = cell.config, cell.spec["engine"]
    model = cell.program.build_model(cfg, "naive")
    params = jax.eval_shape(
        lambda k: cell.program.to_program(
            cell.reference.make_weights(cfg, k), cfg)[0],
        jax.random.PRNGKey(0))
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(params))
    assert 8.78e9 < held < 8.80e9
    # The matrices a tick reads: everything held but the embedding (its
    # rows are gathered) and 0.11 MB a layer of vectors.
    rest = held - densebytes.tick_weight_bytes(cfg) - 2 * 261120 * 5120
    assert 0 < rest < 4 * 150_000
    engine = InferenceEngine(model, params, attention="naive",
                             check_memory=False, **geometry)
    try:
        cache = engine.cache
        full, state = cache.kinds
        slots = geometry["slots"]
        assert (full.layers, state.layers) == (4, 4)
        assert ssmbytes.mamba_layers(cfg) == state.layers
        assert cfg["num_hidden_layers"] == full.layers  # a call a layer
        assert cache.pool_shapes == [(4, 1 + slots * 6, 512, 512),
                                     (4, 1 + slots, 256, 4096)]
        # A block of one K/V sublayer, a state of one state sublayer.
        shape = cache.pool_shapes[0]
        assert moebytes.kv_block_bytes(cfg, 512) == 2 * shape[2] * shape[3] * 2
        shape = cache.pool_shapes[1]
        assert ssmbytes.state_bytes(cfg) == shape[2] * shape[3] * 4
        assert state.num_blocks - 1 == slots  # what live_states_pct is of
        # The engine's kv_blocks_tabled a tick.
        tabled = slots * sum(k.layers * k.entries for k in cache.kinds
                             if k.state is None)
        assert moebytes.kv_tabled_blocks(cfg, geometry) == tabled == (
            slots * 4 * 6)
        stats = engine.stats()
        assert (stats["kv_sublayers"], stats["state_sublayers"]) == (4, 4)
    finally:
        engine.close()


# ---------------------------------------------------------------------------
# The roofline reader, on a hand-made trace
# ---------------------------------------------------------------------------


class _Cell:
    name = "no-such-cell"
    config = None
    spec = {"engine": {"slots": 96, "block_size": 512, "max_len": 3072}}


def _ctx(monkeypatch, rows, modules, config="falcon-h1-34b"):
    loaded = {"window_ns": 1000 * MS, "host": [], "device": []}
    monkeypatch.setattr(spans, "for_cell", lambda ctx: loaded)
    cell = _Cell()
    cell.config = _config(config)
    return {"cell": cell, "peaks": PEAKS,
            "trace": {"rows": rows, "modules": modules, "busy_s": 1.0}}


def test_dense_stream_roofline_leaves_the_two_kernels_out(monkeypatch):
    read = _reader("dense_stream_roofline").read
    args = {"module": "jit_step",
            "kernels": "^(ssm_state_update|paged_decode_attention)"}
    # Two ticks of 15 ms and a prefill; in each tick four state updates
    # of 0.8 ms and four decode attentions of 0.2 ms, the rest matmuls.
    modules = [("jit_step(123)", 0, 15 * MS),
               ("jit_prefill(9)", 20 * MS, 25 * MS),
               ("jit_step(123)", 50 * MS, 15 * MS)]
    rows = []
    for start in (0, 50 * MS):
        for i in range(4):
            rows.append(("ssm_state_update.%d" % i, "",
                         start + i * 3 * MS, 800_000))
            rows.append(("paged_decode_attention.%d" % i, "",
                         start + i * 3 * MS + MS, 200_000))
            rows.append(("fusion.%d" % i, "", start + i * 3 * MS + 2 * MS,
                         MS))
    # The prefill's kernels are not a tick's.
    rows.append(("ssm_state_update.9", "", 30 * MS, 5 * MS))
    ideal = 2 * 6_114_508_800 / 819e9
    took = 2 * (15e-3 - 4 * 0.8e-3 - 4 * 0.2e-3)
    ctx = _ctx(monkeypatch, rows, modules)
    assert read(ctx, **args) == pytest.approx(100.0 * ideal / took)
    assert 60.0 < read(ctx, **args) < 100.0
    # No decode program in the trace, a program without this block (the
    # parent of the PR that added it), no trace: None, and no exception.
    assert read(_ctx(monkeypatch, rows, modules[1:2]), **args) is None
    assert read(_ctx(monkeypatch, rows, modules,
                     "granite-4.0-h-small"), **args) is None
    assert read({"cell": ctx["cell"], "peaks": None, "trace": None},
                **args) is None


# ---------------------------------------------------------------------------
# The reference
# ---------------------------------------------------------------------------


def test_reference_layer_by_layer_in_blocks_equals_its_full_forward():
    """``served_gaps`` makes and applies the weights a layer at a time,
    the mixers a padded sequence at a time, the MLP over all sequences'
    real tokens in slabs, the embedding a block of the vocabulary and
    the head a block of the hidden size at a time: the same logits as ``logits`` with every weight
    in memory, whether a slab holds all the tokens or cuts sequences."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = manifest.Cell("tiny-falcon-h1-serve").reference
    cfg = _config("tiny-falcon-h1")
    key = jax.random.PRNGKey(3)
    weights = ref.make_weights(cfg, key)
    # The head and the embedding whole are their blocks side by side.
    np.testing.assert_array_equal(
        weights["head"][8:16], ref.head_block(cfg, key, 1))
    np.testing.assert_array_equal(
        weights["embed"][128:192], ref.embed_block(cfg, key, 2))
    rng = np.random.default_rng(0)
    sequences = [list(rng.integers(0, 512, n)) for n in (50, 17, 90, 33)]
    slab = ref.SLAB
    assert ref.padded_lengths(cfg) == [64, 128]
    assert ref.padded_lengths(_config("falcon-h1-34b")) == [1536, 3072]
    try:
        for ref.SLAB in (slab, 40):
            hidden = ref._layer_by_layer(cfg, key)(sequences, "f32")
            for tokens, h in zip(sequences, hidden):
                got = ref._head_step(h, key, cfg=cfg, precision="f32")
                want = ref.logits(weights, jnp.asarray(tokens), cfg)
                # float32 both ways: summation order under two jits, on
                # logits of ~1.
                np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)
    finally:
        ref.SLAB = slab
