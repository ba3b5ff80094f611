"""The Granite cell's part of the yardstick: the cell's letter against
ISSUE 37, the rehearsal cell deciding ``correct`` both ways, the state
bytes against hand-worked numbers, and the new roofline reader on a
hand-made trace."""

import json
import os
import time

import pytest

import run as bench_run
from harness import manifest, moebytes, spans, ssmbytes

MS = 1_000_000
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "granite-4.0-h-small-serve"
NEW = ("ssm_update_roofline", "ssm_update_device_pct", "ssm_states_read_pct")


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


def test_granite_keeps_every_published_width():
    cfg = _config("granite-4.0-h-small")
    published = {
        "hidden_size": 4096, "intermediate_size": 768,
        "shared_intermediate_size": 1536, "num_attention_heads": 32,
        "num_key_value_heads": 8, "num_experts_per_tok": 10,
        "mamba_n_heads": 128, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
        "mamba_chunk_size": 256, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "attention_bias": False,
        "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 16,
        "rms_norm_eps": 1e-05, "rope_theta": 10000, "rope_scaling": None,
        "position_embedding_type": "nope", "tie_word_embeddings": True,
        "normalization_function": "rmsnorm", "hidden_act": "silu",
        "model_type": "granitemoehybrid",
    }
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == [
        "layer_types", "max_position_embeddings", "num_experts",
        "num_hidden_layers", "num_local_experts", "vocab_size"]
    # The chip's share: one whole period (9 Mamba-2 layers around the
    # attention layer at 5), 18 of 72 experts under a router of the
    # published width, a quarter of the vocabulary.
    assert cfg["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"],
            cfg["num_routed_experts"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (10, 18, 72, 25088, 2560)
    assert cfg["published"]["num_local_experts"] == 72
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"] == 100352
    assert cfg["num_local_experts"] * 4 == cfg["published"]["num_local_experts"]
    # What the benchmark's byte counts read, under their own names.
    assert (cfg["num_experts"], cfg["moe_intermediate_size"],
            cfg["num_dense_layers"], cfg["param_dtype"],
            cfg["state_dtype"]) == (18, 768, 0, "bfloat16", "float32")
    for key in ("num_experts", "moe_intermediate_size", "num_dense_layers",
                "state_dtype", "in_proj_order", "gate_before_norm",
                "score_function", "mamba_init"):
        assert key in cfg["assumed"], key
    assert "16 chips" in cfg["deployment"]
    assert "a quarter of the rows" in cfg["deployment"]
    assert "Fewer layers make the host's turn" in cfg["deployment"]
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "granite-4.0-h-small")
    assert entry["file"] == "benchmarks/configs/granite-4.0-h-small.json"
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/"
        "config.json")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])


def test_the_cell_serves_the_issues_traffic():
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and cell.config_name == "granite-4.0-h-small"
    entry = next(w for w in manifest.load_manifest()["workloads"]
                 if w["name"] == CELL)
    assert entry["traffic"] == "short_chat_open_loop_0p8knee"
    # 160 slots pass 14.5 GB with the longest prefill's temporaries
    # (tests/test_tpu_compile.py): 128, as ISSUE 37 allows.
    assert cell.spec["engine"] == {"slots": 128, "block_size": 256,
                                   "max_len": 2560, "max_queue": 4096}
    mix = cell.spec["traffic"]
    assert mix["prompt"] == {"median": 384, "sigma": 1.0, "min": 32,
                             "max": 2048}
    assert mix["answer"] == {"median": 192, "sigma": 0.7, "min": 16,
                             "max": 512}
    assert (mix["max_total"], mix["burst"], mix["preroll_s"],
            mix["postroll_s"]) == (2560, 1, 12.0, 4.0)
    # 4/5 of 10.5/s, the highest rate tools/sweep_knee.py read without a
    # backlog on the chip (11/s: 14 queued at the stop; PERF.md section 6).
    assert mix["rate_per_s"] == 8.4
    assert cell.spec["trace"] == {"at_s": 5.0, "seconds": 3.0}
    assert cell.spec["reference"] == {"sample": 8}
    reported = {m["name"] for m in cell.end_to_end()}
    assert reported == {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    layers = {m["name"] for m in cell.per_layer()}
    assert {*NEW, "moe_weight_stream_roofline", "moe_device_pct",
            "experts_touched_pct", "expert_load_max_over_mean",
            "expert_weight_visits_per_touched", "kv_blocks_read_pct",
            "decode_context_tokens", "decode_ticks_in_flight",
            "decode_step_device_ms", "prefill_device_ms",
            "peak_hbm_gb.serve", "compiles_in_window.serve"} <= layers
    # What cannot count this configuration (a state layer is no K/V
    # layer, nothing here is latent or windowed).
    assert not {"paged_decode_roofline", "kv_window_blocks_pct",
                "kv_gather_device_pct", "latent_decode_roofline",
                "latent_decode_device_pct"} & layers
    # The new metrics are the new cell's alone.
    by_name = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "itl_p95_ms"


def _run(name, **driver_args):
    result, _ = bench_run.run_cell(
        manifest.Cell(name), seed=2_147_483_777, seconds=1.5, trace=False,
        phases=bench_run.Phases(time.perf_counter()), **driver_args,
    )
    return result


@pytest.mark.parametrize("broken,correct", [(None, True),
                                            ("token_altered", False)])
def test_rehearsal_cell_decides_correct_both_ways(broken, correct):
    result = _run("tiny-granite-serve", broken=broken)
    assert result["correct"] is correct, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert all(k.startswith("cpu_rehearsal.") for k in result["metrics"])


def test_rehearsal_cell_fails_the_reference_in_fp8():
    """The configuration states bfloat16 compute over a float32 state:
    the control is the reference computed in fp8, its state held so too,
    and it comes out as not correct."""
    result = _run("tiny-granite-serve", control="fp8")
    assert result["correct"] is True, result["compared"]
    row = result["control"]["served_logit_gap_mean"]
    assert row["value"] > row["limit"], row


# ---------------------------------------------------------------------------
# Bytes that have to move, by hand
# ---------------------------------------------------------------------------


def test_state_and_expert_bytes_by_hand():
    cfg = _config("granite-4.0-h-small")
    # One sequence, one Mamba layer: 128 heads x 64 x 128 float32 = 4.19
    # MB whatever the context; the attention layer's K and V are 8 x 128
    # x 2 x 2 = 4,096 B a TOKEN.
    assert ssmbytes.state_bytes(cfg) == 128 * 64 * 128 * 4 == 4_194_304
    assert ssmbytes.mamba_layers(cfg) == 9
    assert moebytes.kv_block_bytes(cfg | {"head_dim": 128}, 1) == 4096
    # A tick over 48 live sequences: each state read and written once a
    # layer, 3.6 GB.
    assert ssmbytes.state_update_bytes(cfg, 48) == 48 * 9 * 2 * 4_194_304
    assert ssmbytes.state_update_bytes(cfg, 48) == pytest.approx(3.62e9,
                                                                 rel=0.01)
    # The HELD experts: 3 matrices of 4,096 x 768 bfloat16 each, 10
    # expert layers x 18 cells (not the router's 72).
    assert moebytes.expert_bytes(cfg) == 3 * 4096 * 768 * 2 == 18_874_368
    assert moebytes.expert_layers(cfg) == 10
    assert moebytes.touched_expert_bytes(cfg, 100.0) == 180 * 18_874_368


# ---------------------------------------------------------------------------
# The roofline reader, on a hand-made trace
# ---------------------------------------------------------------------------


class _Cell:
    name = "no-such-cell"
    config = None
    spec = {"engine": {"slots": 128, "block_size": 256, "max_len": 2560}}


def _ctx(monkeypatch, host, rows):
    loaded = {"window_ns": 1000 * MS, "host": host, "device": []}
    monkeypatch.setattr(spans, "for_cell", lambda ctx: loaded)
    cell = _Cell()
    cell.config = _config("granite-4.0-h-small")
    return {"cell": cell, "peaks": PEAKS,
            "trace": {"rows": rows, "modules": [], "busy_s": 1.0}}


def test_ssm_update_roofline_counts_the_live_states(monkeypatch):
    read = _reader("ssm_update_roofline").read
    pattern = "^ssm_state_update"
    # One call a Mamba layer a tick: two ticks of nine layers, 0.5 ms a
    # call; other operations are not the kernel's.
    rows = [("ssm_state_update.%d" % i, "", i * MS, MS // 2)
            for i in range(18)] + [("fusion.7", "", 20 * MS, 5 * MS)]

    def host(live_pct):
        return [("serve.decode.prepare", 10 * MS * i, MS, "py",
                 {"active": 48, "live_blocks_pct": 20.0,
                  "context_tokens": 1000, "live_states_pct": live_pct})
                for i in range(3)]

    # 37.5% of 128 states live = 48: 48 x 9 x 2 x 4.19 MB a tick.
    ctx = _ctx(monkeypatch, host(37.5), rows)
    ideal = 2 * 48 * 9 * 2 * 4_194_304 / 819e9
    assert read(ctx, pattern) == pytest.approx(100.0 * ideal / 9e-3)
    assert read(ctx, pattern) < 100.0
    # A program without the kernel, or without the span argument (the
    # parent of the PR that added them): None, and no exception.
    assert read(_ctx(monkeypatch, host(37.5), rows[-1:]), pattern) is None
    bare = [(n, s, d, t, {"active": 48, "live_blocks_pct": 20.0})
            for n, s, d, t, _ in host(37.5)]
    assert read(_ctx(monkeypatch, bare, rows), pattern) is None
    assert read({"cell": _Cell(), "peaks": None, "trace": None},
                pattern) is None


def test_reference_layer_by_layer_in_slabs_equals_its_full_forward():
    """``served_gaps`` applies the weights a layer at a time, the mixer a
    padded sequence at a time and the feed-forward over all sequences'
    real tokens in slabs: the same logits as ``logits`` with every weight
    in memory, whether a slab holds all the tokens or cuts sequences."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = manifest.Cell("tiny-granite-serve").reference
    cfg = _config("tiny-granite")
    key = jax.random.PRNGKey(3)
    weights = ref.make_weights(cfg, key)
    rng = np.random.default_rng(0)
    sequences = [list(rng.integers(0, 512, n)) for n in (50, 17, 90, 33)]
    slab = ref.SLAB
    assert ref.padded_lengths(cfg) == [64, 128]
    assert ref.padded_lengths(_config("granite-4.0-h-small")) == [
        1280, 2560]
    try:
        for ref.SLAB in (slab, 40):
            hidden = ref._layer_by_layer(cfg, key)(sequences, "f32")
            for tokens, h in zip(sequences, hidden):
                got = ref.head(h, weights, cfg)
                want = ref.logits(weights, jnp.asarray(tokens), cfg)
                # float32 both ways: summation order under two jits,
                # on logits of ~0.01.
                np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    finally:
        ref.SLAB = slab
