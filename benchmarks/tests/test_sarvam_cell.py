"""The Sarvam cell's part of the yardstick: the cell's letter against
ISSUE 35, the rehearsal cell deciding ``correct`` both ways, the latent
bytes and operations against hand-worked numbers, and the new roofline
reader on a hand-made trace."""

import json
import os
import time

import pytest

import run as bench_run
from harness import manifest, mlabytes, moebytes, spans

MS = 1_000_000
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


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


def test_sarvam_105b_keeps_every_published_width():
    cfg = _config("sarvam-105b")
    published = {
        "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 576,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "q_head_dim": 192, "v_head_dim": 128, "intermediate_size": 16384,
        "moe_intermediate_size": 2048, "num_experts_per_tok": 8,
        "num_shared_experts": 1, "first_k_dense_replace": 1,
        "routed_scaling_factor": 2.5, "rope_theta": 10000,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "use_qk_norm": True, "moe_router_enable_expert_bias": True,
        "model_type": "sarvam_mla", "hidden_act": "silu",
    }
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "deepseek_yarn"}
    assert sorted(cfg["reduced"]) == [
        "max_position_embeddings", "num_experts", "num_hidden_layers",
        "vocab_size"]
    # The chip's share: 1 + 4 layers, 16 of 128 experts under a router of
    # the published width, an eighth of the vocabulary.
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["num_routed_experts"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (5, 16, 128, 32768, 17408)
    assert cfg["published"] == {
        "num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144,
        "max_position_embeddings": 131072}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["assumed"] and "8 chips" in cfg["deployment"]
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "sarvam-105b")
    assert entry["file"] == "benchmarks/configs/sarvam-105b.json"
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])


def test_the_cell_serves_the_issues_traffic():
    cell = manifest.Cell("sarvam-105b-serve")
    assert cell.chips == 1 and cell.config_name == "sarvam-105b"
    # 64 slots pass 14.5 GB with the longest prefill's temporaries
    # (tests/test_tpu_compile.py): 48, as ISSUE 35 allows.
    assert cell.spec["engine"] == {"slots": 48, "block_size": 1024,
                                   "max_len": 17408, "max_queue": 4096}
    mix = cell.spec["traffic"]
    assert mix["prompt"] == {"median": 4096, "sigma": 1.0, "min": 256,
                             "max": 16384}
    assert mix["answer"] == {"median": 256, "sigma": 0.7, "min": 16,
                             "max": 1024}
    assert (mix["max_total"], mix["burst"], mix["preroll_s"],
            mix["postroll_s"]) == (17408, 1, 12.0, 4.0)
    assert cell.spec["trace"] == {"at_s": 5.0, "seconds": 3.0}
    assert cell.spec["reference"] == {"sample": 8}
    reported = {m["name"] for m in cell.end_to_end()}
    assert reported == {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    layers = {m["name"] for m in cell.per_layer()}
    assert {"latent_decode_roofline", "latent_decode_device_pct",
            "decode_context_tokens", "moe_weight_stream_roofline",
            "moe_device_pct", "experts_touched_pct",
            "expert_load_max_over_mean", "expert_weight_visits_per_touched",
            "kv_blocks_read_pct", "decode_step_device_ms",
            "prefill_device_ms", "peak_hbm_gb.serve",
            "compiles_in_window.serve"} <= layers
    assert not {"paged_decode_roofline", "kv_window_blocks_pct",
                "kv_gather_device_pct", "flash_attention_roofline"} & layers
    # The new metrics are the new cell's alone; the old cells' lists
    # gained nothing else.
    for metric in manifest.load_manifest()["per_layer"][-3:]:
        assert metric["workloads"] == ["sarvam-105b-serve"]


def _run(name, **driver_args):
    result, _ = bench_run.run_cell(
        manifest.Cell(name), seed=2_147_483_777, seconds=1.5, trace=False,
        phases=bench_run.Phases(time.perf_counter()), **driver_args,
    )
    return result


@pytest.mark.parametrize("broken,correct", [(None, True),
                                            ("token_altered", False)])
def test_rehearsal_cell_decides_correct_both_ways(broken, correct):
    result = _run("tiny-sarvam-serve", broken=broken)
    assert result["correct"] is correct, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert all(k.startswith("cpu_rehearsal.") for k in result["metrics"])


def test_rehearsal_cell_fails_the_reference_in_fp8():
    """The configuration states bfloat16: the control is the reference
    computed in fp8, and it comes out as not correct. (Sound readings of
    the rehearsal size over six seeds: 0 to 1.7e-5; the limit is 1.5e-4;
    this control 5.1e-4 to 1.1e-3.)"""
    result = _run("tiny-sarvam-serve", control="fp8")
    assert result["correct"] is True, result["compared"]
    row = result["control"]["served_logit_gap_mean"]
    assert row["value"] > row["limit"], row


# ---------------------------------------------------------------------------
# Bytes and operations that have to move, by hand
# ---------------------------------------------------------------------------


def test_latent_and_expert_bytes_by_hand():
    cfg = _config("sarvam-105b")
    # One token, one layer: (512 + 64) bfloat16 = 1,152 B; K and V of 64
    # heads uncompressed would be 64 x (192 + 128) x 2 = 40,960 B.
    assert mlabytes.latent_row_bytes(cfg) == 1152
    assert mlabytes.latent_block_bytes(cfg, 1024) == 1024 * 1152
    engine = {"slots": 48, "block_size": 1024, "max_len": 17408}
    assert mlabytes.latent_tabled_blocks(cfg, engine) == 48 * 5 * 17
    # 10,000 cached positions, five layers, 64 heads, a row as key (576)
    # and as value (512), 2 operations a multiply-add.
    assert mlabytes.latent_decode_flops(cfg, 10_000) == (
        5 * 10_000 * 64 * (576 + 512) * 2)
    # 121 operations a byte: between streaming and the MXU's ridge (240).
    assert 64 * (576 + 512) * 2 / 1152 == pytest.approx(120.9, abs=0.1)
    # The HELD experts: 3 matrices of 4,096 x 2,048 bfloat16 each, 4
    # expert layers x 16 cells (not the router's 128).
    assert moebytes.expert_bytes(cfg) == 3 * 4096 * 2048 * 2 == 50_331_648
    assert moebytes.expert_layers(cfg) == 4
    assert moebytes.touched_expert_bytes(cfg, 100.0) == 64 * 50_331_648


# ---------------------------------------------------------------------------
# The roofline reader, on a hand-made trace
# ---------------------------------------------------------------------------


class _Cell:
    name = "no-such-cell"
    config = None
    spec = {"engine": {"slots": 48, "block_size": 1024, "max_len": 17408}}


def _ctx(monkeypatch, host, rows):
    loaded = {"window_ns": 1000 * MS, "host": host, "device": []}
    monkeypatch.setattr(spans, "for_cell", lambda ctx: loaded)
    cell = _Cell()
    cell.config = _config("sarvam-105b")
    return {"cell": cell, "peaks": PEAKS,
            "trace": {"rows": rows, "modules": [], "busy_s": 1.0}}


def test_latent_decode_roofline_takes_the_larger_bound(monkeypatch):
    read = _reader("latent_decode_roofline").read
    pattern = "^paged_latent_decode"
    # One call a layer a tick: two ticks of five layers, 1 ms a call.
    rows = [("paged_latent_decode.%d" % i, "", i * 2 * MS, MS)
            for i in range(10)]

    def host(live_pct, context):
        return [("serve.decode.prepare", 10 * MS * i, MS, "py",
                 {"active": 30, "live_blocks_pct": live_pct,
                  "context_tokens": context}) for i in range(3)]

    # Memory-bound: 25% of 48 x 5 x 17 layer-blocks of 1,024 x 1,152 B.
    ctx = _ctx(monkeypatch, host(25.0, 100_000), rows)
    memory = 0.25 * 4080 * 1024 * 1152 / 819e9
    compute = 5 * 100_000 * 64 * 1088 * 2 / 197e12
    assert memory > compute
    assert read(ctx, pattern) == pytest.approx(100.0 * 2 * memory / 10e-3)
    # Compute-bound: the same blocks, full to their last row and more
    # positions than they could hold (the reader takes what it is told).
    ctx = _ctx(monkeypatch, host(25.0, 2_500_000), rows)
    compute = 5 * 2_500_000 * 64 * 1088 * 2 / 197e12
    assert compute > memory
    assert read(ctx, pattern) == pytest.approx(100.0 * 2 * compute / 10e-3)
    # A program without the kernel, or without the span argument: None.
    assert read(_ctx(monkeypatch, host(25.0, 1000), []), pattern) is None
    bare = [(n, s, d, t, {"active": 30, "live_blocks_pct": 25.0})
            for n, s, d, t, _ in host(25.0, 1000)]
    assert read(_ctx(monkeypatch, bare, rows), pattern) is None


def test_reference_layer_by_layer_in_slabs_equals_its_full_forward():
    """``served_gaps`` applies the weights a layer at a time, attention a
    padded sequence at a time and the feed-forward over all sequences'
    real tokens in slabs: the same logits as ``logits`` with every weight
    in memory, whether a slab holds all the tokens or cuts sequences."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = manifest.Cell("tiny-sarvam-serve").reference
    cfg = _config("tiny-sarvam")
    key = jax.random.PRNGKey(3)
    weights = ref.make_weights(cfg, key)
    rng = np.random.default_rng(0)
    sequences = [list(rng.integers(0, 512, n)) for n in (50, 17, 90, 33)]
    pad, slab = ref.PAD, ref.SLAB
    try:
        ref.PAD = 32
        for ref.SLAB in (slab, 40):
            hidden = ref._layer_by_layer(cfg, key)(sequences, "f32")
            for tokens, h in zip(sequences, hidden):
                x = ref._rms_norm(h, weights["norm_out"], cfg["rms_norm_eps"])
                got = ref._mm("td,dv->tv", x, weights["head"], "f32")
                want = ref.logits(weights, jnp.asarray(tokens), cfg)
                # float32 both ways: summation order under two jits.
                np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)
    finally:
        ref.PAD, ref.SLAB = pad, slab
