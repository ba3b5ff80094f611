"""The Trinity cell's part of the yardstick: the rehearsal cell decides
``correct`` both ways, the byte counts against hand-worked numbers, and
the two roofline readers on hand-made traces."""

import json
import os
import time

import pytest

import run as bench_run
from harness import manifest, moebytes, spans

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


def test_trinity_mini_keeps_every_published_width():
    cfg = _config("trinity-mini")
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "intermediate_size": 6144,
        "moe_intermediate_size": 1024, "num_experts": 128,
        "num_experts_per_tok": 8, "num_shared_experts": 1,
        "sliding_window": 2048, "vocab_size": 200192, "route_scale": 2.826,
        "route_norm": True, "score_func": "sigmoid", "rope_theta": 10000,
        "rms_norm_eps": 1e-05, "mup_enabled": True,
        "tie_word_embeddings": False,
    }
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == ["layer_types", "max_position_embeddings",
                                      "num_dense_layers", "num_hidden_layers"]
    # The kept dense layer, then one whole period: 3 window : 1 full.
    assert cfg["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"]) == (5, 1)
    assert cfg["assumed"] and "layers 1 and 4-7 of 32" in cfg["deployment"]


def test_the_cell_serves_the_issues_traffic():
    cell = manifest.Cell("trinity-mini-serve")
    assert cell.chips == 1 and cell.config_name == "trinity-mini"
    assert cell.spec["engine"] == {"slots": 64, "block_size": 512,
                                   "max_len": 8704, "max_queue": 4096}
    mix = cell.spec["traffic"]
    assert mix["prompt"] == {"median": 1024, "sigma": 1.0, "min": 64,
                             "max": 8192}
    assert mix["answer"] == {"median": 128, "sigma": 0.7, "min": 16,
                             "max": 512}
    assert (mix["max_total"], mix["burst"], mix["pairing_seed"],
            mix["schedule_seed"]) == (8704, 1, 20260928, 7)
    reported = {m["name"] for m in cell.end_to_end()}
    assert reported == {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    layers = {m["name"] for m in cell.per_layer()}
    assert {"moe_device_pct", "moe_weight_stream_roofline",
            "paged_decode_roofline", "experts_touched_pct",
            "expert_load_max_over_mean", "kv_window_blocks_pct",
            "kv_blocks_read_pct", "decode_step_device_ms"} <= layers
    assert not {"kv_gather_device_pct", "flash_attention_roofline"} & layers


def _run(name, **driver_args):
    result, _ = bench_run.run_cell(
        manifest.Cell(name), seed=2_147_483_777, seconds=1.5, trace=False,
        phases=bench_run.Phases(time.perf_counter()), **driver_args,
    )
    return result


@pytest.mark.parametrize("broken,correct", [(None, True),
                                            ("token_altered", False)])
def test_rehearsal_cell_decides_correct_both_ways(broken, correct):
    result = _run("tiny-trinity-serve", broken=broken)
    assert result["correct"] is correct, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert all(k.startswith("cpu_rehearsal.") for k in result["metrics"])


def test_rehearsal_cell_fails_the_reference_in_fp8():
    """The configuration states bfloat16: the control is the reference
    computed in fp8, and it comes out as not correct. (Sound readings of
    the rehearsal size over seeds: 0 to 1.2e-3; the limit is 3.5e-3;
    this control 1.3e-2.)"""
    result = _run("tiny-trinity-serve", control="fp8")
    assert result["correct"] is True, result["compared"]
    row = result["control"]["served_logit_gap_mean"]
    assert row["value"] > row["limit"], row


# ---------------------------------------------------------------------------
# Bytes that have to move, by hand
# ---------------------------------------------------------------------------


def test_expert_and_cache_bytes_by_hand():
    cfg = _config("trinity-mini")
    # One expert: 3 matrices of 2,048 x 1,024 bfloat16 = 12,582,912 B.
    assert moebytes.expert_bytes(cfg) == 3 * 2048 * 1024 * 2 == 12_582_912
    assert moebytes.expert_layers(cfg) == 4
    # Every cell of 4 layers x 128 experts touched: 6.44 GB a tick.
    assert moebytes.touched_expert_bytes(cfg, 100.0) == 512 * 12_582_912
    assert moebytes.touched_expert_bytes(cfg, 25.0) == 128 * 12_582_912
    # A block of one layer: 512 positions x 4 heads x 128 x 2 B, K and V.
    assert moebytes.kv_block_bytes(cfg, 512) == 2 * 512 * 512 * 2 == 1_048_576
    # 64 slots: four window layers a ring of ceil((2,048 + 512) / 512) = 5
    # blocks, the full layer 8,704 / 512 = 17.
    engine = {"slots": 64, "block_size": 512, "max_len": 8704}
    assert moebytes.kv_tabled_blocks(cfg, engine) == 64 * (4 * 5 + 17)
    # A model without a window: every layer its whole table.
    dense = dict(cfg, sliding_window=None,
                 layer_types=["full_attention"] * 5)
    assert moebytes.kv_tabled_blocks(dense, engine) == 64 * 5 * 17


# ---------------------------------------------------------------------------
# The roofline readers, on hand-made traces
# ---------------------------------------------------------------------------


class _Cell:
    name = "no-such-cell"
    config = None
    spec = {"engine": {"slots": 64, "block_size": 512, "max_len": 8704}}


def _ctx(monkeypatch, host, rows, modules):
    loaded = {"window_ns": 1000 * MS, "host": host, "device": []}
    monkeypatch.setattr(spans, "for_cell", lambda ctx: loaded)
    cell = _Cell()
    cell.config = _config("trinity-mini")
    return {"cell": cell, "peaks": PEAKS,
            "trace": {"rows": rows, "modules": modules, "busy_s": 1.0}}


def test_moe_stream_roofline_counts_the_decode_ticks_only(monkeypatch):
    read = _reader("moe_stream_roofline").read
    host = [("serve.decode.deliver", (10 + 20 * i) * MS, MS, "py",
             {"experts_touched_pct": 50.0}) for i in range(2)]
    # Two decode ticks of 10 ms; a prefill between them.
    modules = [("jit_step(1)", 0, 10 * MS), ("jit_prefill(2)", 10 * MS, 5 * MS),
               ("jit_step(1)", 20 * MS, 10 * MS)]
    rows = [("ragged-dot-none.1", "", 1 * MS, 4 * MS),
            ("fusion.3", "", 5 * MS, 2 * MS),
            ("ragged-dot-none.7", "", 11 * MS, 3 * MS),  # the prefill's
            ("ragged-dot-none.1", "", 21 * MS, 4 * MS)]
    ctx = _ctx(monkeypatch, host, rows, modules)
    # 256 of 512 (layer, expert) cells a tick, 12,582,912 B each, over
    # 819 GB/s: 3.933 ms a tick against 4 ms of grouped matmuls.
    ideal = 256 * 12_582_912 / 819e9
    assert read(ctx, "^ragged-dot", "jit_step") == pytest.approx(
        100.0 * ideal / 4e-3)
    # A program without expert layers: no such span argument, no reading.
    bare = [(n, s, d, t, {}) for n, s, d, t, _ in host]
    assert read(_ctx(monkeypatch, bare, rows, modules), "^ragged-dot",
                "jit_step") is None
    assert read(_ctx(monkeypatch, host, rows[1:2], modules), "^ragged-dot",
                "jit_step") is None


def test_paged_decode_roofline_counts_live_blocks(monkeypatch):
    read = _reader("paged_decode_roofline").read
    host = [("serve.decode.prepare", 10 * MS * i, MS, "py",
             {"active": 8, "live_blocks_pct": 10.0}) for i in range(3)]
    # One call a layer a tick: two ticks of five layers, 0.2 ms a call.
    rows = [("paged_decode_attention.%d" % i, "", i * MS, 200_000)
            for i in range(10)]
    ctx = _ctx(monkeypatch, host, rows, [])
    # 10% of 64 x 37 layer-blocks, 1 MiB each (K and V), two ticks.
    ideal = 2 * 0.10 * 64 * 37 * 1_048_576 / 819e9
    assert read(ctx, "^paged_decode_attention") == pytest.approx(
        100.0 * ideal / 2e-3)
    assert read(_ctx(monkeypatch, host, [], []),
                "^paged_decode_attention") is None
    bare = [(n, s, d, t, {"active": 8}) for n, s, d, t, _ in host]
    assert read(_ctx(monkeypatch, bare, rows, []),
                "^paged_decode_attention") is None
