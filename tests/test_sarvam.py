"""Latent attention (``sarvam_mla``) through the configuration-driven
decoder LM, its cache kind and the serving engine, against the plain
reference the benchmark keeps (``benchmarks/configs/sarvam.reference.py``:
float32 at ``highest``, un-absorbed attention, dense over the experts
held), at a small size on seeded random weights.
"""

import importlib.util
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _oracles import poisoned_past_the_groups
from fluxmpi_tpu.models import DecoderConfig, ExpertMLP, Keeps
from fluxmpi_tpu.models import decoder as decoder_mod
from fluxmpi_tpu.models.decoder import LatentAttention
from fluxmpi_tpu.serving import InferenceEngine
from fluxmpi_tpu.serving.cache import BlockKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmarks", "configs")
BLOCK = 8


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(CONFIGS, name)
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("sarvam.reference.py")
prog = _load("sarvam.program.py")


def _json(name):
    with open(os.path.join(CONFIGS, f"{name}.json"), encoding="utf-8") as f:
        return json.load(f)


def _cfg(**changes):
    """The rehearsal configuration (4 heads, latent 32 + rotary 8, 4 of
    16 experts held, top-4, one shared), float32 compute so that the
    comparison is tight."""
    cfg = _json("tiny-sarvam")
    cfg.update({"compute_dtype": "float32", **changes})
    return cfg


def _model_and_weights(cfg, seed=3):
    weights = ref.make_weights(cfg, jax.random.PRNGKey(seed))
    variables, _ = prog.to_program(weights, cfg)
    return prog.build_model(cfg, "naive"), variables, weights


# ---------------------------------------------------------------------------
# (a) the configuration: both models' key names, the YaRN numbers by hand
# ---------------------------------------------------------------------------


def test_from_hf_maps_the_sarvam_keys_and_leaves_trinity_as_it_was():
    c = DecoderConfig.from_hf(_json("sarvam-105b"))
    assert c.layer_types == ("latent_attention",) * 5
    assert (c.num_dense_layers, c.route_scale) == (1, 2.5)
    assert (c.num_experts, c.num_routed_experts) == (16, 128)
    assert (c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim, c.head_dim, c.latent_row) == (512, 128, 64, 128,
                                                        192, 576)
    assert (c.norm_placement, c.output_gate, c.mup_enabled) == (
        "pre", False, False)
    assert dict(c.rope_scaling)["type"] == "deepseek_yarn"
    hash(c)  # a module field
    t = DecoderConfig.from_hf(_json("trinity-mini"))
    assert (t.norm_placement, t.output_gate, t.num_routed_experts,
            t.rope_scaling, t.kv_lora_rank) == ("sandwich", True, 0, None, 0)
    base = dict(vocab_size=8, hidden_size=8, num_attention_heads=2,
                num_key_value_heads=2, head_dim=4, intermediate_size=8)
    with pytest.raises(ValueError, match="need kv_lora_rank"):
        DecoderConfig(layer_types=("latent_attention",), **base)
    with pytest.raises(ValueError, match="norm_placement"):
        DecoderConfig(layer_types=("full_attention",), norm_placement="post",
                      **base)
    with pytest.raises(ValueError, match="rope_scaling type"):
        DecoderConfig(layer_types=("full_attention",),
                      rope_scaling=(("type", "linear"),), **base)
    with pytest.raises(ValueError, match="cannot hold"):
        DecoderConfig(layer_types=("full_attention",), num_experts=8,
                      num_routed_experts=4, **base)


@pytest.mark.parametrize("side", ["program", "reference"])
def test_yarn_scale_and_angles_against_hand_values(side):
    cfg = _json("sarvam-105b")
    if side == "program":
        freq, trig, scale = decoder_mod.latent_scales(
            DecoderConfig.from_hf(cfg))
    else:
        freq, trig, scale = ref.yarn(cfg)
    # m = 0.1 * 1 * ln(40) + 1 = 1.36889; s = 192 ** -0.5 * m ** 2.
    assert 0.1 * math.log(40) + 1 == pytest.approx(1.3689, abs=1e-4)
    assert scale == pytest.approx(0.13523, abs=1e-5)
    assert trig == 1.0  # mscale / mscale_all_dim
    # 32 pairs over the 64 rotary dims. A pair whose wavelength makes 32
    # turns in 4,096 positions: 64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4) =
    # 10.47 -> 10; one turn: 22.51 -> 23. Up to pair 10 the plain angle,
    # from 23 on a fortieth, pair 16 six thirteenths of the way.
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)
    assert len(freq) == 32
    np.testing.assert_allclose(freq[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(freq[23:], plain[23:] / 40, rtol=1e-12)
    ramp = 6 / 13
    assert freq[16] == pytest.approx(
        plain[16] * (1 - ramp) + plain[16] / 40 * ramp, rel=1e-12)
    assert plain[16] == pytest.approx(0.01, rel=1e-12)
    # Without rope_scaling: plain angles and 192 ** -0.5.
    bare = dict(cfg, rope_scaling=None)
    got = (decoder_mod.latent_scales(DecoderConfig.from_hf(bare))
           if side == "program" else ref.yarn(bare))
    np.testing.assert_allclose(got[0], plain, rtol=1e-12)
    assert got[1:] == (1.0, pytest.approx(192 ** -0.5))


def test_rotary_pairs_rotate_consecutive_lanes_where_they_lie():
    cfg = _cfg()
    freq, trig, _ = ref.yarn(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 37, 3, 8))
    positions = jnp.arange(37)[None]
    got = decoder_mod._rotary_pairs(x, positions, freq, trig)
    want = ref._rotary(x[0], freq, trig)
    np.testing.assert_allclose(got[0], want, rtol=0, atol=1e-5)
    # Lanes (0, 1) by hand, position 5, the first frequency.
    a, b, t = x[0, 5, 0, 0], x[0, 5, 0, 1], 5 * float(freq[0])
    np.testing.assert_allclose(
        got[0, 5, 0, :2],
        [a * math.cos(t) - b * math.sin(t), b * math.cos(t) + a * math.sin(t)],
        atol=1e-5)
    # bfloat16 input: the partner lane is exact, the result float32.
    half = decoder_mod._rotary_pairs(x.astype(jnp.bfloat16), positions,
                                     freq, trig)
    assert half.dtype == jnp.float32
    np.testing.assert_allclose(
        half, decoder_mod._rotary_pairs(
            x.astype(jnp.bfloat16).astype(jnp.float32), positions, freq,
            trig), rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# (b) one layer: absorbed = un-absorbed = the reference
# ---------------------------------------------------------------------------


class _DenseRows:
    """A latent sublayer's handle that reads a pool: every position's row
    of one sequence, handed over whole; position ``t``'s absorbed queries
    meet rows ``0 .. t`` (what the paged kernel does block by block)."""

    kind, reads_pool = "latent", True

    def __init__(self, rows, rank):
        self.rows, self.rank = rows, rank  # [seq, width]

    def attend_absorbed(self, q_abs, q_rope, row):
        t = q_abs.shape[0]  # one token a "slot": [seq, 1, heads, .]
        q = jnp.concatenate([q_abs, q_rope], axis=-1)[:, 0]
        s = jnp.einsum("thc,kc->thk", q, self.rows)
        s = jnp.where(jnp.arange(t)[None, None, :] <= jnp.arange(t)[:, None,
                                                                    None],
                      s, -jnp.inf)
        out = jnp.einsum("thk,kc->thc", jax.nn.softmax(s, axis=-1),
                         self.rows[:, :self.rank])
        return out[:, None]


@pytest.mark.parametrize("scaling", ["yarn", "plain"])
def test_latent_layer_absorbed_equals_unabsorbed_equals_reference(scaling):
    cfg = _cfg() if scaling == "yarn" else _cfg(rope_scaling=None)
    config = DecoderConfig.from_hf(cfg)
    w = ref.layer_weights(cfg, jax.random.PRNGKey(5), 1)
    params = {"params": {k: w[k] for k in prog.ATTENTION}}
    seq = 45
    u = jax.random.normal(jax.random.PRNGKey(6), (seq, cfg["hidden_size"]))
    positions = jnp.arange(seq)[None]
    want = ref.attention(u, w, cfg)
    for mode in ("naive", "flash"):
        layer = LatentAttention(config, jnp.float32, mode)
        got = layer.apply(params, u[None], positions)[0]
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-6,
                                   err_msg=mode)

    # The rows a cache would keep: what the prefill's handle is handed.
    class Keep:
        kind, reads_pool = "latent", False

        def attend(self, q, k, v, row):
            self.row = row
            assert q.shape[-1] == k.shape[-1] == 24 and v.shape[-1] == 16
            return decoder_mod.causal_attention(q, k, v, window=None,
                                                mode="naive")

    kept = Keep()
    LatentAttention(config, jnp.float32).apply(
        params, u[None], positions, kept)
    rows = kept.row[0]
    assert rows.shape == (seq, config.latent_row)
    # Absorbed: every position a batch row of one token at its position.
    absorbed = LatentAttention(config, jnp.float32).apply(
        params, u[:, None], jnp.arange(seq)[:, None],
        _DenseRows(rows, config.kv_lora_rank))[:, 0]
    np.testing.assert_allclose(absorbed, want, rtol=0, atol=2e-6)


# ---------------------------------------------------------------------------
# (c) the model against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("attention", ["naive", "flash"])
@pytest.mark.parametrize("layers,dense", [(3, 1), (2, 0), (2, 2)],
                         ids=["dense_then_experts", "experts", "dense"])
def test_sarvam_logits_match_the_reference(layers, dense, attention):
    cfg = _cfg(num_hidden_layers=layers, first_k_dense_replace=dense)
    model, variables, weights = _model_and_weights(cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 48), 0, cfg["vocab_size"]
    )
    got = model.clone(attention=attention).apply(variables, tokens)
    want = jnp.stack([ref.logits(weights, row, cfg) for row in tokens])
    assert got.dtype == jnp.float32
    # float32 on both sides: what is left is summation order.
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    at = jnp.asarray([3, tokens.shape[1] - 1])
    last = model.apply(variables, tokens, head_at=at)
    np.testing.assert_allclose(
        last, want[jnp.arange(2), at], rtol=0, atol=2e-5
    )


def test_sarvam_parameter_tree_and_cache_layers():
    cfg = _cfg()
    model, variables, _ = _model_and_weights(cfg)
    made = jax.eval_shape(
        lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0),
    )
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: x.shape, tree)
    assert shapes(made) == shapes(variables)
    layer = variables["params"]["layer_1"]
    # Two norms a layer, no gate, the experts HELD under a router of 16.
    assert sorted(layer) == ["attn", "moe", "norm_in", "norm_pre_ff"]
    assert sorted(layer["attn"]) == ["kv_norm", "wkva", "wkvb", "wo", "wq"]
    assert layer["moe"]["router"].shape == (64, 16)
    assert layer["moe"]["w1"].shape == (4, 64, 32)
    # One row of 32 + 8 a token, no K/V heads, no window.
    assert model.cache_layers() == (Keeps("latent", width=40),) * 3


def test_sarvam_runs_in_bfloat16_and_a_lower_precision_is_further_off():
    cfg = _cfg(compute_dtype="bfloat16")
    model, variables, weights = _model_and_weights(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 40), 0, 512)
    got = model.apply(variables, tokens)
    want = ref.logits(weights, tokens[0], cfg)
    assert got.dtype == jnp.float32
    bf16 = float(jnp.mean(jnp.abs(got[0] - want)))
    # The program in bfloat16 sits where the reference in bfloat16 sits;
    # the reference in fp8 (the control) is several times further.
    fp8 = float(jnp.mean(jnp.abs(
        ref.logits(weights, tokens[0], cfg, "fp8") - want)))
    assert bf16 < 0.02 and fp8 > 3 * bf16, (bf16, fp8)


# ---------------------------------------------------------------------------
# (d) the expert layer: shares of a wider router, slabs
# ---------------------------------------------------------------------------


def _expert_layer(cfg, expert_range, include_shared=True):
    return ExpertMLP(
        num_experts=cfg["num_routed_experts"], top_k=cfg["num_experts_per_tok"],
        width=cfg["moe_intermediate_size"],
        shared_width=cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        route_scale=cfg["routed_scaling_factor"], expert_range=expert_range,
        include_shared=include_shared, dtype=jnp.float32,
    )


def _layer_params(w, lo, hi):
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    return {
        "router": f32(w["router"]), "bias": f32(w["bias"]),
        "w1": f32(w["ew1"][lo:hi]), "w3": f32(w["ew3"][lo:hi]),
        "w2": f32(w["ew2"][lo:hi]),
        "shared": {k: f32(w[k]) for k in ("w1", "w3", "w2")},
    }


def test_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The deployment's cut: eight chips hold 2 of the 16 experts each
    (the rehearsal's eighths), every chip the shared expert. Their parts,
    the shared expert counted once, sum to what the reference gives for
    the whole layer; the FIRST share is what the cut reference gives."""
    whole = _cfg(num_experts=16)
    w = ref.layer_weights(whole, jax.random.PRNGKey(5), 1)
    w["bias"] = w["bias"].at[2].set(4.0).at[5].set(-4.0)  # uneven routing
    u = jax.random.normal(jax.random.PRNGKey(6), (48, whole["hidden_size"]))
    total, parts = 0.0, []
    for lo in range(0, 16, 2):
        layer = _expert_layer(whole, (lo, lo + 2), include_shared=lo == 0)
        part, state = layer.apply(
            {"params": _layer_params(w, lo, lo + 2)}, u,
            mutable=["intermediates"])
        parts.append(part)
        total = total + part
        held = np.asarray(state["intermediates"]["expert_tokens"][0])
        assert held.shape == (2,)  # the pairs of the experts HELD only
        if lo == 2:
            assert held[0] == u.shape[0]  # expert 2: every token
        if lo == 4:
            assert held[1] == 0  # expert 5: none
    np.testing.assert_allclose(total, ref.expert_layer(u, w, whole),
                               rtol=0, atol=2e-5)
    cut = _cfg(num_experts=2)
    first = {k: (v[:2] if k in ("ew1", "ew3", "ew2") else v)
             for k, v in w.items()}
    np.testing.assert_allclose(parts[0], ref.expert_layer(u, first, cut),
                               rtol=0, atol=2e-5)


@pytest.mark.parametrize("steer", ["uneven", "all_to_one_share"])
def test_shares_read_no_row_past_their_own_pairs(steer, monkeypatch):
    """The same cut with the rows past each share's pairs poisoned: the
    parts still sum to the uncut layer. ``all_to_one_share``: every pair
    goes to experts 4-7, so two shares receive every pair between them
    and six receive nothing."""
    from fluxmpi_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(
        gm, "grouped_matmul", poisoned_past_the_groups(gm.grouped_matmul))
    whole = _cfg(num_experts=16)
    w = ref.layer_weights(whole, jax.random.PRNGKey(5), 1)
    if steer == "uneven":
        w["bias"] = w["bias"].at[2].set(4.0).at[5].set(-4.0)
    else:
        w["bias"] = jnp.where((jnp.arange(16) >= 4) & (jnp.arange(16) < 8),
                              50.0, 0.0)
    u = jax.random.normal(jax.random.PRNGKey(6), (48, whole["hidden_size"]))
    pairs = u.shape[0] * whole["num_experts_per_tok"]
    total, received = 0.0, []
    for lo in range(0, 16, 2):
        layer = _expert_layer(whole, (lo, lo + 2), include_shared=lo == 0)
        part, state = layer.apply(
            {"params": _layer_params(w, lo, lo + 2)}, u,
            mutable=["intermediates"])
        assert bool(jnp.all(jnp.isfinite(part)))
        total = total + part
        received.append(
            int(state["intermediates"]["expert_tokens"][0].sum()))
    assert sum(received) == pairs
    if steer == "all_to_one_share":
        assert received[2] + received[3] == pairs
    np.testing.assert_allclose(total, ref.expert_layer(u, w, whole),
                               rtol=0, atol=2e-5)


def test_tick_says_the_row_tiles_its_expert_layers_worked(monkeypatch):
    from fluxmpi_tpu.ops import grouped_matmul as gm
    from fluxmpi_tpu.telemetry import schema, tracing

    # 2 slots x top-4 = 8 rows a tick: four row tiles of 2.
    monkeypatch.setattr(gm, "_SUB_ROWS", 2)
    monkeypatch.setattr(gm, "_TILE_ROWS", 2)
    cfg = _cfg()
    model, variables, _ = _model_and_weights(cfg)
    assert model.expert_row_tiles(2, [0, 0]) == (0, 8)
    assert model.expert_row_tiles(2, [1, 8]) == (1 + 4, 8)
    assert model.expert_row_tiles(2, [3, 4]) == (2 + 2, 8)
    eng = InferenceEngine(model, variables, slots=2, block_size=BLOCK,
                          max_len=64, check_memory=False)
    try:
        tracer = tracing.Tracer(enabled=True)
        previous = tracing.set_tracer(tracer)
        try:
            eng.submit(np.arange(11, dtype=np.int32), 4)
            eng.submit(np.arange(20, dtype=np.int32), 4)
            eng.run()
        finally:
            tracing.set_tracer(previous)
        stats = eng.stats()
        spans = [e["args"] for e in tracer.export()["traceEvents"]
                 if e.get("name") == "serve.decode.deliver"]
    finally:
        eng.close()
    # Two expert layers, four tiles each, every tick; 4 of 16 experts
    # held: a tick's 8 pairs leave most tiles to the other shares.
    assert stats["expert_row_tiles"] == 8 * stats["decode_steps"]
    assert 0 < stats["expert_row_tiles_worked"] < stats["expert_row_tiles"]
    known = (set(schema.HOT_PATH_SPAN_ARGS["serve.decode.deliver"])
             | set(schema.HOT_PATH_SPAN_OPTIONAL_ARGS["serve.decode.deliver"]))
    assert all(set(a) <= known for a in spans)
    said = [a["expert_row_tiles_worked_pct"] for a in spans]
    assert len(said) == stats["decode_steps"]
    assert sum(said) / 100 * 8 == pytest.approx(
        stats["expert_row_tiles_worked"])
    assert stats["expert_row_tiles_worked"] <= -(
        -stats["expert_tokens"] // 2) + 2 * stats["decode_steps"]


def test_feed_forwards_take_long_prompts_in_equal_slabs(monkeypatch):
    # The rule: the fewest equal slabs that keep tokens x width x 4 B
    # under the limit. The cells served before this model stay whole.
    assert decoder_mod._slabs(8704, 8 * 2048) == 1  # trinity's longest
    assert decoder_mod._slabs(8704, 6144) == 1
    assert decoder_mod._slabs(16384, 8 * 4096) == 4  # this model's
    assert decoder_mod._slabs(16384, 16384) == 2
    assert decoder_mod._slabs(5120, 8 * 4096) == 1
    assert decoder_mod._slabs(7168, 8 * 4096) == 2
    assert decoder_mod._slabs(11264, 8 * 4096) == 2
    assert decoder_mod._slabs(13312, 8 * 4096) == 4
    cfg = _cfg()
    w = ref.layer_weights(cfg, jax.random.PRNGKey(5), 1)
    u = jax.random.normal(jax.random.PRNGKey(6), (48, cfg["hidden_size"]))
    mask = jnp.arange(48) < 41
    layer = _expert_layer(cfg, (0, 4))
    params = {"params": _layer_params(w, 0, 4)}
    want, state = layer.apply(params, u, mask, mutable=["intermediates"])
    dense = decoder_mod.GatedMLP(cfg["intermediate_size"], jnp.float32)
    w0 = ref.layer_weights(cfg, jax.random.PRNGKey(5), 0)
    dense_params = {"params": {k: jnp.asarray(w0[k], jnp.float32)
                               for k in ("w1", "w3", "w2")}}
    want_dense = dense.apply(dense_params, u)
    # 48 tokens x (4 pairs x 64) x 4 B = 49,152 B: three slabs of 16.
    monkeypatch.setattr(decoder_mod, "_SLAB_BYTES", 20000)
    assert decoder_mod._slabs(48, 4 * 64) == 3
    got, slabbed = layer.apply(params, u, mask, mutable=["intermediates"])
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    np.testing.assert_array_equal(
        slabbed["intermediates"]["expert_tokens"][0],
        state["intermediates"]["expert_tokens"][0])
    np.testing.assert_allclose(dense.apply(dense_params, u), want_dense,
                               rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# (e) the engine: prefill, then decode through the latent pool
# ---------------------------------------------------------------------------

# Prompts under, on and over block edges, more requests than slots so
# that they join mid-flight.
REQUESTS = ((5, 20), (32, 30), (70, 40), (100, 28), (33, 10), (BLOCK, 3))


@pytest.mark.parametrize("attention", ["naive", "flash"])
def test_engine_serves_what_the_reference_puts_first(attention):
    cfg = _cfg()
    model, variables, weights = _model_and_weights(cfg)
    eng = InferenceEngine(model, variables, attention=attention, slots=3,
                          block_size=BLOCK, max_len=128, check_memory=False)
    try:
        # One kind, the latent one: one pool of rows padded to whole
        # lane tiles, no V pool.
        (kind,) = eng.cache.kinds
        assert kind.latent and kind.window is None and kind.layers == 3
        assert eng.cache.pool_shapes == [(3, 49, BLOCK, 128)]
        assert eng.cache.v_pools == (None,)
        rng = np.random.default_rng(0)
        requests = [
            eng.submit(rng.integers(0, 512, plen).astype(np.int32), new)
            for plen, new in REQUESTS
        ]
        eng.run()
        for req, (plen, new) in zip(requests, REQUESTS):
            assert req.status == "finished" and len(req.tokens) == new
            full = jnp.asarray(np.concatenate([req.prompt, req.tokens]))
            logits = ref.logits(weights, full, cfg)[plen - 1:-1]
            served = jnp.take_along_axis(
                logits, jnp.asarray(req.tokens)[:, None], axis=-1
            )[:, 0]
            # Logits, not tokens: prefill (un-absorbed) then decode
            # (absorbed, through the pool) serve the token the
            # reference's full forward puts first, to float32 rounding.
            gap = jnp.max(logits, axis=-1) - served
            assert float(jnp.max(gap)) < 1e-5, (plen, new)
        stats = eng.stats()
        assert stats["admissions"] == stats["evictions"] == len(REQUESTS)
        # Two expert layers of 4 HELD experts under a router of 16: the
        # cells are the held ones, the pairs those that reached them.
        assert stats["expert_slots"] == stats["decode_steps"] * 2 * 4
        assert 0 < stats["experts_touched"] <= stats["expert_slots"]
        assert 0 < stats["expert_tokens"] < stats["slot_steps_active"] * 4 * 2
        # Every tick read each active slot's whole context: a request of
        # p prompt tokens and n answers is read at lengths p + 1 ... p +
        # n - 1 (its first token comes from the prefill).
        assert stats["context_tokens"] == sum(
            sum(range(plen + 1, plen + new)) for plen, new in REQUESTS)
        assert stats["kv_blocks_window"] == stats["kv_blocks_uniform"] == 0
        assert eng.cache.used_blocks == 0
    finally:
        eng.close()


def test_decode_tick_counts_held_experts_only_and_says_its_context():
    from fluxmpi_tpu.telemetry import tracing

    cfg = _cfg()
    model, variables, _ = _model_and_weights(cfg)
    eng = InferenceEngine(model, variables, slots=2, block_size=BLOCK,
                          max_len=64, check_memory=False)
    try:
        cache = eng.cache
        out, k_pools, v_pools = eng._decode_step(
            variables, cache.k_pools, cache.v_pools, *eng._idle_tick(),
            eng._last_output(), jnp.zeros((2,), bool),
        )
        cache.k_pools, cache.v_pools = k_pools, v_pools
        # 2 tokens, then 2 expert layers x 4 held experts (not the
        # router's 16); idle slots are routed nowhere.
        assert out.shape == (2 + 2 * 4,) and int(out[2:].sum()) == 0
        assert v_pools == (None,)
        tracer = tracing.Tracer(enabled=True)
        previous = tracing.set_tracer(tracer)
        try:
            eng.submit(np.arange(11, dtype=np.int32), 3)
            eng.submit(np.arange(20, dtype=np.int32), 3)
            eng.run()
        finally:
            tracing.set_tracer(previous)
        spans = [e for e in tracer.export()["traceEvents"]
                 if e.get("name") == "serve.decode.prepare"]
        # Both slots live in both ticks: lengths 12 + 21, then 13 + 22.
        assert [s["args"]["context_tokens"] for s in spans] == [33, 35]
        assert all("live_blocks_pct" in s["args"] for s in spans)
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# (f) the allocator and the bytes of the latent kind
# ---------------------------------------------------------------------------


def _cache(**kw):
    return BlockKVCache(
        [Keeps("latent", width=576)] * 3, num_blocks=1 + 2 * 16,
        block_size=BLOCK, max_blocks_per_seq=16, **kw
    )


@pytest.mark.parametrize("tokens", [1, BLOCK, BLOCK + 1, 128])
def test_latent_kind_allocates_by_tokens_and_blocks_return(tokens):
    cache = _cache()
    (kind,) = cache.kinds
    assert (kind.latent, kind.window, kind.entries, kind.layers) == (
        True, None, 16, 3)
    assert cache.layer_kind == [(0, 0), (0, 1), (0, 2)]
    free = cache.free_blocks
    held = cache.alloc(tokens)
    assert len(held) == cache.blocks_for(tokens) == -(-tokens // BLOCK)
    assert cache.table_row(held).shape == (16,)
    assert cache.used_blocks == len(held)
    cache.free(held)
    assert cache.free_blocks == free
    with pytest.raises(ValueError, match="double free"):
        cache.free(held[:1])


def test_latent_kind_counts_one_padded_row_a_token_and_no_v_pool():
    cache = _cache(dtype=jnp.bfloat16)
    # 576 lanes padded to 5 whole tiles of 128; ONE pool.
    assert cache.pool_shapes == [(3, 33, BLOCK, 640)]
    assert cache.pool_bytes == 3 * 33 * BLOCK * 640 * 2
    assert cache.k_pools[0].shape == (3, 33, BLOCK, 640)
    assert cache.v_pools == (None,)
    assert cache.can_alloc(128) and cache.fits_pool(128)
    cache.alloc(128), cache.alloc(128)
    assert not cache.can_alloc(1)
    # Beside K/V layers a latent layer is a third kind, after the others.
    full = Keeps("full", 1, 128)
    mixed = BlockKVCache(
        [full, full._replace(kind="window", window=8),
         Keeps("latent", width=128), full],
        num_blocks=9, block_size=4, max_blocks_per_seq=4, dtype=jnp.bfloat16,
    )
    assert [(k.layer_ids, k.window, k.latent) for k in mixed.kinds] == [
        ((0, 3), None, False), ((1,), 8, False), ((2,), None, True)]
    assert mixed.layer_kind == [(0, 0), (1, 0), (2, 0), (0, 1)]
    assert mixed.pool_bytes == 2 * (2 * (2 * 9 + 1 * 7) + 1 * 9) * 4 * 128
    with pytest.raises(ValueError, match="and no other, names its window"):
        BlockKVCache([Keeps("latent", width=8, window=8)], num_blocks=9,
                     block_size=4, max_blocks_per_seq=4)
    # A prefill stacks every sublayer's rows for one scatter a kind.
    with pytest.raises(ValueError, match="of one shape"):
        BlockKVCache([Keeps("full", 2, 64), Keeps("latent", width=128)],
                     num_blocks=9, block_size=4, max_blocks_per_seq=4)


def test_flash_forward_takes_values_of_their_own_width():
    from fluxmpi_tpu.ops.flash_attention import flash_attention

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (2, 64, 4, 24))
    k = jax.random.normal(keys[1], (2, 64, 4, 24))
    v = jax.random.normal(keys[2], (2, 64, 4, 16))
    got = flash_attention(q, k, v, causal=True)
    want = decoder_mod.causal_attention(q, k, v, window=None, mode="naive")
    assert got.shape == (2, 64, 4, 16)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    with pytest.raises(NotImplementedError, match="served, not trained"):
        jax.grad(lambda v: flash_attention(q, k, v, causal=True).sum())(v)
    with pytest.raises(ValueError, match="q and k head_dim differ"):
        flash_attention(q, k[..., :16], v, causal=True)


def test_a_closed_engine_and_its_weights_are_freed_without_a_collection():
    """The decode step must not close over the engine: a cycle kept the
    engine, and with it the weights, alive past ``del`` until a garbage
    collection, and the benchmark's float32 reference then lacked their
    5.3 GB on the chip (PERF.md, PR 35)."""
    import gc
    import weakref

    cfg = _cfg()
    model, variables = _model_and_weights(cfg)[:2]
    variables = jax.tree_util.tree_map(jnp.copy, variables)  # the only holder
    gc.collect()
    gc.disable()
    try:
        eng = InferenceEngine(model, variables, slots=2, block_size=BLOCK,
                              max_len=64, check_memory=False)
        eng.submit(np.arange(9, dtype=np.int32), 3)
        eng.run()
        assert eng.stats()["expert_slots"] == 2 * 2 * 4  # 2 ticks
        engine, leaf = weakref.ref(eng), weakref.ref(
            jax.tree_util.tree_leaves(variables)[0])
        eng.close()
        del eng, variables
        jax.clear_caches()
        assert engine() is None and leaf() is None
    finally:
        gc.enable()
