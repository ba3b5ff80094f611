"""Nemotron-H (``nemotron_h``): layers that are ONE sublayer each (Mamba-2
| routed experts | attention), un-gated relu2 experts, Mamba-2 with
several groups of B and C, through the configuration-driven decoder LM,
the cache and the serving engine, against the plain reference the
benchmark keeps (``benchmarks/configs/nemotron.reference.py``: float32 at
``highest``, the recurrence one token at a time, dense over the experts
held), at a small size on seeded random weights: hidden 96 (no whole
lane tile), experts 40 wide, two groups of four heads.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _oracles import (DenseState as _DenseState, Kept as _Kept,
                      served_logits as _served_logits)
from fluxmpi_tpu.models import DecoderConfig, ExpertMLP, Keeps
from fluxmpi_tpu.models.decoder import MambaMixer
from fluxmpi_tpu.ops.ssm import from_pool_layout
from fluxmpi_tpu.serving import InferenceEngine

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


ref = _load("nemotron.reference.py")
prog = _load("nemotron.program.py")
granite_ref = _load("granite.reference.py")


def _json(name):
    with open(os.path.join(CONFIGS, f"{name}.json"), encoding="utf-8") as f:
        return json.load(f)


def _cfg(**changes):
    """The rehearsal configuration (``MEM*E``: 2 Mamba-2 layers of 8 heads
    of 16 over a state of 16 in 2 groups, 2 expert layers holding 8 of 16
    un-gated experts of 40, top-4, a shared expert of 80, 1 attention
    layer of 8 over 2 heads), float32 compute so that the comparison is
    tight."""
    cfg = _json("tiny-nemotron")
    cfg.update({"compute_dtype": "float32", **changes})
    return cfg


def _ref_logits(weights, tokens, cfg, precision="f32"):
    return jax.jit(lambda w, t: ref.logits(w, t, cfg, precision))(
        weights, tokens)


def _model_and_weights(cfg, seed=3):
    weights = ref.make_weights(cfg, jax.random.PRNGKey(seed))
    variables, _ = prog.to_program(weights, cfg)
    return prog.build_model(cfg, "naive"), variables, weights


# ---------------------------------------------------------------------------
# (a) the configuration
# ---------------------------------------------------------------------------


def test_from_hf_maps_the_nemotron_keys_and_leaves_the_others_as_they_were():
    c = DecoderConfig.from_hf(_json("nemotron-3-nano-30b-a3b"))
    m, e, a = "mamba", "experts", "full_attention"
    assert c.layer_types == (m, e, m, e, m, a, e, m, e)
    assert c.block == "single" and c.mlp_activation == "relu2"
    assert c.expert_layer_ids == (1, 3, 6, 8)
    assert (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state) == (64, 64, 128)
    assert (c.mamba_n_groups, c.mamba_d_conv,
            c.mamba_chunk_size) == (8, 4, 128)
    assert (c.mamba_inner, c.mamba_conv_dim) == (4096, 6144)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim) == (
        32, 2, 128)
    assert c.attention_multiplier is None and not c.qk_norm
    assert not c.output_gate and c.norm_placement == "pre"
    # 64 of the router's 128 held, six a token, experts of 1,856 and a
    # shared one of 3,712, sigmoid scores normalised, times 2.5.
    assert (c.num_experts, c.num_routed_experts, c.num_experts_per_tok) == (
        64, 128, 6)
    assert (c.moe_intermediate_size, c.shared_width) == (1856, 3712)
    assert (c.score_func, c.route_norm,
            c.route_scale) == ("sigmoid", True, 2.5)
    assert (c.hidden_size, c.vocab_size) == (2688, 65536)
    assert not c.tie_word_embeddings and c.rms_norm_eps == 1e-5
    # The whole model's pattern: 23 : 23 : 6, every expert held.
    whole = dict(_json("nemotron-3-nano-30b-a3b"))
    whole.update(hybrid_override_pattern=(
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"),
        num_hidden_layers=52)
    del whole["num_experts"]
    c = DecoderConfig.from_hf(whole)
    assert [c.layer_types.count(k) for k in (m, e, a)] == [23, 23, 6]
    assert c.num_experts == c.num_routed_experts == 128
    # The other models: today's defaults.
    for name in ("trinity-mini", "sarvam-105b", "granite-4.0-h-small"):
        other = DecoderConfig.from_hf(_json(name))
        assert other.block == "pair" and other.mlp_activation == "swiglu"
        assert "experts" not in other.layer_types
        assert other.mamba_n_groups == 1
        assert other.expert_layer_ids == tuple(
            range(other.num_dense_layers, other.num_layers))


def test_config_refuses_what_the_layers_cannot_compute():
    cfg = _json("tiny-nemotron")
    with pytest.raises(ValueError, match="layers of M, E or"):
        DecoderConfig.from_hf({**cfg, "hybrid_override_pattern": "ME-M*"})
    with pytest.raises(ValueError, match="layers of M, E or"):
        DecoderConfig.from_hf({**cfg, "num_hidden_layers": 4})
    with pytest.raises(ValueError, match="relu2"):
        DecoderConfig.from_hf({**cfg, "mlp_hidden_act": "silu"})
    base = dict(vocab_size=32, hidden_size=16, num_attention_heads=2,
                num_key_value_heads=2, head_dim=8, intermediate_size=16)
    # A layer that is its experts alone exists in a "single" block only.
    with pytest.raises(ValueError, match="unknown layer types"):
        DecoderConfig(**base, layer_types=("experts",))
    DecoderConfig(**base, layer_types=("experts",), block="single")
    with pytest.raises(ValueError, match="not whole groups"):
        DecoderConfig(**base, layer_types=("mamba",), mamba_n_heads=4,
                      mamba_d_head=8, mamba_d_state=8, mamba_n_groups=3)
    with pytest.raises(ValueError, match="unknown mlp_activation"):
        DecoderConfig(**base, layer_types=("full_attention",),
                      mlp_activation="gelu")


def test_nemotron_parameter_tree_and_cache_layers():
    cfg = _cfg()
    model, variables, _ = _model_and_weights(cfg)
    made = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))
    shapes = jax.tree_util.tree_map(lambda x: x.shape, made)
    assert shapes == jax.tree_util.tree_map(lambda x: x.shape, variables)
    params = variables["params"]
    assert params["head"].shape == (96, 512)  # untied
    # One sublayer a layer, one norm before it.
    assert set(params["layer_0"]) == {"norm_in", "mamba"}
    assert set(params["layer_1"]) == {"norm_in", "moe"}
    assert set(params["layer_3"]) == {"norm_in", "attn"}
    moe = params["layer_1"]["moe"]
    assert set(moe) == {"router", "bias", "w_up", "w_down", "shared"}
    # Two matrices an expert, both [held, width, hidden].
    assert moe["w_up"].shape == moe["w_down"].shape == (8, 40, 96)
    assert moe["router"].shape == (96, 16)
    assert set(moe["shared"]) == {"w_up", "w_down"}
    assert params["layer_0"]["mamba"]["w_in"].shape == (
        96, 128 + (128 + 2 * 2 * 16) + 8)
    # A Mamba layer keeps a STATE a sequence, the attention layer rows a
    # token, an expert layer NOTHING.
    state = Keeps("state", state=(8, 16, 16), tail=(3, 8 * 16 + 2 * 2 * 16))
    assert model.cache_layers() == (
        state, None, state, Keeps("full", 2, 16), None)
    assert model.expert_row_tile(4) is None  # a CPU: ragged_dot


# ---------------------------------------------------------------------------
# (b) one mixer: chunked scan = recurrence through a cache = the reference
# ---------------------------------------------------------------------------


def _mixer(cfg, seed=11):
    w = ref.layer_weights(cfg, jax.random.PRNGKey(seed), 0, "M")
    params = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()
              if k != "norm_in"}
    return DecoderConfig.from_hf(cfg), {"params": params}, w


# Groups: the rehearsal's 2, and one a head (8). Inside the first chunk,
# on an edge, one past it, chunks and a tail.
@pytest.mark.parametrize("groups", [2, 8])
@pytest.mark.parametrize("seq", [2, 8, 9, 21])
def test_mixer_chunked_equals_recurrence_equals_reference(seq, groups):
    cfg = _cfg(n_groups=groups)
    config, variables, w = _mixer(cfg)
    assert config.mamba_conv_dim == 128 + 2 * groups * 16
    u = jax.random.normal(jax.random.PRNGKey(seq), (seq, cfg["hidden_size"]))
    want, want_state, want_tail = ref.mamba(u, w, cfg, state_out=True)
    kept = _Kept()
    got = MambaMixer(config, jnp.float32).apply(
        variables, u[None], cache=kept)[0]
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    np.testing.assert_allclose(kept.state[0], want_state, rtol=0, atol=2e-5)
    np.testing.assert_allclose(kept.tail[0], want_tail, rtol=0, atol=1e-6)
    cache = _DenseState(config)
    layer = MambaMixer(config, jnp.float32)
    steps = jnp.concatenate(
        [layer.apply(variables, u[None, t:t + 1], cache=cache)[0]
         for t in range(seq)])
    np.testing.assert_allclose(steps, want, rtol=0, atol=2e-5)
    np.testing.assert_allclose(from_pool_layout(cache.pool[0, 1], 8),
                               want_state, rtol=0, atol=2e-5)
    np.testing.assert_allclose(cache.tail()[0], want_tail, rtol=0, atol=1e-6)


def test_groups_matter_and_one_group_is_still_granites_numbers():
    """The same weights read with the heads' B and C taken from the wrong
    group are far off; with ONE group the mixer gives what the Granite
    reference (one group, one norm over all of ``inner``) gives."""
    cfg = _cfg()
    config, variables, w = _mixer(cfg)
    u = jax.random.normal(jax.random.PRNGKey(1), (12, cfg["hidden_size"]))
    want = ref.mamba(u, w, cfg)
    swapped = dict(w)
    conv = np.asarray(w["conv_w"], np.float32)
    # Group 0's and group 1's B columns change places.
    conv[128:144], conv[144:160] = conv[144:160].copy(), conv[128:144].copy()
    swapped["conv_w"] = jnp.asarray(conv)
    assert float(jnp.max(jnp.abs(ref.mamba(u, swapped, cfg) - want))) > 1e-3
    granite = {
        "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
        "mamba_d_conv": 4, "hidden_size": 96, "rms_norm_eps": 1e-5,
        "initializer_range": 0.02, "num_attention_heads": 8,
        "num_key_value_heads": 2,
    }
    gw = granite_ref.mixer_weights(granite, jax.random.PRNGKey(4), "mamba")
    one = DecoderConfig.from_hf(_cfg(n_groups=1))
    got = MambaMixer(one, jnp.float32).apply(
        {"params": {k: jnp.asarray(v, jnp.float32) for k, v in gw.items()}},
        u[None])[0]
    np.testing.assert_allclose(got, granite_ref.mamba(u, gw, granite),
                               rtol=0, atol=2e-5)


@pytest.mark.parametrize("length", [1, 2, 5, 16, 19])
def test_a_padded_prompt_leaves_the_state_and_tail_of_the_unpadded(length):
    cfg = _cfg()
    config, variables, w = _mixer(cfg)
    bucket = 24
    u = jax.random.normal(jax.random.PRNGKey(length),
                          (bucket, cfg["hidden_size"]))
    mask = (jnp.arange(bucket) < length)[None]
    padded, plain = _Kept(), _Kept()
    out = MambaMixer(config, jnp.float32).apply(
        variables, u[None], mask, padded)[0]
    want = MambaMixer(config, jnp.float32).apply(
        variables, u[None, :length], cache=plain)[0]
    np.testing.assert_allclose(out[:length], want, rtol=0, atol=1e-6)
    np.testing.assert_allclose(padded.state, plain.state, rtol=0, atol=1e-6)
    np.testing.assert_array_equal(padded.tail, plain.tail)
    _, want_state, want_tail = ref.mamba(u[:length], w, cfg, state_out=True)
    np.testing.assert_allclose(padded.state[0], want_state, rtol=0, atol=2e-5)
    np.testing.assert_allclose(padded.tail[0], want_tail, rtol=0, atol=1e-6)
    if length < 3:
        np.testing.assert_array_equal(padded.tail[0, :3 - length], 0.0)


# ---------------------------------------------------------------------------
# (c) the model against the reference
# ---------------------------------------------------------------------------


# Logits of std ~0.2 here (an untied head of std 0.02 over hidden 96).
# float32 compute against the float32 reference reads under 2e-6; a state
# held in bfloat16 loses 2 ** -9 of itself a step and reads over 1e-4.
LOGIT_TOLERANCE = 5e-6


@pytest.mark.parametrize("attention, seq", [("naive", 5), ("naive", 37),
                                            ("flash", 32)])
def test_nemotron_logits_match_the_reference(attention, seq):
    cfg = _cfg()
    model, variables, weights = _model_and_weights(cfg)
    model = model.clone(attention=attention)
    tokens = jax.random.randint(jax.random.PRNGKey(seq), (seq,), 0, 512)
    got = model.apply(variables, tokens[None])[0]
    want = _ref_logits(weights, tokens, cfg)
    np.testing.assert_allclose(got, want, rtol=0, atol=LOGIT_TOLERANCE)


def test_nemotron_runs_in_bfloat16_and_a_lower_precision_is_further_off():
    cfg = _cfg(compute_dtype="bfloat16")
    model, variables, weights = _model_and_weights(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (24,), 0, 512)
    want = _ref_logits(weights, tokens, cfg)
    got = model.apply(variables, tokens[None])[0]
    assert got.dtype == jnp.float32
    served = float(jnp.max(jnp.abs(got - want)))
    control = float(jnp.max(jnp.abs(
        _ref_logits(weights, tokens, cfg, "fp8") - want)))
    spread = float(jnp.std(want))
    assert served < 0.1 * spread
    assert control > 3 * served


# ---------------------------------------------------------------------------
# (d) the expert layer: un-gated relu2, sigmoid weights, shares that add up
# ---------------------------------------------------------------------------


def test_sigmoid_weights_normalised_and_scaled_by_hand():
    layer = ExpertMLP(num_experts=6, top_k=3, width=4, route_scale=2.5,
                      activation="relu2")
    # One token whose router logits are its own coordinates.
    u = jnp.asarray([[2.0, -1.0, 0.5, 3.0, 0.0, 1.0]])
    experts, weights = layer.route(u, jnp.eye(6), jnp.zeros((6,)))
    assert sorted(np.asarray(experts[0]).tolist()) == [0, 3, 5]
    s = 1.0 / (1.0 + np.exp(-np.asarray([3.0, 2.0, 1.0])))
    order = np.argsort(-np.asarray(u[0])[np.asarray(experts[0])])
    np.testing.assert_allclose(np.asarray(weights[0])[order],
                               2.5 * s / s.sum(), rtol=1e-6)
    cfg = {"num_experts_per_tok": 3, "norm_topk_prob": True,
           "routed_scaling_factor": 2.5}
    gates = ref.route(u, {"router": jnp.eye(6)}, cfg)
    np.testing.assert_allclose(gates[0, [3, 0, 5]], 2.5 * s / s.sum(),
                               rtol=1e-6)
    assert float(jnp.sum(gates)) == pytest.approx(2.5)


def test_one_relu2_expert_by_hand():
    """One expert, every token its own: ``relu(u W_up^T)^2 W_down`` with
    both matrices held ``[width, hidden]``."""
    layer = ExpertMLP(num_experts=1, top_k=1, width=5, route_norm=True,
                      activation="relu2")
    rng = np.random.default_rng(0)
    u = rng.normal(size=(7, 6)).astype(np.float32)
    up = rng.normal(size=(1, 5, 6)).astype(np.float32)
    down = rng.normal(size=(1, 5, 6)).astype(np.float32)
    params = {"router": jnp.zeros((6, 1)), "bias": jnp.zeros((1,)),
              "w_up": jnp.asarray(up), "w_down": jnp.asarray(down)}
    got = layer.apply({"params": params}, jnp.asarray(u))
    want = np.square(np.maximum(u @ up[0].T, 0.0)) @ down[0]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _expert_layer(cfg, expert_range, include_shared=True):
    return ExpertMLP(
        num_experts=cfg["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"], width=cfg["moe_intermediate_size"],
        shared_width=cfg["moe_shared_expert_intermediate_size"],
        route_scale=cfg["routed_scaling_factor"], activation="relu2",
        expert_range=expert_range, include_shared=include_shared,
        dtype=jnp.float32,
    )


def _layer_params(w, lo, hi):
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    return {
        "router": f32(w["router"]),
        "bias": jnp.zeros((w["router"].shape[1],), jnp.float32),
        "w_up": f32(w["e_up"][lo:hi]), "w_down": f32(w["e_down"][lo:hi]),
        "shared": {"w_up": f32(w["s_up"]), "w_down": f32(w["s_down"])},
    }


def test_two_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The deployment's cut: two chips hold 8 of the 16 experts each (the
    rehearsal's halves), both the shared expert. Their parts, the shared
    expert counted once, sum to what the reference gives for the whole
    layer; the FIRST share is what the cut reference gives."""
    whole = _cfg(num_experts=16)
    w = ref.layer_weights(whole, jax.random.PRNGKey(5), 1, "E")
    u = jax.random.normal(jax.random.PRNGKey(6), (48, whole["hidden_size"]))
    total, parts = 0.0, []
    for lo in (0, 8):
        layer = _expert_layer(whole, (lo, lo + 8), include_shared=lo == 0)
        part, state = layer.apply(
            {"params": _layer_params(w, lo, lo + 8)}, u,
            mutable=["intermediates"])
        parts.append(part)
        total = total + part
        held = np.asarray(state["intermediates"]["expert_tokens"][0])
        assert held.shape == (8,)  # the pairs of the experts HELD only
    np.testing.assert_allclose(total, ref.experts(u, w, whole),
                               rtol=0, atol=1e-5)
    cut = _cfg()
    first = {k: (v[:8] if k in ("e_up", "e_down") else v)
             for k, v in w.items()}
    np.testing.assert_allclose(parts[0], ref.experts(u, first, cut),
                               rtol=0, atol=1e-5)
    # Every one of the 48 x 4 pairs went to one share or the other.
    assert float(jnp.max(jnp.abs(parts[1]))) > 0.0


# ---------------------------------------------------------------------------
# (e) the engine: prefill, then decode through the state pool and the K/V
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16"])
def test_prefill_then_64_decode_ticks_through_the_state_pool(state_dtype):
    cfg = _cfg()
    model, variables, weights = _model_and_weights(cfg)
    eng = InferenceEngine(model, variables, attention="naive", slots=2,
                          block_size=BLOCK, max_len=128, check_memory=False)
    try:
        kind = eng.cache.kinds[eng.cache.state_kind]
        assert eng.cache.k_pools[eng.cache.state_kind].dtype == jnp.float32
        if state_dtype != "float32":
            # No option chooses the state's dtype: the programs follow
            # the pool's, and the test swaps the pool.
            kind.k_pool = kind.k_pool.astype(state_dtype)
        prompt = np.random.default_rng(1).integers(0, 512, 21).astype(np.int32)
        tokens, got = _served_logits(eng, variables, prompt, 64)
        full = jnp.asarray(np.concatenate([prompt, tokens[:-1]]))
        want = _ref_logits(weights, full, cfg)
        assert tokens[0] == int(jnp.argmax(want[len(prompt) - 1]))
        worst = float(jnp.max(jnp.abs(got - want[len(prompt):])))
        if state_dtype == "float32":
            assert worst < LOGIT_TOLERANCE
        else:
            assert worst > 10 * LOGIT_TOLERANCE
    finally:
        eng.close()


# More requests than slots, short and long: prompts shorter than the
# convolution reaches, on a block's edge, across chunks; answers that end
# at different ticks, so that requests join mid-flight into slots, blocks
# and state entries others have just left.
REQUESTS = ((5, 20), (33, 30), (70, 12), (BLOCK, 3), (2, 40), (1, 5))


@pytest.mark.parametrize("attention", ["naive", "flash"])
def test_engine_serves_what_the_reference_puts_first(attention):
    cfg = _cfg()
    model, variables, weights = _model_and_weights(cfg)
    eng = InferenceEngine(model, variables, attention=attention, slots=3,
                          block_size=BLOCK, max_len=128, check_memory=False)
    try:
        # Five layers, three keep something: the cache numbers those, in
        # the order their calls come; the expert layers have no pool.
        full, state = eng.cache.kinds
        assert eng.cache.num_layers == 3
        assert full.layer_ids == (2,) and full.state is None
        assert state.layer_ids == (0, 1) and state.entries == 1
        assert eng.cache.pool_shapes == [(1, 49, BLOCK, 32), (2, 4, 16, 128)]
        at = eng.cache.state_kind
        eng.cache.k_pools = tuple(
            jnp.full_like(pool, 1e3) if i == at else pool
            for i, pool in enumerate(eng.cache.k_pools))
        rng = np.random.default_rng(0)
        requests = [
            eng.submit(rng.integers(0, 512, plen).astype(np.int32), new)
            for plen, new in REQUESTS
        ]
        eng.run()
        for req, (plen, new) in zip(requests, REQUESTS):
            assert req.status == "finished" and len(req.tokens) == new
            whole = jnp.asarray(np.concatenate([req.prompt, req.tokens]))
            logits = _ref_logits(weights, whole, cfg)[plen - 1:-1]
            served = jnp.take_along_axis(
                logits, jnp.asarray(req.tokens)[:, None], axis=-1
            )[:, 0]
            gap = jnp.max(logits, axis=-1) - served
            assert float(jnp.max(gap)) < LOGIT_TOLERANCE, (plen, new)
        stats = eng.stats()
        steps = stats["decode_steps"]
        assert stats["admissions"] == stats["evictions"] == len(REQUESTS)
        # The attention layer's blocks and lengths only.
        assert stats["context_tokens"] == sum(
            sum(range(plen + 1, plen + new)) for plen, new in REQUESTS)
        assert stats["kv_blocks_tabled"] == steps * 3 * 16
        # The two Mamba layers' states: a live slot's a tick.
        assert stats["state_entries"] == steps * 3
        assert stats["state_entries_used"] == stats["slot_steps_active"]
        # The experts are counted over the TWO layers that have them:
        # 8 held experts each, a live slot's four pairs a layer a tick at
        # most (those routed to the other half are not this chip's).
        assert eng._expert_layers == [2]
        assert stats["expert_slots"] == steps * 2 * 8
        assert 0 < stats["expert_tokens"] <= (
            stats["slot_steps_active"] * 2 * 4)
        assert stats["expert_row_tiles"] == steps * 2
        assert 0 < stats["expert_row_tiles_worked"] <= steps * 2
        assert eng.cache.used_blocks == 0
    finally:
        eng.close()


def test_spans_count_experts_over_the_layers_that_have_them():
    """``serve.decode.deliver``'s ``expert_*`` arguments of a model whose
    expert layers stand between its mixers: shares over 2 layers x 8 held
    experts; ``serve.decode.prepare`` counts the two state layers' states
    and the one attention layer's positions."""
    from fluxmpi_tpu.telemetry import tracing

    cfg = _cfg()
    model, variables, _ = _model_and_weights(cfg)
    eng = InferenceEngine(model, variables, attention="naive", slots=4,
                          block_size=BLOCK, max_len=64, check_memory=False)
    try:
        tracer = tracing.Tracer(enabled=True)
        previous = tracing.set_tracer(tracer)
        try:
            eng.submit(np.arange(11, dtype=np.int32), 3)
            eng.submit(np.arange(20, dtype=np.int32), 3)
            eng.run()
        finally:
            tracing.set_tracer(previous)
        events = tracer.export()["traceEvents"]
    finally:
        eng.close()
    prepared = [e["args"] for e in events
                if e.get("name") == "serve.decode.prepare"]
    assert [a["live_states_pct"] for a in prepared] == [50.0, 50.0]
    assert [a["context_tokens"] for a in prepared] == [33, 35]
    delivered = [e["args"] for e in events
                 if e.get("name") == "serve.decode.deliver"
                 and "experts_touched_pct" in e["args"]]
    assert len(delivered) == 2
    for args in delivered:
        # 16 (layer, held expert) cells: a share of them is k / 16.
        cells = args["experts_touched_pct"] * 16 / 100.0
        assert cells == pytest.approx(round(cells)) and 1 <= cells <= 16
        assert args["expert_load_max_over_mean"] >= 1.0
        assert 0.0 <= args["expert_row_tiles_worked_pct"] <= 100.0
        assert "expert_weight_visits_per_touched" not in args  # ragged_dot
