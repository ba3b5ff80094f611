"""Mamba-2 layers (``granitemoehybrid``) through the configuration-driven
decoder LM, the cache's state kind and the serving engine, against the
plain reference the benchmark keeps
(``benchmarks/configs/granite.reference.py``: float32 at ``highest``, the
recurrence one token at a time, dense over the experts held), at a small
size on seeded random weights.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _oracles import (DenseState as _DenseState, Kept as _Kept,
                      poisoned_past_the_groups,
                      served_logits as _served_logits)
from fluxmpi_tpu.models import DecoderConfig, ExpertMLP, Keeps
from fluxmpi_tpu.models.decoder import MambaMixer
from fluxmpi_tpu.ops.ssm import from_pool_layout, tail_from_pool_layout
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


ref = _load("granite.reference.py")
prog = _load("granite.program.py")


def _json(name):
    with open(os.path.join(CONFIGS, f"{name}.json"), encoding="utf-8") as f:
        return json.load(f)


def _cfg(**changes):
    """The rehearsal configuration (3 Mamba-2 layers of 8 heads of 16 over
    a state of 16 and 1 attention layer, chunks of 8, 4 of 16 experts
    held, top-4, a shared MLP), float32 compute so that the comparison is
    tight."""
    cfg = _json("tiny-granite")
    cfg.update({"compute_dtype": "float32", **changes})
    return cfg


def _ref_logits(weights, tokens, cfg, precision="f32"):
    """The reference's full forward, compiled (a fifth of the time it
    takes operation by operation)."""
    return jax.jit(lambda w, t: ref.logits(w, t, cfg, precision))(
        weights, tokens)


def _model_and_weights(cfg, seed=3):
    weights = ref.make_weights(cfg, jax.random.PRNGKey(seed))
    variables, _ = prog.to_program(weights, cfg)
    return prog.build_model(cfg, "naive"), variables, weights


# ---------------------------------------------------------------------------
# (a) the configuration
# ---------------------------------------------------------------------------


def test_from_hf_maps_the_granite_keys_and_leaves_the_others_as_they_were():
    c = DecoderConfig.from_hf(_json("granite-4.0-h-small"))
    assert c.layer_types == ("mamba",) * 5 + ("full_attention",) + (
        "mamba",) * 4
    assert (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state) == (128, 64, 128)
    assert (c.mamba_inner, c.mamba_conv_dim) == (8192, 8448)
    assert (c.mamba_d_conv, c.mamba_chunk_size, c.mamba_n_groups) == (4, 256, 1)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim) == (
        32, 8, 128)
    assert c.attention_multiplier == 1 / 128 and not c.qk_norm
    assert not c.output_gate and c.norm_placement == "pre"
    assert (c.embedding_multiplier, c.residual_multiplier,
            c.logits_scaling) == (12, 0.22, 16)
    assert c.tie_word_embeddings and c.score_func == "softmax"
    # 18 of the router's 72 held, ten a token, experts of 768, a shared
    # MLP of a width of its own.
    assert (c.num_experts, c.num_routed_experts, c.num_experts_per_tok) == (
        18, 72, 10)
    assert (c.moe_intermediate_size, c.shared_width) == (768, 1536)
    assert c.num_dense_layers == 0 and c.vocab_size == 25088
    # The other models: today's defaults.
    for name in ("trinity-mini", "sarvam-105b"):
        other = DecoderConfig.from_hf(_json(name))
        assert other.qk_norm and other.attention_multiplier is None
        assert other.residual_multiplier == other.logits_scaling == 1.0
        assert not other.tie_word_embeddings and other.score_func == "sigmoid"
        assert other.shared_width == (
            other.num_shared_experts * other.moe_intermediate_size)
        assert "mamba" not in other.layer_types


def test_config_refuses_what_the_layer_cannot_compute():
    base = dict(vocab_size=32, hidden_size=16, layer_types=("mamba",),
                num_attention_heads=2, num_key_value_heads=2, head_dim=8,
                intermediate_size=16)
    with pytest.raises(ValueError, match="mamba layers need"):
        DecoderConfig(**base)
    with pytest.raises(ValueError, match="not whole groups of B and C"):
        DecoderConfig(**base, mamba_n_heads=2, mamba_d_head=16,
                      mamba_d_state=8, mamba_n_groups=3)
    with pytest.raises(ValueError, match="unknown score_func"):
        DecoderConfig(**{**base, "layer_types": ("full_attention",)},
                      score_func="tanh")


def test_granite_parameter_tree_and_cache_layers():
    cfg = _cfg()
    model, variables, _ = _model_and_weights(cfg)
    made = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))
    shapes = jax.tree_util.tree_map(lambda x: x.shape, made)
    assert shapes == jax.tree_util.tree_map(lambda x: x.shape, variables)
    params = variables["params"]
    assert "head" not in params  # tied: the embedding's transpose
    assert set(params["layer_0"]) == {"norm_in", "norm_pre_ff", "mamba",
                                      "moe"}
    assert set(params["layer_2"]["attn"]) == {"wq", "wk", "wv", "wo"}
    # A Mamba layer keeps a STATE a sequence, not rows a token.
    state = Keeps("state", state=(8, 16, 16), tail=(3, 8 * 16 + 2 * 16))
    assert model.cache_layers() == (
        state, state, Keeps("full", 2, 16), state)


def test_mamba_scalars_start_where_mamba2_publishes_them():
    cfg = _cfg()
    model = prog.build_model(cfg, "naive")
    mixer = model.init(jax.random.PRNGKey(7), jnp.zeros((1, 8), jnp.int32))[
        "params"]["layer_0"]["mamba"]
    rate = np.exp(np.asarray(mixer["a_log"]))
    step = np.log1p(np.exp(np.asarray(mixer["dt_bias"])))
    assert rate.min() >= 1.0 and rate.max() <= 16.0
    assert step.min() >= 0.001 - 1e-6 and step.max() <= 0.1 + 1e-6
    np.testing.assert_array_equal(mixer["d_skip"], 1.0)
    assert np.abs(np.asarray(mixer["conv_w"])).max() <= 0.5
    # The reference's weights, from the seed, the same way.
    w = ref.mixer_weights(cfg, jax.random.PRNGKey(7), "mamba")
    rate = np.exp(np.asarray(w["a_log"]))
    step = np.log1p(np.exp(np.asarray(w["dt_bias"])))
    assert 1.0 <= rate.min() and rate.max() <= 16.0
    assert 0.001 - 1e-6 <= step.min() and step.max() <= 0.1 + 1e-6


# ---------------------------------------------------------------------------
# (b) one mixer: chunked scan = recurrence through a cache = the reference
# ---------------------------------------------------------------------------


def _mixer(cfg, seed=11):
    w = ref.mixer_weights(cfg, jax.random.PRNGKey(seed), "mamba")
    params = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    config = DecoderConfig.from_hf(cfg)
    return config, {"params": params}, w


# Inside the first chunk, on an edge, one past it, chunks and a tail.
@pytest.mark.parametrize("seq", [2, 8, 9, 21])
def test_mixer_chunked_equals_recurrence_equals_reference(seq):
    cfg = _cfg()
    config, variables, w = _mixer(cfg)
    u = jax.random.normal(jax.random.PRNGKey(seq), (seq, cfg["hidden_size"]))
    want, want_state, want_tail = ref.mamba(u, w, cfg, state_out=True)
    # Over its own tokens: the chunked scan (chunks of 8).
    kept = _Kept()
    got = MambaMixer(config, jnp.float32).apply(
        variables, u[None], cache=kept)[0]
    # float32 on both sides; the chunked sums in another order.
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    np.testing.assert_allclose(kept.state[0], want_state, rtol=0, atol=2e-5)
    np.testing.assert_allclose(kept.tail[0], want_tail, rtol=0, atol=1e-6)
    # A token a call against a cache: the recurrence itself.
    cache = _DenseState(config)
    layer = MambaMixer(config, jnp.float32)
    steps = jnp.concatenate(
        [layer.apply(variables, u[None, t:t + 1], cache=cache)[0]
         for t in range(seq)])
    np.testing.assert_allclose(steps, want, rtol=0, atol=2e-5)
    np.testing.assert_allclose(from_pool_layout(cache.pool[0, 1], 8),
                               want_state, rtol=0, atol=2e-5)
    np.testing.assert_allclose(cache.tail()[0], want_tail, rtol=0, atol=1e-6)


# Shorter than the convolution reaches, inside a chunk, on a block's edge.
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
    # The state after the bucket IS the state after the prompt; the tail
    # its last three real columns, zeros before the start.
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


# Logits of std 8e-4 here (a tied head of std 0.02 / 12 over
# logits_scaling 16). float32 compute against the float32 reference reads
# 1.9e-9 ... 2.0e-9 (rounding through 4 layers and 64 steps of the
# recurrence); a state held in bfloat16 loses 2 ** -9 of itself a step and
# reads 1.1e-6 ... 2.3e-6.
LOGIT_TOLERANCE = 2e-8


@pytest.mark.parametrize("attention, seq", [("naive", 5), ("naive", 37),
                                            ("flash", 32)])
def test_granite_logits_match_the_reference(attention, seq):
    cfg = _cfg()
    model, variables, weights = _model_and_weights(cfg)
    model = model.clone(attention=attention)
    tokens = jax.random.randint(jax.random.PRNGKey(seq), (seq,), 0, 512)
    got = model.apply(variables, tokens[None])[0]
    want = _ref_logits(weights, tokens, cfg)
    np.testing.assert_allclose(got, want, rtol=0, atol=LOGIT_TOLERANCE)


def test_granite_runs_in_bfloat16_and_a_lower_precision_is_further_off():
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
    # bfloat16 operands: under a twentieth of the logits' spread; the
    # reference in fp8 (4 significant bits, its state held so too) is
    # several times further off.
    assert served < 0.05 * spread
    assert control > 3 * served


# ---------------------------------------------------------------------------
# (d) the expert layer: softmax after top-k, shares that add up
# ---------------------------------------------------------------------------


def test_softmax_after_topk_weights_by_hand():
    layer = ExpertMLP(num_experts=6, top_k=3, width=4, score_func="softmax")
    # One token whose router logits are its own coordinates.
    u = jnp.asarray([[2.0, -1.0, 0.5, 3.0, 0.0, 1.0]])
    experts, weights = layer.route(u, jnp.eye(6), jnp.zeros((6,)))
    assert sorted(np.asarray(experts[0]).tolist()) == [0, 3, 5]
    e = np.exp(np.asarray([3.0, 2.0, 1.0]))
    order = np.argsort(-np.asarray(u[0])[np.asarray(experts[0])])
    np.testing.assert_allclose(np.asarray(weights[0])[order], e / e.sum(),
                               rtol=1e-6)
    # The reference's gates: the same numbers on the same experts.
    cfg = {"num_experts_per_tok": 3}
    gates = ref.route(u, {"router": jnp.eye(6)}, cfg)
    np.testing.assert_allclose(gates[0, [3, 0, 5]], e / e.sum(), rtol=1e-6)
    assert float(jnp.sum(gates)) == pytest.approx(1.0)


def _expert_layer(cfg, expert_range, include_shared=True):
    return ExpertMLP(
        num_experts=cfg["num_routed_experts"],
        top_k=cfg["num_experts_per_tok"], width=cfg["intermediate_size"],
        shared_width=cfg["shared_intermediate_size"], score_func="softmax",
        expert_range=expert_range, include_shared=include_shared,
        dtype=jnp.float32,
    )


def _layer_params(w, lo, hi):
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    return {
        "router": f32(w["router"]),
        "bias": jnp.zeros((w["router"].shape[1],), jnp.float32),
        "w1": f32(w["ew1"][lo:hi]), "w3": f32(w["ew3"][lo:hi]),
        "w2": f32(w["ew2"][lo:hi]),
        "shared": {k: f32(w[k]) for k in ("w1", "w3", "w2")},
    }


def test_four_shares_and_the_shared_mlp_once_add_up_to_the_uncut_layer():
    """The deployment's cut: four chips hold 4 of the 16 experts each
    (the rehearsal's quarters), every chip the shared MLP. Their parts,
    the shared MLP counted once, sum to what the reference gives for the
    whole layer; the FIRST share is what the cut reference gives."""
    whole = _cfg(num_local_experts=16)
    w = ref.layer_weights(whole, jax.random.PRNGKey(5), 1, mixer=False)
    u = jax.random.normal(jax.random.PRNGKey(6), (48, whole["hidden_size"]))
    total, parts = 0.0, []
    for lo in range(0, 16, 4):
        layer = _expert_layer(whole, (lo, lo + 4), include_shared=lo == 0)
        part, state = layer.apply(
            {"params": _layer_params(w, lo, lo + 4)}, u,
            mutable=["intermediates"])
        parts.append(part)
        total = total + part
        held = np.asarray(state["intermediates"]["expert_tokens"][0])
        assert held.shape == (4,)  # the pairs of the experts HELD only
    np.testing.assert_allclose(total, ref.feed_forward(u, w, whole),
                               rtol=0, atol=2e-6)
    cut = _cfg()
    first = {k: (v[:4] if k in ("ew1", "ew3", "ew2") else v)
             for k, v in w.items()}
    np.testing.assert_allclose(parts[0], ref.feed_forward(u, first, cut),
                               rtol=0, atol=2e-6)


@pytest.mark.parametrize("steer", ["as_routed", "all_to_one_share"])
def test_shares_read_no_row_past_their_own_pairs(steer, monkeypatch):
    """The same cut with the rows past each share's pairs poisoned: the
    parts still sum to the uncut layer. ``all_to_one_share``: the
    router's columns for experts 8-11 are raised, so that share receives
    every pair it can (4 of a token's top-k)."""
    from fluxmpi_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(
        gm, "grouped_matmul", poisoned_past_the_groups(gm.grouped_matmul))
    whole = _cfg(num_local_experts=16)
    w = ref.layer_weights(whole, jax.random.PRNGKey(5), 1, mixer=False)
    u = jax.random.normal(jax.random.PRNGKey(6), (48, whole["hidden_size"]))
    bias = jnp.zeros((16,))
    if steer == "all_to_one_share":
        bias = jnp.where((jnp.arange(16) >= 8) & (jnp.arange(16) < 12),
                         50.0, 0.0)
    pairs = u.shape[0] * whole["num_experts_per_tok"]
    total, received = 0.0, []
    for lo in range(0, 16, 4):
        layer = _expert_layer(whole, (lo, lo + 4), include_shared=lo == 0)
        params = dict(_layer_params(w, lo, lo + 4), bias=bias)
        part, state = layer.apply(
            {"params": params}, u, mutable=["intermediates"])
        assert bool(jnp.all(jnp.isfinite(part)))
        total = total + part
        received.append(
            int(state["intermediates"]["expert_tokens"][0].sum()))
    assert sum(received) == pairs
    if steer == "all_to_one_share":
        assert received[2] == u.shape[0] * min(
            4, whole["num_experts_per_tok"])
        # The reference routes without a bias: against the uncut layer.
        uncut = _expert_layer(whole, None).apply(
            {"params": dict(_layer_params(w, 0, 16), bias=bias)}, u)
        np.testing.assert_allclose(total, uncut, rtol=0, atol=2e-6)
        return
    np.testing.assert_allclose(total, ref.feed_forward(u, w, whole),
                               rtol=0, atol=2e-6)


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
        # The first token is the prefill's; tick t reads position p + t.
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
        full, state = eng.cache.kinds
        assert full.layer_ids == (2,) and full.state is None
        assert state.layer_ids == (0, 1, 3) and state.entries == 1
        assert eng.cache.pool_shapes == [(1, 49, BLOCK, 32), (3, 4, 16, 128)]
        # A re-used entry starts from the new prompt's state: whatever
        # the pool held is overwritten whole, never accumulated into.
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
            # Logits, not tokens: prefill (chunked scan) then decode
            # (the state pool, the paged K/V) serve the token the
            # reference's full forward by recurrence puts first.
            gap = jnp.max(logits, axis=-1) - served
            assert float(jnp.max(gap)) < LOGIT_TOLERANCE, (plen, new)
        stats = eng.stats()
        assert stats["admissions"] == stats["evictions"] == len(REQUESTS)
        # The attention layer's blocks and lengths only: a request of p
        # prompt tokens and n answers is read at lengths p + 1 ... p + n - 1.
        assert stats["context_tokens"] == sum(
            sum(range(plen + 1, plen + new)) for plen, new in REQUESTS)
        assert stats["kv_blocks_tabled"] == stats["decode_steps"] * 3 * 16
        assert stats["kv_blocks_window"] == stats["kv_blocks_uniform"] == 0
        # The state kind reports its own: a live slot's state a tick.
        assert stats["state_entries"] == stats["decode_steps"] * 3
        assert stats["state_entries_used"] == stats["slot_steps_active"]
        assert stats["state_bytes"] == (
            2 * stats["state_entries_used"] * eng.cache.state_entry_bytes)
        assert eng.cache.used_blocks == 0
    finally:
        eng.close()


def _ref_columns(weights, tokens, cfg):
    """The reference's full forward (the recurrence one token at a time)
    once more, keeping what a cache's tails hold the last three of: every
    Mamba layer's pre-convolution columns. ``(logits [seq, vocab],
    columns [state layers, seq, conv_dim])``."""
    *_, inner, conv_dim = ref._sizes(cfg)

    def forward(weights, tokens):
        x = cfg["embedding_multiplier"] * weights["embed"][tokens].astype(
            jnp.float32)
        columns = []
        for kind, w in zip(cfg["layer_types"], weights["layers"]):
            if kind == "mamba":
                u = ref._rms_norm(x, w["norm_in"], cfg["rms_norm_eps"])
                columns.append(ref._mm("td,dn->tn", u, w["w_in"], "f32")[
                    :, inner:inner + conv_dim])
            h, u = ref.mix(x, w, cfg, kind, "f32")
            x = h + cfg["residual_multiplier"] * ref.feed_forward(
                u, w, cfg, "f32")
        return ref.head(x, weights, cfg), jnp.stack(columns)

    return jax.jit(forward)(weights, tokens)


# (prompt, answer, the iteration it is submitted at): three slots, so the
# later ones wait for an eviction and join into the slot, the blocks and
# the state entry a finished request has just left, while others decode.
ARRIVALS = ((5, 24, 0), (33, 9, 0), (2, 40, 0), (17, 30, 3), (BLOCK, 6, 10),
            (1, 22, 12), (40, 12, 30), (3, 28, 44))


def test_64_ticks_of_joins_and_evictions_keep_every_live_tail():
    """The engine driven by hand: after EVERY iteration each live slot's
    entry of the tail pool holds the last three pre-convolution columns of
    exactly the tokens its sequence has fed, in every state layer (a tail
    written to another slot's entry, or one left by the entry's last
    holder, is not that), the trash entry is never written, and the
    served tokens are the ones the reference's plain forward puts first."""
    cfg = _cfg()
    model, variables, weights = _model_and_weights(cfg)
    eng = InferenceEngine(model, variables, attention="naive", slots=3,
                          block_size=BLOCK, max_len=128, check_memory=False)
    try:
        at = eng.cache.state_kind
        shape = eng.cache.kinds[at].state[1]
        assert shape == (3, 160) and eng.cache.tail_tiles == 4
        assert eng.cache.v_pools[at].shape == (3, 4, 4, 128)
        # Noise where zeros were: what a prefill does not overwrite whole,
        # or a tick writes where no live slot points, shows.
        eng.cache.v_pools = tuple(
            jnp.full_like(pool, 1e3) if i == at else pool
            for i, pool in enumerate(eng.cache.v_pools))
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 512, plen).astype(np.int32)
                   for plen, _, _ in ARRIVALS]
        requests, seen, iteration = {}, [], 0
        while iteration < 200:
            for i, (_, new, due) in enumerate(ARRIVALS):
                if due == iteration:
                    requests[i] = eng.submit(prompts[i], new)
            worked = eng.step()
            iteration += 1
            tails = np.asarray(eng.cache.v_pools[at])
            np.testing.assert_array_equal(tails[:, 0], 1e3)  # the trash entry
            for slot in eng._slots:
                if slot is not None:
                    index = next(i for i, r in requests.items()
                                 if r is slot.req)
                    seen.append((index, slot.position,
                                 tails[:, slot.blocks[at][0]]))
            if not worked and len(requests) == len(ARRIVALS):
                break
        stats = eng.stats()
        assert stats["decode_steps"] >= 64
        assert stats["admissions"] == stats["evictions"] == len(ARRIVALS)
        assert len({index for index, _, _ in seen}) == len(ARRIVALS)
        columns = {}
        for i, req in requests.items():
            plen, new, _ = ARRIVALS[i]
            assert req.status == "finished" and len(req.tokens) == new
            whole = jnp.asarray(np.concatenate([req.prompt, req.tokens]))
            logits, columns[i] = _ref_columns(weights, whole, cfg)
            logits = logits[plen - 1:-1]
            served = jnp.take_along_axis(
                logits, jnp.asarray(req.tokens)[:, None], axis=-1)[:, 0]
            assert float(jnp.max(jnp.max(logits, axis=-1) - served)
                         ) < LOGIT_TOLERANCE, ARRIVALS[i]
        for index, position, held in seen:
            # Positions ``position - 3 .. position - 1``, zeros before
            # the sequence's start.
            want = np.pad(np.asarray(columns[index]),
                          ((0, 0), (3, 0), (0, 0)))[:, position:position + 3]
            got = tail_from_pool_layout(held, shape)
            # float32 on both sides, as the mixer's own tests hold a tail.
            np.testing.assert_allclose(got, want, rtol=0, atol=1e-6,
                                       err_msg=f"{ARRIVALS[index]} at "
                                       f"position {position}")
            # The pool's padding lanes (480 of 512): written as zeros.
            np.testing.assert_array_equal(
                np.asarray(held).reshape(3, -1)[:, 480:], 0.0)
    finally:
        eng.close()


def test_spans_say_the_live_states_and_the_entry_taken():
    from fluxmpi_tpu.telemetry import tracing

    cfg = _cfg()
    model, variables, _ = _model_and_weights(cfg)
    eng = InferenceEngine(model, variables, slots=4, block_size=BLOCK,
                          max_len=64, check_memory=False)
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
        prepared = [e["args"] for e in events
                    if e.get("name") == "serve.decode.prepare"]
        # Two of the pool's four states live in both ticks; the attention
        # layer's lengths 12 + 21, then 13 + 22.
        assert [a["live_states_pct"] for a in prepared] == [50.0, 50.0]
        assert [a["context_tokens"] for a in prepared] == [33, 35]
        assert all("live_blocks_pct" in a for a in prepared)
        for name in ("serve.admit", "serve.prefill"):
            taken = [e["args"]["state_entry"] for e in events
                     if e.get("name") == name]
            assert len(taken) == 2 and all(1 <= t <= 4 for t in taken)
            assert taken[0] != taken[1]
    finally:
        eng.close()


def test_a_model_without_state_layers_says_nothing_of_states():
    """The other models' programs and spans are today's: no state kind,
    no ``live_states_pct``, the new counters at 0."""
    from fluxmpi_tpu.telemetry import tracing

    sarvam_ref = _load("sarvam.reference.py")
    sarvam = _load("sarvam.program.py")
    cfg = {**_json("tiny-sarvam"), "compute_dtype": "float32"}
    variables, _ = sarvam.to_program(
        sarvam_ref.make_weights(cfg, jax.random.PRNGKey(3)), cfg)
    eng = InferenceEngine(sarvam.build_model(cfg, "naive"), variables,
                          slots=2, block_size=BLOCK, max_len=64,
                          check_memory=False)
    try:
        assert eng.cache.state_kind is None
        assert eng.cache.state_entry_bytes == 0
        tracer = tracing.Tracer(enabled=True)
        previous = tracing.set_tracer(tracer)
        try:
            eng.submit(np.arange(9, dtype=np.int32), 3)
            eng.run()
        finally:
            tracing.set_tracer(previous)
        events = tracer.export()["traceEvents"]
        for event in events:
            assert "live_states_pct" not in event.get("args", {})
            assert "state_entry" not in event.get("args", {})
        stats = eng.stats()
        assert stats["state_entries"] == stats["state_entries_used"] == 0
        assert stats["state_bytes"] == 0
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# (f) the allocator and the bytes of the state kind
# ---------------------------------------------------------------------------

STATE = ((128, 64, 128), (3, 8448))
KEEPS_STATE = Keeps("state", state=STATE[0], tail=STATE[1])


def _cache(**kw):
    # The cell's first period: 9 state layers around one attention layer
    # of 8 K/V heads of 128; 4 sequences of 10 blocks of 256.
    layers = [KEEPS_STATE] * 5 + [Keeps("full", 8, 128)] + [KEEPS_STATE] * 4
    return BlockKVCache(
        layers, num_blocks=1 + 4 * 10, block_size=256, max_blocks_per_seq=10,
        dtype=jnp.bfloat16, **kw)


@pytest.mark.parametrize("tokens", [1, 256, 257, 2560])
def test_state_kind_allocates_one_entry_whatever_the_length(tokens):
    cache = _cache()
    full, state = cache.kinds
    assert cache.state_kind == 1 and state.layers == 9 and full.layers == 1
    assert state.entries == 1 and state.num_blocks == 1 + 4
    assert cache.blocks_for(tokens, 1) == 1
    assert cache.blocks_for(tokens, 0) == -(-tokens // 256)
    taken = [cache.alloc(tokens, 1) for _ in range(4)]
    assert sorted(t[0] for t in taken) == [1, 2, 3, 4]  # never the trash
    # Admission is bounded by STATES: the fifth sequence waits, however
    # short and however many blocks of K/V are free.
    assert not cache.can_alloc(1) and len(full.free) == 40
    with pytest.raises(RuntimeError, match="exhausted"):
        cache.alloc(1, 1)
    row = cache.table_row(taken[0], 1)
    assert row.shape == (1,) and row[0] == taken[0][0]
    cache.free(taken[2], 1)
    assert cache.can_alloc(tokens) and cache.alloc(tokens, 1) == taken[2]
    cache.free(taken[0], 1)
    with pytest.raises(ValueError, match="double free"):
        cache.free(taken[0], 1)


def test_state_kind_counts_float32_states_and_tails_by_their_bytes():
    cache = _cache()
    assert cache.pool_shapes == [(1, 41, 256, 1024), (9, 5, 128, 8192)]
    state = 128 * 64 * 128 * 4  # 4.19 MB a sequence a layer
    tail = 3 * 8448 * 2
    assert state == 4194304
    assert cache.state_entry_bytes == 9 * (state + tail)
    assert cache.pool_bytes == (
        2 * 41 * 256 * 1024 * 2 + 5 * 9 * (state + tail))
    k_pools, v_pools = cache.k_pools, cache.v_pools
    assert k_pools[1].shape == (9, 5, 128, 8192)
    assert k_pools[1].dtype == jnp.float32  # whatever the model's dtype
    # A tail's three columns of 8,448 end to end: 198 whole 128-lane tiles.
    assert v_pools[1].shape == (9, 5, 198, 128) and cache.tail_tiles == 198
    assert v_pools[1].dtype == k_pools[0].dtype == jnp.bfloat16
    assert sum(x.size * x.dtype.itemsize
               for x in k_pools + v_pools) == cache.pool_bytes
    cache.drop_pools()


def test_state_kind_refuses_what_it_cannot_hold():
    geometry = dict(num_blocks=9, block_size=4, max_blocks_per_seq=2)
    with pytest.raises(ValueError, match="one state shape a model"):
        BlockKVCache([KEEPS_STATE,
                      Keeps("state", state=(2, 2, 2), tail=(3, 8))],
                     **geometry)
    with pytest.raises(ValueError, match="and no other, names its window"):
        BlockKVCache([KEEPS_STATE._replace(window=16), Keeps("full", 1, 8)],
                     **geometry)
    # A model of state layers alone: one kind, live slots told by it; a
    # sublayer that keeps nothing is no layer of the cache.
    alone = BlockKVCache([KEEPS_STATE, None, KEEPS_STATE], **geometry)
    assert alone.num_layers == 2
    assert [k.state for k in alone.kinds] == [STATE]
    assert alone.kinds[0].num_blocks == 1 + 4
