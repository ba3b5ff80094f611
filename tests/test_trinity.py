"""The configuration-driven decoder LM, its expert layer and the serving
engine around them, against the plain reference the benchmark keeps
(``benchmarks/configs/trinity.reference.py``: float32 at ``highest``,
dense over the experts), at a small size on seeded random weights.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fluxmpi_tpu.models import DecoderConfig, DecoderLM, ExpertMLP, Keeps
from fluxmpi_tpu.serving import InferenceEngine
from fluxmpi_tpu.serving.cache import BlockKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmarks", "configs")
SLIDING, FULL = "sliding_attention", "full_attention"
WINDOW, BLOCK = 32, 8


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(CONFIGS, name)
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("trinity.reference.py")
prog = _load("trinity.program.py")


def _cfg(**changes):
    """The rehearsal configuration (window 32, 16 experts, top-4, one
    shared), float32 compute so that the comparison is tight."""
    with open(os.path.join(CONFIGS, "tiny-trinity.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    cfg.update(compute_dtype="float32", **changes)
    cfg["num_hidden_layers"] = len(cfg["layer_types"])
    return cfg


def _model_and_weights(cfg, seed=3):
    weights = ref.make_weights(cfg, jax.random.PRNGKey(seed))
    variables, _ = prog.to_program(weights, cfg)
    return prog.build_model(cfg, "naive"), variables, weights


# ---------------------------------------------------------------------------
# (a) the full forward against the reference
# ---------------------------------------------------------------------------

LAYERINGS = {
    "window_only_dense": ([SLIDING, SLIDING], 2),
    "full_only_experts": ([FULL, FULL], 0),
    "dense_then_period": ([SLIDING, SLIDING, SLIDING, FULL], 1),
    "full_first": ([FULL, SLIDING, FULL, SLIDING], 1),
}


@pytest.mark.parametrize("attention", ["naive", "flash"])
@pytest.mark.parametrize("layering", sorted(LAYERINGS))
def test_decoder_lm_logits_match_the_reference(layering, attention):
    layer_types, dense = LAYERINGS[layering]
    cfg = _cfg(layer_types=layer_types, num_dense_layers=dense)
    model, variables, weights = _model_and_weights(cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 2 * WINDOW + 16), 0, cfg["vocab_size"]
    )
    got = model.clone(attention=attention).apply(variables, tokens)
    want = jnp.stack([ref.logits(weights, row, cfg) for row in tokens])
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    # The head at one position a row is the same head.
    at = jnp.asarray([3, tokens.shape[1] - 1])
    last = model.apply(variables, tokens, head_at=at)
    np.testing.assert_allclose(
        last, want[jnp.arange(2), at], rtol=0, atol=2e-5
    )


def test_decoder_lm_parameter_tree_is_the_mapped_reference_layout():
    cfg = _cfg()
    model, variables, _ = _model_and_weights(cfg)
    made = jax.eval_shape(
        lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0),
    )
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: x.shape, tree)
    assert shapes(made) == shapes(variables)
    ring = Keeps("window", 2, 16, WINDOW)
    assert model.cache_layers() == (ring, ring, Keeps("full", 2, 16), ring)


def test_decoder_lm_runs_in_bfloat16_with_float32_logits():
    cfg = _cfg()
    cfg["compute_dtype"] = "bfloat16"
    model, variables, weights = _model_and_weights(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 40), 0, 512)
    got = model.apply(variables, tokens)
    want = ref.logits(weights, tokens[0], cfg)
    assert got.dtype == jnp.float32
    # Near-ties of the router's scores flip under bfloat16 activations.
    assert float(jnp.mean(jnp.abs(got[0] - want))) < 0.05


def test_decoder_config_refuses_what_it_cannot_build():
    base = dict(vocab_size=8, hidden_size=8, num_attention_heads=4,
                num_key_value_heads=2, head_dim=4, intermediate_size=8)
    with pytest.raises(ValueError, match="unknown layer types"):
        DecoderConfig(layer_types=("linear_attention",), **base)
    with pytest.raises(ValueError, match="need sliding_window"):
        DecoderConfig(layer_types=(SLIDING,), **base)
    with pytest.raises(ValueError, match="not a multiple"):
        DecoderConfig(layer_types=(FULL,), **{**base,
                                              "num_key_value_heads": 3})
    with pytest.raises(ValueError, match="score_func"):
        DecoderConfig(layer_types=(FULL,), score_func="tanh", **base)


# ---------------------------------------------------------------------------
# (b), (c) the expert layer alone
# ---------------------------------------------------------------------------


def _expert_layer(cfg, expert_range=None, include_shared=True):
    return ExpertMLP(
        num_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        width=cfg["moe_intermediate_size"],
        shared_width=cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        route_norm=cfg["route_norm"], route_scale=cfg["route_scale"],
        expert_range=expert_range, include_shared=include_shared,
        dtype=jnp.float32,
    )


def _layer_params(w, lo=0, hi=None):
    """The program's parameters of one expert layer from the reference's
    weights (float32 copies, so that a gradient means something), the
    experts ``[lo, hi)`` held."""
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    return {
        "router": f32(w["router"]), "bias": f32(w["bias"]),
        "w1": f32(w["ew1"][lo:hi]), "w3": f32(w["ew3"][lo:hi]),
        "w2": f32(w["ew2"][lo:hi]),
        "shared": {k: f32(w[k]) for k in ("w1", "w3", "w2")},
    }


def _uneven(cfg, routing):
    """An expert layer's weights and 48 tokens; ``uneven`` routing gives
    expert 2 most tokens (a large bias) and expert 5 none."""
    w = ref.layer_weights(cfg, jax.random.PRNGKey(5), 1)
    if routing == "uneven":
        w["bias"] = w["bias"].at[2].set(4.0).at[5].set(-4.0)
    u = jax.random.normal(jax.random.PRNGKey(6), (48, cfg["hidden_size"]))
    return w, u


@pytest.mark.parametrize("routing", ["near_uniform", "uneven"])
def test_expert_layer_matches_dense_over_experts(routing):
    cfg = _cfg()
    w, u = _uneven(cfg, routing)
    layer = _expert_layer(cfg)
    got, state = layer.apply({"params": _layer_params(w)}, u,
                             mutable=["intermediates"])
    np.testing.assert_allclose(got, ref.expert_layer(u, w, cfg),
                               rtol=0, atol=1e-5)
    counts = np.asarray(state["intermediates"]["expert_tokens"][0])
    # No token is dropped: every token's top_k pairs are counted.
    assert counts.sum() == u.shape[0] * cfg["num_experts_per_tok"]
    if routing == "uneven":
        assert counts[2] == u.shape[0] and counts[5] == 0
    # Masked tokens are routed nowhere and keep the shared expert.
    mask = jnp.arange(u.shape[0]) < 40
    masked, state = layer.apply({"params": _layer_params(w)}, u, mask,
                                mutable=["intermediates"])
    np.testing.assert_allclose(masked[:40], got[:40], rtol=0, atol=1e-6)
    np.testing.assert_allclose(
        masked[40:], ref.swiglu(u[40:], w["w1"], w["w3"], w["w2"]),
        rtol=0, atol=1e-5,
    )
    assert int(state["intermediates"]["expert_tokens"][0].sum()) == (
        40 * cfg["num_experts_per_tok"]
    )


@pytest.mark.parametrize("routing", ["near_uniform", "uneven"])
def test_expert_layer_gradients_match_dense_over_experts(routing):
    cfg = _cfg()
    w, u = _uneven(cfg, routing)
    layer = _expert_layer(cfg)
    target = jax.random.normal(jax.random.PRNGKey(7), u.shape)

    def program_loss(params, u):
        return jnp.sum(layer.apply({"params": params}, u) * target)

    def reference_loss(w, u):
        return jnp.sum(ref.expert_layer(u, w, cfg) * target)

    got_p, got_u = jax.grad(program_loss, argnums=(0, 1))(_layer_params(w), u)
    w32 = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    want_w, want_u = jax.grad(reference_loss, argnums=(0, 1))(w32, u)
    np.testing.assert_allclose(got_u, want_u, rtol=0, atol=2e-5)
    want_p = _layer_params(want_w)
    for path, got in jax.tree_util.tree_leaves_with_path(got_p):
        name = jax.tree_util.keystr(path)
        if name == "['bias']":
            continue  # the bias only chooses: no gradient on either side
        want = want_p
        for entry in path:
            want = want[entry.key]
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5,
                                   err_msg=name)
    if routing == "uneven":
        # The expert no token reached has a zero gradient.
        assert float(jnp.max(jnp.abs(got_p["w1"][5]))) == 0.0


@pytest.mark.parametrize("held", [4, 8, 16])
def test_expert_shares_add_up_to_the_whole_layer(held):
    """The parts computed by the ranges [0, held) [held, 2 held) ... of
    the 16-expert layer, the shared expert counted once, sum to the
    uncut reference's output for the whole layer."""
    cfg = _cfg()
    w, u = _uneven(cfg, "uneven")
    total = 0.0
    for lo in range(0, cfg["num_experts"], held):
        layer = _expert_layer(cfg, (lo, lo + held), include_shared=lo == 0)
        total = total + layer.apply(
            {"params": _layer_params(w, lo, lo + held)}, u
        )
    np.testing.assert_allclose(total, ref.expert_layer(u, w, cfg),
                               rtol=0, atol=2e-5)


# ---------------------------------------------------------------------------
# (d) the engine: prefill, then decode through the paged cache
# ---------------------------------------------------------------------------

# Prompts shorter than, equal to and longer than the window (32), some
# past the window AND the ring (40 positions), more requests than slots
# so that they join mid-flight.
REQUESTS = ((5, 20), (WINDOW, 30), (70, 40), (100, 28), (WINDOW + 1, 10),
            (BLOCK, 3))


@pytest.mark.parametrize("attention", ["naive", "flash"])
def test_engine_serves_what_the_reference_puts_first(attention):
    cfg = _cfg()
    model, variables, weights = _model_and_weights(cfg)
    eng = InferenceEngine(model, variables, attention=attention, slots=3,
                          block_size=BLOCK, max_len=128, check_memory=False)
    try:
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
            # Logits, not tokens: the served token's reference logit is
            # the reference's best, to float32 rounding.
            gap = jnp.max(logits, axis=-1) - served
            assert float(jnp.max(gap)) < 1e-5, (plen, new)
        stats = eng.stats()
        assert stats["admissions"] == stats["evictions"] == len(REQUESTS)
        # Three expert layers, four experts a token, active slots only.
        assert stats["expert_tokens"] == stats["slot_steps_active"] * 4 * 3
        assert 0 < stats["experts_touched"] <= stats["expert_slots"]
        assert stats["expert_slots"] == stats["decode_steps"] * 3 * 16
        # Every block came back.
        assert eng.cache.used_blocks == 0
    finally:
        eng.close()


def test_engine_tick_returns_expert_counts_with_the_tokens_in_one_array():
    cfg = _cfg()
    model, variables, _ = _model_and_weights(cfg)
    eng = InferenceEngine(model, variables, slots=2, block_size=BLOCK,
                          max_len=64, check_memory=False)
    try:
        cache = eng.cache
        out, _, _ = eng._decode_step(
            variables, cache.k_pools, cache.v_pools, *eng._idle_tick(),
            eng._last_output(), jnp.zeros((2,), bool),
        )
        # 2 tokens, then 3 expert layers x 16 experts; idle slots (all
        # trash tables) are routed nowhere.
        assert out.shape == (2 + 3 * 16,) and out.dtype == jnp.int32
        assert int(out[2:].sum()) == 0
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# (e) the allocator: window layers hold a ring, full layers the context
# ---------------------------------------------------------------------------


def _cache(**kw):
    ring, full = Keeps("window", 2, 16, WINDOW), Keeps("full", 2, 16)
    return BlockKVCache(
        [ring, ring, full, ring], num_blocks=1 + 3 * 16, block_size=BLOCK,
        max_blocks_per_seq=16, **kw
    )


@pytest.mark.parametrize("tokens", [1, BLOCK, WINDOW, WINDOW + BLOCK,
                                    WINDOW + BLOCK + 1, 128])
def test_window_layers_hold_a_ring_and_blocks_return(tokens):
    cache = _cache()
    full, window = cache.kinds
    ring = -(-(WINDOW + BLOCK) // BLOCK)
    assert (full.layers, full.window, full.entries) == (1, None, 16)
    assert (window.layers, window.window, window.entries) == (3, WINDOW, ring)
    assert cache.layer_kind == [(1, 0), (1, 1), (0, 0), (1, 2)]
    free = cache.free_blocks
    held = [cache.alloc(tokens, kind) for kind in (0, 1)]
    need = -(-tokens // BLOCK)
    assert len(held[0]) == need
    # Never more than ceil((window + block) / block) blocks of a sequence.
    assert len(held[1]) == min(need, ring)
    assert cache.table_row(held[1], 1).shape == (ring,)
    assert cache.table_row(held[0]).shape == (16,)
    assert cache.used_blocks == len(held[0]) + len(held[1])
    for kind in (0, 1):
        cache.free(held[kind], kind)
    assert cache.free_blocks == free and cache.used_blocks == 0
    with pytest.raises(ValueError, match="double free"):
        cache.free(held[1][:1], 1)


def test_cache_pools_follow_the_kinds():
    cache = _cache(dtype=jnp.bfloat16)
    ring = -(-(WINDOW + BLOCK) // BLOCK)
    assert cache.pool_shapes == [(1, 49, BLOCK, 32), (3, 1 + 3 * ring, BLOCK, 32)]
    assert cache.pool_bytes == 2 * 2 * (49 + 3 * (1 + 3 * ring)) * BLOCK * 32
    assert cache.can_alloc(128) and cache.fits_pool(128)
    held = [cache.alloc(128, 1) for _ in range(3)]  # the window kind is full
    assert not cache.can_alloc(1)
    assert cache.fits_pool(128)
    cache.free(held[0], 1)
    assert cache.can_alloc(128)
    with pytest.raises(ValueError, match="one window size"):
        BlockKVCache([Keeps("window", 1, 4, 8), Keeps("window", 1, 4, 16)],
                     num_blocks=9, block_size=4, max_blocks_per_seq=4)


def test_engine_counts_blocks_held_by_kind_against_one_uniform_pool():
    cfg = _cfg()
    model, variables, _ = _model_and_weights(cfg)
    eng = InferenceEngine(model, variables, slots=2, block_size=BLOCK,
                          max_len=128, check_memory=False)
    try:
        rng = np.random.default_rng(1)
        # Inside the ring: the kinds together hold what one pool would.
        eng.submit(rng.integers(0, 512, 6).astype(np.int32), 4)
        eng.run()
        s = eng.stats()
        assert s["kv_blocks_window"] + s["kv_blocks_full"] == s["kv_blocks_uniform"]
        # Past the window: less.
        eng.submit(rng.integers(0, 512, 90).astype(np.int32), 12)
        eng.run()
        s = eng.stats()
        assert s["kv_blocks_window"] + s["kv_blocks_full"] < s["kv_blocks_uniform"]
        assert s["kv_blocks_live"] < s["kv_blocks_tabled"]
    finally:
        eng.close()


def test_gpt2_shaped_model_reports_the_new_counters_as_zero(tmp_path):
    from fluxmpi_tpu.models import TransformerLM

    lm = TransformerLM(vocab_size=32, max_len=32, num_layers=1, d_model=16,
                       num_heads=2, d_ff=32)
    variables = lm.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                        train=False)
    eng = InferenceEngine(lm, variables, slots=2, block_size=8,
                          check_memory=False)
    try:
        eng.submit(np.arange(5, dtype=np.int32), 4)
        eng.run()
        s = eng.stats()
        assert len(eng.cache.kinds) == 1
        for key in ("kv_blocks_full", "kv_blocks_window", "kv_blocks_uniform",
                    "expert_tokens", "experts_touched", "expert_slots"):
            assert s[key] == 0
    finally:
        eng.close()
