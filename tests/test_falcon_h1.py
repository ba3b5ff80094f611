"""A block of two mixers side by side (``falcon_h1``: a Mamba-2 mixer and
rotary full attention on ONE normed input, muP multipliers on every
branch, a dense SwiGLU MLP) through the configuration-driven decoder LM,
both of the cache's pools a layer and the serving engine, against the
plain reference the benchmark keeps
(``benchmarks/configs/falcon.reference.py``: float32 at ``highest``, the
recurrence one token at a time, dense attention with rotary), at a small
size on seeded random weights: 2 groups of B and C, 5 query heads a K/V
head, a state wider than a head, every multiplier away from 1.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _oracles import Kept as _Kept, served_logits as _served_logits
from fluxmpi_tpu.models import DecoderConfig, Keeps
from fluxmpi_tpu.models.decoder import MambaMixer
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


ref = _load("falcon.reference.py")
prog = _load("falcon.program.py")


def _json(name):
    with open(os.path.join(CONFIGS, f"{name}.json"), encoding="utf-8") as f:
        return json.load(f)


def _cfg(**changes):
    """The rehearsal configuration (3 layers, each 8 Mamba heads of 8 over
    a state of 16 in 2 groups BESIDE 10 query over 2 K/V heads of 16, an
    MLP of 96), float32 compute so that the comparison is tight."""
    cfg = _json("tiny-falcon-h1")
    cfg.update({"compute_dtype": "float32", **changes})
    return cfg


def _ref_logits(weights, tokens, cfg, precision="f32", state_scale=1.0):
    return jax.jit(lambda w, t: ref.logits(
        w, t, cfg, precision, state_scale))(weights, tokens)


def _model_and_weights(cfg, seed=3):
    weights = ref.make_weights(cfg, jax.random.PRNGKey(seed))
    variables, _ = prog.to_program(weights, cfg)
    return prog.build_model(cfg, "naive"), variables, weights


# ---------------------------------------------------------------------------
# (a) the configuration
# ---------------------------------------------------------------------------


def test_from_hf_maps_the_falcon_keys_and_leaves_the_others_as_they_were():
    cfg = _json("falcon-h1-34b")
    c = DecoderConfig.from_hf(cfg)
    # The file's ``layer_types`` is for the benchmark's byte counts; the
    # model's layers are the block of two mixers, every one.
    assert c.layer_types == ("mamba_attention",) * 4 and c.block == "pair"
    assert (c.hidden_size, c.intermediate_size, c.vocab_size) == (
        5120, 21504, 261120)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim) == (
        20, 4, 128)
    assert (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state) == (32, 128, 256)
    assert (c.mamba_inner, c.mamba_conv_dim) == (4096, 5120)
    assert (c.mamba_n_groups, c.mamba_d_conv, c.mamba_chunk_size) == (
        2, 4, 128)
    assert c.full_attention_rope and c.rope_theta == 1e11
    assert not c.qk_norm and not c.output_gate and c.norm_placement == "pre"
    assert not c.tie_word_embeddings and c.attention_multiplier is None
    assert c.num_dense_layers == 4 and c.expert_layer_ids == ()
    assert c.embedding_multiplier == pytest.approx(32 ** 0.5)
    assert (c.attention_in_multiplier, c.attention_out_multiplier) == (
        1, 0.0375)
    assert c.key_multiplier == pytest.approx(2 ** -6.5)
    assert c.ssm_in_multiplier == 0.25
    assert c.ssm_out_multiplier == pytest.approx(2 ** -3.5)
    assert c.ssm_multipliers == pytest.approx(
        (2 ** -1.5, 0.25, 2 ** -2.5, 0.5, 2 ** -1.5))
    assert c.mlp_multipliers == pytest.approx((2 ** -2.5, 1 / 89.6))
    assert c.lm_head_multiplier == 1 / 128
    assert isinstance(hash(c), int)  # a module field
    # The other models: no multiplier, no rotary on a full layer.
    plain = DecoderConfig(
        vocab_size=8, hidden_size=8, layer_types=("full_attention",),
        num_attention_heads=1, num_key_value_heads=1, head_dim=8,
        intermediate_size=8)
    new = ("full_attention_rope", "attention_in_multiplier",
           "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
           "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers",
           "lm_head_multiplier")
    for name in ("trinity-mini", "sarvam-105b", "granite-4.0-h-small",
                 "nemotron-3-nano-30b-a3b"):
        other = DecoderConfig.from_hf(_json(name))
        assert "mamba_attention" not in other.layer_types
        for field in new:
            assert getattr(other, field) == getattr(plain, field), field


@pytest.mark.parametrize("key, value", [
    ("attn_layer_indices", [0, 2]), ("mamba_use_mlp", False),
    ("mamba_norm_before_gate", True), ("mamba_rms_norm", False),
    ("mamba_conv_bias", False), ("mamba_proj_bias", True),
    ("attention_bias", True), ("mlp_bias", True), ("projectors_bias", True),
    ("hidden_act", "gelu"), ("rope_scaling", {"type": "linear"}),
    ("mamba_d_ssm", 96),
])
def test_from_hf_raises_for_what_it_cannot_build(key, value):
    with pytest.raises(ValueError, match=f"falcon_h1 as served.*{key}"):
        DecoderConfig.from_hf({**_json("tiny-falcon-h1"), key: value})


def test_config_refuses_what_the_layer_cannot_compute():
    base = dict(vocab_size=32, hidden_size=16, num_attention_heads=2,
                num_key_value_heads=2, head_dim=8, intermediate_size=16)
    with pytest.raises(ValueError, match="mamba layers need"):
        DecoderConfig(**base, layer_types=("mamba_attention",))
    # One sublayer a layer: the pair of mixers is no such layer.
    with pytest.raises(ValueError, match="unknown layer types"):
        DecoderConfig(**base, layer_types=("mamba_attention",),
                      block="single", mamba_n_heads=2, mamba_d_head=8,
                      mamba_d_state=8)
    with pytest.raises(ValueError, match="ssm_multipliers are five"):
        DecoderConfig(**base, layer_types=("full_attention",),
                      ssm_multipliers=(1.0, 1.0))


def test_falcon_parameter_tree_and_the_keeping_sublayers_in_call_order():
    cfg = _cfg()
    model, variables, _ = _model_and_weights(cfg)
    made = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))
    shapes = jax.tree_util.tree_map(lambda x: x.shape, made)
    assert shapes == jax.tree_util.tree_map(lambda x: x.shape, variables)
    params = variables["params"]
    assert params["head"].shape == (64, 512)  # untied
    for i in range(3):
        assert set(params[f"layer_{i}"]) == {
            "norm_in", "norm_pre_ff", "mamba", "attn", "mlp"}
    assert set(params["layer_0"]["attn"]) == {"wq", "wk", "wv", "wo"}
    assert params["layer_0"]["mamba"]["w_in"].shape == (
        64, 64 + (64 + 2 * 2 * 16) + 8)
    # Each layer keeps a STATE a sequence and K/V rows a token: two
    # keeping sublayers, the state numbered first.
    state = Keeps("state", state=(8, 8, 16), tail=(3, 64 + 2 * 2 * 16))
    assert model.cache_layers() == (state, Keeps("full", 2, 16)) * 3

    class Numbered:
        """A view whose handles say which sublayer's they are and what
        their mixer did with them."""

        def __init__(self, kinds):
            self.kinds, self.used = kinds, {}

        def sublayer(self, number):
            view = self

            class Handle:
                kind, reads_pool = view.kinds[number], False

                def keep(self, tail, state):
                    view.used[number] = "state"

                def attend(self, q, k, v):
                    view.used[number] = "kv"
                    return jnp.zeros_like(q)

            return Handle()

    # Each sublayer received the handle of its own number.
    view = Numbered(["state", "full"] * 3)
    model.apply(variables, jnp.zeros((1, 8), jnp.int32), cache=view)
    assert view.used == {n: ("state", "kv")[n % 2] for n in range(6)}
    # A model that numbers its sublayers otherwise than its cache does
    # fails while it is traced, and reads no other sublayer's rows.
    with pytest.raises(TypeError, match="keeps state was handed.*'full'"):
        model.apply(variables, jnp.zeros((1, 8), jnp.int32),
                    cache=Numbered(["full", "state"] * 3))


# ---------------------------------------------------------------------------
# (b) the Mamba mixer at these shapes: groups, a state wider than a head
# ---------------------------------------------------------------------------


def _mixer(cfg, seed=11):
    w = ref.mamba_weights(cfg, jax.random.PRNGKey(seed))
    params = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    return DecoderConfig.from_hf(cfg), {"params": params}, w


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
    np.testing.assert_allclose(out[:length], want, rtol=0, atol=2e-6)
    np.testing.assert_allclose(padded.state, plain.state, rtol=0, atol=2e-6)
    np.testing.assert_array_equal(padded.tail, plain.tail)
    # The five multipliers sit on the projection's segments, as the
    # reference's do.
    ref_out, want_state, want_tail = ref.mamba(
        u[:length], w, cfg, state_out=True)
    np.testing.assert_allclose(want, ref_out, rtol=0, atol=5e-5)
    np.testing.assert_allclose(padded.state[0], want_state, rtol=0, atol=5e-5)
    np.testing.assert_allclose(padded.tail[0], want_tail, rtol=0, atol=2e-6)


# ---------------------------------------------------------------------------
# (c) the model against the reference
# ---------------------------------------------------------------------------

# Logits of std 1.0 here. float32 compute against the float32 reference
# reads 2e-6 ... 9e-6 (rounding through 3 layers of two mixers and up to
# 64 steps of the recurrence); a state held in bfloat16 loses 2 ** -9 of
# itself a step and reads 2.8e-2 over 64 ticks; a state that adds
# nothing, 3 and more.
LOGIT_TOLERANCE = 1e-4


@pytest.mark.parametrize("attention, seq", [("naive", 5), ("naive", 37),
                                            ("flash", 32)])
def test_falcon_logits_match_the_reference(attention, seq):
    cfg = _cfg()
    model, variables, weights = _model_and_weights(cfg)
    model = model.clone(attention=attention)
    tokens = jax.random.randint(jax.random.PRNGKey(seq), (seq,), 0, 512)
    got = model.apply(variables, tokens[None])[0]
    want = _ref_logits(weights, tokens, cfg)
    np.testing.assert_allclose(got, want, rtol=0, atol=LOGIT_TOLERANCE)
    # The state matters: a reference whose state adds nothing is another
    # model by far more than the tolerance.
    zeroed = _ref_logits(weights, tokens, cfg, state_scale=0.0)
    assert float(jnp.max(jnp.abs(zeroed - want))) > 1000 * LOGIT_TOLERANCE


def test_every_branch_adds_its_tenth_of_a_layers_update():
    cfg = _cfg()
    weights = ref.make_weights(cfg, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (48,), 0, 512)
    x = cfg["embedding_multiplier"] * weights["embed"][tokens].astype(
        jnp.float32)
    for w in weights["layers"]:
        read = ref.branch_magnitudes(x, w, cfg)
        for part in ("attention", "state", "mlp"):
            assert read[part] > 0.1 * read["update"], (part, read)
        h, u = ref.mix(x, w, cfg)
        x = h + ref.mlp(u, w, cfg)


MULTIPLIERS = [
    ("embedding_multiplier", None), ("attention_in_multiplier", None),
    ("attention_out_multiplier", None), ("key_multiplier", None),
    ("ssm_in_multiplier", None), ("ssm_out_multiplier", None),
    ("lm_head_multiplier", None), ("mlp_multipliers", 0),
    ("mlp_multipliers", 1), ("ssm_multipliers", 0), ("ssm_multipliers", 1),
    ("ssm_multipliers", 2), ("ssm_multipliers", 3), ("ssm_multipliers", 4),
]


@pytest.mark.parametrize("name, at", MULTIPLIERS)
def test_each_multiplier_moved_alone_moves_the_logits_as_the_references(
        name, at):
    cfg = _cfg()
    tokens = jax.random.randint(jax.random.PRNGKey(4), (19,), 0, 512)
    base = _ref_logits(ref.make_weights(cfg, jax.random.PRNGKey(3)),
                       tokens, cfg)
    if at is None:
        moved = _cfg(**{name: cfg[name] * 1.5})
    else:
        values = list(cfg[name])
        values[at] *= 1.5
        moved = _cfg(**{name: values})
    model, variables, weights = _model_and_weights(moved)
    got = model.apply(variables, tokens[None])[0]
    want = _ref_logits(weights, tokens, moved)
    np.testing.assert_allclose(got, want, rtol=0, atol=LOGIT_TOLERANCE)
    # And it is no no-op: the logits are others than at the base.
    assert float(jnp.max(jnp.abs(want - base))) > 100 * LOGIT_TOLERANCE


def test_falcon_runs_in_bfloat16_and_a_lower_precision_is_further_off():
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
    # bfloat16 operands: under a fifth of the logits' spread at the
    # widest; the reference in fp8 (4 significant bits, its state held so
    # too) is several times further off.
    assert served < 0.2 * spread
    assert control > 3 * served


# ---------------------------------------------------------------------------
# (d) the engine: prefill, then decode through BOTH pools a layer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("state", ["float32", "bfloat16", "zeroed"])
def test_prefill_then_64_decode_ticks_through_both_pools(state):
    cfg = _cfg()
    model, variables, weights = _model_and_weights(cfg)
    eng = InferenceEngine(model, variables, attention="naive", slots=2,
                          block_size=BLOCK, max_len=128, check_memory=False)
    try:
        at = eng.cache.state_kind
        kind = eng.cache.kinds[at]
        assert eng.cache.k_pools[at].dtype == jnp.float32
        if state == "bfloat16":
            # No option chooses the state's dtype: the programs follow
            # the pool's, and the test swaps the pool.
            kind.k_pool = kind.k_pool.astype(state)
        prompt = np.random.default_rng(1).integers(0, 512, 21).astype(np.int32)
        if state == "zeroed":
            # The state the prefill left is wiped before the first tick:
            # the K/V rows alone do not carry the answer.
            real_prefill = eng._prefill_step

            def wiping(bucket):
                fn = real_prefill(bucket)

                def run(*args):
                    first, k_pools, v_pools = fn(*args)
                    return first, tuple(
                        jnp.zeros_like(p) if i == at else p
                        for i, p in enumerate(k_pools)), v_pools

                return run

            eng._prefill_step = wiping
        tokens, got = _served_logits(eng, variables, prompt, 64)
        full = jnp.asarray(np.concatenate([prompt, tokens[:-1]]))
        want = _ref_logits(weights, full, cfg)
        # The first token is the prefill's; tick t reads position p + t.
        assert tokens[0] == int(jnp.argmax(want[len(prompt) - 1]))
        worst = float(jnp.max(jnp.abs(got - want[len(prompt):])))
        if state == "float32":
            assert worst < LOGIT_TOLERANCE
        else:
            assert worst > 10 * LOGIT_TOLERANCE
    finally:
        eng.close()


def test_a_padded_prompt_leaves_the_unpadded_state_tail_and_rows():
    """The prefill program at two buckets: a prompt of 11 tokens padded
    to 16 and to 32 leaves the same state, the same tail and the same 11
    K/V rows in every layer; what lies past the prompt went to the trash
    block."""
    cfg = _cfg()
    model, variables, _ = _model_and_weights(cfg)
    prompt = np.random.default_rng(2).integers(0, 512, 11).astype(np.int32)
    kept = []
    for bucket in (16, 32):
        eng = InferenceEngine(model, variables, attention="naive", slots=2,
                              block_size=16, max_len=64, check_memory=False)
        try:
            cache = eng.cache
            full, state = cache.kinds
            tables = [cache.table_row(cache.alloc(40, kind), kind)
                      for kind in range(2)]
            padded = np.zeros((bucket,), np.int32)
            padded[:11] = prompt
            first, k_pools, v_pools = eng._prefill_step(bucket)(
                variables, cache.k_pools, cache.v_pools, jnp.asarray(padded),
                jnp.int32(11), tuple(jnp.asarray(t) for t in tables))
            entry, block = tables[1][0], tables[0][0]
            kept.append((
                int(first), np.asarray(k_pools[1][:, entry]),
                np.asarray(v_pools[1][:, entry]),
                np.asarray(k_pools[0][:, block, :11]),
                np.asarray(v_pools[0][:, block, :11]),
                np.asarray(k_pools[0][:, block, 11:]),
            ))
        finally:
            eng.close()
    short, long = kept
    assert short[0] == long[0]
    for a, b in zip(short[1:5], long[1:5]):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-6)
    assert np.abs(short[3]).max() > 0
    np.testing.assert_array_equal(short[5], 0.0)  # rows past the prompt
    np.testing.assert_array_equal(long[5], 0.0)


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
        # The cache's layers are the keeping SUBLAYERS: six of them, the
        # states even and the K/V odd, a kind each.
        full, state = eng.cache.kinds
        assert eng.cache.num_layers == 6
        assert full.layer_ids == (1, 3, 5) and full.state is None
        assert state.layer_ids == (0, 2, 4) and state.entries == 1
        assert eng.cache.pool_shapes == [(3, 49, BLOCK, 32), (3, 4, 16, 64)]
        # A re-used entry starts from the new prompt's state.
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
        assert stats["admissions"] == stats["evictions"] == len(REQUESTS)
        # Context is counted once a request, whatever the K/V sublayers.
        assert stats["context_tokens"] == sum(
            sum(range(plen + 1, plen + new)) for plen, new in REQUESTS)
        # Blocks by the K/V sublayers (3), states by the state sublayers.
        assert stats["kv_blocks_tabled"] == stats["decode_steps"] * 3 * 3 * 16
        assert stats["kv_sublayers"] == stats["state_sublayers"] == 3
        assert stats["state_entries"] == stats["decode_steps"] * 3
        assert stats["state_entries_used"] == stats["slot_steps_active"]
        assert stats["state_bytes"] == (
            2 * stats["state_entries_used"] * eng.cache.state_entry_bytes)
        assert eng.cache.state_entry_bytes == 3 * (
            4 * 8 * 8 * 16 + 4 * 3 * 128)
        assert eng.cache.used_blocks == 0
    finally:
        eng.close()


@pytest.mark.parametrize("short_of", ["states", "blocks"])
def test_admission_stops_at_whichever_of_states_or_blocks_runs_out(short_of):
    cfg = _cfg()
    model, variables, _ = _model_and_weights(cfg)
    # 4 slots of 8 blocks: 4 states. ``blocks``: a pool of 12 blocks
    # holds one request of 64 tokens (8 blocks) and no second.
    blocks = None if short_of == "states" else 1 + 12
    eng = InferenceEngine(model, variables, attention="naive", slots=4,
                          block_size=BLOCK, max_len=64, num_blocks=blocks,
                          check_memory=False)
    try:
        full, state = eng.cache.kinds
        rng = np.random.default_rng(3)
        if short_of == "states":
            assert (full.num_blocks, state.num_blocks) == (33, 5)
            # Short requests: a block each, a STATE each; the fifth waits
            # for a state with 28 blocks free.
            taken = [eng.cache.alloc(4, kind) for _ in range(4)
                     for kind in (0, 1)]
            assert len(full.free) == 28 and not state.free
            assert not eng.cache.can_alloc(1)
            eng.cache.free(taken[1], 1)
            assert eng.cache.can_alloc(1)
        else:
            assert (full.num_blocks, state.num_blocks) == (13, 2)
            long = rng.integers(0, 512, 40).astype(np.int32)
            first = eng.submit(long, 24)
            second = eng.submit(long[:30], 30)
            eng.step()
            # One request holds 8 of the 12 blocks: the second needs 8.
            assert first.status == "active" and second.status == "queued"
            assert len(full.free) == 4
            eng.run()
            assert second.status == "finished"
    finally:
        eng.close()


ARRIVALS = ((5, 24, 0), (33, 9, 0), (2, 40, 0), (17, 30, 3), (BLOCK, 6, 10),
            (1, 22, 12), (40, 12, 30), (3, 28, 44))


def test_64_ticks_of_joins_and_evictions_keep_every_live_state_and_row():
    """The engine driven by hand through joins and evictions: every
    request is served the tokens the reference's plain forward puts
    first, the trash entry and the trash block are never read into a
    result, and after the run every block and entry is back."""
    cfg = _cfg()
    model, variables, weights = _model_and_weights(cfg)
    eng = InferenceEngine(model, variables, attention="naive", slots=3,
                          block_size=BLOCK, max_len=128, check_memory=False)
    try:
        at = eng.cache.state_kind
        # Noise where zeros were: what a prefill does not overwrite whole
        # shows in the tokens served.
        eng.cache.v_pools = tuple(
            jnp.full_like(pool, 1e3) if i == at else pool
            for i, pool in enumerate(eng.cache.v_pools))
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 512, plen).astype(np.int32)
                   for plen, _, _ in ARRIVALS]
        requests, iteration = {}, 0
        while iteration < 200:
            for i, (_, new, due) in enumerate(ARRIVALS):
                if due == iteration:
                    requests[i] = eng.submit(prompts[i], new)
            worked = eng.step()
            iteration += 1
            if not worked and len(requests) == len(ARRIVALS):
                break
        stats = eng.stats()
        assert stats["decode_steps"] >= 64
        assert stats["admissions"] == stats["evictions"] == len(ARRIVALS)
        for i, req in requests.items():
            plen, new, _ = ARRIVALS[i]
            assert req.status == "finished" and len(req.tokens) == new
            whole = jnp.asarray(np.concatenate([req.prompt, req.tokens]))
            logits = _ref_logits(weights, whole, cfg)[plen - 1:-1]
            served = jnp.take_along_axis(
                logits, jnp.asarray(req.tokens)[:, None], axis=-1)[:, 0]
            assert float(jnp.max(jnp.max(logits, axis=-1) - served)
                         ) < LOGIT_TOLERANCE, ARRIVALS[i]
        assert eng.cache.used_blocks == 0
    finally:
        eng.close()


def test_spans_say_states_and_blocks_of_the_keeping_sublayers():
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
        # Two of the pool's four states live in both ticks; lengths 12 +
        # 21, then 13 + 22, once a request; blocks 2 + 3 of the 4 x 8 a
        # K/V sublayer spans.
        assert [a["live_states_pct"] for a in prepared] == [50.0, 50.0]
        assert [a["context_tokens"] for a in prepared] == [33, 35]
        assert [a["live_blocks_pct"] for a in prepared] == [
            100.0 * 5 / 32] * 2
        assert all(a["state_sublayers"] == a["kv_sublayers"] == 3
                   for a in prepared)
        for name in ("serve.admit", "serve.prefill"):
            taken = [e["args"]["state_entry"] for e in events
                     if e.get("name") == name]
            assert len(taken) == 2 and taken[0] != taken[1]
    finally:
        eng.close()
