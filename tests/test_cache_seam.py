"""The seam between a model and its serving cache (``serving/cache.py``:
the protocol): every keeping sublayer is handed the handle of its own
number, a handle of another kind is refused while tracing, the order a
block runs its mixers in changes nothing a pool holds, and the records a
model gives build the kinds the cache built from the three parallel lists
they replaced (PR 47)."""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fluxmpi_tpu.models import DecoderConfig, Keeps, TransformerLM
from fluxmpi_tpu.models.decoder import (
    Attention,
    LatentAttention,
    MambaMixer,
)
from fluxmpi_tpu.serving import BlockKVCache
from fluxmpi_tpu.serving.cache import DecodeView, PrefillView
from fluxmpi_tpu.serving.engine import _cache_layers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmarks", "configs")


def _json(name):
    with open(os.path.join(CONFIGS, f"{name}.json"), encoding="utf-8") as f:
        return json.load(f)


def _model(config_name, program):
    spec = importlib.util.spec_from_file_location(
        program.replace(".", "_"), os.path.join(CONFIGS, program))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.build_model(_json(config_name), "naive")


# What ``BlockKVCache.kinds`` held at the parent of PR 47 for every
# rehearsal configuration at its cell's geometry (4 slots of 128 positions
# in blocks of 16), built there from three parallel lists (windows, latent
# flags, state shapes): per kind ``(layer ids, window, entries, blocks,
# latent, state)``, then the pools' shapes and bytes.
_MAMBA = ((8, 16, 16), (3, 160))
PARENT_KINDS = {
    ("tiny-falcon-h1", "falcon.program.py"): (
        [((1, 3, 5), None, 8, 33, False, None),
         ((0, 2, 4), None, 1, 5, False, ((8, 8, 16), (3, 128)))],
        [(3, 33, 16, 32), (3, 5, 16, 64)], 275712),
    ("tiny-granite", "granite.program.py"): (
        [((2,), None, 8, 33, False, None),
         ((0, 1, 3), None, 1, 5, False, _MAMBA)],
        [(1, 33, 16, 32), (3, 5, 16, 128)], 205824),
    ("tiny-gpt2-bf16", "gpt2.program.py"): (
        [((0, 1), None, 8, 33, False, None)], [(2, 33, 16, 64)], 270336),
    ("tiny-nemotron", "nemotron.program.py"): (
        [((2,), None, 8, 33, False, None),
         ((0, 1), None, 1, 5, False, ((8, 16, 16), (3, 192)))],
        [(1, 33, 16, 32), (2, 5, 16, 128)], 162304),
    ("tiny-sarvam", "sarvam.program.py"): (
        [((0, 1, 2), None, 8, 33, True, None)], [(3, 33, 16, 128)], 405504),
    ("tiny-trinity", "trinity.program.py"): (
        [((2,), None, 8, 33, False, None),
         ((0, 1, 3), 32, 3, 13, False, None)],
        [(1, 33, 16, 32), (3, 13, 16, 32)], 147456),
}


@pytest.mark.parametrize("config_name, program", sorted(PARENT_KINDS))
def test_a_models_records_build_the_kinds_the_three_lists_built(
        config_name, program):
    model = _model(config_name, program)
    kinds, shapes, pool_bytes = PARENT_KINDS[config_name, program]
    cache = BlockKVCache(
        _cache_layers(model), num_blocks=1 + 4 * 8, block_size=16,
        max_blocks_per_seq=8, dtype=model.dtype)
    assert [(k.layer_ids, k.window, k.entries, k.num_blocks, k.latent,
             k.state) for k in cache.kinds] == kinds
    assert cache.pool_shapes == shapes and cache.pool_bytes == pool_bytes
    # A layer's number is its place among the records that keep something.
    assert sorted(n for k in cache.kinds for n in k.layer_ids) == list(
        range(cache.num_layers))
    assert [cache.kinds[at].layer_ids[i] for at, i in cache.layer_kind] == (
        list(range(cache.num_layers)))


def test_transformer_lm_is_given_the_records_of_its_full_layers():
    lm = TransformerLM(vocab_size=32, max_len=16, num_layers=3, d_model=24,
                       num_heads=4, d_ff=32)
    assert _cache_layers(lm) == (Keeps("full", 4, 6),) * 3


class _Wrong:
    """A handle of ``kind`` that offers nothing: reaching for it fails."""

    reads_pool = False

    def __init__(self, kind):
        self.kind = kind


def _tiny_config(**kw):
    return DecoderConfig(
        vocab_size=32, hidden_size=16, num_attention_heads=2,
        num_key_value_heads=2, head_dim=8, intermediate_size=16,
        sliding_window=8, kv_lora_rank=8, qk_nope_head_dim=4,
        qk_rope_head_dim=4, v_head_dim=4, mamba_n_heads=2, mamba_d_head=8,
        mamba_d_state=8, **kw)


@pytest.mark.parametrize("mixer, own, handed", [
    (mixer, own, handed)
    for mixer, own in (("full_attention", "full"),
                       ("sliding_attention", "window"),
                       ("latent_attention", "latent"), ("mamba", "state"))
    for handed in ("full", "window", "latent", "state") if handed != own
])
def test_a_mixer_refuses_the_handle_of_another_kind(mixer, own, handed):
    config = _tiny_config(layer_types=(mixer,))
    u = jnp.zeros((1, 4, 16))
    positions = jnp.arange(4)[None]
    if mixer == "mamba":
        module, args = MambaMixer(config, jnp.float32), (u, None)
    elif mixer == "latent_attention":
        module, args = LatentAttention(config, jnp.float32), (u, positions)
    else:
        module, args = Attention(config, mixer, jnp.float32), (u, positions)
    variables = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), *args))
    with pytest.raises(TypeError,
                       match=f"keeps {own} was handed.*{handed!r}"):
        jax.eval_shape(
            lambda v: module.apply(v, *args, _Wrong(handed)), variables)


def _falcon_cache():
    model = _model("tiny-falcon-h1", "falcon.program.py")
    return BlockKVCache(
        model.cache_layers(), num_blocks=1 + 2 * 4, block_size=8,
        max_blocks_per_seq=4, dtype=jnp.float32)


def _pools(cache, key):
    """Pools that hold something everywhere, so that a write shows."""
    keys = jax.random.split(key, 2 * len(cache.kinds))
    return tuple(
        tuple(jax.random.normal(k, kind_pool.shape, kind_pool.dtype)
              for k, kind_pool in zip(ks, pools))
        for ks, pools in ((keys[::2], cache.k_pools),
                          (keys[1::2], cache.v_pools)))


def _toy_block(step, attention_first):
    """``(pools, results)`` after three toy layers of ``tiny-falcon-h1``'s
    shapes ran against one view, each its Mamba mixer and its attention in
    the order asked, every mixer through the handle of its own number."""
    cache = _falcon_cache()
    k_pools, v_pools = _pools(cache, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    if step == "prefill":
        bucket = 16
        tables = (jnp.asarray([3, 5, 0, 0], jnp.int32),
                  jnp.asarray([2], jnp.int32))
        view = PrefillView(cache, k_pools, v_pools, tables, jnp.int32(11),
                           kernel=False)
        feeds = [((normal(1, 3, 128), normal(1, 8, 8, 16)),
                  (normal(1, bucket, 10, 16), normal(1, bucket, 2, 16),
                   normal(1, bucket, 2, 16))) for _ in range(3)]
    else:
        slots = 2  # slot 0 idles
        tables = (jnp.asarray([[0, 0, 0, 0], [3, 5, 0, 0]], jnp.int32),
                  jnp.asarray([[0], [2]], jnp.int32))
        view = DecodeView(cache, k_pools, v_pools, tables,
                          jnp.asarray([0, 9], jnp.int32), kernel=False)
        feeds = [((normal(slots, 3, 128), normal(slots, 8, 8),
                   jnp.abs(normal(slots, 8)),
                   jnp.exp(-jnp.abs(normal(slots, 8))),
                   normal(slots, 2, 16), normal(slots, 2, 16)),
                  (normal(slots, 1, 10, 16), normal(slots, 1, 2, 16),
                   normal(slots, 1, 2, 16))) for _ in range(3)]
    results = {}
    for layer, (state_feed, kv_feed) in enumerate(feeds):
        def mamba():
            handle = view.sublayer(2 * layer)
            assert handle.kind == "state"
            if step == "prefill":
                handle.keep(*state_feed)
            else:
                results[layer, "tail"] = handle.tail()
                results[layer, "y"] = handle.update(*state_feed)

        def attention():
            handle = view.sublayer(2 * layer + 1)
            assert handle.kind == "full"
            results[layer, "attention"] = handle.attend(*kv_feed)

        for mixer in ((attention, mamba) if attention_first
                      else (mamba, attention)):
            mixer()
    return (k_pools, v_pools), view.pools(), results


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_the_order_a_block_runs_its_mixers_in_changes_no_pool(step):
    """Every layer of ``tiny-falcon-h1`` keeps a state (its first keeping
    sublayer) and K/V rows (its second). A block that runs its attention
    BEFORE its Mamba mixer fills the pools as one that runs it after: each
    mixer holds the handle of its own number. (A counter of calls, which
    this replaced, gave the attention the state's rows.)"""
    (k_old, _), (k_new, v_new), results = _toy_block(step, False)
    # The pools took what they were given, in the sequence's own entry
    # (state 2) and blocks (3 and 5; a tick at position 9 writes in the
    # second), and nowhere else.
    assert not np.array_equal(k_new[1][:, 2], k_old[1][:, 2])
    assert not np.array_equal(k_new[0][:, 5], k_old[0][:, 5])
    np.testing.assert_array_equal(k_new[1][:, 1], k_old[1][:, 1])
    np.testing.assert_array_equal(k_new[0][:, 1], k_old[0][:, 1])
    _, swapped_pools, swapped = _toy_block(step, True)
    jax.tree_util.tree_map(np.testing.assert_array_equal,
                           ((k_new, v_new), results),
                           (swapped_pools, swapped))
