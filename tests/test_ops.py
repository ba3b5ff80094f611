"""Pallas flash attention vs dense oracle (interpret mode on CPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def _dense(q, k, v, causal=False):
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _qkv(b=2, s=64, h=2, d=32, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
        for _ in range(3)
    )


def test_flash_matches_dense(world):
    from fluxmpi_tpu.ops import flash_attention

    q, k, v = _qkv()
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_dense(q, k, v)), atol=2e-5
    )


def test_flash_causal(world):
    from fluxmpi_tpu.ops import flash_attention

    q, k, v = _qkv(seed=1)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_dense(q, k, v, causal=True)), atol=2e-5
    )


def test_flash_single_block(world):
    from fluxmpi_tpu.ops import flash_attention

    q, k, v = _qkv(s=16, seed=2)
    out = flash_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_dense(q, k, v)), atol=2e-5
    )


def test_flash_bf16(world):
    from fluxmpi_tpu.ops import flash_attention

    q, k, v = (t.astype(jnp.bfloat16) for t in _qkv(seed=3))
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    assert out.dtype == jnp.bfloat16
    expected = _dense(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    )
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(expected), atol=0.05
    )


def test_flash_bad_blocks_rejected(world):
    from fluxmpi_tpu.ops import flash_attention

    q, k, v = _qkv(s=48, seed=4)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=32, block_k=32)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grad_matches_dense(world, causal):
    # The Pallas backward kernels (dq + dk/dv) against autodiff through the
    # dense oracle (VERDICT r1 next #3).
    from fluxmpi_tpu.ops import flash_attention

    q, k, v = _qkv(seed=5)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, causal=causal,
                                               block_q=32, block_k=32)))

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(_dense(q, k, v, causal=causal)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_lse_and_its_gradient(world):
    # flash_attention_with_lse: the lse output matches dense logsumexp and
    # its cotangent is honored (the merge key ring attention relies on).
    from fluxmpi_tpu.ops import flash_attention_with_lse

    q, k, v = _qkv(seed=6)
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    lse_dense = jax.scipy.special.logsumexp(s, axis=-1)  # [b, h, q]

    out, lse = flash_attention_with_lse(q, k, v, block_q=32, block_k=32)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(lse_dense), atol=1e-5
    )

    def loss_flash(q, k, v):
        out, lse = flash_attention_with_lse(q, k, v, block_q=32, block_k=32)
        return jnp.sum(jnp.cos(lse)) + jnp.sum(out**2)

    def loss_dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        return jnp.sum(jnp.cos(lse)) + jnp.sum(_dense(q, k, v) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_transformer_trains_through_flash_attention(world):
    # A TransformerLM whose attention is the Pallas kernel end-to-end: the
    # compiled DP train step runs and the flash model's gradients match the
    # dense-attention model's (same params, same batch).
    import optax

    import fluxmpi_tpu as fm
    from fluxmpi_tpu.models import TransformerLM
    from fluxmpi_tpu.ops import flash_attention_fn
    from fluxmpi_tpu.parallel import TrainState, make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    mesh = fm.global_mesh()
    kwargs = dict(vocab_size=64, max_len=32, num_layers=1, d_model=32,
                  num_heads=2, d_ff=64)
    flash_model = TransformerLM(
        attention_fn=flash_attention_fn(causal=True), **kwargs
    )
    dense_model = TransformerLM(**kwargs)

    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.integers(0, 64, size=(16, 32)).astype(np.int32))
    params = dense_model.init(jax.random.PRNGKey(0), tokens[:2], train=False)

    def make_loss(model):
        def loss_fn(p, mstate, batch):
            logits = model.apply(p, batch, train=True)
            targets = jnp.roll(batch, -1, axis=1)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], targets[:, :-1]
            ).mean()
            return loss, mstate

        return loss_fn

    gf = jax.grad(lambda p: make_loss(flash_model)(p, None, tokens)[0])(params)
    gd = jax.grad(lambda p: make_loss(dense_model)(p, None, tokens)[0])(params)
    flat_f = jax.tree_util.tree_leaves(gf)
    flat_d = jax.tree_util.tree_leaves(gd)
    for a, b in zip(flat_f, flat_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)

    step = make_train_step(
        make_loss(flash_model), optax.adam(1e-3), mesh=mesh, style="auto"
    )
    state = replicate(TrainState.create(params, optax.adam(1e-3)), mesh)
    data = shard_batch(tokens, mesh)
    state, loss0 = step(state, data)
    state, loss1 = step(state, data)
    assert np.isfinite(float(loss0)) and float(loss1) < float(loss0)


# ---- segment-id / padding masking (VERDICT r2 next #5) ----


from _oracles import dense_seg_attention as _dense_seg  # noqa: E402


def _packed_segments(b=2, s=64):
    seg = np.zeros((b, s), np.int32)
    seg[0, :16] = 1
    seg[0, 16:48] = 2
    seg[0, 48:] = 3
    seg[1, :40] = 1
    seg[1, 40:] = 2
    return jnp.asarray(seg)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_segments_packed(world, causal):
    # Packed-sequence masking: documents attend only within themselves.
    from fluxmpi_tpu.ops import flash_attention

    q, k, v = _qkv(seed=10)
    seg = _packed_segments()
    out = flash_attention(q, k, v, causal=causal, segment_ids=seg,
                          block_q=16, block_k=16)
    expected = _dense_seg(q, k, v, seg, seg, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), atol=2e-5
    )


def test_flash_segments_padding_rows_zero(world):
    # Pad tokens (segment id 0) attend nothing and output exactly zero;
    # valid rows are unaffected by the padding.
    from fluxmpi_tpu.ops import flash_attention

    q, k, v = _qkv(seed=11)
    seg = np.ones((2, 64), np.int32)
    seg[0, 48:] = 0
    seg[1, 56:] = 0
    seg = jnp.asarray(seg)
    out = flash_attention(q, k, v, segment_ids=seg, block_q=16, block_k=16)
    expected = _dense_seg(q, k, v, seg, seg)
    valid = np.asarray(seg) != 0
    np.testing.assert_allclose(
        np.asarray(out)[valid], np.asarray(expected)[valid], atol=2e-5
    )
    assert np.all(np.asarray(out)[~valid] == 0.0)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_segments_grad_matches_dense(world, causal):
    # Backward kernels under segment masking, padding included: grads match
    # autodiff through the dense oracle when the loss reads valid rows only
    # (the dense oracle's pad rows are garbage by construction).
    from fluxmpi_tpu.ops import flash_attention

    q, k, v = _qkv(seed=12)
    seg = _packed_segments()
    seg = seg.at[0, 56:].set(0)  # add a pad tail too
    row_w = (seg != 0).astype(jnp.float32)[:, :, None, None]

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, segment_ids=seg,
                              block_q=16, block_k=16)
        return jnp.sum(jnp.sin(out) * row_w)

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(_dense_seg(q, k, v, seg, seg, causal)) * row_w)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_fn_accepts_flax_padding_mask(world):
    # flash_attention_fn honors nn.make_attention_mask-style padding masks
    # (VERDICT r2 next #5: "accepting flax's padding mask instead of
    # raising").
    import flax.linen as nn

    from fluxmpi_tpu.ops import flash_attention_fn

    q, k, v = _qkv(seed=13)
    valid = np.ones((2, 64), bool)
    valid[0, 40:] = False
    valid[1, 60:] = False
    valid = jnp.asarray(valid)
    mask = nn.make_attention_mask(valid, valid)  # [b, 1, sq, sk]

    out = flash_attention_fn(block_q=16, block_k=16)(q, k, v, mask=mask)
    seg = valid.astype(jnp.int32)
    expected = _dense_seg(q, k, v, seg, seg)
    ok = np.asarray(valid)
    np.testing.assert_allclose(
        np.asarray(out)[ok], np.asarray(expected)[ok], atol=2e-5
    )


def test_flash_fn_combined_causal_padding_mask(world):
    # ADVICE r2 #1: causal=True with a combined causal∧padding mask must
    # honor the padding component, not silently drop it.
    import flax.linen as nn

    from fluxmpi_tpu.ops import flash_attention_fn

    q, k, v = _qkv(seed=14)
    valid = np.ones((2, 64), bool)
    valid[0, 32:] = False
    valid = jnp.asarray(valid)
    mask = nn.combine_masks(
        nn.make_causal_mask(jnp.zeros((2, 64))),
        nn.make_attention_mask(valid, valid),
    )

    out = flash_attention_fn(causal=True, block_q=16, block_k=16)(
        q, k, v, mask=mask
    )
    seg = valid.astype(jnp.int32)
    expected = _dense_seg(q, k, v, seg, seg, causal=True)
    ok = np.asarray(valid)
    np.testing.assert_allclose(
        np.asarray(out)[ok], np.asarray(expected)[ok], atol=2e-5
    )


def test_flash_fn_rejects_bias(world):
    from fluxmpi_tpu.ops import flash_attention_fn

    q, k, v = _qkv(seed=15)
    with pytest.raises(ValueError, match="bias"):
        flash_attention_fn()(q, k, v, bias=jnp.zeros((2, 2, 64, 64)))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_fn_packed_sequence_mask(world, causal):
    # Code-review r3: the flax packed-sequence idiom
    # nn.make_attention_mask(seg, seg, jnp.equal) (block-diagonal) must be
    # recovered EXACTLY — tokens must not attend across document
    # boundaries.
    import flax.linen as nn

    from fluxmpi_tpu.ops import flash_attention_fn

    q, k, v = _qkv(seed=16)
    seg = _packed_segments()  # contiguous docs, no padding
    mask = nn.make_attention_mask(seg, seg, jnp.equal)
    if causal:
        mask = nn.combine_masks(mask, nn.make_causal_mask(jnp.zeros((2, 64))))

    out = flash_attention_fn(causal=causal, block_q=16, block_k=16)(
        q, k, v, mask=mask
    )
    expected = _dense_seg(q, k, v, seg, seg, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), atol=2e-5
    )


def test_flash_fn_packed_plus_padding_mask(world):
    # Packing AND a trailing pad, combined with causal — the full flax
    # combine_masks stack.
    import flax.linen as nn

    from fluxmpi_tpu.ops import flash_attention_fn

    q, k, v = _qkv(seed=17)
    seg = np.zeros((2, 64), np.int32)
    seg[0, :24] = 1
    seg[0, 24:48] = 2  # then pad tail (0)
    seg[1, :64] = 1
    seg = jnp.asarray(seg)
    valid = seg != 0
    mask = nn.combine_masks(
        nn.make_attention_mask(seg, seg, jnp.equal),
        nn.make_attention_mask(valid, valid),
        nn.make_causal_mask(jnp.zeros((2, 64))),
    )
    out = flash_attention_fn(causal=True, block_q=16, block_k=16)(
        q, k, v, mask=mask
    )
    expected = _dense_seg(q, k, v, seg, seg, causal=True)
    ok = np.asarray(valid)
    np.testing.assert_allclose(
        np.asarray(out)[ok], np.asarray(expected)[ok], atol=2e-5
    )


def test_flash_fn_decode_prefix_mask_skips_garbage_tiles(world):
    # The serving decode shape (ISSUE 19): ONE query position against a
    # gathered paged cache, masked by flax's cache-index prefix mask
    # ([b, 1, 1, sk]). The masked tail holds garbage (the paged pool's
    # trash-block rows), planted to discriminate the two masking
    # mechanisms: LARGE-FINITE garbage in the partially-masked tile
    # (where-masked: p -> 0, and 0 x finite = 0 contributes nothing)
    # and NaN in the fully-masked tiles — if those tiles were computed
    # at all, 0 x NaN = NaN would poison the contraction, so a finite
    # output PROVES the @pl.when tile skip, not just the where mask.
    from fluxmpi_tpu.ops import flash_attention_fn

    block_k = 16
    b, sk, h, d = 2, 64, 2, 8
    rng = np.random.default_rng(21)
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)).astype(np.float32))
    k = rng.normal(size=(b, sk, h, d)).astype(np.float32)
    v = rng.normal(size=(b, sk, h, d)).astype(np.float32)
    lengths = (9, 40)
    for i, n in enumerate(lengths):
        tile_end = -(-n // block_k) * block_k  # end of the partial tile
        k[i, n:tile_end] = 1e6
        v[i, n:tile_end] = 1e6
        k[i, tile_end:] = np.nan
        v[i, tile_end:] = np.nan
    k, v = jnp.asarray(k), jnp.asarray(v)
    mask = (
        jnp.arange(sk)[None, None, None, :]
        < jnp.asarray(lengths)[:, None, None, None]
    )

    # mask_check=False mirrors the decode path (models/transformer.py):
    # the prefix mask is representable by construction there.
    out = flash_attention_fn(mask_check=False, block_k=block_k)(
        q, k, v, mask=mask
    )
    assert np.isfinite(np.asarray(out)).all(), "fully-masked tile was computed"
    scale = 1.0 / np.sqrt(d)
    for i, n in enumerate(lengths):
        s = jnp.einsum("qhd,khd->hqk", q[i], k[i, :n]) * scale
        w = jax.nn.softmax(s, axis=-1)
        ref = jnp.einsum("hqk,khd->qhd", w, v[i, :n])
        np.testing.assert_allclose(
            np.asarray(out[i]), np.asarray(ref), atol=2e-5
        )


def test_flash_fn_rejects_unrepresentable_concrete_mask(world):
    # VERDICT r3 next #10: an unrepresentable CONCRETE mask (e.g. a causal
    # mask passed with causal=False) must be a Python ValueError at call
    # time — not a mid-training NaN.
    import pytest
    import flax.linen as nn

    from fluxmpi_tpu.ops import flash_attention_fn

    q, k, v = _qkv(seed=18)
    causal_mask = nn.make_causal_mask(jnp.zeros((2, 64)))
    with pytest.raises(ValueError, match="not representable"):
        flash_attention_fn(block_q=16, block_k=16)(q, k, v, mask=causal_mask)

    # …and a representable mask on the same path works.
    valid = jnp.asarray(np.ones((2, 64), bool))
    pad_mask = nn.make_attention_mask(valid, valid)
    out = flash_attention_fn(block_q=16, block_k=16)(q, k, v, mask=pad_mask)
    assert not np.any(np.isnan(np.asarray(out, dtype=np.float32)))


def test_flash_fn_poisons_unrepresentable_traced_mask(world):
    # Genuinely dynamic (traced) masks can only be checked on-device: the
    # NaN-poison remains the last resort there — loud failure, never
    # silently-wrong attention.
    import flax.linen as nn

    from fluxmpi_tpu.ops import flash_attention_fn

    q, k, v = _qkv(seed=18)
    causal_mask = nn.make_causal_mask(jnp.zeros((2, 64)))

    @jax.jit
    def run(q, k, v, mask):
        return flash_attention_fn(block_q=16, block_k=16)(q, k, v, mask=mask)

    out = run(q, k, v, causal_mask)
    assert np.all(np.isnan(np.asarray(out, dtype=np.float32)))

    # mask_check=False skips the runtime check (validated-pipeline mode):
    # same call, no poison — the mask degrades to its segment projection.
    @jax.jit
    def run_unchecked(q, k, v, mask):
        return flash_attention_fn(block_q=16, block_k=16, mask_check=False)(
            q, k, v, mask=mask
        )

    out = run_unchecked(q, k, v, causal_mask)
    assert not np.any(np.isnan(np.asarray(out, dtype=np.float32)))


def test_flash_fn_head_varying_mask_rejected(world):
    # Per-head masks are unrepresentable by per-batch segment ids; the
    # any-over-heads reduction used to let them through silently.
    import pytest

    from fluxmpi_tpu.ops import flash_attention_fn

    q, k, v = _qkv(seed=19)
    m = np.ones((2, 4, 64, 64), bool)
    m[:, 0] = False  # head 0 attends nothing; other heads attend all
    with pytest.raises(ValueError, match="not representable"):
        flash_attention_fn(block_q=16, block_k=16)(q, k, v, mask=jnp.asarray(m))


def test_flash_fn_dropout_dense_fallback(world):
    # VERDICT r3 next #9: dropout_rate > 0 in training mode transparently
    # takes the dense fallback with flax-exact semantics — no user-visible
    # branching, and it matches flax's own dot_product_attention under the
    # same rng.
    import flax.linen as nn

    from fluxmpi_tpu.ops import flash_attention_fn

    q, k, v = _qkv(seed=20)
    rng = jax.random.PRNGKey(7)
    out = flash_attention_fn(causal=True)(
        q, k, v,
        dropout_rng=rng, dropout_rate=0.3, deterministic=False,
        broadcast_dropout=True,
    )
    mask = nn.make_causal_mask(jnp.zeros((2, 64)))
    expected = nn.dot_product_attention(
        q, k, v, mask=mask,
        dropout_rng=rng, dropout_rate=0.3, deterministic=False,
        broadcast_dropout=True,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), atol=2e-5
    )
    # deterministic=True ignores dropout and stays on the flash path.
    out_det = flash_attention_fn(causal=True)(
        q, k, v, dropout_rate=0.3, deterministic=True
    )
    no_drop = flash_attention_fn(causal=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out_det), np.asarray(no_drop))


def test_flash_fn_dropout_module_trains(world):
    # A flax attention module with dropout trains through the adapter end
    # to end (grads finite), with no user-visible branching.
    import flax.linen as nn
    import optax

    from fluxmpi_tpu.ops import flash_attention_fn

    attn = nn.MultiHeadDotProductAttention(
        num_heads=4, qkv_features=32, dropout_rate=0.2,
        attention_fn=flash_attention_fn(causal=True),
    )
    x = jnp.asarray(
        np.random.default_rng(21).normal(size=(2, 16, 32)).astype(np.float32)
    )
    params = attn.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        x, x, deterministic=False,
    )

    def loss_fn(p, rng):
        y = attn.apply(
            p, x, x, deterministic=False, rngs={"dropout": rng}
        )
        return jnp.mean(y**2)

    g = jax.jit(jax.grad(loss_fn))(params, jax.random.PRNGKey(2))
    assert all(
        np.all(np.isfinite(np.asarray(leaf)))
        for leaf in jax.tree_util.tree_leaves(g)
    )
    # and an optimizer step applies cleanly
    opt = optax.adam(1e-3)
    state = opt.init(params)
    updates, _ = opt.update(g, state, params)
    optax.apply_updates(params, updates)


def _dense_window(q, k, v, window):
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    sq, sk = q.shape[1], k.shape[1]
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = (qpos >= kpos) & (qpos - kpos < window)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("window", [16, 40])
def test_flash_sliding_window(world, window):
    # Mistral-style local attention: position i attends (i-window, i].
    from fluxmpi_tpu.ops import flash_attention

    q, k, v = _qkv(s=128, seed=30)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=16, block_k=16)
    expected = _dense_window(q, k, v, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5)


def test_flash_sliding_window_grads(world):
    from fluxmpi_tpu.ops import flash_attention

    q, k, v = _qkv(s=64, seed=31)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=True, window=24, block_q=16, block_k=16)))

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(_dense_window(q, k, v, 24)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_window_requires_causal(world):
    from fluxmpi_tpu.ops import flash_attention

    q, k, v = _qkv(seed=32)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=16)


def test_flash_window_composes_with_segments(world):
    from fluxmpi_tpu.ops import flash_attention

    q, k, v = _qkv(s=64, seed=33)
    seg = np.ones((2, 64), np.int32)
    seg[0, 48:] = 0  # pad tail
    seg = jnp.asarray(seg)
    out = flash_attention(q, k, v, causal=True, window=24, segment_ids=seg,
                          block_q=16, block_k=16)
    # dense oracle: window ∧ causal ∧ segments
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    qpos = jnp.arange(64)[:, None]
    kpos = jnp.arange(64)[None, :]
    mask = (qpos >= kpos) & (qpos - kpos < 24)
    mask = mask[None] & (seg[:, :, None] == seg[:, None, :]) & (
        seg[:, None, :] != 0
    )
    s = jnp.where(mask[:, None], s, -1e30)
    expected = jnp.einsum(
        "bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v
    )
    ok = np.asarray(seg) != 0
    np.testing.assert_allclose(
        np.asarray(out)[ok], np.asarray(expected)[ok], atol=2e-5
    )


def test_flash_cross_attention(world):
    # sq != sk (encoder-decoder cross attention): separate q/kv lengths and
    # a (q_seg, kv_seg) pair.
    from fluxmpi_tpu.ops import flash_attention

    rng = np.random.default_rng(40)
    q = jnp.asarray(rng.normal(size=(2, 32, 2, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 64, 2, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 64, 2, 32)).astype(np.float32))
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_dense(q, k, v)), atol=2e-5
    )

    # With validity segments on both sides.
    q_valid = np.ones((2, 32), bool); q_valid[0, 24:] = False
    kv_valid = np.ones((2, 64), bool); kv_valid[1, 48:] = False
    qseg = jnp.asarray(q_valid.astype(np.int32))
    kseg = jnp.asarray(kv_valid.astype(np.int32))
    out = flash_attention(q, k, v, segment_ids=(qseg, kseg),
                          block_q=16, block_k=16)
    expected = _dense_seg(q, k, v, qseg, kseg)
    ok = q_valid
    np.testing.assert_allclose(
        np.asarray(out)[ok], np.asarray(expected)[ok], atol=2e-5
    )


def test_flash_cross_attention_grads(world):
    from fluxmpi_tpu.ops import flash_attention

    rng = np.random.default_rng(41)
    q = jnp.asarray(rng.normal(size=(2, 32, 2, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 64, 2, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 64, 2, 32)).astype(np.float32))

    gf = jax.grad(
        lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
            q, k, v, block_q=16, block_k=16))),
        argnums=(0, 1, 2),
    )(q, k, v)
    gd = jax.grad(
        lambda q, k, v: jnp.sum(jnp.sin(_dense(q, k, v))), argnums=(0, 1, 2)
    )(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# ---- grouped-query attention (GQA/MQA) ----


def _repeat_kv(t, group):
    b, s, h_kv, d = t.shape
    return jnp.repeat(t, group, axis=2)


@pytest.mark.parametrize("h_kv", [1, 2])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_matches_dense(world, causal, h_kv):
    # k/v with fewer heads: each query head attends its group's kv head —
    # identical to dense attention over group-repeated k/v.
    from fluxmpi_tpu.ops import flash_attention

    rng = np.random.default_rng(50)
    h = 4
    q = jnp.asarray(rng.normal(size=(2, 64, h, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 64, h_kv, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 64, h_kv, 32)).astype(np.float32))
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    group = h // h_kv
    expected = _dense(q, _repeat_kv(k, group), _repeat_kv(v, group),
                      causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), atol=2e-5
    )


def test_flash_gqa_grads_match_dense(world):
    from fluxmpi_tpu.ops import flash_attention

    rng = np.random.default_rng(51)
    h, h_kv = 4, 2
    group = h // h_kv
    q = jnp.asarray(rng.normal(size=(2, 32, h, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 32, h_kv, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 32, h_kv, 32)).astype(np.float32))

    gf = jax.grad(
        lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16))),
        argnums=(0, 1, 2),
    )(q, k, v)

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(_dense(
            q, _repeat_kv(k, group), _repeat_kv(v, group), causal=True)))

    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_gqa_rejects_indivisible_heads(world):
    from fluxmpi_tpu.ops import flash_attention

    rng = np.random.default_rng(52)
    q = jnp.asarray(rng.normal(size=(2, 32, 4, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 32, 3, 32)).astype(np.float32))
    with pytest.raises(ValueError, match="multiple of the kv head"):
        flash_attention(q, k, k)


def test_flash_gqa_with_segments(world):
    # GQA × segment masking: the kv-head-major dkv grid decodes batch as
    # g0 // h_kv while q operands use the folded q-row map — this pins the
    # two decodings together (fwd + bwd).
    from fluxmpi_tpu.ops import flash_attention

    rng = np.random.default_rng(53)
    h, h_kv = 4, 2
    group = h // h_kv
    q = jnp.asarray(rng.normal(size=(2, 64, h, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 64, h_kv, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 64, h_kv, 32)).astype(np.float32))
    seg = _packed_segments()
    seg = seg.at[1, 56:].set(0)  # pad tail on row 1
    row_w = (seg != 0).astype(jnp.float32)[:, :, None, None]

    out = flash_attention(q, k, v, segment_ids=seg, block_q=16, block_k=16)
    expected = _dense_seg(q, _repeat_kv(k, group), _repeat_kv(v, group),
                          seg, seg)
    ok = np.asarray(seg) != 0
    np.testing.assert_allclose(
        np.asarray(out)[ok], np.asarray(expected)[ok], atol=2e-5
    )

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, segment_ids=seg, block_q=16, block_k=16)
        return jnp.sum(jnp.sin(o) * row_w)

    def loss_dense(q, k, v):
        o = _dense_seg(q, _repeat_kv(k, group), _repeat_kv(v, group), seg, seg)
        return jnp.sum(jnp.sin(o) * row_w)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("seed", range(6))
def test_flash_property_sweep(world, seed):
    # Randomized config sweep: one dense-oracle comparison per seed across
    # the kernel's whole feature cross-product (GQA ratio x causal x
    # window x segments x block sizes x dtype) — breadth the individual
    # feature tests don't cover pairwise.
    from fluxmpi_tpu.ops import flash_attention

    rng = np.random.default_rng(100 + seed)
    b = int(rng.integers(1, 3))
    sq = int(rng.choice([16, 32, 48]))
    h_kv = int(rng.choice([1, 2]))
    h = h_kv * int(rng.choice([1, 2, 4]))
    d = int(rng.choice([8, 16]))
    causal = bool(rng.integers(0, 2))
    window = int(rng.choice([4, 8])) if causal and rng.integers(0, 2) else None
    use_seg = bool(rng.integers(0, 2))
    block = int(rng.choice([8, 16]))
    dtype = jnp.bfloat16 if rng.integers(0, 2) else jnp.float32
    atol = 0.06 if dtype == jnp.bfloat16 else 3e-5
    drop = float(rng.choice([0.0, 0.3]))

    q = jnp.asarray(rng.normal(size=(b, sq, h, d)).astype(np.float32)).astype(dtype)
    k = jnp.asarray(rng.normal(size=(b, sq, h_kv, d)).astype(np.float32)).astype(dtype)
    v = jnp.asarray(rng.normal(size=(b, sq, h_kv, d)).astype(np.float32)).astype(dtype)

    seg = None
    valid = np.ones((b, sq), bool)
    if use_seg:
        seg_np = np.ones((b, sq), np.int32)
        for row in range(b):
            cut = int(rng.integers(1, sq))
            seg_np[row, cut:] = 2
            if rng.integers(0, 2):
                pad = int(rng.integers(1, sq // 4 + 1))
                seg_np[row, -pad:] = 0
        seg = jnp.asarray(seg_np)
        valid = seg_np != 0

    out = flash_attention(
        q, k, v, causal=causal, window=window, segment_ids=seg,
        block_q=block, block_k=block,
        dropout_rate=drop, dropout_seed=seed if drop else None,
    )

    # Dense oracle with identical semantics (f32 math; bf16 inputs upcast).
    kf = jnp.repeat(k, h // h_kv, axis=2).astype(jnp.float32)
    vf = jnp.repeat(v, h // h_kv, axis=2).astype(jnp.float32)
    q = q.astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kf) / np.sqrt(d)
    mask = np.ones((b, 1, sq, sq), bool)
    if causal:
        pos = np.arange(sq)[:, None] >= np.arange(sq)[None, :]
        if window is not None:
            pos = pos & (np.arange(sq)[:, None] - np.arange(sq)[None, :] < window)
        mask = mask & pos[None, None]
    if seg is not None:
        sm = (np.asarray(seg)[:, :, None] == np.asarray(seg)[:, None, :]) & (
            np.asarray(seg)[:, None, :] != 0
        )
        mask = mask & sm[:, None]
    s = jnp.where(jnp.asarray(mask), s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if drop:
        from fluxmpi_tpu.ops.flash_attention import _dropout_keep

        p = jnp.where(jnp.asarray(mask), p, 0.0)
        q_pos = jnp.broadcast_to(jnp.arange(sq)[:, None], (sq, sq))
        k_pos = jnp.broadcast_to(jnp.arange(sq)[None, :], (sq, sq))
        keep = jax.vmap(
            lambda bh: _dropout_keep(
                jnp.uint32(seed), bh, q_pos, k_pos, 1.0 - drop
            )
        )(jnp.arange(b * h, dtype=jnp.uint32)).reshape(b, h, sq, sq)
        p = jnp.where(keep, p / (1.0 - drop), 0.0)
    expected = jnp.einsum("bhqk,bkhd->bqhd", p, vf)

    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32)[valid],
        np.asarray(expected)[valid], atol=atol,
        err_msg=f"config: b={b} sq={sq} h={h} h_kv={h_kv} causal={causal} "
                f"window={window} seg={use_seg} block={block} dtype={dtype} "
                f"drop={drop}",
    )


# ---- in-kernel dropout (counter-based position hash) ----


def _kernel_dropout_oracle(q, k, v, seed, rate, causal=False):
    """Dense attention applying the EXACT mask the kernels generate: the
    same murmur-hash keep decision per (bh, q_pos, k_pos), post-softmax,
    1/keep_prob scaled."""
    from fluxmpi_tpu.ops.flash_attention import _dropout_keep

    b, s, h, d = q.shape
    kp = 1.0 - rate
    scale = 1.0 / np.sqrt(d)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        pos = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.where(pos[None, None], sc, -1e30)
    w = jax.nn.softmax(sc, axis=-1)
    q_pos = jnp.broadcast_to(jnp.arange(s)[:, None], (s, s))
    k_pos = jnp.broadcast_to(jnp.arange(s)[None, :], (s, s))
    keep = jax.vmap(
        lambda bh: _dropout_keep(jnp.uint32(seed), bh, q_pos, k_pos, kp)
    )(jnp.arange(b * h, dtype=jnp.uint32))
    keep = keep.reshape(b, h, s, s)
    w = jnp.where(keep, w / kp, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_dropout_matches_oracle(world, causal):
    # The in-kernel dropout is a deterministic function of (seed, head,
    # positions) — rebuild the identical mask at the JAX level and the
    # outputs must agree to float tolerance.
    from fluxmpi_tpu.ops import flash_attention

    q, k, v = _qkv(s=32, seed=60)
    out = flash_attention(
        q, k, v, causal=causal, dropout_rate=0.3, dropout_seed=1234,
        block_q=16, block_k=16,
    )
    expected = _kernel_dropout_oracle(q, k, v, 1234, 0.3, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), atol=2e-5
    )


def test_flash_kernel_dropout_grads_match_oracle(world):
    # All three kernels regenerate the same mask: grads through the flash
    # path equal autodiff through the dense oracle holding the mask fixed.
    from fluxmpi_tpu.ops import flash_attention

    q, k, v = _qkv(s=32, seed=61)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=True, dropout_rate=0.25, dropout_seed=7,
            block_q=16, block_k=16,
        )))

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(
            _kernel_dropout_oracle(q, k, v, 7, 0.25, causal=True)
        ))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_kernel_dropout_statistics(world):
    # Keep fraction ≈ keep_prob; mean output ≈ undropped output (unbiased);
    # different seeds give different masks, same seed reproduces.
    from fluxmpi_tpu.ops import flash_attention

    q, k, v = _qkv(s=64, seed=62)
    kwargs = dict(block_q=16, block_k=16, dropout_rate=0.5)
    a1 = np.asarray(flash_attention(q, k, v, dropout_seed=1, **kwargs))
    a1b = np.asarray(flash_attention(q, k, v, dropout_seed=1, **kwargs))
    a2 = np.asarray(flash_attention(q, k, v, dropout_seed=2, **kwargs))
    np.testing.assert_array_equal(a1, a1b)  # deterministic per seed
    assert np.abs(a1 - a2).max() > 1e-3  # seed changes the mask

    # Unbiasedness: averaging over many seeds approaches the clean output.
    clean = np.asarray(flash_attention(q, k, v, block_q=16, block_k=16))
    acc = np.zeros_like(clean)
    n = 24
    for s in range(n):
        acc += np.asarray(flash_attention(q, k, v, dropout_seed=100 + s,
                                          **kwargs))
    np.testing.assert_allclose(acc / n, clean, atol=0.25)


def test_flash_kernel_dropout_gqa_and_segments(world):
    # Dropout composes with GQA (dkv kernel rebuilds the query-head index
    # from its kv-head-major grid) and segment masking.
    from fluxmpi_tpu.ops import flash_attention

    rng = np.random.default_rng(63)
    b, s, h, h_kv, d = 2, 32, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, h_kv, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, h_kv, d)).astype(np.float32))
    seg = np.ones((b, s), np.int32)
    seg[0, 20:] = 2
    seg[1, 24:] = 0
    seg = jnp.asarray(seg)

    def loss(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=True, segment_ids=seg,
            dropout_rate=0.2, dropout_seed=9, block_q=16, block_k=16,
        )))

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert all(np.all(np.isfinite(np.asarray(t))) for t in g)

    # Oracle: repeated-KV dense with segment mask + the kernel's hash mask
    # (keyed by the QUERY head index — exactly what the dkv kernel must
    # reconstruct from its kv-head-major grid).
    from fluxmpi_tpu.ops.flash_attention import _dropout_keep

    kf = jnp.repeat(k, 2, axis=2)
    vf = jnp.repeat(v, 2, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, kf) / np.sqrt(d)
    segm = (np.asarray(seg)[:, :, None] == np.asarray(seg)[:, None, :]) & (
        np.asarray(seg)[:, None, :] != 0
    )
    pos = np.arange(s)[:, None] >= np.arange(s)[None, :]
    mask = jnp.asarray(segm[:, None] & pos[None, None])
    sc = jnp.where(mask, sc, -1e30)
    w = jax.nn.softmax(sc, axis=-1)
    w = jnp.where(mask, w, 0.0)  # fully-masked rows: uniform → zero
    q_pos = jnp.broadcast_to(jnp.arange(s)[:, None], (s, s))
    k_pos = jnp.broadcast_to(jnp.arange(s)[None, :], (s, s))
    keep = jax.vmap(
        lambda bh: _dropout_keep(jnp.uint32(9), bh, q_pos, k_pos, 0.8)
    )(jnp.arange(b * h, dtype=jnp.uint32)).reshape(b, h, s, s)
    w = jnp.where(keep, w / 0.8, 0.0)
    expected = jnp.einsum("bhqk,bkhd->bqhd", w, vf)
    out = flash_attention(
        q, k, v, causal=True, segment_ids=seg,
        dropout_rate=0.2, dropout_seed=9, block_q=16, block_k=16,
    )
    valid = np.asarray(seg) != 0
    np.testing.assert_allclose(
        np.asarray(out)[valid], np.asarray(expected)[valid], atol=2e-5
    )

    # Backward oracle too: autodiff through the same dense hash-masked
    # formulation — a wrong bh_q reconstruction in the dkv kernel would
    # pass the forward check and finite-grad check but fail here.
    def oracle_loss(q, k, v):
        kf = jnp.repeat(k, 2, axis=2)
        vf = jnp.repeat(v, 2, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, kf) / np.sqrt(d)
        sc = jnp.where(mask, sc, -1e30)
        w = jax.nn.softmax(sc, axis=-1)
        w = jnp.where(mask, w, 0.0)
        w = jnp.where(keep, w / 0.8, 0.0)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, vf)
        vmask = jnp.asarray(valid)[:, :, None, None]
        return jnp.sum(jnp.where(vmask, jnp.sin(o), 0.0))

    def flash_loss(q, k, v):
        o = flash_attention(
            q, k, v, causal=True, segment_ids=seg,
            dropout_rate=0.2, dropout_seed=9, block_q=16, block_k=16,
        )
        vmask = jnp.asarray(valid)[:, :, None, None]
        return jnp.sum(jnp.where(vmask, jnp.sin(o), 0.0))

    gf = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(oracle_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_fn_kernel_dropout_path(world):
    # dropout_impl="kernel" on the adapter: stays on the flash path, trains
    # through a flax module, deterministic under a fixed rng.
    import flax.linen as nn

    from fluxmpi_tpu.ops import flash_attention_fn

    attn = nn.MultiHeadDotProductAttention(
        num_heads=4, qkv_features=32, dropout_rate=0.2,
        attention_fn=flash_attention_fn(causal=True, dropout_impl="kernel"),
    )
    x = jnp.asarray(
        np.random.default_rng(64).normal(size=(2, 16, 32)).astype(np.float32)
    )
    params = attn.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        x, x, deterministic=False,
    )

    def loss_fn(p, rng):
        y = attn.apply(p, x, x, deterministic=False, rngs={"dropout": rng})
        return jnp.mean(y**2)

    g1 = jax.jit(jax.grad(loss_fn))(params, jax.random.PRNGKey(2))
    g2 = jax.jit(jax.grad(loss_fn))(params, jax.random.PRNGKey(2))
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert all(np.all(np.isfinite(np.asarray(t)))
               for t in jax.tree_util.tree_leaves(g1))

    with pytest.raises(ValueError, match="dropout_impl"):
        flash_attention_fn(dropout_impl="bogus")


# ---- chunked fused unembed + cross-entropy (round-5 perf surface) ----


def _ce_oracle(h, W, targets):
    logits = (h.astype(jnp.float32) @ W.astype(jnp.float32).T)
    import optax

    return optax.softmax_cross_entropy_with_integer_labels(logits, targets)


@pytest.mark.parametrize("chunk", [7, 16, 64, 100])
def test_unembed_ce_matches_dense(world, chunk):
    # chunk=7 and 100: the trailing vocab tile is zero-padded and masked
    # (64 % 7 != 0; 100 > 64 clamps to one full tile) — the tile size is
    # never silently shrunk.
    from fluxmpi_tpu.ops import unembed_cross_entropy

    rng = np.random.default_rng(0)
    b, s, d, v = 2, 8, 16, 64
    h = jnp.asarray(rng.normal(size=(b, s, d)).astype(np.float32))
    W = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32) * 0.3)
    t = jnp.asarray(rng.integers(0, v, size=(b, s)).astype(np.int32))
    out = unembed_cross_entropy(h, W, t, chunk=chunk)
    expected = _ce_oracle(h.reshape(-1, d), W, t.reshape(-1)).reshape(b, s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=1e-5)


def test_unembed_ce_grads_match_dense(world):
    from fluxmpi_tpu.ops import unembed_cross_entropy

    rng = np.random.default_rng(1)
    n, d, v = 24, 16, 48
    h = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    W = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32) * 0.3)
    t = jnp.asarray(rng.integers(0, v, size=(n,)).astype(np.int32))
    # Non-uniform per-token cotangents through a weighted mean.
    wgt = jnp.asarray(rng.uniform(0.5, 1.5, size=(n,)).astype(np.float32))

    def loss_fused(h, W):
        return jnp.sum(unembed_cross_entropy(h, W, t, chunk=16) * wgt)

    def loss_dense(h, W):
        return jnp.sum(_ce_oracle(h, W, t) * wgt)

    gf = jax.grad(loss_fused, argnums=(0, 1))(h, W)
    gd = jax.grad(loss_dense, argnums=(0, 1))(h, W)
    np.testing.assert_allclose(np.asarray(gf[0]), np.asarray(gd[0]),
                               atol=5e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gf[1]), np.asarray(gd[1]),
                               atol=5e-5, rtol=1e-4)


def test_unembed_ce_bf16_operands(world):
    # bf16 h/W with f32 accumulation: close to the f32 oracle at bf16
    # tolerance, and gradients come back in the operand dtypes.
    from fluxmpi_tpu.ops import unembed_cross_entropy

    rng = np.random.default_rng(2)
    n, d, v = 16, 32, 64
    h32 = rng.normal(size=(n, d)).astype(np.float32)
    W32 = (rng.normal(size=(v, d)) * 0.3).astype(np.float32)
    t = jnp.asarray(rng.integers(0, v, size=(n,)).astype(np.int32))
    h = jnp.asarray(h32, jnp.bfloat16)
    W = jnp.asarray(W32, jnp.bfloat16)
    out = unembed_cross_entropy(h, W, t, chunk=16)
    assert out.dtype == jnp.float32
    expected = _ce_oracle(
        jnp.asarray(h32, jnp.bfloat16), jnp.asarray(W32, jnp.bfloat16), t
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=5e-2, rtol=5e-2)
    gh, gW = jax.grad(
        lambda h, W: jnp.mean(unembed_cross_entropy(h, W, t, chunk=16)),
        argnums=(0, 1),
    )(h, W)
    assert gh.dtype == jnp.bfloat16 and gW.dtype == jnp.bfloat16

    # Mixed: bf16 hidden states against an f32 table (the weight-tied
    # model layout) — the table's gradient comes back f32, un-quantized.
    gh, gW = jax.grad(
        lambda h, W: jnp.mean(unembed_cross_entropy(h, W, t, chunk=16)),
        argnums=(0, 1),
    )(h, jnp.asarray(W32))
    assert gh.dtype == jnp.bfloat16 and gW.dtype == jnp.float32


def test_unembed_ce_shape_errors(world):
    from fluxmpi_tpu.ops import unembed_cross_entropy

    h = jnp.ones((2, 4, 8))
    W = jnp.ones((16, 8))
    with pytest.raises(ValueError, match="targets shape"):
        unembed_cross_entropy(h, W, jnp.zeros((2, 3), jnp.int32))
    with pytest.raises(ValueError, match="hidden dim"):
        unembed_cross_entropy(h, jnp.ones((16, 9)), jnp.zeros((2, 4), jnp.int32))


def test_tp_unembed_ce_matches_dense(world):
    # Megatron-style vocab-sharded CE over a tp axis: exact global loss
    # and gradients from shard-local tables + three tiny collectives.
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from fluxmpi_tpu.ops import tp_unembed_cross_entropy

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("tp",))
    rng = np.random.default_rng(3)
    b, s, d, v = 2, 8, 16, 64
    h = jnp.asarray(rng.normal(size=(b, s, d)).astype(np.float32))
    W = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32) * 0.3)
    t = jnp.asarray(rng.integers(0, v, size=(b, s)).astype(np.int32))
    W_sharded = jax.device_put(W, NamedSharding(mesh, P("tp", None)))

    out = jax.jit(
        lambda h, W, t: tp_unembed_cross_entropy(
            h, W, t, mesh=mesh, axis_name="tp", chunk=4
        )
    )(h, W_sharded, t)
    expected = _ce_oracle(h.reshape(-1, d), W, t.reshape(-1)).reshape(b, s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=1e-5)

    # Gradients: dh and the vocab-sharded dW both match the dense oracle.
    def loss_tp(h, W):
        return jnp.mean(tp_unembed_cross_entropy(
            h, W, t, mesh=mesh, axis_name="tp", chunk=4))

    def loss_dense(h, W):
        return jnp.mean(_ce_oracle(h.reshape(-1, d), W, t.reshape(-1)))

    gf = jax.jit(jax.grad(loss_tp, argnums=(0, 1)))(h, W_sharded)
    gd = jax.grad(loss_dense, argnums=(0, 1))(h, W)
    np.testing.assert_allclose(np.asarray(gf[0]), np.asarray(gd[0]),
                               atol=5e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gf[1]), np.asarray(gd[1]),
                               atol=5e-5, rtol=1e-4)


def test_tp_unembed_ce_validation(world):
    from jax.sharding import Mesh

    from fluxmpi_tpu.ops import tp_unembed_cross_entropy

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("tp",))
    h = jnp.ones((2, 4, 8))
    with pytest.raises(ValueError, match="divide evenly"):
        tp_unembed_cross_entropy(
            h, jnp.ones((60, 8)), jnp.zeros((2, 4), jnp.int32),
            mesh=mesh, axis_name="tp",
        )
    with pytest.raises(ValueError, match="no axis"):
        tp_unembed_cross_entropy(
            h, jnp.ones((64, 8)), jnp.zeros((2, 4), jnp.int32),
            mesh=mesh, axis_name="model",
        )


def test_tp_unembed_ce_with_batch_sharding(world):
    # dp×tp mesh, token dim sharded over dp: every device works on its
    # own token slice; the table gradient psums over dp. Exact vs dense.
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from fluxmpi_tpu.ops import tp_unembed_cross_entropy

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2), ("dp", "tp"))
    rng = np.random.default_rng(4)
    n, d, v = 16, 8, 32
    h = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    W = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32) * 0.3)
    t = jnp.asarray(rng.integers(0, v, size=(n,)).astype(np.int32))
    hs = jax.device_put(h, NamedSharding(mesh, P("dp", None)))
    Ws = jax.device_put(W, NamedSharding(mesh, P("tp", None)))

    def loss_tp(h, W):
        return jnp.mean(tp_unembed_cross_entropy(
            h, W, t, mesh=mesh, axis_name="tp", batch_axis_name="dp",
            chunk=8))

    def loss_dense(h, W):
        return jnp.mean(_ce_oracle(h, W, t))

    lf = jax.jit(loss_tp)(hs, Ws)
    np.testing.assert_allclose(float(lf), float(loss_dense(h, W)), rtol=1e-5)
    gf = jax.jit(jax.grad(loss_tp, argnums=(0, 1)))(hs, Ws)
    gd = jax.grad(loss_dense, argnums=(0, 1))(h, W)
    np.testing.assert_allclose(np.asarray(gf[0]), np.asarray(gd[0]),
                               atol=5e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gf[1]), np.asarray(gd[1]),
                               atol=5e-5, rtol=1e-4)

    with pytest.raises(ValueError, match="cannot include the tp axis"):
        tp_unembed_cross_entropy(
            h, W, t, mesh=mesh, axis_name="tp", batch_axis_name="tp")
    with pytest.raises(ValueError, match="chunk"):
        tp_unembed_cross_entropy(
            h, W, t, mesh=mesh, axis_name="tp", chunk=0)


def test_unembed_ce_composes_with_sequence_sharding(world):
    # SP composition: hidden states sharded over the sequence axis, the
    # fused CE computed per shard inside shard_map (table replicated) —
    # per-token losses equal the dense full-sequence oracle.
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from fluxmpi_tpu.ops import unembed_cross_entropy

    from fluxmpi_tpu.parallel._compat import shard_map_unchecked

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("sp",))
    rng = np.random.default_rng(5)
    b, s, d, v = 2, 32, 8, 32
    h = jnp.asarray(rng.normal(size=(b, s, d)).astype(np.float32))
    W = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32) * 0.3)
    t = jnp.asarray(rng.integers(0, v, size=(b, s)).astype(np.int32))
    hs = jax.device_put(h, NamedSharding(mesh, P(None, "sp", None)))
    ts = jax.device_put(t, NamedSharding(mesh, P(None, "sp")))

    mapped = shard_map_unchecked(
        lambda h, W, t: unembed_cross_entropy(h, W, t, chunk=8),
        mesh=mesh,
        in_specs=(P(None, "sp", None), P(), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    out = jax.jit(mapped)(hs, W, ts)
    expected = _ce_oracle(h.reshape(-1, d), W, t.reshape(-1)).reshape(b, s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=1e-5)


def test_unembed_ce_label_smoothing_matches_dense(world):
    # Smoothed target distribution (1-eps)*onehot + eps/V: values AND
    # both gradients vs optax's soft-label CE, including a padded tile.
    import optax

    from fluxmpi_tpu.ops import unembed_cross_entropy

    rng = np.random.default_rng(6)
    n, d, v, eps = 12, 8, 20, 0.1
    h = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    W = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32) * 0.3)
    t = jnp.asarray(rng.integers(0, v, size=(n,)).astype(np.int32))

    def dense(h, W):
        logits = h @ W.T
        soft = (1 - eps) * jax.nn.one_hot(t, v) + eps / v
        return optax.softmax_cross_entropy(logits, soft)

    def fused(h, W):
        return unembed_cross_entropy(h, W, t, chunk=8, label_smoothing=eps)

    np.testing.assert_allclose(np.asarray(fused(h, W)),
                               np.asarray(dense(h, W)),
                               atol=2e-5, rtol=1e-5)
    gf = jax.grad(lambda h, W: jnp.mean(fused(h, W)), argnums=(0, 1))(h, W)
    gd = jax.grad(lambda h, W: jnp.mean(dense(h, W)), argnums=(0, 1))(h, W)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-4)

    with pytest.raises(ValueError, match="label_smoothing"):
        unembed_cross_entropy(h, W, t, label_smoothing=1.0)


def test_tp_unembed_ce_label_smoothing_matches_dense(world):
    import optax
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from fluxmpi_tpu.ops import tp_unembed_cross_entropy

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("tp",))
    rng = np.random.default_rng(7)
    n, d, v, eps = 8, 8, 32, 0.2
    h = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    W = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32) * 0.3)
    t = jnp.asarray(rng.integers(0, v, size=(n,)).astype(np.int32))
    Ws = jax.device_put(W, NamedSharding(mesh, P("tp", None)))

    def dense(h, W):
        soft = (1 - eps) * jax.nn.one_hot(t, v) + eps / v
        return optax.softmax_cross_entropy(h @ W.T, soft)

    def fused(h, W):
        return tp_unembed_cross_entropy(
            h, W, t, mesh=mesh, axis_name="tp", chunk=4,
            label_smoothing=eps)

    np.testing.assert_allclose(np.asarray(jax.jit(fused)(h, Ws)),
                               np.asarray(dense(h, W)),
                               atol=2e-5, rtol=1e-5)
    gf = jax.jit(jax.grad(lambda h, W: jnp.mean(fused(h, W)),
                          argnums=(0, 1)))(h, Ws)
    gd = jax.grad(lambda h, W: jnp.mean(dense(h, W)), argnums=(0, 1))(h, W)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# spmd_attention_layout: kernels inside a program XLA partitions (the
# chip's compiler refuses a Mosaic kernel there — tests/test_tpu_compile.py
# holds that half; here the per-device results are held to the direct call)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "axes, batch, heads, kv_heads",
    [
        ({"dp": 4, "tp": 2}, 4, 4, 2),   # batch over dp, GQA heads over tp
        ({"dp": 8}, 2, 2, 2),            # 8 does not divide 2: replicated
        ({"dp": 2, "fsdp": 4}, 8, 2, 2),  # batch over a tuple of axes
    ],
)
def test_flash_fn_per_device_under_spmd_layout(world, axes, batch, heads,
                                               kv_heads):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fluxmpi_tpu import ParallelConfig
    from fluxmpi_tpu.ops import flash_attention_fn
    from fluxmpi_tpu.ops.flash_attention import spmd_attention_layout

    plan = ParallelConfig(**axes).resolve()
    mesh = plan.mesh
    lead = plan.batch_spec[0]
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(batch, 64, heads, 32)), jnp.float32)
    k, v = (
        jnp.asarray(rng.normal(size=(batch, 64, kv_heads, 32)), jnp.float32)
        for _ in range(2)
    )
    # A padding mask, so segment ids travel through the shard_map too.
    valid = jnp.arange(64)[None, :] < jnp.asarray(
        rng.integers(32, 64, size=(batch, 1))
    )
    mask = (valid[:, None, :, None] & valid[:, None, None, :])
    attend = flash_attention_fn(causal=True)

    def loss(q, k, v, mask):
        out = attend(q, k, v, mask=mask)
        return jnp.sum(jnp.where(valid[:, :, None, None], out, 0.0) ** 2), out

    grad = jax.grad(loss, (0, 1, 2), has_aux=True)
    want_g, want = jax.jit(grad)(q, k, v, mask)

    def spmd(q, k, v, mask):
        with spmd_attention_layout(mesh, lead, plan.axis_name("tp")):
            return grad(q, k, v, mask)

    sharding = NamedSharding(
        mesh, P(lead) if batch % plan.data_parallel_size == 0 else P()
    )
    got_g, got = jax.jit(spmd, in_shardings=(sharding,) * 4)(q, k, v, mask)
    assert "shard_map" in str(jax.make_jaxpr(spmd)(q, k, v, mask))
    rows = np.asarray(valid)[:, :, None, None]
    np.testing.assert_allclose(
        np.asarray(got) * rows, np.asarray(want) * rows, atol=1e-6
    )
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)


def test_spmd_layout_gives_each_shard_its_own_dropout_stream(world):
    """Kernel dropout hashes the LOCAL (batch, head) index: without a
    per-shard seed every device would drop the same pattern."""
    from fluxmpi_tpu.ops import flash_attention_fn
    from fluxmpi_tpu.ops.flash_attention import spmd_attention_layout

    rng = np.random.default_rng(1)
    row = rng.normal(size=(1, 64, 2, 32)).astype(np.float32)
    # Eight identical rows: identical outputs iff identical masks.
    q, k, v = (jnp.asarray(np.repeat(row, 8, axis=0)) for _ in range(3))
    attend = flash_attention_fn(causal=True, dropout_impl="kernel")

    def run(q, k, v):
        with spmd_attention_layout(world, world.axis_names[0]):
            return attend(q, k, v, dropout_rate=0.5, deterministic=False,
                          dropout_rng=jax.random.PRNGKey(0))

    out = np.asarray(jax.jit(run)(q, k, v))
    assert all(not np.array_equal(out[0], out[i]) for i in range(1, 8))
