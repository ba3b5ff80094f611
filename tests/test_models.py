"""Model zoo tests: every BASELINE config builds, runs forward, and takes a
DP train step on the 8-device mesh; DEQ gradients match the unrolled oracle;
BatchNorm state flows through the train step and synchronize."""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# forward shapes
# ---------------------------------------------------------------------------


def test_cnn_forward(world):
    from fluxmpi_tpu.models import CNN

    model = CNN(num_classes=10)
    x = jnp.ones((4, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (4, 10)
    assert "batch_stats" in variables


def test_resnet18_forward(world):
    from fluxmpi_tpu.models import ResNet18

    model = ResNet18(num_classes=10)
    x = jnp.ones((2, 64, 64, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)


def test_resnet50_builds(world):
    from fluxmpi_tpu.models import ResNet50

    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    x = jnp.ones((2, 64, 64, 3), jnp.bfloat16)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 1000)
    assert out.dtype == jnp.float32  # f32 head
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(variables["params"]))
    assert 20e6 < n_params < 30e6  # ~25.5M — the ResNet-50 signature


def test_deq_forward(world):
    from fluxmpi_tpu.models import DEQ

    model = DEQ(hidden=32, out=1)
    x = jnp.ones((4, 3))
    variables = model.init(jax.random.PRNGKey(0), x)
    out = model.apply(variables, x)
    assert out.shape == (4, 1)
    assert np.all(np.isfinite(np.asarray(out)))


def test_transformer_forward(world):
    from fluxmpi_tpu.models import TransformerEncoder, TransformerLM

    enc = TransformerEncoder(num_layers=2, d_model=32, num_heads=4, d_ff=64)
    x = jnp.ones((2, 16, 32))
    variables = enc.init(jax.random.PRNGKey(0), x, train=False)
    out = enc.apply(variables, x, train=False)
    assert out.shape == (2, 16, 32)

    lm = TransformerLM(vocab_size=64, max_len=32, num_layers=2, d_model=32,
                       num_heads=4, d_ff=64)
    toks = jnp.zeros((2, 16), jnp.int32)
    variables = lm.init(jax.random.PRNGKey(0), toks, train=False)
    logits = lm.apply(variables, toks, train=False)
    assert logits.shape == (2, 16, 64)


# ---------------------------------------------------------------------------
# DEQ implicit gradient oracle
# ---------------------------------------------------------------------------


def test_deq_implicit_gradient_matches_unrolled(world):
    from fluxmpi_tpu.models.deq import fixed_point_solve

    hidden, batch = 8, 4
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    W = jax.random.normal(k1, (hidden, hidden)) * 0.1
    U = jax.random.normal(k2, (3, hidden)) * 0.5
    b = jnp.zeros((hidden,))
    x = jax.random.normal(k3, (batch, 3))

    def cell(params, xx, z):
        W_, U_, b_ = params
        return jnp.tanh(z @ W_ + xx @ U_ + b_)

    def loss_implicit(params):
        z0 = jnp.zeros((batch, hidden))
        z = fixed_point_solve(cell, params, x, z0, 1e-8, 200, 1.0)
        return jnp.sum(z**2)

    def loss_unrolled(params):
        z = jnp.zeros((batch, hidden))
        for _ in range(200):  # plain unrolled AD as oracle
            z = cell(params, x, z)
        return jnp.sum(z**2)

    g_imp = jax.grad(loss_implicit)((W, U, b))
    g_unr = jax.grad(loss_unrolled)((W, U, b))
    for a, b_ in zip(g_imp, g_unr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4)


def test_deq_trains_under_dp(world):
    # collectives + custom VJP under jit over the mesh (SURVEY.md §7 hard part)
    import fluxmpi_tpu as fm
    from fluxmpi_tpu.models import DEQ
    from fluxmpi_tpu.parallel import TrainState, make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    model = DEQ(hidden=16, out=1)
    x = np.random.default_rng(0).normal(size=(32, 3)).astype(np.float32)
    y = (x.sum(axis=1, keepdims=True) ** 2).astype(np.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(x[:2]))
    optimizer = optax.adam(1e-2)

    def loss_fn(p, ms, batch):
        bx, by = batch
        return jnp.mean((model.apply(p, bx) - by) ** 2), ms

    # shard_map style: the custom VJP runs per-device with explicit psum after
    step = make_train_step(
        loss_fn, optimizer, style="shard_map", grad_reduce="mean", donate=False
    )
    state = replicate(TrainState.create(params, optimizer))
    batch = shard_batch((jnp.asarray(x), jnp.asarray(y)))
    losses = []
    for _ in range(30):
        state, loss = step(state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# BatchNorm model state under DP
# ---------------------------------------------------------------------------


def _cnn_setup():
    from fluxmpi_tpu.models import CNN

    model = CNN(num_classes=10, channels=(8, 16))
    x = np.random.default_rng(0).normal(size=(16, 16, 16, 3)).astype(np.float32)
    y = np.random.default_rng(1).integers(0, 10, size=(16,)).astype(np.int32)
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(x[:2]), train=False)
    return model, variables, x, y


def test_cnn_train_step_updates_batch_stats(world):
    import fluxmpi_tpu as fm
    from fluxmpi_tpu.parallel import TrainState, make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    model, variables, x, y = _cnn_setup()
    optimizer = optax.sgd(0.1)

    def loss_fn(params, batch_stats, batch):
        bx, by = batch
        logits, updates = model.apply(
            {"params": params, "batch_stats": batch_stats},
            bx,
            train=True,
            mutable=["batch_stats"],
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, by).mean()
        return loss, updates["batch_stats"]

    step = make_train_step(loss_fn, optimizer, style="auto", donate=False)
    state = replicate(
        TrainState.create(variables["params"], optimizer, variables["batch_stats"])
    )
    batch = shard_batch((jnp.asarray(x), jnp.asarray(y)))
    before = np.asarray(
        jax.tree_util.tree_leaves(state.model_state)[0]
    ).copy()
    state, loss = step(state, batch)
    after = np.asarray(jax.tree_util.tree_leaves(state.model_state)[0])
    assert np.isfinite(float(loss))
    assert not np.array_equal(before, after)  # running stats moved


def test_cnn_sync_bn_matches_global_stats(world, nworkers):
    # Cross-replica BN in shard_map must equal global-batch BN in auto style
    import fluxmpi_tpu as fm
    from fluxmpi_tpu.models import CNN
    from fluxmpi_tpu.parallel import make_train_step, TrainState
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    x = np.random.default_rng(0).normal(size=(16, 8, 8, 3)).astype(np.float32)
    y = np.zeros((16,), np.int32)
    optimizer = optax.sgd(0.1)

    results = {}
    for style, axis_name in (("auto", None), ("shard_map", "dp")):
        model = CNN(num_classes=4, channels=(8,), axis_name=axis_name)
        variables = model.init(
            jax.random.PRNGKey(0), jnp.asarray(x[:2]), train=False
        )

        def loss_fn(params, batch_stats, batch, model=model):
            bx, by = batch
            logits, updates = model.apply(
                {"params": params, "batch_stats": batch_stats},
                bx,
                train=True,
                mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, by
            ).mean()
            return loss, updates["batch_stats"]

        step = make_train_step(
            loss_fn, optimizer, style=style, grad_reduce="mean",
            state_reduce="mean", donate=False
        )
        state = replicate(
            TrainState.create(
                variables["params"], optimizer, variables["batch_stats"]
            )
        )
        batch = shard_batch((jnp.asarray(x), jnp.asarray(y)))
        state, _ = step(state, batch)
        results[style] = jax.tree_util.tree_map(np.asarray, state.model_state)

    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6),
        results["auto"],
        results["shard_map"],
    )


def test_transformer_trains(world):
    import fluxmpi_tpu as fm
    from fluxmpi_tpu.models import TransformerLM
    from fluxmpi_tpu.parallel import TrainState, make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    model = TransformerLM(vocab_size=32, max_len=16, num_layers=2, d_model=32,
                          num_heads=2, d_ff=64)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 32, size=(16, 16)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(toks[:2]), train=False)
    optimizer = optax.adam(1e-3)

    def loss_fn(p, ms, batch):
        b = batch
        logits = model.apply(p, b, train=True)
        targets = jnp.roll(b, -1, axis=-1)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], targets[:, :-1]
        ).mean()
        return loss, ms

    step = make_train_step(loss_fn, optimizer, style="auto", donate=False)
    state = replicate(TrainState.create(params, optimizer))
    batch = shard_batch(jnp.asarray(toks))
    losses = []
    for _ in range(10):
        state, loss = step(state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_vit_forward(world):
    from fluxmpi_tpu.models import ViT

    model = ViT(num_classes=10, patch=8, num_layers=2, d_model=32,
                num_heads=2, d_ff=64)
    x = jnp.ones((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)
    # 32/8 = 4x4 patches + CLS = 17 position embeddings
    assert variables["params"]["pos_embed"].shape == (1, 17, 32)
    with pytest.raises(ValueError, match="patch"):
        model.init(jax.random.PRNGKey(0), jnp.ones((1, 30, 30, 3)),
                   train=False)


def test_vit_trains_under_dp(world):
    from fluxmpi_tpu.models import ViT
    from fluxmpi_tpu.parallel import TrainState, make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    model = ViT(num_classes=4, patch=8, num_layers=2, d_model=32,
                num_heads=2, d_ff=64)
    rng = np.random.default_rng(1)
    xs = jnp.asarray(rng.normal(size=(16, 16, 16, 3)).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, 4, size=(16,)).astype(np.int32))
    params = model.init(jax.random.PRNGKey(0), xs[:2], train=False)
    optimizer = optax.adam(1e-3)

    def loss_fn(p, ms, batch):
        bx, by = batch
        logits = model.apply(p, bx, train=True)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, by
        ).mean(), ms

    step = make_train_step(loss_fn, optimizer, style="auto", donate=False)
    state = replicate(TrainState.create(params, optimizer))
    batch = shard_batch((xs, ys))
    losses = []
    for _ in range(10):
        state, loss = step(state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_vit_with_flash_attention(world):
    # The attention_fn hook composes: ViT through the flash kernel matches
    # the dense encoder (196-token sequences are exactly the shape the
    # kernel auto-picks blocks for).
    from fluxmpi_tpu.models import ViT
    from fluxmpi_tpu.ops import flash_attention_fn

    kw = dict(num_classes=4, patch=8, num_layers=1, d_model=32,
              num_heads=2, d_ff=64)
    dense = ViT(**kw)
    # 17 tokens (16 patches + CLS): the auto-picker takes the full axis as
    # one block — indivisible sequence lengths work out of the box.
    flash = ViT(**kw, attention_fn=flash_attention_fn())
    x = jnp.asarray(
        np.random.default_rng(2).normal(size=(2, 32, 32, 3)).astype(np.float32)
    )
    variables = dense.init(jax.random.PRNGKey(0), x, train=False)
    np.testing.assert_allclose(
        np.asarray(dense.apply(variables, x, train=False)),
        np.asarray(flash.apply(variables, x, train=False)),
        atol=3e-5,
    )


# ---- Anderson-accelerated DEQ solver ----


def test_anderson_matches_damped_fixed_point(world):
    # Same cell, same tolerance: both solvers land on the same fixed point,
    # Anderson in (far) fewer iterations.
    from fluxmpi_tpu.models.deq import _anderson_iteration, _damped_iteration

    rng = np.random.default_rng(70)
    d = 32
    W = jnp.asarray(
        (rng.normal(size=(d, d)) * 0.2 / np.sqrt(d)).astype(np.float32)
    )
    b = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))

    def g(z):
        return jnp.tanh(z @ W + b)

    z0 = jnp.zeros((8, d), jnp.float32)
    z_damped, it_damped = _damped_iteration(g, z0, 1e-6, 500, 0.7)
    z_anderson, it_anderson = _anderson_iteration(g, z0, 1e-6, 500, m=5)
    np.testing.assert_allclose(
        np.asarray(z_anderson), np.asarray(z_damped), atol=1e-4
    )
    assert int(it_anderson) < int(it_damped), (
        int(it_anderson), int(it_damped),
    )


def test_deq_anderson_grads_match_damped(world):
    # The implicit gradients are solver-independent (same z*, same IFT
    # adjoint solution).
    from fluxmpi_tpu.models import DEQ

    rng = np.random.default_rng(71)
    x = jnp.asarray(rng.normal(size=(16, 3)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(16, 1)).astype(np.float32))

    kw = dict(hidden=32, out=1, tol=1e-6, max_iter=300)
    damped = DEQ(**kw, solver="damped")
    anderson = DEQ(**kw, solver="anderson")
    params = damped.init(jax.random.PRNGKey(0), x)

    def loss(model):
        return lambda p: jnp.mean((model.apply(p, x) - y) ** 2)

    ld, gd = jax.value_and_grad(loss(damped))(params)
    la, ga = jax.value_and_grad(loss(anderson))(params)
    np.testing.assert_allclose(float(la), float(ld), rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_deq_anderson_trains_under_dp(world):
    from fluxmpi_tpu.models import DEQ
    from fluxmpi_tpu.parallel import TrainState, make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    model = DEQ(hidden=32, out=1, solver="anderson")
    rng = np.random.default_rng(72)
    xs = jnp.asarray(rng.uniform(-2, 2, size=(32, 1)).astype(np.float32))
    ys = xs**2
    params = model.init(jax.random.PRNGKey(0), xs[:2])

    def loss_fn(p, ms, batch):
        bx, by = batch
        return jnp.mean((model.apply(p, bx) - by) ** 2), ms

    step = make_train_step(loss_fn, optax.adam(1e-2), donate=False)
    state = replicate(TrainState.create(params, optax.adam(1e-2)))
    batch = shard_batch((xs, ys))
    losses = []
    for _ in range(20):
        state, loss = step(state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_broyden_matches_damped_fixed_point(world):
    from fluxmpi_tpu.models.deq import _broyden_iteration, _damped_iteration

    rng = np.random.default_rng(73)
    d = 32
    W = jnp.asarray(
        (rng.normal(size=(d, d)) * 0.2 / np.sqrt(d)).astype(np.float32)
    )
    b = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))

    def g(z):
        return jnp.tanh(z @ W + b)

    z0 = jnp.zeros((8, d), jnp.float32)
    z_damped, it_damped = _damped_iteration(g, z0, 1e-6, 500, 0.7)
    z_broyden, it_broyden = _broyden_iteration(g, z0, 1e-6, 500, m=8)
    np.testing.assert_allclose(
        np.asarray(z_broyden), np.asarray(z_damped), atol=1e-4
    )
    assert int(it_broyden) < int(it_damped)


def test_deq_broyden_grads_match_damped(world):
    from fluxmpi_tpu.models import DEQ

    rng = np.random.default_rng(74)
    x = jnp.asarray(rng.normal(size=(16, 3)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(16, 1)).astype(np.float32))

    kw = dict(hidden=32, out=1, tol=1e-6, max_iter=300)
    damped = DEQ(**kw, solver="damped")
    broyden = DEQ(**kw, solver="broyden")
    params = damped.init(jax.random.PRNGKey(0), x)

    def loss(model):
        return lambda p: jnp.mean((model.apply(p, x) - y) ** 2)

    ld, gd = jax.value_and_grad(loss(damped))(params)
    lb, gb = jax.value_and_grad(loss(broyden))(params)
    np.testing.assert_allclose(float(lb), float(ld), rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(gb),
                    jax.tree_util.tree_leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_transformer_fused_loss_matches_dense_head(world):
    # targets= path: per-token losses from the chunked fused head equal
    # softmax-CE over the dense logits (same params, f32 model dtype),
    # and gradients agree — the [tokens, vocab] tensor is never built.
    import optax

    from fluxmpi_tpu.models import TransformerLM

    lm = TransformerLM(vocab_size=64, max_len=32, num_layers=2, d_model=32,
                       num_heads=4, d_ff=64)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 64, size=(2, 16)).astype(np.int32))
    tgts = jnp.asarray(rng.integers(0, 64, size=(2, 16)).astype(np.int32))
    variables = lm.init(jax.random.PRNGKey(0), toks, train=False)

    def fused(v):
        return jnp.mean(lm.apply(v, toks, train=False, targets=tgts,
                                 loss_chunk=16))

    def dense(v):
        logits = lm.apply(v, toks, train=False)
        return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
            logits, tgts))

    lf, gf = jax.value_and_grad(fused)(variables)
    ld, gd = jax.value_and_grad(dense)(variables)
    np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=1e-4
        ),
        gf, gd,
    )


# ---------------------------------------------------------------------------
# Autoregressive generation (KV-cache decode)
# ---------------------------------------------------------------------------


def test_decode_logits_match_full_forward(world):
    # The cached single-position decode pass must reproduce the training
    # forward's logits position by position (same params, dense path).
    from fluxmpi_tpu.models import TransformerLM
    from fluxmpi_tpu.models.generate import _decode_twin

    lm = TransformerLM(vocab_size=32, max_len=16, num_layers=2, d_model=32,
                       num_heads=4, d_ff=64)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 32, size=(2, 10)).astype(np.int32))
    variables = lm.init(jax.random.PRNGKey(0), toks, train=False)
    full_logits = lm.apply(variables, toks, train=False)  # [2, 10, 32]

    twin = _decode_twin(lm)
    cache = twin.init(jax.random.PRNGKey(0), jnp.zeros((2, 10), jnp.int32),
                      train=False)["cache"]
    for pos in range(10):
        step_logits, mut = twin.apply(
            {"params": variables["params"], "cache": cache},
            toks[:, pos:pos + 1], train=False, pos_offset=pos,
            mutable=["cache"],
        )
        cache = mut["cache"]
        np.testing.assert_allclose(
            np.asarray(step_logits[:, 0]), np.asarray(full_logits[:, pos]),
            atol=2e-5, rtol=1e-4,
        )


def test_generate_greedy_matches_naive_loop(world):
    # One-scan prefill+generate == the naive recompute-everything loop.
    from fluxmpi_tpu.models import TransformerLM, generate

    lm = TransformerLM(vocab_size=32, max_len=24, num_layers=2, d_model=32,
                       num_heads=4, d_ff=64)
    rng = np.random.default_rng(1)
    prompt = jnp.asarray(rng.integers(0, 32, size=(2, 5)).astype(np.int32))
    variables = lm.init(jax.random.PRNGKey(0), prompt, train=False)

    out = generate(lm, variables, prompt, max_new_tokens=8)
    assert out.shape == (2, 13)
    np.testing.assert_array_equal(np.asarray(out[:, :5]), np.asarray(prompt))

    naive = np.asarray(prompt)
    for _ in range(8):
        logits = lm.apply(variables, jnp.asarray(naive), train=False)
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))[:, None]
        naive = np.concatenate([naive, nxt], axis=1)
    np.testing.assert_array_equal(np.asarray(out), naive)


def test_batched_prefill_bit_identical_to_scan(world):
    """The batched-prefill fast path (one causal forward populates the
    KV caches) is bit-for-bit equivalent to the one-token-per-tick scan
    prefill for greedy decoding — and, because the rng stream advances
    identically, for sampled and eos-absorbed decoding too."""
    from fluxmpi_tpu.models import TransformerLM, generate

    lm = TransformerLM(vocab_size=32, max_len=32, num_layers=2, d_model=32,
                       num_heads=4, d_ff=64)
    rng = np.random.default_rng(3)
    variables = lm.init(jax.random.PRNGKey(0), jnp.zeros((2, 4), jnp.int32),
                        train=False)
    for plen in (1, 2, 7):
        prompt = jnp.asarray(
            rng.integers(0, 32, size=(2, plen)).astype(np.int32)
        )
        greedy_scan = generate(lm, variables, prompt, 8, prefill="scan")
        greedy_batched = generate(lm, variables, prompt, 8)
        np.testing.assert_array_equal(
            np.asarray(greedy_scan), np.asarray(greedy_batched)
        )
        key = jax.random.PRNGKey(plen)
        s_scan = generate(lm, variables, prompt, 8, temperature=1.0,
                          top_k=5, rng=key, prefill="scan")
        s_batched = generate(lm, variables, prompt, 8, temperature=1.0,
                             top_k=5, rng=key, prefill="batched")
        np.testing.assert_array_equal(np.asarray(s_scan), np.asarray(s_batched))
        e_scan = generate(lm, variables, prompt, 8, eos_token=3,
                          prefill="scan")
        e_batched = generate(lm, variables, prompt, 8, eos_token=3)
        np.testing.assert_array_equal(np.asarray(e_scan), np.asarray(e_batched))
    with pytest.raises(ValueError, match="prefill"):
        generate(lm, variables, prompt, 4, prefill="bogus")


def test_moe_generate_auto_prefill_keeps_scan_path(world):
    """prefill="auto" must NOT silently switch MoE models to the
    batched prompt forward: capacity routing can drop over-capacity
    prompt tokens there that the one-token-per-tick scan never drops,
    changing outputs. auto == scan for MoE, bit-for-bit."""
    from fluxmpi_tpu.models import MoETransformerLM, TransformerLM, generate

    assert TransformerLM.batched_prefill_safe is True
    assert MoETransformerLM.batched_prefill_safe is False
    lm = MoETransformerLM(vocab_size=32, max_len=24, num_layers=2,
                          d_model=32, num_heads=4, d_ff=64,
                          num_experts=2, capacity_factor=1.0)
    rng = np.random.default_rng(2)
    prompt = jnp.asarray(rng.integers(0, 32, size=(2, 6)).astype(np.int32))
    variables = lm.init(jax.random.PRNGKey(0), prompt, train=False)
    auto = generate(lm, variables, prompt, 6)
    scan = generate(lm, variables, prompt, 6, prefill="scan")
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(scan))


def test_prefill_kv_matches_scan_warmed_cache(world):
    """prefill_kv/prefill_cache produce the cache state the scan would
    reach: K/V for every prompt position (float-close — the batched and
    single-query attends reduce in different orders) with cache_index
    advanced past the prompt."""
    from fluxmpi_tpu.models import TransformerLM
    from fluxmpi_tpu.models.generate import (
        _decode_twin, _sized_cache, prefill_cache, prefill_kv,
    )

    lm = TransformerLM(vocab_size=32, max_len=24, num_layers=2, d_model=32,
                       num_heads=4, d_ff=64)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, 32, size=(2, 6)).astype(np.int32))
    variables = lm.init(jax.random.PRNGKey(0), prompt, train=False)

    k, v, logits = prefill_kv(lm, variables, prompt)
    assert k.shape == (2, 2, 6, 4, 8)  # [layers, batch, plen, heads, hd]
    assert logits.shape == (2, 6, 32)

    twin = _decode_twin(lm)
    scan_cache = _sized_cache(twin, 2, 12)
    for pos in range(6):
        _, mut = twin.apply(
            {"params": variables["params"], "cache": scan_cache},
            prompt[:, pos:pos + 1], train=False, pos_offset=pos,
            mutable=["cache"],
        )
        scan_cache = mut["cache"]
    batched_cache, last = prefill_cache(lm, variables, prompt, 12)
    flat_scan = jax.tree_util.tree_leaves(scan_cache)
    flat_batched = jax.tree_util.tree_leaves(batched_cache)
    for a, b in zip(flat_scan, flat_batched):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=2e-5, rtol=1e-4,
        )
    full = lm.apply(variables, prompt, train=False)
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(last, -1)),
        np.asarray(jnp.argmax(full[:, -1], -1)),
    )


def test_generate_sampling_and_validation(world):
    from fluxmpi_tpu.models import TransformerLM, generate

    lm = TransformerLM(vocab_size=32, max_len=16, num_layers=1, d_model=16,
                       num_heads=2, d_ff=32)
    prompt = jnp.zeros((1, 4), jnp.int32)
    variables = lm.init(jax.random.PRNGKey(0), prompt, train=False)

    # Deterministic per key, key changes the sample.
    a = generate(lm, variables, prompt, 6, temperature=1.0,
                 rng=jax.random.PRNGKey(1))
    b = generate(lm, variables, prompt, 6, temperature=1.0,
                 rng=jax.random.PRNGKey(1))
    c = generate(lm, variables, prompt, 6, temperature=5.0,
                 rng=jax.random.PRNGKey(2))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))

    with pytest.raises(ValueError, match="max_len"):
        generate(lm, variables, prompt, 100)
    with pytest.raises(ValueError, match="rng"):
        generate(lm, variables, prompt, 4, temperature=1.0)
    with pytest.raises(ValueError, match="max_new_tokens"):
        generate(lm, variables, prompt, 0)


def test_generate_works_with_flash_trained_model(world):
    # A model TRAINED with the flash attention_fn generates through the
    # dense decode twin — identical parameter tree.
    from fluxmpi_tpu.models import TransformerLM, generate
    from fluxmpi_tpu.ops import flash_attention_fn

    lm = TransformerLM(vocab_size=32, max_len=16, num_layers=1, d_model=32,
                       num_heads=4, d_ff=64,
                       attention_fn=flash_attention_fn(causal=True))
    prompt = jnp.asarray([[3, 1, 4]], jnp.int32)
    variables = lm.init(jax.random.PRNGKey(0), prompt, train=False)
    out = generate(lm, variables, prompt, 5)
    assert out.shape == (1, 8)
    assert np.all((np.asarray(out) >= 0) & (np.asarray(out) < 32))


def test_attention_switch_flash_matches_naive_oracle(world):
    """The kernel-plane switch (ISSUE 19): attention="flash" must be a
    pure kernel substitution — same params, same batch, the fused-CE
    training loss AND its gradients (through the flash custom_vjp)
    match the naive dense attend to dtype tolerance, and greedy decode
    streams bit-identical tokens."""
    from fluxmpi_tpu.models import TransformerLM, generate

    naive = TransformerLM(vocab_size=32, max_len=32, num_layers=2,
                          d_model=32, num_heads=4, d_ff=64)
    flash = naive.clone(attention="flash")
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.integers(0, 32, size=(2, 24)).astype(np.int32))
    y = jnp.asarray(rng.integers(0, 32, size=(2, 24)).astype(np.int32))
    variables = naive.init(jax.random.PRNGKey(0), x, train=False)

    def loss(model):
        def fn(p):
            return model.apply(p, x, train=True, targets=y).mean()
        return fn

    l_n, g_n = jax.value_and_grad(loss(naive))(variables)
    l_f, g_f = jax.value_and_grad(loss(flash))(variables)
    np.testing.assert_allclose(float(l_f), float(l_n), atol=1e-5)
    flat_n = jax.tree_util.tree_leaves(g_n)
    flat_f = jax.tree_util.tree_leaves(g_f)
    for a, b in zip(flat_f, flat_n):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5
        )

    prompt = x[:, :5]
    np.testing.assert_array_equal(
        np.asarray(generate(flash, variables, prompt, 6)),
        np.asarray(generate(naive, variables, prompt, 6)),
    )


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr and of the jaxprs in it."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _pallas_calls(inner)
    return found


def test_flash_lm_hands_its_kernels_causality_and_no_segment_ids(world):
    """The LM's own causal mask says nothing the flash path's ``causal``
    does not: with ``attention="flash"`` no mask reaches the attention
    function, so the kernels take q, k, v alone (the forward) and work
    the diagonal as a staircase, where a mask would come back as one
    segment id a token, compared on every tile (PR 42). A caller's own
    ``attention_fn`` still gets the mask."""
    from fluxmpi_tpu.models import TransformerLM
    from fluxmpi_tpu.ops import flash_attention_fn

    lm = TransformerLM(vocab_size=32, max_len=256, num_layers=1,
                       d_model=32, num_heads=2, d_ff=64, attention="flash")
    x = jnp.zeros((1, 256), jnp.int32)
    variables = jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0), x, train=False))

    def kernel_operands(model):
        jaxpr = jax.make_jaxpr(
            lambda p: model.apply(p, x, train=True))(variables)
        return [len(eqn.invars) for eqn in _pallas_calls(jaxpr.jaxpr)]

    assert kernel_operands(lm) == [3]
    masked = lm.clone(attention="naive",
                      attention_fn=flash_attention_fn(causal=True))
    assert kernel_operands(masked) == [5]  # ... and the two segment rows


def test_attention_switch_validation(world):
    """Switch error paths: an unknown mode raises at apply time,
    attention='flash' conflicts with an explicit attention_fn, and
    'auto' resolves to naive off-TPU (this suite runs on CPU)."""
    from fluxmpi_tpu.models import TransformerLM
    from fluxmpi_tpu.models.transformer import _resolve_attention_mode
    from fluxmpi_tpu.ops import flash_attention_fn

    assert _resolve_attention_mode("auto") == "naive"  # CPU backend
    with pytest.raises(ValueError, match="attention must be"):
        _resolve_attention_mode("fast")

    x = jnp.zeros((1, 8), jnp.int32)
    lm = TransformerLM(vocab_size=32, max_len=16, num_layers=1, d_model=32,
                       num_heads=4, d_ff=64, attention="flash",
                       attention_fn=flash_attention_fn(causal=True))
    with pytest.raises(ValueError, match="conflicts"):
        lm.init(jax.random.PRNGKey(0), x, train=False)


def test_beam_search_beam1_matches_greedy(world):
    from fluxmpi_tpu.models import TransformerLM, beam_search, generate

    lm = TransformerLM(vocab_size=32, max_len=24, num_layers=2, d_model=32,
                       num_heads=4, d_ff=64)
    rng = np.random.default_rng(3)
    prompt = jnp.asarray(rng.integers(0, 32, size=(2, 5)).astype(np.int32))
    variables = lm.init(jax.random.PRNGKey(0), prompt, train=False)

    greedy = generate(lm, variables, prompt, max_new_tokens=7)
    toks, scores = beam_search(lm, variables, prompt, max_new_tokens=7,
                               beam_size=1)
    assert toks.shape == (2, 12) and scores.shape == (2,)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(greedy))
    assert np.all(np.isfinite(np.asarray(scores)))


def test_beam_search_finds_global_optimum(world):
    # With beam_size = vocab**max_new_tokens the search is exhaustive, so
    # the result must equal the true argmax over all continuations scored
    # by teacher-forced log-likelihood on the TRAINING forward — an
    # independent oracle path (full forward, no KV cache).
    from itertools import product

    from fluxmpi_tpu.models import TransformerLM, beam_search

    vocab, plen, new = 6, 2, 3
    lm = TransformerLM(vocab_size=vocab, max_len=8, num_layers=1,
                       d_model=16, num_heads=2, d_ff=32)
    rng = np.random.default_rng(7)
    prompt = jnp.asarray(rng.integers(0, vocab, size=(2, plen))
                         .astype(np.int32))
    variables = lm.init(jax.random.PRNGKey(2), prompt, train=False)

    best_toks, best_scores = beam_search(
        lm, variables, prompt, max_new_tokens=new, beam_size=vocab ** new)

    conts = np.array(list(product(range(vocab), repeat=new)), np.int32)
    n = len(conts)  # 216
    for row in range(2):
        seqs = np.concatenate(
            [np.tile(np.asarray(prompt[row]), (n, 1)), conts], axis=1)
        logits = lm.apply(variables, jnp.asarray(seqs), train=False)
        logp = np.asarray(jax.nn.log_softmax(
            logits.astype(jnp.float32), axis=-1))
        scores = np.zeros(n)
        for t in range(plen - 1, plen + new - 1):
            scores += logp[np.arange(n), t, seqs[:, t + 1]]
        k = int(np.argmax(scores))
        np.testing.assert_allclose(float(best_scores[row]), scores[k],
                                   atol=1e-4, rtol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(best_toks[row, plen:]), conts[k])


def test_beam_search_eos_absorbing_and_validation(world):
    from fluxmpi_tpu.models import TransformerLM, beam_search

    vocab = 4
    lm = TransformerLM(vocab_size=vocab, max_len=12, num_layers=1,
                       d_model=16, num_heads=2, d_ff=32)
    prompt = jnp.asarray([[1, 2], [0, 3]], jnp.int32)
    variables = lm.init(jax.random.PRNGKey(0), prompt, train=False)

    for eos in range(vocab):
        toks, scores = beam_search(lm, variables, prompt, max_new_tokens=6,
                                   beam_size=3, eos_token=eos,
                                   length_penalty=0.6)
        gen = np.asarray(toks[:, 2:])
        assert np.all(np.isfinite(np.asarray(scores)))
        for row in gen:
            hits = np.flatnonzero(row == eos)
            if hits.size:  # everything after the first eos is eos
                assert np.all(row[hits[0]:] == eos)
        # Returned score == teacher-forced rescoring of the returned
        # sequence, length-penalized at the finish length (independent
        # full-forward oracle, no KV cache).
        hits = np.flatnonzero(gen[0] == eos)
        flen = int(hits[0]) + 1 if hits.size else 6
        seq = np.asarray(toks[0:1, :2 + flen])
        logp = np.asarray(jax.nn.log_softmax(
            lm.apply(variables, jnp.asarray(seq),
                     train=False).astype(jnp.float32), axis=-1))
        raw = sum(logp[0, t, seq[0, t + 1]] for t in range(1, 1 + flen))
        lp = ((5.0 + flen) / 6.0) ** 0.6
        np.testing.assert_allclose(float(scores[0]), raw / lp,
                                   atol=1e-4, rtol=1e-5)

    with pytest.raises(ValueError, match="beam_size"):
        beam_search(lm, variables, prompt, 4, beam_size=0)
    with pytest.raises(ValueError, match="max_len"):
        beam_search(lm, variables, prompt, 100, beam_size=2)
    with pytest.raises(ValueError, match="vocabulary"):
        beam_search(lm, variables, prompt, 4, beam_size=2, eos_token=vocab)


def test_transformer_hidden_escape_hatch(world):
    # hidden=True exposes (pre-head states, tied table) so custom heads
    # (e.g. the TP vocab-sharded CE) compose; consistent with logits.
    from fluxmpi_tpu.models import TransformerLM

    lm = TransformerLM(vocab_size=32, max_len=16, num_layers=1, d_model=16,
                       num_heads=2, d_ff=32)
    toks = jnp.zeros((2, 8), jnp.int32)
    variables = lm.init(jax.random.PRNGKey(0), toks, train=False)
    h, table = lm.apply(variables, toks, train=False, hidden=True)
    assert h.shape == (2, 8, 16) and table.shape == (32, 16)
    logits = lm.apply(variables, toks, train=False)
    np.testing.assert_allclose(
        np.asarray(logits),
        np.asarray(h.astype(jnp.float32) @ table.astype(jnp.float32).T),
        atol=1e-5,
    )
    with pytest.raises(ValueError, match="either targets or hidden"):
        lm.apply(variables, toks, train=False, hidden=True,
                 targets=jnp.zeros((2, 8), jnp.int32))


def test_generate_eos_and_top_k(world):
    from fluxmpi_tpu.models import TransformerLM, generate

    lm = TransformerLM(vocab_size=16, max_len=20, num_layers=1, d_model=16,
                       num_heads=2, d_ff=32)
    prompt = jnp.zeros((2, 3), jnp.int32)
    variables = lm.init(jax.random.PRNGKey(0), prompt, train=False)

    # Greedy with eos = whatever the model emits first: everything after
    # the first occurrence must be eos too.
    free = np.asarray(generate(lm, variables, prompt, 8))
    eos = int(free[0, 3])
    out = np.asarray(generate(lm, variables, prompt, 8, eos_token=eos))
    for row in out:
        hits = np.where(row[3:] == eos)[0]
        if hits.size:
            assert np.all(row[3 + hits[0]:] == eos)

    # top_k=1 sampling == greedy regardless of temperature.
    topk1 = np.asarray(generate(lm, variables, prompt, 8, temperature=2.0,
                                top_k=1, rng=jax.random.PRNGKey(3)))
    np.testing.assert_array_equal(topk1, free)

    import pytest as _pytest

    with _pytest.raises(ValueError, match="top_k"):
        generate(lm, variables, prompt, 4, temperature=1.0, top_k=0,
                 rng=jax.random.PRNGKey(0))


def test_generate_top_p(world):
    from fluxmpi_tpu.models import TransformerLM, generate

    lm = TransformerLM(vocab_size=16, max_len=20, num_layers=1, d_model=16,
                       num_heads=2, d_ff=32)
    prompt = jnp.zeros((2, 3), jnp.int32)
    variables = lm.init(jax.random.PRNGKey(0), prompt, train=False)

    greedy = np.asarray(generate(lm, variables, prompt, 8))
    # A tiny nucleus keeps only the argmax token: sampling == greedy at
    # any temperature.
    tiny = np.asarray(generate(lm, variables, prompt, 8, temperature=3.0,
                               top_p=1e-6, rng=jax.random.PRNGKey(4)))
    np.testing.assert_array_equal(tiny, greedy)

    # top_p=1.0 is a no-op: bit-identical to unfiltered sampling with the
    # same key.
    full = np.asarray(generate(lm, variables, prompt, 8, temperature=1.0,
                               top_p=1.0, rng=jax.random.PRNGKey(5)))
    plain = np.asarray(generate(lm, variables, prompt, 8, temperature=1.0,
                                rng=jax.random.PRNGKey(5)))
    np.testing.assert_array_equal(full, plain)

    # Composes with top_k and stays in-vocab / finite.
    both = np.asarray(generate(lm, variables, prompt, 8, temperature=1.0,
                               top_k=8, top_p=0.9,
                               rng=jax.random.PRNGKey(6)))
    assert both.shape == (2, 11)
    assert (both >= 0).all() and (both < 16).all()

    import pytest as _pytest

    with _pytest.raises(ValueError, match="top_p"):
        generate(lm, variables, prompt, 4, temperature=1.0, top_p=0.0,
                 rng=jax.random.PRNGKey(0))
    with _pytest.raises(ValueError, match="top_p"):
        generate(lm, variables, prompt, 4, temperature=1.0, top_p=1.5,
                 rng=jax.random.PRNGKey(0))
