"""Train-step factory tests: both styles agree with each other and with a
serial single-device update (the end-to-end analogue of the reference's
optimizer equivalence oracle, test/test_optimizer.jl:20-26)."""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp


def _setup(world):
    from fluxmpi_tpu.models import MLP
    from fluxmpi_tpu.parallel import TrainState

    model = MLP(features=(8, 8, 1))
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 2)))
    optimizer = optax.sgd(0.1)
    state = TrainState.create(params, optimizer)

    def loss_fn(p, mstate, batch):
        x, y = batch
        pred = model.apply(p, x)
        return jnp.mean((pred - y) ** 2), mstate

    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, 2)).astype(np.float32)
    y = rng.normal(size=(16, 1)).astype(np.float32)
    return model, params, optimizer, state, loss_fn, (x, y)


def test_auto_matches_serial(world):
    import fluxmpi_tpu as fm
    from fluxmpi_tpu.parallel import make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    model, params, optimizer, state, loss_fn, batch = _setup(world)
    step = make_train_step(loss_fn, optimizer, style="auto", donate=False)
    new_state, loss = step(replicate(state), shard_batch(batch))

    # serial oracle on one device
    (sloss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, None, batch
    )
    updates, _ = optimizer.update(grads, optimizer.init(params), params)
    serial_params = optax.apply_updates(params, updates)

    np.testing.assert_allclose(float(loss), float(sloss), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        ),
        new_state.params,
        serial_params,
    )
    assert int(new_state.step) == 1


def test_shard_map_matches_auto(world):
    from fluxmpi_tpu.parallel import make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    model, params, optimizer, state, loss_fn, batch = _setup(world)
    auto = make_train_step(loss_fn, optimizer, style="auto", donate=False)
    explicit = make_train_step(
        loss_fn, optimizer, style="shard_map", grad_reduce="mean", donate=False
    )
    s1, l1 = auto(replicate(state), shard_batch(batch))
    s2, l2 = explicit(replicate(state), shard_batch(batch))
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        ),
        s1.params,
        s2.params,
    )


def test_sum_semantics_with_distributed_optimizer(world, nworkers):
    # reference pattern: DistributedOptimizer sums; loss scaled by 1/workers
    import fluxmpi_tpu as fm
    from fluxmpi_tpu.parallel import make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch
    from fluxmpi_tpu.parallel import TrainState

    model, params, optimizer, _, _, batch = _setup(world)

    def scaled_loss(p, mstate, b):
        x, y = b
        pred = model.apply(p, x)
        return jnp.mean((pred - y) ** 2) / nworkers, mstate

    dopt = fm.DistributedOptimizer(optax.sgd(0.1), axis_name="dp")
    state = TrainState.create(params, dopt)
    step = make_train_step(
        scaled_loss, dopt, style="shard_map", grad_reduce=None, donate=False
    )
    s1, _ = step(replicate(state), shard_batch(batch))

    # mean-reduce path with plain optimizer must give the same parameters
    def plain_loss(p, mstate, b):
        x, y = b
        pred = model.apply(p, x)
        return jnp.mean((pred - y) ** 2), mstate

    plain = optax.sgd(0.1)
    state2 = TrainState.create(params, plain)
    step2 = make_train_step(
        plain_loss, plain, style="shard_map", grad_reduce="mean", donate=False
    )
    s2, _ = step2(replicate(state2), shard_batch(batch))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        ),
        s1.params,
        s2.params,
    )


def test_training_converges(world):
    import fluxmpi_tpu as fm
    from fluxmpi_tpu.parallel import TrainState, make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch
    from fluxmpi_tpu.models import MLP

    model = MLP(features=(16, 16, 1))
    params = model.init(jax.random.PRNGKey(1), jnp.ones((1, 1)))
    optimizer = optax.adam(1e-2)

    def loss_fn(p, mstate, b):
        x, y = b
        return jnp.mean((model.apply(p, x) - y) ** 2), mstate

    step = make_train_step(loss_fn, optimizer, style="auto")
    state = replicate(TrainState.create(params, optimizer))
    rng = np.random.default_rng(0)
    x = rng.uniform(-2, 2, size=(64, 1)).astype(np.float32)
    batch = shard_batch((x, (x**2).astype(np.float32)))
    losses = []
    for _ in range(60):
        state, loss = step(state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.1


def test_invalid_style_rejected(world):
    import optax
    from fluxmpi_tpu.parallel import make_train_step

    with pytest.raises(ValueError):
        make_train_step(lambda p, s, b: (0.0, s), optax.sgd(0.1), style="magic")
    with pytest.raises(ValueError):
        make_train_step(
            lambda p, s, b: (0.0, s), optax.sgd(0.1), grad_reduce="median"
        )


def test_remat_matches_plain(world):
    """jax.checkpoint rematerialization must not change the math."""
    import optax as _optax

    from fluxmpi_tpu.parallel import make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    model, params, optimizer, state, loss_fn, batch = _setup(world)
    plain = make_train_step(loss_fn, optimizer, style="auto", donate=False)
    remat = make_train_step(
        loss_fn, optimizer, style="auto", donate=False, remat=True
    )
    s1, l1 = plain(replicate(state), shard_batch(batch))
    s2, l2 = remat(replicate(state), shard_batch(batch))
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        ),
        s1.params,
        s2.params,
    )


def test_grad_accum_matches_full_batch(world):
    """K accumulation microbatches == one full-batch step (same mean-loss
    semantics, single optimizer update)."""
    from fluxmpi_tpu.parallel import make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    model, params, optimizer, state, loss_fn, batch = _setup(world)
    full = make_train_step(loss_fn, optimizer, style="auto", donate=False)
    accum = make_train_step(
        loss_fn, optimizer, style="auto", donate=False, grad_accum_steps=4
    )
    s1, l1 = full(replicate(state), shard_batch(batch))
    s2, l2 = accum(replicate(state), shard_batch(batch))
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        ),
        s1.params,
        s2.params,
    )
    assert int(s2.step) == 1  # one update, not four


def test_scan_steps_match_sequential(world):
    """K scanned updates in one dispatch == K sequential single-step calls
    (same updates in the same order; [K] per-update losses returned)."""
    from jax.sharding import PartitionSpec as P

    from fluxmpi_tpu.parallel import make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    model, params, optimizer, state, loss_fn, batch = _setup(world)
    K = 3
    rng = np.random.default_rng(7)
    xs = rng.normal(size=(K, 16, 2)).astype(np.float32)
    ys = rng.normal(size=(K, 16, 1)).astype(np.float32)

    single = make_train_step(loss_fn, optimizer, style="auto", donate=False)
    s1 = replicate(state)
    losses_seq = []
    for i in range(K):
        s1, l = single(s1, shard_batch((xs[i], ys[i])))
        losses_seq.append(float(l))

    scanned = make_train_step(
        loss_fn, optimizer, style="auto", donate=False, scan_steps=K
    )
    s2, losses = scanned(
        replicate(state), shard_batch((xs, ys), spec=P(None, "dp"))
    )
    assert losses.shape == (K,)
    np.testing.assert_allclose(np.asarray(losses), losses_seq, rtol=1e-5)
    assert int(s2.step) == K
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        ),
        s1.params,
        s2.params,
    )


def test_scan_steps_requires_auto(world):
    from fluxmpi_tpu.parallel import make_train_step

    model, params, optimizer, state, loss_fn, batch = _setup(world)
    with pytest.raises(ValueError, match="scan_steps"):
        make_train_step(
            loss_fn, optimizer, style="shard_map", scan_steps=2
        )


def test_grad_accum_divisibility_error(world):
    from fluxmpi_tpu.parallel import make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    model, params, optimizer, state, loss_fn, batch = _setup(world)
    step = make_train_step(
        loss_fn, optimizer, style="auto", donate=False, grad_accum_steps=5
    )
    with pytest.raises(ValueError, match="not divisible"):
        step(replicate(state), shard_batch(batch))


def test_eval_step(world):
    from fluxmpi_tpu.parallel import make_eval_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    model, params, optimizer, state, loss_fn, batch = _setup(world)

    def metric_fn(p, mstate, b):
        x, y = b
        pred = model.apply(p, x)
        return {"mse": jnp.mean((pred - y) ** 2), "mae": jnp.mean(jnp.abs(pred - y))}

    ev = make_eval_step(metric_fn)
    metrics = ev(replicate(state), shard_batch(batch))
    x, y = batch
    pred = model.apply(params, x)
    np.testing.assert_allclose(
        float(metrics["mse"]), float(jnp.mean((pred - y) ** 2)), rtol=1e-5
    )
    np.testing.assert_allclose(
        float(metrics["mae"]), float(jnp.mean(jnp.abs(pred - y))), rtol=1e-5
    )


def test_remat_dots_matches_plain(world):
    """checkpoint_dots policy must not change the math either."""
    import optax as _optax  # noqa: F401

    from fluxmpi_tpu.parallel import make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    model, params, optimizer, state, loss_fn, batch = _setup(world)
    plain = make_train_step(loss_fn, optimizer, style="auto", donate=False)
    dots = make_train_step(
        loss_fn, optimizer, style="auto", donate=False, remat="dots"
    )
    s1, l1 = plain(replicate(state), shard_batch(batch))
    s2, l2 = dots(replicate(state), shard_batch(batch))
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        ),
        s1.params, s2.params,
    )

    with pytest.raises(ValueError, match="remat"):
        make_train_step(loss_fn, optimizer, style="auto", remat="everything")


def test_scan_steps_composes_with_fsdp_sharding(world):
    """scan_steps under an FSDP state layout: the scan carry keeps the
    sharded TrainState layout and the result matches replicated scan."""
    from jax.sharding import PartitionSpec as P

    from fluxmpi_tpu.models import MLP
    from fluxmpi_tpu.parallel import (
        TrainState, fsdp_rule, make_train_step, shard_tree,
    )
    from fluxmpi_tpu.parallel.train import replicate, shard_batch
    import fluxmpi_tpu as fm

    mesh = fm.global_mesh()
    model = MLP(features=(32, 32, 1))
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 2)))
    opt = optax.adam(1e-2)

    def loss_fn(p, ms, batch):
        x, y = batch
        return jnp.mean((model.apply(p, x) - y) ** 2), ms

    K = 2
    rng = np.random.default_rng(3)
    xs = rng.normal(size=(K, 16, 2)).astype(np.float32)
    ys = rng.normal(size=(K, 16, 1)).astype(np.float32)
    batch = shard_batch((xs, ys), spec=P(None, "dp"))

    state0 = TrainState.create(params, opt)
    sharded, shardings = shard_tree(state0, mesh, fsdp_rule(mesh, min_size=8))
    step_fsdp = make_train_step(
        loss_fn, opt, mesh=mesh, donate=False, scan_steps=K,
        state_sharding=shardings,
    )
    s1, l1 = step_fsdp(sharded, batch)

    step_rep = make_train_step(loss_fn, opt, mesh=mesh, donate=False,
                               scan_steps=K)
    s2, l2 = step_rep(replicate(state0), batch)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        ),
        jax.device_get(s1.params), jax.device_get(s2.params),
    )


def test_policy_casts_params_entering_loss(world):
    # policy= : the loss sees compute-dtype params, the TrainState keeps
    # f32 masters, gradients/updates run f32, and training still works.
    import fluxmpi_tpu as fm
    from fluxmpi_tpu.parallel import make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch
    from fluxmpi_tpu.utils import get_policy

    model, params, optimizer, state, _, batch = _setup(world)
    seen = []

    def loss_fn(p, mstate, b):
        x, y = b
        seen.append(jax.tree_util.tree_leaves(p)[0].dtype)
        pred = model.apply(p, x.astype(jnp.bfloat16))
        return jnp.mean((pred.astype(jnp.float32) - y) ** 2), mstate

    step = make_train_step(loss_fn, optimizer, style="auto", donate=False,
                           policy=get_policy("bf16"))
    st = replicate(state)
    data = shard_batch(batch)
    for _ in range(40):
        st, loss = step(st, data)
        # Drain every update: forty 8-device programs queued back to back
        # can starve XLA:CPU's in-process all-reduce rendezvous of a
        # thread (7 of 8 arrive), and it aborts the process after 40 s.
        loss.block_until_ready()
    assert seen and all(d == jnp.bfloat16 for d in seen)  # compute dtype
    leaves = jax.tree_util.tree_leaves(st.params)
    assert all(x.dtype == jnp.float32 for x in leaves)  # f32 masters
    assert float(loss) < 1.0  # learns through the cast

    # Eval step gets the same cast.
    from fluxmpi_tpu.parallel.train import make_eval_step

    eval_seen = []

    def metric_fn(p, mstate, b):
        x, y = b
        eval_seen.append(jax.tree_util.tree_leaves(p)[0].dtype)
        pred = model.apply(p, x.astype(jnp.bfloat16))
        return jnp.mean((pred.astype(jnp.float32) - y) ** 2)

    ev = make_eval_step(metric_fn, policy=get_policy("bf16"))
    _ = ev(st, data)
    assert eval_seen and eval_seen[0] == jnp.bfloat16


def test_train_step_not_retraced_across_steps(world):
    # Recompilation guard: the compiled step traces ONCE; repeated calls
    # (including through loader-produced batches, whose sharding object
    # is constant per epoch) hit the jit cache.
    import optax

    from fluxmpi_tpu.data import ArrayDataset, DistributedDataLoader
    from fluxmpi_tpu.models import MLP
    from fluxmpi_tpu.parallel import TrainState, make_train_step
    from fluxmpi_tpu.parallel.train import replicate

    model = MLP(features=(8, 1))

    def loss_fn(p, ms, b):
        bx, by = b
        return jnp.mean((model.apply(p, bx) - by) ** 2), ms

    opt = optax.sgd(1e-2)
    params = jax.device_get(
        model.init(jax.random.PRNGKey(0), jnp.zeros((2, 1)))
    )
    step = make_train_step(loss_fn, opt, mesh=world)
    assert step.scan_steps == 1  # loop-driver metadata rides the step
    x = np.linspace(-1, 1, 64, dtype=np.float32)[:, None]
    loader = DistributedDataLoader(ArrayDataset((x, x**2)), 32, mesh=world)
    state = replicate(TrainState.create(params, opt, None), world)
    for _ in range(2):
        for batch in loader:
            state, _ = step(state, batch)
    assert step._cache_size() == 1

    # Instrumented steps expose the same guarantee through the wrapper.
    step_i = make_train_step(loss_fn, opt, mesh=world, metrics=True)
    state = replicate(TrainState.create(params, opt, None), world)
    for batch in loader:
        state, _ = step_i(state, batch)
    assert step_i.__fluxmpi_compiled__._cache_size() == 1
