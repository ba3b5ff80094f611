"""ParallelConfig composition-engine tests (parallel/plan.py): topology
validation, the strict rule engine, plan-derived specs vs the hand-written
rules, the dp×fsdp×tp GPT-2 end-to-end proof, and the plan's reach into
loader/manifest/restore/axis-name defaults."""

import contextlib

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@contextlib.contextmanager
def _fresh_runtime():
    """Swap the runtime out so a test can init() its own plan/mesh and
    hand the session fixture's world back untouched (the test_common
    save/restore pattern, extended with the plan slot)."""
    from fluxmpi_tpu import runtime

    saved = (
        runtime._state.initialized,
        runtime._state.mesh,
        runtime._state.plan,
    )
    runtime._state.initialized = False
    runtime._state.mesh = None
    runtime._state.plan = None
    try:
        yield
    finally:
        (
            runtime._state.initialized,
            runtime._state.mesh,
            runtime._state.plan,
        ) = saved


# ---------------------------------------------------------------------------
# Topology validation
# ---------------------------------------------------------------------------


def test_parallel_config_rejects_non_covering(world):
    from fluxmpi_tpu import ParallelConfig
    from fluxmpi_tpu.errors import TopologyMismatchError

    with pytest.raises(TopologyMismatchError, match="covers 6 device"):
        ParallelConfig(dp=3, tp=2).resolve()
    with pytest.raises(TopologyMismatchError, match="not divisible"):
        ParallelConfig(dp=-1, tp=3).resolve()
    with pytest.raises(ValueError, match="at most one"):
        ParallelConfig(dp=-1, tp=-1)
    with pytest.raises(ValueError, match="positive int or -1"):
        ParallelConfig(dp=0)
    with pytest.raises(ValueError, match="plan axes"):
        ParallelConfig(dp=8, axis_names={"zz": "z"})


def test_parallel_config_resolution(world):
    from fluxmpi_tpu import ParallelConfig

    # Default: everything data-parallel.
    plan = ParallelConfig().resolve()
    assert dict(plan.mesh.shape) == {"dp": 8}
    assert plan.data_parallel_size == 8
    assert plan.batch_spec == P("dp")

    # Canonical axis order, inference, composed batch spec.
    plan = ParallelConfig(fsdp=2, tp=2, dp=-1).resolve()
    assert tuple(plan.mesh.axis_names) == ("dp", "fsdp", "tp")
    assert dict(plan.mesh.shape) == {"dp": 2, "fsdp": 2, "tp": 2}
    assert plan.data_axes == ("dp", "fsdp")
    assert plan.data_parallel_size == 4
    assert plan.batch_spec == P(("dp", "fsdp"))
    assert plan.axis_name("tp") == "tp"
    assert plan.axis_name("pp") is None

    # sp rides the batch spec's sequence dim.
    plan = ParallelConfig(dp=4, sp=2).resolve()
    assert plan.batch_spec == P("dp", "sp")


def test_parallel_config_axis_name_overrides(world):
    from fluxmpi_tpu import ParallelConfig

    plan = ParallelConfig(
        dp=4, tp=2, axis_names={"dp": "data", "tp": "model"}
    ).resolve()
    assert dict(plan.mesh.shape) == {"data": 4, "model": 2}
    # The TP table follows the renamed axis.
    spec = plan.rule("encoder/block_0/ff1/kernel", (32, 64))
    assert spec == P(None, "model")


# ---------------------------------------------------------------------------
# The rule engine
# ---------------------------------------------------------------------------


def test_match_partition_rules_strict_raises(world):
    from fluxmpi_tpu import match_partition_rules

    tree = {
        "dense": {"kernel": jnp.ones((8, 4)), "bias": jnp.ones((4,))},
        "scalar": jnp.ones(()),
    }
    # Full coverage: every non-scalar leaf matched, scalars get P().
    specs = match_partition_rules(
        [(r"kernel$", P("dp", None)), (r"bias$", P())], tree
    )
    assert specs["dense"]["kernel"] == P("dp", None)
    assert specs["scalar"] == P()

    # An unmatched non-scalar path raises — no silent replication.
    with pytest.raises(ValueError, match="dense/bias"):
        match_partition_rules([(r"kernel$", P("dp", None))], tree)


def test_plan_strict_partition_specs(world):
    from fluxmpi_tpu import ParallelConfig

    tree = {"w": jnp.ones((16, 4)), "oddball": jnp.ones((4, 4))}
    plan = ParallelConfig(
        dp=8, rules=[(r"^w$", P("dp", None))], strict=True
    ).resolve()
    with pytest.raises(ValueError, match="oddball"):
        plan.partition_specs(tree)
    # Non-strict counts the fall-through instead.
    plan = ParallelConfig(dp=8, rules=[(r"^w$", P("dp", None))]).resolve()
    specs = plan.partition_specs(tree)
    assert specs["oddball"] == P()
    assert plan.rule_hits == {"table": 1, "replicated": 1}


def _tiny_lm():
    from fluxmpi_tpu.models import TransformerLM

    return TransformerLM(
        vocab_size=64, max_len=32, num_layers=2, d_model=32,
        num_heads=4, d_ff=64,
    )


def test_plan_specs_equal_handwritten_rules(world):
    """The plan's combined rule reproduces the hand-written
    transformer_tp_rules + fsdp_rule specs leaf-for-leaf on the
    transformer (params AND optax state, via the path-suffix
    convention)."""
    from fluxmpi_tpu import ParallelConfig
    from fluxmpi_tpu.parallel import TrainState, combine_rules, fsdp_rule
    from fluxmpi_tpu.parallel import transformer_tp_rules
    from fluxmpi_tpu.parallel.sharding import tree_partition_specs

    model = _tiny_lm()
    params = model.init(
        jax.random.PRNGKey(0), jnp.ones((2, 16), jnp.int32), train=False
    )
    state = TrainState.create(params, optax.adam(1e-2))

    plan = ParallelConfig(dp=2, fsdp=2, tp=2, fsdp_min_size=256).resolve()
    hand = combine_rules(
        transformer_tp_rules(tp_axis="tp"),
        fsdp_rule(plan.mesh, axis_name="fsdp", min_size=256),
    )
    expected = tree_partition_specs(state, plan.mesh, hand)
    got = plan.partition_specs(state)
    flat_e = jax.tree_util.tree_flatten(
        expected, is_leaf=lambda x: isinstance(x, P)
    )[0]
    flat_g = jax.tree_util.tree_flatten(
        got, is_leaf=lambda x: isinstance(x, P)
    )[0]
    assert flat_e == flat_g
    # And the TP table actually matched something.
    assert plan.rule_hits.get("tp", 0) > 0
    assert plan.rule_hits.get("fsdp", 0) > 0


# ---------------------------------------------------------------------------
# End-to-end: HF-imported GPT-2 under one composed ParallelConfig
# ---------------------------------------------------------------------------


def _gpt2_workload():
    """A real HF GPT-2 (tiny random config) through lm_from_gpt2 when
    torch/transformers are installed; the same-architecture TransformerLM
    otherwise — the composition proof must run in tier-1 either way."""
    try:
        import torch
        from transformers import GPT2Config, GPT2LMHeadModel

        from fluxmpi_tpu.models.hf_gpt2 import lm_from_gpt2

        # Seeded: the bitwise dp-vs-dp×fsdp comparison below must test
        # the LAYOUT, not sample the weight distribution (an unlucky
        # draw can land a reduce-scatter rounding one ULP off the
        # all-reduce order).
        torch.manual_seed(0)
        cfg = GPT2Config(
            vocab_size=128, n_positions=32, n_embd=32, n_layer=2,
            n_head=4, resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
        )
        model, variables = lm_from_gpt2(GPT2LMHeadModel(cfg))
        return model, variables, 128
    except ImportError:  # pragma: no cover - torch-less environments
        model = _tiny_lm()
        variables = model.init(
            jax.random.PRNGKey(0), jnp.ones((2, 16), jnp.int32),
            train=False,
        )
        return model, variables, 64


def _loss_trajectory(plan, model, variables, vocab, batches):
    import fluxmpi_tpu as fm
    from fluxmpi_tpu.parallel import TrainState, make_train_step
    from fluxmpi_tpu.parallel.train import replicate, shard_batch

    optimizer = optax.adam(1e-2)

    def loss_fn(p, mstate, batch):
        x, y = batch
        logits = model.apply(p, x, train=False)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), y
        ).mean()
        return loss, mstate

    with _fresh_runtime():
        mesh = fm.init(parallel=plan)
        assert fm.global_plan() is plan
        # state.params carries the full variables dict ({"params": ...})
        # — the same convention the sharding tests use, so model.apply
        # consumes it directly.
        state = TrainState.create(jax.device_get(variables), optimizer)
        if plan.shards_parameters:
            state, shardings = plan.shard_state(state)
            assert plan.state_sharding is shardings
        else:
            state = replicate(state, mesh)
        step = make_train_step(loss_fn, optimizer, parallel=plan,
                               donate=False)
        losses = []
        for batch in batches:
            state, loss = step(
                state, shard_batch(batch, mesh, spec=plan.batch_spec)
            )
            losses.append(
                np.asarray(jax.device_get(loss)).astype(np.float64)
            )
    return np.array(losses)


def test_gpt2_composed_plan_matches_dp_only(world):
    """The composition proof: one HF-imported GPT-2, one ParallelConfig,
    three layouts on the 8-way virtual mesh. dp vs dp×fsdp is
    bit-identical (ZeRO is pure layout — same math, same reduction
    tree); adding tp stays within float32 reduction-order ULPs (the
    partitioner splits the matmul accumulations, so exact bit equality
    is not defined for that leg)."""
    from fluxmpi_tpu import ParallelConfig

    model, variables, vocab = _gpt2_workload()
    rng = np.random.default_rng(0)
    batches = [
        (
            rng.integers(0, vocab, size=(8, 16)).astype(np.int32),
            rng.integers(0, vocab, size=(8, 16)).astype(np.int32),
        )
        for _ in range(4)
    ]

    dp_only = _loss_trajectory(
        ParallelConfig(dp=-1).resolve(), model, variables, vocab, batches
    )
    dp_fsdp = _loss_trajectory(
        ParallelConfig(dp=4, fsdp=2, fsdp_min_size=256).resolve(),
        model, variables, vocab, batches,
    )
    composed = _loss_trajectory(
        ParallelConfig(dp=2, fsdp=2, tp=2, fsdp_min_size=256).resolve(),
        model, variables, vocab, batches,
    )
    assert np.isfinite(dp_only).all()
    # ZeRO composition: bit-for-bit.
    assert np.array_equal(dp_only, dp_fsdp), (dp_only, dp_fsdp)
    # + tensor parallelism: same trajectory to reduction-order ULPs.
    np.testing.assert_allclose(dp_only, composed, rtol=0, atol=1e-5)


def test_train_loop_fused_window_under_plan(world):
    """train_loop(fuse="window") drives a plan-sharded step at one
    dispatch per window — the benchmark's ``dispatches_per_update``,
    held under the plan-derived (dp×fsdp) sharding."""
    import fluxmpi_tpu as fm
    from fluxmpi_tpu import ParallelConfig
    from fluxmpi_tpu.data import ArrayDataset, DistributedDataLoader
    from fluxmpi_tpu.models import MLP
    from fluxmpi_tpu.parallel import TrainState, make_train_step, train_loop

    window = 4
    with _fresh_runtime():
        plan = ParallelConfig(dp=4, fsdp=2, fsdp_min_size=64).resolve()
        mesh = fm.init(parallel=plan)
        model = MLP(features=(32, 32, 1))
        params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 2)))
        optimizer = optax.adam(1e-3)

        def loss_fn(p, mstate, batch):
            x, y = batch
            return jnp.mean((model.apply(p, x) - y) ** 2), mstate

        state, _ = plan.shard_state(TrainState.create(params, optimizer))
        step = make_train_step(loss_fn, optimizer, parallel=plan)

        gbs = 16
        rng = np.random.default_rng(0)
        x = rng.normal(size=(gbs * window, 2)).astype(np.float32)
        dataset = ArrayDataset((x, (x**2).sum(-1, keepdims=True)))
        loader = DistributedDataLoader(dataset, gbs, mesh=mesh)
        # The loader's default batch axis comes from the installed plan.
        assert loader.axis_name == ("dp", "fsdp")

        state, summary = train_loop(
            step, state, loader, epochs=2, fuse="window",
            flush_every=window, metrics=False,
        )
        assert summary["fused_window"] == window
        assert summary["updates"] == 2 * window
        assert summary["dispatches"] / summary["updates"] == 1.0 / window
        assert np.isfinite(summary["loss"])


# ---------------------------------------------------------------------------
# Manifest / restore composition
# ---------------------------------------------------------------------------


def test_manifest_records_plan_and_restore_parallel(world, tmp_path):
    import fluxmpi_tpu as fm
    from fluxmpi_tpu import ParallelConfig
    from fluxmpi_tpu.parallel import TrainState
    from fluxmpi_tpu.utils.checkpoint import (
        restore_checkpoint,
        save_checkpoint,
    )
    from fluxmpi_tpu.utils.manifest import read_manifest

    model = _tiny_lm()
    params = model.init(
        jax.random.PRNGKey(0), jnp.ones((2, 16), jnp.int32), train=False
    )
    optimizer = optax.adam(1e-2)
    path = str(tmp_path / "ckpt")

    with _fresh_runtime():
        plan = ParallelConfig(dp=2, fsdp=2, tp=2, fsdp_min_size=256).resolve()
        fm.init(parallel=plan)
        state, _ = plan.shard_state(TrainState.create(params, optimizer))
        save_checkpoint(path, state)
        manifest = read_manifest(path)
        assert manifest is not None
        assert manifest["parallel"] == {
            "axes": {"dp": 2, "fsdp": 2, "tp": 2},
            "axis_names": {"dp": "dp", "fsdp": "fsdp", "tp": "tp"},
        }

        # Restore THROUGH the plan: parallel= in place of (mesh=, rule=).
        host_like = jax.device_get(state)
        restored = restore_checkpoint(
            path, host_like, parallel=plan, allow_layout_change=True
        )
        blk = restored.params["params"]["encoder"]["block_0"]
        assert tuple(blk["ff1"]["kernel"].sharding.spec) == (None, "tp")
        with pytest.raises(ValueError, match="not both"):
            restore_checkpoint(
                path, host_like, parallel=plan, mesh=plan.mesh
            )

        # And elastically onto a DIFFERENT plan (dp-only: everything
        # replicated again).
        dp_plan = ParallelConfig(dp=-1).resolve()
        flat = restore_checkpoint(
            path, host_like, parallel=dp_plan, allow_layout_change=True
        )
        blk = flat.params["params"]["encoder"]["block_0"]
        assert all(s is None for s in tuple(blk["ff1"]["kernel"].sharding.spec))


# ---------------------------------------------------------------------------
# Axis-name resolution + observability board
# ---------------------------------------------------------------------------


def test_plan_axis_name_resolution(world):
    import fluxmpi_tpu as fm
    from fluxmpi_tpu import ParallelConfig, config
    from fluxmpi_tpu.parallel import plan_axis_name

    # No plan installed: preferences win.
    assert plan_axis_name("pp") == config.PP_AXIS_NAME
    with _fresh_runtime():
        plan = ParallelConfig(
            dp=2, pp=2, sp=2, axis_names={"pp": "stage"}
        ).resolve()
        fm.init(parallel=plan)
        assert plan_axis_name("pp") == "stage"
        assert plan_axis_name("sp") == "sp"
        # An axis the plan lacks falls back to the preference.
        assert plan_axis_name("tp") == config.TP_AXIS_NAME
        assert fm.dp_axis_name() == "dp"


def test_parallel_status_board(world):
    from fluxmpi_tpu import ParallelConfig
    from fluxmpi_tpu.parallel.plan import post_board
    from fluxmpi_tpu.telemetry import export as export_mod
    from fluxmpi_tpu.telemetry.export import Exporter
    from fluxmpi_tpu.telemetry.schema import validate_status_record

    plan = ParallelConfig(dp=4, fsdp=2, fsdp_min_size=64).resolve()
    plan.partition_specs({"w": jnp.ones((64, 64))})
    exporter = Exporter(port=0)
    prev = export_mod.set_exporter(exporter)
    try:
        post_board(plan)
        status = exporter.build_status()
        assert validate_status_record(status) == []
        board = status["parallel"]
        assert board["mesh"] == {"dp": 4, "fsdp": 2}
        assert board["data_parallel_size"] == 8
        assert board["rule_hits"].get("fsdp", 0) >= 1
    finally:
        export_mod.set_exporter(prev)

    # fluxmpi_top renders the board.
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "_fm_top",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", "fluxmpi_top.py"),
    )
    top = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(top)
    frame = top.render_frame({"host0": status}, {})
    assert "PARALLEL" in frame
    assert "dp:4" in frame and "fsdp:2" in frame
