"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
published widths of the models the repo supports, and checks what comes
out by the repo's own means. ONE process, no children (a chip belongs to
one process at a time).

    python chip_smoke.py                       # one chip, every default phase
    python chip_smoke.py --phase kernels       # only the named phase(s)
    python chip_smoke.py --chips 4             # only the four-chip phase

It refuses to run unless ``jax.devices()[0].platform == "tpu"``. Every
phase prints one JSON object (phase name, what was asserted, and its wall
and compile seconds — smoke timings of a cold or warm start, NOT
measurements); a summary object follows, and the LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as JAX reports it. On any other platform, or when a phase
fails, the last line carries ``"ok": false`` and the reason, the
traceback goes to stderr and the exit code is 1.

Default phases (one chip):
  device          what JAX sees, versions, memory, compile cache, native loader
  kernels         scripts/tpu_kernel_check.py's matrix, compiled, vs the oracle
  train_lm        GPT-2 small: loader -> make_train_step -> train_loop
  serve_lm        GPT-2 small in serving.InferenceEngine vs models.generate
  train_resnet50  ResNet-50 224x224, batch 128, loader-fed train_loop
``--chips 4`` runs only ``multichip``: GPT-2 small under dp=4 and fsdp=4
against the same batch and parameters on one of the four devices.

The phase functions take their model configuration as an argument;
``tests/test_chip_smoke.py`` drives them at tiny sizes on the CPU mesh.
This script itself has no small-size or CPU switch.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

_ROOT = os.path.dirname(os.path.abspath(__file__))

# GPT-2 small as published (the model fluxmpi_tpu/models/hf_gpt2.py
# imports): 12 layers, d_model 768, 12 heads (head_dim 64), d_ff 3072,
# vocabulary 50,257, context 1,024, tied head, LayerNorm eps 1e-5.
GPT2_SMALL = {
    "vocab_size": 50257, "max_len": 1024, "num_layers": 12,
    "d_model": 768, "num_heads": 12, "d_ff": 3072, "ln_eps": 1e-5,
}
# ResNet-50 on ImageNet shapes, the source paper's headline (BASELINE.md).
RESNET50 = {"model": "ResNet50", "image": 224, "classes": 1000, "batch": 128}

DEFAULT_PHASES = ("device", "kernels", "train_lm", "serve_lm",
                  "train_resnet50")

# Stated tolerances. Losses here are ~ln(vocab) = 10.8; bf16 keeps 8
# bits of mantissa, so two correct bf16 programs that order their sums
# differently agree to a few 1e-3 of the loss.
LOSS_TOL = 5e-2
# Serving: a greedy token that differs from generate() is accepted only
# as a bf16 near-tie — the reference's own logit for it lies within
# this of the reference's top logit (logits at random init have unit
# scale, so a wrong token misses by ~4, not ~0.1).
TIE_TOL = 1e-1


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def _check(cond: bool, what: str, asserted: list) -> None:
    """One named assertion of a phase: recorded when it holds, raised —
    never swallowed — when it does not (``assert`` would vanish under
    ``python -O``)."""
    if not cond:
        raise AssertionError(what)
    asserted.append(what)


def _monitor():
    from fluxmpi_tpu.telemetry.compileplane import get_compile_monitor

    mon = get_compile_monitor()
    if mon is None:
        raise RuntimeError("init(compileplane=True) installed no monitor")
    return mon


# ---------------------------------------------------------------------------
# Shared model plumbing
# ---------------------------------------------------------------------------


def _lm(cfg: dict, **overrides):
    """TransformerLM at ``cfg``: bf16 compute over f32 parameters."""
    import jax.numpy as jnp

    from fluxmpi_tpu.models import TransformerLM

    return TransformerLM(**{**cfg, "dtype": jnp.bfloat16, **overrides})


def _lm_params(cfg: dict, seed: int):
    """Random f32 weights from ``seed``, as host arrays. Initialised in
    ONE compiled program through the dense twin (the parameter tree does
    not depend on the attention switch)."""
    import jax
    import jax.numpy as jnp

    model = _lm(cfg)
    init = jax.jit(
        lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32), train=False)
    )
    return jax.device_get(init(jax.random.PRNGKey(seed)))


def _lm_losses(cfg: dict):
    """``(flash_loss, reference_loss)`` in make_train_step's signature:
    the path under test — flash attention plus the fused cross-entropy
    head — and the plain reference — dense attend, dense f32 head."""
    import jax.numpy as jnp
    import optax

    flash = _lm(cfg, attention="flash")
    naive = _lm(cfg, attention="naive")

    def flash_loss(params, mstate, batch):
        x, y = batch
        return flash.apply(params, x, train=True, targets=y).mean(), mstate

    def reference_loss(params, mstate, batch):
        x, y = batch
        logits = naive.apply(params, x, train=True)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), y
        ).mean(), mstate

    return flash_loss, reference_loss


def _token_dataset(cfg: dict, n_seq: int, seq: int, seed: int):
    """``n_seq`` seeded token rows as next-token (inputs, targets)."""
    import numpy as np

    from fluxmpi_tpu.data import ArrayDataset

    tokens = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], size=(n_seq, seq + 1), dtype=np.int32
    )
    return ArrayDataset((tokens[:, :-1], tokens[:, 1:]))


def _place_state(host_params, optimizer, model_state=None):
    """A fresh TrainState laid out as the installed plan declares
    (sharded and banked when the plan shards parameters, replicated over
    the global mesh otherwise)."""
    import fluxmpi_tpu as fm
    from fluxmpi_tpu.parallel import TrainState
    from fluxmpi_tpu.parallel.train import replicate

    state = TrainState.create(host_params, optimizer, model_state)
    plan = fm.global_plan()
    if plan is not None and plan.shards_parameters:
        return plan.shard_state(state)[0]
    return replicate(state)


def _loop_program_text(step, summary, state, batch) -> str:
    """Optimised HLO of the program train_loop dispatched: the cached
    fused-window executable(s) when windows engaged, else the step."""
    if summary["fused_window"]:
        hot = getattr(step, "__fluxmpi_compiled__", step)
        return "\n".join(
            prog.as_text() for prog in hot.__fluxmpi_window_cache__.values()
        )
    return step.lower(state, batch).compile().as_text()


def _run_loop(step, state, loader, *, epochs=None, steps=None, window):
    """train_loop with its defaults (``fuse="auto"``, device gather
    "auto") and a hook collecting the per-flush records. Returns
    ``(state, summary, records)`` and asserts nothing itself."""
    from fluxmpi_tpu.parallel import train_loop

    records: list[dict] = []
    state, summary = train_loop(
        step, state, loader, epochs=epochs, steps=steps,
        flush_every=window, metrics=records.append,
    )
    return state, summary, records


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_device(devices=None) -> dict:
    """Bring-up: ``fm.init`` with the compile monitor, and what the
    machine is (main() has already switched the compile cache on,
    through the one runtime function)."""
    import importlib.metadata as md

    import jax

    import fluxmpi_tpu as fm
    from fluxmpi_tpu.io.native import native_available

    mesh = fm.init(devices=devices, compileplane=True)
    dev = mesh.devices.flat[0]
    stats = dev.memory_stats() or {}
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "mesh": {str(k): int(v) for k, v in mesh.shape.items()},
        "versions": {
            name: md.version(name)
            for name in ("jax", "jaxlib", "libtpu", "flax", "optax")
        },
        "bytes_limit": stats.get("bytes_limit"),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "native_loader": native_available(),
    }


def phase_kernels(*, interpret: bool = False, quick: bool = False) -> dict:
    """The flash kernels against the dense oracle — the matrix of
    scripts/tpu_kernel_check.py, ``interpret=`` passed explicitly and
    (compiled) ``tpu_custom_call`` asserted in every case's program."""
    scripts = os.path.join(_ROOT, "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    import tpu_kernel_check

    asserted: list[str] = []
    records = tpu_kernel_check.run_matrix(interpret=interpret, quick=quick)
    for rec in records:
        _check(rec["ok"], f"{rec['case']}: within tolerance of the oracle"
               + ("" if interpret else ", kernels in the lowered program"),
               asserted)
    return {"cases": len(records), "interpret": interpret,
            "asserted": asserted}


def phase_train_lm(cfg: dict, *, seed: int, batch: int = 8,
                   batches_per_epoch: int = 2, epochs: int = 4,
                   window: int = 2, lr: float = 1e-3,
                   compiled: bool = True) -> dict:
    """The LM trainer: seeded token rows -> DistributedDataContainer +
    DistributedDataLoader -> make_train_step -> train_loop(defaults)."""
    import numpy as np
    import optax

    import fluxmpi_tpu as fm
    from fluxmpi_tpu.data import (
        DistributedDataContainer,
        DistributedDataLoader,
    )
    from fluxmpi_tpu.parallel import make_eval_step, make_train_step
    from fluxmpi_tpu.parallel.train import shard_batch

    fm.init(compileplane=True)
    mon = _monitor()
    asserted: list[str] = []
    seq = cfg["max_len"]
    flash_loss, reference_loss = _lm_losses(cfg)
    optimizer = optax.adamw(lr)
    dataset = _token_dataset(cfg, batch * batches_per_epoch, seq, seed)
    loader = DistributedDataLoader(DistributedDataContainer(dataset), batch)
    state = _place_state(_lm_params(cfg, seed), optimizer)

    # The reference for step 1: the same parameters and first batch
    # through the dense attend and the dense f32 head, on this device.
    first_batch = shard_batch(
        tuple(a[:batch] for a in dataset.arrays)
    )
    ref_first = float(
        make_eval_step(lambda p, ms, b: reference_loss(p, ms, b)[0])(
            state, first_batch
        )
    )

    step = make_train_step(flash_loss, optimizer)
    state, summary, records = _run_loop(
        step, state, loader, epochs=epochs, window=window
    )
    want = epochs * batches_per_epoch
    _check(summary["updates"] == want, f"summary['updates'] == {want}",
           asserted)
    _check(len(records) >= 2, "at least two flush windows", asserted)

    # With width-2 windows the first window's sum and last give step 1.
    if summary["fused_window"] == 2:
        first = 2 * records[0]["loss_window_mean"] - records[0]["loss"]
    else:
        first = records[0]["loss"]
    losses = [first] + [r["loss"] for r in records]
    _check(bool(np.all(np.isfinite(losses))), "every loss finite", asserted)
    _check(abs(first - ref_first) <= LOSS_TOL,
           f"|first loss - naive/f32-head reference| <= {LOSS_TOL}",
           asserted)
    _check(losses[-1] < first, "loss fell over the repeated dataset",
           asserted)
    _check(mon.retraces == [], "zero compiles after warm-up", asserted)

    kernels = _loop_program_text(step, summary, state, first_batch).count(
        "tpu_custom_call"
    )
    if compiled:
        need = 3 * cfg["num_layers"]
        _check(kernels >= need,
               f"program holds fwd+dq+dkv flash kernels for every layer "
               f"(>= {need} tpu_custom_call)", asserted)
    return {
        "model": cfg, "batch": [batch, seq], "updates": summary["updates"],
        "fused_window": summary["fused_window"],
        "device_gather": loader.fusible(),
        "dispatches": summary["dispatches"],
        "tpu_custom_calls": kernels,
        "first_loss": first, "reference_first_loss": ref_first,
        "last_loss": losses[-1],
        "dropout": 0.0,
        "note": "dropout 0.1 (GPT-2's published rate) under "
                "attention='flash' trains through the DENSE attend today: "
                "models/transformer.py passes no dropout_impl and "
                "flash_attention_fn defaults to 'dense'",
        "asserted": asserted,
    }


def phase_serve_lm(cfg: dict, *, seed: int,
                   prompt_lengths=(32, 100, 250, 505, 32, 100, 250, 505),
                   new_tokens: int = 64, late: int = 3,
                   head_start: int = 8, compiled: bool = True) -> dict:
    """The LM server: the same model in serving.InferenceEngine with
    ``attention="flash"``; ``late`` of the requests join while the
    others decode. Greedy streams are held to models.generate."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import fluxmpi_tpu as fm
    from fluxmpi_tpu.models.generate import generate
    from fluxmpi_tpu.serving import InferenceEngine

    fm.init(compileplane=True)
    mon = _monitor()
    asserted: list[str] = []
    model = _lm(cfg)
    params = jax.device_put(_lm_params(cfg, seed))
    rng = np.random.default_rng(seed + 1)
    prompts = [
        rng.integers(0, cfg["vocab_size"], size=(n,), dtype=np.int32)
        for n in prompt_lengths
    ]

    engine = InferenceEngine(model, params, attention="flash")
    try:
        engine.warmup(prompt_lengths=tuple(prompt_lengths))
        mon.observe_flush()  # the warm-up boundary
        n_first = len(prompts) - late
        requests = [engine.submit(p, new_tokens) for p in prompts[:n_first]]
        for _ in range(head_start):
            engine.step()
        requests += [engine.submit(p, new_tokens) for p in prompts[n_first:]]
        summary = engine.run()
        joined = mon.observe_flush()
        decode_programs = engine._decode_step._cache_size()
        kernels = _engine_kernel_counts(engine)
    finally:
        engine.close()
    _check(summary["completed"] == len(prompts)
           and all(r.status == "finished" for r in requests),
           f"all {len(prompts)} requests finished", asserted)
    _check(all(len(r.tokens) == new_tokens for r in requests),
           f"{new_tokens} new tokens each", asserted)
    _check(joined["events"] == 0 and decode_programs == 1,
           "no compile or retrace on the mid-flight joins", asserted)
    if compiled:
        _check(min(kernels.values()) >= 1,
               "decode and every prefill program lower to the compiled "
               "flash kernel (tpu_custom_call present)", asserted)

    # The reference: generate() per distinct prompt length (one compiled
    # program each), same-length prompts batched.
    by_len: dict[int, list[int]] = {}
    for i, p in enumerate(prompts):
        by_len.setdefault(len(p), []).append(i)
    gen = jax.jit(lambda prm, batch: generate(model, prm, batch, new_tokens))
    exact, ties = 0, []
    for plen, idxs in sorted(by_len.items()):
        ref = np.asarray(gen(params, jnp.asarray(
            np.stack([prompts[i] for i in idxs])
        )))[:, plen:]
        for row, i in zip(ref, idxs):
            got = np.asarray(requests[i].tokens, np.int32)
            if np.array_equal(got, row):
                exact += 1
                continue
            ties.append(_tie_margin(model, params, prompts[i], got))
    worst = max(ties, default=0.0)
    _check(worst <= TIE_TOL,
           "greedy tokens equal models.generate"
           + (f", or differ only at reference near-ties (<= {TIE_TOL})"
              if ties else ""), asserted)
    return {
        "model": cfg, "requests": len(prompts),
        "prompt_lengths": list(prompt_lengths), "new_tokens": new_tokens,
        "late_joins": late, "decode_steps": summary["decode_steps"],
        "tpu_custom_calls": kernels,
        "tokens": summary["tokens"],
        "exact_vs_generate": exact, "near_tie_requests": len(ties),
        "worst_tie_margin": worst, "asserted": asserted,
    }


def _engine_kernel_counts(engine) -> dict:
    """``tpu_custom_call`` sites in the engine's decode program and in
    each prefill bucket it compiled (lowered again from warm-up-shaped
    arguments; nothing runs). Lowered text holds one site for all the
    layers: they call one shared function until XLA inlines it."""
    import jax.numpy as jnp

    def zeros(*shape):
        return jnp.zeros(shape, jnp.int32)

    slots, mb = engine.slots, engine.max_blocks_per_seq
    pools = (engine.params, engine.cache.k_pools, engine.cache.v_pools)
    programs = {"decode": engine._decode_step.lower(
        *pools, (zeros(slots, mb),), zeros(slots), zeros(slots),
        zeros(slots), jnp.zeros((slots,), bool),
    )}
    for bucket, fn in engine._prefill_steps.items():
        programs[f"prefill_{bucket}"] = fn.lower(
            *pools, zeros(bucket), jnp.int32(1), (zeros(mb),)
        )
    return {name: low.as_text().count("tpu_custom_call")
            for name, low in programs.items()}


def _tie_margin(model, params, prompt, tokens) -> float:
    """How far the served ``tokens`` ever are from greedy under the
    reference itself: teacher-force prompt+tokens through the dense
    model and return the largest (top logit - served token's logit)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    full = np.concatenate([prompt, tokens])[None]
    logits = jax.jit(lambda prm, t: model.apply(prm, t, train=False))(
        params, jnp.asarray(full)
    )[0, len(prompt) - 1:-1].astype(jnp.float32)
    served = jnp.take_along_axis(logits, jnp.asarray(tokens)[:, None], axis=1)
    return float(jnp.max(jnp.max(logits, axis=1) - served[:, 0]))


def phase_train_resnet(cfg: dict, *, seed: int, batches_per_epoch: int = 2,
                       epochs: int = 4, window: int = 2,
                       lr: float = 0.05) -> dict:
    """The conv trainer: seeded images -> loader -> make_train_step ->
    train_loop(defaults), SGD+momentum, bf16 compute, BatchNorm state."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import fluxmpi_tpu as fm
    from fluxmpi_tpu import models
    from fluxmpi_tpu.data import (
        ArrayDataset,
        DistributedDataContainer,
        DistributedDataLoader,
    )
    from fluxmpi_tpu.parallel import make_train_step

    fm.init(compileplane=True)
    mon = _monitor()
    asserted: list[str] = []
    side, classes, batch = cfg["image"], cfg["classes"], cfg["batch"]
    model = getattr(models, cfg["model"])(
        num_classes=classes, dtype=jnp.bfloat16
    )
    variables = jax.device_get(jax.jit(
        lambda key: model.init(
            key, jnp.zeros((2, side, side, 3), jnp.float32), train=False
        )
    )(jax.random.PRNGKey(seed)))
    stats0 = variables["batch_stats"]

    def loss_fn(params, batch_stats, data):
        x, y = data
        logits, new = model.apply(
            {"params": params, "batch_stats": batch_stats}, x, train=True,
            mutable=["batch_stats"],
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), y
        ).mean()
        return loss, new["batch_stats"]

    rng = np.random.default_rng(seed)
    n = batch * batches_per_epoch
    dataset = ArrayDataset((
        rng.standard_normal((n, side, side, 3), dtype=np.float32),
        rng.integers(0, classes, size=(n,), dtype=np.int32),
    ))
    loader = DistributedDataLoader(DistributedDataContainer(dataset), batch)
    optimizer = optax.sgd(lr, momentum=0.9)
    state = _place_state(variables["params"], optimizer, stats0)
    step = make_train_step(loss_fn, optimizer)
    state, summary, records = _run_loop(
        step, state, loader, epochs=epochs, window=window
    )
    want = epochs * batches_per_epoch
    _check(summary["updates"] == want, f"summary['updates'] == {want}",
           asserted)
    losses = [r["loss"] for r in records]
    _check(bool(np.all(np.isfinite(losses))), "every loss finite", asserted)
    start = records[0].get("loss_window_max", losses[0])
    _check(losses[-1] < start, "loss fell over the repeated dataset",
           asserted)
    moved = jax.tree_util.tree_map(
        lambda a, b: bool(np.any(np.asarray(a) != np.asarray(b))),
        stats0, jax.device_get(state.model_state),
    )
    _check(all(jax.tree_util.tree_leaves(moved)),
           "every batch_stats leaf changed", asserted)
    _check(mon.retraces == [], "zero compiles after warm-up", asserted)
    return {
        "model": cfg, "updates": summary["updates"],
        "fused_window": summary["fused_window"],
        "device_gather": loader.fusible(),
        "first_window_max_loss": start,
        "last_loss": losses[-1], "asserted": asserted,
    }


def _check_layout(name, plan, state, probe, batch, asserted) -> int:
    """Every state leaf over all the plan's devices and laid out as the
    plan declares, and the ``probe`` batch split on its leading axis.
    Returns how many state leaves are partitioned."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    n = plan.mesh.size
    leaves = jax.tree_util.tree_leaves(state)
    if plan.shards_parameters:
        want = jax.tree_util.tree_leaves(plan.state_sharding)
    else:
        want = [NamedSharding(plan.mesh, PartitionSpec())] * len(leaves)
    _check(all(len(x.sharding.device_set) == n for x in leaves),
           f"{name}: every state leaf spans {n} distinct devices", asserted)
    _check(all(x.sharding.is_equivalent_to(w, x.ndim)
               for x, w in zip(leaves, want)),
           f"{name}: every state leaf has the plan's sharding", asserted)
    split = sum(
        x.addressable_shards[0].data.shape != x.shape for x in leaves
    )
    if plan.shards_parameters:
        _check(split > 0, f"{name}: parameter/optimizer leaves are "
               f"partitioned, not replicated", asserted)
    else:
        _check(split == 0, f"{name}: state replicated", asserted)
    _check(all(len(b.sharding.device_set) == n
               and b.sharding.spec == plan.batch_spec
               and b.addressable_shards[0].data.shape[0] == batch // n
               for b in probe),
           f"{name}: batch laid out as plan.batch_spec, "
           f"{batch // n} rows per device", asserted)
    return int(split)


def _check_eager_collectives(name, n, sample, asserted) -> None:
    """One eager ``fm.allreduce`` and ``fm.synchronize`` over the
    installed mesh of ``n`` workers."""
    import jax
    import numpy as np

    import fluxmpi_tpu as fm

    x = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
    _check(np.array_equal(np.asarray(fm.allreduce(x)),
                          np.tile(x.sum(0), (n, 1))),
           f"{name}: eager fm.allreduce returns the sum on every worker",
           asserted)
    synced = fm.synchronize(jax.device_put({"w": sample}))["w"]
    _check(np.array_equal(np.asarray(synced), sample)
           and len(synced.sharding.device_set) == n,
           f"{name}: fm.synchronize returns the root's values on all {n} "
           f"devices", asserted)


def phase_multichip(cfg: dict, *, devices, seed: int, batch: int = 8,
                    steps: int = 3, lr: float = 1e-3,
                    compiled: bool = True) -> dict:
    """The data-parallel framework across ``devices``: the LM under
    ``ParallelConfig(dp=n)`` and ``ParallelConfig(fsdp=n)`` through
    init -> loader -> make_train_step -> train_loop, against the same
    global batch and parameters through the same factories on
    ``devices[:1]`` (``runtime.shutdown()`` and a fresh ``init`` between
    layouts — the chips belong to this one process)."""
    import numpy as np
    import optax

    import fluxmpi_tpu as fm
    from fluxmpi_tpu import ParallelConfig, runtime
    from fluxmpi_tpu.data import (
        DistributedDataContainer,
        DistributedDataLoader,
    )
    from fluxmpi_tpu.parallel import make_train_step

    n = len(devices)
    asserted: list[str] = []
    seq = cfg["max_len"]
    flash_loss, _ = _lm_losses(cfg)
    optimizer = optax.adamw(lr)
    dataset = _token_dataset(cfg, batch * steps, seq, seed)
    host_params = _lm_params(cfg, seed)
    layouts = (
        ("one_device", devices[:1], ParallelConfig(dp=1), ()),
        (f"dp{n}", devices, ParallelConfig(dp=n), ("all-reduce",)),
        (f"fsdp{n}", devices, ParallelConfig(fsdp=n),
         ("reduce-scatter", "all-gather")),
    )
    out: dict = {}
    for name, devs, config, collectives in layouts:
        runtime.shutdown()
        fm.init(devices=devs, parallel=config, compileplane=True)
        plan = fm.global_plan()
        state = _place_state(host_params, optimizer)
        step = make_train_step(flash_loss, optimizer, parallel=plan)

        def loader():
            return DistributedDataLoader(
                DistributedDataContainer(dataset), batch
            )

        state, summary, records = _run_loop(
            step, state, loader(), steps=steps, window=1
        )
        losses = [r["loss"] for r in records]
        _check(summary["updates"] == steps and len(losses) == steps,
               f"{name}: {steps} updates, one loss each", asserted)
        probe = next(iter(loader()))  # one batch, as the loader lays it out
        text = _loop_program_text(step, summary, state, probe)
        out[name] = {
            "smoke_compile_s": round(_monitor().compile_seconds(), 2),
            "losses": losses, "fused_window": summary["fused_window"],
            "mesh": {str(k): int(v) for k, v in plan.mesh.shape.items()},
            "tpu_custom_calls": text.count("tpu_custom_call"),
        }
        if len(devs) == 1:
            continue
        out[name]["partitioned_leaves"] = _check_layout(
            name, plan, state, probe, batch, asserted
        )
        _check(any(c in text for c in collectives),
               f"{name}: compiled program contains "
               f"{' or '.join(collectives)}", asserted)
        if compiled:
            _check(out[name]["tpu_custom_calls"] >= 3 * cfg["num_layers"],
                   f"{name}: flash kernels in the partitioned program",
                   asserted)
        worst = float(np.max(np.abs(
            np.subtract(losses, out["one_device"]["losses"])
        )))
        _check(worst <= LOSS_TOL,
               f"{name}: per-step losses within {LOSS_TOL} of one device",
               asserted)
        out[name]["max_loss_diff"] = worst
        if not plan.shards_parameters:
            _check_eager_collectives(
                name, n, host_params["params"]["pos_embed"], asserted
            )
    runtime.shutdown()
    return {
        "model": cfg, "batch": [batch, seq], "steps": steps,
        "reference": "the same factories after runtime.shutdown() and "
                     "init(devices=devices[:1])",
        "layouts": out, "asserted": asserted,
    }


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def _device_report() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--phase", action="append",
                        choices=DEFAULT_PHASES, default=None)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    if args.chips == 4 and args.phase:
        parser.error("--chips 4 runs the multichip phase only")

    device = _device_report()
    if device["platform"] != "tpu":
        _emit({"ok": False, "reason": "no TPU: jax.devices()[0].platform "
               f"== {device['platform']!r}", "device": device})
        return 1
    if device["count"] < args.chips:
        _emit({"ok": False, "reason": f"--chips {args.chips} needs "
               f"{args.chips} devices", "device": device})
        return 1

    import jax

    try:
        from fluxmpi_tpu.runtime import enable_compile_cache
        from fluxmpi_tpu.telemetry.compileplane import get_compile_monitor
    except ImportError as exc:  # the script alone is not the system
        _emit({"ok": False, "reason": f"fluxmpi_tpu is not importable from "
               f"{_ROOT}: {exc}", "device": device})
        return 1
    enable_compile_cache()
    devices = jax.devices()[: args.chips]
    seed = args.seed
    phases = {
        "device": lambda: phase_device(devices),
        "kernels": phase_kernels,
        "train_lm": lambda: phase_train_lm(GPT2_SMALL, seed=seed),
        "serve_lm": lambda: phase_serve_lm(GPT2_SMALL, seed=seed),
        "train_resnet50": lambda: phase_train_resnet(RESNET50, seed=seed),
        "multichip": lambda: phase_multichip(
            GPT2_SMALL, devices=devices, seed=seed
        ),
    }
    if args.chips == 4:
        names = ["multichip"]
    else:
        names = list(args.phase or DEFAULT_PHASES)
        if "device" not in names:
            names.insert(0, "device")  # bring-up comes first, always

    t_run = time.perf_counter()
    done: list[str] = []
    for name in names:
        mon0 = get_compile_monitor()
        c0 = mon0.compile_seconds() if mon0 is not None else 0.0
        t0 = time.perf_counter()
        try:
            record = phases[name]()
        except Exception as exc:  # the run's one boundary: report, exit 1
            traceback.print_exc()
            _emit({"ok": False, "reason": f"phase {name} failed: "
                   f"{type(exc).__name__}: {exc}"[:2000], "device": device})
            return 1
        timing = {"smoke_wall_s": round(time.perf_counter() - t0, 2)}
        mon1 = get_compile_monitor()
        if mon1 is not None:  # multichip reports per layout instead
            timing["smoke_compile_s"] = round(
                mon1.compile_seconds() - (c0 if mon1 is mon0 else 0.0), 2
            )
        _emit({"phase": name, "ok": True, **timing, **record})
        done.append(name)
    _emit({
        "phase": "summary", "phases": done, "seed": seed,
        "chips": args.chips,
        "smoke_wall_s": round(time.perf_counter() - t_run, 2),
        "timings_are": "smoke timings of one start, not measurements",
        "claim": None,
    })
    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
