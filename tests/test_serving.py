"""Serving plane tests: the paged KV cache's free-list round trip,
token-budget admission control, continuous batching's bit-identity with
``generate()``, zero-retrace mid-flight joins, streaming delivery,
preemption draining under load, the ``serving.admit``/``serving.decode``
fault sites, and the telemetry/status wiring."""

import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import fluxmpi_tpu as fm
from fluxmpi_tpu import faults, runtime, serving
from fluxmpi_tpu.errors import FaultInjectedError, RequestRejectedError
from fluxmpi_tpu.models import Keeps, TransformerLM
from fluxmpi_tpu.models.generate import generate
from fluxmpi_tpu.serving import BlockKVCache, InferenceEngine, blocks_for_tokens
from fluxmpi_tpu.serving import observe
from fluxmpi_tpu.telemetry import Exporter, export, get_registry
from fluxmpi_tpu.telemetry import compileplane, tracing
from fluxmpi_tpu.telemetry.anomaly import AnomalyDetector, set_anomaly_detector
from fluxmpi_tpu.telemetry.schema import (
    KNOWN_METRIC_NAMES,
    validate_metric,
    validate_record,
    validate_status_record,
)


@pytest.fixture(scope="module")
def model(world):
    lm = TransformerLM(vocab_size=32, max_len=64, num_layers=2, d_model=32,
                       num_heads=4, d_ff=64)
    variables = lm.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), train=False
    )
    return lm, variables


@pytest.fixture()
def engine_factory(model):
    lm, variables = model
    built = []

    def make(**kwargs):
        kwargs.setdefault("slots", 2)
        kwargs.setdefault("block_size", 8)
        eng = InferenceEngine(lm, variables, **kwargs)
        built.append(eng)
        return eng

    yield make
    for eng in built:
        eng.close()
    serving.shutdown()
    observe.shutdown()
    runtime.clear_preemption()
    get_registry().reset()


def _prompt(rng, n):
    return rng.integers(0, 32, size=(n,)).astype(np.int32)


# ---------------------------------------------------------------------------
# Block cache / free-list allocator
# ---------------------------------------------------------------------------


def test_free_list_round_trip():
    cache = BlockKVCache([Keeps("full", 4, 8)] * 2,
                         num_blocks=9, block_size=16, max_blocks_per_seq=4)
    assert cache.free_blocks == 8  # block 0 is the reserved trash block
    assert cache.capacity_tokens == 8 * 16
    a = cache.alloc(40)  # 3 blocks
    assert len(a) == 3 and 0 not in a
    b = cache.alloc(16)
    assert cache.used_blocks == 4
    cache.free(a)
    assert cache.free_blocks == 7
    # Freed blocks are reused (LIFO — the most recently freed first).
    c = cache.alloc(48)
    assert set(c) <= set(a) | set(range(1, 9))
    assert set(a) & set(c), "freed blocks must be handed out again"
    cache.free(b)
    cache.free(c)
    assert cache.free_blocks == 8


def test_allocator_rejects_bad_frees_and_exhaustion():
    cache = BlockKVCache([Keeps("full", 1, 4)],
                         num_blocks=4, block_size=8, max_blocks_per_seq=3)
    blocks = cache.alloc(24)  # all 3
    assert not cache.can_alloc(1)
    with pytest.raises(RuntimeError, match="exhausted"):
        cache.alloc(8)
    with pytest.raises(ValueError, match="outside the pool"):
        cache.free([0])
    cache.free(blocks)
    with pytest.raises(ValueError, match="double free"):
        cache.free([blocks[0]])


def test_blocks_for_tokens_math():
    assert blocks_for_tokens(1, 16) == 1
    assert blocks_for_tokens(16, 16) == 1
    assert blocks_for_tokens(17, 16) == 2


def test_table_row_pads_with_trash():
    cache = BlockKVCache([Keeps("full", 1, 4)],
                         num_blocks=8, block_size=8, max_blocks_per_seq=5)
    row = cache.table_row([3, 1])
    assert row.tolist() == [3, 1, 0, 0, 0]


def test_memory_plane_admission_check(model, monkeypatch):
    """The OOM-safe construction check: a pool that cannot fit the
    device's remaining HBM refuses at engine build (PR 9 memory plane),
    never at the first admission."""
    from fluxmpi_tpu.telemetry import memory as memory_mod

    lm, variables = model
    monkeypatch.setattr(
        memory_mod, "device_memory_stats",
        lambda d: {"bytes_limit": 1024.0, "bytes_in_use": 0.0},
    )
    with pytest.raises(RuntimeError, match="device memory"):
        InferenceEngine(lm, variables, slots=2, block_size=8)
    serving.shutdown()
    # Stat-less backends (CPU) have nothing to check against: fine.
    monkeypatch.setattr(memory_mod, "device_memory_stats", lambda d: {})
    eng = InferenceEngine(lm, variables, slots=2, block_size=8)
    eng.close()


# ---------------------------------------------------------------------------
# Correctness: engine output == generate()
# ---------------------------------------------------------------------------


def test_greedy_streams_bit_identical_to_generate(model, engine_factory):
    """The serving correctness proof: for a mixed-length batch of
    requests flowing through admission -> batched prefill -> continuous
    decode -> eviction, every streamed greedy continuation is
    bit-identical to ``generate()`` on the same prompt."""
    lm, variables = model
    eng = engine_factory(slots=3)
    eng.warmup(prompt_lengths=(3, 9, 16))
    rng = np.random.default_rng(7)
    cases = [(5, 8, None), (9, 4, None), (3, 12, None), (16, 6, None),
             (6, 20, 3), (4, 1, None)]
    reqs = [
        (eng.submit(_prompt(rng, plen), mnew, eos_token=eos), mnew, eos)
        for plen, mnew, eos in cases
    ]
    summary = eng.run()
    assert summary["completed"] == len(cases)
    for (req, mnew, eos) in reqs:
        ref = np.asarray(
            generate(lm, variables, jnp.asarray(req.prompt[None]), mnew,
                     eos_token=eos)
        )[0][len(req.prompt):]
        if eos is not None:
            hits = np.where(ref == eos)[0]
            if len(hits):
                ref = ref[: hits[0] + 1]  # engine stops AT eos
        np.testing.assert_array_equal(
            np.asarray(req.tokens, np.int32), ref
        )
    # Eviction returned every block: the pool is whole again.
    assert eng.cache.free_blocks == eng.cache.num_blocks - 1


def test_midflight_join_zero_retrace(model, engine_factory):
    """A request admitted mid-flight joins the decode batch without
    recompiling the decode step: the compile monitor sees ZERO compile
    events after the warmup boundary, and the decode jit's cache holds
    exactly one entry."""
    lm, variables = model
    mon = compileplane.CompileMonitor()
    compileplane.set_compile_monitor(mon)
    try:
        eng = engine_factory(slots=2)
        eng.warmup(prompt_lengths=(5, 9, 16))
        mon.observe_flush()  # warmup boundary
        rng = np.random.default_rng(1)
        eng.submit(_prompt(rng, 9), 20)
        for _ in range(3):
            eng.step()
        late = eng.submit(_prompt(rng, 5), 8)   # joins mid-flight
        later = eng.submit(_prompt(rng, 12), 6)  # different length, same buckets
        summary = eng.run()
        assert summary["completed"] == 3
        info = mon.observe_flush()
        assert info["events"] == 0, f"steady-state compiles: {info}"
        assert mon.retraces == []
        assert eng._decode_step._cache_size() == 1
        ref = np.asarray(
            generate(lm, variables, jnp.asarray(late.prompt[None]), 8)
        )[0][5:]
        np.testing.assert_array_equal(np.asarray(late.tokens, np.int32), ref)
        assert later.status == "finished"
    finally:
        compileplane.set_compile_monitor(None)


def test_flash_decode_bit_identical_with_midflight_join(model, engine_factory):
    """Kernel plane (ISSUE 19): ``attention="flash"`` routes every
    decode attend through the Pallas kernel (interpret mode on CPU),
    reading K/V gathered through the paged block table. The greedy
    token streams must stay bit-identical to ``generate()`` on the
    naive path, and a mid-flight join must still cost zero steady-state
    retraces — the kernel swap must not perturb the PR 13 contract."""
    lm, variables = model
    mon = compileplane.CompileMonitor()
    compileplane.set_compile_monitor(mon)
    try:
        eng = engine_factory(slots=2, attention="flash")
        assert eng.attention == "flash"
        eng.warmup(prompt_lengths=(5, 9))
        mon.observe_flush()  # warmup boundary
        rng = np.random.default_rng(3)
        first = eng.submit(_prompt(rng, 9), 10)
        for _ in range(3):
            eng.step()
        late = eng.submit(_prompt(rng, 5), 8)  # joins mid-flight
        summary = eng.run()
        assert summary["completed"] == 2
        info = mon.observe_flush()
        assert info["events"] == 0, f"steady-state compiles: {info}"
        assert mon.retraces == []
        assert eng._decode_step._cache_size() == 1
        for req, mnew in ((first, 10), (late, 8)):
            ref = np.asarray(
                generate(lm, variables, jnp.asarray(req.prompt[None]), mnew)
            )[0][len(req.prompt):]
            np.testing.assert_array_equal(
                np.asarray(req.tokens, np.int32), ref
            )
    finally:
        compileplane.set_compile_monitor(None)


def test_flash_decode_masks_trash_block_garbage(model, engine_factory):
    """The paged decode kernel reads the pool through the block tables:
    a table's unused entries point at the trash block and a request's
    last block holds stale rows past its length — neither may
    contaminate the output. Poison the reserved trash block (block 0)
    with large finite garbage (stale K/V is what it really holds after
    warmup); greedy streams must stay bit-identical to ``generate()``,
    which never sees a paged pool at all. (The kernel-level variant,
    garbage in every block no table names and past every length:
    test_paged_attention.py.)"""
    lm, variables = model
    eng = engine_factory(slots=2, attention="flash")
    eng.warmup(prompt_lengths=(4, 6))
    (k_pool,), (v_pool,) = eng.cache.k_pools, eng.cache.v_pools
    poison = jnp.full_like(k_pool[:, 0], 1e6)
    eng.cache.k_pools = (k_pool.at[:, 0].set(poison),)
    eng.cache.v_pools = (v_pool.at[:, 0].set(poison),)
    rng = np.random.default_rng(11)
    # plen + max_new <= 2 blocks each: most of every gathered row is
    # trash-block garbage.
    reqs = [(eng.submit(_prompt(rng, plen), mnew), plen, mnew)
            for plen, mnew in ((4, 6), (6, 4), (5, 8))]
    summary = eng.run()
    assert summary["completed"] == len(reqs)
    for req, plen, mnew in reqs:
        toks = np.asarray(req.tokens, np.int32)
        assert np.all(toks >= 0) and np.all(toks < 32)
        ref = np.asarray(
            generate(lm, variables, jnp.asarray(req.prompt[None]), mnew)
        )[0][plen:]
        np.testing.assert_array_equal(toks, ref)


# ---------------------------------------------------------------------------
# One decode tick in flight: tick N+1 is dispatched from the tokens on the
# device, tick N fetched and delivered while it runs
# ---------------------------------------------------------------------------


def _reference(lm, variables, req, eos=None):
    ref = np.asarray(generate(
        lm, variables, jnp.asarray(req.prompt[None]), req.max_new_tokens,
        eos_token=eos,
    ))[0][len(req.prompt):]
    if eos is not None and (ref == eos).any():
        ref = ref[: int(np.argmax(ref == eos)) + 1]  # the engine stops AT eos
    return ref


def _span_args(tracer, name):
    return [e["args"] for e in tracer.export()["traceEvents"]
            if e.get("name") == name]


def test_one_tick_in_flight_staggered_joins_restart_and_count_eviction(
    model, engine_factory
):
    """Served tokens equal ``generate()``'s, token for token, through
    admissions staggered mid-flight, evictions by count while others go
    on, and a restart from an empty engine; the decode program compiles
    once though nothing warmed it; the dispatch span says how often a
    tick was in flight behind it."""
    lm, variables = model
    mon = compileplane.CompileMonitor()
    compileplane.set_compile_monitor(mon)
    tracer = tracing.Tracer(enabled=True)
    previous = tracing.set_tracer(tracer)
    try:
        eng = engine_factory(slots=3)
        rng = np.random.default_rng(5)
        reqs = [eng.submit(_prompt(rng, 9), 14)]
        for plen, mnew in ((5, 3), (12, 9), (4, 2), (7, 6)):
            for _ in range(2):
                eng.step()
            assert eng._in_flight is not None  # hand-stepped: one tick lags
            reqs.append(eng.submit(_prompt(rng, plen), mnew))
        summary = eng.run()
        assert summary["completed"] == 5 and eng._in_flight is None
        mon.observe_flush()
        # Idle -> busy: the first tick after an empty engine feeds the
        # host's tokens, and nothing recompiles.
        assert eng.active_count == 0 and not eng.step()
        reqs += [eng.submit(_prompt(rng, 6), 8), eng.submit(_prompt(rng, 3), 5)]
        eng.run()
        assert mon.observe_flush()["events"] == 0 and mon.retraces == []
        assert eng._decode_step._cache_size() == 1
    finally:
        tracing.set_tracer(previous)
        compileplane.set_compile_monitor(None)
    for req in reqs:
        assert req.status == "finished"
        np.testing.assert_array_equal(
            np.asarray(req.tokens, np.int32), _reference(lm, variables, req)
        )
    assert eng.cache.free_blocks == eng.cache.num_blocks - 1
    stats = eng.stats()
    assert stats["tokens_discarded"] == 0
    assert stats["tokens"] == sum(len(r.tokens) for r in reqs)
    dispatches = _span_args(tracer, "serve.decode.dispatch")
    assert len(dispatches) == stats["decode_steps"]
    assert sum(d["in_flight"] for d in dispatches) == (
        stats["decode_steps_overlapped"]
    )
    # Both starts from an empty engine had nothing in flight; most
    # ticks went out behind one.
    assert [d["in_flight"] for d in dispatches].count(0) == 2
    assert dispatches[0]["in_flight"] == 0
    # Every tick was fetched once, in order, each named by its own step.
    assert [f["step"] for f in _span_args(tracer, "serve.decode.fetch")] == [
        d["step"] for d in dispatches
    ]


def test_warmup_with_a_tick_in_flight_feeds_it_back_unharmed(
    model, engine_factory
):
    """A hand-stepped engine may warm a new bucket up mid-flight: the
    warm-up's own decode dispatch must not stand in for the output the
    next tick takes its tokens from."""
    lm, variables = model
    eng = engine_factory(slots=2)
    rng = np.random.default_rng(29)
    req = eng.submit(_prompt(rng, 5), 10)
    for _ in range(3):
        eng.step()
    assert eng._in_flight is not None
    eng.warmup(prompt_lengths=(20,))
    eng.run()
    np.testing.assert_array_equal(
        np.asarray(req.tokens, np.int32), _reference(lm, variables, req)
    )
    assert eng._decode_step._cache_size() == 1


def _eos_case(lm, variables, rng, max_new):
    """A prompt whose greedy continuation first shows some token at an
    index in ``[2, max_new - 2]``: an ``eos_token`` that ends the request
    with a tick in flight behind the one that made it."""
    for _ in range(64):
        prompt = _prompt(rng, 6)
        ref = np.asarray(
            generate(lm, variables, jnp.asarray(prompt[None]), max_new)
        )[0][6:]
        for at in range(2, max_new - 1):
            if ref[at] not in ref[:at]:
                return prompt, int(ref[at]), at
    raise AssertionError("no prompt of this seed changes its token")


def test_eos_hit_midflight_discards_one_token_and_frees_blocks_once(
    model, engine_factory
):
    """What the host cannot know ahead: a slot whose request has an
    ``eos_token`` rides the next tick speculatively. When the tick in
    flight ends it, nothing is delivered past the end, exactly one token
    is discarded, its blocks come back once and serve the next request."""
    lm, variables = model
    rng = np.random.default_rng(21)
    prompt, eos, at = _eos_case(lm, variables, rng, 12)
    # One usable reservation beside the bystander's: the follower below
    # can only be served from the blocks the eos eviction returned.
    eng = engine_factory(slots=2, num_blocks=1 + 3 + 3, max_queue=4)
    seen = []
    ended = eng.submit(prompt, 12, eos_token=eos, on_token=seen.append)
    bystander = eng.submit(_prompt(rng, 5), 16)
    follower = eng.submit(_prompt(rng, 9), 10)  # waits for blocks
    while ended.status != "finished":
        eng.step()
    # The tick dispatched before the end was seen is still in flight and
    # carries the evicted slot; its blocks are already free.
    assert eng._in_flight is not None
    assert any(slot.req is ended for _, slot in eng._in_flight.riders)
    assert len(ended.tokens) == at + 1 and ended.tokens[-1] == eos
    eng.run()
    assert seen == ended.tokens and len(seen) == at + 1
    assert eng.stats()["tokens_discarded"] == 1
    np.testing.assert_array_equal(
        np.asarray(ended.tokens, np.int32),
        _reference(lm, variables, ended, eos),
    )
    for req in (bystander, follower):
        assert req.status == "finished"
        np.testing.assert_array_equal(
            np.asarray(req.tokens, np.int32), _reference(lm, variables, req)
        )
    # A double free raises in the allocator; every block is back.
    assert eng.cache.free_blocks == eng.cache.num_blocks - 1
    assert eng.stats()["evictions"] == 3


@pytest.mark.parametrize("end", ["run", "drain", "stop"])
def test_ends_leave_no_tick_in_flight_and_no_request_unfinished(
    model, engine_factory, end
):
    lm, variables = model
    eng = engine_factory(slots=2, max_queue=4)
    rng = np.random.default_rng(13)
    reqs = [eng.submit(_prompt(rng, 5), 40), eng.submit(_prompt(rng, 8), 30)]
    if end == "stop":
        eng.start()
        stream = reqs[0].stream(timeout=60.0)
        for _ in range(4):
            next(stream)
        assert eng.stop()
        assert eng._in_flight is None
        # Parked: what was delivered is a prefix, and the next driver
        # serves the rest.
        held = [len(r.tokens) for r in reqs]
        assert all(r.tokens == list(_reference(lm, variables, r)[:n])
                   for r, n in zip(reqs, held))
    else:
        for _ in range(3):
            eng.step()
        assert eng._in_flight is not None
    if end == "drain":
        shed = eng.submit(_prompt(rng, 4), 4)  # queued: no slot is free
        eng.drain()
        assert shed.status == "rejected" and shed.reject_reason == "draining"
    eng.run()
    assert eng._in_flight is None and eng.active_count == 0
    for req in reqs:
        assert req.status == "finished"
        np.testing.assert_array_equal(
            np.asarray(req.tokens, np.int32), _reference(lm, variables, req)
        )
    # A hand-stepped engine's stop() delivers the lagging tick too.
    late = eng.submit(_prompt(rng, 4), 6) if end != "drain" else None
    if late is not None:
        for _ in range(3):
            eng.step()
        delivered = len(late.tokens)
        assert eng._in_flight is not None and eng.stop()
        assert eng._in_flight is None and len(late.tokens) == delivered + 1
        eng.run()
        assert late.status == "finished" and len(late.tokens) == 6


@pytest.mark.parametrize("driver", ["thread", "inline"])
def test_fault_with_a_tick_in_flight_fails_each_request_once(
    model, engine_factory, driver
):
    """The third dispatch hits the ``serving.decode`` site with the
    second tick in flight. On the serve thread both ticks' requests fail
    once (``reason="error"``) and their blocks come back once; driven
    inline the exception reaches the caller with the tick still in
    flight, and the next run serves on without losing or repeating a
    token."""
    lm, variables = model
    get_registry().reset()
    eng = engine_factory(slots=2)
    eng.warmup(prompt_lengths=(4, 7))
    rng = np.random.default_rng(17)
    if driver == "thread":
        with faults.scope("serving.decode@step=3"):
            eng.start()
            reqs = [eng.submit(_prompt(rng, 4), 20),
                    eng.submit(_prompt(rng, 7), 20)]
            assert all(r.wait(timeout=60.0) for r in reqs)
        eng.stop()
        assert isinstance(eng.serve_error, FaultInjectedError)
        assert [r.reject_reason for r in reqs] == ["error", "error"]
        assert eng._rejected == 2 and eng._in_flight is None
        assert eng.active_count == 0
        assert eng.cache.free_blocks == eng.cache.num_blocks - 1
        return
    reqs = [eng.submit(_prompt(rng, 4), 20), eng.submit(_prompt(rng, 7), 20)]
    with faults.scope("serving.decode@step=3"):
        with pytest.raises(FaultInjectedError, match="serving.decode"):
            eng.run()
    assert eng._in_flight is not None and eng._decode_steps == 2
    eng.run()
    for req in reqs:
        assert req.status == "finished"
        np.testing.assert_array_equal(
            np.asarray(req.tokens, np.int32), _reference(lm, variables, req)
        )
    assert eng._rejected == 0 and eng._in_flight is None


@pytest.mark.parametrize("attention", ["naive", "flash"])
def test_moe_lm_serves_through_the_same_decode_program(world, attention):
    """The decode program runs the model's own blocks, so a subclass's
    ``make_ff`` (the MoE block) serves unchanged: every slot's token is
    its own routing group, as under ``generate()``'s one-token ticks.
    With ample capacity the greedy streams equal ``generate()``'s."""
    from fluxmpi_tpu.models.moe import MoETransformerLM

    lm = MoETransformerLM(vocab_size=32, max_len=64, num_layers=2,
                          d_model=32, num_heads=4, d_ff=64, num_experts=4,
                          capacity_factor=4.0)
    variables = lm.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), train=False
    )
    with pytest.warns(UserWarning, match="batched_prefill_safe"):
        eng = InferenceEngine(lm, variables, slots=3, block_size=8,
                              attention=attention)
    try:
        rng = np.random.default_rng(0)
        reqs = [eng.submit(_prompt(rng, plen), mnew)
                for plen, mnew in ((5, 7), (9, 4), (3, 10), (12, 6))]
        assert eng.run()["completed"] == len(reqs)
        for req in reqs:
            ref = np.asarray(generate(
                lm, variables, jnp.asarray(req.prompt[None]),
                req.max_new_tokens, prefill="batched",
            ))[0][len(req.prompt):]
            np.testing.assert_array_equal(
                np.asarray(req.tokens, np.int32), ref
            )
    finally:
        eng.close()
        serving.shutdown()


def test_engine_attention_option_validation(model):
    """The attention option's error paths: an unknown mode raises, a
    model without the switch raises a named error, and the env-var
    default (FLUXMPI_TPU_SERVING_ATTENTION) reaches the engine."""
    lm, variables = model
    with pytest.raises(ValueError, match="naive.*flash.*auto"):
        InferenceEngine(lm, variables, slots=2, block_size=8,
                        attention="fast")
    os.environ["FLUXMPI_TPU_SERVING_ATTENTION"] = "naive"
    try:
        eng = InferenceEngine(lm, variables, slots=2, block_size=8)
        assert eng.attention == "naive"
        eng.close()
    finally:
        del os.environ["FLUXMPI_TPU_SERVING_ATTENTION"]
    serving.shutdown()


def test_warmup_touches_only_the_trash_block(model, engine_factory):
    eng = engine_factory()
    free_before = eng.cache.free_blocks
    eng.warmup(prompt_lengths=(4, 11))
    assert eng.cache.free_blocks == free_before
    assert eng.queue_depth == 0 and eng.active_count == 0


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------


def test_queue_full_rejects_with_counter(model, engine_factory):
    get_registry().reset()
    eng = engine_factory(slots=1, max_queue=2)
    rng = np.random.default_rng(0)
    reqs = [eng.submit(_prompt(rng, 4), 4) for _ in range(3)]
    assert [r.status for r in reqs[:2]] == ["queued", "queued"]
    assert reqs[2].status == "rejected"
    assert reqs[2].reject_reason == "queue_full"
    with pytest.raises(RuntimeError, match="queue_full"):
        reqs[2].result()
    snap = {
        (m["name"], tuple(sorted(m["labels"].items()))): m
        for m in get_registry().snapshot()
    }
    key = ("serving.admission_rejects", (("reason", "queue_full"),))
    assert snap[key]["value"] == 1
    eng.run()


def test_oversized_request_raises(model, engine_factory):
    eng = engine_factory()
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(_prompt(rng, 30), eng.max_len)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(_prompt(rng, 4), 0)
    with pytest.raises(ValueError, match="vocabulary"):
        eng.submit(_prompt(rng, 4), 4, eos_token=99)


def test_capacity_queueing_and_block_reuse(model, engine_factory):
    """Token-budget admission: a pool sized for ONE request at a time
    queues the second until eviction frees its blocks — then serves it
    from the recycled blocks, correctly."""
    lm, variables = model
    # 5 usable blocks of 8 = 40 tokens; each request reserves 4 blocks.
    eng = engine_factory(slots=2, num_blocks=6, max_queue=8)
    rng = np.random.default_rng(3)
    a = eng.submit(_prompt(rng, 8), 16)   # 24 tokens -> 3 blocks
    b = eng.submit(_prompt(rng, 10), 12)  # 22 tokens -> 3 blocks, must wait
    eng.step()
    assert a.status == "active" and b.status == "queued"
    eng.run()
    assert a.status == "finished" and b.status == "finished"
    for req, mnew in ((a, 16), (b, 12)):
        ref = np.asarray(
            generate(lm, variables, jnp.asarray(req.prompt[None]), mnew)
        )[0][len(req.prompt):]
        np.testing.assert_array_equal(np.asarray(req.tokens, np.int32), ref)
    assert eng.cache.free_blocks == 5


@pytest.mark.parametrize("busy", [False, True])
def test_one_admission_an_iteration_while_slots_decode(
    model, engine_factory, busy
):
    """A prefill stalls every active slot, so a queue of waiting
    requests is taken one an iteration while anything is decoding: a gap
    between two tokens holds at most one admission. An idle engine
    fills its slots at once."""
    rng = np.random.default_rng(11)
    eng = engine_factory(slots=4, max_queue=8)
    first = eng.submit(_prompt(rng, 6), 12) if busy else None
    if busy:
        eng.step()  # admitted: the first token came from the prefill
        assert first.status == "active"
        eng.step()  # its first decode tick is in flight
        assert len(first.tokens) == 1 and eng._in_flight is not None
    waiting = [eng.submit(_prompt(rng, 4 + i), 6) for i in range(3)]
    before = len(first.tokens) if busy else 0
    admitted = []
    for _ in range(3):
        eng.step()
        admitted.append(sum(r.status != "queued" for r in waiting))
    assert admitted == ([1, 2, 3] if busy else [3, 3, 3])
    if busy:
        # No gap between two of the active slot's tokens held more than
        # one admission: every iteration delivered it the token of the
        # tick dispatched the iteration before.
        assert len(first.tokens) == before + 3
    eng.run()
    assert all(r.status == "finished" for r in waiting)


# ---------------------------------------------------------------------------
# Streaming + latency accounting
# ---------------------------------------------------------------------------


def test_streaming_callback_iterator_and_latency(model, engine_factory):
    lm, variables = model
    eng = engine_factory()
    eng.warmup(prompt_lengths=(5,))
    rng = np.random.default_rng(11)
    seen = []
    eng.start()
    try:
        req = eng.submit(_prompt(rng, 5), 10, on_token=seen.append)
        streamed = list(req.stream(timeout=30.0))
    finally:
        eng.stop()
    assert req.status == "finished"
    assert streamed == req.tokens == seen
    ref = np.asarray(
        generate(lm, variables, jnp.asarray(req.prompt[None]), 10)
    )[0][5:]
    np.testing.assert_array_equal(np.asarray(streamed, np.int32), ref)
    assert req.queue_wait_s is not None and req.queue_wait_s >= 0
    assert req.ttft_s is not None and req.ttft_s >= req.queue_wait_s
    assert req.per_token_s is not None and req.per_token_s >= 0


def test_slo_violation_counter(model, engine_factory):
    get_registry().reset()
    # Impossible SLOs: every completion violates both.
    eng = engine_factory(slo_ttft_s=0.0, slo_token_s=0.0)
    rng = np.random.default_rng(2)
    eng.submit(_prompt(rng, 4), 4)
    summary = eng.run()
    assert summary["slo_violations"] == 2
    snap = {
        (m["name"], tuple(sorted(m["labels"].items()))): m["value"]
        for m in get_registry().snapshot()
        if m["name"] == "serving.slo_violations"
    }
    assert snap[("serving.slo_violations", (("kind", "ttft"),))] == 1
    assert snap[("serving.slo_violations", (("kind", "per_token"),))] == 1


# ---------------------------------------------------------------------------
# Preemption + faults under load (the PR 8 convention)
# ---------------------------------------------------------------------------


def test_sigterm_drains_inflight_rejects_new(model, engine_factory):
    """The preemption contract under load: in-flight requests decode to
    completion, queued and new admissions reject, and the summary
    reports the drained/rejected split."""
    lm, variables = model
    eng = engine_factory(slots=2, max_queue=8)
    rng = np.random.default_rng(9)
    a = eng.submit(_prompt(rng, 5), 24)
    b = eng.submit(_prompt(rng, 7), 24)
    c = eng.submit(_prompt(rng, 4), 4)  # queued behind the two slots
    eng.step()  # admit a + b
    runtime.request_preemption()
    try:
        summary = eng.run()
    finally:
        runtime.clear_preemption()
    assert summary["preempted"] is True
    assert summary["drained"] == 2
    assert summary["rejected"] == 1
    assert a.status == "finished" and len(a.tokens) == 24
    assert b.status == "finished" and len(b.tokens) == 24
    assert c.status == "rejected" and c.reject_reason == "preempted"
    # Drained output is still the exact generate() continuation.
    ref = np.asarray(
        generate(lm, variables, jnp.asarray(a.prompt[None]), 24)
    )[0][5:]
    np.testing.assert_array_equal(np.asarray(a.tokens, np.int32), ref)
    late = eng.submit(_prompt(rng, 4), 4)
    assert late.status == "rejected" and late.reject_reason == "draining"


@pytest.mark.parametrize("site", ["serving.admit", "serving.decode"])
def test_serving_sites_are_injectable(model, engine_factory, site):
    # Every serving.* entry of faults.KNOWN_SITES has a live trigger —
    # the coverage contract the fluxlint unregistered-fault-site rule
    # greps this file for.
    eng = engine_factory()
    rng = np.random.default_rng(4)
    with faults.scope(site + "@step=1"):
        with pytest.raises(FaultInjectedError, match=site):
            if site == "serving.admit":
                eng.submit(_prompt(rng, 4), 4)
            else:
                eng.submit(_prompt(rng, 4), 4)
                eng.run()
    # Disarmed: the engine still serves (the decode crash left its slot
    # active; the rerun drains it cleanly).
    req = eng.submit(_prompt(rng, 4), 4)
    eng.run()
    assert req.status == "finished"


def test_decode_stall_feeds_watchdog_clock(model, engine_factory):
    """A delay= fault at serving.decode stalls the loop in place — and
    the engine's per-iteration notify_progress keeps feeding the same
    clock /healthz reads, so a stuck decode is visible liveness, not
    silence."""
    from fluxmpi_tpu.telemetry.watchdog import progress_value

    eng = engine_factory()
    rng = np.random.default_rng(4)
    before = progress_value()
    with faults.scope("serving.decode@step=1:delay=0.05"):
        eng.submit(_prompt(rng, 4), 3)
        summary = eng.run()
    assert summary["completed"] == 1
    assert progress_value() > before


# ---------------------------------------------------------------------------
# Telemetry, status board, env wiring, shutdown discipline
# ---------------------------------------------------------------------------


def test_metrics_schema_valid_and_namespace_closed(model, engine_factory):
    get_registry().reset()
    eng = engine_factory()
    rng = np.random.default_rng(6)
    eng.submit(_prompt(rng, 5), 6)
    eng.run()
    rec = get_registry().flush()
    assert validate_record(rec) == []
    emitted = {m["name"] for m in rec["metrics"] if m["name"].startswith("serving.")}
    assert emitted and emitted <= KNOWN_METRIC_NAMES
    # The namespace is CLOSED: an off-schema serving.* name is producer
    # drift, rejected by the validator (and fluxlint at PR time).
    bad = {"name": "serving.bogus", "type": "gauge", "labels": {}, "value": 1.0}
    assert any("framework-owned" in e for e in validate_metric(bad))


def test_status_board_and_fluxmpi_top_serving_view(model, engine_factory):
    exp = Exporter(0, "127.0.0.1", deadline=3600.0)
    export.configure(exp)
    observe.configure(True)  # the request plane enriches the board
    try:
        eng = engine_factory()
        rng = np.random.default_rng(8)
        for _ in range(3):
            eng.submit(_prompt(rng, 5), 6)
        summary = eng.run()
        with urllib.request.urlopen(
            f"http://127.0.0.1:{exp.port}/status", timeout=5
        ) as resp:
            status = json.load(resp)
        assert validate_status_record(status) == []
        srv = status["serving"]
        assert srv["phase"] == "finished"
        assert srv["completed"] == summary["completed"] == 3
        assert srv["tokens"] == summary["tokens"]
        assert srv["kv_blocks_in_use"] == 0
        # Request-plane enrichment: burn + TTFT percentiles + the
        # logged-record count ride the same snapshot.
        assert srv["requests_logged"] == 3
        assert srv["burn_rate"] == 0.0  # healthy run burns nothing
        assert srv["ttft_p50"] is not None and srv["ttft_p99"] is not None
        # The fleet dashboard renders the serving view from the same
        # snapshot (stdlib CLI, --once exit semantics unchanged).
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, os.path.join(here, "scripts", "fluxmpi_top.py"),
             f"http://127.0.0.1:{exp.port}", "--once"],
            capture_output=True, text=True, timeout=30,
        )
        assert proc.returncode == 0, proc.stderr
        assert "SERVING" in proc.stdout
        assert "finished" in proc.stdout
        assert "burn" in proc.stdout  # the request-plane ticker line
    finally:
        observe.shutdown()
        export.shutdown()


def test_configure_env_forms(model, monkeypatch):
    serving.shutdown()
    monkeypatch.setenv("FLUXMPI_TPU_SERVING", "1")
    monkeypatch.setenv("FLUXMPI_TPU_SERVING_SLOTS", "3")
    monkeypatch.setenv("FLUXMPI_TPU_SERVING_BLOCK_SIZE", "4")
    monkeypatch.setenv("FLUXMPI_TPU_SERVING_QUEUE", "5")
    serving.configure()
    assert serving.enabled()
    lm, variables = model
    eng = InferenceEngine(lm, variables)
    try:
        assert eng.slots == 3
        assert eng.block_size == 4
        assert eng.max_queue == 5
    finally:
        eng.close()
        serving.shutdown()
    assert not serving.enabled()


def test_configure_dict_and_env_typo(model, monkeypatch):
    cfg = serving.configure({"slots": 5, "block_size": 8})
    assert cfg.slots == 5
    with pytest.raises(ValueError, match="unknown serving config"):
        serving.configure({"slotz": 5})
    serving.shutdown()
    # An env typo degrades with a warning, never crashes bring-up (the
    # faults.configure convention).
    monkeypatch.setenv("FLUXMPI_TPU_SERVING_SLOTS", "many")
    lm, variables = model
    with pytest.warns(UserWarning, match="FLUXMPI_TPU_SERVING_SLOTS"):
        eng = InferenceEngine(lm, variables, block_size=8)
    try:
        assert eng.slots == 8  # the built-in default
    finally:
        eng.close()
        serving.shutdown()


def test_init_serving_kwarg(model, world):
    fm.init(serving={"slots": 3})
    assert serving.enabled()
    lm, variables = model
    eng = InferenceEngine(lm, variables, block_size=8)
    assert eng.slots == 3
    eng.close()
    fm.init(serving=False)
    assert not serving.enabled()


def test_env_typo_on_master_switch_warns_not_crashes(monkeypatch):
    # FLUXMPI_TPU_SERVING="true" (a natural typo for "1") must degrade
    # with a warning, never crash init() of a job that may not even
    # serve — the export-plane env-typo convention.
    serving.shutdown()
    monkeypatch.setenv("FLUXMPI_TPU_SERVING", "true")
    with pytest.warns(UserWarning, match="FLUXMPI_TPU_SERVING"):
        cfg = serving.configure()
    assert cfg is None and not serving.enabled()
    # The programmatic spelling still raises (a code bug, not a typo).
    with pytest.raises(ValueError, match="serving spec"):
        serving.configure("true")


def test_serve_thread_error_fails_pending_requests(model, engine_factory):
    """A dying serve thread must not strand consumers: an error inside
    an iteration (here the serving.decode chaos site) rejects every
    pending request with reason="error" and banks the exception."""
    eng = engine_factory()
    eng.warmup(prompt_lengths=(4,))
    rng = np.random.default_rng(0)
    with faults.scope("serving.decode@step=1"):
        eng.start()
        req = eng.submit(_prompt(rng, 4), 8)
        assert req.wait(timeout=60.0)
    assert req.status == "rejected" and req.reject_reason == "error"
    with pytest.raises(RuntimeError, match="error"):
        list(req.stream(timeout=5.0))
    assert isinstance(eng.serve_error, FaultInjectedError)
    eng.stop()


def test_stop_then_run_inline_serves_again(model, engine_factory):
    """The documented driver switch — stop() the serve thread, then
    drive run() inline — must actually serve: submissions landing in
    the parked window QUEUE (a parked engine simply has no driver yet)
    and the next run() drains them; nothing is silently shed."""
    lm, variables = model
    eng = engine_factory()
    rng = np.random.default_rng(3)
    eng.start()
    first = eng.submit(_prompt(rng, 4), 4)
    assert first.wait(timeout=60.0)
    eng.stop()
    parked = eng.submit(_prompt(rng, 4), 6)
    assert parked.status == "queued"
    summary = eng.run()
    assert parked.status == "finished" and len(parked.tokens) == 6
    assert summary["completed"] >= 1
    ref = np.asarray(
        generate(lm, variables, jnp.asarray(parked.prompt[None]), 6)
    )[0][4:]
    np.testing.assert_array_equal(np.asarray(parked.tokens, np.int32), ref)
    # tokens_per_sec is per-RUN: the lifetime token count must not be
    # divided by one run's wall (an idle follow-up run rates 0, while
    # the lifetime counters keep their totals).
    idle = eng.run()
    assert idle["tokens_per_sec"] == 0.0
    assert idle["tokens"] == summary["tokens"] == 10


def test_registry_counters_match_summary_across_driver_switch(
    model, engine_factory
):
    """Decode ticks between the last flush and a driver switch must
    still reach the cumulative registry counters — the delta baselines
    survive _resolve_run instead of being silently re-based."""
    get_registry().reset()
    eng = engine_factory(flush_every=16)
    rng = np.random.default_rng(1)
    eng.submit(_prompt(rng, 4), 8)
    for _ in range(4):  # admit + a few un-flushed ticks (< flush_every)
        eng.step()
    summary = eng.run()
    snap = {
        m["name"]: m["value"]
        for m in get_registry().snapshot()
        if m["type"] == "counter"
    }
    assert snap["serving.decode_steps"] == summary["decode_steps"]
    assert snap["serving.tokens_generated"] == summary["tokens"]


def test_idle_serve_thread_does_not_feed_watchdog(model, engine_factory):
    """An idle background serving loop must NOT advance the process
    watchdog progress counter: it would mask a co-resident train
    loop's stall from the watchdog and /healthz. Progress only moves
    when the engine admits or decodes."""
    import time as _time

    from fluxmpi_tpu.telemetry.watchdog import progress_value

    eng = engine_factory()
    eng.start()
    try:
        _time.sleep(0.2)  # several idle poll cycles
        before = progress_value()
        _time.sleep(0.3)
        assert progress_value() == before
        rng = np.random.default_rng(0)
        req = eng.submit(_prompt(rng, 4), 4)
        assert req.wait(timeout=60.0)
        assert progress_value() > before
    finally:
        eng.stop()


def test_warmup_refuses_while_serving(model, engine_factory):
    # warmup dispatches DONATE the pool buffers — racing the serve
    # thread would invalidate the arrays under its in-flight dispatch.
    eng = engine_factory()
    eng.start()
    try:
        with pytest.raises(RuntimeError, match="donate"):
            eng.warmup(prompt_lengths=(8,))
    finally:
        eng.stop()


def test_stream_timeout_raises_timeout_error(model, engine_factory):
    # The documented exception type — not the internal queue.Empty.
    eng = engine_factory()
    rng = np.random.default_rng(0)
    req = eng.submit(_prompt(rng, 4), 4)  # queued; nothing drives it
    with pytest.raises(TimeoutError, match="no token"):
        list(req.stream(timeout=0.05))
    eng.run()
    assert req.status == "finished"


def test_engine_close_fails_pending_and_drops_pools(model):
    get_registry().reset()
    lm, variables = model
    eng = InferenceEngine(lm, variables, slots=1, block_size=8, max_queue=4)
    rng = np.random.default_rng(1)
    active = eng.submit(_prompt(rng, 5), 30)
    queued = eng.submit(_prompt(rng, 5), 30)
    eng.step()
    assert serving.get_engine() is eng
    rejected_before = eng._rejected
    eng.close()
    assert active.status == "rejected" and active.reject_reason == "shutdown"
    assert queued.status == "rejected" and queued.reject_reason == "shutdown"
    # Shutdown rejections ride the same accounting as every other
    # rejection path — the summary/board must not undercount them.
    assert eng._rejected == rejected_before + 2
    snap = {
        (m["name"], tuple(sorted(m["labels"].items()))): m["value"]
        for m in get_registry().snapshot()
        if m["name"] == "serving.admission_rejects"
    }
    assert snap[("serving.admission_rejects", (("reason", "shutdown"),))] == 2
    assert all(k.k_pool is None and k.v_pool is None for k in eng.cache.kinds)
    assert eng.cache.free_blocks == eng.cache.num_blocks - 1
    assert serving.get_engine() is None


# ---------------------------------------------------------------------------
# Request-observability plane (serving/observe.py)
# ---------------------------------------------------------------------------


def test_kv_high_watermark_and_fragmentation():
    """The forensics gauges: the watermark is a pool-lifetime peak (it
    never comes back down), fragmentation measures free-list scatter —
    1 - longest contiguous free run / free blocks."""
    cache = BlockKVCache([Keeps("full", 4, 8)] * 2,
                         num_blocks=9, block_size=8, max_blocks_per_seq=8)
    assert cache.high_watermark_blocks == 0
    assert cache.fragmentation == 0.0  # pristine free list is one run
    a = cache.alloc(24)  # blocks 1,2,3
    b = cache.alloc(24)  # blocks 4,5,6
    assert cache.high_watermark_blocks == 6
    cache.free(a)
    # The watermark is a peak, not an occupancy gauge.
    assert cache.used_blocks == 3 and cache.high_watermark_blocks == 6
    # Free ids {1,2,3,7,8}: longest run 3 of 5 free -> 0.4 scattered.
    assert cache.fragmentation == pytest.approx(1.0 - 3.0 / 5.0)
    cache.free(b)
    assert cache.fragmentation == 0.0  # coalesced back to one run
    assert cache.high_watermark_blocks == 6


def test_slo_burn_tracker_multi_window_math():
    now = {"t": 0.0}
    t = observe.SLOBurnTracker(
        window=120.0, slo_target=0.9, clock=lambda: now["t"]
    )
    assert t.windows == (10.0, 120.0)
    assert t.budget == pytest.approx(0.1)
    # An idle service burns nothing — and alerts on nothing.
    assert t.burn_rate() == 0.0
    assert t.alert_rate() is None
    for _ in range(8):
        t.observe(True)
    for _ in range(2):
        t.observe(False)
    # 2 bad of 10 over a 10% budget = burning 2x as fast as it accrues.
    assert t.burn_rate(10.0) == pytest.approx(2.0)
    assert t.burn_rate(120.0) == pytest.approx(2.0)
    assert t.alert_rate() == pytest.approx(2.0)
    # A recovered service: the short window clears first, and the
    # multi-window AND (min) stops alerting even while the long window
    # still remembers the bad minutes.
    now["t"] = 50.0
    t.observe(True)
    assert t.burn_rate(10.0) == 0.0
    assert t.burn_rate(120.0) == pytest.approx((2.0 / 11.0) / 0.1)
    assert t.alert_rate() == 0.0
    t.reset()
    assert t.total == 0 and t.good == 0
    assert t.alert_rate() is None
    with pytest.raises(ValueError, match="window"):
        observe.SLOBurnTracker(window=0.0)
    with pytest.raises(ValueError, match="slo_target"):
        observe.SLOBurnTracker(slo_target=1.0)


def test_slo_burn_anomaly_rule():
    get_registry().reset()
    det = AnomalyDetector(dump=False)
    assert det.policies["slo_burn"] == "warn"
    # Below threshold (default 2.0): quiet.
    assert det.observe(slo_burn=1.5, step=1) == []
    with pytest.warns(UserWarning, match="slo_burn"):
        events = det.observe(slo_burn=2.5, step=2)
    assert [e["rule"] for e in events] == ["slo_burn"]
    assert events[0]["action"] == "warn"
    snap = {
        (m["name"], tuple(sorted(m["labels"].items()))): m["value"]
        for m in get_registry().snapshot()
    }
    assert snap[("anomaly.triggered", (("rule", "slo_burn"),))] == 1
    get_registry().reset()


def test_request_log_complete_under_sigterm_drain(
    model, engine_factory, tmp_path
):
    """The drain-completeness contract (and the reject live-lookup):
    every in-flight, queued, AND post-drain request lands in the
    request log with its terminal status — asserted end-to-end through
    the schema checker."""
    path_spec = str(tmp_path / "requests.{process}.jsonl")
    observe.configure(path_spec)
    eng = engine_factory(slots=2, max_queue=8)
    rng = np.random.default_rng(9)
    a = eng.submit(_prompt(rng, 5), 24)
    b = eng.submit(_prompt(rng, 7), 24)
    c = eng.submit(_prompt(rng, 4), 4)  # queued behind the two slots
    eng.step()  # admit a + b
    runtime.request_preemption()
    try:
        summary = eng.run()
    finally:
        runtime.clear_preemption()
    assert summary["drained"] == 2 and summary["rejected"] == 1
    late = eng.submit(_prompt(rng, 4), 4)
    assert late.status == "rejected" and late.reject_reason == "draining"
    path = path_spec.format(process=0)
    with open(path, encoding="utf-8") as f:
        records = {r["request_id"]: r for r in map(json.loads, f)}
    assert set(records) == {req.id for req in (a, b, c, late)}
    assert records[a.id]["status"] == "finished"
    assert records[a.id]["output_tokens"] == 24
    assert records[b.id]["status"] == "finished"
    assert records[c.id]["status"] == "rejected"
    assert records[c.id]["reason"] == "preempted"
    assert records[late.id]["reason"] == "draining"
    # Drained completions carry full timings; rejects carry the nulls
    # the schema allows.
    assert records[a.id]["ttft_s"] is not None
    assert records[late.id]["ttft_s"] is None
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable,
         os.path.join(here, "scripts", "check_metrics_schema.py"), path],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_rejected_requests_raise_typed_error(model, engine_factory):
    """result()/stream() on a rejected request raise
    RequestRejectedError — a RuntimeError subclass carrying the reason
    so callers branch without string-matching (the retry/resubmit
    split)."""
    eng = engine_factory(slots=1, max_queue=1)
    rng = np.random.default_rng(0)
    eng.submit(_prompt(rng, 4), 4)
    shed = eng.submit(_prompt(rng, 4), 4)
    assert shed.status == "rejected"
    with pytest.raises(RequestRejectedError, match="queue_full") as exc_info:
        shed.result()
    assert exc_info.value.reject_reason == "queue_full"
    assert isinstance(exc_info.value, RuntimeError)  # old except clauses hold
    with pytest.raises(RequestRejectedError, match="queue_full"):
        list(shed.stream(timeout=1.0))
    eng.run()


def test_request_plane_fully_off_never_touches_observer(
    model, engine_factory, monkeypatch
):
    """The PR 4 zero-cost contract: with the plane off, a full serving
    run — including a load-shed reject — never calls ANY plane method.
    Exploding mocks, not timers."""
    observe.shutdown()
    assert observe.get_request_observer() is None

    def boom(*a, **k):
        raise AssertionError("request plane touched while off")

    monkeypatch.setattr(observe.RequestObserver, "observe_terminal", boom)
    monkeypatch.setattr(observe.RequestObserver, "board", boom)
    monkeypatch.setattr(observe.RequestObserver, "maybe_write_bundle", boom)
    monkeypatch.setattr(observe.SLOBurnTracker, "observe", boom)
    monkeypatch.setattr(observe.RequestLog, "write", boom)
    eng = engine_factory(slots=1, max_queue=1)
    rng = np.random.default_rng(2)
    ok = eng.submit(_prompt(rng, 4), 4)
    shed = eng.submit(_prompt(rng, 4), 4)  # queue_full reject path
    eng.run()
    assert ok.status == "finished" and len(ok.tokens) == 4
    assert shed.status == "rejected" and shed.reject_reason == "queue_full"


def test_request_plane_e2e_trace_log_report(model, engine_factory, tmp_path):
    """The acceptance loop: one plane-on run yields (a) a Perfetto-valid
    merged trace with the request span chains on named tracks, (b) a
    schema-valid request JSONL, and (c) a serving_report aggregation
    whose totals match the registry counters."""
    get_registry().reset()
    log_spec = str(tmp_path / "requests.{process}.jsonl")
    trace_spec = str(tmp_path / "trace.{process}.json")
    tracing.configure(trace_spec)
    obs = observe.configure(log_spec)
    obs.dump_dir = str(tmp_path)  # the queue_full bundle lands here too
    try:
        eng = engine_factory(slots=2, max_queue=2)
        rng = np.random.default_rng(7)
        good = [eng.submit(_prompt(rng, 5), 6) for _ in range(2)]
        shed = [eng.submit(_prompt(rng, 5), 6) for _ in range(3)]
        summary = eng.run()
        assert [r.status for r in good] == ["finished", "finished"]
        assert {r.reject_reason for r in shed} == {"queue_full"}
        trace_path = tracing.shutdown()
        assert trace_path is not None
    finally:
        tracing.configure(False)
        tracing.reset()
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    merged = str(tmp_path / "merged.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(here, "scripts", "merge_traces.py"),
         "-o", merged, trace_path],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    log_path = log_spec.format(process=0)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(here, "scripts", "check_metrics_schema.py"),
         merged, log_path],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    with open(merged, encoding="utf-8") as f:
        trace = json.load(f)
    names = {e.get("name") for e in trace["traceEvents"]}
    assert {"request.queue", "request.prefill", "request.decode",
            "request.done", "request.rejected"} <= names
    # Every request rides its own named virtual track.
    track_names = {
        e["args"]["name"]
        for e in trace["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    assert {f"request {r.id}" for r in good} <= track_names
    # serving_report totals must agree with the registry counters — the
    # two accounting paths (JSONL records, metric counters) cannot
    # drift.
    proc = subprocess.run(
        [sys.executable, os.path.join(here, "scripts", "serving_report.py"),
         "--json", log_path],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    snap = {}
    for m in get_registry().snapshot():
        if m["type"] == "counter":
            snap.setdefault(m["name"], 0)
            snap[m["name"]] += m["value"]
    assert report["requests"] == 5
    assert report["finished"] == snap["serving.requests_completed"] == 2
    assert report["rejected"] == snap["serving.admission_rejects"] == 3
    assert report["reject_reasons"] == {"queue_full": 3}
    assert report["output_tokens"] == summary["tokens"]
    assert report["ttft"]["count"] == 2
    assert report["slo_ok"] == 2


def test_slo_burn_anomaly_fires_on_regression_silent_when_healthy(
    model, engine_factory
):
    """The burn alert end-to-end: an injected latency regression (an
    SLO floor no real request can meet) trips the slo_burn rule through
    the engine's flush; a healthy run with the same wiring stays
    silent."""
    get_registry().reset()
    set_anomaly_detector(AnomalyDetector(dump=False))
    observe.configure(True)
    try:
        eng = engine_factory(slo_ttft_s=1e-9)  # every completion violates
        rng = np.random.default_rng(4)
        for _ in range(3):
            eng.submit(_prompt(rng, 4), 4)
        with pytest.warns(UserWarning, match="slo_burn"):
            eng.run()
        snap = {
            (m["name"], tuple(sorted(m["labels"].items()))): m["value"]
            for m in get_registry().snapshot()
            if m["type"] == "counter"
        }
        assert snap[("anomaly.triggered", (("rule", "slo_burn"),))] >= 1
        # Healthy service, same wiring: silent.
        observe.shutdown()
        observe.configure(True)
        set_anomaly_detector(AnomalyDetector(dump=False))
        get_registry().reset()
        eng2 = engine_factory()
        for _ in range(3):
            eng2.submit(_prompt(rng, 4), 4)
        eng2.run()
        assert not any(
            m["name"] == "anomaly.triggered"
            for m in get_registry().snapshot()
        )
    finally:
        set_anomaly_detector(None)
        observe.shutdown()


def test_queue_full_load_shed_writes_debug_bundle_once(
    model, engine_factory, tmp_path
):
    """The first load-shed writes the OOM-style pool-census bundle (who
    ate the KV pool, at the moment it mattered); later sheds do not
    rewrite it — forensics are rate-limited to the triggering event."""
    obs = observe.configure(True)
    obs.dump_dir = str(tmp_path)
    eng = engine_factory(slots=1, max_queue=1)
    rng = np.random.default_rng(6)
    held = eng.submit(_prompt(rng, 5), 24)
    eng.step()  # admit: the slot now holds blocks the census reports
    eng.submit(_prompt(rng, 4), 4)  # fills the queue
    shed = eng.submit(_prompt(rng, 4), 4)
    assert shed.reject_reason == "queue_full"
    bundle_path = os.path.join(str(tmp_path), "fluxmpi_serving.0.json")
    assert obs.last_dump_path == bundle_path
    with open(bundle_path, encoding="utf-8") as f:
        bundle = json.load(f)
    srv = bundle["serving"]
    assert srv["blocks_total"] == eng.cache.num_blocks - 1
    assert srv["blocks_in_use"] > 0
    assert srv["census"][0]["request_id"] == held.id
    assert srv["census"][0]["blocks"] == eng._slots[0].num_blocks
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable,
         os.path.join(here, "scripts", "check_metrics_schema.py"),
         bundle_path],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # Rate-limited: a second shed does NOT rewrite the bundle.
    os.unlink(bundle_path)
    again = eng.submit(_prompt(rng, 4), 4)
    assert again.reject_reason == "queue_full"
    assert not os.path.exists(bundle_path)
    eng.run()


def test_request_log_configure_env_forms_and_typo(monkeypatch, tmp_path):
    observe.shutdown()
    monkeypatch.delenv("FLUXMPI_TPU_REQUEST_LOG", raising=False)
    # Unset env: configure(None) is a no-op.
    assert observe.configure() is None
    # "1": plane on without a file log (spans/burn/forensics only).
    obs = observe.configure(True)
    assert obs is not None and obs.log is None
    assert observe.configure("1") is obs  # idempotent replay reuses
    # A path spec installs a log; an equivalent replay keeps the
    # observer (and its burn windows).
    spec = str(tmp_path / "requests.{process}.jsonl")
    obs2 = observe.configure(spec)
    assert obs2 is not obs and obs2.log.path == spec.format(process=0)
    assert observe.configure(spec) is obs2
    # The env spelling of a malformed path warns and degrades...
    observe.shutdown()
    monkeypatch.setenv("FLUXMPI_TPU_REQUEST_LOG", "req.{proc}.jsonl")
    with pytest.warns(UserWarning, match="FLUXMPI_TPU_REQUEST_LOG"):
        assert observe.configure() is None
    # ...the programmatic spelling raises (a code bug, not a typo).
    with pytest.raises(ValueError, match="not formattable"):
        observe.configure("req.{proc}.jsonl")
    with pytest.raises(ValueError, match="request_log spec"):
        observe.configure(3.5)
    monkeypatch.delenv("FLUXMPI_TPU_REQUEST_LOG")
    observe.configure(True)
    assert observe.configure(False) is None
    assert observe.get_request_observer() is None
    # The burn-window env var follows the same warn-and-degrade rule.
    monkeypatch.setenv("FLUXMPI_TPU_SLO_WINDOW", "soon")
    with pytest.warns(UserWarning, match="FLUXMPI_TPU_SLO_WINDOW"):
        t = observe.SLOBurnTracker()
    assert t.windows[-1] == 300.0  # the built-in default held


def test_init_request_log_kwarg(world, tmp_path):
    spec = str(tmp_path / "requests.{process}.jsonl")
    fm.init(request_log=spec)
    obs = observe.get_request_observer()
    assert obs is not None and obs.log.path_spec == spec
    fm.init(request_log=False)
    assert observe.get_request_observer() is None
