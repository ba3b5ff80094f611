"""The program's own spans on the hot paths: ``train_loop``'s ``loop.*``
and the serving engine's ``serve.*``, read back through both sinks of the
one ``tracing.span`` call (the ring, and a ``jax.profiler`` capture's
host plane), the engine's public occupancy counters, the disabled path,
and the names the compiled programs carry."""

import glob
import os
import re

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from fluxmpi_tpu.data import ArrayDataset, DistributedDataLoader
from fluxmpi_tpu.models import MLP, TransformerLM
from fluxmpi_tpu.parallel import TrainState, make_train_step, train_loop
from fluxmpi_tpu.parallel.train import make_window_program, replicate
from fluxmpi_tpu.serving import InferenceEngine, observe
from fluxmpi_tpu.telemetry import Tracer, tracing, validate_trace_export
from fluxmpi_tpu.telemetry.schema import HOT_PATH_SPAN_ARGS, validate_trace_event
from fluxmpi_tpu.utils import profile_trace
from fluxmpi_tpu.utils.profiling import SPANS_FILE


# ---------------------------------------------------------------------------
# Two ways to read the spans back: (name, start_ns, end_ns, args) tuples
# ---------------------------------------------------------------------------


def _program_spans_of_xplane(logdir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
    )
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.split(".")[0] in ("loop", "serve"):
                    out.append((
                        ev.name, int(ev.start_ns),
                        int(ev.start_ns + ev.duration_ns), dict(ev.stats),
                    ))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _program_spans_of_ring(tracer):
    out = []
    for ev in tracer.export()["traceEvents"]:
        if ev.get("ph") == "X" and ev["name"].split(".")[0] in (
            "loop", "serve", "request"
        ):
            out.append((
                ev["name"], int(ev["ts"] * 1e3),
                int((ev["ts"] + ev["dur"]) * 1e3), ev.get("args") or {},
            ))
    return sorted(out, key=lambda e: (e[1], -e[2]))


@pytest.fixture()
def capture(request, tmp_path):
    """``channel`` -> a context that records the enclosed block's spans,
    and a reader for them: the ring alone, or a profiler capture with
    the ring off (the state of a ``--trace 1`` benchmark run)."""
    import contextlib

    channel = request.param
    box = {}

    @contextlib.contextmanager
    def recording():
        if channel == "ring":
            tracer = Tracer(enabled=True)
            prev = tracing.set_tracer(tracer)
            try:
                yield
            finally:
                tracing.set_tracer(prev)
            box["spans"] = _program_spans_of_ring(tracer)
            # The ring's export holds every hot-path span to its contract.
            assert validate_trace_export(tracer.export()) == []
        else:
            assert not tracing.get_tracer().enabled
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(
                str(tmp_path), profiler_options=options
            )
            try:
                yield
            finally:
                jax.profiler.stop_trace()
            box["spans"] = _program_spans_of_xplane(str(tmp_path))

    return recording, box


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _int_args(span):
    return {k: int(float(v)) for k, v in span[3].items()}


# ---------------------------------------------------------------------------
# train_loop
# ---------------------------------------------------------------------------


def _tiny_training(device_gather):
    model = MLP(features=(8, 1))
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 4)))
    optimizer = optax.sgd(0.05)

    def loss_fn(p, mstate, batch):
        x, y = batch
        return jnp.mean((model.apply(p, x) - y) ** 2), mstate

    rng = np.random.default_rng(0)
    data = ArrayDataset((
        rng.normal(size=(64, 4)).astype(np.float32),
        rng.normal(size=(64, 1)).astype(np.float32),
    ))
    loader = DistributedDataLoader(
        data, 8, device_gather=device_gather, prefetch=0
    )
    step = make_train_step(loss_fn, optimizer)
    state = replicate(TrainState.create(params, optimizer))
    return step, state, loader


@pytest.mark.parametrize("capture", ["ring", "xplane"], indirect=True)
@pytest.mark.parametrize("path", ["per_step", "fused"])
def test_train_loop_spans(world, capture, path):
    recording, box = capture
    fused = path == "fused"
    step, state, loader = _tiny_training(device_gather=fused)
    # One untraced pass first: compiles stay out of the spans' way.
    state, _ = train_loop(step, state, loader, steps=4, flush_every=4,
                          fuse="window" if fused else False, in_flight=1)
    with recording():
        state, summary = train_loop(
            step, state, loader, steps=8, flush_every=4,
            fuse="window" if fused else False, in_flight=1,
        )
    spans = box["spans"]
    dispatch = _named(spans, "loop.dispatch")
    flushes = _named(spans, "loop.flush")
    assert bool(summary["fused_window"]) == fused
    assert [_int_args(s)["update"] for s in flushes] == [4, 8]
    assert all(bool(_int_args(s)["fused"]) == fused for s in flushes)
    assert all(s[2] > s[1] for s in spans)
    # Siblings on one thread: no two of the loop's spans overlap.
    loop_spans = [s for s in spans if s[0].startswith("loop.")]
    for a, b in zip(loop_spans, loop_spans[1:]):
        assert a[2] <= b[1], (a, b)
    if fused:
        assert [_int_args(s) for s in dispatch] == [
            {"update": 0, "width": 4}, {"update": 4, "width": 4},
        ]
        epochs = _named(spans, "loop.device_epoch")
        assert epochs and "epoch" in epochs[0][3]
        assert not _named(spans, "loop.fetch")
        assert not _named(spans, "loop.backpressure")
    else:
        assert [_int_args(s) for s in dispatch] == [
            {"update": u, "width": 1} for u in range(8)
        ]
        fetches = _named(spans, "loop.fetch")
        # One fetch per dispatch, each before its dispatch.
        assert [_int_args(s)["update"] for s in fetches[:8]] == list(range(8))
        assert all(f[2] <= d[1] for f, d in zip(fetches, dispatch))
        # in_flight=1: every dispatch but the first waits for its
        # predecessor's result.
        waits = _named(spans, "loop.backpressure")
        assert len(waits) >= 6
        assert not _named(spans, "loop.device_epoch")


# ---------------------------------------------------------------------------
# The serving engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_lm(world):
    lm = TransformerLM(vocab_size=32, max_len=64, num_layers=2, d_model=32,
                       num_heads=4, d_ff=64)
    variables = lm.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), train=False
    )
    return lm, variables


def _serve(tiny_lm, n_requests=3, new_tokens=5):
    lm, variables = tiny_lm
    engine = InferenceEngine(lm, variables, slots=2, block_size=8)
    delivered = []
    try:
        engine.warmup(prompt_lengths=(5,))
        rng = np.random.default_rng(3)
        requests = [
            engine.submit(
                rng.integers(1, 32, size=5).astype(np.int32), new_tokens,
                on_token=delivered.append,
            )
            for _ in range(n_requests)
        ]
        engine.run()
        stats = engine.stats()
    finally:
        engine.close()
    return requests, delivered, stats


@pytest.mark.parametrize("capture", ["ring", "xplane"], indirect=True)
def test_engine_spans(tiny_lm, capture, request):
    recording, box = capture
    observe.configure(True)
    try:
        with recording():
            requests, _, stats = _serve(tiny_lm)
    finally:
        observe.shutdown()
    spans = box["spans"]
    ids = [r.id for r in requests]
    admits = _named(spans, "serve.admit")
    prefills = _named(spans, "serve.prefill")
    assert [_int_args(s)["request_id"] for s in admits] == ids
    assert [_int_args(s)["request_id"] for s in prefills] == ids
    for admit, prefill in zip(admits, prefills):
        assert _inside(prefill, admit)
        assert _int_args(admit)["prompt_tokens"] == 5
        assert _int_args(admit)["bucket"] == _int_args(prefill)["bucket"] == 8
    # Two slots, equal lengths: the first two requests leave together
    # and the third is admitted into an empty batch.
    assert [_int_args(s)["active"] for s in admits] == [0, 1, 0]
    iterations = _named(spans, "serve.iteration")
    assert all(any(_inside(a, it) for it in iterations) for a in admits)
    ticks = {
        kind: _named(spans, f"serve.decode.{kind}")
        for kind in ("prepare", "dispatch", "fetch", "deliver")
    }
    assert len({len(v) for v in ticks.values()}) == 1
    assert len(ticks["dispatch"]) == stats["decode_steps"]
    for prep, disp, fetch, deliv in zip(*ticks.values()):
        assert prep[2] <= disp[1] and disp[2] <= fetch[1] <= deliv[1]
        assert _int_args(disp)["step"] == _int_args(fetch)["step"]
        assert _int_args(deliv)["tokens"] == _int_args(prep)["active"]
        assert float(deliv[3]["gap_ms"]) > 0.0
        # One tick in flight: a tick is prepared and dispatched in one
        # iteration, fetched and delivered in the next.
        assert any(_inside(prep, it) and _inside(disp, it)
                   for it in iterations)
        assert any(_inside(fetch, it) and _inside(deliv, it)
                   and not _inside(disp, it) for it in iterations)
    # The tables' transfer is a child of the prepare, one a tick, and
    # says what it moved: a table of 8 entries, a position and a token
    # (int32) and ``use_prev`` (bool) for each of the 2 slots.
    uploads = _named(spans, "serve.decode.upload")
    assert len(uploads) == len(ticks["prepare"])
    for upload, prep in zip(uploads, ticks["prepare"]):
        assert _inside(upload, prep)
        assert _int_args(upload)["bytes"] == 2 * (4 * 8 + 4 + 4 + 1)
    # The gap ledger on the deliveries: every rider's gap is counted
    # once, a stalled one names the admission that caused it, and the
    # ticks sum back to stats()'s counters.
    assert sum(_int_args(s)["tokens"] for s in ticks["deliver"]) == (
        stats["gaps"]) == stats["tokens"] - stats["admissions"]
    assert sum(_int_args(s)["stalled"] for s in ticks["deliver"]) == (
        stats["gaps_stalled"])
    assert sum(float(s[3]["gap_ms"]) * _int_args(s)["tokens"]
               for s in ticks["deliver"]) / 1e3 == pytest.approx(
        stats["gap_seconds"])
    for deliv in ticks["deliver"]:
        assert ("stalled_by" in deliv[3]) == (_int_args(deliv)["stalled"] > 0)
    # The idle engine admits its first two requests at once: the first
    # one's first gap holds the second's admission, and no other does.
    stalled = [s for s in ticks["deliver"] if _int_args(s)["stalled"]]
    assert [(_int_args(s)["stalled"], _int_args(s)["stalled_by"])
            for s in stalled] == [(1, ids[1])]
    assert 0.0 < stats["gap_stalled_seconds"] < stats["gap_seconds"]
    # run() never waits: no idle span.
    assert not _named(spans, "serve.idle")
    overlapped = 0
    for k, (disp, fetch) in enumerate(zip(ticks["dispatch"], ticks["fetch"])):
        behind = int(_int_args(disp)["in_flight"])
        overlapped += behind
        if behind:
            # Dispatched BEFORE the tick in flight was fetched.
            assert disp[2] <= ticks["fetch"][k - 1][1]
        elif k:
            assert ticks["deliver"][k - 1][2] <= ticks["prepare"][k][1]
    assert overlapped == stats["decode_steps_overlapped"] > 0
    assert stats["tokens_discarded"] == 0
    assert sum(_int_args(s)["active"] for s in ticks["prepare"]) == (
        stats["slot_steps_active"]
    )
    assert sum(_int_args(s)["evicted"] for s in ticks["deliver"]) == len(ids)
    # live_blocks_pct: the blocks the decode kernel read this tick over
    # the blocks the slots' tables span (2 slots x 8 blocks of 8 here),
    # each counted once a layer (2); summed back over the ticks it is
    # stats()'s pair of counters.
    tabled = 2 * 2 * 8
    assert stats["kv_blocks_tabled"] == tabled * stats["decode_steps"]
    shares = [float(s[3]["live_blocks_pct"]) for s in ticks["prepare"]]
    assert sum(shares) * tabled / 100.0 == pytest.approx(
        stats["kv_blocks_live"]
    )
    # Prompts of 5 and answers of 5: positions 5..8, so one block a slot
    # until position 8 opens the second.
    assert max(shares) == pytest.approx(100.0 * 2 * 4 / tabled)
    assert min(shares) == pytest.approx(100.0 * 2 * 1 / tabled)
    # kernel_steps_per_live_block: the grid steps the tick's paged
    # kernels were handed over those blocks. A walk visits the live
    # blocks only, and a slot always rides here: one step a block.
    assert stats["kv_kernel_steps"] == stats["kv_blocks_live"]
    assert {float(s[3]["kernel_steps_per_live_block"])
            for s in ticks["prepare"]} == {1.0}
    chain = _named(spans, "request.prefill")
    if request.node.callspec.params["capture"] == "ring":
        # The ring also holds the request chain (written when a request
        # ends, ring-only): the same identifiers.
        assert {_int_args(s)["request_id"] for s in chain} == set(ids)
    else:
        assert not chain


def test_engine_stats_agree_with_delivery(tiny_lm):
    requests, delivered, stats = _serve(tiny_lm, n_requests=3, new_tokens=5)
    assert stats["tokens"] == len(delivered) == sum(
        len(r.tokens) for r in requests
    ) == 15
    assert stats["admissions"] == stats["evictions"] == 3
    # Every token but a request's first comes out of a decode step.
    assert stats["slot_steps_active"] == stats["tokens"] - stats["admissions"]
    assert 0 < stats["slot_steps_active"] <= 2 * stats["decode_steps"]
    assert set(stats) == {"decode_steps", "tokens", "slot_steps_active",
                          "admissions", "evictions", "kv_blocks_live",
                          "kv_blocks_tabled", "kv_kernel_steps",
                          "context_tokens",
                          "kv_blocks_full",
                          "kv_blocks_window", "kv_blocks_uniform",
                          "expert_tokens", "experts_touched", "expert_slots",
                          "expert_weight_visits", "expert_row_tiles_worked",
                          "expert_row_tiles", "decode_steps_overlapped",
                          "tokens_discarded", "state_entries",
                          "state_entries_used", "state_bytes", "gaps",
                          "gaps_stalled", "gap_seconds",
                          "gap_stalled_seconds", "kv_sublayers",
                          "state_sublayers"}
    # The cache's layers are the model's two attention sublayers.
    assert (stats["kv_sublayers"], stats["state_sublayers"]) == (2, 0)
    # Every tick but the two started from an empty engine (the third
    # request waits for a slot) went out behind the one in flight.
    assert stats["decode_steps_overlapped"] == stats["decode_steps"] - 2
    # 3 requests x 4 decode steps at positions 5..8 of 8-token blocks:
    # one live block each, two at position 8, in each of the 2 layers.
    assert stats["kv_blocks_live"] == 2 * 3 * (1 + 1 + 1 + 2)
    # No window layer, no expert layer: those counters stay 0.
    assert all(stats[k] == 0 for k in stats
               if k.startswith("expert") or k in (
                   "kv_blocks_full", "kv_blocks_window", "kv_blocks_uniform"))
    # Plain numbers: ints, and the two sums of seconds.
    seconds = {"gap_seconds", "gap_stalled_seconds"}
    assert all(type(v) is (float if k in seconds else int)
               for k, v in stats.items())
    assert stats["gaps"] == stats["tokens"] - stats["admissions"]


@pytest.mark.parametrize("capture", ["ring", "xplane"], indirect=True)
def test_idle_engine_span(tiny_lm, capture):
    """The serve thread asleep on its wake event is a ``serve.idle``
    span, 50 ms at most; the ``submit()`` that wakes it ends it."""
    import time

    lm, variables = tiny_lm
    recording, box = capture
    engine = InferenceEngine(lm, variables, slots=2, block_size=8)
    try:
        engine.warmup(prompt_lengths=(5,))
        with recording():
            engine.start()
            time.sleep(0.12)  # two waits run out
            req = engine.submit(np.arange(1, 6, dtype=np.int32), 3)
            assert req.wait(timeout=120.0)
            assert engine.stop()
    finally:
        engine.close()
    assert engine.serve_error is None
    idles = _named(box["spans"], "serve.idle")
    woken = [_int_args(s)["woken"] for s in idles]
    assert len(idles) >= 3 and set(woken) == {0, 1}
    # A wait that ran out lasted its 50 ms.
    for span, hit in zip(idles, woken):
        ms = (span[2] - span[1]) / 1e6
        assert ms < 1000.0 and (hit or ms >= 49.0)
    # Idle spans and iterations take turns on the thread.
    iterations = _named(box["spans"], "serve.iteration")
    assert iterations and not any(
        _inside(idle, it) for idle in idles for it in iterations)
    # The first iteration is the one the submit woke.
    first = min(iterations, key=lambda s: s[1])
    before = max((s for s in idles if s[2] <= first[1]), key=lambda s: s[2])
    assert _int_args(before)["woken"] == 1


def test_gap_ledger_counts_the_riders_behind_each_admission(tiny_lm):
    """Requests admitted while others decode: every slot that rode the
    tick in flight waits out the prefill, so ``gaps_stalled`` grows by
    the riders live at each admission; on an injected clock the seconds
    are exact."""
    lm, variables = tiny_lm
    ticks = iter(range(10**6))
    tracer = Tracer(enabled=True)
    prev = tracing.set_tracer(tracer)
    engine = InferenceEngine(lm, variables, slots=4, block_size=8,
                             clock=lambda: float(next(ticks)))
    try:
        engine.warmup(prompt_lengths=(5,))
        prompt = np.arange(1, 6, dtype=np.int32)
        first = engine.submit(prompt, 12)
        engine.step()  # admitted into an empty engine: nobody stalls
        assert engine.stats()["gaps"] == 0
        live_at_admission = []
        joined = []
        for _ in range(3):
            joined.append(engine.submit(prompt, 12))
            live_at_admission.append(engine.active_count)
            engine.step()  # dispatch a tick, then admit behind it
            engine.step()  # that tick lands AFTER the prefill: stalled
            engine.step()  # a clean tick
        assert live_at_admission == [1, 2, 3]
        mid = engine.stats()
        assert mid["gaps_stalled"] == sum(live_at_admission)
        engine.run()
        stats = engine.stats()
    finally:
        engine.close()
        tracing.set_tracer(prev)
    requests = [first] + joined
    assert all(r.status == "finished" for r in requests)
    assert stats["tokens"] == 4 * 12 and stats["admissions"] == 4
    assert stats["gaps"] == stats["tokens"] - len(requests)
    # No admission after the last one joined: nothing more stalls.
    assert stats["gaps_stalled"] == sum(live_at_admission) == 6
    assert 0.0 < stats["gap_stalled_seconds"] <= stats["gap_seconds"]
    # Every gap of a request lies between its first token and its end.
    assert stats["gap_seconds"] <= sum(
        r.finished_t - r.first_token_t for r in requests)
    # The deliveries say the same, tick by tick, and name the culprit.
    delivers = [e for e in tracer.export()["traceEvents"]
                if e["name"] == "serve.decode.deliver"]
    assert [validate_trace_event(e) for e in delivers] == [[]] * len(delivers)
    stalled = [(e["args"]["stalled"], e["args"]["stalled_by"])
               for e in delivers if e["args"]["stalled"]]
    assert stalled == [(n, r.id) for n, r in zip(live_at_admission, joined)]
    assert sum(e["args"]["tokens"] for e in delivers) == stats["gaps"]
    assert sum(e["args"]["gap_ms"] * e["args"]["tokens"]
               for e in delivers) / 1e3 == pytest.approx(stats["gap_seconds"])


# ---------------------------------------------------------------------------
# The one span call: disabled, ring, session
# ---------------------------------------------------------------------------


def test_disabled_span_builds_nothing_and_reads_no_clock(monkeypatch):
    assert not tracing.get_tracer().enabled
    other = tracing.Tracer()

    def explode(*args, **kwargs):
        raise AssertionError("the disabled span path did work")

    monkeypatch.setattr(tracing, "_Span", explode)
    monkeypatch.setattr(tracing, "_Annotation", explode)
    monkeypatch.setattr(tracing.time, "perf_counter_ns", explode)
    monkeypatch.setattr(tracing.time, "perf_counter", explode)
    first = tracing.span("loop.dispatch", update=1, width=6)
    with first as sp:
        sp.set_metadata(evicted=0)
    assert first is tracing.span("serve.iteration", active=0, queued=1)
    assert first is other.span("x", a=1)


@pytest.mark.parametrize("ring", [False, True], ids=["session", "both"])
def test_span_reaches_a_profiler_session(world, tmp_path, ring):
    """With a session recording host events the span is in the xplane
    with its arguments as stats, whether or not the ring records too;
    metadata set inside the span arrives with it."""
    tracer = Tracer(enabled=ring)
    prev = tracing.set_tracer(tracer)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tracing.span("serve.decode.deliver", step=7) as sp:
            sp.set_metadata(evicted=2)
    finally:
        jax.profiler.stop_trace()
        tracing.set_tracer(prev)
    (span,) = _program_spans_of_xplane(str(tmp_path))
    assert span[0] == "serve.decode.deliver"
    assert _int_args(span) == {"step": 7, "evicted": 2}
    ring_spans = _program_spans_of_ring(tracer)
    assert len(ring_spans) == (1 if ring else 0)
    if ring:
        assert ring_spans[0][3] == {"step": 7, "evicted": 2}
        # The ring's interval lies inside the xplane's (it opens later
        # and closes earlier), and both are the same few microseconds.
        assert (ring_spans[0][2] - ring_spans[0][1]) <= (span[2] - span[1])


def test_profile_trace_without_host_events_keeps_the_ring(world, tmp_path):
    """A capture at host_tracer_level 0 comes back with no host plane
    events: the ring, enabled for the capture's length, is the channel,
    and its export lies beside the xplane."""
    import json

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    tracer = Tracer()
    prev = tracing.set_tracer(tracer)
    try:
        with tracing.span("loop.fetch", update=0):
            pass  # before the capture: ring off, nothing kept
        with profile_trace(str(tmp_path), profiler_options=options):
            assert tracer.enabled
            with tracing.span("loop.fetch", update=1):
                pass
        assert not tracer.enabled
    finally:
        tracing.set_tracer(prev)
    assert _program_spans_of_xplane(str(tmp_path)) == []
    with open(tmp_path / SPANS_FILE, encoding="utf-8") as f:
        export = json.load(f)
    fetches = [e for e in export["traceEvents"] if e["name"] == "loop.fetch"]
    assert [e["args"] for e in fetches] == [{"update": 1}]


@pytest.mark.parametrize("name", sorted(HOT_PATH_SPAN_ARGS))
def test_span_contract_names_and_arguments(name):
    """The schema holds an exported hot-path span to the arguments the
    contract lists (what the benchmark's readers rely on), and every name
    of the contract is one the program emits."""
    args = {k: 1 for k in HOT_PATH_SPAN_ARGS[name]}
    event = {"name": name, "ph": "X", "ts": 1.0, "dur": 2.0, "pid": 1,
             "tid": 1, "args": args}
    assert validate_trace_event(event) == []
    args.pop(HOT_PATH_SPAN_ARGS[name][-1])
    (error,) = validate_trace_event(event)
    assert HOT_PATH_SPAN_ARGS[name][-1] in error
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    where = "parallel/loop.py" if name.startswith("loop.") else (
        "serving/engine.py")
    with open(os.path.join(here, "fluxmpi_tpu", where),
              encoding="utf-8") as f:
        assert f'"{name}"' in f.read()


# ---------------------------------------------------------------------------
# Names inside the compiled programs
# ---------------------------------------------------------------------------


def _engine_program_text(tiny_lm, which, slots=2, attention="flash"):
    lm, variables = tiny_lm
    engine = InferenceEngine(lm, variables, slots=slots, block_size=8,
                             attention=attention)
    try:
        cache = engine.cache
        if which == "decode":
            lowered = engine._decode_step.lower(
                variables, cache.k_pools, cache.v_pools,
                (jnp.zeros((slots, engine.max_blocks_per_seq), jnp.int32),),
                jnp.zeros((slots,), jnp.int32), jnp.zeros((slots,), jnp.int32),
                jnp.zeros((slots,), jnp.int32), jnp.zeros((slots,), bool),
            )
        else:
            lowered = engine._prefill_step(8).lower(
                variables, cache.k_pools, cache.v_pools,
                jnp.zeros((8,), jnp.int32), jnp.int32(1),
                (jnp.zeros((engine.max_blocks_per_seq,), jnp.int32),),
            )
        return lowered.as_text(debug_info=True)
    finally:
        engine.close()


def _train_program_text(which):
    lm = TransformerLM(vocab_size=32, max_len=16, num_layers=1, d_model=16,
                       num_heads=2, d_ff=32)
    tokens = jnp.zeros((8, 16), jnp.int32)
    variables = lm.init(jax.random.PRNGKey(0), tokens[:1], train=False)
    optimizer = optax.adamw(1e-3)

    def loss_fn(p, mstate, batch):
        x, y = batch
        return lm.apply(p, x, train=True, targets=y).mean(), mstate

    step = make_train_step(loss_fn, optimizer)
    state = replicate(TrainState.create(variables, optimizer))
    if which == "step":
        return step.lower(state, (tokens, tokens)).as_text(debug_info=True)
    data = (jnp.zeros((32, 16), jnp.int32),) * 2
    window = make_window_program(step, width=2, lbs=8)
    return window.lower(
        state, data, jnp.arange(32, dtype=jnp.int32), np.int32(0)
    ).as_text(debug_info=True)


@pytest.mark.parametrize("program,scope", [
    ("decode", "kv_write"),
    ("decode", "decode_attention"), ("prefill", "kv_write"),
    ("prefill", "prefill_attention"), ("step", "ce_head"),
    ("step", "optimizer_update"), ("window", "batch_gather"),
    ("window", "ce_head"), ("window", "optimizer_update"),
])
def test_compiled_programs_carry_the_programs_scope_names(
    world, tiny_lm, program, scope
):
    text = (
        _engine_program_text(tiny_lm, program)
        if program in ("decode", "prefill") else _train_program_text(program)
    )
    # A named_scope is a path component of the operations' locations
    # (relative inside a scan's body, inside jvp(...) under a gradient).
    assert re.search(rf'[/("]{scope}[/)]', text), scope
    if scope.endswith("_attention"):
        # Outside the kernel's own jit, whose name the chip's compiler
        # gives the kernels' instructions.
        kernel = ("paged_decode_attention" if program == "decode"
                  else "flash_attention")
        assert f"{scope}/jit({kernel})" in text


def test_decode_program_builds_no_per_slot_cache(world):
    """The decode program reads the pool through the block tables: with
    the kernel no value in the lowered program has a ``slots x ... x
    max_len`` (or ``slots x max_blocks x block_size``) K/V shape, and the
    scopes around what it does are still there. Sizes no other dimension
    of the model shares: 3 slots, 5 blocks of 8 = 40 positions."""
    lm = TransformerLM(vocab_size=32, max_len=40, num_layers=2, d_model=24,
                       num_heads=2, d_ff=48)
    variables = lm.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), train=False
    )
    per_slot = re.compile(r"tensor<3x(\d+x)*40x|tensor<3x5x8x")
    text = _engine_program_text((lm, variables), "decode", slots=3)
    assert not per_slot.search(text), per_slot.search(text).group(0)
    for scope in ("kv_write", "decode_attention"):
        assert re.search(rf'[/("]{scope}[/)]', text), scope
    assert "kv_gather" not in text
    # The plain reference gathers one layer's tabled blocks: the pattern
    # does see such a shape where there is one.
    naive = _engine_program_text((lm, variables), "decode", slots=3,
                                 attention="naive")
    assert per_slot.search(naive)
