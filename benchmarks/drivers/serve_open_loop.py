"""Driver ``serve_open_loop``: a serving cell through
``InferenceEngine.start()`` / ``submit(..., on_token=)``.

Open loop: requests are sent on a schedule fixed before the run (a
Poisson process at the cell's fixed rate, from the seed), whether or not
earlier ones have finished. Every token is stamped on the host clock as
it is delivered. A pre-roll of arrivals, part of set-up, brings the
engine to its steady occupancy; arrivals go on for a short post-roll
after the window so that requests due late in it still see the load.
Latencies count from when a request was DUE, not from when it was sent.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from harness import compare, device as device_mod, manifest, traffic
from harness import trace as trace_mod


def _percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def run(cell, *, seed: int, seconds: float, trace: bool, phases,
        control: str | None = None, broken: str | None = None) -> dict:
    import jax

    import fluxmpi_tpu as fm

    from drivers.train import seed_key

    spec, cfg = cell.spec, cell.config
    devices = jax.devices()[: cell.chips]
    fm.init(devices=devices, compileplane=True)
    phases.mark("init")
    key = seed_key(seed)
    plan = make_plan(cell, seed, seconds)
    engine = build_engine(cell, key, phases)
    try:
        engine.warmup(prompt_lengths=tuple(sorted(
            {len(r["prompt"]) for _, _, reqs in plan for r in reqs}
        )))
        phases.mark("compile_or_cache_load")
        out = _drive(cell, engine, plan, seconds, trace, phases, broken)
        device = device_mod.report(devices)
    finally:
        engine.close()
    out["values"]["peak_hbm_gb"] = device["memory_peak_bytes"] / 1e9
    out["device"] = device

    # ---- free the program's state, then the reference ------------------
    del engine
    fm.shutdown()
    jax.clear_caches()
    t_ref = time.perf_counter()
    sample = _sample(out.pop("finished"), seed, spec["reference"]["sample"])
    readings = cell.reference.served_gaps(
        cfg, key, [(r["prompt"], r["served"]) for r in sample],
        control=control,
    )
    print("served tokens against the reference:", readings, flush=True)
    limit = spec["limits"]["served_logit_gap_mean"]
    comparison = compare.Comparison()
    comparison.add("served_logit_gap_mean", readings["served"]["mean"], limit)
    out.update(comparison=comparison, cell=cell,
               reference_s=time.perf_counter() - t_ref)
    if control is not None:
        ctl = compare.Comparison()
        ctl.add("served_logit_gap_mean", readings["control"]["mean"], limit)
        out["control"] = ctl
    return out


def make_plan(cell, seed: int, seconds: float) -> list:
    """``(phase, base_s, requests)``: the pre-roll, the window and the
    post-roll, each drawn from the seed at the mix's fixed rate."""
    mix, vocab = cell.spec["traffic"], cell.config["vocab_size"]
    pre_s = mix["preroll_s"]
    return [
        ("pre", 0.0, traffic.arrivals(mix, pre_s, seed, vocab, stream=1)),
        ("window", pre_s, traffic.arrivals(mix, seconds, seed, vocab)),
        ("post", pre_s + seconds,
         traffic.arrivals(mix, mix["postroll_s"], seed, vocab, stream=2)),
    ]


def build_engine(cell, key, phases):
    """The engine over weights made on the device from the seed, in one
    jitted call."""
    import jax

    from fluxmpi_tpu.serving import InferenceEngine

    spec, cfg, prog = cell.spec, cell.config, cell.program
    params = jax.jit(
        lambda k: prog.to_program(cell.reference.make_weights(cfg, k), cfg)[0]
    )(key)
    jax.block_until_ready(params)
    phases.mark("weights_and_data")
    return InferenceEngine(
        prog.build_model(cfg, "naive"), params, attention=spec["attention"],
        **spec["engine"],
    )



def _sample(finished: list[dict], seed: int, n: int) -> list[dict]:
    """``n`` of the finished requests, drawn from the seed, the longest
    among them."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: len(r["prompt"]) + len(r["served"]))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    picks = rng.permutation(len(rest))[: max(0, n - 1)]
    return [longest] + [rest[i] for i in picks]


def _drive(cell, engine, plan, seconds, trace, phases, broken) -> dict:
    from fluxmpi_tpu.telemetry.compileplane import get_compile_monitor

    spec = cell.spec
    mix = spec["traffic"]
    mon = get_compile_monitor()
    pre_s = mix["preroll_s"]
    end_s = pre_s + seconds + mix["postroll_s"]
    schedule = sorted(
        ((base + r["due"], phase, r) for phase, base, reqs in plan
         for r in reqs), key=lambda e: e[0],
    )
    records: list[dict] = []
    marks: dict[str, dict] = {}

    def snapshot(name):
        marks[name] = {"t": time.perf_counter(),
                       "decode_steps": engine._decode_steps,
                       "compiles": mon.events}
        if name == "window_start":
            phases.mark("pre_roll")
            marks[name]["setup_s"] = phases.since_start()

    tracer = None
    reduced_box: dict = {}
    if trace:
        logdir = os.path.join(manifest.ROOT, ".bench_out", "trace", cell.name)
        lo = pre_s + min(spec["trace"]["at_s"], max(0.0, seconds - 1.0))
        hi = min(lo + spec["trace"]["seconds"], pre_s + seconds)

        def trace_window(t_zero):
            time.sleep(max(0.0, t_zero + lo - time.perf_counter()))
            trace_mod.start(logdir, spec["trace"].get("host_tracer_level", 1))
            t_a = time.perf_counter()
            time.sleep(max(0.0, t_zero + hi - time.perf_counter()))
            import jax

            reduced_box["seconds"] = time.perf_counter() - t_a
            jax.profiler.stop_trace()  # writing the file takes seconds
            reduced_box["logdir"] = logdir

    engine.start()
    t_zero = time.perf_counter()
    if trace:
        tracer = threading.Thread(target=trace_window, args=(t_zero,),
                                  name="bench-tracer")
        tracer.start()
    boundaries = [(pre_s, "window_start"), (pre_s + seconds, "window_end")]
    for due, phase, r in schedule:
        while boundaries and boundaries[0][0] <= due:
            at, name = boundaries.pop(0)
            time.sleep(max(0.0, t_zero + at - time.perf_counter()))
            snapshot(name)
        time.sleep(max(0.0, t_zero + due - time.perf_counter()))
        rec = {"due": t_zero + due, "phase": phase, "prompt": r["prompt"],
               "stamps": [], "served": []}

        def on_token(tok, rec=rec):
            rec["stamps"].append(time.perf_counter())
            rec["served"].append(int(tok))

        rec["sent"] = time.perf_counter()
        rec["request"] = engine.submit(
            r["prompt"], r["max_new_tokens"], on_token=on_token
        )
        records.append(rec)
    for at, name in boundaries:
        time.sleep(max(0.0, t_zero + at - time.perf_counter()))
        snapshot(name)
    time.sleep(max(0.0, t_zero + end_s - time.perf_counter()))
    t_stop = time.perf_counter()
    stopped = engine.stop()
    if tracer is not None:
        tracer.join(timeout=60.0)
    if engine.serve_error is not None:
        raise RuntimeError(f"serving loop failed: {engine.serve_error!r}")
    if not stopped:
        raise RuntimeError("serving thread did not stop")

    w0, w1 = marks["window_start"]["t"], marks["window_end"]["t"]
    window = [r for r in records if r["phase"] == "window"]
    in_window = sum(1 for r in records for t in r["stamps"] if w0 <= t < w1)
    firsts_in_window = sum(
        1 for r in records if r["stamps"] and w0 <= r["stamps"][0] < w1
    )
    ttft, gaps, queue_wait, failed = [], [], [], 0
    for r in window:
        req = r["request"]
        if not r["stamps"]:
            failed += 1  # rejected, or no first token by the stop
            ttft.append(t_stop - r["due"])
            continue
        ttft.append(r["stamps"][0] - r["due"])
        gaps.extend(np.diff(r["stamps"]))
        if req.admitted_t is not None:
            queue_wait.append(req.admitted_t - req.submitted_t)
    late = [r["sent"] - r["due"] for r in records]
    steps = marks["window_end"]["decode_steps"] - marks["window_start"]["decode_steps"]
    values = {
        "serve_tokens_per_s": in_window / (w1 - w0),
        "ttft_p90_ms": 1e3 * _percentile(ttft, 90),
        "itl_p95_ms": 1e3 * _percentile(gaps, 95) if gaps else float("inf"),
        "setup_s": marks["window_start"]["setup_s"],
        "ttft_p50_ms": 1e3 * _percentile(ttft, 50),
        "itl_p50_ms": 1e3 * _percentile(gaps, 50) if gaps else None,
        "queue_wait_p90_ms": (1e3 * _percentile(queue_wait, 90)
                              if queue_wait else None),
        "gen_late_p95_ms": 1e3 * _percentile(late, 95),
        "compiles_in_window": float(
            marks["window_end"]["compiles"] - marks["window_start"]["compiles"]
        ),
        "decode_steps_in_window": steps,
        "decode_occupancy_pct": (
            100.0 * (in_window - firsts_in_window) / (steps * engine.slots)
            if steps else None
        ),
        "window_elapsed_s": w1 - w0,
        "requests_due_in_window": len(window),
    }
    reduced = None
    if reduced_box:
        reduced = trace_mod.reduce(
            trace_mod.load_events(reduced_box["logdir"]), reduced_box["seconds"]
        )
    finished = [
        {"prompt": r["prompt"], "served": list(r["served"])}
        for r in window if r["request"].status == "finished"
    ]
    if broken == "token_altered" and finished:
        # The test's broken timed path: one served token is not the one
        # the model put first.
        worst = max(finished, key=lambda r: len(r["prompt"]) + len(r["served"]))
        worst["served"][0] = (worst["served"][0] + 7) % cell.config["vocab_size"]
    return {"values": values, "trace": reduced, "finished": finished,
            "attempted": len(window), "failed": failed}
