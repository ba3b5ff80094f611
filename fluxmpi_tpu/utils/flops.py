"""The live-MFU plane's operation count, and the autotuner's static cost.

The run-health plane (:mod:`fluxmpi_tpu.telemetry.goodput`, fed by
``train_loop``) reports an operator's **live** ``goodput.mfu``: the
FLOPs XLA's ``cost_analysis`` gives for the step's executable
(:func:`cost_analysis_flops`, :func:`executable_flops`), times updates
per second, over the chip's peak (:func:`chip_peak_flops`, :func:`mfu`).
The layout autotuner scores candidates on :func:`executable_cost` plus
:func:`pallas_kernel_cost`.

This is explicitly NOT the benchmark's ``mfu_pct`` (``PERF.md`` §3,
``benchmarks/harness/opsbytes.py``), which counts the operations the
model REQUIRES from its shapes. ``cost_analysis`` counts what the
program EXECUTES: recomputation (remat, a flash backward's second pass
over the scores) reads as useful work, and a Pallas custom call reads
as zero. The live number is for watching one run against itself; a
claim about speed quotes the benchmark's.

Deliberately import-light: nothing here imports jax at module scope
(``cost_analysis_flops`` only touches the compiled-step objects handed
to it).
"""

from __future__ import annotations

from typing import Any

__all__ = [
    "chip_peak_flops",
    "cost_analysis_flops",
    "executable_cost",
    "executable_flops",
    "mfu",
    "pallas_kernel_cost",
    "PEAK_FLOPS",
]

# Peak bf16 FLOPs/s per chip by device_kind substring (public spec
# sheets). Ordered: first substring match wins, so the more specific
# entries ("v5p") come before their prefixes would.
PEAK_FLOPS: tuple[tuple[str, float], ...] = (
    ("v6", 918e12),  # Trillium
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def chip_peak_flops(device_kind: str) -> float | None:
    """Peak bf16 FLOPs/s for a device kind, or None when unknown (CPU,
    future chips not yet in the table)."""
    kind = device_kind.lower()
    for sub, peak in PEAK_FLOPS:
        if sub in kind:
            return peak
    return None


def executable_flops(compiled: Any) -> float | None:
    """FLOPs per call of an ALREADY-compiled executable (the product of
    ``jit(...).lower().compile()``) from XLA's cost model, if exposed —
    the AOT twin of :func:`cost_analysis_flops`, used by the fused-window
    path which compiles its program once up front."""
    try:
        analysis = compiled.cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else None
        if analysis:
            flops = float(analysis.get("flops", 0.0))
            return flops if flops > 0 else None
    except Exception:
        pass
    return None


def executable_cost(compiled: Any) -> dict[str, float] | None:
    """FLOPs AND bytes-accessed per call of an already-compiled
    executable — :func:`executable_flops` grown with the memory-traffic
    term the layout autotuner's static score needs (an all-gather the
    partitioner inserted shows up as bytes accessed, not FLOPs).
    Returns ``{"flops": ..., "bytes_accessed": ...}`` with absent /
    non-positive entries as 0.0, or None when the backend exposes no
    cost model at all."""
    try:
        analysis = compiled.cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else None
        if analysis:
            flops = float(analysis.get("flops", 0.0) or 0.0)
            accessed = float(analysis.get("bytes accessed", 0.0) or 0.0)
            if not accessed:
                # Some backends report only the split per-operand form
                # ("bytes accessed operand N{}", "bytes accessed output").
                accessed = sum(
                    float(v or 0.0)
                    for k, v in analysis.items()
                    if isinstance(k, str) and k.startswith("bytes accessed")
                )
            return {
                "flops": max(flops, 0.0),
                "bytes_accessed": max(accessed, 0.0),
            }
    except Exception:
        pass
    return None


def _iter_subjaxprs(jaxpr: Any):
    """Nested jaxprs reachable from one jaxpr's equation params (cond
    branches, scan/while bodies, pjit/custom_vjp call bodies) — duck-typed
    so this module still never imports jax."""
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for w in vs:
                if hasattr(w, "eqns"):
                    yield eqn, w
                elif hasattr(w, "jaxpr") and hasattr(w.jaxpr, "eqns"):
                    yield eqn, w.jaxpr


def _prod(shape) -> float:
    out = 1.0
    for s in shape:
        out *= float(s)
    return out


def jaxpr_dot_flops(jaxpr: Any) -> float:
    """Total ``dot_general`` FLOPs in a jaxpr, recursing into nested
    jaxprs (2 × output elements × contraction length per dot). ``scan``
    bodies multiply by the trip count; ``cond`` counts every branch and
    ``while`` bodies count once — for kernels that guard compute behind
    a predicate (the flash kernels' masked-tile skip) the result is an
    upper bound on the executed matmul work, which is the right sign for
    a cost model."""
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lhs_c, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval
            contract = _prod(lhs.shape[d] for d in lhs_c)
            total += 2.0 * _prod(eqn.outvars[0].aval.shape) * contract
    for eqn, sub in _iter_subjaxprs(jaxpr):
        inner = jaxpr_dot_flops(sub)
        if eqn.primitive.name == "scan":
            inner *= float(eqn.params.get("length", 1))
        total += inner
    return total


def pallas_kernel_cost(jaxpr: Any) -> dict[str, float] | None:
    """Analytic cost of every ``pallas_call`` in a (closed) jaxpr —
    the kernel-plane term XLA's cost model cannot see (a pallas kernel
    lowers to an opaque custom call, so its matmuls and HBM traffic
    report as zero; a layout autotuner scoring on XLA cost alone would
    think flash attention is free).

    FLOPs: per-grid-point ``dot_general`` work of the kernel body
    (block-shaped avals) × the grid size. Bytes: the streamed sizes of
    the call's global operands and results — the flash-style ideal where
    each operand crosses HBM O(1) times, which is exactly the advantage
    the score should see over a dense attend's materialized [s, s]
    scores. Returns ``{"flops", "bytes_accessed", "calls"}`` or None
    when the jaxpr holds no pallas calls. Tile-skip predicates (causal /
    fully-masked tiles) are not modeled — the FLOPs term is an upper
    bound."""
    closed = getattr(jaxpr, "jaxpr", None)
    root = closed if closed is not None and hasattr(closed, "eqns") else jaxpr
    calls: list[Any] = []

    def find(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.append(eqn)
        for _, sub in _iter_subjaxprs(jx):
            find(sub)

    find(root)
    if not calls:
        return None
    flops = 0.0
    bytes_accessed = 0.0
    for eqn in calls:
        body = eqn.params.get("jaxpr")
        grid = getattr(eqn.params.get("grid_mapping"), "grid", ()) or ()
        if body is not None:
            flops += _prod(grid) * jaxpr_dot_flops(body)
        for v in (*eqn.invars, *eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                bytes_accessed += _prod(aval.shape) * float(
                    getattr(aval.dtype, "itemsize", 4)
                )
    return {
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "calls": float(len(calls)),
    }


def cost_analysis_flops(step: Any, state: Any, data: Any) -> float | None:
    """FLOPs per compiled step call straight from XLA's cost model, if
    exposed. ``step`` is anything with a ``.lower(state, data)`` (a
    ``jax.jit`` wrapper or a :func:`~fluxmpi_tpu.parallel.make_train_step`
    product); lowering does not execute or consume donated buffers, so
    it is safe to call on the live pre-first-dispatch state."""
    try:
        return executable_flops(step.lower(state, data).compile())
    except Exception:
        return None


def mfu(
    flops_per_step: float | None,
    rate: float,
    n_dev: int,
    device_kind: str | None = None,
    *,
    peak: float | None = None,
) -> float | None:
    """Model FLOPs utilization per chip: FLOPs/step × steps/sec ÷
    (chips × peak), rounded to 4 places.

    Returns None when the FLOPs estimate or the peak is unknown
    (``peak`` overrides the ``device_kind`` table lookup — the live
    tracker's hook for tests and unlisted chips). The RAW value is
    returned even when it exceeds 1.0 — an impossible number means a
    broken clock or FLOPs estimate, and the *caller* decides whether to
    discard it or to surface it."""
    if not flops_per_step:
        return None
    if peak is None:
        if device_kind is None:
            return None
        peak = chip_peak_flops(device_kind)
    if peak is None or peak <= 0 or n_dev < 1:
        return None
    return round(flops_per_step * rate / (n_dev * peak), 4)
