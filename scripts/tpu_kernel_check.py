"""On-hardware correctness check for the Pallas flash-attention kernels.

The test suite runs the kernels in CPU interpret mode (conftest), so the
compiled Mosaic lowering itself only executes on a chip. This script runs
the forward AND both backward kernels against the dense oracle (same
segment semantics as the suite's ``tests/_oracles.py``) across the
feature matrix: plain / causal / windowed / segmented / GQA, in f32 and
bf16 (production dtype), plus in-kernel dropout determinism and keep-rate
sanity. Tolerances follow the arithmetic that really ran (``_tolerance``):
tight only where the matmuls are true f32, which on the chip they are
not — the MXU's default precision rounds f32 operands to bf16, in these
kernels as in XLA's own dots. Every case passes
``interpret=`` explicitly and, when compiled, asserts that its lowered
program holds the kernels (``tpu_custom_call``) — a case can never pass
through the interpreter by accident.

``CASES`` / :func:`run_case` / :func:`run_dropout_case` /
:func:`run_matrix` are importable: ``chip_smoke.py`` calls them for its
``kernels`` phase.

Usage:  python scripts/tpu_kernel_check.py   (one JSON line per case)
        --allow-cpu   rehearse in interpret mode off-TPU
        --quick       one small case only (interpret mode is slow)
Exit code 1 if any case fails its tolerance, 2 without a TPU.
"""

from __future__ import annotations

import json
import os
import sys
import zlib

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax
import jax.numpy as jnp

from _oracles import dense_seg_attention  # the suite's single source
from fluxmpi_tpu.ops import flash_attention
from fluxmpi_tpu.ops.flash_attention import padding_to_segment_ids

# The feature matrix: (name, run_case keyword arguments).
CASES: tuple[tuple[str, dict], ...] = (
    ("plain_f32", {}),
    ("causal_f32", {"causal": True}),
    ("window_f32", {"causal": True, "window": 128}),
    ("segments_f32", {"segments": True}),
    ("gqa_causal_f32", {"causal": True, "h_kv": 2}),
    ("causal_bf16", {"causal": True, "dtype": jnp.bfloat16}),
    ("gqa_window_bf16", {"causal": True, "window": 128, "h_kv": 2,
                         "dtype": jnp.bfloat16}),
    ("long_causal_bf16", {"seq": 2048, "causal": True,
                          "dtype": jnp.bfloat16}),
)
QUICK_CASES: tuple[tuple[str, dict], ...] = (
    ("seg_gqa_window_f32", {"seq": 128, "segments": True, "causal": True,
                            "window": 64, "h_kv": 2}),
)

# Forward, dq and dkv: what one differentiated flash call must lower to.
_KERNELS_PER_GRAD = 3

# Tolerances, as a fraction of the reference tensor's largest magnitude
# (floored at 1). True f32 arithmetic — f32 inputs in interpret mode —
# is held to 2e-3. Everything else went through bf16: bf16 inputs and
# outputs, or f32 inputs on the chip, where the MXU's default precision
# rounds matmul operands to bf16 (measured on a v5e, PR 21: f32 cases
# err 2e-3..2e-2 against the full-precision oracle, the same as the
# bf16 cases). bf16 keeps 8 bits, so one rounding is 2**-8 of a value;
# 2**-6 leaves room for the few roundings between inputs and outputs.
_F32_TOL = 2e-3
_BF16_TOL = 2.0 ** -6


def _tolerance(dtype, interpret: bool) -> float:
    exact_f32 = interpret and jnp.dtype(dtype) == jnp.float32
    return _F32_TOL if exact_f32 else _BF16_TOL


def dense_oracle(q, k, v, qseg, kseg, causal=False, window=None):
    """The suite's oracle (single source for segment-mask semantics),
    plus a GQA kv-head repeat, an f32 upcast and full-precision matmuls
    (a TPU's default f32 matmul rounds its inputs to bf16 — fine for a
    model, not for the reference a kernel is held to)."""
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    with jax.default_matmul_precision("highest"):
        return dense_seg_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), qseg, kseg, causal=causal, window=window,
        )


def _kernel_count(lowered, interpret: bool) -> int:
    """``tpu_custom_call`` sites in a lowered program (0 is expected —
    and only accepted — in interpret mode)."""
    return 0 if interpret else lowered.as_text().count("tpu_custom_call")


def run_case(name, *, interpret, seq=512, h=8, h_kv=None, d=64,
             causal=False, window=None, segments=False,
             dtype=jnp.float32) -> dict:
    """One matrix case: output, dq, dk, dv of the kernels against the
    oracle. Returns the case record (``ok`` says whether every error is
    within its limit and, compiled, the kernels are in the program)."""
    key = jax.random.PRNGKey(zlib.crc32(name.encode()) % (2**31))
    kq, kk, kv, kc, ks = jax.random.split(key, 5)
    b = 2
    h_kv = h_kv or h
    q = jax.random.normal(kq, (b, seq, h, d), dtype)
    k = jax.random.normal(kk, (b, seq, h_kv, d), dtype)
    v = jax.random.normal(kv, (b, seq, h_kv, d), dtype)
    cot = jax.random.normal(kc, (b, seq, h, d), jnp.float32)
    if segments:
        lengths = jax.random.randint(ks, (b,), seq // 2, seq)
        seg = padding_to_segment_ids(jnp.arange(seq)[None, :] < lengths[:, None])
        valid = (seg != 0).astype(jnp.float32)[:, :, None, None]
    else:
        seg = jnp.ones((b, seq), jnp.int32)
        valid = jnp.ones((b, seq, 1, 1), jnp.float32)

    def flash_loss(q, k, v):
        o = flash_attention(q, k, v, causal=causal, window=window,
                            segment_ids=seg if segments else None,
                            interpret=interpret)
        return jnp.sum(o.astype(jnp.float32) * cot * valid), o

    def dense_loss(q, k, v):
        o = dense_oracle(q, k, v, seg, seg, causal=causal, window=window)
        return jnp.sum(o * cot * valid), o

    lowered = jax.jit(
        jax.value_and_grad(flash_loss, (0, 1, 2), has_aux=True)
    ).lower(q, k, v)
    kernels = _kernel_count(lowered, interpret)
    (_, o_f), g_f = lowered.compile()(q, k, v)
    (_, o_d), g_d = jax.value_and_grad(dense_loss, (0, 1, 2),
                                       has_aux=True)(q, k, v)
    tol = _tolerance(dtype, interpret)
    errs, limits = {}, {}
    for nm, got, ref, mask in zip(
        ("out", "dq", "dk", "dv"), (o_f, *g_f), (o_d, *g_d),
        (valid, 1.0, 1.0, 1.0),
    ):
        ref = ref.astype(jnp.float32) * mask
        errs[nm] = float(jnp.max(jnp.abs(got.astype(jnp.float32) * mask - ref)))
        limits[nm] = tol * max(1.0, float(jnp.max(jnp.abs(ref))))
    compiled_ok = interpret or kernels >= _KERNELS_PER_GRAD
    ok = compiled_ok and all(errs[nm] <= limits[nm] for nm in errs)
    return {"case": name, "dtype": jnp.dtype(dtype).name, "ok": ok,
            "interpret": interpret, "tpu_custom_calls": kernels,
            "tol": tol, "max_abs_err": errs, "limit": limits}


def run_dropout_case(*, interpret, seq=512) -> dict:
    """In-kernel dropout: same seed → same mask, another seed → another,
    and the 1/keep scaling preserves the mean magnitude."""
    key = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(key, 3)
    b, h, d = 2, 4, 64
    q = jax.random.normal(kq, (b, seq, h, d))
    k = jax.random.normal(kk, (b, seq, h, d))
    v = jax.random.normal(kv, (b, seq, h, d))
    rate = 0.25

    def att(s):
        return flash_attention(q, k, v, causal=True, dropout_rate=rate,
                               dropout_seed=s, interpret=interpret)

    kernels = _kernel_count(jax.jit(att).lower(jnp.uint32(123)), interpret)
    o1, o2, o3 = att(jnp.uint32(123)), att(jnp.uint32(123)), att(jnp.uint32(456))
    deterministic = bool(jnp.array_equal(o1, o2))
    differs = bool(jnp.any(o1 != o3))
    o0 = flash_attention(q, k, v, causal=True, interpret=interpret)
    # With 1/keep scaling the mean magnitude is preserved in expectation;
    # a dropped-prob output differs from the no-dropout one almost surely.
    changed_frac = float(jnp.mean((o1 != o0).astype(jnp.float32)))
    ratio = float(jnp.mean(jnp.abs(o1)) / jnp.mean(jnp.abs(o0)))
    ok = (
        (interpret or kernels >= 1)
        and deterministic and differs and changed_frac > 0.5
        and 0.8 < ratio < 1.3
    )
    return {"case": "dropout", "ok": ok, "interpret": interpret,
            "tpu_custom_calls": kernels, "deterministic": deterministic,
            "seed_sensitivity": differs,
            "changed_frac": round(changed_frac, 4),
            "mean_abs_ratio": round(ratio, 4)}


def run_matrix(*, interpret: bool, quick: bool = False) -> list[dict]:
    """Every case of the matrix (``quick``: one small case, for an
    interpret-mode rehearsal), one record each, printed as it lands."""
    records = []
    for name, kwargs in QUICK_CASES if quick else CASES:
        records.append(run_case(name, interpret=interpret, **kwargs))
        print(json.dumps(records[-1]), flush=True)
    if not quick:
        records.append(run_dropout_case(interpret=interpret))
        print(json.dumps(records[-1]), flush=True)
    return records


def main():
    interpret = "--allow-cpu" in sys.argv
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform,
                      "kind": dev.device_kind}), flush=True)
    if dev.platform != "tpu" and not interpret:
        print(json.dumps({"aborted": "not a TPU"}), flush=True)
        sys.exit(2)
    records = run_matrix(interpret=interpret, quick="--quick" in sys.argv)
    ok = all(r["ok"] for r in records)
    print(json.dumps({"all_ok": ok}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
