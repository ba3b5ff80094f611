"""The single seam between the repo and jax API spellings that drift.

Everything whose name or signature has changed between jax releases
gets one wrapper here, and every other module imports the wrapper — the
next jax bump is a one-file fix. The ``jax-compat-drift`` fluxlint rule
enforces the discipline: direct use of the drifted spellings
(``jax.lax.axis_size``, pallas ``*CompilerParams`` classes,
``shard_map(..., check_vma=)``) outside this file is a finding.

Each wrapper carries exactly ONE spelling: the installed jax's
(``pyproject.toml`` pins ``jax>=0.9``). No branch here probes for an
older release.

- :data:`shard_map` — ``jax.shard_map``.
- :func:`shard_map_unchecked` — shard_map with the replication checker
  off (``check_vma=False``).
- :func:`axis_size` — ``jax.lax.axis_size``; raises ``NameError`` on an
  unbound axis (callers' ``except NameError`` fallbacks rely on it).
- :func:`pallas_tpu_compiler_params` — ``pltpu.CompilerParams``.
- :func:`enable_cpu_cross_process_collectives` — opt the CPU backend
  into its gloo cross-process collectives before the backend client is
  created. Without it, a multi-process CPU world (the localhost
  jax.distributed harness tier-1 uses) fails every device collective
  with "Multiprocess computations aren't implemented on the CPU
  backend"; with it, the same program runs the real cross-process
  paths. TPU/GPU backends never consult the option.
"""

from __future__ import annotations

import os

import jax

shard_map = jax.shard_map

__all__ = [
    "axis_size",
    "enable_cpu_cross_process_collectives",
    "pallas_tpu_compiler_params",
    "shard_map",
    "shard_map_unchecked",
]


def enable_cpu_cross_process_collectives() -> bool:
    """Turn on the CPU backend's gloo cross-process collectives.

    Must run BEFORE the first backend use (the client is created once);
    ``runtime.init(distributed=True)`` calls it just ahead of
    ``jax.distributed.initialize`` when the selected platform is CPU.
    Returns True when the option was applied, False when the platform
    is not CPU or the user already picked an implementation explicitly.
    """
    platforms = (
        os.environ.get("JAX_PLATFORMS")
        or getattr(jax.config, "jax_platforms", None)
        or ""
    )
    if "cpu" not in str(platforms).split(","):
        return False
    if os.environ.get("JAX_CPU_COLLECTIVES_IMPLEMENTATION"):
        return False  # explicit user choice wins
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    # Gloo's TCP transport cannot tolerate two in-flight collectives on
    # the same pair (it aborts with "op.preamble.length <= op.nbytes"),
    # and the CPU client's async dispatch pipelines exactly that way —
    # serialize dispatch for correctness on multi-process CPU worlds.
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    return True


def shard_map_unchecked(body, mesh, in_specs, out_specs):
    """``shard_map`` with the replication checker off (its auto-psum on
    cotangents of replicated inputs would double-count explicit collectives
    in the body)."""
    return shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def axis_size(name):
    """Size of the bound mesh axis ``name`` (a concrete python int).

    Raises ``NameError("unbound axis name: ...")`` outside a binding
    context, which callers that probe for an unbound axis (ring/ulysses
    init paths) catch.
    """
    return jax.lax.axis_size(name)


def pallas_tpu_compiler_params(**kwargs):
    """The pallas TPU compiler-params struct (``pltpu.CompilerParams``).
    Imported lazily so this module stays cheap for non-pallas users of
    the seam."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)
