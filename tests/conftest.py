"""Test config: simulate an 8-device TPU world on CPU.

The TPU analogue of the reference's self-spawning MPI test harness
(reference: test/runtests.jl:11-16 runs every test file under
``mpiexec -n N``): instead of N OS processes over localhost MPI, we run one
process with N virtual XLA CPU devices
(``--xla_force_host_platform_device_count``) and exercise the real XLA
collective path over the simulated mesh — no mock backend.
"""

import os

# Tests run on the CPU, whatever the environment selects: the test world
# is 8 simulated devices.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import contextlib  # noqa: E402

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def world():
    """Initialized runtime over the 8-device CPU mesh."""
    import fluxmpi_tpu as fm

    mesh = fm.init(verbose=True)
    yield mesh


@contextlib.contextmanager
def _own_runtime():
    """Let a test init()/shutdown() its own runtime and planes, as a
    script on the chip does, and hand the session fixture's world back
    untouched."""
    from fluxmpi_tpu import runtime
    from fluxmpi_tpu.telemetry import compileplane

    saved = (runtime._state.initialized, runtime._state.mesh,
             runtime._state.plan)
    runtime._state.initialized = False
    runtime._state.mesh = None
    runtime._state.plan = None
    try:
        yield
    finally:
        runtime.shutdown()
        compileplane.set_compile_monitor(None)
        (runtime._state.initialized, runtime._state.mesh,
         runtime._state.plan) = saved


@pytest.fixture()
def own_runtime():
    """``with own_runtime(): fm.init(...)``: see :func:`_own_runtime`."""
    return _own_runtime


@pytest.fixture()
def nworkers(world):
    import fluxmpi_tpu as fm

    return fm.total_workers()
