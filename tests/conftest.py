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

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def world():
    """Initialized runtime over the 8-device CPU mesh."""
    import fluxmpi_tpu as fm

    mesh = fm.init(verbose=True)
    yield mesh


@pytest.fixture()
def nworkers(world):
    import fluxmpi_tpu as fm

    return fm.total_workers()
