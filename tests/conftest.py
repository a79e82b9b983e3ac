"""Test harness configuration.

Mirrors the reference test strategy (SURVEY.md §4): the same suite runs on a
virtual multi-device mesh — the analogue of `mpirun -np 8` on one box
(examples/README.md:404-407) — by forcing 8 XLA host-platform devices
BEFORE jax initialises.  Tests compare against a dense NumPy oracle
(tests/oracle.py, the analogue of tests/utilities.cpp QVector/QMatrix) in
double precision.
"""

import os

# The suite runs on the CPU (jax.config.update below, on top of the
# JAX_PLATFORMS=cpu the test commands set); the device-count flag must be
# set before jax initialises.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Belt and braces: a pytest plugin may have imported jax before this conftest,
# in which case the env var alone is too late (the backend isn't initialised
# until first use, so the config update below still wins).
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402

import quest_tpu as qt  # noqa: E402

qt.set_precision(2)

# Reference suite fixes NUM_QUBITS=5 (tests/utilities.hpp:36)
NUM_QUBITS = 5


@pytest.fixture(scope="session")
def env():
    return qt.createQuESTEnv()


@pytest.fixture
def psi(env):
    q = qt.createQureg(NUM_QUBITS, env)
    qt.initDebugState(q)
    return q


@pytest.fixture
def rho(env):
    q = qt.createDensityQureg(NUM_QUBITS, env)
    qt.initDebugState(q)
    return q
