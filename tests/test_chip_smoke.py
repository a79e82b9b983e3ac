"""chip_smoke.py on the CPU: its phases at 12-14 qubits by direct call
(the TPU check lives in main(), which these calls bypass), and the
script itself refusing to run without a TPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke
import quest_tpu as qt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def env1():
    return qt.createQuESTEnv(num_devices=1)


def test_random_circuit_and_inverse_return_to_zero(env1):
    ok, q = chip_smoke.phase_random_circuit(qt, env1, 14)
    assert ok
    amps = np.asarray(q.amps)
    assert abs(amps[0, 0] - 1.0) < 1e-9


def test_qft_and_pauli_phases_on_one_register(env1):
    ok, q = chip_smoke.phase_random_circuit(qt, env1, 14)
    assert ok
    assert chip_smoke.phase_qft(qt, q)
    assert chip_smoke.phase_pauli(qt, q)


def test_dense_reference_phase(env1):
    assert chip_smoke.phase_reference(qt, env1, 12)


def test_reference_state_matches_the_api_on_a_sharded_register(env):
    """The generator's dense reference agrees with an 8-shard drain."""
    n = 12
    ops = chip_smoke.random_circuit(n, layers=6, seed=3)
    q = qt.createQureg(n, env)
    with qt.gateFusion(q):
        chip_smoke.apply_circuit(qt, q, ops)
    got = np.asarray(q.amps)
    want = chip_smoke.reference_state(n, ops)
    np.testing.assert_allclose(got[0] + 1j * got[1], want, atol=1e-10)


def test_sharded_vs_single_phase(env, env1):
    assert chip_smoke.phase_sharded_vs_single(qt, env, env1, 12)


def test_random_circuit_is_seeded_and_long_range():
    a = chip_smoke.random_circuit(14)
    assert a == chip_smoke.random_circuit(14)
    assert len(a) == chip_smoke.LAYERS * (14 + 7 + 1) - chip_smoke.LAYERS // 2
    assert ("cnot", 0, 13) in a and ("cnot", 6, 7) in a


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_script_exits_nonzero_on_the_cpu():
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO, capture_output=True,
        text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        try:
            assert json.loads(line).get("ok") is not True
        except json.JSONDecodeError:
            pass
    assert '"ok": true' not in proc.stdout
