"""CPU rehearsal of the benchmark at 14 qubits: the traffic generators,
the references and the whole run of every cell, with the comparison that
decides ``correct``.  Runs on JAX's CPU backend with the Pallas kernels
interpreted:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os

import numpy as np
import pytest

from benchmark import reference as R
from benchmark import run
from rehearsal_size import SMALL, small_limits

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BIG_SEED = 2 ** 31 + 12345


def _workloads():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("workload", _workloads())
def test_stream_same_sizes_for_every_seed(workload):
    cell = run.load_cell(workload, overrides=SMALL)
    shapes = set()
    for seed in (0, 7, BIG_SEED):
        a = run.Stream(cell.mix, cell.family, seed)
        b = run.Stream(cell.mix, cell.family, seed)
        ca = [a.circuit(i) for i in range(4)]
        cb = [b.circuit(i) for i in range(4)]
        for x, y in zip(ca, cb):      # the same seed, the same circuits
            assert x.init == y.init and x.read == y.read
            assert np.array_equal(np.asarray(x.params, float),
                                  np.asarray(y.params, float),
                                  equal_nan=True)
        shapes.add(tuple((c.init is None, c.init[0] if c.init else None,
                          np.shape(c.params), np.shape(c.read)) for c in ca))
    assert len(shapes) == 1


def test_amp_reads_are_fresh_and_in_range():
    cell = run.load_cell("qft30.basis", overrides=SMALL)
    s = run.Stream(cell.mix, cell.family, BIG_SEED)
    idx = [s.circuit(i).read for i in range(4)]
    assert all(len(r) == cell.mix["read_args"]["count"] for r in idx)
    assert all(0 <= k < 1 << cell.family.n for r in idx for k in r)
    assert len(set(idx)) == 4


@pytest.mark.parametrize("x", [0, 1, 0b10110011101001])
def test_qft_amplitude_matches_closed_form(x):
    from benchmark.families.qft import Family

    fam = Family({"qubits": 14})
    ref = next(fam.reference(("basis", x), [None], on_chip=False))
    hi, lo = R.qft_factors(14, x, 7)
    want = np.outer(hi, lo).reshape(-1)
    for k in (0, 1, 127, 128, 5000, (1 << 14) - 1):
        assert abs(ref.amplitude(k) - want[k]) < 1e-15


@pytest.mark.parametrize("n,h", [(14, 2), (17, 2), (16, 3), (22, 0)])
def test_chunked_reference_matches_dense(n, h):
    from benchmark.families.random_layers import Family

    fam = Family({"qubits": n, "layers": 3, "structure_seed": 5,
                  "reference_chunk_bits": h})
    ops = fam.ops(fam.draw_params(np.random.default_rng(n)))
    want = R.dense_state(n, ops)
    st = R.ChunkedState(n, h)
    st.apply(ops)
    re, im = st.host_state().astype(np.float64)
    got = re + 1j * im
    assert np.max(np.abs(got - want)) < 1e-6
    mask = 0b1011011 | (1 << (n - 1))
    assert abs(st.z_expectation(mask)
               - R.dense_z_expectation(want, mask)) < 1e-6


@pytest.mark.parametrize("low", [0, 3, 5, 10])
def test_qft_closed_form_matches_fft(low):
    n, x = 10, 0b1011001101
    psi = np.zeros(1 << n, complex)
    psi[x] = 1.0
    want = np.fft.ifft(psi) * np.sqrt(1 << n)
    hi, lo = R.qft_factors(n, x, low)
    assert np.max(np.abs(np.outer(hi, lo).reshape(-1) - want)) < 1e-12


@pytest.mark.parametrize("workload", _workloads())
def test_cell_runs_and_is_correct_at_14_qubits(workload):
    result, checks, compiles = run.run_cell(
        workload, BIG_SEED, 1.0, False, require_chip=False,
        overrides=SMALL, limits=small_limits(workload))
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert compiles == 0
    assert set(checks) == set(run.load_cell(workload).limits)
    assert list(result)[-1] == "checks"
