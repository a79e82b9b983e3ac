"""rc32x4 at rehearsal size: the spread reference of the
``random_layers_x4`` family against the dense one over four devices, the
whole ``rc32x4.floquet`` run over four devices, and the shard-exchange
readers on a hand-built trace.

The four-device tests run in a child process with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``: this process's
JAX has one CPU device (the parametrised rehearsal of
``test_rehearsal.py`` runs the cell there, unsharded).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rc32x4.py -q
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from benchmark import tracefile
from benchmark.metrics import (_exchange, exchange_roofline,
                               exchange_s_per_circuit)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BIG_SEED = 2 ** 31 + 12345


def _four_devices(code: str) -> dict:
    """Run ``code`` in a child with four CPU devices; the JSON object it
    prints last."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("n,h", [(16, 4), (17, 3), (18, 2)])
def test_spread_reference_matches_dense(n, h):
    """Chunks on all four devices: device bits n-1 and n-2 pair chunks on
    two devices (every layer rotates them; the long-range CNOTs hit them
    as target), slot bits pair chunks on one."""
    got = _four_devices(f"""
        import json
        import jax
        import numpy as np
        from benchmark import reference as R
        from benchmark.families.random_layers_x4 import Family, SpreadState

        n, h = {n}, {h}
        fam = Family({{"qubits": n, "layers": 3, "structure_seed": 5,
                      "reference_chunk_bits": h, "chips": 4}})
        ops = fam.ops(fam.draw_params(np.random.default_rng(n)))
        # every kind of CNOT across chunks: device/device, device ->
        # local, local -> device, lowest chunk bit -> local and -> device,
        # device -> lowest chunk bit
        ops += [("cnot", n - 1, n - 2), ("cnot", n - 2, 3),
                ("cnot", 3, n - 2), ("cnot", n - h, 2),
                ("cnot", n - h, n - 1), ("cnot", n - 1, n - h),
                ("cnot", n - 2, n - 1)]
        want = R.dense_state(n, ops)
        st = SpreadState(n, h, jax.devices())
        st.apply(ops)
        devs = {{d.id for c in st.chunks for d in c.devices()}}
        re, im = np.asarray(st.host_state()).astype(np.float64)
        mask = 0b1011011 | (3 << (n - 2))
        print(json.dumps({{
            "devices": len(devs), "sbits": st.sbits,
            "err": float(np.max(np.abs(re + 1j * im - want))),
            "z": abs(st.z_expectation(mask)
                     - R.dense_z_expectation(want, mask)),
            "dist": st.sq_dist(st.host_state())}}))
    """)
    assert got["devices"] == 4 and got["sbits"] == h - 2
    assert got["err"] < 1e-6 and got["z"] < 1e-6
    assert got["dist"] == 0.0


def test_rc32x4_floquet_runs_correct_over_four_devices():
    """The cell at 16 qubits over four CPU devices (2^14 amplitudes a
    shard: the canonical layout), correct with no compile in the window;
    the reference control over the same devices is not."""
    got = _four_devices(f"""
        import json
        import sys
        sys.path.insert(0, "benchmark/tests")
        from benchmark import run
        from benchmark.control import reference_control
        from rehearsal_size import small_limits

        size = {{"qubits": 16, "reference_chunk_bits": 4}}
        limits = small_limits("rc32x4.floquet")
        result, checks, compiles = run.run_cell(
            "rc32x4.floquet", {BIG_SEED}, 1.0, False, require_chip=False,
            overrides=size, limits=limits)
        control = reference_control("rc32x4.floquet", {BIG_SEED},
                                    on_chip=False, overrides=size,
                                    limits=limits)
        print(json.dumps({{"correct": result["correct"],
                          "failed": result["failed"],
                          "attempted": result["attempted"],
                          "compiles": compiles, "checks": checks,
                          "control": control["correct"],
                          "control_checks": control["checks"]}}))
    """)
    assert got["correct"], got["checks"]
    assert got["failed"] == 0 and got["attempted"] >= 1
    assert got["compiles"] == 0
    assert not got["control"], got["control_checks"]


def _op(device, name, start, end, nbytes):
    return tracefile.Op(device, name, start, end, "other", nbytes)


def _trace():
    """Two chips; chip 0 runs a start/done pair (in flight 1.0 -> 3.0)
    and an overlapping synchronous permute (2.5 -> 3.5), chip 1 one pair
    (1.0 -> 2.0); a kernel and a pair outside [0, 10] do not count."""
    x = 1 << 30                     # one transfer's result: 1 GiB
    tr = tracefile.Trace(devices=[0, 1])
    tr.ops = [
        _op(0, "collective-permute-start", 1.0, 1.1, 3 * x),
        _op(0, "_apply_window_stack_jit", 1.1, 2.0, 4 * x),
        _op(0, "collective-permute-done", 2.9, 3.0, 3 * x),
        _op(0, "collective-permute", 2.5, 3.5, 2 * x),
        _op(1, "collective-permute-start", 1.0, 1.2, 3 * x),
        _op(1, "collective-permute-done", 1.8, 2.0, 3 * x),
        _op(1, "collective-permute-start", 11.0, 11.1, 3 * x),
        _op(1, "collective-permute-done", 11.5, 12.0, 3 * x),
    ]
    return tr


def test_exchange_readers_count_each_transfer_once(monkeypatch):
    import types

    tr = _trace()
    acc = _exchange.per_device(tr, 0.0, 10.0)
    x = 1 << 30
    assert acc == {0: (2.5, 2 * x), 1: (1.0, 1 * x)}
    ctx = types.SimpleNamespace(trace=tr, w0=0.0, w1=10.0, circuits=2)
    assert exchange_s_per_circuit.read(ctx) == pytest.approx(
        (2.5 + 1.0) / 2 / 2)
    monkeypatch.setattr(exchange_roofline, "ici_peak", lambda: 1e9)
    assert exchange_roofline.read(ctx) == pytest.approx(
        100.0 * 3 * x / (3.5 * 1e9))
    none = types.SimpleNamespace(trace=tracefile.Trace(devices=[0]),
                                 w0=0.0, w1=10.0, circuits=2)
    assert exchange_s_per_circuit.read(none) is None
    assert exchange_roofline.read(none) is None


def test_ici_peak_is_the_v5e_interconnect():
    assert _exchange.ICI_BYTES_PER_S["TPU v5 lite"] == 200e9
