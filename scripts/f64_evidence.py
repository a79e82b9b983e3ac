"""f64-on-TPU evidence: the same workload in f32 and f64.

Runs config 1 (12q hadamard + controlledRotateX chain + calcProbOfOutcome)
and a config-2-shaped random circuit at qreal = double (set_precision(2),
jax_enable_x64) on the current default backend, dumping the probability
and the full amplitude array.  Run once on the TPU and once with
QT_F64_CPU=1 (forces the CPU backend); compare_f64.py diffs the dumps.

The reference's north star asks for bit-exact calcProbOfOutcome between
the TPU and CPU backends at double precision; XLA's TPU f64 is software
emulation, so the honest claim is measured here, not assumed.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

if os.environ.get("QT_F64_CPU") == "1":
    jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np

import quest_tpu as qt

qt.set_precision(2)


def config1(env):
    n = 12
    q = qt.createQureg(n, env)
    qt.hadamard(q, 0)
    for t in range(1, n):
        qt.controlledRotateX(q, t - 1, t, 0.3 + 0.01 * t)
    t0 = time.perf_counter()
    p = qt.calcProbOfOutcome(q, n - 1, 0)
    wall = time.perf_counter() - t0
    return np.asarray(q.amps), p, wall


def config2(env, n):
    rng = np.random.default_rng(7)
    q = qt.createQureg(n, env)
    with qt.gateFusion(q):
        for d in range(6):
            for t in range(n):
                u, _ = np.linalg.qr(rng.standard_normal((2, 2))
                                    + 1j * rng.standard_normal((2, 2)))
                qt.unitary(q, t, u)
            for t in range(d % 2, n - 1, 2):
                qt.controlledNot(q, t, t + 1)
    t0 = time.perf_counter()
    p = qt.calcProbOfOutcome(q, n - 1, 0)
    wall = time.perf_counter() - t0
    return np.asarray(q.amps), p, wall


if __name__ == "__main__":
    tag = "cpu" if os.environ.get("QT_F64_CPU") == "1" else jax.default_backend()
    env = qt.createQuESTEnv(num_devices=1)
    n2 = int(os.environ.get("QT_F64_N2", "20"))
    a1, p1, w1 = config1(env)
    t0 = time.perf_counter()
    a2, p2, w2 = config2(env, n2)
    total2 = time.perf_counter() - t0
    np.savez(f"/tmp/f64_{tag}.npz", a1=a1, p1=p1, a2=a2, p2=p2)
    print(f"backend={tag} dtype={a1.dtype} "
          f"cfg1: p={p1!r} cfg2(n={n2}): p={p2!r} "
          f"cfg2 total={total2:.2f}s")
