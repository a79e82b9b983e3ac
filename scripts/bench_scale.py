"""Single-chip scaling runs: config-2 shape and full QFT at 28-31q.

Default execution is CHAINED (circuit.execute_plan_chained): each pass is
its own cached jitted program and the state stays in the canonical
(2, nb, 128, 128) view between calls, so
  * compile cost = a few seconds per distinct pass signature (the
    monolithic whole-circuit trace took 7-14 min at 28-29q), and
  * no full-state layout copy at program boundaries (the copy that OOMed
    the 30q monolithic program: 8 GB args + 8 GB copy > 15.75 GB HBM).
Set QT_SCALE_MONOLITHIC=1 for the old one-program path.

Timing: steady-state best-of-N wall, device estimate = wall minus the
measured scalar-fetch overhead, and a K-diff (2 circuits minus 1) arm.

Usage: python scripts/bench_scale.py rand:30 qft:30 ...
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from functools import partial
import numpy as np

import quest_tpu as qt
from quest_tpu import circuit as C
from quest_tpu.models import circuits
from quest_tpu.ops import calculations, kernels

MONO = os.environ.get("QT_SCALE_MONOLITHIC") == "1"
REPS = int(os.environ.get("QT_SCALE_REPS", "5"))
# the canonical-view helpers need n >= 15 (nb >= 2 tiles); small sizes
# run the monolithic path, where compile cost is a non-issue anyway
CHAIN_MIN_QUBITS = 15


def fetch_overhead():
    s = jnp.float32(1.0)
    f = jax.jit(lambda x: x + 1)
    float(f(s))
    t0 = time.perf_counter()
    for _ in range(5):
        float(f(s))
    return (time.perf_counter() - t0) / 5


# shared canonical-view helpers live in quest_tpu.models.circuits
_zero_canonical = circuits.zero_state_canonical
_amp00 = circuits.amp00_canonical
_prob_top_zero = circuits.prob_top_zero_canonical
build_gates = circuits.bench_gate_list


def run_random(n, depth=20):
    fn, us = circuits.build_random_circuit(n, depth, seed=7)
    us = np.asarray(us)
    mono = MONO or n < CHAIN_MIN_QUBITS

    if mono:
        @partial(jax.jit, donate_argnums=0)
        def prog(amps, us):
            amps = C.apply_circuit(amps, build_gates(n, depth, us), n)
            return calculations.calc_prob_of_outcome_statevec(
                amps, num_qubits=n, target=n - 1, outcome=0)

        def run_once():
            a = jnp.asarray(kernels.init_zero_state(1 << n, np.float32))
            t0 = time.perf_counter()
            p = float(prog(a, us))
            return time.perf_counter() - t0, p
    else:
        t0 = time.perf_counter()
        ops = C.plan_to_device(C.plan_circuit(build_gates(n, depth, us), n),
                               jnp.float32)
        plan_s = time.perf_counter() - t0

        def run_once(k=1):
            a = _zero_canonical(n)
            t0 = time.perf_counter()
            for _ in range(k):
                a = C.execute_plan_chained(a, ops, n)
            p = float(_prob_top_zero(a))
            return time.perf_counter() - t0, p

    t0 = time.perf_counter()
    _, p = run_once()
    compile_s = time.perf_counter() - t0
    best = None
    for _ in range(REPS):
        dt, p = run_once()
        best = dt if best is None else min(best, dt)
    r = {"workload": f"{n}q depth-{depth} random",
         "mode": "monolithic" if mono else "chained",
         "compile_s": round(compile_s, 1), "wall_s": round(best, 3), "prob": p}
    if not mono:
        r["plan_s"] = round(plan_s, 2)
        # K-diff: two chained circuits minus one (removes fetch + dispatch)
        t2 = min(run_once(2)[0] for _ in range(3))
        r["kdiff_device_s"] = round(t2 - best, 3)
        r["passes"] = len(ops)
    return r


def run_qft(n):
    mono = MONO or n < CHAIN_MIN_QUBITS
    if mono:
        @partial(jax.jit, donate_argnums=0)
        def prog(amps):
            amps = C.fused_qft(amps, n, 0, n)
            return amps[0, 0]

        def run_once(k=1):
            a = jnp.asarray(kernels.init_zero_state(1 << n, np.float32))
            t0 = time.perf_counter()
            float(prog(a))
            return time.perf_counter() - t0
    else:
        last_amp0 = [None]

        def run_once(k=1):
            a = _zero_canonical(n)
            t0 = time.perf_counter()
            for _ in range(k):
                a = C.fused_qft(a, n, 0, n)
            last_amp0[0] = float(_amp00(a))
            return time.perf_counter() - t0

    t0 = time.perf_counter()
    run_once()
    compile_s = time.perf_counter() - t0
    best = min(run_once() for _ in range(REPS))
    r = {"workload": f"{n}q full QFT",
         "mode": "monolithic" if mono else "chained",
         "compile_s": round(compile_s, 1), "wall_s": round(best, 3)}
    if not mono:
        # oracle self-check: QFT|0> is uniform, amp[0] = 2^(-n/2)
        r["amp0"] = last_amp0[0]
        r["amp0_expect"] = 2.0 ** (-n / 2)
        t2 = min(run_once(2) for _ in range(3))
        r["kdiff_device_s"] = round(t2 - best, 3)
    return r


if __name__ == "__main__":
    ov = fetch_overhead()
    print(json.dumps({"fetch_overhead_s": round(ov, 3), "mode":
                      "monolithic" if MONO else "chained"}), flush=True)
    for arg in sys.argv[1:]:
        kind, n = arg.split(":")
        try:
            r = run_random(int(n)) if kind == "rand" else run_qft(int(n))
            r["device_s_est"] = round(r["wall_s"] - ov, 3)
            print(json.dumps(r), flush=True)
        except Exception as e:
            print(json.dumps({"workload": arg, "error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
