#!/usr/bin/env python
"""Dispatch-count / per-program-overhead breakdown of the bench.py
config-2 headline — the r04->r05 regression bisection (ISSUE 18).

## Why

A headline iteration of 27 separately dispatched programs is bounded
below by 27 x the host's per-program dispatch cost: when a paired K=2
estimator lands exactly on the sustained-dispatch ceiling with zero
spread, the dispatch jitter has swallowed the device marginal.  This
script measures that per-program cost on the host it runs on.

## The fix this script quantifies

The lever arm of the dispatch-bound regime is PROGRAMS PER ITERATION.
The §29 window megakernel (QT_MEGAKERNEL) regroups consecutive fused
window passes into single-dispatch megawin groups: this script builds
the config-2 plan in both arms and reports the program count, the
per-op window-size histogram, a measured per-program dispatch-overhead
probe on THIS host, and the predicted dispatch-bound iteration floor
(programs x overhead) next to the measured chained-loop marginal — so
any future round can check mechanically which regime it measured.

Usage: python scripts/bench_dispatch.py [--n 16] [--depth 20] [--reps 3]
(defaults CPU-shrunk; on a TPU run --n 26 --depth 20 for the true
headline shape).  Prints one JSON line; diagnostic only, always exits 0.
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from quest_tpu import circuit as C  # noqa: E402
from quest_tpu.models import circuits  # noqa: E402


def _arg(flag, default, cast=int):
    return cast(sys.argv[sys.argv.index(flag) + 1]) \
        if flag in sys.argv else default


def dispatch_overhead_s(calls=200):
    """Median per-call cost of dispatching a TRIVIAL jitted program and
    blocking on its result: the fixed per-program overhead every
    separately dispatched plan op pays on this host."""
    @jax.jit
    def bump(x):
        return x + 1.0

    x = jnp.zeros(16384, jnp.float32)
    bump(x).block_until_ready()
    ts = []
    for _ in range(calls):
        t0 = time.perf_counter()
        bump(x).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def _plan_breakdown(flag, n, depth, us):
    """Plan the config-2 circuit under one QT_MEGAKERNEL arm: program
    count and the per-op window-size (k) histogram."""
    os.environ["QT_MEGAKERNEL"] = flag
    plan = C.plan_circuit(circuits.bench_gate_list(n, depth, us), n)
    hist: dict = {}
    for op in plan:
        if op[0] == "winfused":
            hist[f"k={op[1]}"] = hist.get(f"k={op[1]}", 0) + 1
        elif op[0] == "megawin":
            key = "mega[" + ",".join(str(s[1]) for s in op[1]) + "]"
            hist[key] = hist.get(key, 0) + 1
        else:
            hist[op[0]] = hist.get(op[0], 0) + 1
    return plan, {"megakernel": flag, "programs_per_iter": len(plan),
                  "op_histogram": hist,
                  "stats": {k: v for k, v in C.stats(plan).items() if v}}


def _measured_marginal(plan, n, k=3, reps=3):
    """Best-of-reps chained-loop marginal for one planned program —
    device/XLA truth with no per-program dispatch in the loop."""
    ops = C.plan_to_device(plan, jnp.float32)

    def run():
        a = circuits.zero_state_canonical(n)
        t0 = time.perf_counter()
        for _ in range(k):
            a = C.execute_plan_chained(a, ops, n)
        float(circuits.amp00_canonical(a))
        return time.perf_counter() - t0

    run()
    return min(run() for _ in range(reps)) / k


def run(n=16, depth=20, reps=3):
    _fn, us = circuits.build_random_circuit(n, depth, seed=7)
    us = np.asarray(us)
    prev = os.environ.get("QT_MEGAKERNEL")
    try:
        arms = {}
        overhead = dispatch_overhead_s()
        for flag in ("off", "on"):
            plan, breakdown = _plan_breakdown(flag, n, depth, us)
            breakdown["chained_marginal_s"] = round(
                _measured_marginal(plan, n, reps=reps), 4)
            # the dispatch-bound floor an op-at-a-time driver pays: one
            # host dispatch per separately dispatched program
            breakdown["dispatch_floor_s"] = round(
                breakdown["programs_per_iter"] * overhead, 4)
            arms[flag] = breakdown
    finally:
        if prev is None:
            os.environ.pop("QT_MEGAKERNEL", None)
        else:
            os.environ["QT_MEGAKERNEL"] = prev
    return {
        "bench": "dispatch_breakdown",
        "n": n, "depth": depth,
        "backend": jax.default_backend(),
        "per_program_dispatch_s": round(overhead, 6),
        "arms": arms,
        "programs_saved": (arms["off"]["programs_per_iter"]
                           - arms["on"]["programs_per_iter"]),
        "dispatch_floor_saved_s": round(
            arms["off"]["dispatch_floor_s"] - arms["on"]["dispatch_floor_s"],
            4),
        "verdict": (
            "an iteration of N separately dispatched programs is bounded "
            "below by N x dispatch_overhead_s; megakernel grouping "
            "shrinks programs/iter"),
    }


def main():
    rec = run(n=_arg("--n", 16), depth=_arg("--depth", 20),
              reps=_arg("--reps", 3))
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
