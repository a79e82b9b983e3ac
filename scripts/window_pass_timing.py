#!/usr/bin/env python
"""Time one window pass (ops/fused.py _apply_window_stack_jit) of each
pass signature the benchmark plans issue, on the chip, at full size.

Each signature is (window offset k, apply_a, apply_b, with_mask) at
rank 1, on a state in the canonical (2, 2^(n-14), 128, 128) device
shape.  A signature is compiled, run once, then timed as ``--reps``
chained passes (the state donated from one pass to the next) with one
wait at the end; the best of ``--trials`` such runs gives ms a pass.
The HBM floor (a read and a write of the state at 819 GB/s) is printed
beside each.

Usage (on a v5e):  python scripts/window_pass_timing.py [--qubits 30]
    [--reps 10] [--trials 2] [--label change] [--out-dir chiprun_out]
Prints one JSON line per signature and writes them all to
<out-dir>/window_pass_timing_<label>.json.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (name, k, apply_a, apply_b, with_mask)
SIGNATURES = (
    ("B", 7, False, True, False),
    ("B", 14, False, True, False),
    ("B_masked", 7, False, True, True),
    ("B_masked", 20, False, True, True),
    ("dual", 7, True, True, False),
    ("dual", 23, True, True, False),
    ("dual_masked", 7, True, True, True),
    ("dual_masked", 23, True, True, True),
    ("A_masked", 18, True, False, True),
    ("mask_only", 17, False, False, True),
)
HBM_BYTES_PER_S = 819e9


def _unitary(rng, dim=128):
    import numpy as np

    z = rng.standard_normal((dim, dim)) + 1j * rng.standard_normal((dim, dim))
    q, r = np.linalg.qr(z)
    return q * (np.diag(r) / np.abs(np.diag(r)))


def _soa(u):
    import numpy as np

    return np.stack([u.real, u.imag]).astype(np.float32)[None]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--qubits", type=int, default=30)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--trials", type=int, default=2)
    ap.add_argument("--label", default="tree")
    ap.add_argument("--out-dir", default="chiprun_out")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from quest_tpu.ops import fused

    n = args.qubits
    shape = (2, 1 << (n - 14), 128, 128)
    amp = float(2.0 ** (-n / 2))
    x = jax.jit(lambda: jnp.full(shape, amp, jnp.float32))()
    rng = np.random.default_rng(n)
    floor_ms = 2 * 2 * (1 << n) * 4 / HBM_BYTES_PER_S * 1e3
    rows = []
    for name, k, apply_a, apply_b, with_mask in SIGNATURES:
        k = min(k, n - 7)            # smaller states: the top window
        a = jnp.asarray(_soa(_unitary(rng)))
        b = jnp.asarray(_soa(_unitary(rng)))
        ph = np.exp(1j * rng.uniform(0, 2 * np.pi, (128, 128)))
        mask = (jnp.asarray(np.stack([ph.real, ph.imag]).astype(np.float32))
                if with_mask else None)

        def one(s):
            return fused._apply_window_stack_jit(
                s, a, b, mask, num_qubits=n, k=k, apply_a=apply_a,
                apply_b=apply_b, precision="highest")

        t0 = time.perf_counter()
        x = one(x)
        x.block_until_ready()
        first_s = time.perf_counter() - t0
        best = None
        for _ in range(args.trials):
            t0 = time.perf_counter()
            for _ in range(args.reps):
                x = one(x)
            x.block_until_ready()
            ms = (time.perf_counter() - t0) / args.reps * 1e3
            best = ms if best is None else min(best, ms)
        row = {"label": args.label, "signature": name, "k": k,
               "ms_per_pass": round(best, 3),
               "hbm_floor_ms": round(floor_ms, 3),
               "first_call_s": round(first_s, 3)}
        print(json.dumps(row), flush=True)
        rows.append(row)
    os.makedirs(args.out_dir, exist_ok=True)
    path = os.path.join(args.out_dir,
                        f"window_pass_timing_{args.label}.json")
    with open(path, "w") as f:
        json.dump({"qubits": n, "device": str(jax.devices()[0]),
                   "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
