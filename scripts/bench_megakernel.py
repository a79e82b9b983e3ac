#!/usr/bin/env python
"""Window-megakernel A/B (ISSUE 18 / docs/design.md §29):
QT_MEGAKERNEL=on vs off on a dense-window drain.

Two measurements over the same random dense circuit (the bench.py
config-2 generator shape — per-layer 1q Haar unitaries + an alternating
CNOT ladder, every target shard-local so the planner forms dense fused
windows):

* ``plan``  — the planned program executed as a chained device loop
  (circuit.execute_plan_chained): device/XLA truth of the fused route
  with zero per-call harness overhead.  The two arms are timed
  INTERLEAVED and the headline ``megakernel_speedup_x`` is the MEDIAN
  of the per-rep paired off/on ratios (gates >= 1.3x): shared-machine
  load drift moves both halves of a pair together, so the paired
  median survives contention that makes a best-of quotient swing by
  tens of percent.  The megawin route does every grouped pass per
  state block load where the per-pass route pays one full HBM
  (interpret: full-state materialization) round trip per gate stack.
* ``drain`` — the same circuit drained through the full fusion path
  (gateFusion) in both arms under the process mesh, with
  QT_PERM_FAST=off pinned in BOTH arms (this is the DENSE-window A/B;
  perm-splitting the CNOT ladders leaves nothing groupable at small
  n): amplitude parity <= 1e-10 between arms (the megakernel reuses
  the per-pass kernel's block body, so the diff is exactly 0.0),
  ``model_drift_total == 0`` in BOTH arms (§21 prices the grouping
  identically by construction), the on arm actually routes through
  megawin groups (``megakernel_dispatch_total{route=mega}`` > 0), and
  the per-window HBM-round-trip gauge drops.

Usage: python scripts/bench_megakernel.py [--n 14] [--depth 60]
       [--reps 4] [--floor 1.3] [--no-check]
``make verify-mega`` runs it twice: once scalar (the speedup gate — the
megakernel's overhead win is calibrated against a single-device
process) and once on the 8-device virtual mesh with ``--n 18 --floor
0`` so the drain half exercises the SHARDED dispatch route (parity,
drift, and megawin routing under shard_map; nloc = n-3 must reach 15
before a sharded remap window holds more than one fused window to
group).  --no-check skips every gating assert; --floor overrides just
the speedup floor (0 disables it).
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

import quest_tpu as qt  # noqa: E402
from quest_tpu import circuit as C  # noqa: E402
from quest_tpu import telemetry as T  # noqa: E402
from quest_tpu.models import circuits  # noqa: E402

PARITY_TOL = 1e-10
SPEEDUP_FLOOR = 1.3


def _arg(flag, default, cast=int):
    return cast(sys.argv[sys.argv.index(flag) + 1]) \
        if flag in sys.argv else default


def _haar_units(n, depth, seed=7):
    """(depth, n) complex Haar 2x2s — one per (layer, qubit)."""
    rng = np.random.default_rng(seed)
    z = (rng.standard_normal((depth, n, 2, 2))
         + 1j * rng.standard_normal((depth, n, 2, 2)))
    us = np.empty_like(z)
    for d in range(depth):
        for t in range(n):
            q, r = np.linalg.qr(z[d, t])
            us[d, t] = q * (np.diag(r) / np.abs(np.diag(r)))
    return us


def _plan_ab(n, depth, us, k, reps):
    """Both QT_MEGAKERNEL arms of the chained-plan loop, INTERLEAVED:
    each rep times off then on back to back and contributes one paired
    off/on ratio — the shared-machine drift that moves a whole rep
    moves both arms of the pair, so the median ratio is the
    drift-resistant speedup (a best-of-reps quotient is not: one slow
    draw on either side swings it by tens of percent)."""
    us_soa = np.stack([us.real, us.imag], axis=2)
    arms = {}
    for flag in ("off", "on"):
        os.environ["QT_MEGAKERNEL"] = flag
        plan = C.plan_circuit(circuits.bench_gate_list(n, depth, us_soa), n)
        arms[flag] = {"plan": plan, "st": C.stats(plan),
                      "ops": C.plan_to_device(plan, jnp.float32)}

    def once(flag):
        os.environ["QT_MEGAKERNEL"] = flag
        a = circuits.zero_state_canonical(n)
        t0 = time.perf_counter()
        for _ in range(k):
            a = C.execute_plan_chained(a, arms[flag]["ops"], n)
        amp = float(circuits.amp00_canonical(a))
        return time.perf_counter() - t0, amp

    once("off")  # compile + warm both executables
    once("on")
    best = {"off": float("inf"), "on": float("inf")}
    amp = {}
    ratios = []
    for _ in range(reps):
        s_off, amp["off"] = once("off")
        s_on, amp["on"] = once("on")
        best["off"] = min(best["off"], s_off)
        best["on"] = min(best["on"], s_on)
        ratios.append(s_off / max(s_on, 1e-9))
    out = {}
    for flag in ("off", "on"):
        st = arms[flag]["st"]
        out[flag] = {"megakernel": flag,
                     "seconds": round(best[flag], 4),
                     "programs_per_iter": len(arms[flag]["plan"]),
                     "megawin_groups": st.get("megawin", 0),
                     "megawin_grouped_ops": st.get("megawin_ops", 0),
                     "prob_check": amp[flag]}
    return out, round(statistics.median(ratios), 2)


def _apply_layers(q, n, depth, us):
    """The same circuit through the QuEST API, for the fusion drain."""
    for d in range(depth):
        for t in range(n):
            qt.unitary(q, t, us[d, t])
        for t in range(n - 1):
            if (d + t) % 2 == 0:
                qt.controlledNot(q, t, t + 1)


def _drain_arm(env, flag, n, depth, us, reps):
    """One arm of the full fusion-path drain: parity amplitudes, drift,
    and the megakernel route telemetry."""
    os.environ["QT_MEGAKERNEL"] = flag
    best = float("inf")
    amps = None
    drift = mega = fallback = 0
    trips = None
    for rep in range(reps + 1):  # rep 0 = warm-up/compile
        T.reset()
        q = qt.createQureg(n, env)
        qt.initDebugState(q)
        qt.startGateFusion(q)
        _apply_layers(q, n, depth, us)
        t0 = time.perf_counter()
        qt.stopGateFusion(q)
        amps = np.asarray(q.amps)  # canonical read joins the timed cost
        seconds = time.perf_counter() - t0
        if rep:
            best = min(best, seconds)
        drift = int(T.counter_total("model_drift_total"))
        mega = int(T.counter_sum("megakernel_dispatch_total", route="mega"))
        fallback = int(T.counter_sum("megakernel_dispatch_total",
                                     route="fallback"))
        windows = T.counter_total("fusion_windows_total")
        trips = (T.counter_total("fusion_passes_total") / windows
                 if windows else None)
    return {"megakernel": flag, "seconds": round(best, 4),
            "drift": drift, "mega_dispatches": mega,
            "fallback_dispatches": fallback,
            "hbm_round_trips_per_window": trips}, amps


def run(n=14, depth=60, reps=4, devices=None):
    """``devices`` pins the mesh width (None = every visible device).
    The scalar speedup calibration wants devices=1 even when a virtual
    8-device mesh is forced process-wide (bench_suite's CPU smoke mode):
    sharding a small-n drain leaves nloc < the 14-qubit window and no
    fused windows form at all."""
    env = qt.createQuESTEnv() if devices is None \
        else qt.createQuESTEnv(num_devices=devices)
    prev_mode = T.mode_name()
    prev_flag = os.environ.get("QT_MEGAKERNEL")
    T.configure("on")
    prev_perm = os.environ.get("QT_PERM_FAST")
    try:
        us = _haar_units(n, depth)
        plans, speedup = _plan_ab(n, depth, us, 3, reps)
        plan_off, plan_on = plans["off"], plans["on"]
        # The drain half measures ROUTING (parity, drift, telemetry), and
        # this is the DENSE-window A/B: pin QT_PERM_FAST=off in both arms
        # so the CNOT ladders fuse into the dense windows the megakernel
        # targets instead of splitting every dense run down to a single
        # window (at n=14 a perm-split dense run is one 1q layer = one
        # winfused op, which nothing can group).
        os.environ["QT_PERM_FAST"] = "off"
        drain_off, a_off = _drain_arm(env, "off", n, depth, us, max(1, reps - 1))
        drain_on, a_on = _drain_arm(env, "on", n, depth, us, max(1, reps - 1))
    finally:
        for key, val in (("QT_MEGAKERNEL", prev_flag),
                         ("QT_PERM_FAST", prev_perm)):
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val
        T.reset()
        T.configure(prev_mode)
    return {
        "bench": "megakernel_ab",
        "n": n, "depth": depth, "reps": reps,
        "backend": jax.default_backend(),
        "devices": env.num_devices,
        "plan": {"off": plan_off, "on": plan_on},
        "drain": {"off": drain_off, "on": drain_on},
        "megakernel_speedup_x": speedup,
        "drain_speedup_x": round(
            drain_off["seconds"] / max(drain_on["seconds"], 1e-9), 2),
        "max_abs_err": float(np.abs(a_on - a_off).max()),
    }


def main():
    rec = run(n=_arg("--n", 14), depth=_arg("--depth", 60),
              reps=_arg("--reps", 4), devices=_arg("--devices", None))
    floor = _arg("--floor", SPEEDUP_FLOOR, float)
    print(json.dumps(rec), flush=True)
    if "--no-check" in sys.argv:
        return 0
    ok = True
    if rec["max_abs_err"] > PARITY_TOL:
        print(f"FAIL: on/off amplitude mismatch {rec['max_abs_err']:.3e} "
              "— the megakernel must be bit-identical to the per-pass "
              "route (same block body, same order)", file=sys.stderr)
        ok = False
    for arm in ("off", "on"):
        if rec["drain"][arm]["drift"]:
            print(f"FAIL: {arm}-arm model_drift_total="
                  f"{rec['drain'][arm]['drift']} (§21 must price both "
                  "QT_MEGAKERNEL arms identically)", file=sys.stderr)
            ok = False
    if not rec["drain"]["on"]["mega_dispatches"]:
        print("FAIL: on arm dispatched no megawin groups — the dense "
              "windows did not route through the megakernel",
              file=sys.stderr)
        ok = False
    if rec["drain"]["off"]["mega_dispatches"]:
        print("FAIL: off arm dispatched megawin groups "
              f"({rec['drain']['off']['mega_dispatches']})",
              file=sys.stderr)
        ok = False
    t_off = rec["drain"]["off"]["hbm_round_trips_per_window"]
    t_on = rec["drain"]["on"]["hbm_round_trips_per_window"]
    if t_off is not None and t_on is not None and not t_on < t_off:
        print(f"FAIL: HBM round trips per window did not drop "
              f"(off={t_off} on={t_on})", file=sys.stderr)
        ok = False
    if floor and rec["megakernel_speedup_x"] < floor:
        print(f"FAIL: megakernel_speedup_x {rec['megakernel_speedup_x']}x "
              f"below the {floor}x acceptance floor",
              file=sys.stderr)
        ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
