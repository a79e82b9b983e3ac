#!/usr/bin/env python3
"""End-to-end smoke run of the qt.* main path on a TPU, at deployment size.

One chip (the default): a 30-qubit f32 state-vector — 8 GiB of the chip's
16 GiB HBM — through createQuESTEnv / createQureg / gateFusion:

  phase 1  a seeded random circuit (20 layers of rotateX/Y/Z on every
           qubit, CNOT ladders at alternating offsets, one long-range
           controlledNot(q, n-1-q) per layer), then its exact inverse;
           total probability 1 and |amp 0|^2 = 1;
  phase 2  initClassicalState + applyFullQFT; P(qubit t = 0) = 0.5;
  phase 3  calcExpecPauliSum on |0...0> (no workspace register): Z strings
           give 1, X strings give 0;
  phase 4  phase 1's generator at 14 qubits against a dense NumPy
           reference, amplitude by amplitude.

``--chips 4`` runs phase 1 at 32 qubits sharded over four chips (checking
each device holds its share of the register), and phase 1's generator at
28 qubits on four chips against one chip.

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The script exits non-zero without printing that line when JAX finds no
TPU, when a phase fails a check, or when anything raises.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # a four-chip host
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

SEED = 20261015
LAYERS = 20
TOL_PROB = 1e-4
TOL_EXACT = 1e-5


def random_circuit(n: int, layers: int = LAYERS, seed: int = SEED) -> list:
    """Seeded gate list: per layer a random rotateX/Y/Z on every qubit, a
    CNOT ladder at offset layer % 2, and controlledNot(q, n-1-q) with
    q = layer % (n // 2) — the long-range gates put windows on high
    qubits (and, on a sharded register, on the mesh bits)."""
    rng = np.random.default_rng(seed)
    ops = []
    for layer in range(layers):
        for q in range(n):
            ops.append(("rot", int(rng.integers(3)), q,
                        float(rng.uniform(0.0, 2.0 * np.pi))))
        for q in range(layer % 2, n - 1, 2):
            ops.append(("cnot", q, q + 1))
        q = layer % (n // 2)
        ops.append(("cnot", q, n - 1 - q))
    return ops


def apply_circuit(qt, q, ops, inverse: bool = False) -> None:
    """Issue ``ops`` (or their exact inverse) through the public API."""
    rot = (qt.rotateX, qt.rotateY, qt.rotateZ)
    for op in (reversed(ops) if inverse else ops):
        if op[0] == "rot":
            rot[op[1]](q, op[2], -op[3] if inverse else op[3])
        else:
            qt.controlledNot(q, op[1], op[2])


def reference_state(n: int, ops) -> np.ndarray:
    """Dense complex128 state after ``ops`` on |0...0> (rotations are
    exp(-i angle/2 P), QuEST's convention)."""
    psi = np.zeros(1 << n, np.complex128)
    psi[0] = 1.0
    paulis = (np.array([[0, 1], [1, 0]], np.complex128),
              np.array([[0, -1j], [1j, 0]], np.complex128),
              np.array([[1, 0], [0, -1]], np.complex128))
    for op in ops:
        if op[0] == "rot":
            _, axis, q, ang = op
            u = (np.cos(ang / 2) * np.eye(2)
                 - 1j * np.sin(ang / 2) * paulis[axis])
            v = psi.reshape(-1, 2, 1 << q)
            psi = np.einsum("ab,ibj->iaj", u, v).reshape(-1)
        else:
            _, c, t = op
            idx = np.arange(1 << n)
            sel = ((idx >> c) & 1) == 1
            src = idx.copy()
            src[sel] ^= 1 << t
            psi = psi[src]
    return psi


class _Phase:
    """Wall and backend-compile seconds of one phase, its checks, and the
    megawin groups its drains planned and dispatched."""

    _compile = [0.0]
    _listening = [False]

    def __init__(self, name: str, **info):
        self.name = name
        self.info = info
        self.checks = {}
        self.ok = True

    @classmethod
    def _listen(cls):
        if cls._listening[0]:
            return
        import jax.monitoring

        def on_duration(event, duration, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                cls._compile[0] += duration

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        cls._listening[0] = True

    def __enter__(self):
        from quest_tpu import fusion, telemetry

        self._listen()
        self.c0 = self._compile[0]
        self.keys0 = set(fusion._plan_cache)
        self.mega0 = telemetry.counter_sum("megakernel_dispatch_total",
                                           route="mega")
        self.t0 = time.perf_counter()
        return self

    def check(self, name: str, value: float, ok: bool) -> None:
        self.checks[name] = float(value)
        self.ok = self.ok and bool(ok)

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        from quest_tpu import fusion, native, telemetry

        wall = time.perf_counter() - self.t0
        planned = 0
        for key, (program, _arrays, _fp) in fusion._plan_cache.items():
            if key in self.keys0:
                continue
            for part in program:
                if part[0] == "plan":
                    planned += sum(1 for sk in part[1] if sk[0] == "megawin")
        executed = telemetry.counter_sum(
            "megakernel_dispatch_total", route="mega") - self.mega0
        line = {"phase": self.name, **self.info,
                "compile_s": round(self._compile[0] - self.c0, 3),
                "wall_s": round(wall, 3), "checks": self.checks,
                "megawin_groups": {"planned": planned,
                                   "executed": int(executed)},
                "planner": "windowed/" + ("native"
                                          if native.native_available()
                                          else "python"),
                "ok": self.ok}
        print(json.dumps(line), flush=True)
        return False


def phase_random_circuit(qt, env, n: int, name: str = "random_circuit"):
    """Phase 1: the circuit then its inverse; returns (ok, register)."""
    ops = random_circuit(n)
    with _Phase(name, qubits=n, devices=env.num_devices) as ph:
        q = qt.createQureg(n, env)
        with qt.gateFusion(q):
            apply_circuit(qt, q, ops)
        total = qt.calcTotalProb(q)
        with qt.gateFusion(q):
            apply_circuit(qt, q, ops, inverse=True)
        a0 = qt.getAmp(q, 0)
        p0 = a0.real ** 2 + a0.imag ** 2
        ph.check("total_prob", total, abs(total - 1.0) <= TOL_PROB)
        ph.check("amp0_prob", p0, p0 >= 1.0 - TOL_PROB)
    return ph.ok, q


def phase_qft(qt, q):
    """Phase 2: QFT of a seeded basis state leaves every qubit at 1/2."""
    n = q.num_qubits_represented
    index = int(np.random.default_rng(SEED + 2).integers(1 << n))
    with _Phase("full_qft", qubits=n, devices=q.env.num_devices) as ph:
        qt.initClassicalState(q, index)
        qt.applyFullQFT(q)
        for t in (0, n // 2 - 1, n - 1):
            p = qt.calcProbOfOutcome(q, t, 0)
            ph.check(f"p0_q{t}", p, abs(p - 0.5) <= TOL_EXACT)
    return ph.ok


def phase_pauli(qt, q):
    """Phase 3: <0|P|0> is 1 for Z strings and 0 for X strings."""
    n = q.num_qubits_represented
    rng = np.random.default_rng(SEED + 3)
    with _Phase("expec_pauli_sum", qubits=n,
                devices=q.env.num_devices) as ph:
        qt.initZeroState(q)
        for kind, code, want in (("z", 3, 1.0), ("x", 1, 0.0)):
            for i in range(3):
                codes = np.where(rng.random(n) < 0.5, code, 0)
                codes[rng.integers(n)] = code
                e = qt.calcExpecPauliSum(q, codes, [1.0])
                ph.check(f"{kind}{i}", e, abs(e - want) <= TOL_EXACT)
    return ph.ok


def phase_reference(qt, env, n: int = 14):
    """Phase 4: phase 1's generator against the dense reference."""
    ops = random_circuit(n)
    with _Phase("dense_reference", qubits=n, devices=env.num_devices) as ph:
        q = qt.createQureg(n, env)
        with qt.gateFusion(q):
            apply_circuit(qt, q, ops)
        got = np.asarray(q.amps, np.float64)
        want = reference_state(n, ops)
        err = float(np.max(np.abs(got[0] + 1j * got[1] - want)))
        ph.check("max_abs_err", err, err <= TOL_EXACT)
        qt.destroyQureg(q, env)
    return ph.ok


def phase_sharded_vs_single(qt, env4, env1, n: int = 28):
    """Four-chip vs one-chip results of the same circuit: every
    per-qubit P(0) and 64 seeded amplitudes."""
    ops = random_circuit(n)
    with _Phase("sharded_vs_single", qubits=n,
                devices=env4.num_devices) as ph:
        regs = []
        for env in (env4, env1):
            q = qt.createQureg(n, env)
            with qt.gateFusion(q):
                apply_circuit(qt, q, ops)
            regs.append(q)
        worst_p = max(abs(qt.calcProbOfOutcome(regs[0], t, 0)
                          - qt.calcProbOfOutcome(regs[1], t, 0))
                      for t in range(n))
        idx = np.random.default_rng(SEED + 4).integers(1 << n, size=64)
        worst_a = max(abs(qt.getAmp(regs[0], int(i))
                          - qt.getAmp(regs[1], int(i))) for i in idx)
        ph.check("max_prob_diff", worst_p, worst_p <= TOL_EXACT)
        ph.check("max_amp_diff", worst_a, worst_a <= TOL_EXACT)
        for q, env in zip(regs, (env4, env1)):
            qt.destroyQureg(q, env)
    return ph.ok


def device_balance(qt, env, n: int):
    """Create an ``n``-qubit register on ``env`` and report each device's
    bytes_in_use: (ok, per-device bytes, register)."""
    q = qt.createQureg(n, env)
    q.device_amps().block_until_ready()
    used = [int(d.memory_stats()["bytes_in_use"])
            for d in env.mesh.devices.flat]
    mean = sum(used) / len(used)
    ok = max(used) <= 1.1 * mean
    print(json.dumps({"phase": "device_balance", "qubits": n,
                      "bytes_in_use": used, "ok": ok}), flush=True)
    return ok, q


def run(chips: int) -> bool:
    import quest_tpu as qt
    from quest_tpu import telemetry

    telemetry.configure("on")
    qt.set_precision(1)
    oks = []
    if chips == 4:
        env4 = qt.createQuESTEnv(num_devices=4)
        ok, q = device_balance(qt, env4, 32)
        oks.append(ok)
        qt.destroyQureg(q, env4)
        del q
        ok, q = phase_random_circuit(qt, env4, 32)
        oks.append(ok)
        qt.destroyQureg(q, env4)
        del q
        oks.append(phase_sharded_vs_single(
            qt, env4, qt.createQuESTEnv(num_devices=1)))
    else:
        env = qt.createQuESTEnv(num_devices=1)
        ok, q = phase_random_circuit(qt, env, 30)
        oks.append(ok)
        oks.append(phase_qft(qt, q))
        oks.append(phase_pauli(qt, q))
        qt.destroyQureg(q, env)
        del q
        oks.append(phase_reference(qt, env))
    degraded = qt.degradation_report()
    oom_retries = telemetry.counter_total("oom_retries_total")
    if degraded or oom_retries:
        print(json.dumps({"degradations": degraded,
                          "oom_retries_total": oom_retries}), flush=True)
        return False
    return all(oks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX reports "
              f"{devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX reports "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    if not run(args.chips):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
