"""Whole-circuit builders: fuse many gates into ONE jitted XLA program.

The reference dispatches one kernel launch per gate (QuEST.c); tracing a
whole circuit lets XLA fuse adjacent elementwise/diagonal gates and
eliminate intermediate HBM round-trips — the main idiomatic performance win
of the TPU design (SURVEY.md §7 "fusion of gate sequences is free").

These functional circuits power the benchmarks (bench.py) and the example
models (Grover, Bernstein-Vazirani, QFT) and run on raw SoA amplitude
arrays; the imperative API remains available for gate-at-a-time use.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import cplx, gatedefs, kernels, paulis, phasefunc

_H_SOA = cplx.soa(gatedefs.HADAMARD)


def ghz_layer(amps, num_qubits: int):
    """H + CNOT chain."""
    amps = kernels.apply_matrix(amps, _H_SOA, num_qubits=num_qubits, targets=(0,))
    for t in range(1, num_qubits):
        amps = kernels.apply_multi_qubit_not(
            amps, num_qubits=num_qubits, targets=(t,), controls=(t - 1,)
        )
    return amps


def build_random_circuit(num_qubits: int, depth: int, seed: int = 0,
                         use_scan: bool = True):
    """Returns (fn, unitaries): fn(amps, unitaries) applies the whole
    depth-layer circuit as one traceable program.

    ``use_scan`` rolls the depth loop into ``lax.scan`` so the compiled
    program is one layer body regardless of depth (compiler-friendly
    control flow; the unrolled form is kept for fusion comparison)."""
    rng = np.random.default_rng(seed)
    us = np.empty((depth, num_qubits, 2, 2, 2))
    for d in range(depth):
        for q in range(num_qubits):
            m = _random_unitary_host(rng)
            us[d, q] = cplx.soa(m)
    unitaries = jnp.asarray(us, jnp.float32)

    n = num_qubits

    def _gates(amps, u_layer):
        for q in range(n):
            amps = kernels.apply_matrix(amps, u_layer[q], num_qubits=n, targets=(q,))
        return amps

    def _ladder(amps, offset: int):
        for q in range(offset, n - 1, 2):
            amps = kernels.apply_multi_qubit_not(
                amps, num_qubits=n, targets=(q + 1,), controls=(q,)
            )
        return amps

    if not use_scan:
        def fn(amps, unitaries):
            for d in range(depth):
                amps = _gates(amps, unitaries[d])
                amps = _ladder(amps, d % 2)
            return amps
        return fn, unitaries

    parities = jnp.arange(depth, dtype=jnp.int32) % 2

    def fn(amps, unitaries):
        def body(a, xs):
            u_layer, parity = xs
            a = _gates(a, u_layer)
            a = jax.lax.cond(
                parity == 0, lambda s: _ladder(s, 0), lambda s: _ladder(s, 1), a
            )
            return a, None

        amps, _ = jax.lax.scan(body, amps, (unitaries, parities))
        return amps

    return fn, unitaries


def _random_unitary_host(rng):
    a = rng.standard_normal((2, 2)) + 1j * rng.standard_normal((2, 2))
    q, r = np.linalg.qr(a)
    return q * (np.diag(r) / np.abs(np.diag(r)))


def qft_circuit(amps, num_qubits: int, layered: bool = False):
    """Full QFT as one traceable program.

    Default: circuit.fused_qft — one fused elementwise ladder pass per
    high layer (Hadamard + whole controlled-phase ladder), the low layers
    folded by the windowed scheduler, and the swap network collapsed to
    ONE bit-reversal axis permutation.

    ``layered=True`` (or n below the window size) uses the reference's
    per-layer strategy instead: H + SCALED_PRODUCT phase-ladder sweeps +
    pairwise swaps (agnostic_applyQFT, QuEST_common.c:836-898)."""
    n = num_qubits
    if not layered and n >= 14:
        from quest_tpu import circuit as CIRC

        return CIRC.fused_qft(amps, n, 0, n)
    empty_i = np.zeros((0, 2), np.int64)
    empty_p = np.zeros((0,), np.float64)
    for q in range(num_qubits - 1, -1, -1):
        amps = kernels.apply_matrix(amps, _H_SOA, num_qubits=num_qubits, targets=(q,))
        if q == 0:
            break
        params = np.array([math.pi / (1 << q), 0.0])
        amps = phasefunc.apply_named_phase_func(
            amps, params, empty_i, empty_p,
            num_qubits=num_qubits,
            reg_qubits=(tuple(range(q)), (q,)),
            encoding=phasefunc.UNSIGNED,
            func_name=phasefunc.SCALED_PRODUCT,
        )
    for i in range(num_qubits // 2):
        amps = kernels.swap_qubit_amps(
            amps, num_qubits=num_qubits, qb1=i, qb2=num_qubits - i - 1
        )
    return amps


def grover_circuit(num_qubits: int, marked: int, dtype=jnp.float32):
    """Grover search as one traceable program (reference example
    examples/grovers_search.c): optimal-iteration amplitude amplification.
    Prepares its own |+>^n start state."""
    n = num_qubits
    flip_marked = np.ones(1 << n)
    flip_marked[marked] = -1.0
    flip_zero = np.ones(1 << n)
    flip_zero[0] = -1.0
    d_marked = np.stack([flip_marked, np.zeros(1 << n)])
    d_zero = np.stack([flip_zero, np.zeros(1 << n)])

    amps = kernels.init_plus_state(1 << n, dtype)
    reps = max(1, int(round(math.pi / 4 * math.sqrt(2 ** n))))
    for _ in range(reps):
        # oracle: flip the marked amplitude
        amps = kernels.apply_diagonal(
            amps, d_marked, num_qubits=n, targets=tuple(range(n))
        )
        # diffusion: H^n . (flip |0>) . H^n
        for q in range(n):
            amps = kernels.apply_matrix(amps, _H_SOA, num_qubits=n, targets=(q,))
        amps = kernels.apply_diagonal(
            amps, d_zero, num_qubits=n, targets=tuple(range(n))
        )
        for q in range(n):
            amps = kernels.apply_matrix(amps, _H_SOA, num_qubits=n, targets=(q,))
    return amps


def bernstein_vazirani_circuit(num_qubits: int, secret: int, dtype=jnp.float32):
    """Bernstein-Vazirani (reference examples/bernstein_vazirani_circuit.c):
    finds `secret` with one oracle query.  Phase-oracle formulation: H^n,
    phase (-1)^{s.x}, H^n.  Prepares its own |+>^n start state."""
    n = num_qubits
    signs = np.array(
        [(-1.0) ** bin(i & secret).count("1") for i in range(1 << n)]
    )
    d_oracle = np.stack([signs, np.zeros(1 << n)])
    amps = kernels.init_plus_state(1 << n, dtype)
    amps = kernels.apply_diagonal(amps, d_oracle, num_qubits=n, targets=tuple(range(n)))
    for q in range(n):
        amps = kernels.apply_matrix(amps, _H_SOA, num_qubits=n, targets=(q,))
    return amps


# ---------------------------------------------------------------------------
# Benchmark-workload helpers shared by bench.py / scripts/bench_scale.py
# (BASELINE.json config 2 shape)
# ---------------------------------------------------------------------------

CNOT_SOA = np.zeros((2, 4, 4), np.float32)
CNOT_SOA[0] = np.array(
    [[1, 0, 0, 0], [0, 0, 0, 1], [0, 0, 1, 0], [0, 1, 0, 0]], np.float32)


def bench_gate_list(num_qubits: int, depth: int, unitaries):
    """The config-2 gate list (per-layer 1q unitaries + alternating CNOT
    ladder) as circuit.Gate objects, for the windowed planner.  CNOT
    convention: control = matrix bit 0 (= targets[0]), target = bit 1."""
    from .. import circuit as C

    gates = []
    for d in range(depth):
        for q in range(num_qubits):
            gates.append(C.Gate((q,), unitaries[d, q]))
        for q in range(d % 2, num_qubits - 1, 2):
            gates.append(C.Gate((q, q + 1), CNOT_SOA))
    return gates


def zero_state_canonical(num_qubits: int):
    """|0...0> directly in the canonical (2, nb, 128, 128) tiled view,
    built inside ONE jitted program (an eager zeros + scatter transiently
    holds two full states — an OOM at 30q)."""
    return _zero_state_canonical_jit(n=num_qubits)


@partial(jax.jit, static_argnames=("n",))
def _zero_state_canonical_jit(*, n):
    nb = 1 << (n - 14)
    return jnp.zeros((2, nb, 128, 128), jnp.float32).at[0, 0, 0, 0].set(1.0)


@jax.jit
def prob_top_zero_canonical(a):
    """P(top qubit = 0) on the canonical view: a contiguous half-slice
    sum — layout-preserving (calc_prob's generic reshape would re-tile
    the canonical layout into an 8 GB temp at 30q).  Needs n >= 15 so
    the top qubit is a whole slice of the tile axis."""
    if a.shape[1] < 2:
        raise ValueError("prob_top_zero_canonical needs >= 2 tiles (n >= 15)")
    h = a[:, : a.shape[1] // 2]
    return jnp.sum(h * h)


@jax.jit
def amp00_canonical(a):
    """Layout-preserving scalar sync on the canonical view (a gather-style
    a[0,0,0,0] makes XLA re-layout the whole state)."""
    return jnp.sum(a[:1, :1, :1, :1])
