"""Public API, part 2: measurements, decoherence channels, calculations,
composite operators (apply*), and QASM recording control.

Continues quest_tpu.api (same dispatch conventions; see that module's
docstring).  Reference parity: QuEST.c:985-1602 + QuEST_common.c composites.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from . import telemetry as _telemetry
from . import validation as V
from .ops import calculations as C
from .ops import density as D
from .ops import kernels as K
from .ops import paulis as P
from .ops import phasefunc as PF
from .precision import get_precision, real_eps
from .qureg import DiagonalOp, PauliHamil, Qureg
from .rng import GLOBAL_RNG
# qlint: allow(layer-violation): api_ops.py is api.py's size-split continuation (one API surface split across two files, see module docstring), not a second API composing the first; it shares api.py's private helpers by design
from .api import (
    PAULI_I,
    _apply_diag,
    _apply_unitary,
    _shift,
    _sv_n,
    hadamard,
    swapGate,
)



def _quad() -> bool:
    """prec-4: route reductions through double-double accumulation."""
    return get_precision() == 4

# ---------------------------------------------------------------------------
# Measurement (QuEST.c:985-995, QuEST_common.c:168-183,374-380)
# ---------------------------------------------------------------------------


def calcProbOfOutcome(qureg: Qureg, measureQubit: int, outcome: int) -> float:
    """Probability of measuring the given outcome of one qubit (QuEST.h:3047)."""
    V.validate_target(qureg, measureQubit, "calcProbOfOutcome")
    V.validate_outcome(outcome, "calcProbOfOutcome")
    quad = _quad()
    if qureg.is_density_matrix:
        p = C.calc_prob_of_outcome_density(
            qureg.amps, num_qubits=qureg.num_qubits_represented,
            target=measureQubit, outcome=outcome, quad=quad)
    else:
        # read where the state lies: the live permutation only moves
        # which physical bit holds the measured qubit
        p = C.calc_prob_of_outcome_statevec(
            qureg.device_amps_raw(), num_qubits=_sv_n(qureg),
            target=qureg._phys_bits((measureQubit,))[0],
            outcome=outcome, quad=quad)
    return float(p)


def calcProbOfAllOutcomes(qureg: Qureg, qubits: Sequence[int]) -> np.ndarray:
    """Probabilities of every outcome of a sub-register measurement (QuEST.h:3136)."""
    qubits = [int(q) for q in qubits]
    V.validate_multi_targets(qureg, qubits, "calcProbOfAllOutcomes")
    if qureg.is_density_matrix:
        p = C.calc_prob_of_all_outcomes_density(
            qureg.amps, num_qubits=qureg.num_qubits_represented, qubits=tuple(qubits)
        )
    else:
        p = C.calc_prob_of_all_outcomes_statevec(
            qureg.amps, num_qubits=_sv_n(qureg), qubits=tuple(qubits)
        )
    return np.asarray(p)


def _generate_measurement_outcome(zero_prob: float):
    """(generateMeasurementOutcome, QuEST_common.c:168-183): degenerate
    probabilities short-circuit; otherwise draw from the global MT RNG."""
    if zero_prob < real_eps():
        return 1
    if 1 - zero_prob < real_eps():
        return 0
    return 0 if GLOBAL_RNG.uniform() <= zero_prob else 1


def _collapse(qureg: Qureg, qubit: int, outcome: int, prob: float) -> None:
    if qureg.is_density_matrix:
        qureg.amps = K.collapse_density(
            qureg.amps, float(prob), num_qubits=qureg.num_qubits_represented,
            target=qubit, outcome=outcome,
        )
    else:
        qureg.amps = K.collapse_statevec(
            qureg.amps, float(prob), num_qubits=_sv_n(qureg),
            target=qubit, outcome=outcome,
        )


def collapseToOutcome(qureg: Qureg, measureQubit: int, outcome: int) -> float:
    """Project one qubit to a known outcome and renormalise (QuEST.h:3170)."""
    V.validate_target(qureg, measureQubit, "collapseToOutcome")
    V.validate_outcome(outcome, "collapseToOutcome")
    prob = calcProbOfOutcome(qureg, measureQubit, outcome)
    if prob < real_eps():
        raise V.QuESTError(
            "collapseToOutcome: Can't collapse to state with zero probability."
        )
    _collapse(qureg, measureQubit, outcome, prob)
    qureg.qasm_log.comment(f"collapseToOutcome({outcome}) on qubit {measureQubit}")
    return prob


def measure(qureg: Qureg, measureQubit: int) -> int:
    """Measure one qubit, collapsing the state (QuEST.h:3194)."""
    outcome, _ = measureWithStats(qureg, measureQubit)
    return outcome


def measureWithStats(qureg: Qureg, measureQubit: int):
    """Measure one qubit, also returning the outcome probability
    (QuEST.h:3219).  Default: ONE fused device program per shot — prob
    reduce, on-device threshold draw from the seeded key, conditional
    collapse (ops/measurement.py).  QT_HOST_MEASURE=1 (or strict parity
    mode) restores the reference's host-MT sampling stream
    (calcProb -> generateMeasurementOutcome -> collapse)."""
    if getattr(qureg, "batch_size", 0):
        raise V.QuESTError(
            "measureWithStats: the register is a BatchedQureg bank — "
            "use quest_tpu.batch.measureBatched, which draws from the "
            "per-element key streams")
    V.validate_target(qureg, measureQubit, "measureWithStats")
    _telemetry.inc("measurement_shots_total")
    from .ops import measurement as M
    if M.host_path_enabled():
        zero_prob = calcProbOfOutcome(qureg, measureQubit, 0)
        outcome = _generate_measurement_outcome(zero_prob)
        prob = zero_prob if outcome == 0 else 1 - zero_prob
        _collapse(qureg, measureQubit, outcome, prob)
        qureg.qasm_log.measure(measureQubit)
        return outcome, prob
    key, shot = M.KEYS.next_shots()
    amps, outcome, prob = M.measure_fused(
        qureg.amps, key, shot, num_qubits=qureg.num_qubits_represented,
        target=measureQubit, is_density=qureg.is_density_matrix,
        quad=_quad())
    qureg.amps = amps
    qureg.qasm_log.measure(measureQubit)
    return int(outcome), float(prob)


def measureSequence(qureg: Qureg, qubits: Sequence[int]):
    """EXTENSION (no reference analogue — its measure is irreducibly one
    host round-trip per qubit): measure a sequence of qubits in ONE
    compiled device program, each step collapsing before the next
    qubit's probability is computed, exactly as a loop of measure()
    calls — same seeded outcome stream, one dispatch total (on-chip at
    26q: 8 ms/shot vs the host loop's 510 ms/shot).  Returns
    (outcomes list, probabilities list).  Respects QT_HOST_MEASURE=1 by
    falling back to a loop of host-path measureWithStats."""
    from .ops import measurement as M

    if getattr(qureg, "batch_size", 0):
        raise V.QuESTError(
            "measureSequence: the register is a BatchedQureg bank — "
            "use quest_tpu.batch.measureBatched, which draws from the "
            "per-element key streams")
    qubits = [int(q) for q in qubits]
    for q in qubits:
        V.validate_target(qureg, q, "measureSequence")
    if not qubits:
        return [], []
    if M.host_path_enabled():
        outs, probs = [], []
        for q in qubits:
            o, p = measureWithStats(qureg, q)
            outs.append(o)
            probs.append(p)
        return outs, probs
    # (the host path above counts per measureWithStats call)
    _telemetry.inc("measurement_shots_total", len(qubits))
    key, shot = M.KEYS.next_shots(len(qubits))
    amps, outs, probs = M.measure_sequence(
        qureg.amps, key, shot, num_qubits=qureg.num_qubits_represented,
        targets=tuple(qubits), is_density=qureg.is_density_matrix,
        quad=_quad())
    qureg.amps = amps
    for q in qubits:
        qureg.qasm_log.measure(q)
    return [int(o) for o in np.asarray(outs)], [float(p)
                                                for p in np.asarray(probs)]


# ---------------------------------------------------------------------------
# Decoherence (QuEST.c:1259-1331; channels in ops.density)
# ---------------------------------------------------------------------------


def mixDephasing(qureg: Qureg, targetQubit: int, prob: float) -> None:
    """One-qubit dephasing channel (QuEST.h:3421)."""
    V.validate_density_matrix(qureg, "mixDephasing")
    V.validate_target(qureg, targetQubit, "mixDephasing")
    V.validate_one_qubit_dephase_prob(prob, "mixDephasing")
    from .ops import gatedefs as G
    if _capture_channel(
            qureg,
            [math.sqrt(1 - prob) * G.PAULI_I, math.sqrt(prob) * G.PAULI_Z],
            (targetQubit,)):
        return
    qureg.amps = D.mix_dephasing(
        qureg.amps, prob, num_qubits=qureg.num_qubits_represented, target=targetQubit
    )


def mixTwoQubitDephasing(qureg: Qureg, qubit1: int, qubit2: int, prob: float) -> None:
    """Two-qubit dephasing channel (QuEST.h:3453)."""
    V.validate_density_matrix(qureg, "mixTwoQubitDephasing")
    V.validate_unique_targets(qureg, qubit1, qubit2, "mixTwoQubitDephasing")
    V.validate_two_qubit_dephase_prob(prob, "mixTwoQubitDephasing")
    from .ops import gatedefs as G
    i2, z = np.asarray(G.PAULI_I), np.asarray(G.PAULI_Z)
    # Kraus order (q2 (x) q1): matrix bit 0 = qubit1
    ops = [math.sqrt(1 - prob) * np.kron(i2, i2),
           math.sqrt(prob / 3) * np.kron(i2, z),
           math.sqrt(prob / 3) * np.kron(z, i2),
           math.sqrt(prob / 3) * np.kron(z, z)]
    if _capture_channel(qureg, ops, (qubit1, qubit2)):
        return
    qureg.amps = D.mix_two_qubit_dephasing(
        qureg.amps, prob, num_qubits=qureg.num_qubits_represented,
        qubit1=qubit1, qubit2=qubit2,
    )


def _mix_kraus(qureg: Qureg, ops, targets) -> None:
    """Apply a Kraus channel: under gateFusion the superoperator is
    CAPTURED into the drain as a dense gate on (T, T+n) — noise channels
    then fold into the same window passes as gates (one compiled program
    for a whole noise layer); on a sharded register with sharded bra
    bits the superoperator routes through the dense-gate dispatcher
    (SWAP-relocalization, 2 ppermutes per sharded bit — the reference's
    distributed multiQubitUnitary strategy the Kraus fold rides,
    QuEST_common.c:630-652 + QuEST_cpu_distributed.c:1503-1545);
    otherwise the generic superoperator kernel runs eagerly."""
    if _capture_channel(qureg, ops, targets):
        return
    if _explicit_sharded(qureg):
        from .api import _dispatch_matrix
        from .ops import cplx as CX
        from .parallel import dist as PAR

        nq = qureg.num_qubits_represented
        nloc = 2 * nq - PAR.num_shard_bits(qureg.env.mesh)
        sv_targets = D.kraus_targets(tuple(targets), nq)
        # locality is judged at the PHYSICAL positions of the live
        # permutation — _dispatch_matrix relocalizes lazily from there
        if any(t >= nloc for t in qureg._phys_bits(sv_targets)):
            sup = D.superoperator_from_kraus(ops)
            dt = (np.float64 if np.dtype(qureg.dtype) == np.float64
                  else np.float32)
            _dispatch_matrix(
                qureg, CX.soa(sup).astype(dt), tuple(sv_targets), (), ())
            return
    qureg.amps = D.apply_kraus_map(
        qureg.amps, ops, num_qubits=qureg.num_qubits_represented, targets=tuple(targets)
    )


def _capture_channel(qureg: Qureg, ops, targets) -> bool:
    from . import fusion
    from .ops import cplx as CX

    if getattr(qureg, "_fusion", None) is None:
        return False
    sup = D.superoperator_from_kraus(ops)
    sv_targets = D.kraus_targets(tuple(targets), qureg.num_qubits_represented)
    dt = np.float64 if qureg.amps.dtype == jnp.float64 else np.float32
    return fusion.capture_raw(qureg, CX.soa(sup).astype(dt), sv_targets)


def _pair_channel_sharded(qureg: Qureg, prob: float, target: int,
                          kind: str) -> bool:
    """Explicit ppermute path for depolarise/damping when the bra target
    bit is a mesh-coordinate bit (dist.mix_pair_channel_sharded)."""
    from .parallel import dist as PAR

    env = qureg.env
    if not PAR.explicit_dist_enabled() or not _spans_mesh(qureg):
        return False
    nq = qureg.num_qubits_represented
    nloc = 2 * nq - PAR.num_shard_bits(env.mesh)
    if target + nq < nloc:
        return False
    qureg.amps = PAR.mix_pair_channel_sharded(
        qureg.amps, prob, mesh=env.mesh, num_qubits=nq, target=target,
        kind=kind)
    return True


def mixDepolarising(qureg: Qureg, targetQubit: int, prob: float) -> None:
    """One-qubit depolarising channel (QuEST.h:3496).  Routed, in order:
    fusion capture (superoperator folds into the drain's window passes) ->
    explicit ppermute pair-exchange for sharded bra bits -> the dedicated
    elementwise pair-average kernel (ref QuEST_cpu.c:125-246), never the
    16x generic superoperator."""
    V.validate_density_matrix(qureg, "mixDepolarising")
    V.validate_target(qureg, targetQubit, "mixDepolarising")
    V.validate_one_qubit_depol_prob(prob, "mixDepolarising")
    # Under gateFusion the channel is captured as a ChannelItem — the
    # SAME one-pass elementwise kernel, run inside the drain program in
    # call order (never the rank-4 superoperator fold, which measured
    # slower) — so a whole noise layer costs one dispatch.  Outside
    # fusion this drains (no-op) and runs eagerly.
    from . import fusion
    if fusion.capture_pair_channel(qureg, "depol", targetQubit, prob):
        return
    if _pair_channel_sharded(qureg, prob, targetQubit, "depol"):
        return
    qureg.amps = D.mix_depolarising(
        qureg.amps, prob, num_qubits=qureg.num_qubits_represented,
        target=targetQubit)


def mixDamping(qureg: Qureg, targetQubit: int, prob: float) -> None:
    """One-qubit amplitude damping channel (QuEST.h:3534).  Same routing
    as mixDepolarising (ref elementwise form QuEST_cpu.c:300-385)."""
    V.validate_density_matrix(qureg, "mixDamping")
    V.validate_target(qureg, targetQubit, "mixDamping")
    V.validate_one_qubit_damping_prob(prob, "mixDamping")
    # captured as a ChannelItem under gateFusion — see mixDepolarising
    from . import fusion
    if fusion.capture_pair_channel(qureg, "damping", targetQubit, prob):
        return
    if _pair_channel_sharded(qureg, prob, targetQubit, "damping"):
        return
    qureg.amps = D.mix_damping(
        qureg.amps, prob, num_qubits=qureg.num_qubits_represented,
        target=targetQubit)


def mixTwoQubitDepolarising(qureg: Qureg, qubit1: int, qubit2: int, prob: float) -> None:
    """Two-qubit depolarising channel (QuEST.h:3601).  Routed, in order:
    fusion capture (superoperator folds into the drain) -> explicit
    <=2-ppermute double-flip orbit kernel for sharded bra bits
    (dist.mix_two_qubit_depol_sharded, the reference's dedicated
    distributed algorithm QuEST_cpu_distributed.c:553-852) -> the
    dedicated elementwise orbit kernel (never the 256x generic
    superoperator, ref QuEST_cpu.c:387-733)."""
    V.validate_density_matrix(qureg, "mixTwoQubitDepolarising")
    V.validate_unique_targets(qureg, qubit1, qubit2, "mixTwoQubitDepolarising")
    V.validate_two_qubit_depol_prob(prob, "mixTwoQubitDepolarising")
    if _capture_channel(
            qureg, D.two_qubit_depolarising_kraus(prob, qureg.dtype),
            (qubit1, qubit2)):
        return
    if _explicit_sharded(qureg):
        from .parallel import dist as PAR

        nq = qureg.num_qubits_represented
        nloc = 2 * nq - PAR.num_shard_bits(qureg.env.mesh)
        if max(qubit1, qubit2) + nq >= nloc:
            qureg.amps = PAR.mix_two_qubit_depol_sharded(
                qureg.amps, prob, mesh=qureg.env.mesh, num_qubits=nq,
                qubit1=qubit1, qubit2=qubit2)
            return
    qureg.amps = D.mix_two_qubit_depolarising(
        qureg.amps, prob, num_qubits=qureg.num_qubits_represented,
        qubit1=qubit1, qubit2=qubit2)


def mixPauli(qureg: Qureg, targetQubit: int, probX: float, probY: float, probZ: float) -> None:
    """One-qubit Pauli channel with probabilities (pX, pY, pZ) (QuEST.h:3642)."""
    V.validate_density_matrix(qureg, "mixPauli")
    V.validate_target(qureg, targetQubit, "mixPauli")
    V.validate_one_qubit_pauli_probs(probX, probY, probZ, "mixPauli")
    _mix_kraus(qureg, D.pauli_kraus(probX, probY, probZ, qureg.dtype), (targetQubit,))


def mixDensityMatrix(combineQureg: Qureg, prob: float, otherQureg: Qureg) -> None:
    """Mix another density matrix in: rho = (1-p) rho + p other (QuEST.h:3664)."""
    V.validate_density_matrix(combineQureg, "mixDensityMatrix")
    V.validate_density_matrix(otherQureg, "mixDensityMatrix")
    V.validate_matching_qureg_dims(combineQureg, otherQureg, "mixDensityMatrix")
    V.validate_prob(prob, "mixDensityMatrix")
    combineQureg.amps = D.mix_density_matrix(combineQureg.amps, otherQureg.amps, prob)


def mixKrausMap(qureg: Qureg, target: int, ops, numOps: Optional[int] = None) -> None:
    """Apply a one-qubit CPTP Kraus map (QuEST.h:4789)."""
    ops = list(ops)[: int(numOps)] if numOps is not None else list(ops)
    V.validate_density_matrix(qureg, "mixKrausMap")
    V.validate_target(qureg, target, "mixKrausMap")
    V.validate_kraus_ops(ops, 1, "mixKrausMap")
    _mix_kraus(qureg, [np.asarray(o, complex) for o in ops], (target,))


def mixTwoQubitKrausMap(qureg: Qureg, target1: int, target2: int, ops, numOps: Optional[int] = None) -> None:
    """Apply a two-qubit CPTP Kraus map (QuEST.h:4828)."""
    ops = list(ops)[: int(numOps)] if numOps is not None else list(ops)
    V.validate_density_matrix(qureg, "mixTwoQubitKrausMap")
    V.validate_unique_targets(qureg, target1, target2, "mixTwoQubitKrausMap")
    V.validate_kraus_ops(ops, 2, "mixTwoQubitKrausMap")
    _mix_kraus(qureg, [np.asarray(o, complex) for o in ops], (target1, target2))


def mixMultiQubitKrausMap(qureg: Qureg, targets: Sequence[int], ops, numOps: Optional[int] = None) -> None:
    """Apply an N-qubit CPTP Kraus map (QuEST.h:4878)."""
    ops = list(ops)[: int(numOps)] if numOps is not None else list(ops)
    targets = [int(t) for t in targets]
    V.validate_density_matrix(qureg, "mixMultiQubitKrausMap")
    V.validate_multi_targets(qureg, targets, "mixMultiQubitKrausMap")
    V.validate_multi_qubit_matrix_fits_in_node(qureg, 2 * len(targets), "mixMultiQubitKrausMap")
    V.validate_kraus_ops(ops, len(targets), "mixMultiQubitKrausMap")
    _mix_kraus(qureg, [np.asarray(o, complex) for o in ops], tuple(targets))


# ---------------------------------------------------------------------------
# Calculations (QuEST.h:1987-2099, 3246-3724, 4189-4285, 4911)
# ---------------------------------------------------------------------------


def getAmp(qureg: Qureg, index: int) -> complex:
    """Fetch one complex amplitude (QuEST.h:1987).  Routed through the
    layout-safe dynamic-slice kernel (ops/element.py): O(1 tile) on a
    canonically-held big state, never a full-state re-layout — matching
    the reference's O(1) chunk read (QuEST_cpu_local.c:225-233)."""
    from .ops import element as E

    V.validate_state_vector(qureg, "getAmp")
    V.validate_num_amps(qureg, index, 1, "getAmp")
    amps = qureg.device_amps_raw()
    # a live permutation relabels the index bits: read the physical slot
    phys = 0
    for q, p in enumerate(qureg._phys_bits(range(_sv_n(qureg)))):
        phys |= ((int(index) >> q) & 1) << p
    pair = np.asarray(E.get_amp_pair(amps, phys))
    return complex(pair[0], pair[1])


def getRealAmp(qureg: Qureg, index: int) -> float:
    """Fetch the real part of one amplitude (QuEST.h:2008)."""
    return getAmp(qureg, index).real


def getImagAmp(qureg: Qureg, index: int) -> float:
    """Fetch the imaginary part of one amplitude (QuEST.h:2029)."""
    return getAmp(qureg, index).imag


def getProbAmp(qureg: Qureg, index: int) -> float:
    """Fetch |amp|^2 of one amplitude (QuEST.h:2050)."""
    a = getAmp(qureg, index)
    return a.real * a.real + a.imag * a.imag


def getDensityAmp(qureg: Qureg, row: int, col: int) -> complex:
    """Fetch one density-matrix element rho[row, col] (QuEST.h:2072) —
    same layout-safe slice kernel as getAmp."""
    from .ops import element as E

    V.validate_density_matrix(qureg, "getDensityAmp")
    dim = 1 << qureg.num_qubits_represented
    if not (0 <= row < dim and 0 <= col < dim):
        raise V.QuESTError("getDensityAmp: Invalid amplitude index.")
    pair = np.asarray(E.get_amp_pair(qureg.amps, int(row + col * dim)))
    return complex(pair[0], pair[1])


def calcTotalProb(qureg: Qureg) -> float:
    """Total probability (trace / norm^2) of the register, Kahan-summed
    (QuEST.h:2099).  Quad precision (set_precision(4)) accumulates in
    double-double (C.quad_sum — the QuEST_PREC=4 scope decision,
    precision.set_precision docstring)."""
    if qureg.is_density_matrix:
        if _quad():
            return float(C.calc_total_prob_density_quad(
                qureg.amps, num_qubits=qureg.num_qubits_represented))
        return float(
            C.calc_total_prob_density(qureg.amps, num_qubits=qureg.num_qubits_represented)
        )
    # the norm is invariant under the live permutation: no remap
    amps = qureg.device_amps_raw()
    if _quad():
        return float(C.calc_total_prob_statevec_quad(amps))
    return float(C.calc_total_prob_statevec(amps))


def calcInnerProduct(bra: Qureg, ket: Qureg) -> complex:
    """Complex inner product <bra|ket> of two state-vectors (QuEST.h:3246)."""
    V.validate_state_vector(bra, "calcInnerProduct")
    V.validate_state_vector(ket, "calcInnerProduct")
    V.validate_matching_qureg_dims(bra, ket, "calcInnerProduct")
    if _quad():
        r = np.asarray(C.calc_inner_product_quad(bra.amps, ket.amps))
    else:
        r = np.asarray(C.calc_inner_product(bra.amps, ket.amps))
    return complex(r[0], r[1])


def calcDensityInnerProduct(rho1: Qureg, rho2: Qureg) -> float:
    """Hilbert-Schmidt inner product Tr(rho1^dag rho2) of two density matrices (QuEST.h:3299)."""
    V.validate_density_matrix(rho1, "calcDensityInnerProduct")
    V.validate_density_matrix(rho2, "calcDensityInnerProduct")
    V.validate_matching_qureg_dims(rho1, rho2, "calcDensityInnerProduct")
    return float(C.calc_density_inner_product(
        rho1.amps, rho2.amps, quad=_quad()))


def calcPurity(qureg: Qureg) -> float:
    """Purity Tr(rho^2) of a density matrix (QuEST.h:3692)."""
    V.validate_density_matrix(qureg, "calcPurity")
    return float(C.calc_purity(qureg.amps, quad=_quad()))


def calcFidelity(qureg: Qureg, pureState: Qureg) -> float:
    """Fidelity of a register against a pure reference state (QuEST.h:3724)."""
    V.validate_second_qureg_state_vec(pureState, "calcFidelity")
    V.validate_matching_qureg_dims(qureg, pureState, "calcFidelity")
    quad = _quad()
    if qureg.is_density_matrix:
        return float(C.calc_fidelity_density(
            qureg.amps, pureState.amps,
            num_qubits=qureg.num_qubits_represented, quad=quad))
    ip_fn = C.calc_inner_product_quad if quad else C.calc_inner_product
    ip = np.asarray(ip_fn(qureg.amps, pureState.amps))
    return float(ip[0] ** 2 + ip[1] ** 2)


def calcHilbertSchmidtDistance(a: Qureg, b: Qureg) -> float:
    """Hilbert-Schmidt distance between two density matrices (QuEST.h:4911)."""
    V.validate_density_matrix(a, "calcHilbertSchmidtDistance")
    V.validate_density_matrix(b, "calcHilbertSchmidtDistance")
    V.validate_matching_qureg_dims(a, b, "calcHilbertSchmidtDistance")
    return float(C.calc_hilbert_schmidt_distance(
        a.amps, b.amps, quad=_quad()))


def _spans_mesh(qureg: Qureg) -> bool:
    """True when the register's amplitude axis actually spans a
    multi-device mesh (replicated-small registers do not)."""
    from .parallel import dist as PAR

    env = qureg.env
    return (env.mesh is not None and PAR.amp_axis_size(env.mesh) > 1
            and qureg.num_amps_total >= env.num_devices)


def _explicit_sharded(qureg: Qureg) -> bool:
    """Route to the explicit shard_map kernels: the register spans the
    mesh and the explicit-collective layer is enabled (the default).
    This is the ONE routing predicate for scan-based composites — the
    same kernels run on the virtual CPU mesh and on real multi-chip TPU
    meshes (one-kernel-set contract, QuEST_internal.h:63-292)."""
    from .parallel import dist as PAR

    return PAR.explicit_dist_enabled() and _spans_mesh(qureg)


def _gspmd_pallas_unsafe(qureg: Qureg) -> bool:
    """True when GSPMD propagation of raw Pallas kernels would fail: a
    real TPU backend with the register actually spanning the mesh (a raw
    pallas_call has no GSPMD partitioning rule there; the virtual CPU
    mesh partitions interpret-mode kernels as plain XLA ops).  Only
    consulted on the explicitly-opted-out GSPMD path
    (dist.use_explicit_dist(False)) — the default explicit path has no
    such fallback."""
    import jax as _jax

    return _jax.default_backend() == "tpu" and _spans_mesh(qureg)


def _full_codes(qureg, targets, codes) -> tuple:
    n = qureg.num_qubits_represented
    full = [PAULI_I] * n
    for t, c in zip(targets, codes):
        full[t] = int(c)
    return tuple(full)


def calcExpecPauliProd(qureg: Qureg, targetQubits, pauliCodes, workspace: Optional[Qureg] = None) -> float:
    """Expected value of a product of Pauli operators (uses workspace) (QuEST.h:4189)."""
    targets = [int(t) for t in targetQubits]
    codes = [int(c) for c in pauliCodes]
    V.validate_multi_targets(qureg, targets, "calcExpecPauliProd")
    V.validate_pauli_codes(codes, "calcExpecPauliProd")
    coeffs = np.ones(1)
    flat = _full_codes(qureg, targets, codes)
    quad = _quad()
    if qureg.is_density_matrix:
        val = P.calc_expec_pauli_sum_density(
            qureg.amps, coeffs, num_qubits=qureg.num_qubits_represented,
            codes_flat=flat, num_terms=1, quad=quad,
        )
    else:
        val = P.calc_expec_pauli_sum_statevec(
            qureg.amps, coeffs, num_qubits=qureg.num_qubits_represented,
            codes_flat=flat, num_terms=1, quad=quad,
        )
    return float(val)


def calcExpecPauliSum(qureg: Qureg, allPauliCodes, termCoeffs, workspace: Optional[Qureg] = None) -> float:
    """Expected value of a weighted sum of Pauli products (uses workspace) (QuEST.h:4244)."""
    n = qureg.num_qubits_represented
    codes = tuple(int(c) for c in np.asarray(allPauliCodes).ravel())
    coeffs = np.asarray(termCoeffs, dtype=np.float64)
    num_terms = coeffs.size
    V.validate_num_pauli_sum_terms(num_terms, "calcExpecPauliSum")
    if len(codes) != num_terms * n:
        raise V.QuESTError("calcExpecPauliSum: Number of Pauli codes doesn't match numSumTerms*numQubits.")
    V.validate_pauli_codes(codes, "calcExpecPauliSum")
    cj = coeffs
    quad = _quad()
    if qureg.is_density_matrix:
        val = P.calc_expec_pauli_sum_density(
            qureg.amps, cj, num_qubits=n, codes_flat=codes,
            num_terms=num_terms, quad=quad
        )
    elif (_explicit_sharded(qureg) and not quad
          and set(codes) <= {P.PAULI_I, P.PAULI_Z}):
        # I/Z strings on a sharded register: one read pass over the
        # shards in their device shape (the flat view is a second state
        # per chip); device_amps() canonicalises a live permutation with
        # one batched remap, so the next drain starts from canonical
        # order and repeats its plan (docs/design.md §32)
        amps = qureg.device_amps()
        val = P.expec_z_sum(
            amps, P.z_sum_masks(np.reshape(codes, (num_terms, n)),
                                amps.shape),
            jnp.asarray(cj, amps.dtype))
    elif _gspmd_pallas_unsafe(qureg) and not _explicit_sharded(qureg):
        # opted-out GSPMD mode on a real TPU mesh: the scan's Pallas
        # product layers cannot partition there — per-term kernels
        val = P.calc_expec_pauli_sum_statevec(
            qureg.amps, cj, num_qubits=n, codes_flat=codes,
            num_terms=num_terms, quad=quad,
        )
    else:
        # scan over the term table: one compiled body regardless of term
        # count (the unrolled variant took ~100 s to compile at 16x24q);
        # sharded registers run the SAME scan inside one shard_map with
        # explicit collectives (dist.expec_pauli_sum_scan_sharded)
        codes_seq = jnp.asarray(
            np.asarray(codes, np.int32).reshape(num_terms, n))
        if _explicit_sharded(qureg):
            from .parallel import dist as PAR
            val = PAR.expec_pauli_sum_scan_sharded(
                qureg.amps, codes_seq, jnp.asarray(cj),
                mesh=qureg.env.mesh, num_qubits=n, quad=quad)
        else:
            val = P.expec_pauli_sum_scan(
                qureg.device_amps(), codes_seq, jnp.asarray(cj),
                num_qubits=n, quad=quad,
            )
    return float(val)


def calcExpecPauliHamil(qureg: Qureg, hamil: PauliHamil, workspace: Optional[Qureg] = None) -> float:
    """Expected value of a PauliHamil (uses workspace register) (QuEST.h:4285)."""
    V.validate_pauli_hamil(hamil, "calcExpecPauliHamil")
    V.validate_hamil_matches_qureg(hamil, qureg, "calcExpecPauliHamil")
    return calcExpecPauliSum(qureg, hamil.pauli_codes, hamil.term_coeffs, workspace)


def calcExpecDiagonalOp(qureg: Qureg, op: DiagonalOp) -> complex:
    """Expected value of a diagonal operator in the given state (QuEST.h:1255)."""
    V.validate_diag_op_matches_qureg(op, qureg, "calcExpecDiagonalOp")
    quad = _quad()
    if qureg.is_density_matrix:
        r = np.asarray(C.calc_expec_diagonal_density(
            qureg.amps, op.real, op.imag,
            num_qubits=qureg.num_qubits_represented, quad=quad))
    else:
        r = np.asarray(C.calc_expec_diagonal_statevec(
            qureg.amps, op.real, op.imag, quad=quad))
    return complex(r[0], r[1])


# ---------------------------------------------------------------------------
# Composite operators — apply* family: NO twin, NO unitarity checks
# (QuEST.c:1074-1105)
# ---------------------------------------------------------------------------


def setWeightedQureg(fac1, qureg1: Qureg, fac2, qureg2: Qureg, facOut, out: Qureg) -> None:
    """out = f1 q1 + f2 q2 + fOut out (weighted register sum) (QuEST.h:4936)."""
    V.validate_matching_qureg_types(qureg1, qureg2, "setWeightedQureg")
    V.validate_matching_qureg_types(qureg1, out, "setWeightedQureg")
    V.validate_matching_qureg_dims(qureg1, qureg2, "setWeightedQureg")
    V.validate_matching_qureg_dims(qureg1, out, "setWeightedQureg")
    facs = np.array(
        [
            [complex(facOut).real, complex(fac1).real, complex(fac2).real],
            [complex(facOut).imag, complex(fac1).imag, complex(fac2).imag],
        ]
    )
    if out is qureg1 or out is qureg2:
        # aliased call (out doubles as an input): donating out would hand
        # XLA a buffer that is also a live argument — keep the copy
        out.amps = K.set_weighted_qureg(
            out.amps, qureg1.amps, qureg2.amps, facs)
    else:
        out.amps = K.set_weighted_qureg_donated(
            out.amps, qureg1.amps, qureg2.amps, facs)


def _apply_matrix_raw(qureg: Qureg, m, targets, controls=()):
    from .ops import cplx as CX

    _telemetry.inc("dispatch_total", family="matrix_raw")
    qureg.amps = K.apply_matrix(
        qureg.amps, CX.soa(m), num_qubits=_sv_n(qureg),
        targets=tuple(int(t) for t in targets), controls=tuple(int(c) for c in controls),
    )
    qureg.qasm_log.comment("here a numeric matrix was applied (not recordable in QASM)")


def applyMatrix2(qureg: Qureg, targetQubit: int, u) -> None:
    """Left-multiply an arbitrary 2x2 matrix (no unitarity check, no density-matrix twin) (QuEST.h:5140)."""
    V.validate_target(qureg, targetQubit, "applyMatrix2")
    V.validate_matrix_size(u, 1, "applyMatrix2")
    _apply_matrix_raw(qureg, u, (targetQubit,))


def applyMatrix4(qureg: Qureg, targetQubit1: int, targetQubit2: int, u) -> None:
    """Left-multiply an arbitrary 4x4 matrix (no unitarity check, no density-matrix twin) (QuEST.h:5192)."""
    V.validate_unique_targets(qureg, targetQubit1, targetQubit2, "applyMatrix4")
    V.validate_matrix_size(u, 2, "applyMatrix4")
    _apply_matrix_raw(qureg, u, (targetQubit1, targetQubit2))


def applyMatrixN(qureg: Qureg, targs: Sequence[int], u) -> None:
    """Left-multiply an arbitrary 2^N x 2^N matrix (no unitarity check, no density-matrix twin) (QuEST.h:5260)."""
    targets = [int(t) for t in targs]
    V.validate_multi_targets(qureg, targets, "applyMatrixN")
    V.validate_multi_qubit_matrix_fits_in_node(qureg, len(targets), "applyMatrixN")
    V.validate_matrix_size(u, len(targets), "applyMatrixN")
    _apply_matrix_raw(qureg, u, tuple(targets))


def applyMultiControlledMatrixN(qureg: Qureg, ctrls: Sequence[int], targs: Sequence[int], u) -> None:
    """Left-multiply a controlled arbitrary matrix (no unitarity check, no twin) (QuEST.h:5313)."""
    controls = [int(c) for c in ctrls]
    targets = [int(t) for t in targs]
    V.validate_multi_controls_targets(qureg, controls, targets, "applyMultiControlledMatrixN")
    V.validate_matrix_size(u, len(targets), "applyMultiControlledMatrixN")
    _apply_matrix_raw(qureg, u, tuple(targets), tuple(controls))


def applyPauliSum(inQureg: Qureg, allPauliCodes, termCoeffs, outQureg: Qureg) -> None:
    """Left-multiply a weighted sum of Pauli products, writing outQureg (QuEST.h:4995)."""
    n = inQureg.num_qubits_represented
    codes = tuple(int(c) for c in np.asarray(allPauliCodes).ravel())
    coeffs = np.asarray(termCoeffs, dtype=np.float64)
    num_terms = coeffs.size
    V.validate_num_pauli_sum_terms(num_terms, "applyPauliSum")
    if len(codes) != num_terms * n:
        raise V.QuESTError("applyPauliSum: Number of Pauli codes doesn't match numSumTerms*numQubits.")
    V.validate_pauli_codes(codes, "applyPauliSum")
    V.validate_matching_qureg_types(inQureg, outQureg, "applyPauliSum")
    V.validate_matching_qureg_dims(inQureg, outQureg, "applyPauliSum")
    outQureg.amps = P.apply_pauli_sum(
        inQureg.amps, coeffs, outQureg.amps,
        num_qubits=n, num_state_qubits=_sv_n(inQureg),
        codes_flat=codes, num_terms=num_terms,
    )


def applyPauliHamil(inQureg: Qureg, hamil: PauliHamil, outQureg: Qureg) -> None:
    """Left-multiply a PauliHamil onto inQureg, writing outQureg (QuEST.h:5039)."""
    V.validate_pauli_hamil(hamil, "applyPauliHamil")
    V.validate_hamil_matches_qureg(hamil, inQureg, "applyPauliHamil")
    applyPauliSum(inQureg, hamil.pauli_codes, hamil.term_coeffs, outQureg)


def applyTrotterCircuit(qureg: Qureg, hamil: PauliHamil, time: float, order: int, reps: int) -> None:
    """Symmetrized Suzuki-Trotter e^{-iHt} (agnostic_applyTrotterCircuit,
    QuEST_common.c:752-834).

    The whole gate stream runs as ONE lax.scan over a (T, n) Pauli-code
    table (paulis.trotter_scan): compile cost is a single term body
    regardless of term count / order / reps, where the unrolled per-term
    multiRotatePauli stream took minutes to compile at config-5 scale.
    With QASM recording active the per-term path runs instead so each
    rotation is logged."""
    V.validate_pauli_hamil(hamil, "applyTrotterCircuit")
    V.validate_hamil_matches_qureg(hamil, qureg, "applyTrotterCircuit")
    V.validate_trotter_params(order, reps, "applyTrotterCircuit")
    if time == 0:
        return
    seq = _trotter_schedule(hamil.num_sum_terms, time, order, reps)
    if qureg.qasm_log.is_logging or (
            _gspmd_pallas_unsafe(qureg) and not _explicit_sharded(qureg)):
        # per-term path so every rotation is QASM-logged.  NOTE:
        # deliberately NOT wrapped in fusion.gate_fusion — the per-term
        # parity phase forces a drain every ~36 rotations, and the
        # drain's host-side plan materialization costs more than the
        # saved passes (measured 0.3 s unfused vs 2.9 s fused for a 20q
        # 8-term stream).
        from .api import multiRotatePauli

        targets = list(range(hamil.num_qubits))
        for t, fac in seq:
            multiRotatePauli(qureg, targets,
                             [int(c) for c in hamil.pauli_codes[t]],
                             2 * fac * float(hamil.term_coeffs[t]))
        return
    t_idx = np.asarray([t for t, _ in seq])
    facs = np.asarray([f for _, f in seq])
    codes_seq = np.asarray(hamil.pauli_codes)[t_idx].astype(np.int32)
    angles = 2.0 * facs * np.asarray(hamil.term_coeffs, np.float64)[t_idx]
    if _explicit_sharded(qureg):
        # same scan inside one shard_map: per-shard window layers +
        # ppermute exchange for sharded qubits (one-kernel-set contract
        # on real multi-chip meshes)
        from .parallel import dist as PAR
        qureg.amps = PAR.trotter_scan_sharded(
            qureg.amps, jnp.asarray(codes_seq), jnp.asarray(angles),
            mesh=qureg.env.mesh,
            num_qubits=qureg.num_qubits_in_state_vec,
            rep_qubits=qureg.num_qubits_represented,
        )
        return
    qureg.amps = P.trotter_scan(
        qureg.amps, jnp.asarray(codes_seq), jnp.asarray(angles),
        num_qubits=qureg.num_qubits_in_state_vec,
        rep_qubits=qureg.num_qubits_represented,
    )


def _trotter_schedule(num_terms: int, time: float, order: int, reps: int):
    """(term index, time factor) sequence of the symmetrized Suzuki
    recursion — the same expansion _symmetrized_trotter walks, flattened
    so the scan can consume it as data."""
    seq = []

    def exp_hamil(fac, reverse):
        rng = range(num_terms)
        for t in (reversed(rng) if reverse else rng):
            seq.append((t, fac))

    def symm(t, o):
        if o == 1:
            exp_hamil(t, False)
        elif o == 2:
            exp_hamil(t / 2, False)
            exp_hamil(t / 2, True)
        else:
            p = 1.0 / (4 - 4 ** (1.0 / (o - 1)))
            lower = o - 2
            symm(p * t, lower)
            symm(p * t, lower)
            symm((1 - 4 * p) * t, lower)
            symm(p * t, lower)
            symm(p * t, lower)

    for _ in range(reps):
        symm(time / reps, order)
    return seq


def applyDiagonalOp(qureg: Qureg, op: DiagonalOp) -> None:
    """Left-multiplies D onto the state — on rho this is D.rho, NOT D rho D^dag
    (QuEST.c apply-family semantics; densmatr path QuEST_cpu.c:4042-4082)."""
    V.validate_diag_op_matches_qureg(op, qureg, "applyDiagonalOp")
    if qureg.is_density_matrix:
        nq = qureg.num_qubits_represented
        routed = False
        if _explicit_sharded(qureg):
            from .parallel import dist as PAR

            r = PAR.num_shard_bits(qureg.env.mesh)
            # op must itself be sharded over the amp axis (tiny
            # replicated ops have nothing to gather) and rows shard-local
            if (1 << nq) >= PAR.amp_axis_size(qureg.env.mesh) and r <= nq:
                qureg.amps = PAR.apply_diag_op_density_sharded(
                    qureg.amps, op.real, op.imag, mesh=qureg.env.mesh,
                    num_qubits=nq)
                routed = True
        if not routed:
            qureg.amps = D.apply_diagonal_op_density(
                qureg.amps, op.real, op.imag, num_qubits=nq
            )
    else:
        qureg.amps = K.apply_full_diagonal(qureg.amps, op.real, op.imag)
    qureg.qasm_log.comment("here a diagonal operator was applied")


# ---------------------------------------------------------------------------
# Phase functions (QuEST.h:5571-6326)
# ---------------------------------------------------------------------------


def _empty_overrides():
    return np.zeros((0, 1), np.int64), np.zeros((0,), np.float64)


def _norm_overrides(overrideInds, overridePhases, num_regs):
    if overrideInds is None or len(np.asarray(overridePhases).ravel()) == 0:
        return np.zeros((0, num_regs), np.int64), np.zeros((0,), np.float64)
    inds = np.asarray(overrideInds, np.int64).reshape(-1, num_regs)
    phases = np.asarray(overridePhases, np.float64).ravel()
    return inds, phases


def _pad_params(params, func_name, num_regs):
    """Named-func divergence/shift params live at fixed slots
    (QuEST_cpu.c:4484-4543); pad so the kernel can index them statically."""
    p = np.asarray(params, np.float64).ravel() if params is not None else np.zeros(0)
    need = 2 + num_regs  # covers the largest (shifted-norm) layout
    if p.size < need:
        p = np.concatenate([p, np.zeros(need - p.size)])
    return p


def applyPhaseFunc(qureg: Qureg, qubits, encoding, coeffs, exponents) -> None:
    """Apply exp(i coeff * x^exp) phases from the index of one sub-register (QuEST.h:5571)."""
    applyPhaseFuncOverrides(qureg, qubits, encoding, coeffs, exponents, None, None)


def applyPhaseFuncOverrides(qureg: Qureg, qubits, encoding, coeffs, exponents, overrideInds, overridePhases) -> None:
    """Single-variable phase function with explicit per-index overrides (QuEST.h:5682)."""
    qubits = [int(q) for q in qubits]
    V.validate_qubit_subregs(qureg, [qubits], "applyPhaseFunc")
    V.validate_bit_encoding(int(encoding), "applyPhaseFunc",
                            num_qubits=len(qubits))
    inds, phases = _norm_overrides(overrideInds, overridePhases, 1)
    V.validate_phase_func_terms(len(qubits), int(encoding), coeffs, exponents,
                                [i[0] for i in inds], "applyPhaseFunc")
    V.validate_phase_func_overrides([len(qubits)], int(encoding), inds, "applyPhaseFunc")
    qureg.amps = PF.apply_phase_func(
        qureg.amps, np.asarray(coeffs, np.float64), np.asarray(exponents, np.float64),
        inds, phases,
        num_qubits=_sv_n(qureg), qubits=tuple(qubits), encoding=int(encoding),
    )
    qureg.qasm_log.phase_func(
        qubits, int(encoding), list(np.asarray(coeffs, np.float64).ravel()),
        list(np.asarray(exponents, np.float64).ravel()), inds, phases)


def applyMultiVarPhaseFunc(qureg: Qureg, qubits, numQubitsPerReg, encoding, coeffs, exponents, numTermsPerReg) -> None:
    """Apply exp(i sum_r coeff * x_r^exp) over multiple sub-register variables (QuEST.h:5843)."""
    applyMultiVarPhaseFuncOverrides(
        qureg, qubits, numQubitsPerReg, encoding, coeffs, exponents, numTermsPerReg, None, None
    )


def _split_regs(qubits, numQubitsPerReg):
    regs = []
    flat = [int(q) for q in np.asarray(qubits).ravel()]
    pos = 0
    for nq in numQubitsPerReg:
        regs.append(tuple(flat[pos:pos + int(nq)]))
        pos += int(nq)
    return tuple(regs)


def applyMultiVarPhaseFuncOverrides(qureg, qubits, numQubitsPerReg, encoding, coeffs, exponents, numTermsPerReg, overrideInds, overridePhases) -> None:
    """Multi-variable phase function with explicit per-index phase overrides (QuEST.h:5925)."""
    regs = _split_regs(qubits, numQubitsPerReg)
    V.validate_qubit_subregs(qureg, [list(r) for r in regs],
                             "applyMultiVarPhaseFunc")
    V.validate_multi_reg_bit_encoding([len(r) for r in regs], int(encoding),
                                      "applyMultiVarPhaseFunc")
    exps = np.asarray(exponents, np.float64)
    pos = 0
    exps_per_reg = []
    for t in numTermsPerReg:
        exps_per_reg.append(exps[pos:pos + int(t)])
        pos += int(t)
    V.validate_multi_var_phase_func_terms(
        [len(r) for r in regs], int(encoding), exps_per_reg,
        "applyMultiVarPhaseFunc")
    inds, phases = _norm_overrides(overrideInds, overridePhases, len(regs))
    V.validate_phase_func_overrides(
        [len(r) for r in regs], int(encoding), inds, "applyMultiVarPhaseFunc"
    )
    qureg.amps = PF.apply_multi_var_phase_func(
        qureg.amps, np.asarray(coeffs, np.float64), np.asarray(exponents, np.float64),
        inds, phases,
        num_qubits=_sv_n(qureg), reg_qubits=regs, encoding=int(encoding),
        terms_per_reg=tuple(int(t) for t in numTermsPerReg),
    )
    qureg.qasm_log.multi_var_phase_func(
        regs, int(encoding), list(np.asarray(coeffs, np.float64).ravel()),
        list(exps.ravel()), [int(t) for t in numTermsPerReg], inds, phases)


def applyNamedPhaseFunc(qureg, qubits, numQubitsPerReg, encoding, functionNameCode) -> None:
    """Apply one of the 14 named phase functions over sub-register variables (QuEST.h:6065)."""
    applyParamNamedPhaseFuncOverrides(
        qureg, qubits, numQubitsPerReg, encoding, functionNameCode, None, None, None
    )


def applyNamedPhaseFuncOverrides(qureg, qubits, numQubitsPerReg, encoding, functionNameCode, overrideInds, overridePhases) -> None:
    """Named phase function with explicit per-index phase overrides (QuEST.h:6138)."""
    applyParamNamedPhaseFuncOverrides(
        qureg, qubits, numQubitsPerReg, encoding, functionNameCode, None,
        overrideInds, overridePhases,
    )


def applyParamNamedPhaseFunc(qureg, qubits, numQubitsPerReg, encoding, functionNameCode, params) -> None:
    """Named phase function with extra scalar parameters (QuEST.h:6251)."""
    applyParamNamedPhaseFuncOverrides(
        qureg, qubits, numQubitsPerReg, encoding, functionNameCode, params, None, None
    )


def applyParamNamedPhaseFuncOverrides(qureg, qubits, numQubitsPerReg, encoding, functionNameCode, params, overrideInds, overridePhases, *, _conj=False) -> None:
    """Parameterised named phase function with per-index overrides (QuEST.h:6326)."""
    regs = _split_regs(qubits, numQubitsPerReg)
    shift = _shift(qureg) if _conj else 0
    V.validate_qubit_subregs(
        qureg, [[q - shift for q in r] for r in regs], "applyNamedPhaseFunc")
    V.validate_multi_reg_bit_encoding([len(r) for r in regs], int(encoding),
                                      "applyNamedPhaseFunc")
    num_params = 0 if params is None else int(np.asarray(params).size)
    V.validate_phase_func_name(int(functionNameCode), len(regs), num_params,
                               "applyNamedPhaseFunc")
    inds, phases = _norm_overrides(overrideInds, overridePhases, len(regs))
    V.validate_phase_func_overrides(
        [len(r) for r in regs], int(encoding), inds, "applyNamedPhaseFunc"
    )
    qureg.amps = PF.apply_named_phase_func(
        qureg.amps, _pad_params(params, int(functionNameCode), len(regs)),
        inds, phases,
        num_qubits=_sv_n(qureg), reg_qubits=regs, encoding=int(encoding),
        func_name=int(functionNameCode), conj=_conj,
    )
    qureg.qasm_log.named_phase_func(
        regs, int(encoding), int(functionNameCode),
        [] if params is None else list(np.asarray(params, np.float64).ravel()),
        inds, phases)


# ---------------------------------------------------------------------------
# QFT (agnostic_applyQFT, QuEST_common.c:836-898)
# ---------------------------------------------------------------------------


def applyQFT(qureg: Qureg, qubits: Sequence[int], numQubits: Optional[int] = None) -> None:
    """Apply the quantum Fourier transform to the given qubits (QuEST.h:6536)."""
    qubits = [int(q) for q in qubits]
    V.validate_multi_targets(qureg, qubits, "applyQFT")
    _apply_qft(qureg, qubits)


def applyFullQFT(qureg: Qureg) -> None:
    """Apply the quantum Fourier transform to every qubit (QuEST.h:6420)."""
    _apply_qft(qureg, list(range(qureg.num_qubits_represented)))


def _apply_qft(qureg: Qureg, qubits) -> None:
    if _qft_fused(qureg, qubits):
        return
    n = len(qubits)
    for q in range(n - 1, -1, -1):
        hadamard(qureg, qubits[q])
        if q == 0:
            break
        # fused controlled-phase ladder: theta = (pi/2^q) * x_low * x_q
        regs = (tuple(qubits[:q]), (qubits[q],))
        params = np.array([math.pi / (1 << q)])
        inds = np.zeros((0, 2), np.int64)
        phases = np.zeros((0,), np.float64)
        qureg.amps = PF.apply_named_phase_func(
            qureg.amps, _pad_params(params, PF.SCALED_PRODUCT, 2), inds, phases,
            num_qubits=_sv_n(qureg), reg_qubits=regs, encoding=PF.UNSIGNED,
            func_name=PF.SCALED_PRODUCT, conj=False,
        )
        if qureg.is_density_matrix:
            sh = _shift(qureg)
            sregs = (tuple(x + sh for x in regs[0]), tuple(x + sh for x in regs[1]))
            qureg.amps = PF.apply_named_phase_func(
                qureg.amps, _pad_params(params, PF.SCALED_PRODUCT, 2), inds, phases,
                num_qubits=_sv_n(qureg), reg_qubits=sregs, encoding=PF.UNSIGNED,
                func_name=PF.SCALED_PRODUCT, conj=True,
            )
        qureg.qasm_log.comment("here a controlled-phase ladder (QFT layer) was applied")
    for i in range(n // 2):
        swapGate(qureg, qubits[i], qubits[n - i - 1])


def _qft_fused(qureg: Qureg, qubits) -> bool:
    """Fused QFT (circuit.fused_qft): per-layer elementwise ladder passes +
    one scheduled low-qubit window pass + ONE bit-reversal permute for the
    whole swap network (both halves at once for a density matrix), instead
    of the reference's per-layer dispatch (agnostic_applyQFT,
    QuEST_common.c:836-898).  Applies when the targeted qubits are a
    contiguous ascending run starting at 0 or >= 7 and the state vector is
    window-sized; otherwise returns False and the layered path runs.

    Sharded registers: a FULL-register statevector QFT runs as ONE
    explicit shard_map program (dist.fused_qft_sharded — ppermute H
    exchanges for mesh-bit layers, the same Pallas ladder kernels
    per-shard for local layers, and an all_to_all bit-reversal); partial
    and density QFTs run the general-run shard_map kernel
    (dist.fused_qft_runs_sharded), so the fused kernel set runs on real
    TPU meshes for EVERY QFT shape (QuEST_internal.h:63-292
    one-kernel-set contract).  Only the explicitly-opted-out GSPMD mode
    (dist.use_explicit_dist(False)) retains a layered-path fallback on
    real multi-chip TPU meshes (a raw pallas_call has no GSPMD
    partitioning rule)."""
    import jax as _jax

    from quest_tpu import circuit as CIRC
    from quest_tpu.parallel import dist as PAR

    nsv = _sv_n(qureg)
    if nsv < CIRC.WINDOW:
        return False
    env = qureg.env
    nt = len(qubits)
    start = qubits[0]
    if list(qubits) != list(range(start, start + nt)):
        return False
    if not (start == 0 or start >= CIRC.LANE):
        return False

    sharded = _spans_mesh(qureg)
    if sharded:
        r = PAR.num_shard_bits(env.mesh)
        if (not qureg.is_density_matrix and start == 0 and nt == nsv
                and nsv - r >= r):
            qureg.amps = PAR.fused_qft_sharded(
                qureg.amps, mesh=env.mesh, num_qubits=nsv)
            _qft_qasm_trail(qureg, qubits, nt)
            return True
        if PAR.explicit_dist_enabled():
            # partial-register / density QFT on a sharded register: the
            # general-run shard_map kernel (fully-local runs execute the
            # unsharded fused kernels per shard; runs reaching mesh bits
            # use ppermute layers + the mixed bit reversal)
            runs = [(start, nt, False)]
            if qureg.is_density_matrix:
                runs.append((start + _shift(qureg), nt, True))
            qureg.amps = PAR.fused_qft_runs_sharded(
                qureg.amps, mesh=env.mesh, num_qubits=nsv,
                runs=tuple(runs))
            _qft_qasm_trail(qureg, qubits, nt)
            return True
        if _jax.default_backend() == "tpu":
            # opted-out GSPMD mode cannot partition the raw Pallas
            # kernels on a real mesh: layered path
            return False

    shifts = [0, _shift(qureg)] if qureg.is_density_matrix else [0]
    qureg.amps = CIRC.fused_qft(qureg.device_amps(), nsv, start, nt,
                                shifts=shifts)
    _qft_qasm_trail(qureg, qubits, nt)
    return True


def _qft_qasm_trail(qureg: Qureg, qubits, nt: int) -> None:
    """QASM record mirroring the layered path's trail."""
    for q in range(nt - 1, -1, -1):
        qureg.qasm_log.gate("h", (), qubits[q])
        if q:
            qureg.qasm_log.comment(
                "here a controlled-phase ladder (QFT layer) was applied")
    for i in range(nt // 2):
        qureg.qasm_log.gate("swap", (qubits[i],), qubits[nt - 1 - i])


# ---------------------------------------------------------------------------
# Circuit optimizer knob (optimizer.py, docs/design.md §26)
# ---------------------------------------------------------------------------


def setCircuitOptimizer(mode: Optional[str]) -> None:
    """Select the circuit-optimizer mode for subsequent fusion drains:
    ``"off"``, ``"on"`` (cancellation/merging, diagonal coalescing, and
    greedy cost-guided reordering), or ``"aggressive"`` (wider reorder
    search + near-identity drops).  ``None`` returns control to the
    ``QT_OPTIMIZER`` env var.  The mode is part of the fusion plan-cache
    key and the batch structure fingerprint, so flipping it retraces
    rather than replaying a stale plan."""
    from . import optimizer as _optimizer

    _optimizer.set_circuit_optimizer(mode)


def getCircuitOptimizer() -> str:
    """The active circuit-optimizer mode string."""
    from . import optimizer as _optimizer

    return _optimizer.get_circuit_optimizer()


# ---------------------------------------------------------------------------
# QASM recording (QuEST.h:3351-3390)
# ---------------------------------------------------------------------------


def startRecordingQASM(qureg: Qureg) -> None:
    """Begin recording API gates as OPENQASM 2.0 (QuEST.h:3351)."""
    qureg.qasm_log.start()


def stopRecordingQASM(qureg: Qureg) -> None:
    """Stop recording QASM (QuEST.h:3362)."""
    qureg.qasm_log.stop()


def clearRecordedQASM(qureg: Qureg) -> None:
    """Clear the register's recorded QASM buffer (QuEST.h:3370)."""
    qureg.qasm_log.clear()


def printRecordedQASM(qureg: Qureg) -> None:
    """Print the recorded QASM to stdout (QuEST.h:3379)."""
    print(str(qureg.qasm_log), end="")


def writeRecordedQASMToFile(qureg: Qureg, filename: str) -> None:
    """Write the recorded QASM to a file (QuEST.h:3390)."""
    try:
        with open(filename, "w") as f:
            f.write(str(qureg.qasm_log))
    except OSError:
        raise V.QuESTError(f"writeRecordedQASMToFile: Could not open file {filename}")
