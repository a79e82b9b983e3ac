"""Public API: the TPU-native equivalent of the reference's dispatch layer
(QuEST/src/QuEST.c) exposing the full ~140-function surface of QuEST.h.

Every function follows the reference's dispatch shape (QuEST.c:177-186):
validate -> kernel on the ket qubits -> if density matrix, conjugated twin
kernel on the bra qubits (+numQubits shift; QuEST.c:8-10,181-183) -> QASM
record.  Kernels are jit-compiled pure functions over the register's on-HBM
amplitude array (quest_tpu.ops.*); the register object just re-binds its
``amps`` handle, so a chain of API calls is a chain of donated in-place XLA
updates.

Semantic trap preserved (SURVEY.md §2.3): the ``apply*`` family
(applyMatrix2/4/N, applyMultiControlledMatrixN, applyPauliSum/Hamil,
applyPhaseFunc*, applyDiagonalOp) performs NO unitarity validation and NO
density-matrix twin — on a density matrix it left-multiplies
(QuEST.c:1074-1105).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import env as _env
from . import fusion as _fusion
from . import rng as _rng
from . import telemetry as _telemetry
from . import validation as V
from .ops import calculations as C
from .ops import cplx as CX
from .parallel import dist as PAR
from .ops import density as D
from .ops import gatedefs as G
from .ops import kernels as K
from .ops import paulis as P
from .ops import phasefunc as PF
from .precision import complex_dtype, real_dtype, validation_eps
from .qureg import DiagonalOp, PauliHamil, Qureg

# pauliOpType (QuEST.h:96)
PAULI_I, PAULI_X, PAULI_Y, PAULI_Z = 0, 1, 2, 3

# per-kernel-family dispatch counters: keys prebuilt once so the per-gate
# hot-loop cost is one int test + one dict upsert (telemetry.inc_key)
_K_UNITARY = _telemetry.counter_key("dispatch_total", family="unitary")
_K_DIAG = _telemetry.counter_key("dispatch_total", family="diag")
_K_NOT = _telemetry.counter_key("dispatch_total", family="not")
_K_PARITY = _telemetry.counter_key("dispatch_total", family="parity_phase")
_K_SWAP = _telemetry.counter_key("dispatch_total", family="swap")
_K_PERM = _telemetry.counter_key("dispatch_total", family="permutation")
# bitEncoding (QuEST.h:269)
UNSIGNED, TWOS_COMPLEMENT = 0, 1


def _bw(qureg) -> int:
    """Telemetry weight of one dispatch on this register: a BatchedQureg
    applies every gate to all B bank elements, so dispatch_total counts
    B logical gate applications (batch.py; telemetry truthfulness under
    batching)."""
    return int(getattr(qureg, "batch_size", 0) or 0) or 1


def _guard_batched_eager(qureg, what: str) -> None:
    """A BatchedQureg's (B, 2, 2^n) bank only flows through the fused
    drain (vmapped) and the batch helpers — the eager scalar kernels
    would silently misread the leading batch axis, so falling out of the
    capture path is a structured error, never a wrong answer."""
    if getattr(qureg, "batch_size", 0):
        raise V.QuESTError(
            f"{what}: the operation fell out of the fused capture path, "
            "and a BatchedQureg bank has no eager scalar dispatch — keep "
            "gates within fusion limits (<= "
            f"{_fusion.FUSION_MAX_GATE_QUBITS} qubits, shard-local on a "
            "mesh) or use the quest_tpu.batch helpers")

# ---------------------------------------------------------------------------
# Environment (QuEST.h:1851-1939)
# ---------------------------------------------------------------------------

createQuESTEnv = _env.create_quest_env
initDistributed = _env.init_distributed  # multi-host MPI_Init analogue
destroyQuESTEnv = _env.destroy_quest_env
syncQuESTEnv = _env.sync_quest_env
syncQuESTSuccess = _env.sync_quest_success
reportQuESTEnv = _env.report_quest_env
getEnvironmentString = _env.get_environment_string
seedQuEST = _env.seed_quest
seedQuESTDefault = _env.seed_quest_default
QuESTError = V.QuESTError


def copyStateToGPU(qureg: Qureg) -> None:
    """No-op: amplitudes are always device-resident (the reference GPU
    backend keeps a host mirror it must sync, QuEST_gpu.cu:517-539)."""


def copyStateFromGPU(qureg: Qureg) -> None:
    """No-op: see copyStateToGPU."""


def invalidQuESTInputError(errMsg: str, errFunc: str):
    """Reference's overridable error hook (QuEST.h:5354); in Python the
    equivalent is catching QuESTError."""
    raise V.QuESTError(f"{errFunc}: {errMsg}")


# ---------------------------------------------------------------------------
# Register lifecycle (QuEST.c:36-76)
# ---------------------------------------------------------------------------


def createQureg(numQubits: int, env: _env.QuESTEnv) -> Qureg:
    """Create a state-vector register of numQubits qubits (QuEST.h:529).
    Admission-controlled: with an HBM budget active, a register whose
    modeled footprint does not fit raises a structured
    MemoryAdmissionError BEFORE any device allocation (governor.py) —
    the governed analogue of validateMemoryAllocationSize."""
    from . import governor as _gov

    V.validate_num_qubits(numQubits, "createQureg", num_ranks=env.num_ranks)
    q = Qureg(numQubits, env, is_density_matrix=False)
    _gov.admit_new(q, "createQureg")
    _init_state(q, "basis", 0,
                lambda: K.init_zero_state(q.num_amps_total, q.dtype))
    return q


def createDensityQureg(numQubits: int, env: _env.QuESTEnv) -> Qureg:
    """Create a density-matrix register (state-vector of 2N qubits) (QuEST.h:623)."""
    from . import governor as _gov

    V.validate_num_qubits(numQubits, "createDensityQureg", num_ranks=env.num_ranks)
    q = Qureg(numQubits, env, is_density_matrix=True)
    _gov.admit_new(q, "createDensityQureg")
    q.amps = q.device_put(
        K.init_classical_density(numQubits, 0, q.dtype)
    )
    return q


def createCloneQureg(qureg: Qureg, env: _env.QuESTEnv) -> Qureg:
    """Create a new register cloning an existing one (QuEST.h:644)."""
    from . import governor as _gov

    q = Qureg(qureg.num_qubits_represented, env, qureg.is_density_matrix)
    _gov.admit_new(q, "createCloneQureg")
    q.amps = jnp.array(qureg.amps, copy=True)
    return q


def destroyQureg(qureg: Qureg, env: Optional[_env.QuESTEnv] = None) -> None:
    """Free a register's amplitude storage (QuEST.h:666)."""
    from . import governor as _gov

    _gov.release(qureg)
    qureg.amps = None


def reportState(qureg: Qureg) -> None:
    """Dump amplitudes to one state_rank_<r>.csv per amplitude chunk — the
    reference writes one file per MPI rank from that rank's chunk
    (QuEST_common.c:229-245, header on rank 0 only); here each mesh
    device's shard plays the chunk role, so no full-state gather to one
    host buffer ever happens."""
    from .parallel import dist as PAR

    amps = qureg.amps
    # chunk = amp-axis shard size (NOT total/num_devices: a multi-axis
    # (dp, amps) mesh has fewer amplitude shards than devices)
    env = qureg.env
    ndev_amp = PAR.amp_axis_size(env.mesh) if env.mesh is not None else 1
    chunk = (qureg.num_amps_total // ndev_amp
             if qureg.num_amps_total >= ndev_amp else qureg.num_amps_total)
    shards = sorted(
        amps.addressable_shards,
        key=lambda sh: (sh.index[1].start or 0) if len(sh.index) > 1 else 0,
    )
    seen = set()
    for sh in shards:
        start = (sh.index[1].start or 0) if len(sh.index) > 1 else 0
        rank = start // chunk if chunk else 0
        if rank in seen:   # replicated small registers: write once
            continue
        seen.add(rank)
        data = np.asarray(sh.data)
        with open(f"state_rank_{rank}.csv", "w") as f:
            if rank == 0:
                f.write("real, imag\n")
            for re, im in zip(data[0], data[1]):
                f.write(f"{re:.12f}, {im:.12f}\n")


def reportStateToScreen(qureg: Qureg, env=None, reportRank: int = 0) -> None:
    """Print all amplitudes to stdout (QuEST.h:1289)."""
    from .debug import _guard_host_gather

    _guard_host_gather(qureg, "reportStateToScreen")
    amps = np.asarray(qureg.amps)
    print("Reporting state from rank 0:")
    for re, im in zip(amps[0], amps[1]):
        print(f"{re} {im}")


def reportQuregParams(qureg: Qureg) -> None:
    """Print register metadata (QuEST.h:1297)."""
    print(f"QUBITS:\nNumber of qubits is {qureg.num_qubits_represented}.")
    print(f"Number of amps is {qureg.num_amps_total}.")
    print(f"Number of amps per rank is {qureg.num_amps_per_chunk}.")


def getNumQubits(qureg: Qureg) -> int:
    """Number of qubits represented (QuEST.h:1333)."""
    return qureg.num_qubits_represented


def getNumAmps(qureg: Qureg) -> int:
    """Number of amplitudes (2^numQubits) (QuEST.h:1351)."""
    V.validate_state_vector(qureg, "getNumAmps")
    return qureg.num_amps_total


# ---------------------------------------------------------------------------
# Matrix / operator structures (QuEST.c:1383-1602)
# ---------------------------------------------------------------------------


def createComplexMatrixN(numQubits: int) -> np.ndarray:
    """Allocate a 2^N x 2^N complex matrix (QuEST.h:721)."""
    V.validate_num_qubits(numQubits, "createComplexMatrixN")
    dim = 1 << numQubits
    return np.zeros((dim, dim), dtype=np.complex128)


def destroyComplexMatrixN(matrix) -> None:
    """Free a ComplexMatrixN (no-op placeholder for parity) (QuEST.h:739)."""
    pass


def initComplexMatrixN(m: np.ndarray, reals, imags) -> None:
    """Fill a ComplexMatrixN from real/imag nested lists (QuEST.h:764)."""
    m[...] = np.asarray(reals, dtype=np.float64) + 1j * np.asarray(imags, np.float64)


def getStaticComplexMatrixN(reals, imags) -> np.ndarray:
    return np.asarray(reals, dtype=np.float64) + 1j * np.asarray(imags, np.float64)


def createPauliHamil(numQubits: int, numSumTerms: int) -> PauliHamil:
    """Allocate a PauliHamil (flat pauli codes + term coefficients) (QuEST.h:802)."""
    V.validate_hamil_params(numQubits, numSumTerms, "createPauliHamil")
    return PauliHamil(numQubits, numSumTerms)


def destroyPauliHamil(hamil: PauliHamil) -> None:
    """Free a PauliHamil (QuEST.h:810)."""
    pass


def createPauliHamilFromFile(filename: str) -> PauliHamil:
    """Text format: per line 'coeff code_0 code_1 ... code_{n-1}'
    (reference parser, QuEST.c:1405-1488; file-specific error codes from
    QuEST_validation.c:539-545, 660-697)."""
    func = "createPauliHamilFromFile"
    try:
        with open(filename) as f:
            lines = [ln.split() for ln in f if ln.strip()]
    except OSError:
        V.validate_file_opened(False, filename, func)
    num_qubits = len(lines[0]) - 1 if lines else 0
    num_terms = len(lines)
    V.validate_hamil_file_params(num_qubits, num_terms, filename, func)
    h = PauliHamil(num_qubits, num_terms)
    for t, toks in enumerate(lines):
        V.validate_hamil_file_pauli_parsed(len(toks) == num_qubits + 1,
                                           filename, func)
        try:
            h.term_coeffs[t] = float(toks[0])
        except ValueError:
            V.validate_hamil_file_coeff_parsed(False, filename, func)
        codes = []
        for x in toks[1:]:
            try:
                codes.append(int(x))
            except ValueError:
                V.validate_hamil_file_pauli_parsed(False, filename, func)
        for c in codes:
            V.validate_hamil_file_pauli_code(c, filename, func)
        h.pauli_codes[t, :] = codes
    return h


def initPauliHamil(hamil: PauliHamil, coeffs, codes) -> None:
    """Fill a PauliHamil from coefficients and pauli codes (QuEST.h:897)."""
    V.validate_hamil_params(hamil.num_qubits, hamil.num_sum_terms, "initPauliHamil")
    codes = np.asarray(codes).reshape(hamil.num_sum_terms, hamil.num_qubits)
    V.validate_pauli_codes(codes.ravel(), "initPauliHamil")
    hamil.term_coeffs[:] = np.asarray(coeffs, dtype=np.float64)
    hamil.pauli_codes[...] = codes


def reportPauliHamil(hamil: PauliHamil) -> None:
    """Print a PauliHamil in the reference text format (QuEST.h:1321)."""
    for t in range(hamil.num_sum_terms):
        codes = " ".join(str(int(c)) for c in hamil.pauli_codes[t])
        print(f"{hamil.term_coeffs[t]:g}\t{codes}")


def createDiagonalOp(numQubits: int, env: _env.QuESTEnv) -> DiagonalOp:
    """Allocate a distributed diagonal operator (QuEST.h:977)."""
    V.validate_num_qubits_in_diag_op(numQubits, env.num_ranks, "createDiagonalOp")
    return DiagonalOp(numQubits, env)


def destroyDiagonalOp(op: DiagonalOp, env=None) -> None:
    """Free a DiagonalOp (QuEST.h:991)."""
    pass


def syncDiagonalOp(op: DiagonalOp) -> None:
    """No-op: the reference must mirror host arrays into
    op.deviceOperator (QuEST.h:297); ours are always device-resident."""


def initDiagonalOp(op: DiagonalOp, reals, imags) -> None:
    """Fill a DiagonalOp from real/imag arrays (QuEST.h:1039)."""
    rdt = real_dtype()
    dim = 1 << op.num_qubits
    sharding = op.env.sharding_for_dim(dim)
    V.validate_finite(np.asarray(reals), "initDiagonalOp")
    V.validate_finite(np.asarray(imags), "initDiagonalOp")
    op.real = jax.device_put(jnp.asarray(np.asarray(reals), rdt), sharding)
    op.imag = jax.device_put(jnp.asarray(np.asarray(imags), rdt), sharding)


def setDiagonalOpElems(op: DiagonalOp, startInd: int, reals, imags, numElems: int) -> None:
    """Overwrite a contiguous range of diagonal-operator elements (QuEST.h:1185)."""
    reals = np.asarray(reals, dtype=np.float64)[:numElems]
    imags = np.asarray(imags, dtype=np.float64)[:numElems]
    V.validate_num_elems(op, startInd, numElems, "setDiagonalOpElems")
    V.validate_finite(reals, "setDiagonalOpElems")
    V.validate_finite(imags, "setDiagonalOpElems")
    op.real = op.real.at[startInd:startInd + numElems].set(reals.astype(op.real.dtype))
    op.imag = op.imag.at[startInd:startInd + numElems].set(imags.astype(op.imag.dtype))


def initDiagonalOpFromPauliHamil(op: DiagonalOp, hamil: PauliHamil) -> None:
    """Requires an all-I/Z Hamiltonian; diagonal_d = sum_t c_t prod_q
    (-1)^{z_q(d)}, computed ON DEVICE over the sharded index space
    (reference agnostic_initDiagonalOpFromPauliHamil,
    QuEST_cpu.c:4188-4227; paulis.diag_from_z_hamil)."""
    V.validate_diag_pauli_hamil(op, hamil, "initDiagonalOpFromPauliHamil")
    codes = np.asarray(hamil.pauli_codes)
    zmasks = np.zeros(hamil.num_sum_terms, np.uint64)
    for q in range(hamil.num_qubits):
        zmasks |= ((codes[:, q] == PAULI_Z).astype(np.uint64) << np.uint64(q))
    split = P._PAR_LO_BITS
    lo = (zmasks & np.uint64((1 << split) - 1)).astype(np.uint32)
    hi = (zmasks >> np.uint64(split)).astype(np.uint32)
    rdt = real_dtype()
    dim = 1 << op.num_qubits
    sharding = op.env.sharding_for_dim(dim)
    diag = P.diag_from_z_hamil(
        jnp.asarray(lo), jnp.asarray(hi),
        jnp.asarray(hamil.term_coeffs, rdt),
        num_qubits=op.num_qubits, dtype=rdt, sharding=sharding,
    )
    op.real = jax.device_put(diag, sharding)
    op.imag = jax.device_put(jnp.zeros((dim,), rdt), sharding)


def createDiagonalOpFromPauliHamilFile(filename: str, env: _env.QuESTEnv) -> DiagonalOp:
    """Build a diagonal operator from an all-Z PauliHamil file (QuEST.h:1137)."""
    hamil = createPauliHamilFromFile(filename)
    op = DiagonalOp(hamil.num_qubits, env)
    initDiagonalOpFromPauliHamil(op, hamil)
    return op


# ---------------------------------------------------------------------------
# State initialisation (QuEST.h:1361-1559)
# ---------------------------------------------------------------------------


def _init_state(qureg: Qureg, kind: str, x, flat_fn) -> None:
    """Write a basis / plus / blank state: built on the device in the
    register's device shape (Qureg.fill, old state donated), or from the
    flat host-side kernel for a register bank."""
    if qureg.device_shape() is None:
        qureg.amps = qureg.device_put(flat_fn())
    else:
        qureg.fill(kind, x)


def initBlankState(qureg: Qureg) -> None:
    """Set all amplitudes to zero (QuEST.h:1361)."""
    _init_state(qureg, "blank", 0, lambda: K.init_blank_state(
        qureg.num_amps_total, qureg.dtype))


def initZeroState(qureg: Qureg) -> None:
    """Set the register to |0...0> (QuEST.h:1375)."""
    if qureg.is_density_matrix:
        _init_state(qureg, "basis", 0, lambda: K.init_classical_density(
            qureg.num_qubits_represented, 0, qureg.dtype))
    else:
        _init_state(qureg, "basis", 0, lambda: K.init_zero_state(
            qureg.num_amps_total, qureg.dtype))
    qureg.qasm_log.init_zero()


def initPlusState(qureg: Qureg) -> None:
    """Set the register to |+>^n (uniform superposition) (QuEST.h:1394)."""
    if qureg.is_density_matrix:
        qureg.amps = qureg.device_put(
            D.init_pure_state_density(
                K.init_plus_state(1 << qureg.num_qubits_represented, qureg.dtype),
                num_qubits=qureg.num_qubits_represented,
            )
        )
    else:
        _init_state(qureg, "plus", 1.0 / math.sqrt(qureg.num_amps_total),
                    lambda: K.init_plus_state(qureg.num_amps_total,
                                              qureg.dtype))


def initClassicalState(qureg: Qureg, stateInd: int) -> None:
    """Set the register to a computational basis state (QuEST.h:1431)."""
    V.validate_state_index(qureg, stateInd, "initClassicalState")
    if qureg.is_density_matrix:
        dim = 1 << qureg.num_qubits_represented
        _init_state(qureg, "basis", stateInd * (dim + 1),
                    lambda: K.init_classical_density(
                        qureg.num_qubits_represented, stateInd, qureg.dtype))
    else:
        _init_state(qureg, "basis", stateInd,
                    lambda: K.init_classical_state(
                        qureg.num_amps_total, stateInd, qureg.dtype))


def initPureState(qureg: Qureg, pure: Qureg) -> None:
    """Initialise a register (or rho = |psi><psi|) from a pure state (QuEST.h:1451)."""
    V.validate_state_vector(pure, "initPureState")
    V.validate_matching_qureg_dims(qureg, pure, "initPureState")
    if qureg.is_density_matrix:
        qureg.amps = qureg.device_put(
            D.init_pure_state_density(pure.amps, num_qubits=qureg.num_qubits_represented)
        )
    else:
        qureg.amps = jnp.array(pure.amps, copy=True)


def initDebugState(qureg: Qureg) -> None:
    """Set amplitude k to (2k mod ..)/10 + i(2k+1 mod ..)/10 (test oracle state) (QuEST.h:1463)."""
    qureg.amps = qureg.device_put(K.init_debug_state(qureg.num_amps_total, qureg.dtype))


def initStateFromAmps(qureg: Qureg, reals, imags) -> None:
    """Set all amplitudes from real/imag arrays (QuEST.h:1490;
    state-vectors only, QuEST.c:157-158)."""
    V.validate_state_vector(qureg, "initStateFromAmps")
    re = np.asarray(reals, dtype=np.float64).ravel()
    im = np.asarray(imags, dtype=np.float64).ravel()
    if re.size != qureg.num_amps_total or im.size != qureg.num_amps_total:
        raise V.QuESTError("initStateFromAmps: Incorrect number of amplitudes.")
    V.validate_finite(re, "initStateFromAmps")
    V.validate_finite(im, "initStateFromAmps")
    qureg.amps = qureg.device_put(np.stack([re, im]))


def initSparseState(qureg: Qureg, indices, amps) -> None:
    """Initialise from a SPARSE amplitude list: ``state[indices[k]] =
    amps[k]``, every other amplitude zero (docs/design.md §28; sparse
    state preparation per arXiv:2504.08705).  State-vectors only.

    The register is admitted under the governor at SPARSE cost — the
    indices + values, not the dense 2^n footprint — and densifies
    lazily on the first touch under admission control
    (governor.admit_sparse_state), so a budget too tight for the dense
    state today still accepts the description and makes room when the
    first drain arrives.  On an ungoverned scalar register the dense
    state scatters directly on device (kernels.init_sparse_state) —
    either route produces bit-identical amplitudes."""
    from . import governor as _governor

    V.validate_state_vector(qureg, "initSparseState")
    _guard_batched_eager(qureg, "initSparseState")
    idx = np.asarray(indices, dtype=np.int64).ravel()
    vals = np.asarray(amps, dtype=np.complex128).ravel()
    if idx.size == 0 or idx.size != vals.size:
        raise V.QuESTError(
            "initSparseState: indices and amps must be non-empty and "
            "equal length.")
    if int(idx.min()) < 0 or int(idx.max()) >= qureg.num_amps_total:
        raise V.QuESTError("initSparseState: Invalid amplitude index.")
    if np.unique(idx).size != idx.size:
        raise V.QuESTError("initSparseState: duplicate amplitude indices.")
    V.validate_finite(vals.real, "initSparseState")
    V.validate_finite(vals.imag, "initSparseState")
    _telemetry.inc_key(_K_PERM, _bw(qureg))
    _telemetry.inc("sparse_inits_total")
    _telemetry.inc("sparse_init_amps_total", int(idx.size))
    # a wholesale init makes pending fused gates unobservable — drop
    # them like the amps setter does
    if qureg._fusion is not None and qureg._fusion.gates:
        qureg._fusion.gates.clear()
    if not _governor.enabled() and not _fusion._shard_bits(qureg):
        qureg.amps = qureg.device_put(K.init_sparse_state(
            qureg.num_amps_total, idx, vals.real, vals.imag, qureg.dtype))
    else:
        _governor.admit_sparse_state(qureg, idx, vals.real, vals.imag)


def initSparseClusteredState(qureg: Qureg, bases, blocks) -> None:
    """Initialise a sparse CLUSTERED state (arXiv:2504.08705): the
    nonzero amplitudes sit in contiguous blocks, ``state[bases[c] + k] =
    blocks[c][k]`` — the structured-sparsity workload class bench
    config 16 exercises.  Expands the blocks to a flat sparse list and
    delegates to :func:`initSparseState` (same admission semantics)."""
    bl = list(blocks)
    bs = np.asarray(bases, dtype=np.int64).ravel()
    if bs.size == 0 or bs.size != len(bl):
        raise V.QuESTError(
            "initSparseClusteredState: bases and blocks must be "
            "non-empty and equal length.")
    idx_parts = []
    val_parts = []
    for base, block in zip(bs, bl):
        v = np.asarray(block, dtype=np.complex128).ravel()
        if v.size == 0:
            raise V.QuESTError(
                "initSparseClusteredState: empty amplitude block.")
        idx_parts.append(int(base) + np.arange(v.size, dtype=np.int64))
        val_parts.append(v)
    initSparseState(qureg, np.concatenate(idx_parts),
                    np.concatenate(val_parts))


def setAmps(qureg: Qureg, startInd: int, reals, imags, numAmps: int) -> None:
    """Overwrite a contiguous range of amplitudes (QuEST.h:1537)."""
    V.validate_state_vector(qureg, "setAmps")
    V.validate_num_amps(qureg, startInd, numAmps, "setAmps")
    from .ops import element as E

    re = np.asarray(reals, dtype=np.float64).ravel()[:numAmps]
    im = np.asarray(imags, dtype=np.float64).ravel()[:numAmps]
    if re.size != numAmps or im.size != numAmps:
        raise V.QuESTError("setAmps: Incorrect number of amplitudes.")
    V.validate_finite(re, "setAmps")
    V.validate_finite(im, "setAmps")
    vals = np.stack([re, im]).astype(qureg.dtype)
    # layout-safe ranged write: tile-aligned block updates + edge tiles,
    # never the eager .at[].set() whose gather re-layouts a canonically-
    # held big state (ops/element.py)
    qureg.amps = E.set_amp_range(qureg.amps, int(startInd), vals)


def setDensityAmps(qureg: Qureg, reals, imags) -> None:
    """Debug API (QuEST_debug.h): overwrite all rho amplitudes."""
    V.validate_density_matrix(qureg, "setDensityAmps")
    re = np.asarray(reals, dtype=np.float64).ravel()
    im = np.asarray(imags, dtype=np.float64).ravel()
    V.validate_finite(re, "setDensityAmps")
    V.validate_finite(im, "setDensityAmps")
    qureg.amps = qureg.device_put(np.stack([re, im]))


def cloneQureg(targetQureg: Qureg, copyQureg: Qureg) -> None:
    """Overwrite targetQureg with a copy of copyQureg (QuEST.h:1559)."""
    V.validate_matching_qureg_types(targetQureg, copyQureg, "cloneQureg")
    V.validate_matching_qureg_dims(targetQureg, copyQureg, "cloneQureg")
    targetQureg.amps = jnp.array(copyQureg.amps, copy=True)


# ---------------------------------------------------------------------------
# Unitary dispatch helpers (QuEST.c:177-346 twin-op pattern)
# ---------------------------------------------------------------------------


def _sv_n(qureg: Qureg) -> int:
    return qureg.num_qubits_in_state_vec


def _shift(qureg: Qureg) -> int:
    return qureg.num_qubits_represented


def _dispatch_matrix(qureg, stacked, targets, controls, control_states):
    """Route a dense-matrix gate, updating the register IN PLACE: explicit
    ppermute path for sharded target qubits (the reference's Distributed
    kernels), ordinary kernel (GSPMD propagation) otherwise — the locality
    predicate of QuEST_cpu_distributed.c:366-371 as a trace-time branch.

    On a sharded register targets are addressed through the live
    logical->physical permutation (Qureg._perm): a multi-target gate
    reaching mesh-coordinate bits relocalizes with half-shard swaps and
    does NOT swap back — the permutation persists (mpiQulacs-style
    communication avoidance, arXiv:2203.16044), later gates hitting the
    same qubits pay ZERO exchanges, and canonical order rematerializes
    lazily on the next state read.  dist.use_lazy_remap(False) restores
    the reference's eager swap-in/swap-out pairs
    (QuEST_cpu_distributed.c:1447-1545)."""
    env = qureg.env
    n = _sv_n(qureg)
    # size of the amplitude-sharding axis, NOT total devices: meshes may
    # carry extra axes (e.g. the (dp, amps) training mesh)
    _guard_batched_eager(qureg, "_dispatch_matrix")
    ndev = PAR.amp_axis_size(env.mesh) if env.mesh is not None else 1
    if ndev > 1 and (1 << n) > ndev and PAR.explicit_dist_enabled():
        nloc = n - PAR.num_shard_bits(env.mesh)
        lazy = PAR.lazy_remap_enabled()
        if not lazy:
            _ = qureg.amps  # materialize any perm left by a lazy phase
        amps = qureg._amps_raw()  # drains any pending fusion first
        perm = qureg._perm
        ptargets = qureg._phys_bits(targets)
        pcontrols = qureg._phys_bits(controls)
        # recency bookkeeping BEFORE computing the eviction order below:
        # the current gate's qubits are the hottest
        for b in (*targets, *controls):
            qureg._use_clock += 1
            qureg._last_use[b] = qureg._use_clock
        high = [t for t in ptargets if t >= nloc]
        if not high:
            _telemetry.inc("dispatch_route_total", route="perm_local")
            qureg._set_amps_permuted(
                K.apply_matrix(
                    amps, stacked, num_qubits=n,
                    targets=ptargets, controls=pcontrols,
                    control_states=control_states),
                perm)
            return
        if len(ptargets) == 1:
            _telemetry.inc("dispatch_route_total", route="exchange_1q")
            qureg._set_amps_permuted(
                PAR.apply_matrix_1q_sharded(
                    amps, stacked, mesh=env.mesh, num_qubits=n,
                    target=ptargets[0], controls=pcontrols,
                    control_states=control_states),
                perm)
            return
        # evict least-recently-used residents: order the free pool by the
        # occupying LOGICAL qubit's last use (never-used first)
        inv = {p: q for q, p in enumerate(perm)} if perm is not None else None
        last = qureg._last_use
        free_order = sorted(
            range(nloc),
            key=lambda p: last.get(inv[p] if inv is not None else p, -1))
        swaps, new_targets = PAR.plan_relocalization(
            n, nloc, ptargets, pcontrols, free_order=free_order)
        if swaps is not None:
            _telemetry.inc("dispatch_route_total", route="relocalize")
            for lo, hi in swaps:
                amps = PAR.swap_sharded(
                    amps, mesh=env.mesh, num_qubits=n, qb_low=lo, qb_high=hi
                )
            amps = K.apply_matrix(
                amps, stacked, num_qubits=n, targets=new_targets,
                controls=pcontrols, control_states=control_states,
            )
            if not lazy:
                for lo, hi in reversed(swaps):
                    amps = PAR.swap_sharded(
                        amps, mesh=env.mesh, num_qubits=n, qb_low=lo,
                        qb_high=hi
                    )
                qureg.amps = amps
                return
            # no swap-back: fold the relocation into the permutation
            newperm = list(perm) if perm is not None else list(range(n))
            inv = [0] * n
            for q, p in enumerate(newperm):
                inv[p] = q
            for lo, hi in swaps:
                ql, qh = inv[lo], inv[hi]
                newperm[ql], newperm[qh] = hi, lo
                inv[lo], inv[hi] = qh, ql
            qureg._set_amps_permuted(amps, tuple(newperm))
            return
        # not enough free local qubits to relocalize (the reference
        # REJECTS such ops, QuEST_validation.c:469-471): materialize
        # canonical order and fall through to GSPMD propagation
    _telemetry.inc("dispatch_route_total", route="default")
    qureg.amps = K.apply_matrix(
        qureg.amps, stacked, num_qubits=n, targets=targets,
        controls=controls, control_states=control_states,
    )


def _apply_unitary(qureg, matrix, targets, controls=(), control_states=()):
    """Kernel on ket qubits; conjugated twin on bra qubits for rho
    (QuEST.c:181-183).  ``matrix`` is host complex; stacked to SoA here.
    Inside a gateFusion context the gate is buffered instead (fusion.py)."""
    targets = tuple(int(t) for t in targets)
    controls = tuple(int(c) for c in controls)
    control_states = tuple(int(s) for s in control_states)
    _telemetry.inc_key(_K_UNITARY, _bw(qureg))
    stacked = CX.soa(matrix)
    if _fusion.capture_unitary(qureg, stacked, targets, controls, control_states):
        return
    _dispatch_matrix(qureg, stacked, targets, controls, control_states)
    if qureg.is_density_matrix:
        sh = _shift(qureg)
        conj_stacked = np.stack([stacked[0], -stacked[1]])
        _dispatch_matrix(
            qureg,
            conj_stacked,
            tuple(t + sh for t in targets),
            tuple(c + sh for c in controls),
            control_states,
        )


def _apply_diag(qureg, diag, targets, controls=(), control_states=()):
    """Diagonal gates are elementwise in the computational basis, so they
    run at the PHYSICAL bit positions of a live permutation without any
    rematerialization (cf. the reference's no-pairing phase kernels,
    QuEST_cpu.c:3146-3361 — no exchange at any position)."""
    targets = tuple(int(t) for t in targets)
    controls = tuple(int(c) for c in controls)
    control_states = tuple(int(s) for s in control_states)
    _telemetry.inc_key(_K_DIAG, _bw(qureg))
    stacked = CX.soa(diag)
    if _fusion.capture_diag(qureg, stacked, targets, controls, control_states):
        return
    _guard_batched_eager(qureg, "_apply_diag")
    amps = qureg._amps_raw()  # drains any pending fusion first
    perm = qureg._perm
    qureg._set_amps_permuted(
        K.apply_diagonal(
            amps, stacked, num_qubits=_sv_n(qureg),
            targets=qureg._phys_bits(targets),
            controls=qureg._phys_bits(controls),
            control_states=control_states,
        ), perm)
    if qureg.is_density_matrix:
        sh = _shift(qureg)
        conj_stacked = np.stack([stacked[0], -stacked[1]])
        qureg._set_amps_permuted(
            K.apply_diagonal(
                qureg._amps_raw(), conj_stacked, num_qubits=_sv_n(qureg),
                targets=qureg._phys_bits(tuple(t + sh for t in targets)),
                controls=qureg._phys_bits(tuple(c + sh for c in controls)),
                control_states=control_states,
            ), perm)


# ---------------------------------------------------------------------------
# Unitaries (QuEST.h:1595-4744)
# ---------------------------------------------------------------------------


def phaseShift(qureg: Qureg, targetQubit: int, angle: float) -> None:
    """Shift the phase of the |1> amplitude of one qubit (QuEST.h:1595)."""
    V.validate_target(qureg, targetQubit, "phaseShift")
    _apply_diag(qureg, G.phase_shift_diag(angle), (targetQubit,))
    qureg.qasm_log.phase_shift(float(angle), (), targetQubit)


def controlledPhaseShift(qureg: Qureg, idQubit1: int, idQubit2: int, angle: float) -> None:
    """Controlled phase shift by the given angle (QuEST.h:1640)."""
    V.validate_control_target(qureg, idQubit1, idQubit2, "controlledPhaseShift")
    _apply_diag(qureg, G.phase_shift_diag(angle), (idQubit2,), (idQubit1,))
    qureg.qasm_log.phase_shift(float(angle), (idQubit1,), idQubit2)


def multiControlledPhaseShift(qureg: Qureg, controlQubits: Sequence[int], angle: float) -> None:
    """Phase on the all-ones state of the listed qubits.  List lengths
    replace the C API's explicit count arguments throughout this binding."""
    qubits = [int(q) for q in controlQubits]
    V.validate_multi_qubits(qureg, qubits, "multiControlledPhaseShift")
    _apply_diag(qureg, G.phase_shift_diag(angle), (qubits[-1],), tuple(qubits[:-1]))
    qureg.qasm_log.phase_shift(float(angle), tuple(qubits[:-1]), qubits[-1])


def controlledPhaseFlip(qureg: Qureg, idQubit1: int, idQubit2: int) -> None:
    """Controlled phase flip (controlled-Z) (QuEST.h:1723)."""
    V.validate_control_target(qureg, idQubit1, idQubit2, "controlledPhaseFlip")
    _apply_diag(qureg, G.Z_DIAG, (idQubit2,), (idQubit1,))
    qureg.qasm_log.gate("z", (idQubit1,), idQubit2)


def multiControlledPhaseFlip(qureg: Qureg, controlQubits: Sequence[int]) -> None:
    """Phase flip conditioned on all given qubits being 1 (QuEST.h:1768)."""
    qubits = [int(q) for q in controlQubits]
    V.validate_multi_qubits(qureg, qubits, "multiControlledPhaseFlip")
    _apply_diag(qureg, G.Z_DIAG, (qubits[-1],), tuple(qubits[:-1]))
    qureg.qasm_log.gate("z", tuple(qubits[:-1]), qubits[-1])


def sGate(qureg: Qureg, targetQubit: int) -> None:
    """Apply the S (phase) gate (QuEST.h:1801)."""
    V.validate_target(qureg, targetQubit, "sGate")
    _apply_diag(qureg, G.S_GATE_DIAG, (targetQubit,))
    qureg.qasm_log.gate("s", (), targetQubit)


def tGate(qureg: Qureg, targetQubit: int) -> None:
    """Apply the T (pi/8) gate (QuEST.h:1834)."""
    V.validate_target(qureg, targetQubit, "tGate")
    _apply_diag(qureg, G.T_GATE_DIAG, (targetQubit,))
    qureg.qasm_log.gate("t", (), targetQubit)


def compactUnitary(qureg: Qureg, targetQubit: int, alpha, beta) -> None:
    """Apply the compact unitary [[alpha, -conj(beta)], [beta, conj(alpha)]] (QuEST.h:2141)."""
    V.validate_target(qureg, targetQubit, "compactUnitary")
    alpha, beta = complex(alpha), complex(beta)
    if abs(abs(alpha) ** 2 + abs(beta) ** 2 - 1) > 64 * validation_eps():
        raise V.QuESTError("compactUnitary: Compact matrix formed by given complex numbers is not unitary.")
    m = G.compact_unitary_matrix(alpha, beta)
    _apply_unitary(qureg, m, (targetQubit,))
    qureg.qasm_log.unitary_2x2(np.array([[alpha, -np.conj(beta)], [beta, np.conj(alpha)]]), (), targetQubit)


def unitary(qureg: Qureg, targetQubit: int, u) -> None:
    """Arbitrary single-qubit unitary (QuEST.h:2182)."""
    V.validate_target(qureg, targetQubit, "unitary")
    V.validate_unitary(u, 1, "unitary")
    _apply_unitary(qureg, u, (targetQubit,))
    qureg.qasm_log.unitary_2x2(np.asarray(u, complex), (), targetQubit)


def rotateX(qureg: Qureg, rotQubit: int, angle: float) -> None:
    V.validate_target(qureg, rotQubit, "rotateX")
    _apply_unitary(qureg, G.rotate_x_matrix(angle), (rotQubit,))
    qureg.qasm_log.gate("Rx", (), rotQubit, [float(angle)])


def rotateY(qureg: Qureg, rotQubit: int, angle: float) -> None:
    V.validate_target(qureg, rotQubit, "rotateY")
    _apply_unitary(qureg, G.rotate_y_matrix(angle), (rotQubit,))
    qureg.qasm_log.gate("Ry", (), rotQubit, [float(angle)])


def rotateZ(qureg: Qureg, rotQubit: int, angle: float) -> None:
    V.validate_target(qureg, rotQubit, "rotateZ")
    _apply_diag(qureg, G.rotate_z_diag(angle), (rotQubit,))
    qureg.qasm_log.gate("Rz", (), rotQubit, [float(angle)])


def rotateAroundAxis(qureg: Qureg, rotQubit: int, angle: float, axis) -> None:
    """Rotation around an arbitrary Bloch axis (QuEST.h:2327)."""
    V.validate_target(qureg, rotQubit, "rotateAroundAxis")
    ax = _axis_vec(axis)
    V.validate_unit_vector(ax[0], ax[1], ax[2], "rotateAroundAxis")
    m = G.rotate_around_axis_matrix(angle, ax)
    _apply_unitary(qureg, m, (rotQubit,))
    qureg.qasm_log.unitary_2x2(np.asarray(m), (), rotQubit)


def controlledRotateX(qureg, controlQubit, targetQubit, angle) -> None:
    V.validate_control_target(qureg, controlQubit, targetQubit, "controlledRotateX")
    _apply_unitary(qureg, G.rotate_x_matrix(angle), (targetQubit,), (controlQubit,))
    qureg.qasm_log.gate("Rx", (controlQubit,), targetQubit, [float(angle)])


def controlledRotateY(qureg, controlQubit, targetQubit, angle) -> None:
    V.validate_control_target(qureg, controlQubit, targetQubit, "controlledRotateY")
    _apply_unitary(qureg, G.rotate_y_matrix(angle), (targetQubit,), (controlQubit,))
    qureg.qasm_log.gate("Ry", (controlQubit,), targetQubit, [float(angle)])


def controlledRotateZ(qureg, controlQubit, targetQubit, angle) -> None:
    V.validate_control_target(qureg, controlQubit, targetQubit, "controlledRotateZ")
    _apply_diag(
        qureg,
        G.rotate_z_diag(angle),
        (targetQubit,),
        (controlQubit,),
    )
    qureg.qasm_log.gate("Rz", (controlQubit,), targetQubit, [float(angle)])


def controlledRotateAroundAxis(qureg, controlQubit, targetQubit, angle, axis) -> None:
    """Controlled rotation around an arbitrary Bloch axis (QuEST.h:2486)."""
    V.validate_control_target(qureg, controlQubit, targetQubit, "controlledRotateAroundAxis")
    ax = _axis_vec(axis)
    V.validate_unit_vector(ax[0], ax[1], ax[2], "controlledRotateAroundAxis")
    m = G.rotate_around_axis_matrix(angle, ax)
    _apply_unitary(qureg, m, (targetQubit,), (controlQubit,))
    qureg.qasm_log.unitary_2x2(np.asarray(m), (controlQubit,), targetQubit)


def controlledCompactUnitary(qureg, controlQubit, targetQubit, alpha, beta) -> None:
    """Controlled compact unitary (QuEST.h:2537)."""
    V.validate_control_target(qureg, controlQubit, targetQubit, "controlledCompactUnitary")
    alpha, beta = complex(alpha), complex(beta)
    if abs(abs(alpha) ** 2 + abs(beta) ** 2 - 1) > 64 * validation_eps():
        raise V.QuESTError("controlledCompactUnitary: Compact matrix formed by given complex numbers is not unitary.")
    _apply_unitary(qureg, G.compact_unitary_matrix(alpha, beta), (targetQubit,), (controlQubit,))
    qureg.qasm_log.unitary_2x2(
        np.array([[alpha, -np.conj(beta)], [beta, np.conj(alpha)]]),
        (controlQubit,), targetQubit,
    )


def controlledUnitary(qureg, controlQubit, targetQubit, u) -> None:
    """Controlled arbitrary single-qubit unitary (QuEST.h:2588)."""
    V.validate_control_target(qureg, controlQubit, targetQubit, "controlledUnitary")
    V.validate_unitary(u, 1, "controlledUnitary")
    _apply_unitary(qureg, u, (targetQubit,), (controlQubit,))
    qureg.qasm_log.unitary_2x2(np.asarray(u, complex), (controlQubit,), targetQubit)


def multiControlledUnitary(qureg, controlQubits, targetQubit, u) -> None:
    """Multi-controlled arbitrary single-qubit unitary (QuEST.h:2652)."""
    controls, target = [int(c) for c in controlQubits], int(targetQubit)
    V.validate_multi_controls_target(qureg, controls, target, "multiControlledUnitary")
    V.validate_unitary(u, 1, "multiControlledUnitary")
    _apply_unitary(qureg, u, (target,), tuple(controls))
    qureg.qasm_log.unitary_2x2(np.asarray(u, complex), tuple(controls), target)


def multiStateControlledUnitary(qureg, controlQubits, controlStates, targetQubit, u) -> None:
    """Controlled unitary with per-control 0/1 condition states (QuEST.h:3877)."""
    controls = list(controlQubits)
    states = list(controlStates)
    V.validate_multi_controls_target(qureg, controls, targetQubit, "multiStateControlledUnitary")
    V.validate_control_states(controls, states, "multiStateControlledUnitary")
    V.validate_unitary(u, 1, "multiStateControlledUnitary")
    _apply_unitary(qureg, u, (targetQubit,), tuple(controls), tuple(states))
    qureg.qasm_log.unitary_2x2(np.asarray(u, complex), tuple(controls), targetQubit, states)


def pauliX(qureg: Qureg, targetQubit: int) -> None:
    """Apply Pauli-X (QuEST.h:2689)."""
    V.validate_target(qureg, targetQubit, "pauliX")
    _apply_not(qureg, (targetQubit,), ())
    qureg.qasm_log.gate("x", (), targetQubit)


def pauliY(qureg: Qureg, targetQubit: int) -> None:
    """Apply Pauli-Y (QuEST.h:2724)."""
    V.validate_target(qureg, targetQubit, "pauliY")
    _apply_unitary(qureg, G.PAULI_Y, (targetQubit,))
    qureg.qasm_log.gate("y", (), targetQubit)


def pauliZ(qureg: Qureg, targetQubit: int) -> None:
    """Apply Pauli-Z (QuEST.h:2762)."""
    V.validate_target(qureg, targetQubit, "pauliZ")
    _apply_diag(qureg, G.Z_DIAG, (targetQubit,))
    qureg.qasm_log.gate("z", (), targetQubit)


def hadamard(qureg: Qureg, targetQubit: int) -> None:
    """Apply the Hadamard gate (QuEST.h:2794)."""
    V.validate_target(qureg, targetQubit, "hadamard")
    _apply_unitary(qureg, G.HADAMARD, (targetQubit,))
    qureg.qasm_log.gate("h", (), targetQubit)


def controlledNot(qureg: Qureg, controlQubit: int, targetQubit: int) -> None:
    """Controlled Pauli-X (CNOT) (QuEST.h:2838)."""
    V.validate_control_target(qureg, controlQubit, targetQubit, "controlledNot")
    _apply_not(qureg, (targetQubit,), (controlQubit,))
    qureg.qasm_log.gate("x", (controlQubit,), targetQubit)


def multiQubitNot(qureg: Qureg, targs: Sequence[int]) -> None:
    """Pauli-X on several target qubits at once (QuEST.h:2971)."""
    targets = [int(t) for t in targs]
    V.validate_multi_targets(qureg, targets, "multiQubitNot")
    _apply_not(qureg, tuple(targets), ())
    for t in targets:
        qureg.qasm_log.gate("x", (), t)


def multiControlledMultiQubitNot(qureg, ctrls, targs) -> None:
    """Multi-controlled multi-target Pauli-X (QuEST.h:2914)."""
    controls, targets = [int(c) for c in ctrls], [int(t) for t in targs]
    V.validate_multi_controls_targets(qureg, controls, targets, "multiControlledMultiQubitNot")
    _apply_not(qureg, tuple(targets), tuple(controls))
    for t in targets:
        qureg.qasm_log.gate("x", tuple(controls), t)


def _apply_not(qureg, targets, controls, control_states=()):
    """NOTs are pure index-bit flips, position-independent — like
    _apply_diag they run at the physical positions of a live
    permutation."""
    _telemetry.inc_key(_K_NOT, _bw(qureg))
    if _fusion.capture_not(qureg, targets, controls, control_states):
        return
    _guard_batched_eager(qureg, "_apply_not")
    amps = qureg._amps_raw()  # drains any pending fusion first
    perm = qureg._perm
    qureg._set_amps_permuted(
        K.apply_multi_qubit_not(
            amps, num_qubits=_sv_n(qureg),
            targets=qureg._phys_bits(targets),
            controls=qureg._phys_bits(controls),
            control_states=control_states,
        ), perm)
    if qureg.is_density_matrix:
        sh = _shift(qureg)
        qureg._set_amps_permuted(
            K.apply_multi_qubit_not(
                qureg._amps_raw(), num_qubits=_sv_n(qureg),
                targets=qureg._phys_bits(tuple(t + sh for t in targets)),
                controls=qureg._phys_bits(tuple(c + sh for c in controls)),
                control_states=control_states,
            ), perm)


def controlledPauliY(qureg: Qureg, controlQubit: int, targetQubit: int) -> None:
    """Controlled Pauli-Y (QuEST.h:3013)."""
    V.validate_control_target(qureg, controlQubit, targetQubit, "controlledPauliY")
    _apply_unitary(qureg, G.PAULI_Y, (targetQubit,), (controlQubit,))
    qureg.qasm_log.gate("y", (controlQubit,), targetQubit)


_SWAP_SOA = np.stack([
    np.array([[1.0, 0, 0, 0], [0, 0, 1, 0], [0, 1, 0, 0], [0, 0, 0, 1]]),
    np.zeros((4, 4)),
])


def swapGate(qureg: Qureg, qubit1: int, qubit2: int) -> None:
    """Swap two qubits' amplitudes (QuEST.h:3768).

    On a sharded register under the lazy-permutation scheduler a SWAP is
    pure relabeling: it folds into the live logical->physical permutation
    at ZERO data-movement cost (where the reference's distributed
    statevec_swapQubitAmps exchanges half the state,
    QuEST_cpu_distributed.c:1397-1436); canonical order rematerializes on
    the next state read."""
    V.validate_unique_targets(qureg, qubit1, qubit2, "swapGate")
    _telemetry.inc_key(_K_SWAP, _bw(qureg))
    if _fusion.capture_unitary(qureg, _SWAP_SOA, (qubit1, qubit2)):
        qureg.qasm_log.gate("swap", (qubit1,), qubit2)
        return
    env = qureg.env
    ndev = PAR.amp_axis_size(env.mesh) if env.mesh is not None else 1
    if (PAR.lazy_remap_enabled() and PAR.explicit_dist_enabled()
            and ndev > 1 and qureg.num_amps_total >= env.num_devices):
        amps = qureg._amps_raw()
        n = _sv_n(qureg)
        perm = list(qureg._perm or range(n))
        pairs = [(qubit1, qubit2)]
        if qureg.is_density_matrix:
            sh = _shift(qureg)
            pairs.append((qubit1 + sh, qubit2 + sh))
        for a, b in pairs:
            perm[a], perm[b] = perm[b], perm[a]
        qureg._set_amps_permuted(amps, tuple(perm))
        qureg.qasm_log.gate("swap", (qubit1,), qubit2)
        return
    _guard_batched_eager(qureg, "swapGate")
    from . import circuit as _circ

    if _circ.perm_fast_enabled():
        # §28 relabel route: ONE transpose-shaped index relabel
        # (kernels.permute_qubits) instead of swap_qubit_amps' matmul
        # pass — covers ket and bra bits in the same kernel
        n = _sv_n(qureg)
        perm = list(range(n))
        pairs = [(qubit1, qubit2)]
        if qureg.is_density_matrix:
            sh = _shift(qureg)
            pairs.append((qubit1 + sh, qubit2 + sh))
        for a, b in pairs:
            perm[a], perm[b] = perm[b], perm[a]
        _telemetry.inc_key(_K_PERM, _bw(qureg))
        _telemetry.inc("permutation_gates_total", route="relabel")
        qureg.amps = K.permute_qubits(
            qureg.amps, num_qubits=n, perm=tuple(perm))
    else:
        qureg.amps = K.swap_qubit_amps(
            qureg.amps, num_qubits=_sv_n(qureg), qb1=qubit1, qb2=qubit2)
        if qureg.is_density_matrix:
            sh = _shift(qureg)
            qureg.amps = K.swap_qubit_amps(
                qureg.amps, num_qubits=_sv_n(qureg), qb1=qubit1 + sh,
                qb2=qubit2 + sh
            )
    qureg.qasm_log.gate("swap", (qubit1,), qubit2)


def sqrtSwapGate(qureg: Qureg, qb1: int, qb2: int) -> None:
    """Apply the square-root-of-SWAP gate (QuEST.h:3816)."""
    V.validate_unique_targets(qureg, qb1, qb2, "sqrtSwapGate")
    _apply_unitary(qureg, G.SQRT_SWAP, (qb1, qb2))
    qureg.qasm_log.gate("sqrtswap", (qb1,), qb2)


def multiRotateZ(qureg: Qureg, qubits: Sequence[int], angle: float) -> None:
    """Rotation generated by a product of Z operators (parity phase) (QuEST.h:3912)."""
    qubits, angle = [int(q) for q in qubits], float(angle)
    V.validate_multi_targets(qureg, qubits, "multiRotateZ")
    _apply_parity_phase(qureg, angle, tuple(qubits), ())
    qureg.qasm_log.comment(f"multiRotateZ(angle={angle:g}) on qubits {qubits}")


def multiControlledMultiRotateZ(qureg, controlQubits, targetQubits, angle) -> None:
    """Multi-controlled Z-product rotation (QuEST.h:4037)."""
    controls, targets = list(controlQubits), list(targetQubits)
    V.validate_multi_controls_targets(qureg, controls, targets, "multiControlledMultiRotateZ")
    _apply_parity_phase(qureg, angle, tuple(targets), tuple(controls))
    qureg.qasm_log.comment(
        f"multiControlledMultiRotateZ(angle={angle:g}) ctrls {controls} targs {targets}"
    )


def _apply_parity_phase(qureg, angle, qubits, controls, conj=False):
    # parity phases are index-derived (elementwise): physical positions
    # of the live permutation, no rematerialization
    _telemetry.inc_key(_K_PARITY, _bw(qureg))
    _guard_batched_eager(qureg, "_apply_parity_phase")
    a = -angle if conj else angle
    amps = qureg._amps_raw()  # drains any pending fusion first
    perm = qureg._perm
    qureg._set_amps_permuted(
        K.apply_parity_phase(
            amps, a, num_qubits=_sv_n(qureg),
            qubits=qureg._phys_bits(qubits),
            controls=qureg._phys_bits(controls),
        ), perm)
    if qureg.is_density_matrix:
        sh = _shift(qureg)
        qureg._set_amps_permuted(
            K.apply_parity_phase(
                qureg._amps_raw(), -a, num_qubits=_sv_n(qureg),
                qubits=qureg._phys_bits(tuple(q + sh for q in qubits)),
                controls=qureg._phys_bits(tuple(c + sh for c in controls)),
            ), perm)


def multiRotatePauli(qureg: Qureg, targetQubits, targetPaulis, angle: float) -> None:
    """Rotation generated by a product of Pauli operators (QuEST.h:3967)."""
    targets = [int(t) for t in targetQubits]
    paulis = [int(p) for p in targetPaulis]
    V.validate_multi_targets(qureg, targets, "multiRotatePauli")
    V.validate_pauli_codes(paulis, "multiRotatePauli")
    _multi_rotate_pauli(qureg, targets, paulis, float(angle), controls=())
    qureg.qasm_log.comment(
        f"multiRotatePauli(angle={angle:g}) on qubits {targets} paulis {paulis}"
    )


def multiControlledMultiRotatePauli(qureg, controlQubits, targetQubits, targetPaulis, angle) -> None:
    """Multi-controlled Pauli-product rotation (QuEST.h:4138)."""
    controls = [int(c) for c in controlQubits]
    targets = [int(t) for t in targetQubits]
    paulis = [int(p) for p in targetPaulis]
    V.validate_multi_controls_targets(qureg, controls, targets, "multiControlledMultiRotatePauli")
    V.validate_pauli_codes(paulis, "multiControlledMultiRotatePauli")
    _multi_rotate_pauli(qureg, targets, paulis, float(angle), controls=tuple(controls))
    qureg.qasm_log.comment(
        f"multiControlledMultiRotatePauli(angle={angle:g}) ctrls {controls} targs {targets} paulis {paulis}"
    )


_RY_M90 = G.RY_M90  # Z->X
_RX_P90 = G.RX_P90  # Z->Y


def _multi_rotate_pauli(qureg, targets, paulis, angle, controls):
    """Basis-rotate X/Y targets onto Z, multiRotateZ, unrotate
    (statevec_multiRotatePauli, QuEST_common.c:424-462).  The basis gates are
    applied through the twin-aware helpers so the rho path is automatic."""
    z_qubits = []
    for t, p in zip(targets, paulis):
        if p == PAULI_I:
            continue
        z_qubits.append(t)
        if p == PAULI_X:
            _apply_unitary(qureg, _RY_M90, (t,), controls)
        elif p == PAULI_Y:
            _apply_unitary(qureg, _RX_P90, (t,), controls)
    if z_qubits:
        _apply_parity_phase(qureg, angle, tuple(z_qubits), controls)
    for t, p in zip(targets, paulis):
        if p == PAULI_X:
            _apply_unitary(qureg, _RY_M90.conj().T, (t,), controls)
        elif p == PAULI_Y:
            _apply_unitary(qureg, _RX_P90.conj().T, (t,), controls)


def twoQubitUnitary(qureg: Qureg, targetQubit1: int, targetQubit2: int, u) -> None:
    """Arbitrary two-qubit unitary (QuEST.h:4353)."""
    V.validate_unique_targets(qureg, targetQubit1, targetQubit2, "twoQubitUnitary")
    V.validate_unitary(u, 2, "twoQubitUnitary")
    V.validate_multi_qubit_matrix_fits_in_node(qureg, 2, "twoQubitUnitary")
    _apply_unitary(qureg, u, (targetQubit1, targetQubit2))
    qureg.qasm_log.comment("twoQubitUnitary applied")


def controlledTwoQubitUnitary(qureg, controlQubit, targetQubit1, targetQubit2, u) -> None:
    """Controlled arbitrary two-qubit unitary (QuEST.h:4420)."""
    V.validate_multi_controls_targets(
        qureg, [controlQubit], [targetQubit1, targetQubit2], "controlledTwoQubitUnitary"
    )
    V.validate_unitary(u, 2, "controlledTwoQubitUnitary")
    V.validate_multi_qubit_matrix_fits_in_node(qureg, 2, "controlledTwoQubitUnitary")
    _apply_unitary(qureg, u, (targetQubit1, targetQubit2), (controlQubit,))
    qureg.qasm_log.comment("controlledTwoQubitUnitary applied")


def multiControlledTwoQubitUnitary(qureg, controlQubits, targetQubit1, targetQubit2, u) -> None:
    """Multi-controlled arbitrary two-qubit unitary (QuEST.h:4499)."""
    controls = list(controlQubits)
    V.validate_multi_controls_targets(
        qureg, controls, [targetQubit1, targetQubit2], "multiControlledTwoQubitUnitary"
    )
    V.validate_unitary(u, 2, "multiControlledTwoQubitUnitary")
    V.validate_multi_qubit_matrix_fits_in_node(qureg, 2, "multiControlledTwoQubitUnitary")
    _apply_unitary(qureg, u, (targetQubit1, targetQubit2), tuple(controls))
    qureg.qasm_log.comment("multiControlledTwoQubitUnitary applied")


def multiQubitUnitary(qureg: Qureg, targs: Sequence[int], u) -> None:
    """Arbitrary unitary on N target qubits (QuEST.h:4582)."""
    targets = list(targs)
    V.validate_multi_targets(qureg, targets, "multiQubitUnitary")
    V.validate_unitary(u, len(targets), "multiQubitUnitary")
    V.validate_multi_qubit_matrix_fits_in_node(qureg, len(targets), "multiQubitUnitary")
    _apply_unitary(qureg, u, tuple(targets))
    qureg.qasm_log.comment("multiQubitUnitary applied")


def controlledMultiQubitUnitary(qureg, ctrl, targs, u) -> None:
    """Controlled arbitrary multi-qubit unitary (QuEST.h:4655)."""
    targets = list(targs)
    V.validate_multi_controls_targets(qureg, [ctrl], targets, "controlledMultiQubitUnitary")
    V.validate_unitary(u, len(targets), "controlledMultiQubitUnitary")
    V.validate_multi_qubit_matrix_fits_in_node(qureg, len(targets), "controlledMultiQubitUnitary")
    _apply_unitary(qureg, u, tuple(targets), (ctrl,))
    qureg.qasm_log.comment("controlledMultiQubitUnitary applied")


def multiControlledMultiQubitUnitary(qureg, ctrls, targs, u) -> None:
    """Multi-controlled arbitrary multi-qubit unitary (QuEST.h:4744)."""
    controls, targets = list(ctrls), list(targs)
    V.validate_multi_controls_targets(qureg, controls, targets, "multiControlledMultiQubitUnitary")
    V.validate_unitary(u, len(targets), "multiControlledMultiQubitUnitary")
    V.validate_multi_qubit_matrix_fits_in_node(qureg, len(targets), "multiControlledMultiQubitUnitary")
    _apply_unitary(qureg, u, tuple(targets), tuple(controls))
    qureg.qasm_log.comment("multiControlledMultiQubitUnitary applied")


def _axis_vec(axis):
    if hasattr(axis, "x"):
        return (float(axis.x), float(axis.y), float(axis.z))
    ax = np.asarray(axis, dtype=np.float64)
    return (float(ax[0]), float(ax[1]), float(ax[2]))


class Vector:
    """3-vector for rotateAroundAxis (QuEST.h:198)."""

    def __init__(self, x: float, y: float, z: float):
        self.x, self.y, self.z = float(x), float(y), float(z)
