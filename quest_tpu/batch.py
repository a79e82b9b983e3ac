"""Batched registers: ensembles of same-structure circuits on one mesh.

The reference simulates exactly one register per program; running N
small-circuit variants (a VQE parameter sweep, randomized compiling, shot
batches, quantum trajectories) costs N full dispatch pipelines even
though every variant executes the SAME gate structure.  On TPU that
leaves the chip idle: a 20-qubit state is 16 MB — a fraction of one
core's HBM and far below the VPU's saturation point, so amortizing one
compiled program over a leading batch axis is close to free (the same
observation driving qHiPSTER's circuit batching, arXiv:1601.07195 §III,
and mpiQulacs' batched trajectory mode, arXiv:2203.16044 §V).

:class:`BatchedQureg` carries a (B, 2, 2^n) SoA amplitude bank — batch
OUTER, amplitudes inner, so the amplitude axis shards over the mesh
exactly as a scalar register's and every sharded dispatch wrapper works
unchanged per element.  Gate dispatch rides the existing fusion drain
(fusion._run) vmapped over the bank: the circuit plan, the live
logical->physical permutation, and the window-remap schedule are SHARED
across the batch because every element runs the same gate stream; only
the matrices may differ per element ((B, 2, s, s) ``Gate.mat``).
Measurement draws from a PER-ELEMENT key bank, so batched sampling is
bit-identical to B independent seeded runs.

On top of the bank:

- :class:`EnsembleScheduler` — ``submit()`` circuits, ``drain()`` runs
  them grouped by structural fingerprint and padded to power-of-two
  batch buckets, so the jit retrace count is bounded by the bucket
  count, not the submission count.
- :func:`run_trajectories` — quantum-trajectory (Monte-Carlo wavefunction)
  unraveling of mixDephasing / mixDepolarising / mixDamping as
  stochastic gate insertion over a trajectory bank, reducing observables
  with error bars; the B-trajectory mean converges to the exact density
  channel (ops/density.py) it unravels.
"""

from __future__ import annotations

import time
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import circuit as C
from . import fusion as _fusion
from . import telemetry as _telemetry
from .env import AMP_AXIS, QuESTEnv
from .qureg import Qureg
from .validation import QuESTError

__all__ = [
    "BatchedQureg",
    "EnsembleScheduler",
    "bank_gate_items",
    "bank_occupancy",
    "createBatchedQureg",
    "applyBatchedUnitary",
    "measureBatched",
    "calcExpecPauliSumBatched",
    "run_trajectories",
]


# ---------------------------------------------------------------------------
# The register bank
# ---------------------------------------------------------------------------


class BatchedQureg(Qureg):
    """B same-width registers as ONE (B, 2, 2^n) amplitude bank.

    Subclasses :class:`Qureg` so the whole read/drain protocol (the
    ``amps`` property, fusion drain, lazy permutation rematerialization,
    checkpointing) applies to the bank unchanged — fusion and the
    distributed remap detect the leading batch axis and vmap over it.
    Gates issued through the ordinary imperative API (hadamard,
    controlledNot, ...) are always captured into the fusion buffer (it
    re-arms itself after a ``stop_gate_fusion``); operations that would
    fall back to eager scalar dispatch raise a structured error instead
    of silently misreading the bank.
    """

    def __init__(self, num_qubits: int, env: QuESTEnv, batch_size: int, *,
                 is_density_matrix: bool = False, seeds=None):
        if int(batch_size) < 1:
            raise QuESTError(
                f"BatchedQureg: batch_size must be >= 1, got {batch_size}")
        super().__init__(num_qubits, env, is_density_matrix)
        self.batch_size = int(batch_size)
        self.seed_elements(seeds)

    # -- always-capturing fusion: the buffer re-arms after a
    #    stop_gate_fusion (resilience windows stop/start around every
    #    checkpoint) so API gates never fall through to eager dispatch --
    @property
    def _fusion(self):
        buf = self.__dict__.get("_fusion_buf")
        if buf is None:
            buf = _fusion.FusionBuffer()
            self.__dict__["_fusion_buf"] = buf
        return buf

    @_fusion.setter
    def _fusion(self, value):
        self.__dict__["_fusion_buf"] = value

    # -- per-element measurement keys ------------------------------------

    def seed_elements(self, seeds=None) -> None:
        """(Re)seed the per-element measurement key bank.  ``seeds[i]``
        seeds element i exactly as ``seedQuEST(seeds[i])`` would seed a
        standalone register's device measurement stream
        (ops/measurement._KeyState.seed), so batched outcomes are
        bit-identical to B independent runs.  Default: the global RNG
        seed with the element index folded in."""
        from .ops import measurement as M

        B = self.batch_size
        if seeds is None:
            from .rng import GLOBAL_RNG

            base = [int(s) for s in (getattr(GLOBAL_RNG, "_keys", None)
                                     or [0])]
            seeds = [base + [i] for i in range(B)]
        if len(seeds) != B:
            raise QuESTError(
                f"BatchedQureg: got {len(seeds)} seeds for a batch of {B}")
        keys = []
        for s in seeds:
            if isinstance(s, (int, np.integer)):
                s = [int(s)]
            ks = M._KeyState()
            ks.seed([int(x) for x in s])
            raw = jax.random.key_data(ks.key) \
                if jnp.issubdtype(ks.key.dtype, jax.dtypes.prng_key) \
                else ks.key
            keys.append(np.asarray(raw, dtype=np.uint32))
        self._mkeys = np.stack(keys)            # (B, key_words) uint32
        self._mshots = [0] * B                  # per-element shot counters

    def key_state(self) -> dict:
        """JSON-serializable per-element (key, shot counter) bank — the
        batched analogue of measurement._KeyState.get_state, carried in
        checkpoint metadata so resumed banks draw the same streams."""
        return {
            "keys": [[int(x) for x in row] for row in self._mkeys],
            "counters": [int(c) for c in self._mshots],
        }

    def set_key_state(self, state: dict) -> None:
        keys = state.get("keys")
        if keys is None or len(keys) != self.batch_size:
            raise QuESTError(
                "BatchedQureg: checkpoint key bank holds "
                f"{0 if keys is None else len(keys)} elements but the "
                f"register batch is {self.batch_size}")
        self._mkeys = np.array(keys, dtype=np.uint32)
        self._mshots = [int(c) for c in state.get(
            "counters", [0] * self.batch_size)]

    # -- bank-aware array plumbing ---------------------------------------

    def sharding(self):
        """Batch-outer / amps-inner: the amplitude axis (last) shards
        over the mesh exactly as a scalar register's, every element on
        every device's shard — collectives see B independent rows."""
        from jax.sharding import NamedSharding, PartitionSpec

        if self.num_amps_total >= self.env.num_devices:
            return NamedSharding(
                self.env.mesh, PartitionSpec(None, None, AMP_AXIS))
        return self.env.replicated_sharding()

    def _as_bank(self, value):
        """Lift a scalar (2, 2^n) write to the full bank (the init family
        writes one state for all elements); a (B, 2, 2^n) write binds
        element-wise."""
        value = jnp.asarray(value, self.dtype)
        if value.ndim == 2:
            value = jnp.broadcast_to(
                value[None], (self.batch_size,) + value.shape)
        elif value.ndim != 3 or value.shape[0] != self.batch_size:
            raise QuESTError(
                "BatchedQureg: expected amplitudes of shape (2, "
                f"{self.num_amps_total}) or ({self.batch_size}, 2, "
                f"{self.num_amps_total}), got {tuple(value.shape)}")
        return value

    @property
    def amps(self):
        return Qureg.amps.fget(self)

    @amps.setter
    def amps(self, value):
        Qureg.amps.fset(self, jax.device_put(self._as_bank(value),
                                             self.sharding()))

    def device_put(self, amps):
        return jax.device_put(self._as_bank(amps), self.sharding())

    def device_shape(self):
        """A bank keeps its flat (B, 2, 2^n) shape (None: no reshape)."""
        return None

    def element(self, i: int):
        """Canonical-order amplitudes of batch element ``i`` as a
        (2, 2^n) array (pending gates drain, permutation
        rematerializes)."""
        if not 0 <= int(i) < self.batch_size:
            raise QuESTError(
                f"BatchedQureg.element: index {i} out of range for batch "
                f"{self.batch_size}")
        return self.amps[int(i)]


def createBatchedQureg(numQubits: int, env: QuESTEnv, batchSize: int, *,
                       is_density_matrix: bool = False,
                       seeds=None) -> BatchedQureg:
    """Create a bank of ``batchSize`` registers in the zero state
    (|0...0> per element; |0...0><0...0| for a density bank).  ``seeds``
    optionally gives each element its own measurement stream seed
    (default: global seed + element index)."""
    from . import validation as V
    from .ops import kernels as K

    V.validate_num_qubits(numQubits, "createBatchedQureg",
                          num_ranks=env.num_ranks)
    q = BatchedQureg(numQubits, env, batchSize,
                     is_density_matrix=is_density_matrix, seeds=seeds)
    # admission is batch-aware: the modeled footprint carries the bank
    # dimension, so an oversized ensemble is refused before device_put
    from . import governor as _gov

    _gov.admit_new(q, "createBatchedQureg")
    if is_density_matrix:
        q.amps = K.init_classical_density(numQubits, 0, q.dtype)
    else:
        q.amps = K.init_zero_state(q.num_amps_total, q.dtype)
    return q


# ---------------------------------------------------------------------------
# Per-element gates
# ---------------------------------------------------------------------------


def _soa_per_element(mats, batch: int):
    """Stack per-element matrices to a concrete (B, 2, s, s) SoA array.
    Accepts (B, s, s) complex or (B, 2, s, s) already-stacked input."""
    from .ops import cplx as CX

    m = np.asarray(mats)
    if m.ndim == 3:
        m = np.stack([np.asarray(CX.soa(m[b])) for b in range(m.shape[0])])
    if m.ndim != 4 or m.shape[0] != batch or m.shape[1] != 2 \
            or m.shape[2] != m.shape[3]:
        raise QuESTError(
            "applyBatchedUnitary: expected matrices of shape (B, s, s) "
            f"complex or (B, 2, s, s) SoA with B={batch}, got "
            f"{tuple(np.asarray(mats).shape)}")
    return m


def applyBatchedUnitary(qureg: BatchedQureg, targets, mats,
                        controls=(), control_states=()) -> None:
    """Apply a DIFFERENT unitary to each batch element in one fused pass:
    ``mats[b]`` acts on element b's ``targets`` (density banks get the
    conjugated bra twin, as _apply_unitary does).  The per-element stack
    is planned against one shared program skeleton — a (B, 2, s, s)
    ``Gate.mat`` in the fusion buffer — so the bank still drains as one
    vmapped dispatch."""
    from . import api as _api

    if not getattr(qureg, "batch_size", 0):
        raise QuESTError(
            "applyBatchedUnitary: the register is not a BatchedQureg")
    targets = tuple(int(t) for t in targets)
    controls = tuple(int(c) for c in controls)
    B = qureg.batch_size
    stacked = _soa_per_element(mats, B)
    _telemetry.inc_key(_api._K_UNITARY, B)
    if controls:
        stacked = np.stack([
            C.controlled_dense(stacked[b], len(controls), control_states)
            for b in range(B)])
    bits = targets + controls
    if not _fusion._capturable(qureg, bits) or (
            qureg.is_density_matrix and not _fusion._capturable(
                qureg, tuple(b + qureg.num_qubits_represented
                             for b in bits))):
        raise QuESTError(
            "applyBatchedUnitary: the gate does not qualify for the fused "
            f"path (<= {_fusion.FUSION_MAX_GATE_QUBITS} qubits, and "
            "shard-local space for a distributed bank) — batched "
            "registers have no eager fallback")
    buf = qureg._fusion
    buf.gates.append(C.Gate(bits, stacked))
    if qureg.is_density_matrix:
        sh = qureg.num_qubits_represented
        cstacked = np.stack([stacked[:, 0], -stacked[:, 1]], axis=1)
        buf.gates.append(C.Gate(tuple(b + sh for b in bits), cstacked))


# ---------------------------------------------------------------------------
# Batched measurement
# ---------------------------------------------------------------------------


@partial(jax.jit,
         static_argnames=("num_qubits", "target", "is_density", "quad"),
         donate_argnums=0)
def _measure_bank(amps, keys, shots, *, num_qubits: int, target: int,
                  is_density: bool, quad: bool = False):
    from .ops import measurement as M

    def one(a, k, s):
        return M._measure_once(a, k, s, num_qubits, target, is_density,
                               quad)

    return jax.vmap(one)(amps, keys, shots)


def measureBatched(qureg: BatchedQureg, measureQubit: int):
    """Measure ``measureQubit`` on EVERY batch element in one vmapped
    program — each element draws from its OWN key/shot stream, so the
    (outcomes, probabilities) arrays are bit-identical to B independent
    seeded ``measure`` calls.  Collapses the bank in place; returns
    ((B,) int outcomes, (B,) float probabilities)."""
    from . import validation as V
    from .api_ops import _quad

    if not getattr(qureg, "batch_size", 0):
        raise QuESTError("measureBatched: the register is not a "
                         "BatchedQureg")
    V.validate_target(qureg, measureQubit, "measureBatched")
    B = qureg.batch_size
    _telemetry.inc("measurement_shots_total", B)
    amps, outs, probs = _measure_bank(
        qureg.amps, jnp.asarray(qureg._mkeys),
        jnp.asarray(qureg._mshots, jnp.int32),
        num_qubits=qureg.num_qubits_represented, target=int(measureQubit),
        is_density=qureg.is_density_matrix, quad=_quad())
    qureg.amps = amps
    qureg._mshots = [s + 1 for s in qureg._mshots]
    qureg.qasm_log.measure(int(measureQubit))
    return np.asarray(outs), np.asarray(probs)


# ---------------------------------------------------------------------------
# Batched expectation values
# ---------------------------------------------------------------------------


def calcExpecPauliSumBatched(qureg: BatchedQureg, codes, coeffs,
                             *, quad: Optional[bool] = None) -> np.ndarray:
    """Per-element <psi_b| sum_t c_t P_t |psi_b> over the bank as a (B,)
    array.  Elements evaluate through the SAME scan composite a scalar
    register would use (sharded direct body included), sliced from the
    bank — a (2, 2^n) slice of the (B, 2, 2^n) bank keeps the scalar
    sharding geometry, so per-element values are bit-identical to
    standalone runs."""
    from .api_ops import _quad as _qd
    from .ops import paulis as OPS_P

    if not getattr(qureg, "batch_size", 0):
        raise QuESTError("calcExpecPauliSumBatched: the register is not "
                         "a BatchedQureg")
    quad = _qd() if quad is None else bool(quad)
    codes = jnp.asarray(codes, jnp.int32)
    coeffs = jnp.asarray(coeffs)
    n = qureg.num_qubits_represented
    amps = qureg.amps
    nsh = _fusion._shard_bits(qureg)
    vals = []
    for b in range(qureg.batch_size):
        a = amps[b]
        if nsh:
            from .parallel import dist as PAR

            v = PAR.expec_pauli_sum_scan_sharded(
                a, codes, coeffs, mesh=qureg.env.mesh, num_qubits=n,
                quad=quad)
        else:
            v = OPS_P.expec_pauli_sum_scan(a, codes, coeffs, num_qubits=n,
                                           quad=quad)
        vals.append(v)
    return np.asarray([float(v) for v in vals])


# ---------------------------------------------------------------------------
# Ensemble scheduler
# ---------------------------------------------------------------------------


def _bucket_size(count: int, max_batch: int) -> int:
    """Next power of two >= count, capped at max_batch — padding to
    power-of-two buckets bounds the jit retrace count per circuit
    structure by the bucket count (log2(max_batch)+1), not the
    submission count."""
    b = 1
    while b < count:
        b <<= 1
    return min(b, max_batch)


def bank_occupancy(qureg, real: Optional[int] = None) -> dict:
    """Bucket occupancy of a batched register for the plan explainer
    (introspect.explain_circuit): the live batch size, the power-of-two
    bucket it pads to, and the real/padded fraction — the same quantity
    EnsembleScheduler publishes as the ``batch_occupancy`` gauge.

    ``real`` (serving layer): the bank was ALREADY padded to a
    power-of-two batch and only ``real`` of its elements carry live
    jobs — report true occupancy with the padding excluded."""
    bsz = int(getattr(qureg, "batch_size", 0) or 0)
    if not bsz:
        return {"size": 0, "bucket": 0, "occupancy": 1.0}
    if real is not None:
        return {"size": int(real), "bucket": bsz,
                "occupancy": int(real) / bsz}
    bucket = _bucket_size(bsz, 1 << 30)
    return {"size": bsz, "bucket": bucket, "occupancy": bsz / bucket}


def _structure_fingerprint(gates: Sequence, num_qubits: int,
                           is_density: bool) -> tuple:
    """Hashable circuit STRUCTURE (targets + matrix shapes, not values):
    submissions with equal fingerprints plan to the same program skeleton
    and may share a batch bucket.  The circuit-optimizer mode is part of
    the fingerprint — the optimizer rewrites the bank's shared item list
    before planning, so streams bucketed under different QT_OPTIMIZER
    modes must never share a batch."""
    from . import optimizer as _optimizer

    parts = [("q", int(num_qubits), bool(is_density), _optimizer.mode())]
    for g in gates:
        m = np.asarray(g.mat)
        parts.append((tuple(g.targets), m.shape[-1]))
    return tuple(parts)


def bank_gate_items(streams: Sequence[Sequence], num_qubits: int,
                    is_density: bool, *, qureg=None) -> List:
    """Fuse B same-STRUCTURE gate streams into ONE bank item list.

    ``streams[b]`` is submission b's gate sequence; all B must share a
    structural fingerprint (same targets and matrix shapes gate for
    gate).  Gate j collapses to one shared (2, s, s) item when every
    element's matrix is bitwise identical, else stacks to a per-element
    (B, 2, s, s) item (the applyBatchedUnitary representation); density
    banks get the conjugated bra twin after each item.  The result is
    appendable to a :class:`BatchedQureg`'s fusion buffer — the shared
    path of ``EnsembleScheduler._run_bucket`` and the window-stepped
    banks of :mod:`quest_tpu.serve` build their programs through here.

    ``qureg``: when given, each gate is validated against the fused
    path's capture limits (batched registers have no eager fallback).
    """
    B = len(streams)
    items: List = []
    for j in range(len(streams[0])):
        mats = [np.asarray(s[j].mat) for s in streams]
        targets = tuple(int(t) for t in streams[0][j].targets)
        if qureg is not None and (
                not _fusion._capturable(qureg, targets) or (
                    is_density and not _fusion._capturable(
                        qureg, tuple(t + num_qubits for t in targets)))):
            raise QuESTError(
                "bank_gate_items: gate does not qualify for the fused "
                f"path (<= {_fusion.FUSION_MAX_GATE_QUBITS} qubits, and "
                "shard-local space for a distributed bank) — batched "
                "registers have no eager fallback")
        if all(m.tobytes() == mats[0].tobytes() for m in mats[1:]):
            shared = mats[0]
            items.append(C.Gate(targets, shared))
            if is_density:
                items.append(C.Gate(
                    tuple(t + num_qubits for t in targets),
                    np.stack([shared[0], -shared[1]])))
        else:
            stacked = _soa_per_element(np.stack(mats), B)
            items.append(C.Gate(targets, stacked))
            if is_density:
                items.append(C.Gate(
                    tuple(t + num_qubits for t in targets),
                    np.stack([stacked[:, 0], -stacked[:, 1]], axis=1)))
    return items


class EnsembleScheduler:
    """Collect same-width circuit submissions and run them batched.

    ``submit(gates)`` queues a circuit (a sequence of
    :class:`quest_tpu.circuit.Gate` with concrete numpy SoA matrices —
    e.g. the same ansatz at different parameters); ``drain()`` groups the
    queue by structural fingerprint, pads each group to power-of-two
    batch buckets (<= ``max_batch``), runs every bucket as ONE
    BatchedQureg program, and returns each submission's final canonical
    (2, 2^n) amplitudes in submission order.  Identical matrices across
    a bucket collapse to one shared (2, s, s) gate; differing matrices
    ride the per-element (B, 2, s, s) path.  Records
    ``batch_occupancy`` (real/padded fraction), ``ensemble_circuits_total``
    and ``ensemble_circuits_per_sec`` telemetry."""

    def __init__(self, num_qubits: int, env: QuESTEnv, *,
                 is_density_matrix: bool = False, max_batch: int = 64):
        if max_batch < 1 or (max_batch & (max_batch - 1)):
            raise QuESTError(
                f"EnsembleScheduler: max_batch must be a power of two, "
                f"got {max_batch}")
        self.num_qubits = int(num_qubits)
        self.env = env
        self.is_density_matrix = bool(is_density_matrix)
        self.max_batch = int(max_batch)
        self._pending: List[Tuple[int, tuple, list, object]] = []
        self._next_id = 0

    def submit(self, gates: Sequence, *, seed=None) -> int:
        """Queue one circuit; returns its submission id (the index of its
        result in ``drain()``'s list)."""
        gates = list(gates)
        for g in gates:
            if not isinstance(g.mat, np.ndarray):
                raise QuESTError(
                    "EnsembleScheduler.submit: gate matrices must be "
                    "concrete numpy arrays (traced values cannot be "
                    "stacked across submissions)")
        fp = _structure_fingerprint(gates, self.num_qubits,
                                    self.is_density_matrix)
        sid = self._next_id
        self._next_id += 1
        self._pending.append((sid, fp, gates, seed))
        return sid

    def _run_bucket(self, group: list) -> Tuple[dict, int, int]:
        """Execute one fingerprint group bucket; returns
        ({sid: amps}, real, padded) so ``drain()`` can aggregate TRUE
        occupancy (padding excluded) across buckets."""
        real = len(group)
        B = _bucket_size(real, self.max_batch)
        # pad with copies of the last submission: padding elements run
        # (and are discarded), keeping the batch shape a power of two
        padded = group + [group[-1]] * (B - real)
        seeds = [s if s is not None else i
                 for i, (_, _, _, s) in enumerate(padded)]
        q = createBatchedQureg(
            self.num_qubits, self.env, B,
            is_density_matrix=self.is_density_matrix, seeds=seeds)
        from . import api as _api

        items = bank_gate_items([sub[2] for sub in padded],
                                self.num_qubits, self.is_density_matrix,
                                qureg=q)
        _telemetry.inc_key(_api._K_UNITARY, B * len(group[0][2]))
        q._fusion.gates.extend(items)
        bank = np.asarray(q.amps)
        _telemetry.observe("ensemble_bucket_occupancy", real / B)
        return {sub[0]: bank[i] for i, sub in enumerate(group)}, real, B

    def drain(self) -> List[np.ndarray]:
        """Run every pending submission; returns final canonical
        amplitudes in submission order and clears the queue.  The
        ``batch_occupancy`` gauge is set ONCE per drain to the
        aggregate real/padded fraction over every bucket run — a
        partially-filled trailing bucket no longer overwrites the gauge
        with its own (lower or higher) ratio."""
        if not self._pending:
            return []
        t0 = time.perf_counter()
        pending, self._pending = self._pending, []
        groups: dict = {}
        for sub in pending:
            groups.setdefault(sub[1], []).append(sub)
        results: dict = {}
        occ_real = occ_padded = 0
        with _telemetry.span("batch.ensemble_drain",
                             circuits=len(pending), groups=len(groups)):
            for group in groups.values():
                for i in range(0, len(group), self.max_batch):
                    res, real, padded = self._run_bucket(
                        group[i:i + self.max_batch])
                    results.update(res)
                    occ_real += real
                    occ_padded += padded
        if occ_padded:
            _telemetry.set_gauge("batch_occupancy", occ_real / occ_padded)
        dt = time.perf_counter() - t0
        _telemetry.inc("ensemble_circuits_total", len(pending))
        if dt > 0:
            _telemetry.set_gauge("ensemble_circuits_per_sec",
                                 len(pending) / dt)
        return [results[sub[0]] for sub in pending]


# ---------------------------------------------------------------------------
# Quantum trajectories (Monte-Carlo wavefunction unraveling)
# ---------------------------------------------------------------------------

_I2 = np.stack([np.eye(2), np.zeros((2, 2))])
_X2 = np.stack([np.array([[0., 1.], [1., 0.]]), np.zeros((2, 2))])
_Y2 = np.stack([np.zeros((2, 2)), np.array([[0., -1.], [1., 0.]])])
_Z2 = np.stack([np.diag([1., -1.]), np.zeros((2, 2))])


@partial(jax.jit, static_argnames=("num_qubits", "target"))
def _prob1_bank(amps, *, num_qubits: int, target: int):
    from .ops import calculations as CALC

    def one(a):
        return CALC.calc_prob_of_outcome_statevec(
            a, num_qubits=num_qubits, target=target, outcome=1)

    return jax.vmap(one)(amps)


def _sample_pauli_insertion(kind: str, prob: float, u: np.ndarray):
    """Per-trajectory Pauli choice for a unitary-proportional channel:
    dephasing flips Z with probability p; depolarising picks X/Y/Z with
    probability p/3 each (mixDephasing / mixDepolarising Kraus weights,
    which are STATE-INDEPENDENT — no norm feedback needed)."""
    B = u.shape[0]
    mats = np.broadcast_to(_I2, (B, 2, 2, 2)).copy()
    if kind == "dephasing":
        mats[u < prob] = _Z2
    else:  # depolarising
        third = prob / 3.0
        mats[u < third] = _X2
        mats[(u >= third) & (u < 2 * third)] = _Y2
        mats[(u >= 2 * third) & (u < prob)] = _Z2
    return mats


def _sample_damping(qureg: BatchedQureg, target: int, prob: float,
                    rng: np.random.Generator):
    """Amplitude damping is STATE-DEPENDENT: the jump probability is
    p * <1|rho_b|1>, so the bank drains, each element's excited-state
    population reads back, and the per-element renormalized Kraus branch
    (jump: sqrt(p)|0><1| / sqrt(p*p1); no-jump: diag(1, sqrt(1-p)) /
    sqrt(1-p*p1)) applies as one batched gate."""
    B = qureg.batch_size
    p1 = np.asarray(_prob1_bank(
        qureg.amps, num_qubits=qureg.num_qubits_represented,
        target=int(target)))
    pjump = np.clip(prob * p1, 0.0, 1.0)
    u = rng.random(B)
    jump = u < pjump
    mats = np.zeros((B, 2, 2, 2))
    for b in range(B):
        if jump[b]:
            mats[b, 0, 0, 1] = np.sqrt(prob) / np.sqrt(pjump[b])
        else:
            keep = max(1.0 - pjump[b], np.finfo(np.float64).tiny)
            mats[b, 0, 0, 0] = 1.0 / np.sqrt(keep)
            mats[b, 0, 1, 1] = np.sqrt(1.0 - prob) / np.sqrt(keep)
    return mats


_NOISE_KINDS = ("dephasing", "depolarising", "damping")


def run_trajectories(ops: Sequence, num_qubits: int, env: QuESTEnv,
                     n_traj: int, *, observable=None, seed: int = 0):
    """Unravel a noisy circuit as ``n_traj`` quantum trajectories run as
    ONE batched state-vector program.

    ``ops`` is a sequence of circuit entries in order:

    - a :class:`quest_tpu.circuit.Gate` (applied to every trajectory), or
    - ``(kind, target, prob)`` with kind in ``("dephasing",
      "depolarising", "damping")`` — the stochastic unraveling of the
      matching mix* density channel: each trajectory samples its own
      Kraus branch (host RNG, seeded by ``seed``) and the B choices
      apply as one per-element batched gate.

    Returns a dict: ``values`` — the (n_traj,) per-trajectory
    expectation of ``observable`` (a (codes, coeffs) Pauli-sum pair);
    ``mean`` and ``sem`` — its sample mean and standard error, which
    converge to the exact density-matrix channel expectation as 1/sqrt(B)
    (cross-validated against ops/density.py in tests).  With
    ``observable=None``, returns the final (n_traj, 2, 2^n) bank
    instead (key ``amps``)."""
    if n_traj < 1:
        raise QuESTError(f"run_trajectories: n_traj must be >= 1, got "
                         f"{n_traj}")
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    q = createBatchedQureg(num_qubits, env, n_traj,
                           seeds=[seed + i for i in range(n_traj)])
    nsites = 0
    with _telemetry.span("batch.trajectories", n_traj=n_traj,
                         ops=len(ops)):
        for op in ops:
            if isinstance(op, C.Gate):
                from . import api as _api

                _telemetry.inc_key(_api._K_UNITARY, n_traj)
                q._fusion.gates.append(op)
                continue
            kind, target, prob = op
            if kind not in _NOISE_KINDS:
                raise QuESTError(
                    f"run_trajectories: unknown noise kind {kind!r} "
                    f"(expected one of {_NOISE_KINDS})")
            nsites += 1
            prob = float(prob)
            if kind == "damping":
                mats = _sample_damping(q, int(target), prob, rng)
            else:
                mats = _sample_pauli_insertion(kind, prob,
                                               rng.random(n_traj))
            applyBatchedUnitary(q, (int(target),), mats)
        _telemetry.inc("trajectory_runs_total", n_traj)
        _telemetry.set_gauge("trajectory_noise_sites", nsites)
        if observable is None:
            out = {"amps": np.asarray(q.amps)}
        else:
            codes, coeffs = observable
            vals = calcExpecPauliSumBatched(q, codes, coeffs)
            sem = float(vals.std(ddof=1) / np.sqrt(n_traj)) \
                if n_traj > 1 else float("nan")
            out = {"values": vals, "mean": float(vals.mean()), "sem": sem}
    dt = time.perf_counter() - t0
    if dt > 0:
        _telemetry.set_gauge("trajectories_per_sec", n_traj / dt)
    return out
