"""Register and operator data structures.

TPU-native analogues of the reference's user types:

- ``Qureg`` (QuEST.h:322-353): the amplitude array is a single (possibly
  sharded) on-HBM ``jax.Array`` instead of SoA real/imag C buffers; there is
  no pairStateVec (the reference's 2x distributed receive buffer,
  QuEST_cpu.c:1279-1315) because collective permutes materialize only
  transient buffers, and no host mirror (the reference GPU backend keeps a
  full CPU copy, QuEST_gpu.cu:275-319).
- ``PauliHamil`` (QuEST.h:277): codes as an (terms, qubits) int array plus a
  coefficient vector — device-resident so expectation values trace cleanly.
- ``DiagonalOp`` (QuEST.h:297): a sharded complex diagonal kept as real+imag
  pairs, mirroring the reference's SoA layout at the API level.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import precision
from .env import QuESTEnv
from .qasm import QASMLogger


def device_amps_shape(num_qubits: int, shard_bits: int = 0) -> tuple:
    """Device shape of a scalar register's amplitude array.

    Once every shard holds at least 2^14 amplitudes the register keeps
    the canonical tiled view (2, 2^(n-14), 128, 128) — sublanes = amp
    bits [7,14), lanes = bits [0,7) — the shape every window, QFT and
    channel kernel views the state in.  A jit parameter of that shape
    gets the same T(8,128) device layout as the kernels' views, so the
    program boundary is a bitcast.  A flat (2, 2^n) parameter carries a
    different TPU layout, and XLA would insert a full-state copy at every
    program boundary — a second 8 GiB state at 30 qubits, which does not
    fit one 16 GiB chip.  Smaller registers stay flat (2, 2^n)."""
    from .ops.fused import CLUSTER_DIM, CLUSTER_QUBITS

    if num_qubits - shard_bits >= CLUSTER_QUBITS:
        return (2, 1 << (num_qubits - CLUSTER_QUBITS), CLUSTER_DIM,
                CLUSTER_DIM)
    return (2, 1 << num_qubits)


class Qureg:
    """A quantum register: pure state-vector or density matrix.

    ``amps`` is a real SoA array of shape (2, 2^numQubitsInStateVec)
    (channel 0/1 = real/imag — the reference's ComplexArray layout,
    QuEST.h:77; see ops/cplx.py for why this is the TPU-native choice),
    sharded over the env's amplitude mesh on its amplitude axis by leading
    (most-significant-bit) index — the reference's chunkId scheme
    (QuEST.h:330-338) as a NamedSharding.  On the device the same
    amplitudes are held in ``device_shape()`` (device_amps_shape): the
    drain and the readers that must not copy the state take
    ``device_amps()``; ``amps`` is the flat view of it.
    """

    def __init__(self, num_qubits: int, env: QuESTEnv, is_density_matrix: bool):
        self.is_density_matrix = bool(is_density_matrix)
        self.num_qubits_represented = int(num_qubits)
        self.num_qubits_in_state_vec = (2 if is_density_matrix else 1) * int(num_qubits)
        self.env = env
        self.dtype = precision.real_dtype()  # SoA channels are real arrays
        self.qasm_log = QASMLogger(num_qubits)
        self._amps: Optional[jax.Array] = None
        self._fusion = None  # FusionBuffer while a gateFusion context is active
        # governor.SpillHandle while the amplitudes live on host (the
        # memory governor's spill-to-host eviction); restored lazily on
        # the next touch via the amps getters below
        self._spill = None
        # live logical->physical qubit permutation of a SHARDED register
        # (None = canonical order).  _perm[q] = physical state-vector bit
        # holding logical bit q: the communication-avoiding scheduler keeps
        # the state permuted across windows and only rematerializes
        # canonical order on a state read (the ``amps`` getter below) —
        # see parallel/dist.py remap_sharded.
        self._perm: Optional[tuple] = None
        # last-use tick per logical state-vector bit: the relocalizer
        # evicts the least-recently-used residents so an alternating
        # circuit never ping-pongs its hot qubits across the shard
        # boundary
        self._last_use: dict = {}
        self._use_clock: int = 0
        # fusion drains executed on this register (window-boundary
        # accounting for the resilience layer's checkpoint cadence)
        self._drain_count: int = 0

    # -- reference-parity metadata (QuEST.h:330-345) --
    @property
    def num_amps_total(self) -> int:
        return 1 << self.num_qubits_in_state_vec

    @property
    def num_chunks(self) -> int:
        return self.env.num_devices

    @property
    def num_amps_per_chunk(self) -> int:
        return self.num_amps_total // self.num_chunks

    def device_shape(self) -> tuple:
        """Shape of the on-device amplitude array (device_amps_shape)."""
        from . import fusion

        return device_amps_shape(self.num_qubits_in_state_vec,
                                 fusion._shard_bits(self))

    def _to_device_form(self, value):
        if value is None:
            return None
        shape = self.device_shape()
        if shape is not None and tuple(value.shape) != shape:
            value = value.reshape(shape)
        return value

    @staticmethod
    def _flat(value) -> jax.Array:
        """(2, 2^n) view of a device-shaped scalar amplitude array."""
        if value is None or value.ndim != 4:
            return value
        return value.reshape(2, -1)

    def device_amps_raw(self) -> jax.Array:
        """Device-shaped amplitudes WITHOUT rematerializing canonical
        order (pending fused gates drain first).  Readers that map their
        qubits through ``_phys_bits`` read the state where it lies."""
        if self._amps is None:
            from . import governor, validation

            if not governor.restore_register(self):
                raise validation.QuESTError(
                    "Qureg: the register has been destroyed (destroyQureg) "
                    "or never initialised."
                )
        if self._fusion is not None and self._fusion.gates:
            from . import fusion

            fusion.drain(self)  # may leave a live permutation
        return self._amps

    def device_amps(self) -> jax.Array:
        """Device-shaped amplitudes in CANONICAL qubit order: pending
        fused gates drain first, then a live logical->physical
        permutation (left behind by the communication-avoiding scheduler)
        is rematerialized with ONE batched remap — so every reader
        (calculations, measurement, checkpointing, host gathers) sees
        reference semantics."""
        amps = self.device_amps_raw()
        if self._perm is not None:
            from .parallel import dist as PAR

            self._amps = self._to_device_form(PAR.remap_sharded(
                amps, mesh=self.env.mesh,
                num_qubits=self.num_qubits_in_state_vec,
                sigma=PAR.canonical_sigma(self._perm)))
            self._perm = None
        return self._amps

    @property
    def amps(self) -> jax.Array:
        """Flat (2, 2^n) amplitudes in canonical qubit order (see
        device_amps; the flat view of a device-shaped state is a
        full-state copy on the TPU)."""
        return self._flat(self.device_amps())

    @amps.setter
    def amps(self, value: jax.Array):
        if self._fusion is not None and self._fusion.gates:
            # a pure overwrite makes pending gates unobservable (any RHS
            # that depended on the old state already drained via the
            # getter) — discard them instead of computing a dead result
            self._fusion.gates.clear()
        # external overwrites are canonical-order by contract; only the
        # perm-aware writers (_set_amps_permuted) carry a permutation over
        self._perm = None
        self._spill = None  # an overwrite invalidates any host snapshot
        self._amps = self._to_device_form(value)

    def _amps_raw(self) -> jax.Array:
        """Flat amplitudes WITHOUT rematerializing canonical order — the
        perm-aware dispatch path's read (pending fused gates still drain
        first so operation order is preserved)."""
        return self._flat(self.device_amps_raw())

    def _set_amps_permuted(self, value: jax.Array, perm) -> None:
        """Rebind amplitudes held under logical->physical ``perm``
        (identity or None -> canonical).  Unlike the ``amps`` setter this
        PRESERVES the lazy-permutation bookkeeping."""
        self._spill = None
        self._amps = self._to_device_form(value)
        if perm is not None and tuple(perm) == tuple(
                range(self.num_qubits_in_state_vec)):
            perm = None
        self._perm = None if perm is None else tuple(perm)

    def bind_checkpoint_state(self, amps: jax.Array, perm, dtype) -> None:
        """Rebind this register to checkpointed state: raw (possibly
        permuted) amplitudes, the live logical->physical permutation, and
        the dtype the snapshot was taken at — the restore half of the
        resilience layer's generation protocol (resilience.py).  Unlike
        the ``amps`` setter this preserves the permutation; any pending
        fused gates are discarded (they predate the snapshot)."""
        if self._fusion is not None and self._fusion.gates:
            self._fusion.gates.clear()
        self.dtype = np.dtype(dtype)
        self._set_amps_permuted(amps, perm)

    def reshard_to(self, env: QuESTEnv) -> None:
        """Move this register onto ``env``'s mesh in place, carrying any
        live logical->physical permutation over unchanged (the perm is a
        bit permutation of the GLOBAL amplitude index — mesh-shape-
        independent; see resilience._validated_perm).  Pending fused
        gates drain on the OLD mesh first so operation order is
        preserved; subsequent windows plan against the new mesh's shard
        split (fusion keys its plans on nloc, so nothing stale
        survives).  This is the live-state half of elastic recovery —
        checkpointed restores instead reshard on read
        (resilience.load_latest)."""
        amps = self._amps_raw()  # drain pending gates on the old mesh
        perm = self._perm
        self.env = env
        self._amps = self._to_device_form(
            jax.device_put(amps, self.sharding()))
        self._perm = perm

    def _phys_bits(self, bits) -> tuple:
        """Physical positions of logical state-vector bits under the live
        permutation (identity when none is active)."""
        if self._perm is None:
            return tuple(bits)
        return tuple(self._perm[b] for b in bits)

    def sharding(self):
        if self.num_amps_total >= self.env.num_devices:
            return self.env.amp_sharding()
        return self.env.replicated_sharding()

    def fill(self, kind: str, x=0) -> None:
        """Overwrite the state with a "basis" (amplitude index ``x``),
        "plus" (every real part ``x``) or "blank" state built on the
        device in its device shape.  The old device buffer is freed first
        (as a donating kernel would): two states at once do not fit a
        chip that holds one.  Pending fused gates and a live permutation
        are discarded, as by any overwrite."""
        from .ops import kernels

        old, self._amps = self._amps, None
        if isinstance(old, jax.Array) and not old.is_deleted():
            old.delete()
        self.amps = kernels.fill_state(self.device_shape(), self.dtype,
                                       self.sharding(), kind, x)

    def device_put(self, amps) -> jax.Array:
        return jax.device_put(jnp.asarray(amps, self.dtype), self.sharding())


class PauliHamil:
    """Real-weighted sum of Pauli products (QuEST.h:277)."""

    def __init__(self, num_qubits: int, num_sum_terms: int):
        self.num_qubits = int(num_qubits)
        self.num_sum_terms = int(num_sum_terms)
        self.pauli_codes = np.zeros((num_sum_terms, num_qubits), dtype=np.int32)
        self.term_coeffs = np.zeros((num_sum_terms,), dtype=np.float64)


class DiagonalOp:
    """Diagonal operator on the full Hilbert space (QuEST.h:297).  Stored as
    real+imag vectors (SoA like the reference) of length 2^numQubits, sharded
    over the amplitude mesh by the same leading-bit scheme."""

    def __init__(self, num_qubits: int, env: QuESTEnv):
        self.num_qubits = int(num_qubits)
        self.env = env
        rdt = precision.real_dtype()
        dim = 1 << self.num_qubits
        sharding = env.sharding_for_dim(dim)
        self.real = jax.device_put(jnp.zeros((dim,), rdt), sharding)
        self.imag = jax.device_put(jnp.zeros((dim,), rdt), sharding)

    @property
    def num_elems_per_chunk(self) -> int:
        return (1 << self.num_qubits) // self.env.num_devices
