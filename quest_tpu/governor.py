"""Memory-governed execution: HBM budgeting, admission, spill, OOM net.

The reference validates per-rank memory once, at register creation
(QuEST validateMemoryAllocationSize) and then trusts the allocator;
everything after that is an abort.  On TPU the failure mode is worse:
XLA's ``RESOURCE_EXHAUSTED`` kills the process mid-drain, after the
donated input buffer may already be gone (the incidents recorded at
circuit.py "round-2 OOM that blocked 30q" and fusion.py "+1.25 GiB PER
CHANNEL at 13q rho -> 21 GiB OOM").  This module turns memory into an
admission decision the way an inference server gates requests on a
KV-cache budget (docs/design.md §22):

* **Budget** — per-device HBM bytes, from ``Device.memory_stats()``
  (``bytes_limit``) with a ``QT_HBM_BUDGET_BYTES`` override so the
  8-shard CPU dryrun is fully testable.  ``QT_MEM_POLICY`` selects
  ``off`` / ``degrade`` (default) / ``strict``.  With no budget (the
  bare CPU backend) the governor is inert and every path below is a
  cheap no-op.

* **Ledger** — every live register is tracked (weakly) with its modeled
  per-device footprint and an LRU tick, so "available" is always
  budget minus resident bytes, and spill candidates come out in
  least-recently-used order.

* **Predictor** — the analytic peak of a planned drain:
  ``state_shard_bytes x (1 + max part extra) + pass-array bytes``.
  Gate/channel parts keep one extra live copy (input + donated output,
  the optimization_barrier liveness cut in fusion._plan_runner); a
  monolithic window remap keeps two (send + recv transient on top of
  the input — the pinned 2.0-shard number from the PR-3 pipelined
  exchange work), and a C-chunk pipelined remap keeps ``2/C`` (at most
  two chunk-sized transients in flight — the pinned 1.25-shard number
  at C=8).  The same numbers surface as the ``memory`` section of
  ``explain_circuit`` / reportCircuitPlan.

* **Enforcement** — ``admit_new`` gates createQureg /
  createDensityQureg / createBatchedQureg with a structured
  :class:`MemoryAdmissionError` naming predicted vs available bytes;
  ``govern_drain`` walks the degradation ladder when a drain's
  predicted peak exceeds budget: (1) raise the exchange chunk count to
  shrink remap temps, (2) split the program into smaller dispatch
  groups, (3) spill idle registers to host (raw permuted amps + perm +
  per-register RNG key bank behind a lazy handle that restores on next
  touch), and only then (4) refuse.  ``strict`` skips the ladder and
  raises before any device allocation.

* **OOM net** — :func:`oom_net` wraps every drain dispatch: a real (or
  FaultPlan-injected ``oom@W``) RESOURCE_EXHAUSTED evicts LRU-idle
  registers, clears the plan caches, backs off, and retries ONCE; a
  second failure propagates.

Every rung emits telemetry (``admission_rejects_total``,
``spills_total``, ``spill_bytes_total``, ``oom_retries_total``,
``governor_degradations_total{rung}``) and lands in the degradation
registry surfaced by getEnvironmentString.
"""

from __future__ import annotations

import os
import time
import weakref
from typing import List, Optional, Tuple

import numpy as np

from . import telemetry as _telemetry
from .validation import QuESTError

_POLICY_ENV = "QT_MEM_POLICY"
_BUDGET_ENV = "QT_HBM_BUDGET_BYTES"
_POLICIES = ("off", "degrade", "strict")

# --- live-copy multiplier model (docs/design.md §22) ---------------------
# Parts are priced by what the TPU compiler allocates for them.  The
# Pallas window / cluster / swap-cluster / sigma-swap passes, the
# canonical diagonal op and the channel sweep alias their donated input:
# no extra state copy (each compiled for a described v5e at 30 qubits
# with no state-sized temporary, tests/test_chip_compile.py).  Any other
# part — an XLA permutation, general-matrix or pair-channel op, or a
# megawin group, which the v5e compiler refuses — writes one second
# state.  A window remap additionally materializes its exchange
# transient.  On a flat shard: the WHOLE shard when monolithic (2.0
# shards), at most two in-flight chunks when pipelined over C chunks
# (1 + 2/C).  On a canonical shard the swap runs in place
# (dist._swap_halves_canonical) and holds five half-shard chunks: two
# sends in flight, their receive buffers and the combined chunk (2.5/C;
# the 32-qubit drain on a described four-chip v5e, C = 8, compiles to
# temp 2.61 GiB beside its 8 GiB shard, tests/test_chip_compile.py).
GATE_PART_EXTRA = 1.0
IN_PLACE_OPS = frozenset({"winfused", "fused", "swapfused", "sigma_swap",
                          "diag"})


def part_extra(part, chunks: int, canonical: bool) -> float:
    """Extra live state copies one program part holds at its peak
    (``canonical``: the register holds canonical-shape shards)."""
    kind = part[0]
    if kind == "remap":
        return remap_part_extra(chunks, canonical)
    if kind == "chansweep":
        return 0.0
    if kind == "plan" and all(sk[0] in IN_PLACE_OPS for sk in part[1]):
        return 0.0
    return GATE_PART_EXTRA


def remap_part_extra(chunks: int, canonical: bool) -> float:
    """Extra live shard-copies of one ("remap", sigma) part at chunk
    count ``chunks`` — 2.5/C in place on a canonical shard; on a flat
    one 2.0 monolithic, 1 + 2/C pipelined."""
    c = max(int(chunks), 1)
    if canonical:
        return 2.5 / c
    return 2.0 if c <= 1 else 1.0 + 2.0 / c


class MemoryAdmissionError(QuESTError):
    """A register or drain was refused because its predicted per-device
    footprint exceeds the available HBM budget.  Carries the numbers so
    callers (and the pinned tests) can reason about the decision."""

    def __init__(self, func: str, predicted_bytes: int,
                 available_bytes: int, budget_bytes: int):
        self.predicted_bytes = int(predicted_bytes)
        self.available_bytes = int(available_bytes)
        self.budget_bytes = int(budget_bytes)
        super().__init__(
            f"{func}: predicted peak of {self.predicted_bytes} bytes per "
            f"device exceeds the {self.available_bytes} bytes available "
            f"under the {self.budget_bytes}-byte per-device HBM budget "
            f"(policy={policy()}; set {_BUDGET_ENV} / {_POLICY_ENV} to "
            f"adjust)")


class _InjectedOOM(RuntimeError):
    """Synthetic allocator failure raised by a FaultPlan ``oom@W`` event
    BEFORE the dispatch runs (so the donated input is never consumed);
    the message carries the XLA marker so _is_oom treats it like the
    real thing."""


def _is_oom(e: BaseException) -> bool:
    s = f"{type(e).__name__}: {e}"
    return ("RESOURCE_EXHAUSTED" in s or "Out of memory" in s
            or "out of memory" in s)


# ---------------------------------------------------------------------------
# Policy / budget resolution
# ---------------------------------------------------------------------------

# min-over-devices bytes_limit probe, cached per process (CPU -> None)
_DEVICE_LIMIT = [False, None]  # [probed, limit]


def policy() -> str:
    """``QT_MEM_POLICY``: off | degrade (default) | strict."""
    p = os.environ.get(_POLICY_ENV, "degrade").strip().lower() or "degrade"
    if p not in _POLICIES:
        from . import resilience

        resilience.record_degradation(
            "memory_governor_policy",
            f"unknown {_POLICY_ENV}={p!r}; using 'degrade'")
        return "degrade"
    return p


def _device_limit_bytes() -> Optional[int]:
    if not _DEVICE_LIMIT[0]:
        _DEVICE_LIMIT[0] = True
        limit = None
        try:
            import jax

            for d in jax.local_devices():
                try:
                    stats = d.memory_stats()
                # qlint: allow(broad-except): memory_stats() support and failure types are backend-dependent; a probe failure just means "no HBM cap known"
                except Exception:  # pragma: no cover - backend-dependent
                    stats = None
                cap = (stats or {}).get("bytes_limit")
                if cap is None:
                    limit = None
                    break
                limit = cap if limit is None else min(limit, cap)
        # qlint: allow(broad-except): device enumeration with no backend raises version-dependent types; the budget simply stays unknown
        except Exception:  # pragma: no cover - no backend at all
            limit = None
        _DEVICE_LIMIT[1] = int(limit) if limit else None
    return _DEVICE_LIMIT[1]


def budget_bytes() -> Optional[int]:
    """Per-device HBM budget: ``QT_HBM_BUDGET_BYTES`` override, else the
    min ``memory_stats()['bytes_limit']`` over local devices, else None
    (backend exposes no limit — the governor stays inert)."""
    raw = os.environ.get(_BUDGET_ENV)
    if raw is not None:
        try:
            v = int(raw)
            return v if v > 0 else None
        except ValueError:
            from . import resilience

            resilience.record_degradation(
                "memory_governor_budget",
                f"unparseable {_BUDGET_ENV}={raw!r}; ignoring")
            return _device_limit_bytes()
    return _device_limit_bytes()


def enabled() -> bool:
    return policy() != "off" and budget_bytes() is not None


# ---------------------------------------------------------------------------
# Register ledger
# ---------------------------------------------------------------------------


class _Entry:
    __slots__ = ("ref", "bytes", "tick", "spilled")

    def __init__(self, ref, nbytes: int, tick: int):
        self.ref = ref
        self.bytes = int(nbytes)
        self.tick = tick
        self.spilled = False


_LEDGER: dict = {}  # id(qureg) -> _Entry (weakly referenced)
_TICK = [0]
# max modeled (resident + drain transient) bytes seen this process — the
# watermark the CPU dryrun publishes in place of device memory_stats
_MODELED_PEAK: List[Optional[int]] = [None]


def register_bytes_per_device(qureg) -> int:
    """Modeled steady-state bytes ONE device holds for ``qureg``:
    ``B x 2 x 2^n x itemsize`` split over the amplitude shards (a
    register too small to shard is replicated — full bytes per device,
    mirroring Qureg.sharding)."""
    b = max(int(getattr(qureg, "batch_size", 0) or 0), 1)
    total = b * 2 * qureg.num_amps_total * np.dtype(qureg.dtype).itemsize
    env = qureg.env
    if env.mesh is not None and qureg.num_amps_total >= env.num_devices:
        return total // env.num_devices
    return total


def refresh_budget() -> None:
    """Re-derive the per-device budget and re-price the ledger after the
    live mesh changes shape (serve failover/heal, elastic failover): the
    HBM probe cache is dropped — the next :func:`budget_bytes` re-probes
    whatever devices survive — and every resident entry's per-device
    bytes are recomputed against its register's CURRENT environment
    (fewer devices -> more bytes per device, and vice versa on heal)."""
    _DEVICE_LIMIT[0] = False
    _DEVICE_LIMIT[1] = None
    for key in list(_LEDGER):
        e = _LEDGER.get(key)
        q = e.ref() if e is not None else None
        if q is None:
            _LEDGER.pop(key, None)
            continue
        if not e.spilled:
            e.bytes = register_bytes_per_device(q)
    _telemetry.inc("governor_budget_rederivations_total")


def _next_tick() -> int:
    _TICK[0] += 1
    return _TICK[0]


def track(qureg) -> None:
    """Enter ``qureg`` into the ledger (idempotent; always on — the dict
    insert is negligible and keeps 'resident bytes' truthful even when
    the budget is enabled mid-process, as tests do)."""
    key = id(qureg)

    def _gone(_ref, _key=key):
        _LEDGER.pop(_key, None)

    _LEDGER[key] = _Entry(weakref.ref(qureg, _gone),
                          register_bytes_per_device(qureg), _next_tick())


def release(qureg) -> None:
    """Drop ``qureg`` from the ledger (destroyQureg)."""
    _LEDGER.pop(id(qureg), None)


def touch(qureg) -> None:
    """Bump the LRU tick (any drain or restore of the register)."""
    e = _LEDGER.get(id(qureg))
    if e is not None:
        e.tick = _next_tick()


def resident_bytes(exclude=None) -> int:
    """Modeled bytes currently resident per device across tracked
    registers (spilled and destroyed registers do not count)."""
    ex = id(exclude) if exclude is not None else None
    total = 0
    for key in list(_LEDGER):
        e = _LEDGER.get(key)
        if e is None:
            continue
        q = e.ref()
        if q is None:
            _LEDGER.pop(key, None)
            continue
        if key == ex or e.spilled or q._amps is None:
            continue
        total += e.bytes
    return total


# ---------------------------------------------------------------------------
# Admission (register creation)
# ---------------------------------------------------------------------------


def admit_new(qureg, func: str) -> None:
    """Gate a new register BEFORE its device allocation: with a budget
    enabled, refuse (MemoryAdmissionError naming predicted vs available
    bytes) when the modeled footprint does not fit next to the resident
    set — the governed analogue of QuEST's validateMemoryAllocationSize,
    turned from an abort into a structured error."""
    if not enabled():
        track(qureg)
        return
    need = register_bytes_per_device(qureg)
    b = budget_bytes()
    avail = b - resident_bytes()
    if need > avail:
        _telemetry.inc("admission_rejects_total", func=func)
        raise MemoryAdmissionError(func, need, avail, b)
    track(qureg)


# ---------------------------------------------------------------------------
# Spill-to-host eviction
# ---------------------------------------------------------------------------


class SpillHandle:
    """Host-side snapshot of an evicted register: RAW (possibly
    permuted) amplitudes, the live logical->physical permutation, the
    dtype, and — for a BatchedQureg — the per-element measurement key
    bank (the only per-register RNG state; scalar registers draw from
    the process-global stream).  Restored lazily on the next touch
    (Qureg.amps / _amps_raw)."""

    __slots__ = ("amps", "perm", "dtype", "key_state", "nbytes")

    def __init__(self, amps: np.ndarray, perm, dtype, key_state):
        self.amps = amps
        self.perm = None if perm is None else tuple(perm)
        self.dtype = np.dtype(dtype)
        self.key_state = key_state
        self.nbytes = int(amps.nbytes)


class _SparseHandle:
    """Lazy sparse-state handle (§28): ``initSparseState`` admits at the
    cost of its indices + amplitude values and defers the dense
    ``(2, 2^n)`` materialization to the first touch, where
    :func:`restore_register` runs it under the ordinary admission
    machinery (``spill_until`` makes room first).  Duck-types
    :class:`SpillHandle` — restore reads ``.amps`` / ``.perm`` /
    ``.dtype`` / ``.key_state`` and never learns the state was sparse."""

    __slots__ = ("indices", "res", "ims", "num_amps", "perm", "dtype",
                 "key_state", "nbytes")

    def __init__(self, num_amps: int, indices, res, ims, dtype):
        self.num_amps = int(num_amps)
        self.indices = np.asarray(indices, dtype=np.int64)
        self.res = np.asarray(res, dtype=np.dtype(dtype))
        self.ims = np.asarray(ims, dtype=np.dtype(dtype))
        self.perm = None
        self.dtype = np.dtype(dtype)
        self.key_state = None
        self.nbytes = int(self.indices.nbytes + self.res.nbytes
                          + self.ims.nbytes)

    @property
    def amps(self) -> np.ndarray:
        out = np.zeros((2, self.num_amps), dtype=self.dtype)
        out[0, self.indices] = self.res
        out[1, self.indices] = self.ims
        return out


def admit_sparse_state(qureg, indices, res, ims,
                       func: str = "initSparseState") -> None:
    """Install a lazy sparse state: the register's device buffer is
    dropped, the handle is admitted at SPARSE cost (indices + amplitude
    values, NOT the dense 2^n footprint), and densification happens on
    the first touch through restore_register — under admission control,
    so a budget that cannot hold the dense state TODAY still accepts the
    sparse description and spills neighbours when the drain arrives."""
    h = _SparseHandle(1 << qureg.num_qubits_in_state_vec,
                      indices, res, ims, qureg.dtype)
    if enabled():
        b = budget_bytes()
        avail = b - resident_bytes(exclude=qureg)
        if h.nbytes > avail:
            _telemetry.inc("admission_rejects_total", func=func)
            raise MemoryAdmissionError(func, h.nbytes, avail, b)
    qureg._amps = None
    qureg._perm = None
    qureg._spill = h
    e = _LEDGER.get(id(qureg))
    if e is None:
        track(qureg)
        e = _LEDGER[id(qureg)]
    e.spilled = True


def spill_register(qureg) -> int:
    """Evict ``qureg``'s amplitudes to host memory behind a lazy
    :class:`SpillHandle`; returns the modeled per-device bytes freed
    (0 when there was nothing resident).  Pending fused gates stay
    buffered — the restore happens before any drain reads the amps."""
    raw = qureg._amps
    if raw is None or getattr(qureg, "_spill", None) is not None:
        return 0
    host = np.asarray(raw)
    key_state = qureg.key_state() if hasattr(qureg, "key_state") else None
    qureg._spill = SpillHandle(host, qureg._perm, qureg.dtype, key_state)
    qureg._amps = None
    qureg._perm = None
    e = _LEDGER.get(id(qureg))
    if e is None:
        track(qureg)
        e = _LEDGER[id(qureg)]
    e.spilled = True
    _telemetry.inc("spills_total")
    _telemetry.inc("spill_bytes_total", host.nbytes)
    return e.bytes


def restore_register(qureg) -> bool:
    """Bring a spilled register back on device (bit-identical: raw
    permuted amps + perm + key bank); returns False when the register
    was never spilled (so Qureg.amps can raise its destroyed-register
    error instead)."""
    h = getattr(qureg, "_spill", None)
    if h is None:
        return False
    import jax
    import jax.numpy as jnp

    qureg._spill = None
    e = _LEDGER.get(id(qureg))
    if e is not None:
        e.spilled = False
    if enabled():
        # make room for the returning register before device_put
        need = register_bytes_per_device(qureg)
        b = budget_bytes()
        if resident_bytes(exclude=qureg) + need > b:
            spill_until(need, exclude=qureg)
    qureg.dtype = h.dtype
    amps = jax.device_put(jnp.asarray(h.amps, h.dtype), qureg.sharding())
    qureg._set_amps_permuted(amps, h.perm)
    if h.key_state is not None:
        qureg.set_key_state(h.key_state)
    touch(qureg)
    _telemetry.inc("spill_restores_total")
    return True


def ensure_resident(qureg) -> None:
    """Restore ``qureg`` if a prior ladder pass spilled it (the fusion
    drain reads qureg._amps directly, bypassing the property)."""
    if getattr(qureg, "_spill", None) is not None:
        restore_register(qureg)


def _spill_candidates(exclude=None) -> list:
    ex = id(exclude) if exclude is not None else None
    out = []
    for key, e in list(_LEDGER.items()):
        q = e.ref()
        if q is None or key == ex or e.spilled or q._amps is None:
            continue
        out.append((e.tick, e, q))
    out.sort(key=lambda t: t[0])  # least-recently-used first
    return out


def spill_until(need: int, exclude=None) -> int:
    """Spill idle registers in LRU order until ``need`` bytes fit under
    the budget next to what remains resident; returns bytes freed."""
    b = budget_bytes()
    freed = 0
    for _tick, _e, q in _spill_candidates(exclude):
        if b is None or resident_bytes(exclude=exclude) + need <= b:
            break
        freed += spill_register(q)
    return freed


def spill_all_idle(exclude=None) -> int:
    """Evict every idle register (the OOM net's desperation move)."""
    freed = 0
    for _tick, _e, q in _spill_candidates(exclude):
        freed += spill_register(q)
    return freed


# ---------------------------------------------------------------------------
# Drain prediction + degradation ladder
# ---------------------------------------------------------------------------


def _arrays_bytes(arrays) -> int:
    return int(sum(int(getattr(a, "nbytes", 0) or 0) for a in arrays))


def _resolved_chunks(qureg, nloc: int, nsh: int) -> int:
    """Full-shard chunk count the remap parts will resolve under the
    LIVE chunk policy (env override / governor override / heuristic) on
    the register's own devices."""
    if not nsh:
        return 1
    from .parallel import dist as PAR

    return int(PAR.remap_chunk_plan(
        nloc, np.dtype(qureg.dtype).itemsize,
        backend=PAR.mesh_platform(qureg.env.mesh))[1])


def _canonical(qureg) -> bool:
    shape = qureg.device_shape()
    return shape is not None and len(shape) == 4


def _program_peak(program, state: int, arrays_b: int, chunks: int,
                  canonical: bool) -> int:
    """Predicted per-device peak of dispatching ``program`` as ONE
    group: state x (1 + max part extra) + pass-array bytes."""
    extra = max((part_extra(part, chunks, canonical) for part in program),
                default=0.0)
    return int(state * (1.0 + extra)) + int(arrays_b)


def predict_drain(qureg, program, arrays, *, nloc: int, nsh: int,
                  chunks: Optional[int] = None) -> dict:
    """Analytic per-device footprint of draining ``program`` on
    ``qureg`` — the quantity govern_drain enforces and explain_circuit's
    ``memory`` section reports."""
    itemsize = np.dtype(qureg.dtype).itemsize
    state = register_bytes_per_device(qureg)
    arrays_b = _arrays_bytes(arrays)
    c = chunks if chunks is not None else _resolved_chunks(qureg, nloc, nsh)
    peak = (_program_peak(program, state, arrays_b, c, _canonical(qureg))
            if program else state)
    other = resident_bytes(exclude=qureg)
    b = budget_bytes()
    # per-interconnect-tier exchange bytes of the drain's remap parts —
    # the hierarchical (QT_TOPOLOGY) refinement of the exchange volume,
    # weighted by the relative link cost so the drain-peak report also
    # says how much of its traffic rides the slow DCN tier
    tier_b = {"ici": 0, "dcn": 0}
    if nsh:
        from .parallel import dist as PAR
        from .parallel import topology as _topo

        topology = _topo.resolve(1 << nsh)
        for part in program:
            if part[0] != "remap":
                continue
            for t, (_cnt, nb) in PAR.remap_exchange_tiers(
                    part[1], nloc, nsh, itemsize, topology).items():
                tier_b[t] += nb
        weights = _topo.tier_weights()
    else:
        weights = {"ici": 1.0, "dcn": 1.0}
    return {
        "policy": policy(),
        "budget_bytes": b,
        "state_bytes_per_device": int(state),
        "pass_array_bytes": int(arrays_b),
        "live_multiplier": round(
            (peak - arrays_b) / state, 4) if state else 1.0,
        "exchange_chunks": int(c),
        "predicted_peak_bytes": int(peak),
        "other_resident_bytes": int(other),
        "predicted_total_bytes": int(other + peak),
        "headroom_bytes": (None if b is None
                           else int(b - other - peak)),
        "fits": (None if b is None else bool(other + peak <= b)),
        "exchange_tier_bytes": {t: int(v) for t, v in tier_b.items()},
        "weighted_exchange_cost": float(sum(
            weights[t] * v for t, v in tier_b.items())),
    }


def _split_program(program, arrays, state: int, other: int, b: int,
                   chunks: int, canonical: bool):
    """Rung 2: greedily pack program parts into contiguous dispatch
    groups so each group's peak (state x (1+max extra) + its own pass
    arrays) fits the remaining budget.  Part boundaries already carry an
    optimization_barrier in the single-program executor, so the grouped
    execution is bit-identical — only the dispatch count changes.
    Returns a tuple of part-groups, or None when grouping cannot help
    (single part, or a lone part already over budget)."""
    sizes = []
    ai = 0
    for part in program:
        na = part[2] if part[0] == "plan" else 0
        sizes.append(_arrays_bytes(arrays[ai:ai + na]))
        ai += na
    groups: List[tuple] = []
    cur: List[tuple] = []
    for part, _sb in zip(program, sizes):
        trial = cur + [part]
        start = sum(len(g) for g in groups)
        trial_b = sum(sizes[start:start + len(trial)])
        if cur and other + _program_peak(
                trial, state, trial_b, chunks, canonical) > b:
            groups.append(tuple(cur))
            cur = [part]
        else:
            cur = trial
    if cur:
        groups.append(tuple(cur))
    if len(groups) <= 1:
        return None
    # feasible only if every group now fits
    start = 0
    for g in groups:
        gb = sum(sizes[start:start + len(g)])
        start += len(g)
        if other + _program_peak(g, state, gb, chunks, canonical) > b:
            return None
    return tuple(groups)


def govern_drain(qureg, program, arrays, *, nloc: int, nsh: int):
    """Enforce the budget on one planned drain.  Returns None when the
    governor is inert or the drain fits untouched; otherwise a dict
    ``{"groups": tuple-of-part-groups or None, "chunks": C or None}``
    after walking the degradation ladder (chunk bump -> program split ->
    spill idle registers -> refuse).  ``strict`` skips the ladder and
    raises :class:`MemoryAdmissionError` before any device allocation;
    the fusion drain's failure path restores the gate buffer, so state
    and QASM log stay consistent."""
    if not enabled() or not program:
        touch(qureg)
        return None
    touch(qureg)
    from . import resilience as _res
    from .parallel import dist as PAR

    b = budget_bytes()
    state = register_bytes_per_device(qureg)
    arrays_b = _arrays_bytes(arrays)
    other = resident_bytes(exclude=qureg)
    canonical = _canonical(qureg)
    c0 = _resolved_chunks(qureg, nloc, nsh)
    need = _program_peak(program, state, arrays_b, c0, canonical)
    if other + need <= b:
        _record_usage(other + need)
        return None
    if policy() == "strict":
        _telemetry.inc("admission_rejects_total", func="drain")
        raise MemoryAdmissionError("gateFusion drain", need, b - other, b)

    applied = []
    # rung 1: pipeline the window remaps harder (shrinks the exchange
    # transient from a whole shard to 2/C of one).  The explicit
    # QT_EXCHANGE_CHUNKS override is the user's word — never fought.
    c = c0
    if (nsh and any(p[0] == "remap" for p in program)
            and os.environ.get(PAR._EXCHANGE_ENV) is None):
        cap = min(PAR.MAX_EXCHANGE_CHUNKS, 1 << max(nloc - 1, 0))
        pick = None
        t = max(c0, 1)
        while t < cap:
            t *= 2
            if other + _program_peak(program, state, arrays_b, t,
                                     canonical) <= b:
                pick = t
                break
        if pick is None and cap > c0:
            pick = cap  # max shrink, ladder continues
        if pick is not None and pick != c0:
            c = pick
            PAR._GOVERNOR_CHUNKS[0] = int(c)
            applied.append(("chunks",
                            f"exchange chunks {c0} -> {c} to shrink "
                            "remap transients"))
            need = _program_peak(program, state, arrays_b, c, canonical)

    # rung 2: split the oversized window into smaller dispatch groups
    groups = None
    if other + need > b:
        groups = _split_program(program, arrays, state, other, b, c,
                                canonical)
        if groups is not None:
            applied.append(("split",
                            f"drain split into {len(groups)} dispatch "
                            "groups"))
            need = _max_group_peak(groups, arrays, state, c, canonical)

    # rung 3: spill idle registers (LRU) to free co-resident bytes
    if other + need > b:
        freed = spill_until(need, exclude=qureg)
        if freed:
            applied.append(("spill",
                            f"spilled {freed} resident bytes of idle "
                            "registers to host"))
        other = resident_bytes(exclude=qureg)

    if other + need > b:
        _telemetry.inc("admission_rejects_total", func="drain")
        _rollback_chunks()
        raise MemoryAdmissionError("gateFusion drain", need, b - other, b)

    for rung, why in applied:
        _telemetry.inc("governor_degradations_total", rung=rung)
        _res.record_degradation("memory_governor_" + rung, why)
    _record_usage(other + need)
    return {"groups": groups, "chunks": c if c != c0 else None}


def _max_group_peak(groups, arrays, state: int, chunks: int,
                    canonical: bool) -> int:
    """Exact max per-group peak: walks the pass-array offsets group by
    group (the same accounting fusion's dispatch loop uses)."""
    ai = 0
    worst = 0
    for g in groups:
        na = sum(p[2] if p[0] == "plan" else 0 for p in g)
        gb = _arrays_bytes(arrays[ai:ai + na])
        ai += na
        worst = max(worst, _program_peak(g, state, gb, chunks, canonical))
    return worst


def _rollback_chunks() -> None:
    from .parallel import dist as PAR

    PAR._GOVERNOR_CHUNKS[0] = None


def end_drain() -> None:
    """Clear the per-drain chunk escalation (fusion._run's finally)."""
    _rollback_chunks()


def _record_usage(total: int) -> None:
    prev = _MODELED_PEAK[0]
    _MODELED_PEAK[0] = max(int(total), prev or 0)


def modeled_watermark_bytes() -> Optional[int]:
    """Max modeled (resident + transient) per-device bytes any governed
    drain reached — published as ``hbm_watermark_bytes{device="model"}``
    by utils.profiling.memory_watermark when the backend exposes no
    memory_stats, so the CPU dryrun's watermark agrees with the
    predictor instead of reporting host RSS."""
    if not enabled():
        return None
    return _MODELED_PEAK[0]


# ---------------------------------------------------------------------------
# OOM net (last resort)
# ---------------------------------------------------------------------------


def oom_net(fn, qureg=None):
    """Run ``fn()`` (one drain dispatch) under the RESOURCE_EXHAUSTED
    net: on an allocator failure — real, or injected by a FaultPlan
    ``oom@W`` event — evict LRU-idle registers, clear the plan caches,
    back off, and retry ONCE.  A second failure propagates.  Injected
    faults raise BEFORE the dispatch consumes its donated input, so the
    deterministic CI path is always state-safe; the real-OOM retry is a
    documented best effort."""

    from . import resilience as _res

    plan = _res._ACTIVE_FAULTS[0]
    if plan is not None:
        # a drain outside run_resumable never reaches arm_exchange_window;
        # its oom@W events count as window 0
        plan.arm_oom(0)

    def attempt():
        if plan is not None and plan.take_oom_fault():
            raise _InjectedOOM(
                "RESOURCE_EXHAUSTED: injected allocation failure "
                "(FaultPlan oom)")
        return fn()

    try:
        return attempt()
    # qlint: allow(broad-except): the oom_net — XLA surfaces RESOURCE_EXHAUSTED under backend-specific exception classes, so the net catches everything, re-raises non-OOM unchanged, and retries once after eviction
    except Exception as e:
        if not _is_oom(e):
            raise
        _recover_from_oom(qureg, e)
        return attempt()


def _recover_from_oom(qureg, err) -> None:
    from . import fusion as _fusion
    from . import resilience as _res

    _telemetry.inc("oom_retries_total")
    _telemetry.inc("governor_degradations_total", rung="oom_retry")
    _res.record_degradation(
        "memory_governor_oom_retry",
        f"RESOURCE_EXHAUSTED at dispatch ({err!s:.120}); evicted idle "
        "registers and cleared plan caches for one retry")
    spill_all_idle(exclude=qureg)
    _fusion._plan_cache.clear()
    _fusion._plan_runner.cache_clear()
    try:
        import jax

        jax.clear_caches()
    # qlint: allow(broad-except): clear_caches is a version-dependent API; OOM recovery must proceed to the retry even when it is absent or fails
    except Exception:  # pragma: no cover - version-dependent API
        pass
    time.sleep(float(os.environ.get("QT_RETRY_BASE_SECONDS", "0.05")))


# ---------------------------------------------------------------------------
# Introspection / report surfaces
# ---------------------------------------------------------------------------


def explain_memory(qureg, items) -> dict:
    """The ``memory`` section of explain_circuit: plan ``items`` quietly
    (no telemetry, no plan-cache insertion — the dry-run contract) and
    run the predictor over the exact program the drain would dispatch."""
    from . import fusion as F

    program, arrays, _fp, nloc, nsh = F.plan_items_quiet(qureg, items)
    return predict_drain(qureg, program, arrays, nloc=nloc, nsh=nsh)


def summary_line() -> Optional[str]:
    """One-line governor status for reportPerf (None when inert and
    nothing ever fired)."""
    rejects = _telemetry.counter_total("admission_rejects_total")
    spills = _telemetry.counter_total("spills_total")
    ooms = _telemetry.counter_total("oom_retries_total")
    if not enabled() and not (rejects or spills or ooms):
        return None
    b = budget_bytes()
    parts = [f"memory governor: policy={policy()}",
             f"budget={b if b is not None else '-'}",
             f"resident={resident_bytes()}"]
    peak = _MODELED_PEAK[0]
    if peak is not None:
        parts.append(f"modeled_peak={peak}")
    parts.append(f"rejects={int(rejects)} spills={int(spills)} "
                 f"oom_retries={int(ooms)}")
    return " ".join(parts)


def reset() -> None:
    """Forget all governor state (tests): ledger, modeled peak, device
    probe, any live chunk escalation."""
    _LEDGER.clear()
    _TICK[0] = 0
    _MODELED_PEAK[0] = None
    _DEVICE_LIMIT[0] = False
    _DEVICE_LIMIT[1] = None
    try:
        _rollback_chunks()
    # qlint: allow(broad-except): reset() must succeed even before parallel/dist is importable (circular-import window during package init)
    except Exception:  # pragma: no cover - dist not importable yet
        pass
