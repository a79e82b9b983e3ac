"""Explicit distributed kernels: shard_map + ppermute over the amplitude mesh.

TPU-native re-design of the reference's MPI orchestration layer
(QuEST/src/CPU/QuEST_cpu_distributed.c).  The state of n qubits is sharded
over a 1-D device mesh on its leading (most-significant) index bits: with
2^r devices, qubits 0..n-r-1 are *local* (inside each shard) and qubits
n-r..n-1 are *sharded* (their bit IS a mesh-coordinate bit) — exactly the
reference's chunkId scheme (QuEST.h:330-338).

Mapping of the reference's five MPI primitives (SURVEY.md §5.8):

- pairwise full-chunk ``MPI_Sendrecv`` with the XOR-partner rank
  (exchangeStateVectors, :489-517) -> ``lax.ppermute`` with the static
  hypercube permutation [(i, i ^ 2^b)];
- the locality predicate target < log2(chunkSize)
  (halfMatrixBlockFitsInChunk, :366-371) -> a Python-level static branch:
  local targets run the ordinary kernels un-communicated;
- SWAP-relocalization of multi-qubit ops (:1447-1545) -> half-shard
  ppermute swaps (``swap_sharded``) pulling high targets down to free low
  qubits, op applied locally, swaps undone;
- ``MPI_Allreduce`` (:35-117) -> ``lax.psum``;
- ``MPI_Bcast`` replication loops (:379-423) -> ``lax.all_gather``.

Two structural wins over the reference: no pairStateVec — the reference
permanently holds a 2x receive buffer (QuEST_cpu.c:1279-1315) while
ppermute's transient buffer exists only inside one fused program; and the
elementwise combine fuses with the communication epilogue under XLA instead
of being a second pass over memory.  A third (round-8): every exchange is
CHUNK-PIPELINED — ``exchange_pipelined`` splits the payload into C chunks
and issues the ppermute for chunk i+1 before the combine consuming chunk
i, overlapping ICI transfer with VPU work and shrinking the transient
recv buffer to one chunk (qHiPSTER's pipelined exchange,
arXiv:1601.07195 §III; docs/design.md §17).

These kernels are *compile-time* alternatives invoked by the API layer when
a gate touches sharded qubits (quest_tpu.api routes there); the GSPMD path
(plain jit + sharding propagation) remains available via
``use_explicit_dist(False)`` for benchmarking one against the other
(SURVEY.md §7 layer 5 calls for exactly this comparison).
"""

from __future__ import annotations

import math
import os
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import telemetry as _telemetry
from ..contracts import sharded_contract
from ..env import AMP_AXIS, shard_map
from ..ops import cplx, kernels
from . import topology as topo

_CONFIG = {"explicit": True, "lazy_remap": True}


def mesh_topology(mesh: Mesh) -> topo.Topology:
    """The live hierarchical arrangement of this mesh's amplitude axis
    (``QT_TOPOLOGY``; single-host fallback — parallel/topology.py)."""
    return topo.resolve(amp_axis_size(mesh))


def _record_exchange(amps, op: str, count: int, nbytes: int, chunks,
                     tier: str = "ici") -> None:
    """Dispatch-time exchange accounting (telemetry.record_exchange):
    skipped for traced operands — a wrapper reached from inside a user
    jit body would otherwise count once per TRACE, not per execution."""
    if not _telemetry.enabled() or isinstance(amps, jax.core.Tracer):
        return
    _telemetry.record_exchange(op, count, nbytes, chunks=str(chunks),
                               tier=tier)


def _record_exchange_tiers(amps, op: str, parts, chunks) -> None:
    """Per-tier dispatch accounting: ``parts`` maps tier ->
    (count, nbytes); one record_exchange per nonzero tier, so the tier
    series sum exactly to the flat accounting of the same program."""
    if not _telemetry.enabled() or isinstance(amps, jax.core.Tracer):
        return
    for tier, (count, nbytes) in parts.items():
        if count or nbytes:
            _telemetry.record_exchange(op, count, nbytes,
                                       chunks=str(chunks), tier=tier)


def _sweep_exchange_tiers(nex: int, r: int, payload: int,
                          t: "topo.Topology", composed: bool) -> dict:
    """Tier split of a mesh-bit SWEEP op (Trotter / PauliSum rotation
    layers): ``nex`` full-shard exchanges spread uniformly over the
    ``r`` mesh bits, so the DCN share is exactly ``nex * dcn_bits / r``
    (nex is a multiple of r for the layered bodies).  ``composed`` marks
    the direct-gather bodies whose single composed mesh-flip ppermute
    per term may touch ANY mesh bit — conservatively DCN on a multi-host
    topology."""
    if composed:
        tier = "dcn" if t.dcn_bits else "ici"
        return {tier: (nex, nex * payload)}
    dcn_n = nex * t.dcn_bits // max(r, 1)
    return {"ici": (nex - dcn_n, (nex - dcn_n) * payload),
            "dcn": (dcn_n, dcn_n * payload)}


# ---------------------------------------------------------------------------
# Guarded collectives (elastic recovery, docs/design.md §19)
#
# On a healthy mesh an exchange dispatch either completes or raises; on a
# degraded pod it can also hang (a peer stopped answering) or fail with a
# runtime error long after the circuit started.  Every sharded dispatch
# below goes through guarded_dispatch: bounded attempts with exponential
# backoff (retry_io's policy, shared knobs), dispatch latency observed
# into the exchange_latency_seconds histogram, a post-hoc deadline that
# counts exchange_timeouts_total when a dispatch came back slower than
# QT_EXCHANGE_DEADLINE_S, and — when the retry budget is exhausted — a
# ShardLossError that the resilience layer's failover loop converts into
# rollback + mesh shrink (resilience.run_resumable).  Deterministic
# fault injection enters through EXCHANGE_FAULT_HOOK, armed per window
# by resilience.FaultPlan (`stall` / `shard_loss` modes).
# ---------------------------------------------------------------------------

_DEADLINE_ENV = "QT_EXCHANGE_DEADLINE_S"
_GUARD_ATTEMPTS_ENV = "QT_EXCHANGE_RETRIES"

# fault-injection slot: resilience.run_resumable installs the active
# FaultPlan's take_exchange_fault here (a plain module slot rather than
# an import so dist <-> resilience stays acyclic).  The hook takes the
# op name and returns None, "stall", or "shard_loss".
EXCHANGE_FAULT_HOOK: list = [None]


class ShardLossError(RuntimeError):
    """A shard is presumed dead: an exchange dispatch kept failing past
    its retry budget, or the fault plan declared the loss outright.
    Deliberately NOT a QuESTError — it signals infrastructure failure,
    not API misuse — so the resilience layer can catch it for failover
    without masking validation bugs."""

    def __init__(self, msg: str, *, shard: Optional[int] = None,
                 op: str = "exchange"):
        super().__init__(msg)
        self.shard = shard
        self.op = op


def exchange_deadline() -> Optional[float]:
    """The live per-dispatch deadline in seconds (None = no deadline)."""
    raw = os.environ.get(_DEADLINE_ENV)
    if not raw:
        return None
    try:
        d = float(raw)
    except ValueError:
        return None
    return d if d > 0 else None


def guarded_dispatch(fn, *args, op: str = "exchange", shards: int = 1,
                     **kwargs):
    """Run one exchange dispatch under the collective guard.

    Passthrough for traced operands (a dispatch reached from inside a
    user jit can neither be timed nor retried — it is a trace).  For
    concrete operands: up to QT_EXCHANGE_RETRIES attempts (default 3)
    with retry_io-style exponential backoff (QT_RETRY_BASE_SECONDS base);
    each attempt first consumes one injected fault from
    EXCHANGE_FAULT_HOOK — ``stall`` burns the attempt as a timed-out
    dispatch (exchange_timeouts_total), ``shard_loss`` raises
    ShardLossError immediately — then dispatches, observing the host
    dispatch latency into exchange_latency_seconds{op,shards} and
    counting a timeout when it exceeded QT_EXCHANGE_DEADLINE_S (the
    result is still used: a late synchronous dispatch has already
    completed — the deadline is SLO accounting, not cancellation).  A
    real dispatch exception is retried; note most inner programs donate
    their operand, so a retry after a partially-executed dispatch may
    surface a deleted-buffer error — the guard converts either into
    ShardLossError after the budget."""
    import time as _time

    if args and isinstance(args[0], jax.core.Tracer):
        return fn(*args, **kwargs)
    attempts = max(1, int(os.environ.get(_GUARD_ATTEMPTS_ENV, "3")))
    base_delay = float(os.environ.get("QT_RETRY_BASE_SECONDS", "0.05"))
    deadline = exchange_deadline()
    shards = str(shards)
    last = None
    for k in range(attempts):
        hook = EXCHANGE_FAULT_HOOK[0]
        fault = hook(op) if hook is not None else None
        if fault == "shard_loss":
            _telemetry.inc("exchange_timeouts_total", op=op)
            topo.notify_mesh_event("shard_loss", op=op, shard=None)
            raise ShardLossError(
                f"injected shard loss during {op} dispatch", op=op)
        if fault == "host_loss":
            # a whole host's shards die at once: report the highest shard
            # as the observed casualty — the failover maps it back to its
            # host (topology.host_of) and excludes that host's entire
            # device range from the surviving mesh
            _telemetry.inc("exchange_timeouts_total", op=op)
            topo.notify_mesh_event("host_loss", op=op,
                                   shard=int(shards) - 1)
            raise ShardLossError(
                f"injected host loss during {op} dispatch", op=op,
                shard=int(shards) - 1)
        if fault == "stall":
            _telemetry.inc("exchange_timeouts_total", op=op)
            last = TimeoutError(f"injected stall during {op} dispatch")
        else:
            t0 = _time.perf_counter()
            try:
                out = fn(*args, **kwargs)
            # qlint: allow(broad-except): guarded dispatch retries transient runtime failures of any class (backend RPC errors surface under several types); the final attempt re-raises via ShardLossError with the last error chained
            except Exception as e:  # runtime dispatch failure: retry
                last = e
            else:
                elapsed = _time.perf_counter() - t0
                _telemetry.observe("exchange_latency_seconds", elapsed,
                                   op=op, shards=shards)
                if deadline is not None and elapsed > deadline:
                    _telemetry.inc("exchange_timeouts_total", op=op)
                return out
        if k + 1 < attempts:
            _time.sleep(base_delay * (1 << k))
    topo.notify_mesh_event("shard_loss", op=op, shard=None,
                           exhausted_attempts=attempts)
    raise ShardLossError(
        f"{op} dispatch failed after {attempts} attempts "
        f"(last error: {last!r})", op=op) from last


def use_explicit_dist(enabled: bool) -> None:
    """Toggle the explicit ppermute path vs GSPMD propagation."""
    _CONFIG["explicit"] = bool(enabled)


def explicit_dist_enabled() -> bool:
    return _CONFIG["explicit"]


def use_lazy_remap(enabled: bool) -> None:
    """Toggle the communication-avoiding lazy logical->physical
    permutation (mpiQulacs-style, arXiv:2203.16044).  Disabled, every
    sharded-target relocalization swaps back eagerly (the reference's
    per-gate scheme, QuEST_cpu_distributed.c:1447-1545) — kept for A/B
    benchmarking (bench_suite dist_remap config) and bit-identity tests."""
    _CONFIG["lazy_remap"] = bool(enabled)


def lazy_remap_enabled() -> bool:
    return _CONFIG["lazy_remap"]


# ---------------------------------------------------------------------------
# Pipelined chunked exchange (communication/computation overlap)
#
# Every sharded-qubit op below used to move its data in ONE monolithic
# ppermute — the ICI link idle while the combine math ran, the VPU idle
# while amplitudes were in flight, and the transient recv buffer a full
# extra shard of HBM.  qHiPSTER (arXiv:1601.07195 §III) gets most of its
# distributed speedup from splitting the exchange into chunks and
# pipelining communication with computation; the reference itself chunks
# its MPI exchange when buffers are tight, without overlapping
# (exchangeStateVectors, QuEST_cpu_distributed.c:489-517).
# exchange_pipelined is the shared engine: the payload splits into C
# chunks along the amplitude axis and the loop is software-pipelined —
# the ppermute for chunk i+1 is issued BEFORE the combine consuming
# chunk i (an unrolled two-stage schedule with explicit prologue and
# epilogue), so XLA's latency-hiding scheduler lowers each exchange to a
# collective-permute-start/done pair with the previous chunk's combine
# between them, and the transient recv buffer is one chunk instead of
# the whole payload (docs/design.md §17).
# ---------------------------------------------------------------------------

_EXCHANGE_ENV = "QT_EXCHANGE_CHUNKS"

# Small-shard fallback: below this many payload bytes the monolithic
# exchange wins — per-chunk dispatch/slicing overhead exceeds any
# overlap.  Measured on the 8-shard CPU dryrun (bench_suite config 7
# chunk sweep, docs/design.md §17): C=4 costs a steady 21-41% over
# monolithic across 16 KiB..4 MiB shards when there is NO asynchrony to
# recoup it (the CPU backend's collective-permute is a synchronous
# copy), which is why the auto heuristic only engages off-CPU at all;
# there, the overhead side bounds the loss and the threshold sits where
# a shard's transfer time is worth hiding (~2 MiB at v5e ICI rates).
PIPELINE_MIN_BYTES = 1 << 21

# Steady-state chunk sizing: big enough that per-chunk collective setup
# amortizes, small enough that two in-flight chunks hide under a combine.
_TARGET_CHUNK_BYTES = 1 << 22

MAX_EXCHANGE_CHUNKS = 8


# per-drain chunk escalation set by the memory governor's degradation
# ladder (governor.govern_drain rung 1) and cleared in the drain's
# finally (governor.end_drain) — published through exchange_config_key
# so the compiled-executor cache, the telemetry byte accounting, and
# the reconcile prediction all see ONE consistent chunk policy.  The
# explicit QT_EXCHANGE_CHUNKS env override always wins.
_GOVERNOR_CHUNKS: list = [None]


def exchange_config_key() -> Optional[str]:
    """The live chunk-policy override — a cache-key component for
    programs that bake the chunk count in at trace time
    (fusion._plan_runner keys its compiled drain executor on this, so
    flipping the env var between drains retraces instead of silently
    reusing a stale chunk schedule).  ``QT_EXCHANGE_CHUNKS`` first,
    then the memory governor's per-drain escalation."""
    v = os.environ.get(_EXCHANGE_ENV)
    if v is not None:
        return v
    g = _GOVERNOR_CHUNKS[0]
    return None if g is None else str(int(g))


def _pow2_floor(x: int) -> int:
    return 1 << (max(int(x), 1).bit_length() - 1)


def exchange_chunks(payload_bytes: int, limit: int = 1 << 30,
                    backend: Optional[str] = None) -> int:
    """Chunk count for one exchange of ``payload_bytes`` bytes.

    ``QT_EXCHANGE_CHUNKS`` overrides unconditionally (rounded down to a
    power of two — chunks must divide the power-of-two payload — with the
    rounding recorded once in the degradation registry); otherwise the
    heuristic: monolithic on the CPU backend (its collective-permute is
    a synchronous copy — chunking measured a flat 21-41% loss with no
    overlap to recoup, bench_suite config 7) and monolithic below
    PIPELINE_MIN_BYTES (pipeline overhead loses on small shards), else
    ~_TARGET_CHUNK_BYTES chunks capped at MAX_EXCHANGE_CHUNKS.
    ``limit`` is the structural cap of the call site (the payload axis
    the combine must keep intact); always respected.  ``backend``
    defaults to the live jax backend (tests pass it explicitly)."""
    limit = max(1, _pow2_floor(limit))
    override = exchange_config_key()
    if override is not None:
        try:
            c = max(1, int(override))
        except ValueError:
            from .. import resilience

            resilience.record_degradation(
                "exchange_chunks",
                f"unparseable {_EXCHANGE_ENV}={override!r}; monolithic")
            return 1
        if c != _pow2_floor(c):
            from .. import resilience

            resilience.record_degradation(
                "exchange_chunks",
                f"{_EXCHANGE_ENV}={c} not a power of two; "
                f"using {_pow2_floor(c)}")
        return min(_pow2_floor(c), limit)
    if backend is None:
        backend = jax.default_backend()
    if backend == "cpu" or payload_bytes < PIPELINE_MIN_BYTES:
        return 1
    c = _pow2_floor(payload_bytes // _TARGET_CHUNK_BYTES)
    return max(1, min(c, MAX_EXCHANGE_CHUNKS, limit))


def _shard_payload_bytes(amps, mesh: Mesh) -> int:
    """Bytes of ONE shard of a (2, N)-global SoA state — the full-shard
    exchange payload (wrappers resolve chunk counts OUTSIDE the jit so
    the env override participates in dispatch, not in a stale trace).
    A batched (B, 2, N) register bank's shard carries all B elements'
    slices, so its exchange payload (and the telemetry byte accounting
    built on it) scales with the batch size."""
    return int(amps.size) // amp_axis_size(mesh) * amps.dtype.itemsize


def exchange_pipelined(send, perm, combine_fn, *, chunks: int,
                       axis: int = -1):
    """Chunked double-buffered ppermute INSIDE a shard_map body.

    Splits ``send`` into ``chunks`` equal contiguous pieces along
    ``axis`` (default the LAST axis, = the top log2(chunks) bits of a
    flat shard's amplitude index; the block axis 1 of a canonical shard)
    and software-pipelines the exchange:

        prologue : ppermute chunk 0
        steady   : ppermute chunk i+1; combine chunk i   (i = 0..C-2)
        epilogue : combine chunk C-1

    The loop is fully unrolled so every chunk gets its own HLO
    collective-permute — the form XLA's latency-hiding scheduler splits
    into start/done pairs with the neighbouring combine scheduled between
    them — and the transient recv footprint is at most two chunks (the
    one being consumed plus the one in flight) instead of the whole
    payload.  ``combine_fn(i, own_chunk, recv_chunk)`` receives the
    STATIC chunk index, so call sites can resolve chunk-constant bit
    conditions (e.g. high local controls) at trace time.

    ``chunks`` <= 1 (or a non-dividing count) is the monolithic path:
    one ppermute, one combine — bit-identical output either way, since
    the combines are elementwise on disjoint chunks."""
    axis = axis % send.ndim
    m = int(send.shape[axis])
    if chunks <= 1 or m % chunks or m // chunks == 0:
        recv = lax.ppermute(send, AMP_AXIS, perm)
        return combine_fn(0, send, recv)
    step = m // chunks
    parts = jnp.split(send, chunks, axis=axis)
    in_flight = lax.ppermute(parts[0], AMP_AXIS, perm)     # prologue
    out = send
    for i in range(chunks):
        recv = in_flight
        if i + 1 < chunks:
            # issue chunk i+1 before consuming chunk i: the combine below
            # is what the transfer hides behind
            in_flight = lax.ppermute(parts[i + 1], AMP_AXIS, perm)
        # update-slice chain rather than a concat: a concat epilogue costs
        # a second full-payload staging buffer (measured on the CPU
        # dryrun), the chain lets buffer assignment grow the output in
        # place once the source chunks are dead
        start = [0] * send.ndim
        start[axis] = i * step
        out = lax.dynamic_update_slice(
            out, combine_fn(i, parts[i], recv), tuple(start))
    return out


def _swap_halves_in_shard(local, lb: int, mb: int, nloc: int, ndev: int,
                          chunks: int = 1):
    """Half-shard SWAP exchange inside a shard_map body: send the local
    half whose bit ``lb`` mismatches this shard's mesh bit ``mb`` to the
    XOR partner and splice the received half back (the reference's
    'pair processes only swap half their amps', statevec_swapQubitAmps,
    QuEST_cpu_distributed.c:1397-1436), with the half-payload exchange
    chunk-pipelined.  Shared by swap_sharded, _remap_in_shard's mixed
    transpositions, and _reverse_run_sharded."""
    idx = lax.axis_index(AMP_AXIS)
    u = (idx >> mb) & 1
    lv = local.reshape(2, 1 << (nloc - 1 - lb), 2, 1 << lb)
    send = lax.dynamic_index_in_dim(lv, 1 - u, axis=2, keepdims=False)
    recv = exchange_pipelined(
        send.reshape(2, -1), _hypercube_perm(ndev, mb),
        lambda i, own, rv: rv, chunks=chunks)
    return lax.dynamic_update_index_in_dim(
        lv, recv.reshape(send.shape), 1 - u, axis=2).reshape(2, -1)


# amplitude bits inside one (128, 128) block of a canonical shard
_BLOCK_BITS = 14


def route_residue(nloc: int, itemsize: Optional[int] = None) -> bool:
    """Whether a remap's local residue on a shard of 2^nloc amplitudes
    goes through a mesh bit as in-place half-shard swaps
    (decompose_sigma): the shard and the axis permutation's second copy
    of it exceed the per-chip HBM budget (governor.budget_bytes; no
    budget is known on the CPU).  ``itemsize``: bytes of one real
    amplitude, the active precision's by default."""
    from .. import governor, precision

    if itemsize is None:
        itemsize = jnp.dtype(precision.real_dtype()).itemsize
    budget = governor.budget_bytes()
    return budget is not None and 2 * (2 << nloc) * itemsize > budget


def remap_window_cap(nloc: int) -> int:
    """Most distinct qubits one remap window may want shard-local.  A
    bit inside a (128, 128) block swaps with a mesh bit through a
    shard-sized relayout on the TPU (_swap_halves_canonical), which a
    shard of 2^28 amplitudes or more (2 GiB f32; 8 GiB at 32 qubits on
    four chips) cannot afford: its windows leave 14 slots unwanted, so
    every eviction finds a block slot (plan_window_remap prefers those)
    and the swap runs in place.  Smaller shards use every slot."""
    return nloc - _BLOCK_BITS if nloc >= 2 * _BLOCK_BITS else nloc


def _swap_halves_canonical(local, lb: int, mb: int, ndev: int,
                           chunks: int = 1):
    """_swap_halves_in_shard for a canonical (2, B, 128, 128) shard
    (qureg.device_amps_shape), in place and chunk by chunk.

    Each chunk is a static slice of blocks holding both halves of bit
    ``lb``; the half to send is picked by a select on the shard's mesh
    bit, not by a dynamic index (whose TPU compile grew with the shard),
    and the combined chunk is written back where it was read, so the
    TPU compiler runs a block bit's swap (lb >= 14) in place
    (tests/test_chip_compile.py).  For a bit inside the block XLA may lay
    the whole shard out anew, a shard-sized copy, which the window
    planner keeps away from large shards (remap_window_cap)."""
    u = ((lax.axis_index(AMP_AXIS) >> mb) & 1) == 1
    perm = _hypercube_perm(ndev, mb)
    nb = int(local.shape[1])
    lo = 1 << max(lb - _BLOCK_BITS, 0)      # blocks per half-run
    if lb >= _BLOCK_BITS and nb // (2 * lo) < chunks:
        # few long half-runs: a chunk is a block range of one half-run
        # and the same range of its partner run, two slices apart
        per = max(1, min(chunks, nb // 2) * 2 * lo // nb)
        nj = lo // per
        spans = [(h * 2 * lo + j * nj, nj) for h in range(nb // (2 * lo))
                 for j in range(per)]

        def halves(cur, span):
            return (lax.slice_in_dim(cur, span[0], span[0] + nj, axis=1),
                    lax.slice_in_dim(cur, span[0] + lo, span[0] + lo + nj,
                                     axis=1))

        def combine(cur, span, recv):
            # each kept half is read just before its own update
            a = lax.slice_in_dim(cur, span[0], span[0] + nj, axis=1)
            cur = lax.dynamic_update_slice(cur, jnp.where(u, recv, a),
                                           (0, span[0], 0, 0))
            b = lax.slice_in_dim(cur, span[0] + lo, span[0] + lo + nj,
                                 axis=1)
            return lax.dynamic_update_slice(cur, jnp.where(u, b, recv),
                                            (0, span[0] + lo, 0, 0))
    else:
        # a chunk is a block range holding both halves of bit lb
        chunks = max(1, min(chunks, nb // (2 * lo) if lb >= _BLOCK_BITS
                            else nb))
        step = nb // chunks
        spans = [(c * step, step) for c in range(chunks)]
        if lb >= _BLOCK_BITS:
            pview, pax = (2, step // (2 * lo), 2, lo, 128, 128), 2
        elif lb >= 7:            # a sublane bit
            pview, pax = (2, step, 1 << (13 - lb), 2, 1 << (lb - 7), 128), 3
        else:                    # a lane bit
            pview, pax = (2, step, 128, 1 << (6 - lb), 2, 1 << lb), 4

        def halves(cur, span):
            piece = lax.slice_in_dim(cur, span[0], span[0] + step,
                                     axis=1).reshape(pview)
            return (lax.index_in_dim(piece, 0, pax, keepdims=False),
                    lax.index_in_dim(piece, 1, pax, keepdims=False))

        def combine(cur, span, recv):
            a, b = halves(cur, span)
            piece = jnp.stack([jnp.where(u, recv, a), jnp.where(u, b, recv)],
                              axis=pax).reshape(2, step, 128, 128)
            return lax.dynamic_update_slice(cur, piece, (0, span[0], 0, 0))

    def send(cur, span):
        a, b = halves(cur, span)
        return lax.ppermute(jnp.where(u, a, b), AMP_AXIS, perm)

    # the pipeline of exchange_pipelined: chunk i+1 is sent before chunk
    # i is combined.  The combine reads its kept half from the newest
    # chain value: a read of an older value would keep that whole
    # version live and force XLA to copy the shard at every update.
    cur = local
    in_flight = send(cur, spans[0])
    for i, span in enumerate(spans):
        recv = in_flight
        if i + 1 < len(spans):
            in_flight = send(cur, spans[i + 1])
        cur = combine(cur, span, recv)
    return cur


def amp_axis_size(mesh: Mesh) -> int:
    """Size of the amplitude axis — NOT mesh.devices.size: meshes may carry
    extra axes (e.g. the (dp, amps) training mesh)."""
    return int(mesh.shape[AMP_AXIS])


def mesh_platform(mesh: Mesh) -> str:
    """Platform of the mesh's devices ("tpu", "cpu", ...)."""
    return mesh.devices.flat[0].platform


def num_shard_bits(mesh: Mesh) -> int:
    return int(math.log2(amp_axis_size(mesh)))


def _hypercube_perm(ndev: int, bit: int):
    """Static XOR-partner permutation — the reference's pair-rank computation
    chunkId ^ (2^t / chunkSize) (QuEST_cpu_distributed.c:313-333) as a
    ppermute table."""
    return [(i, i ^ (1 << bit)) for i in range(ndev)]


def _shard_coeffs(rmat_like, mybit):
    """Per-shard gate coefficients a = m[b,b], b_coef = m[b,1-b] selected by
    the shard's target-bit value (statevec_compactUnitaryDistributed,
    QuEST_cpu.c:1841-1900 uses rankIsUpper the same way)."""
    row = mybit
    a_re = rmat_like[0, row, row]
    a_im = rmat_like[1, row, row]
    b_re = rmat_like[0, row, 1 - row]
    b_im = rmat_like[1, row, 1 - row]
    return a_re, a_im, b_re, b_im


@sharded_contract(collectives={"collective-permute": 1},
                  max_exchange_bytes=1 << 10,
                  max_tier_bytes={"ici": 1 << 10, "dcn": 1 << 10})
def apply_matrix_1q_sharded(
    amps,
    matrix,
    *,
    mesh: Mesh,
    num_qubits: int,
    target: int,
    controls: Tuple[int, ...] = (),
    control_states: Tuple[int, ...] = (),
    chunks: Optional[int] = None,
):
    """One-qubit dense gate on a *sharded* target qubit: full-shard
    chunk-pipelined ppermute exchange + fused elementwise combine — the
    reference's non-local gate pattern (QuEST_cpu_distributed.c:854-928)
    with the exchange split into chunks so the ICI transfer of chunk i+1
    overlaps the VPU combine of chunk i (exchange_pipelined).

    Low (local) controls restrict the exchanged+combined sub-block; sharded
    controls become a per-shard mask (the reference instead skips ranks
    whose chunk fails the control condition, :1093-1112 — SPMD cannot skip,
    but masked shards do no extra communication since the exchange is
    collective anyway).  ``chunks`` defaults to the per-op heuristic
    (exchange_chunks over the shard bytes); resolved HERE, outside the
    jit, so the env override acts at dispatch time."""
    if chunks is None:
        chunks = exchange_chunks(_shard_payload_bytes(amps, mesh))
    _record_exchange(amps, "matrix_1q", 1, _shard_payload_bytes(amps, mesh),
                     chunks,
                     tier=mesh_topology(mesh).tier_of_bit(
                         target - (num_qubits - num_shard_bits(mesh))))
    return guarded_dispatch(
        _apply_matrix_1q_sharded, amps, matrix,
        op="matrix_1q", shards=amp_axis_size(mesh),
        mesh=mesh, num_qubits=num_qubits, target=target,
        controls=tuple(controls), control_states=tuple(control_states),
        chunks=int(chunks))


@partial(
    jax.jit,
    static_argnames=("mesh", "num_qubits", "target", "controls",
                     "control_states", "chunks"),
    donate_argnums=0,
)
def _apply_matrix_1q_sharded(
    amps,
    matrix,
    *,
    mesh: Mesh,
    num_qubits: int,
    target: int,
    controls: Tuple[int, ...],
    control_states: Tuple[int, ...],
    chunks: int,
):
    ndev = amp_axis_size(mesh)
    r = num_shard_bits(mesh)
    n = num_qubits
    nloc = n - r
    assert target >= nloc, "local targets take the ordinary kernel"
    bit = target - nloc
    perm = _hypercube_perm(ndev, bit)

    states = control_states or (1,) * len(controls)
    local_controls = tuple((c, s) for c, s in zip(controls, states) if c < nloc)
    shard_controls = tuple((c - nloc, s) for c, s in zip(controls, states) if c >= nloc)
    # power-of-two, never more chunks than per-shard amplitudes: the
    # chunk-index bit arithmetic below must agree with the engine's split
    chunks = min(_pow2_floor(chunks), 1 << nloc)
    c_bits = chunks.bit_length() - 1
    nch = nloc - c_bits          # local index bits inside one chunk

    def kernel(local, m):
        # local: (2, amps_per_shard); m: (2, 2, 2) stacked SoA
        idx = lax.axis_index(AMP_AXIS)
        mybit = (idx >> bit) & 1
        a_re, a_im, b_re, b_im = _shard_coeffs(m, mybit)

        def cm(own_block, recv_block):
            return cplx.cmul(own_block, a_re, a_im) + cplx.cmul(recv_block, b_re, b_im)

        def combine(i, own, recv):
            # local controls at bit >= nch are chunk-CONSTANT: resolve
            # them statically from the chunk index (a failing chunk keeps
            # its own amplitudes — the exchange still moved it, matching
            # the monolithic kernel's collective-anyway semantics)
            if any(cb >= nch and ((i >> (cb - nch)) & 1) != s
                   for cb, s in local_controls):
                new = own
            else:
                low = tuple((cb, s) for cb, s in local_controls if cb < nch)
                if low:
                    shape, sel = kernels._interleaved_sel(nch, low)
                    lv = own.reshape(shape)
                    rv = recv.reshape(shape)
                    new = lv.at[sel].set(cm(lv[sel], rv[sel])).reshape(2, -1)
                else:
                    new = cm(own, recv)
            for cbit, s in shard_controls:
                cond = ((idx >> cbit) & 1) == s
                new = jnp.where(cond, new, own)
            return new

        return exchange_pipelined(local, perm, combine, chunks=chunks)

    return shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(None, AMP_AXIS), P()),
        out_specs=P(None, AMP_AXIS),
    )(amps, jnp.asarray(matrix, amps.dtype))


@sharded_contract(collectives={"collective-permute": 1},
                  max_exchange_bytes=1 << 9,
                  max_tier_bytes={"ici": 1 << 9, "dcn": 1 << 9})
def swap_sharded(amps, *, mesh: Mesh, num_qubits: int, qb_low: int,
                 qb_high: int, chunks: Optional[int] = None):
    """SWAP between a local qubit and a sharded qubit: exchange only the
    mismatched half-shard with the XOR partner (statevec_swapQubitAmps
    routing, QuEST_cpu_distributed.c:1397-1436: 'pair processes only swap
    half their amps'), the half-payload chunk-pipelined
    (_swap_halves_in_shard -> exchange_pipelined).

    Derivation: for shard-coordinate bit u (the high qubit's value) and
    local bit v (the low qubit), elements with v == u stay; elements with
    v != u land on the pair rank at local bit position unchanged-in-value.
    So each shard sends its v = 1-u half and splices the received half back
    at the same position."""
    if chunks is None:
        chunks = exchange_chunks(_shard_payload_bytes(amps, mesh) // 2)
    _record_exchange(amps, "swap", 1, _shard_payload_bytes(amps, mesh) // 2,
                     chunks,
                     tier=mesh_topology(mesh).tier_of_bit(
                         qb_high - (num_qubits - num_shard_bits(mesh))))
    return guarded_dispatch(
        _swap_sharded, amps, op="swap", shards=amp_axis_size(mesh),
        mesh=mesh, num_qubits=num_qubits,
        qb_low=qb_low, qb_high=qb_high, chunks=int(chunks))


@partial(jax.jit,
         static_argnames=("mesh", "num_qubits", "qb_low", "qb_high", "chunks"),
         donate_argnums=0)
def _swap_sharded(amps, *, mesh: Mesh, num_qubits: int, qb_low: int,
                  qb_high: int, chunks: int):
    ndev = amp_axis_size(mesh)
    r = num_shard_bits(mesh)
    nloc = num_qubits - r
    assert qb_high >= nloc and qb_low < nloc
    bit = qb_high - nloc
    chunks = min(_pow2_floor(chunks), 1 << (nloc - 1))

    def kernel(local):
        return _swap_halves_in_shard(local, qb_low, bit, nloc, ndev, chunks)

    return shard_map(
        kernel, mesh=mesh, in_specs=P(None, AMP_AXIS), out_specs=P(None, AMP_AXIS)
    )(amps)


@partial(jax.jit, static_argnames=("mesh",))
def total_prob_sharded(amps, *, mesh: Mesh):
    """|amps|^2 with an explicit psum — the reference's local-reduce +
    MPI_Allreduce(SUM) (QuEST_cpu_distributed.c:1308-1322)."""

    def kernel(local):
        return lax.psum(jnp.sum(cplx.abs2(local)), AMP_AXIS)

    return shard_map(
        kernel, mesh=mesh, in_specs=P(None, AMP_AXIS), out_specs=P()
    )(amps)


@sharded_contract(collectives={"all-gather": 1},
                  max_exchange_bytes=1 << 13)
def gather_replicated(amps, *, mesh: Mesh):
    """Replicate the full state onto every device — the analogue of the
    reference's ring-of-broadcasts copyVecIntoMatrixPairState
    (QuEST_cpu_distributed.c:379-423), used to build rho = |psi><psi|."""
    ndev = amp_axis_size(mesh)
    t = mesh_topology(mesh)
    payload = _shard_payload_bytes(amps, mesh)
    # each shard receives ndev-1 peer shards: chips-1 of them over ICI,
    # the rest across hosts — the count rides the slower tier
    dcn_b = payload * (ndev - t.chips)
    _record_exchange_tiers(
        amps, "gather",
        {"ici": (0 if dcn_b else 1, payload * (t.chips - 1)),
         "dcn": (1 if dcn_b else 0, dcn_b)}, 1)
    return guarded_dispatch(_gather_replicated, amps, op="gather",
                            shards=ndev, mesh=mesh)


@partial(jax.jit, static_argnames=("mesh",))
def _gather_replicated(amps, *, mesh: Mesh):

    def kernel(local):
        return lax.all_gather(local, AMP_AXIS, axis=1, tiled=True)

    return shard_map(
        kernel, mesh=mesh, in_specs=P(None, AMP_AXIS), out_specs=P(),
        check_vma=False,
    )(amps)


def _pair_channel_weights(kind: str, p, ktv, btv, dt):
    """(w1, w2) weights for the double-flip pair channels given the ket /
    bra target-bit values (traced scalars or broadcastable arrays):
    depol:   w1 = kt==bt ? 1-2p/3 : 1-4p/3 ; w2 = kt==bt ? 2p/3 : 0
    damping: w1 = [[1, s], [s, 1-p]][bt, kt] (s = sqrt(1-p));
             w2 = p at (kt,bt)=(0,0) else 0."""
    p = jnp.asarray(p, dt)
    same = ktv == btv
    if kind == "depol":
        w1 = jnp.where(same, 1 - 2 * p / 3, 1 - 4 * p / 3).astype(dt)
        w2 = jnp.where(same, 2 * p / 3, 0.0).astype(dt)
        return w1, w2
    s = jnp.sqrt(1 - p)
    w1 = jnp.where(same, jnp.where(ktv == 0, 1.0, 1 - p),
                   s).astype(dt)
    w2 = jnp.where((ktv == 0) & (btv == 0), p, 0.0).astype(dt)
    return w1, w2


@sharded_contract(collectives={"collective-permute": 1},
                  max_exchange_bytes=1 << 10,
                  max_tier_bytes={"ici": 1 << 10, "dcn": 1 << 10})
def mix_pair_channel_sharded(amps, prob, *, mesh: Mesh, num_qubits: int,
                             target: int, kind: str,
                             chunks: Optional[int] = None):
    """Explicit distributed depolarise / damping on a sharded density
    matrix: one chunk-pipelined full-shard ppermute to the double-flip
    partner + a fused elementwise combine — the TPU-native redesign of the
    reference's pack-and-exchange distributed decoherence
    (QuEST_cpu_distributed.c:553-852).  GSPMD compiles the same channel to
    3 collective-permutes (depol) or 3 permutes + 10 all-to-alls
    (damping); this path is exactly one (chunked) collective.

    ``kind``: "depol" | "damping".  Requires the bra target bit
    (target + num_qubits) to be a mesh-coordinate bit; local-bra channels
    take the elementwise kernels (ops/density.py)."""
    if chunks is None:
        chunks = exchange_chunks(_shard_payload_bytes(amps, mesh))
    # partner shard = XOR on the bra mesh bit (and the ket mesh bit too
    # when both are sharded) — the hop crosses DCN iff any flipped
    # mesh-coordinate bit addresses the host
    nloc = 2 * num_qubits - num_shard_bits(mesh)
    xor_mask = 1 << (target + num_qubits - nloc)
    if target >= nloc:
        xor_mask |= 1 << (target - nloc)
    _record_exchange(amps, "pair_channel", 1,
                     _shard_payload_bytes(amps, mesh), chunks,
                     tier=mesh_topology(mesh).tier_of_mask(xor_mask))
    return guarded_dispatch(
        _mix_pair_channel_sharded, amps, prob,
        op="pair_channel", shards=amp_axis_size(mesh),
        mesh=mesh, num_qubits=num_qubits, target=target,
        kind=kind, chunks=int(chunks))


@partial(jax.jit,
         static_argnames=("mesh", "num_qubits", "target", "kind", "chunks"),
         donate_argnums=0)
def _mix_pair_channel_sharded(amps, prob, *, mesh: Mesh, num_qubits: int,
                              target: int, kind: str, chunks: int):
    nq = num_qubits
    nn = 2 * nq
    ndev = amp_axis_size(mesh)
    r = num_shard_bits(mesh)
    nloc = nn - r
    t, b = target, target + nq
    assert b >= nloc, "local channels take ops/density.py"
    bbit = b - nloc
    dt = amps.dtype
    # the bra-sharded/ket-local branch flips the local ket-bit axis inside
    # each chunk: chunk bits must stay strictly above it
    limit = (1 << nloc) if t >= nloc else (1 << (nloc - 1 - t))
    chunks = min(_pow2_floor(chunks), limit)

    def kernel(local, p):
        idx = lax.axis_index(AMP_AXIS)
        btv = (idx >> bbit) & 1
        if t >= nloc:
            # both target bits sharded: partner shard = double XOR;
            # weights are per-shard scalars, the combine chunks freely
            tbit = t - nloc
            perm = [(i, i ^ (1 << bbit) ^ (1 << tbit)) for i in range(ndev)]
            ktv = (idx >> tbit) & 1
            w1, w2 = _pair_channel_weights(kind, p, ktv, btv, dt)
            return exchange_pipelined(
                local, perm, lambda i, own, rv: own * w1 + rv * w2,
                chunks=chunks)
        # ket bit local, bra bit sharded: exchange on the bra mesh bit,
        # partner element = received block with the LOCAL ket bit flipped
        perm = _hypercube_perm(ndev, bbit)
        hi_per_chunk = (1 << (nloc - 1 - t)) // chunks

        def combine(i, own, rv):
            shape = (2, hi_per_chunk, 2, 1 << t)
            v = own.reshape(shape)
            pv = jnp.flip(rv.reshape(shape), axis=2)
            ktv = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 2, 1), 2)
            w1, w2 = _pair_channel_weights(kind, p, ktv, btv, dt)
            return (v * w1 + pv * w2).reshape(own.shape)

        return exchange_pipelined(local, perm, combine, chunks=chunks)

    return shard_map(
        kernel, mesh=mesh, in_specs=(P(None, AMP_AXIS), P()),
        out_specs=P(None, AMP_AXIS),
    )(amps, jnp.asarray(prob, dt))


def _apply_1q_mesh_bit(local, m, bit: int, ndev: int, chunks: int = 1):
    """Dense 1q gate on mesh-coordinate bit ``bit`` INSIDE a shard_map body:
    one chunk-pipelined full-shard ppermute + fused elementwise combine —
    the apply_matrix_1q_sharded kernel body factored out so scan-based
    composites (Trotter, PauliSum expectation) can apply rotation layers
    to sharded qubits with the same exchange pattern the reference's
    distributed compactUnitary uses (QuEST_cpu_distributed.c:854-928).
    ``m`` may be a TRACED (2, 2, 2) SoA matrix (e.g. indexed by a scanned
    Pauli code): an identity simply combines with b-coefficients of zero —
    the ppermute still happens, matching the reference, whose distributed
    basis rotations also exchange regardless of the rotation angle."""
    idx = lax.axis_index(AMP_AXIS)
    mybit = (idx >> bit) & 1
    a_re, a_im, b_re, b_im = _shard_coeffs(m, mybit)
    return exchange_pipelined(
        local, _hypercube_perm(ndev, bit),
        lambda i, own, rv: cplx.cmul(own, a_re, a_im) + cplx.cmul(rv, b_re, b_im),
        chunks=chunks)


def _split_parity_mask(zlo, zhi, nloc: int, r: int):
    """Split TRACED uint32 z-mask halves over global state bits (lo =
    bits [0,31), hi = bits [31,62) — ops/paulis.py convention) at the
    static local/shard boundary ``nloc``: returns (local_lo, local_hi,
    shard_mask) where shard_mask bit j corresponds to global bit
    nloc + j.  Parity factorises over the split, so a global parity sign
    is the product of a per-shard scalar sign and the local sign."""
    from ..ops.paulis import _PAR_LO_BITS as _L

    if nloc <= _L:
        loc_lo = zlo & jnp.uint32((1 << nloc) - 1)
        loc_hi = jnp.uint32(0)
        sm = zlo >> nloc
        if nloc + r > _L:
            sm = sm | (zhi << (_L - nloc))
    else:
        loc_lo = zlo
        loc_hi = zhi & jnp.uint32((1 << (nloc - _L)) - 1)
        sm = zhi >> (nloc - _L)
    return loc_lo, loc_hi, sm & jnp.uint32((1 << r) - 1)


def _shard_parity_sign(shard_mask, dt):
    """(+1/-1) scalar sign of parity(shard_index & shard_mask)."""
    idx = lax.axis_index(AMP_AXIS).astype(jnp.uint32)
    odd = lax.population_count(idx & shard_mask) & jnp.uint32(1)
    return 1.0 - 2.0 * odd.astype(dt)


def _parity_phase_sharded(local, theta, zlo, zhi, nloc: int, r: int):
    """exp(-i theta/2 (-1)^parity(global_idx & zmask)) per shard — the
    sharded form of ops/paulis._parity_phase_mask: the global parity sign
    is the local-index sign times a per-shard scalar."""
    from ..ops import paulis as _paulis

    loc_lo, loc_hi, sm = _split_parity_mask(zlo, zhi, nloc, r)
    s_loc = _paulis._parity_sign_dynamic(loc_lo, loc_hi, nloc, local.dtype)
    s_sh = _shard_parity_sign(sm, local.dtype)
    ang = -0.5 * theta
    return cplx.cmul(local, jnp.cos(ang), jnp.sin(ang) * s_sh * s_loc)


def _split_flip_mask(codes, nq: int, offset: int, nloc: int, r: int):
    """TRACED X|Y flip mask of a Pauli-code row acting on qubits
    [offset, offset+nq), split at the static local/shard boundary:
    (fm_lo, fm_hi) over the LOCAL bits — the row/lane split of
    ops/paulis._flip_gather at _GATHER_LO_BITS — plus the mesh-coordinate
    flip mask (bit j = global bit nloc + j), which selects the static
    ppermute branch in _mesh_flip_gather."""
    from ..ops import paulis as _paulis

    lo = min(_paulis._GATHER_LO_BITS, nloc)
    fm_lo = jnp.uint32(0)
    fm_hi = jnp.uint32(0)
    sfm = jnp.uint32(0)
    for q in range(nq):
        c = codes[q]
        fbit = ((c == _paulis.PAULI_X) | (c == _paulis.PAULI_Y)) \
            .astype(jnp.uint32)
        pos = q + offset
        if pos < lo:
            fm_lo = fm_lo | (fbit << pos)
        elif pos < nloc:
            fm_hi = fm_hi | (fbit << (pos - lo))
        else:
            sfm = sfm | (fbit << (pos - nloc))
    return fm_lo, fm_hi, sfm


def _mesh_flip_gather(local, fm_lo, fm_hi, sfm, nloc: int, ndev: int):
    """psi[global_idx ^ fm] restricted to this shard, with a TRACED flip
    mask whose mesh-coordinate part ``sfm`` cannot ride a static
    ppermute directly: lax.switch over the 2^r possible mesh-flip masks,
    each branch ONE composed static XOR ppermute (branch 0 = identity),
    composed with the local split-axis gather.  r <= 4 keeps the branch
    count <= 16 and the whole term is ONE compiled body — all shards
    take the same branch (``sfm`` derives from the replicated code row),
    so the collective inside the conditional is uniform SPMD."""
    from ..ops import paulis as _paulis

    def _branch(k):
        if k == 0:
            return lambda x: x
        perm = [(i, i ^ k) for i in range(ndev)]
        return lambda x, _p=perm: lax.ppermute(x, AMP_AXIS, _p)

    recv = lax.switch(sfm.astype(jnp.int32),
                      [_branch(k) for k in range(ndev)], local)
    return _paulis._flip_gather(recv, fm_lo, fm_hi, nloc)


def _apply_pauli_sharded(local, codes, nq: int, offset: int, nloc: int,
                         r: int, ndev: int, conj: bool):
    """(P psi) on this shard's slab + the all-identity flag — the direct
    split-axis-gather term body (ops/paulis._apply_pauli_traced) lifted
    into a shard_map kernel: the flip permutation factors into a mesh-bit
    XOR (one composed static ppermute via _mesh_flip_gather) times a
    local XOR gather, and the parity sign into a per-shard scalar times
    the local sign vector (both exact +-1, so the result is bit-identical
    to the unsharded body on the gathered state)."""
    from ..ops import paulis as _paulis

    dt = local.dtype
    n = nloc + r
    fm_lo, fm_hi, sfm = _split_flip_mask(codes, nq, offset, nloc, r)
    # parity mask / Y count over GLOBAL bits (the flip split above is
    # what differs from the unsharded _direct_masks)
    _, _, zlo, zhi, ny = _paulis._direct_masks(codes, nq, offset, n)
    loc_lo, loc_hi, sm = _split_parity_mask(zlo, zhi, nloc, r)
    s = _shard_parity_sign(sm, dt) \
        * _paulis._parity_sign_dynamic(loc_lo, loc_hi, nloc, dt)
    c_re, c_im = _paulis._iexp_factor(ny, dt)
    if conj:
        c_im = -c_im
    pv = _mesh_flip_gather(local, fm_lo, fm_hi, sfm, nloc, ndev)
    pr = s * (c_re * pv[0] - c_im * pv[1])
    pi = s * (c_re * pv[1] + c_im * pv[0])
    return jnp.stack([pr, pi]), (fm_lo | fm_hi | sfm | zlo | zhi) == 0


def _direct_rotation_sharded(local, codes, ang, nq: int, offset: int,
                             nloc: int, r: int, ndev: int, conj: bool):
    """e^{-i ang/2 P} psi on this shard in ONE (possibly exchanged)
    gather + fused combine — the sharded form of
    ops/paulis._direct_rotation, closing the one-kernel-set performance
    gap (~8x) the rotate/phase/unrotate conjugation body left on meshes
    (VERDICT round 5 item (a))."""
    dt = local.dtype
    pv, is_identity = _apply_pauli_sharded(local, codes, nq, offset, nloc,
                                           r, ndev, conj)
    theta = jnp.where(is_identity, jnp.asarray(0.0, dt), ang)
    co = jnp.cos(0.5 * theta)
    si = jnp.sin(0.5 * theta)
    return jnp.stack([co * local[0] + si * pv[1],
                      co * local[1] - si * pv[0]])


def trotter_scan_sharded(amps, codes_seq, angles, *, mesh: Mesh,
                         num_qubits: int, rep_qubits: int,
                         chunks: Optional[int] = None):
    """The whole Trotter gate stream on a SHARDED register as ONE
    shard_map(lax.scan) program — the same one-compiled-term-body design
    as ops/paulis.trotter_scan, with the per-term basis-rotation layers
    applying local qubits through the per-shard window kernels and
    mesh-coordinate qubits through chunk-pipelined ppermute exchange
    (_apply_1q_mesh_bit -> exchange_pipelined), and the parity phase
    split into local x per-shard-scalar signs.  This makes the
    one-kernel-set contract (QuEST_internal.h:63-292) hold for
    applyTrotterCircuit on real multi-chip meshes: the reference's
    agnostic_applyTrotterCircuit (QuEST_common.c:752-834) likewise rides
    the same distributed kernels.

    Term body: the DIRECT Pauli rotation (one mesh-flip ppermute branch
    + local split-axis XOR gather + fused combine, _direct_rotation_
    sharded) whenever the shard-local space fits the gather's int32
    invariant — at most 1 composed ppermute per rotation (2 per term for
    a density matrix: ket + bra twin).  Beyond _DIRECT_MAX_N local bits
    the rotate/phase/unrotate conjugation body with its 2*r*C chunked
    ppermutes per term remains as the fallback."""
    from ..ops import paulis as _paulis

    r = num_shard_bits(mesh)
    nloc = num_qubits - r
    direct = nloc <= _paulis._DIRECT_MAX_N
    if chunks is None:
        chunks = exchange_chunks(_shard_payload_bytes(amps, mesh))
    nterms = int(codes_seq.shape[0])
    if direct:
        chunks = 1  # the switch branch exchange is monolithic
        nex = (2 if num_qubits == 2 * rep_qubits else 1) * nterms
    else:
        nex = 2 * r * nterms
    if nex:
        _record_exchange_tiers(
            amps, "trotter",
            _sweep_exchange_tiers(nex, r, _shard_payload_bytes(amps, mesh),
                                  mesh_topology(mesh), direct), chunks)
    return _trotter_scan_sharded(
        amps, codes_seq, angles, mesh=mesh, num_qubits=num_qubits,
        rep_qubits=rep_qubits, chunks=int(chunks))


@partial(jax.jit,
         static_argnames=("mesh", "num_qubits", "rep_qubits", "chunks"),
         donate_argnums=0)
def _trotter_scan_sharded(amps, codes_seq, angles, *, mesh: Mesh,
                          num_qubits: int, rep_qubits: int, chunks: int):
    from ..ops import paulis as _paulis

    n, nq = num_qubits, rep_qubits
    ndev = amp_axis_size(mesh)
    r = num_shard_bits(mesh)
    nloc = n - r
    dt = amps.dtype
    is_density = n == 2 * nq
    chunks = min(_pow2_floor(chunks), 1 << nloc)
    direct = nloc <= _paulis._DIRECT_MAX_N

    if direct:
        def body(carry, inp):
            codes, ang = inp
            ang = ang.astype(dt)
            carry = _direct_rotation_sharded(carry, codes, ang, nq, 0,
                                             nloc, r, ndev, conj=False)
            if is_density:
                carry = _direct_rotation_sharded(carry, codes, -ang, nq,
                                                 nq, nloc, r, ndev,
                                                 conj=True)
            return carry, None
    else:
        def layer(local, mats):
            local = _paulis._product_layer(local, mats[:nloc], nloc)
            for q in range(nloc, n):
                local = _apply_1q_mesh_bit(local, mats[q], q - nloc, ndev,
                                           chunks)
            return local

        body = _paulis.make_trotter_body(
            dt, nq, is_density, layer=layer,
            parity_phase=lambda carry, theta, zlo, zhi:
                _parity_phase_sharded(carry, theta, zlo, zhi, nloc, r),
        )

    def kernel(local, codes_seq, angles):
        out, _ = jax.lax.scan(body, local, (codes_seq, angles))
        return out

    return shard_map(
        kernel, mesh=mesh,
        in_specs=(P(None, AMP_AXIS), P(), P()),
        out_specs=P(None, AMP_AXIS), check_vma=False,
    )(amps, codes_seq, angles)


def expec_pauli_sum_scan_sharded(amps, codes_seq, coeffs, *, mesh: Mesh,
                                 num_qubits: int, quad: bool = False,
                                 chunks: Optional[int] = None):
    """Re <psi| sum_t c_t P_t |psi> on a SHARDED statevector as ONE
    shard_map(lax.scan) — the sharded form of
    ops/paulis.expec_pauli_sum_scan: per term, basis-rotate per shard
    (chunk-pipelined ppermute for sharded qubits), reduce the
    parity-signed norm locally with the shard-scalar sign factored out,
    and psum ONCE at the end (the reference's local-reduce +
    MPI_Allreduce, QuEST_cpu_distributed.c:35-51).

    Term body: the direct form Re <psi| P |psi> = sum_i (psi_r pr +
    psi_i pi) with (pr, pi) = P psi from ONE mesh-flip ppermute branch +
    local XOR gather (_apply_pauli_sharded) — at most 1 composed
    ppermute per term — whenever the shard-local space fits the gather;
    the rotate-layer fallback (r*C ppermutes per term) covers the rest."""
    from ..ops import paulis as _paulis

    r = num_shard_bits(mesh)
    nloc = num_qubits - r
    direct = nloc <= _paulis._DIRECT_MAX_N
    if chunks is None:
        chunks = exchange_chunks(_shard_payload_bytes(amps, mesh))
    nterms = int(codes_seq.shape[0])
    if direct:
        chunks = 1  # the switch branch exchange is monolithic
        nex = nterms
    else:
        nex = r * nterms
    if nex:
        _record_exchange_tiers(
            amps, "expec",
            _sweep_exchange_tiers(nex, r, _shard_payload_bytes(amps, mesh),
                                  mesh_topology(mesh), direct), chunks)
    return _expec_pauli_sum_scan_sharded(
        amps, codes_seq, coeffs, mesh=mesh, num_qubits=num_qubits,
        quad=quad, chunks=int(chunks))


@partial(jax.jit, static_argnames=("mesh", "num_qubits", "quad", "chunks"))
def _expec_pauli_sum_scan_sharded(amps, codes_seq, coeffs, *, mesh: Mesh,
                                  num_qubits: int, quad: bool, chunks: int):
    from ..ops import paulis as _paulis

    n = num_qubits
    ndev = amp_axis_size(mesh)
    r = num_shard_bits(mesh)
    nloc = n - r
    dt = amps.dtype
    chunks = min(_pow2_floor(chunks), 1 << nloc)
    direct = nloc <= _paulis._DIRECT_MAX_N

    def layer(local, mats):
        phi = _paulis._product_layer(local, mats[:nloc], nloc)
        for q in range(nloc, n):
            phi = _apply_1q_mesh_bit(phi, mats[q], q - nloc, ndev, chunks)
        return phi

    def signed_norm(phi, zlo, zhi):
        loc_lo, loc_hi, sm = _split_parity_mask(zlo, zhi, nloc, r)
        s = _paulis._parity_sign_dynamic(loc_lo, loc_hi, nloc, dt)
        s_sh = _shard_parity_sign(sm, dt)
        if quad:
            from ..ops import calculations as _calc
            return s_sh * _calc.quad_sum2(s * phi[0] * phi[0],
                                          s * phi[1] * phi[1])
        return s_sh * jnp.sum(s * (phi[0] * phi[0] + phi[1] * phi[1]))

    def kernel(local, codes_seq, coeffs):
        from ..ops import calculations as _calc
        if direct:
            def body(acc, inp):
                codes, coeff = inp
                pv, _ = _apply_pauli_sharded(local, codes, n, 0, nloc, r,
                                             ndev, conj=False)
                if quad:
                    v = _calc.quad_sum2(local[0] * pv[0], local[1] * pv[1])
                else:
                    v = jnp.sum(local[0] * pv[0] + local[1] * pv[1])
                v = coeff.astype(dt) * v
                return acc + v, v
        else:
            body = _paulis.make_expec_term_value(
                dt, n, layer=layer, signed_norm=signed_norm)(local)
        tot, vals = jax.lax.scan(body, jnp.zeros((), dt),
                                 (codes_seq, coeffs))
        if not quad:
            return lax.psum(tot, AMP_AXIS)
        # quad: per-shard double-double partials, then ONE all-gather of
        # the (T,) per-shard term values and a deterministic Neumaier
        # combine over the (T, ndev) grid — a plain psum would re-lose
        # cross-shard cancellation at f64 exactly where the reference's
        # MPI_Allreduce of long doubles would not
        # (QuEST_cpu_distributed.c:35-51).  The gathered payload is
        # T*ndev scalars — not a state gather.
        g = lax.all_gather(vals, AMP_AXIS)          # (ndev, T)
        return _calc.neumaier_sum(g.T.reshape(-1))

    return shard_map(
        kernel, mesh=mesh,
        in_specs=(P(None, AMP_AXIS), P(), P()),
        out_specs=P(), check_vma=False,
    )(amps, codes_seq, coeffs)


def mix_two_qubit_depol_sharded(amps, prob, *, mesh: Mesh, num_qubits: int,
                                qubit1: int, qubit2: int):
    """Explicit distributed two-qubit depolarising: the double-flip orbit
    sum S = (1 + F2)(1 + F1) rho computed with AT MOST 2 collectives
    (one ppermute per flip whose bra bit is a mesh-coordinate bit — the
    recursive-doubling trick makes the 4-partner sum cost 2 exchanges,
    where the reference's distributed algorithm is a 3-part
    pack-and-exchange, QuEST_cpu_distributed.c:553-852), then one fused
    elementwise combine (see ops/density.mix_two_qubit_depolarising for
    the block formula)."""
    nloc = 2 * num_qubits - num_shard_bits(mesh)
    t = mesh_topology(mesh)
    payload = _shard_payload_bytes(amps, mesh)
    parts = {"ici": [0, 0], "dcn": [0, 0]}
    for q in (qubit1, qubit2):
        b = q + num_qubits
        if b < nloc:
            continue  # double flip fully shard-local: no exchange
        xor_mask = 1 << (b - nloc)
        if q >= nloc:
            xor_mask |= 1 << (q - nloc)
        acc = parts[t.tier_of_mask(xor_mask)]
        acc[0] += 1
        acc[1] += payload
    if parts["ici"][0] or parts["dcn"][0]:
        _record_exchange_tiers(
            amps, "depol2", {k: tuple(v) for k, v in parts.items()}, 1)
    return _mix_two_qubit_depol_sharded(
        amps, prob, mesh=mesh, num_qubits=num_qubits, qubit1=qubit1,
        qubit2=qubit2)


@partial(jax.jit,
         static_argnames=("mesh", "num_qubits", "qubit1", "qubit2"),
         donate_argnums=0)
def _mix_two_qubit_depol_sharded(amps, prob, *, mesh: Mesh, num_qubits: int,
                                 qubit1: int, qubit2: int):
    nq = num_qubits
    nn = 2 * nq
    ndev = amp_axis_size(mesh)
    r = num_shard_bits(mesh)
    nloc = nn - r
    dt = amps.dtype
    t1, b1 = qubit1, qubit1 + nq
    t2, b2 = qubit2, qubit2 + nq
    from ..ops import kernels as K

    hi, lo = K._split2(nloc)

    def kernel(local, p):
        idx = lax.axis_index(AMP_AXIS)

        def dflip(x, t, b):
            # flip ket bit t AND bra bit b (t < b always: t < nq <= b)
            if b < nloc:
                return K._flip_bits_flat(
                    x.reshape(2, -1), nloc, (t, b)).reshape(x.shape)
            if t < nloc:
                perm = _hypercube_perm(ndev, b - nloc)
                recv = lax.ppermute(x, AMP_AXIS, perm)
                return K._flip_bits_flat(
                    recv.reshape(2, -1), nloc, (t,)).reshape(x.shape)
            perm = [(i, i ^ (1 << (t - nloc)) ^ (1 << (b - nloc)))
                    for i in range(ndev)]
            return lax.ppermute(x, AMP_AXIS, perm)

        s = local + dflip(local, t1, b1)
        s = s + dflip(s, t2, b2)

        def bitval(pos):
            if pos < nloc:
                return K.bit_2d(nloc, pos).astype(dt)
            return ((idx >> (pos - nloc)) & 1).astype(dt)

        def same(t, b):
            d = bitval(t) - bitval(b)
            return 1 - d * d

        block = same(t1, b1) * same(t2, b2)     # scalar/2-d broadcast mix
        c1 = 1 - 16 * p / 15
        c2 = 4 * p / 15
        v = local.reshape(2, 1 << hi, 1 << lo)
        sv = s.reshape(2, 1 << hi, 1 << lo)
        out = v * c1 + sv * jnp.broadcast_to(
            c2 * block, (1 << hi, 1 << lo))[None]
        return out.reshape(local.shape)

    return shard_map(
        kernel, mesh=mesh, in_specs=(P(None, AMP_AXIS), P()),
        out_specs=P(None, AMP_AXIS),
    )(amps, jnp.asarray(prob, dt))


@partial(jax.jit, static_argnames=("mesh", "num_qubits"), donate_argnums=0)
def apply_diag_op_density_sharded(amps, op_re, op_im, *, mesh: Mesh,
                                  num_qubits: int):
    """applyDiagonalOp on a SHARDED rho: D.rho scales element (row, col)
    by D[row]; rows live in the LOW n index bits, so every shard needs
    the whole operator — replicate the (small) op with exactly TWO
    explicit all_gathers (re, im), never touching the state's sharding:
    the reference's copyDiagOpIntoMatrixPairState ring-of-broadcasts
    (QuEST_cpu_distributed.c:1548-1587)."""
    nq = num_qubits
    nn = 2 * nq
    r = num_shard_bits(mesh)
    nloc = nn - r
    assert nloc >= nq, "op rows must be shard-local (r <= num_qubits)"
    dt = amps.dtype

    def kernel(local, re, im):
        re_full = lax.all_gather(re, AMP_AXIS, axis=0, tiled=True)
        im_full = lax.all_gather(im, AMP_AXIS, axis=0, tiled=True)
        v = local.reshape(2, 1 << (nloc - nq), 1 << nq)
        out = cplx.cmul(v, re_full.astype(dt)[None], im_full.astype(dt)[None])
        return out.reshape(local.shape)

    return shard_map(
        kernel, mesh=mesh,
        in_specs=(P(None, AMP_AXIS), P(AMP_AXIS), P(AMP_AXIS)),
        out_specs=P(None, AMP_AXIS), check_vma=False,
    )(amps, op_re, op_im)


def _ladder_phase_chunks(nbits: int, t_eff: int, sgn: float, dt):
    """Host tables factorizing exp(sgn*i*pi*li / 2^t_eff) over 7-bit chunks
    of the ``nbits``-bit index li (an exponential of a sum of per-bit
    contributions — cf. kernels.apply_qft_ladder's table factorization).
    Returns [(width, (2, 2^width) table), ...] low chunk first."""
    import numpy as np

    out = []
    p = 0
    while p < nbits:
        w = min(7, nbits - p)
        j = np.arange(1 << w, dtype=np.float64)
        ang = sgn * np.pi * (j * (1 << p)) / (1 << t_eff)
        out.append((w, np.stack([np.cos(ang), np.sin(ang)]).astype(dt)))
        p += w
    return out


def _apply_local_phase(local, chunks, skip: int = 0):
    """Elementwise multiply by the factored phase over the local index
    bits [skip, nloc) — ``skip`` > 0 leaves a trailing untouched 2^skip
    axis (partial-run ladders whose low end starts above bit 0)."""
    widths = [w for w, _ in chunks]
    shape = [2] + [1 << w for w in reversed(widths)]
    if skip:
        shape.append(1 << skip)
    v = local.reshape(shape)
    ndim = len(shape) - 1
    off = 1 if skip else 0
    for ci, (w, tab) in enumerate(chunks):
        bshape = [1] * ndim
        bshape[ndim - 1 - ci - off] = 1 << w
        v = cplx.cmul(v, jnp.asarray(tab[0]).reshape(bshape),
                      jnp.asarray(tab[1]).reshape(bshape))
    return v.reshape(local.shape)


def _qft_mesh_layer(local, idx, t: int, base: int, nloc: int, ndev: int,
                    sgn: float, dt):
    """One mesh-bit QFT layer (target t >= nloc) inside a shard_map body:
    full-shard ppermute H-exchange (the reference's pairwise exchange,
    QuEST_cpu_distributed.c:854-928) + the controlled-phase ladder over
    run bits [base, t), its phase split into a per-shard scalar (the
    sharded ladder bits) times factored local tables.  Shared by
    fused_qft_sharded (base = 0) and fused_qft_runs_sharded (any base)."""
    bit = t - nloc
    mybit = (idx >> bit) & 1
    recv = lax.ppermute(local, AMP_AXIS, _hypercube_perm(ndev, bit))
    s = jnp.where(mybit == 0, jnp.asarray(1.0, dt), jnp.asarray(-1.0, dt))
    comb = (local * s + recv) * jnp.asarray(0.7071067811865476, dt)
    sb = max(base - nloc, 0)       # shard-bit start of the ladder
    width = bit - sb
    ph = comb
    if base < nloc:
        chunks = _ladder_phase_chunks(nloc - base, t - base, sgn, dt)
        ph = _apply_local_phase(ph, chunks, skip=base)
    if width:
        mlow = ((idx >> sb) & ((1 << width) - 1)).astype(dt)
        theta = jnp.asarray(sgn * math.pi, dt) * mlow / (1 << width)
        ph = cplx.cmul(ph, jnp.cos(theta), jnp.sin(theta))
    return jnp.where(mybit == 1, ph, comb)


def fused_qft_sharded(amps, *, mesh: Mesh, num_qubits: int,
                      conj: bool = False):
    """Full-register QFT on a SHARDED statevector, one shard_map end to
    end — the explicit-collective redesign of the reference's distributed
    QFT (agnostic_applyQFT, QuEST_common.c:836-898, whose H sweeps ride
    exchangeStateVectors):

      * mesh-bit layers (target >= nloc): ONE full-shard ``ppermute``
        (the reference's pairwise exchange) + a fused elementwise
        H-combine x controlled-phase ladder, with the phase split into a
        per-shard scalar (the sharded index part) times factored local
        tables;
      * local layers: the same Pallas ladder kernels every backend uses
        (QuEST_internal.h:63-292 one-kernel-set contract), running
        per-shard inside the shard_map;
      * the final bit reversal: two LOCAL reversals + ONE
        ``lax.all_to_all`` — the lanes<->mesh-bits block swap
        rev[0,n) = rev[0,r) o all_to_all o (rev[0,r) x rev[r,nloc)).

    Collectives: r ppermutes + 1 all_to_all, all riding ICI.
    """
    r = num_shard_bits(mesh)
    if r:
        payload = _shard_payload_bytes(amps, mesh)
        ndev = amp_axis_size(mesh)
        t = mesh_topology(mesh)
        # r full-shard H-exchanges (one per mesh bit, so the tier split
        # is exactly per-bit) + the reversal all_to_all, which moves
        # every block but the diagonal one: (ndev-1)/ndev of a shard —
        # ndev-chips of those blocks cross hosts
        a2a_total = (payload * (ndev - 1)) // ndev
        a2a_dcn = (payload * (ndev - t.chips)) // ndev
        multi = t.dcn_bits > 0
        _record_exchange_tiers(
            amps, "qft",
            {"ici": (t.ici_bits + (0 if multi else 1),
                     t.ici_bits * payload + (a2a_total - a2a_dcn)),
             "dcn": (t.dcn_bits + (1 if multi else 0),
                     t.dcn_bits * payload + a2a_dcn)}, 1)
    return _fused_qft_sharded(amps, mesh=mesh, num_qubits=num_qubits,
                              conj=conj)


@partial(jax.jit, static_argnames=("mesh", "num_qubits", "conj"),
         donate_argnums=0)
def _fused_qft_sharded(amps, *, mesh: Mesh, num_qubits: int,
                       conj: bool = False):
    from ..ops import fused as _fused

    n = num_qubits
    ndev = amp_axis_size(mesh)
    r = num_shard_bits(mesh)
    nloc = n - r
    dt = amps.dtype
    sgn = -1.0 if conj else 1.0
    use_multilayer = (_fused.qft_multilayer_enabled(dt)
                      and nloc >= _fused.CLUSTER_QUBITS + 1)
    radix = _fused._qft_radix()

    def kernel(local):
        idx = lax.axis_index(AMP_AXIS)
        # mesh-bit layers, high to low (shared helper — see _qft_mesh_layer)
        for t in range(n - 1, nloc - 1, -1):
            local = _qft_mesh_layer(local, idx, t, 0, nloc, ndev, sgn, dt)
        # local layers, per shard: multilayer (radix-2^k) passes when the
        # shard is big enough — the SAME grouping helper the unsharded
        # path uses (fused.apply_qft_multilayer_ladders) — else per-layer
        # Pallas ladders for t >= 7 and the XLA elementwise ladder below
        # (a dense window-pass fold here can overflow scoped VMEM when
        # XLA promotes a small shard into VMEM inside this one big
        # program).  NB use_multilayer/radix resolve at TRACE time (the
        # env toggles are frozen into any enclosing jit's cache).
        if use_multilayer:
            local = _fused.apply_qft_multilayer_ladders(
                local, num_qubits=nloc, conj=conj, t_top=nloc - 1,
                radix=radix)
            low_start = _fused.LANE_QUBITS - 1
        else:
            low_start = nloc - 1
        for t in range(low_start, -1, -1):
            local = kernels.apply_qft_ladder(
                local, num_qubits=nloc, target=t, conj=conj)
        # bit reversal: L1 local, all_to_all block swap, L2 local
        # (L1 = rev[0,r) x rev[r,nloc); perm[q] = input qubit at output q)
        if r:
            perm1 = tuple([r - 1 - q for q in range(r)]
                          + [r + (nloc - 1 - q) for q in range(r, nloc)])
            local = kernels.permute_qubits(local, num_qubits=nloc,
                                           perm=perm1)
            v = local.reshape(2, 1 << (nloc - r), 1 << r)
            v = lax.all_to_all(v, AMP_AXIS, split_axis=2, concat_axis=2,
                               tiled=False)
            local = v.reshape(2, -1)
            perm2 = tuple([r - 1 - q for q in range(r)]
                          + list(range(r, nloc)))
            local = kernels.permute_qubits(local, num_qubits=nloc,
                                           perm=perm2)
        else:
            perm = tuple(nloc - 1 - q for q in range(nloc))
            local = kernels.permute_qubits(local, num_qubits=nloc, perm=perm)
        return local

    return shard_map(
        kernel, mesh=mesh, in_specs=P(None, AMP_AXIS),
        out_specs=P(None, AMP_AXIS), check_vma=False,
    )(amps)


def _reverse_run_sharded(local, base: int, count: int, nloc: int,
                         ndev: int):
    """Bit reversal of the contiguous run [base, base+count) of a sharded
    register, inside a shard_map body.  The reversal is a set of disjoint
    bit swaps (base+i <-> base+count-1-i); each class costs:

      * local-local  : folded into ONE per-shard axis permutation;
      * mesh-mesh    : folded into ONE composed full-shard ppermute
        (a pure shard-index permutation);
      * local-mesh   : one half-shard ppermute each (the swap_sharded
        exchange: only the mismatched half moves,
        QuEST_cpu_distributed.c:1397-1436).
    """
    top = base + count
    perm_local = list(range(nloc))
    mesh_pairs = []
    mixed = []
    for i in range(count // 2):
        p, q = base + i, top - 1 - i
        if q < nloc:
            perm_local[p], perm_local[q] = perm_local[q], perm_local[p]
        elif p >= nloc:
            mesh_pairs.append((p - nloc, q - nloc))
        else:
            mixed.append((p, q - nloc))
    if perm_local != list(range(nloc)):
        local = kernels.permute_qubits(local, num_qubits=nloc,
                                       perm=tuple(perm_local))
    if mesh_pairs:
        def sig(i):
            j = i
            for a, b in mesh_pairs:
                ba, bb = (i >> a) & 1, (i >> b) & 1
                j = (j & ~((1 << a) | (1 << b))) | (ba << b) | (bb << a)
            return j

        local = lax.ppermute(local, AMP_AXIS,
                             [(i, sig(i)) for i in range(ndev)])
    for lb, mb in mixed:
        # QFT bit reversals stay monolithic (chunks=1): the reversal is a
        # pure relabeling with no combine math to hide the transfer behind
        local = _swap_halves_in_shard(local, lb, mb, nloc, ndev)
    return local


def qft_runs_exchange_model(runs, nloc: int, itemsize: int = 8):
    """(collective count, per-shard ICI bytes) of fused_qft_runs_sharded
    for ``runs`` — the cost-model companion of circuit.remap_exchange_bytes:
    per run reaching mesh bits, one full-shard ppermute per mesh-bit
    layer, one half-shard exchange per mixed reversal pair, and one
    composed full-shard ppermute when any mesh<->mesh reversal pairs
    fold (matching _reverse_run_sharded's class folding).  Fully-local
    runs cost zero."""
    shard = 2 * (1 << nloc) * itemsize
    count = 0
    nbytes = 0
    for base, cnt, _conj in runs:
        top = base + cnt
        layers = max(0, top - max(base, nloc))
        count += layers
        nbytes += layers * shard
        mixed = mesh_pairs = 0
        for i in range(cnt // 2):
            p, q = base + i, top - 1 - i
            if q < nloc:
                continue
            if p >= nloc:
                mesh_pairs += 1
            else:
                mixed += 1
        if mesh_pairs:
            count += 1
            nbytes += shard
        count += mixed
        nbytes += mixed * (shard // 2)
    return count, nbytes


def qft_runs_exchange_tiers(runs, nloc: int, itemsize: int = 8,
                            topology: Optional["topo.Topology"] = None):
    """Tier split of qft_runs_exchange_model: each mesh-bit layer and
    each mixed reversal pair carries a specific mesh bit (its tier is
    that bit's), the composed mesh<->mesh reversal ppermute is DCN iff
    it moves a host bit.  Sums exactly to the flat model."""
    t = topology
    shard = 2 * (1 << nloc) * itemsize
    parts = {"ici": [0, 0], "dcn": [0, 0]}

    def tier_of(mesh_bit):
        return t.tier_of_bit(mesh_bit) if t is not None else "ici"

    for base, cnt, _conj in runs:
        top = base + cnt
        for q in range(max(base, nloc), top):      # mesh-bit layers
            acc = parts[tier_of(q - nloc)]
            acc[0] += 1
            acc[1] += shard
        mesh_mask = 0
        for i in range(cnt // 2):
            p, q = base + i, top - 1 - i
            if q < nloc:
                continue
            if p >= nloc:
                mesh_mask |= (1 << (p - nloc)) | (1 << (q - nloc))
            else:
                acc = parts[tier_of(q - nloc)]     # mixed half-shard swap
                acc[0] += 1
                acc[1] += shard // 2
        if mesh_mask:
            tier = (t.tier_of_mask(mesh_mask) if t is not None else "ici")
            parts[tier][0] += 1
            parts[tier][1] += shard
    return {k: (v[0], v[1]) for k, v in parts.items()}


def fused_qft_runs_sharded(amps, *, mesh: Mesh, num_qubits: int,
                           runs: Tuple[Tuple[int, int, bool], ...]):
    """QFT over contiguous qubit runs [(base, count, conj), ...] of a
    SHARDED register, one shard_map end to end — the general-run
    companion of fused_qft_sharded covering partial-register QFTs and the
    density-matrix twin (runs = ket run + conjugated bra run), so
    applyQFT / applyFullQFT run the SAME fused kernel set on real
    multi-chip meshes instead of falling back to the layered path
    (one-kernel-set contract, QuEST_internal.h:63-292; reference
    agnostic_applyQFT, QuEST_common.c:836-898).

    Per run: a FULLY-LOCAL run executes circuit.fused_qft per shard —
    identical multilayer/window passes to the unsharded path; a run
    reaching mesh-coordinate bits runs ppermute H-exchange layers
    (one full-shard ppermute each, phase split into per-shard scalar x
    factored local tables), per-shard ladder kernels for its local
    layers, and the mixed bit reversal of _reverse_run_sharded.

    Collectives for a run with s sharded bits: s ppermutes (layers) +
    at most s reversal ppermutes; fully-local runs cost zero."""
    nloc = num_qubits - num_shard_bits(mesh)
    cnt, _nbytes = qft_runs_exchange_model(runs, nloc, amps.dtype.itemsize)
    if cnt:
        _record_exchange_tiers(
            amps, "qft_runs",
            qft_runs_exchange_tiers(runs, nloc, amps.dtype.itemsize,
                                    mesh_topology(mesh)), 1)
    return _fused_qft_runs_sharded(amps, mesh=mesh, num_qubits=num_qubits,
                                   runs=tuple(runs))


@partial(jax.jit, static_argnames=("mesh", "num_qubits", "runs"),
         donate_argnums=0)
def _fused_qft_runs_sharded(amps, *, mesh: Mesh, num_qubits: int,
                            runs: Tuple[Tuple[int, int, bool], ...]):
    from .. import circuit as CIRC

    n = num_qubits
    ndev = amp_axis_size(mesh)
    r = num_shard_bits(mesh)
    nloc = n - r
    dt = amps.dtype

    def kernel(local):
        idx = lax.axis_index(AMP_AXIS)
        for base, count, conj in runs:
            top = base + count
            sgn = -1.0 if conj else 1.0
            if top <= nloc and nloc >= CIRC.WINDOW:
                # fully-local run on a window-sized shard: the unsharded
                # fused kernels per shard (shards below window size use
                # the per-layer ladder path below instead)
                local = CIRC.fused_qft(local, nloc, base, count,
                                       shifts=(0,), conj_first=conj)
                continue
            # mesh-bit layers, top down (shared helper, _qft_mesh_layer)
            for t in range(top - 1, max(base, nloc) - 1, -1):
                local = _qft_mesh_layer(local, idx, t, base, nloc, ndev,
                                        sgn, dt)
            # local layers per shard (same ladder kernels as unsharded)
            for t in range(min(top, nloc) - 1, base - 1, -1):
                local = kernels.apply_qft_ladder(
                    local, num_qubits=nloc, target=t, base=base, conj=conj)
            local = _reverse_run_sharded(local, base, count, nloc, ndev)
        return local

    return shard_map(
        kernel, mesh=mesh, in_specs=P(None, AMP_AXIS),
        out_specs=P(None, AMP_AXIS), check_vma=False,
    )(amps)


# ---------------------------------------------------------------------------
# Lazy logical->physical qubit remapping (communication avoidance)
#
# mpiQulacs (arXiv:2203.16044) and qHiPSTER (arXiv:1601.07195) both amortize
# the distributed simulator's dominant cost — relocalizing sharded target
# qubits — with circuit-level qubit reordering: the state is kept in a
# PERMUTED physical order, later gate targets are rewritten through the live
# permutation, and data only moves when an upcoming window of gates needs a
# different set of local qubits.  The kernels below implement the batched
# exchange realizing one permutation step; quest_tpu.qureg carries the
# logical->physical map (Qureg._perm) and rematerializes canonical order
# lazily on the first state read.
# ---------------------------------------------------------------------------


def decompose_sigma(sigma: Tuple[int, ...], nloc: int, r: int,
                    itemsize: Optional[int] = None):
    """Split a physical bit permutation (``sigma[p]`` = destination
    position of the bit currently at physical position ``p``) into the
    cheapest exchange classes — the class folding of _reverse_run_sharded
    generalized from bit reversals to arbitrary permutations:

      * mixed  : one (local_bit, mesh_bit) transposition per local<->mesh
        boundary crossing, each ONE half-shard ppermute (the swap_sharded
        exchange — only the mismatched half moves);
      * local  : everything left on the local side, ONE per-shard axis
        permutation (a permute_qubits arg: out bit q <- in bit perm[q]);
      * mesh   : everything left on the mesh side, ONE composed full-shard
        ppermute (``mesh_tau[b]`` = destination mesh bit of coordinate
        bit b).

    Where the chip cannot hold the axis permutation's second copy of
    the shard (route_residue: 8 GiB shards of ``itemsize``-byte reals at
    32 qubits over four v5e chips), a local residue that leaves the bits
    inside a (128, 128) block in place is routed through mesh bit 0
    instead, each of its cycles of k bits as k + 1 more mixed swaps,
    which run in place (_swap_halves_canonical).  Those swaps share
    their mesh bit, so the mixed list is an ORDERED sequence.

    Returns (mixed, local_perm | None, mesh_tau | None), applied in that
    order."""
    n = nloc + r
    cur = list(sigma)
    assert sorted(cur) == list(range(n)), sigma
    mixed = []
    from_local = [p for p in range(nloc) if cur[p] >= nloc]
    from_mesh = {p for p in range(nloc, n) if cur[p] < nloc}
    assert len(from_local) == len(from_mesh)
    for l in from_local:
        # pair each crossing local bit with its DESTINATION mesh slot when
        # that slot itself crosses down — a transposition sigma (the window
        # planner's output) then decomposes into pure mixed swaps with no
        # residual composed mesh permute
        m = cur[l] if cur[l] in from_mesh else min(from_mesh)
        from_mesh.discard(m)
        mixed.append((l, m - nloc))
        cur[l], cur[m] = cur[m], cur[l]
    if (r and nloc > _BLOCK_BITS and cur[:nloc] != list(range(nloc))
            and cur[:_BLOCK_BITS] == list(range(_BLOCK_BITS))
            and route_residue(nloc, itemsize)):
        for start in range(_BLOCK_BITS, nloc):
            if cur[start] == start:
                continue
            # the cycle start -> cur[start] -> ... through the slot of
            # mesh bit 0: each swap lands the slot's bit at its
            # destination and picks up the next one
            cycle = [start]
            while cur[cycle[-1]] != start:
                cycle.append(cur[cycle[-1]])
            for l in cycle + [start]:
                mixed.append((l, 0))
                cur[l], cur[nloc] = cur[nloc], cur[l]
    local_perm = None
    if cur[:nloc] != list(range(nloc)):
        inv = [0] * nloc
        for p in range(nloc):
            inv[cur[p]] = p
        local_perm = tuple(inv)
    mesh_tau = None
    tau = [cur[nloc + b] - nloc for b in range(r)]
    if tau != list(range(r)):
        mesh_tau = tuple(tau)
    return tuple(mixed), local_perm, mesh_tau


def remap_exchange_count(sigma: Tuple[int, ...], nloc: int, r: int,
                         itemsize: Optional[int] = None) -> int:
    """Number of exchange programs one remap of ``sigma`` dispatches —
    one half-shard ppermute per mixed transposition plus one composed
    full-shard ppermute when a residual mesh permute remains.  This is
    the ``exchanges_total`` increment remap_sharded / the fusion drain
    record per (unbatched) remap; introspect.predict_window_exchanges
    re-derives drain telemetry from it (companion of
    circuit.remap_exchange_bytes on the count axis)."""
    mixed, _local_perm, mesh_tau = decompose_sigma(tuple(sigma), nloc, r,
                                                   itemsize)
    return len(mixed) + (1 if mesh_tau is not None else 0)


def remap_exchange_tiers(sigma: Tuple[int, ...], nloc: int, r: int,
                         itemsize: int = 8,
                         topology: Optional["topo.Topology"] = None):
    """Per-tier (count, per-shard bytes) split of one remap's exchange
    program — circuit.remap_exchange_bytes refined by interconnect: each
    mixed half-shard swap carries exactly its mesh bit's tier; the
    composed full-shard ppermute is DCN iff it moves any host bit.
    Tier sums equal the flat (remap_exchange_count,
    remap_exchange_bytes) pair exactly."""
    t = topology if topology is not None else topo.resolve(1 << r)
    mixed, _local_perm, mesh_tau = decompose_sigma(tuple(sigma), nloc, r,
                                                   itemsize)
    shard = 2 * (1 << nloc) * itemsize
    parts = {"ici": [0, 0], "dcn": [0, 0]}
    for _lb, mb in mixed:
        acc = parts[t.tier_of_bit(mb)]
        acc[0] += 1
        acc[1] += shard // 2
    if mesh_tau is not None:
        moved = 0
        for b, dst in enumerate(mesh_tau):
            if b != dst:
                moved |= (1 << b) | (1 << dst)
        acc = parts[t.tier_of_mask(moved)]
        acc[0] += 1
        acc[1] += shard
    return {k: (v[0], v[1]) for k, v in parts.items()}


def remap_chunk_plan(nloc: int, itemsize: int = 8,
                     backend: Optional[str] = None) -> Tuple[int, int]:
    """The (half_shard_chunks, full_shard_chunks) pair the
    PIPELINE_MIN_BYTES policy resolves for one per-element shard of
    ``2 * 2^nloc * itemsize`` bytes — the default _remap_in_shard
    computes at trace time, exposed so the plan explainer can predict
    the pipeline split without tracing."""
    nbytes = 2 * (1 << nloc) * itemsize
    return (exchange_chunks(nbytes // 2, backend=backend),
            exchange_chunks(nbytes, backend=backend))


def _remap_in_shard(local, sigma: Tuple[int, ...], nloc: int, ndev: int,
                    chunks: Optional[Tuple[int, int]] = None):
    """Apply the physical bit permutation ``sigma`` INSIDE a shard_map
    body: the mixed half-shard swaps (chunk-pipelined), then one per-shard
    axis permutation, then one composed shard-index ppermute (chunked so
    its transient recv buffer is one chunk) — decompose_sigma.  Shared by
    the standalone remap_sharded program and the fusion drain's
    ("remap", sigma) parts.

    ``local`` is a flat (2, 2^nloc) shard or a canonical
    (2, 2^(nloc-14), 128, 128) one (qureg.device_amps_shape), whose
    mixed swaps run in place (_swap_halves_canonical).

    ``chunks``: (half_shard_chunks, full_shard_chunks); None resolves the
    per-op heuristic from the (static) per-shard payload size at trace
    time — the drain executor keys its compiled-program cache on
    exchange_config_key() so an env-override flip retraces."""
    r = int(math.log2(ndev))
    canonical = local.ndim == 4
    mixed, local_perm, mesh_tau = decompose_sigma(sigma, nloc, r,
                                                  local.dtype.itemsize)
    t = topo.resolve(ndev)
    if (t.dcn_bits and len(mixed) > 1
            and len({b for lm in mixed for b in (lm[0], nloc + lm[1])})
            == 2 * len(mixed)):
        # DCN-overlap schedule (§17 generalized, docs/design.md §25):
        # issue the slow cross-host half-shard swaps FIRST so XLA's
        # latency-hiding scheduler overlaps their transfers against the
        # subsequent intra-host swaps and the local permute.  Mixed
        # transpositions on disjoint (local, mesh) bit pairs compute the
        # identical state in any order; a routed residue's swaps share
        # their mesh bit and keep theirs.
        mixed = tuple(sorted(mixed, key=lambda lm: lm[1] < t.ici_bits))
    if chunks is None:
        chunks = remap_chunk_plan(nloc, local.dtype.itemsize)
    ch_half = min(_pow2_floor(chunks[0]), 1 << max(nloc - 1, 0))
    ch_full = min(_pow2_floor(chunks[1]), 1 << nloc)
    for lb, mb in mixed:
        if canonical:
            local = _swap_halves_canonical(local, lb, mb, ndev, ch_half)
        else:
            local = _swap_halves_in_shard(local, lb, mb, nloc, ndev,
                                          ch_half)
    if local_perm is not None:
        local = kernels.permute_qubits(local, num_qubits=nloc,
                                       perm=local_perm)
    if mesh_tau is not None:
        def dest(i):
            j = 0
            for b, t in enumerate(mesh_tau):
                j |= ((i >> b) & 1) << t
            return j

        local = exchange_pipelined(
            local, [(i, dest(i)) for i in range(ndev)],
            lambda i, own, rv: rv, chunks=ch_full,
            axis=1 if canonical else -1)
    return local


@sharded_contract(collectives={"collective-permute": 1},
                  max_exchange_bytes=1 << 9,
                  max_tier_bytes={"ici": 1 << 9, "dcn": 1 << 9})
def remap_sharded(amps, *, mesh: Mesh, num_qubits: int,
                  sigma: Tuple[int, ...],
                  chunks: Optional[Tuple[int, int]] = None):
    """ONE batched physical-bit permutation of a sharded register: at most
    (#local<->mesh crossings) chunk-pipelined half-shard exchanges + one
    per-shard axis permutation + one composed (chunked) full-shard
    ppermute, regardless of how many gates the window it serves contains;
    where the chip cannot hold the axis permutation's copy of the shard,
    k + 1 more half-shard exchanges per cycle of k local bits in its
    place (decompose_sigma, route_residue).
    This is the communication the window planner schedules ONCE per window
    where the reference pays two half-shard exchanges per sharded-target
    gate (QuEST_cpu_distributed.c:1447-1545)."""
    if chunks is None:
        nbytes = _shard_payload_bytes(amps, mesh)
        chunks = (exchange_chunks(nbytes // 2), exchange_chunks(nbytes))
    if _telemetry.enabled() and not isinstance(amps, jax.core.Tracer):
        r = num_shard_bits(mesh)
        nloc = num_qubits - r
        bw = int(amps.shape[0]) if amps.ndim == 3 else 1
        tiers = remap_exchange_tiers(tuple(sigma), nloc, r,
                                     amps.dtype.itemsize,
                                     mesh_topology(mesh))
        _record_exchange_tiers(
            amps, "remap",
            {k: (c * bw, b * bw) for k, (c, b) in tiers.items()},
            str(chunks))
    return guarded_dispatch(
        _remap_sharded, amps, op="remap", shards=amp_axis_size(mesh),
        mesh=mesh, num_qubits=num_qubits, sigma=tuple(sigma),
        chunks=(int(chunks[0]), int(chunks[1])))


@partial(jax.jit, static_argnames=("mesh", "num_qubits", "sigma", "chunks"),
         donate_argnums=0)
def _remap_sharded(amps, *, mesh: Mesh, num_qubits: int,
                   sigma: Tuple[int, ...], chunks: Tuple[int, int]):
    ndev = amp_axis_size(mesh)
    r = num_shard_bits(mesh)
    nloc = num_qubits - r
    # a (B, 2, 2^n) register bank (batch.BatchedQureg) remaps every batch
    # element with the SAME sigma — one vmap inside the shard_map kernel,
    # batch-outer/amps-inner, so the composed ppermute moves all elements'
    # shard slices in one exchange
    batched = amps.ndim == 3

    def kernel(local):
        if batched:
            return jax.vmap(
                lambda a: _remap_in_shard(a, sigma, nloc, ndev, chunks)
            )(local)
        return _remap_in_shard(local, sigma, nloc, ndev, chunks)

    spec = P(None, None, AMP_AXIS) if batched else P(None, AMP_AXIS)
    return shard_map(
        kernel, mesh=mesh, in_specs=spec,
        out_specs=spec, check_vma=False,
    )(amps)


def canonical_sigma(perm: Tuple[int, ...]) -> Tuple[int, ...]:
    """The physical permutation rematerializing canonical order from a
    live logical->physical ``perm`` (sigma = perm^-1: the bit at physical
    perm[q] returns to position q)."""
    sigma = [0] * len(perm)
    for q, p in enumerate(perm):
        sigma[p] = q
    return tuple(sigma)


def plan_window_remap(num_qubits: int, nloc: int, perm: Tuple[int, ...],
                      want_local, next_use=None, topology=None):
    """Choose the minimal-movement permutation making every logical qubit
    in ``want_local`` shard-local: qubits already local stay put; each
    sharded one swaps with the local slot whose resident logical qubit is
    needed FURTHEST in the future (``next_use``: logical qubit -> distance
    of its next use; absent = never used again, evicted first — the same
    lookahead policy as the paged planner's eviction choice).

    On a hierarchical topology (``topology``; default resolved from the
    mesh size via QT_TOPOLOGY) the planner is additionally TIER-aware:
    wanted qubits currently parked on DCN mesh bits are serviced first,
    so the coldest evictees (front of the eviction pool) land on the
    slow cross-host slots and the hotter ones stay on intra-host ICI
    axes — later windows that re-fetch them pay ICI, not DCN, bytes.
    The permutation itself is identical in shape (same number of mixed
    swaps), results are bit-identical; only WHERE evictees park changes.
    QT_TOPOLOGY_PLANNER=flat restores the flat ordering for A/B runs.

    Returns (sigma | None, new_perm): ``sigma`` is None when nothing
    moves; (None, None) when ``want_local`` exceeds the local capacity —
    the caller must split the window."""
    n = num_qubits
    perm = list(perm)
    want_local = sorted(set(want_local))
    if len(want_local) > nloc:
        return None, None
    inv = [0] * n
    for q, p in enumerate(perm):
        inv[p] = q
    need = [q for q in want_local if perm[q] >= nloc]
    if not need:
        return None, tuple(perm)
    if topology is None:
        topology = topo.resolve(1 << max(num_qubits - nloc, 0))
    if topo.hierarchical_enabled(topology):
        # DCN-resident wanted qubits first (highest mesh bit first within
        # the tier): they consume the coldest pool slots, which are the
        # ones later evictions would otherwise have to push cross-host.
        need.sort(key=lambda q: (perm[q] - nloc < topology.ici_bits,
                                 -(perm[q] - nloc)))
    wanted = set(want_local)
    pool = [p for p in range(nloc) if inv[p] not in wanted]
    assert len(pool) >= len(need)  # guaranteed by |want_local| <= nloc
    if next_use is None:
        next_use = {}
    # on a canonical shard, block slots first (remap_window_cap)
    block = _BLOCK_BITS if nloc >= _BLOCK_BITS else 0
    pool.sort(key=lambda p: (p >= block, next_use.get(inv[p], 1 << 60)),
              reverse=True)
    sigma = list(range(n))
    for q in need:
        p_high = perm[q]
        p_slot = pool.pop(0)
        q_evicted = inv[p_slot]
        sigma[p_slot], sigma[p_high] = p_high, p_slot
        perm[q], perm[q_evicted] = p_slot, p_high
        inv[p_slot], inv[p_high] = q, q_evicted
    return tuple(sigma), tuple(perm)


def plan_relocalization(
    num_qubits: int,
    nloc: int,
    targets: Tuple[int, ...],
    controls: Tuple[int, ...] = (),
    free_order=None,
):
    """Choose swap pairs pulling every sharded target down to a free local
    qubit (reference picks the lowest free qubit and patches the control
    mask on collision, QuEST_cpu_distributed.c:1508-1531; we instead exclude
    controls from the free pool so the mask never needs patching).

    ``free_order``: optional eviction-preference ordering of the local
    slots (coldest first) — under the lazy permutation the dispatch layer
    passes a least-recently-used ordering so a relocation never evicts the
    qubits the circuit is actively using (the ping-pong that would
    otherwise re-pay the exchange every alternation); default is the
    reference's lowest-first choice.

    Returns (swaps, new_targets), or (None, None) when there aren't enough
    free local qubits — the caller falls back to the GSPMD path (the
    reference instead *rejects* such ops via validateMultiQubitUnitaryMatrix,
    QuEST_validation.c:469-471, so this is strictly more capable)."""
    targets = list(targets)
    blocked = set(targets) | set(controls)
    order = free_order if free_order is not None else range(nloc)
    free_local = [q for q in order if q not in blocked]
    swaps = []
    for i, t in enumerate(targets):
        if t >= nloc:
            if not free_local:
                return None, None
            fq = free_local.pop(0)
            swaps.append((fq, t))
            targets[i] = fq
    return tuple(swaps), tuple(targets)
