"""Gate fusion for the imperative API: batch gates, execute in few passes.

The reference dispatches every API gate as one full sweep of the amplitude
array (QuEST/src/QuEST.c:177-186 et al.) — there is nothing like this
module in it.  On TPU a sweep is an HBM-bandwidth-bound pass, so the win
is batching: inside a ``gateFusion(qureg)`` context, dense gates issued
through the ordinary imperative API (hadamard, controlledNot, unitary,
multiControlledUnitary, ...) are BUFFERED instead of executed, and drained
through the circuit scheduler (circuit.plan_circuit — offset-window
passes) the moment anything needs the amplitudes:

    with qt.gateFusion(q):
        for d in range(depth):
            for t in range(n):
                qt.hadamard(q, t)
            for t in range(0, n - 1, 2):
                qt.controlledNot(q, t, t + 1)
    p = qt.calcProbOfOutcome(q, 0, 0)      # (any read would have drained)

Semantics are IDENTICAL to the unfused path — validation and QASM
recording still happen per call, in call order, and any operation that
reads or writes the state (calculations, measurement, decoherence, phase
functions, init) transparently drains the buffer first via the
``Qureg.amps`` property — only the number of HBM passes changes.  Gates
kept out of the buffer (too many qubits, explicit-distributed registers)
drain it and execute eagerly, preserving order.
"""

from __future__ import annotations

from contextlib import contextmanager
from functools import lru_cache, partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from . import circuit as C
from . import optimizer as _opt
from . import telemetry as _telemetry
from .ops import cplx as _cplx

# largest dense gate (targets + controls) worth buffering; anything bigger
# executes eagerly through the standard layout-safe kernels
FUSION_MAX_GATE_QUBITS = 7


class FusionBuffer:
    __slots__ = ("gates",)

    def __init__(self):
        # C.Gate and ChannelItem entries, executed in order by the drain
        self.gates: List[object] = []


def start_gate_fusion(qureg) -> None:
    """Begin buffering dense gates on ``qureg`` (idempotent)."""
    if getattr(qureg, "_fusion", None) is None:
        qureg._fusion = FusionBuffer()


def stop_gate_fusion(qureg) -> None:
    """Drain any buffered gates and stop buffering.  If execution fails the
    buffer stays attached with its gates intact, so state and QASM log
    cannot silently diverge."""
    drain(qureg)
    qureg._fusion = None


def drain(qureg) -> None:
    """Execute buffered gates now (called from the Qureg.amps property).
    On failure the gates are restored to the buffer — a failed drain must
    not be silently absorbed into a state/log divergence."""
    buf = getattr(qureg, "_fusion", None)
    if buf is not None and buf.gates:
        gates, buf.gates = buf.gates, []
        _telemetry.inc("fusion_drains_total")
        _telemetry.observe("fusion_drain_gates", len(gates))
        try:
            with _telemetry.span("fusion.drain", gates=len(gates)):
                _run(qureg, gates)
        except BaseException:
            buf.gates = gates + buf.gates
            raise
        # window-boundary accounting for the resilience layer: checkpoint
        # cadence is asserted against drains, never mid-window
        qureg._drain_count = getattr(qureg, "_drain_count", 0) + 1
        if _telemetry.enabled():
            # window-boundary HBM watermark sample (hbm_watermark_bytes
            # gauge; peak surfaced in getEnvironmentString / reportPerf)
            from .utils import profiling as _prof

            _prof.memory_watermark()


_PLAN_CACHE_MAX = 64
_plan_cache: dict = {}



class ChannelItem:
    """A captured depolarise/damping channel (one-pass elementwise pair
    kernel, ops/density.py) buffered BETWEEN gate segments: the drain runs
    gates-and-channels in order inside one jitted program, so a noise
    layer (BASELINE config 4) costs a single dispatch.  ``prob`` enters
    the compiled program as a traced scalar — re-draining with a
    different probability does not recompile."""

    __slots__ = ("kind", "target", "bra", "prob")

    def __init__(self, kind: str, target: int, bra: int, prob: float):
        self.kind = kind
        self.target = target       # ket bit position in the state vector
        self.bra = bra             # bra twin bit (target + numQubitsRepresented)
        self.prob = float(prob)


def _plan_key(items, nloc: int, sweep_ok: bool, perm0=None, nsh: int = 0):
    """Content key for a fully-concrete item list, or None when any matrix
    is traced/non-numpy.  Matrices in a drain are small (2x2..128x128), so
    hashing their bytes is negligible next to planning them (~0.2 s of
    host work per drain for a 13-qubit noise layer).  Channel items key on
    (kind, target) only — the probability is a runtime argument.  On a
    sharded register the key also carries the live logical->physical
    permutation the drain starts from — the same items plan to different
    windows/remaps under a different starting perm — and the topology
    signature (parallel/topology.py): the tier-aware window planner
    parks evictees differently per arrangement, so a QT_TOPOLOGY /
    planner-mode flip must not reuse a stale plan.  The circuit-optimizer
    mode is part of the key for the same reason: flipping QT_OPTIMIZER
    rewrites the stream, so it must retrace rather than replay a plan
    built under the other mode."""
    parts = []
    for it in items:
        if isinstance(it, ChannelItem):
            parts.append(("chan", it.kind, it.target, it.bra))
            continue
        m = it.mat
        if not isinstance(m, np.ndarray):
            return None
        parts.append((it.targets, m.dtype.str, m.shape, m.tobytes()))
    if nsh:
        from .parallel import topology as _topo

        topo_sig = _topo.signature(1 << nsh)
    else:
        topo_sig = None
    # QT_PERM_FAST is part of the key: flipping it reroutes permutation
    # runs between the gather/relabel lowering and the dense matmul
    # pipeline, so a flip must retrace rather than replay a stale plan.
    # QT_MEGAKERNEL likewise: the grouping rewrite (§29) changes the plan
    # skeleton itself, so a knob flip must re-plan rather than replay a
    # plan grouped under the other mode.
    from .ops import fused as _fused

    return (nloc, sweep_ok, perm0, topo_sig, _opt.mode(),
            C.perm_fast_enabled(), _fused.megakernel_planning(),
            tuple(parts))


def _split_items(items, nloc: int, sweep_ok: bool, phase=C._no_phase):
    """items -> (program, arrays): ``program`` is a hashable tuple of
    ("plan", skeleton, n_arrays) / ("chan", kind, t, b) /
    ("chansweep", ((kind, t, b), ...)) parts executed in order; ``arrays``
    the concatenated traced pass arrays (channel probabilities are
    appended per item at _run time, not here).  With ``sweep_ok``,
    consecutive sweep-eligible channels (ket bit < 14) collapse into ONE
    chansweep part — a few co-residency HBM sweeps for a whole noise
    layer (fused.apply_pair_channel_sweep).

    ``phase`` marks the planning steps of each gate segment
    (telemetry.phases, circuit.plan_circuit): ``fusion.analyse`` takes
    in the permutation-run classification, ``fusion.schedule`` the
    lowering of a permutation run, ``fusion.group`` the split of each
    plan into skeleton and arrays."""
    program = []
    arrays = []
    seg = []
    chans = []

    def flush_gates():
        if seg:
            if not C.PLAN_QUIET[0]:
                _telemetry.observe("fusion_window_gates", len(seg))
            phase("fusion.analyse")
            for kind, sub in _perm_runs(seg):
                if kind == "perm":
                    # permutation run: matrix-free static lowering (§28)
                    # — its own window kind, no gate-matrix stacks
                    phase("fusion.schedule")
                    ops = C.lower_permutation_run(sub, nloc)
                    if ops:
                        program.append(("perm", tuple(ops)))
                else:
                    ops = C.plan_circuit(list(sub), nloc, phase=phase)
                    phase("fusion.group")
                    skeleton, arrs = C.split_plan(ops)
                    program.append(("plan", skeleton, len(arrs)))
                    arrays.extend(arrs)
            seg.clear()

    def flush_chans():
        if not chans:
            return
        sweepable = (sweep_ok and nloc >= 15
                     and all(t < 14 for _, t, _b in chans))
        if sweepable:
            program.append(("chansweep", tuple(chans)))
        else:
            program.extend(("chan", kind, t, b) for kind, t, b in chans)
        chans.clear()

    for it in items:
        if isinstance(it, ChannelItem):
            flush_gates()
            chans.append((it.kind, it.target, it.bra))
        else:
            flush_chans()
            seg.append(it)
    flush_chans()
    flush_gates()
    return tuple(program), tuple(arrays)


def _item_bits(it) -> tuple:
    """Logical state-vector bits an item touches (gate targets incl.
    embedded controls; channel ket + bra bits)."""
    if isinstance(it, ChannelItem):
        return (it.target, it.bra)
    return tuple(it.targets)


# minimum adjacent permutation-classified gates worth splitting out of a
# dense segment: a lone X between dense neighbours fuses better inside
# their window pass than as its own HBM sweep
_PERM_RUN_MIN = 2


def _perm_runs(seg):
    """Partition one gate segment into maximal runs of permutation-
    classified gates and interleaved dense runs, in stream order:
    ``[("perm" | "dense", [gates...]), ...]``.  Runs shorter than
    _PERM_RUN_MIN are demoted to dense; with the permutation fast paths
    off (C.perm_fast_enabled: QT_PERM_FAST, and always on the TPU)
    everything is one dense run."""
    if not C.perm_fast_enabled():
        return [("dense", list(seg))]
    flags = [C.classify_permutation_gate(g.mat) is not None for g in seg]
    i = 0
    while i < len(seg):
        if flags[i]:
            j = i
            while j < len(seg) and flags[j]:
                j += 1
            if j - i < _PERM_RUN_MIN:
                for k in range(i, j):
                    flags[k] = False
            i = j
        else:
            i += 1
    runs: List[tuple] = []
    for flag, g in zip(flags, seg):
        kind = "perm" if flag else "dense"
        if runs and runs[-1][0] == kind:
            runs[-1][1].append(g)
        else:
            runs.append((kind, [g]))
    return runs


def _item_entry(it):
    """Window-planner entry for one drain item: channels expose their
    (ket, bra) bits; gates go through circuit.perm_item_entry, which tags
    pure bit-relabel gates for the zero-motion permutation fold.  EVERY
    cost-model consumer — the sharded planner here, optimizer._stream_cost,
    introspect.explain_circuit, and the §21 reconciliation — builds its
    entries through this one function, so predictions and the dispatched
    plan price the same stream and model drift stays 0 by construction.
    The §29 megakernel regroups the planner's winfused ops AFTER entries
    are priced (circuit.group_megawins is a pure post-pass inside the
    local plan segment): it changes how many Pallas dispatches execute a
    window, never which amplitudes move between shards, so every entry —
    and therefore the §21 reconciliation and §22 drain-peak predictor —
    prices both QT_MEGAKERNEL arms identically by construction."""
    if isinstance(it, ChannelItem):
        return (it.target, it.bra)
    return C.perm_item_entry(it.targets, it.mat)


def _split_items_sharded(items, n: int, nloc: int, perm0, sweep_ok: bool,
                         phase=C._no_phase):
    """Windows + ONE batched remap each for a SHARDED drain: group
    consecutive items whose cumulative qubit set fits the shard-local
    space (circuit.plan_remap_windows), emit a ("remap", sigma) part
    bringing the window's qubits local, then rewrite the window's items
    to their physical bits and fold them with the ordinary local planner.
    The permutation persists across windows AND drains — no swap-back;
    canonical order rematerializes on the next state read (Qureg.amps).
    Returns (program, arrays, final_perm); ``phase`` as _split_items."""
    phase("fusion.analyse")
    entries = [_item_entry(it) for it in items]
    phase("fusion.schedule")
    segments, final_perm = C.plan_remap_windows(entries, n, nloc, perm0)
    program: List[tuple] = []
    arrays: List[object] = []
    for (i, j), sigma, perm in segments:
        if C._is_relabel_entry(entries[i]):
            # permutation fold (§28): items [i, j) composed straight into
            # the plan's final permutation — zero data motion, nothing to
            # dispatch; the composed cross-shard hop (if any) is deferred
            # to the next canonical read like every other live perm
            continue
        if sigma is not None:
            program.append(("remap", sigma))
        phase("fusion.analyse")
        sub = []
        for it in items[i:j]:
            if isinstance(it, ChannelItem):
                pt, pb = perm[it.target], perm[it.bra]
                # the pair kernels want the ket bit below the bra bit;
                # both channel kinds are (t, b)-symmetric (their weights
                # depend only on the two bits' equality pattern), so a
                # remap that lands the bra below the ket just swaps roles
                sub.append(ChannelItem(it.kind, min(pt, pb), max(pt, pb),
                                       it.prob))
            else:
                sub.append(C.Gate(tuple(perm[t] for t in it.targets),
                                  it.mat))
        p2, a2 = _split_items(sub, nloc, sweep_ok, phase)
        program.extend(p2)
        arrays.extend(a2)
    return tuple(program), tuple(arrays), final_perm


def _items_for_element(items, b: int):
    """Item list for batch element ``b``: per-element matrices — an extra
    leading batch axis on ``Gate.mat`` — are sliced down; shared matrices
    and channels pass through unchanged."""
    out = []
    for it in items:
        if isinstance(it, ChannelItem) or getattr(it.mat, "ndim", 0) != 4:
            out.append(it)
        else:
            out.append(C.Gate(it.targets, it.mat[b]))
    return out


def _run(qureg, items) -> None:
    """Plan with the CONCRETE gate matrices (so controlled gates Schmidt-
    decompose to their true rank), then execute the whole item sequence —
    gate-segment plans interleaved with captured channels — as ONE jitted
    dispatch: the pass arrays and channel probabilities enter as traced
    arguments and the compiled program is cached on the program skeleton,
    so repeated drains of the same shape (e.g. angle sweeps, noise-layer
    reps) never recompile and cost a single host->device round-trip.
    Fully-concrete item lists also cache the MATERIALIZED plan (pass
    matrices), so repeated identical drains skip host planning entirely.

    On a BatchedQureg (batch.py) the same program runs vmapped over the
    leading batch axis of the (B, 2, 2^n) amplitude bank — the plan, the
    live logical->physical permutation, and the window remap schedule are
    SHARED across the batch because every element runs the same gate
    stream.  Per-element gate matrices (a (B, 2, s, s) ``Gate.mat``) are
    planned per element against a shared skeleton and the pass arrays
    enter the program with their own batch axis (vmap in_axes 0)."""
    from . import governor as _gov

    # a prior degradation ladder may have spilled this register to host
    # while it sat idle; bring it back BEFORE reading its permutation —
    # the handle carries the perm the plan must start from
    _gov.ensure_resident(qureg)
    n = qureg.num_qubits_in_state_vec
    nsh = _shard_bits(qureg)
    nloc = n - nsh
    perm0 = qureg._perm if nsh else None
    # circuit-optimizer rewrite (optimizer.py): the plan-cache key, the
    # planners, the governor predictor, and the §21 reconciliation below
    # all see the OPTIMIZED stream — predictions are priced on what is
    # actually drained, so model drift stays 0 by construction
    with _telemetry.span("fusion.optimize", items=len(items)):
        items, _ostats = _opt.optimize_items(
            items, n=n, nloc=nloc, nsh=nsh, perm0=perm0)
    if not items:
        return  # everything cancelled: nothing to execute, perm unchanged
    bsz = int(getattr(qureg, "batch_size", 0) or 0)
    mats_batched = bool(bsz) and any(
        not isinstance(it, ChannelItem) and getattr(it.mat, "ndim", 0) == 4
        for it in items)
    from .ops import fused as _fusedmod
    sweep_ok = _fusedmod.channel_sweep_enabled(qureg.dtype)
    with _telemetry.span("fusion.key"):
        key = _plan_key(items, nloc, sweep_ok, perm0, nsh)
        hit = _plan_cache.get(key) if key is not None else None
    if hit is not None:
        _telemetry.inc("fusion_plan_cache_hits_total")
        program, arrays, final_perm = hit
    else:
        _telemetry.inc("fusion_plan_cache_misses_total")
        # the planning steps tile fusion.plan as its child spans
        # (fusion.analyse / schedule / materialize / group)
        with _telemetry.span("fusion.plan", items=len(items)), \
                _telemetry.phases() as phase:
            if mats_batched:
                program, arrays, final_perm = _plan_batched_items(
                    items, bsz, n, nloc, nsh, perm0, sweep_ok, phase)
            elif nsh:
                program, arrays, final_perm = _split_items_sharded(
                    items, n, nloc, perm0, sweep_ok, phase)
            else:
                program, arrays = _split_items(items, nloc, sweep_ok, phase)
                final_perm = None
        if key is not None:
            if len(_plan_cache) >= _PLAN_CACHE_MAX:
                _plan_cache.pop(next(iter(_plan_cache)))
            _plan_cache[key] = (program, arrays, final_perm)
    # memory governance: predict this drain's per-device peak and walk
    # the degradation ladder if it exceeds the budget.  Must run BEFORE
    # the telemetry/reconcile block and the executor-key resolution so a
    # chunk escalation is seen consistently by all three (the override
    # is cleared in the finally).
    gov = None
    try:
        with _telemetry.span("fusion.govern"):
            gov = _gov.govern_drain(qureg, program, arrays, nloc=nloc,
                                    nsh=nsh)
        with _telemetry.span("fusion.dispatch"):
            _run_dispatch(qureg, items, program, arrays, gov,
                          n=n, nsh=nsh, nloc=nloc, bsz=bsz, perm0=perm0,
                          mats_batched=mats_batched, final_perm=final_perm)
    finally:
        _gov.end_drain()


def _group_route(gprog) -> str:
    """Dominant plan-entry family of one dispatch group — the §30
    wall-time attribution label.  Precedence reflects cost dominance: a
    megawin anywhere makes the group megakernel-shaped; else fused
    window passes; else permutation fast paths; else channel sweeps;
    else pure remap exchange."""
    saw = set()
    for part in gprog:
        if part[0] == "plan":
            for sk in part[1]:
                saw.add("megawin" if sk[0] == "megawin" else "winfused")
        elif part[0] == "perm":
            saw.add("permfast")
        elif part[0] in ("chan", "chansweep"):
            saw.add("channel")
        elif part[0] == "remap":
            saw.add("remap")
    for route in ("megawin", "winfused", "permfast", "channel", "remap"):
        if route in saw:
            return route
    return "other"


def _reconcile_remaps(qureg, items, program, *, n, nsh, nloc, bsz,
                      perm0) -> None:
    """Count a sharded drain's window remaps (exchanges_total /
    exchange_bytes_total{op="window_remap"}, per tier) and reconcile them
    against an independent re-plan through the cost model."""
    from . import introspect as _introspect
    from .parallel import dist as PAR
    from .parallel import topology as _topo

    bw = max(bsz, 1)  # each batch element exchanges its own amps
    itemsize = np.dtype(qureg.dtype).itemsize
    ck = str(PAR.exchange_config_key() or "auto")
    topology = _topo.resolve(1 << nsh)
    meas_c0 = _telemetry.counter_sum("exchanges_total",
                                     op="window_remap")
    meas_b0 = _telemetry.counter_sum("exchange_bytes_total",
                                     op="window_remap")
    meas_t0 = {t: _telemetry.counter_sum(
        "exchange_bytes_total", op="window_remap", tier=t)
        for t in _topo.TIERS}
    for part in program:
        if part[0] != "remap":
            continue
        # per-tier exchange classes straight from the cost model the
        # tests pin (dist.remap_exchange_tiers sums exactly to
        # remap_exchange_count / remap_exchange_bytes)
        for tier, (cnt, b) in PAR.remap_exchange_tiers(
                part[1], nloc, nsh, itemsize, topology).items():
            if cnt or b:
                _telemetry.record_exchange(
                    "window_remap", cnt * bw, b * bw,
                    chunks=ck, tier=tier)
    # any disagreement with the re-plan is model drift (introspect,
    # docs/design.md §21)
    _introspect.reconcile_drain(
        bit_sets=[_item_entry(it) for it in items],
        n=n, nloc=nloc, nsh=nsh, perm0=perm0, itemsize=itemsize,
        batch=bsz,
        measured_count=_telemetry.counter_sum(
            "exchanges_total", op="window_remap") - meas_c0,
        measured_bytes=_telemetry.counter_sum(
            "exchange_bytes_total", op="window_remap") - meas_b0,
        measured_chunks=ck,
        measured_tier_bytes={t: _telemetry.counter_sum(
            "exchange_bytes_total", op="window_remap", tier=t)
            - meas_t0[t] for t in _topo.TIERS})


def _run_dispatch(qureg, items, program, arrays, gov, *, n, nsh, nloc,
                  bsz, perm0, mats_batched, final_perm) -> None:
    """Telemetry accounting + dispatch of a planned drain, in (possibly
    governor-split) program groups, each through the RESOURCE_EXHAUSTED
    net at the dispatch boundary."""
    from . import governor as _gov

    if _telemetry.enabled():
        _telemetry.inc("fusion_windows_total",
                       sum(1 for p in program if p[0] == "plan"))
        # §29 megakernel route accounting: one "mega" per megawin group
        # (ONE pallas_call = one HBM round-trip for its whole run), one
        # "fallback" per winfused pass still on the per-pass route while
        # grouping is active.  fusion_passes_total counts the state
        # passes the plan parts run (a megawin group is one): over
        # fusion_windows_total, the HBM round-trips per fusion window —
        # the quantity the megakernel and the planner exist to shrink.
        from .ops import fused as _fusedops

        mega = fallback = trips = 0
        for part in program:
            if part[0] != "plan":
                continue
            for sk in part[1]:
                trips += 1
                if sk[0] == "megawin":
                    mega += 1
                elif sk[0] == "winfused":
                    fallback += 1
        if mega:
            _telemetry.inc("megakernel_dispatch_total", mega, route="mega")
        if fallback and _fusedops.megakernel_planning():
            _telemetry.inc("megakernel_dispatch_total", fallback,
                           route="fallback")
        if trips:
            _telemetry.inc("fusion_passes_total", trips)
        # permutation-family route accounting (§28): lowered window ops
        # count by kind (one coalesced transpose = relabel, static
        # xor/gather passes = gather); sharded relabel FOLDS — which
        # dispatch nothing — count per item below
        for part in program:
            if part[0] != "perm":
                continue
            for op in part[1]:
                _telemetry.inc(
                    "permutation_gates_total",
                    route="relabel" if op[0] == "permute" else "gather")
        if nsh:
            p0 = tuple(perm0) if perm0 is not None else tuple(range(n))
            for it in items:
                e = _item_entry(it)
                if C._is_relabel_entry(e):
                    # "exchange" when the fold touches bits resident on
                    # the shard axis at drain start: the composed
                    # cross-shard ppermute is deferred to the canonical
                    # read rather than avoided
                    ex = any(p0[a] >= nloc or p0[b] >= nloc
                             for a, b in e[1])
                    _telemetry.inc(
                        "permutation_gates_total",
                        route="exchange" if ex else "relabel")
        if nsh:
            # the remap accounting and its reconciliation: host work on
            # every sharded drain, plan-cache hits included
            with _telemetry.span("fusion.reconcile"):
                _reconcile_remaps(qureg, items, program, n=n, nsh=nsh,
                                  nloc=nloc, bsz=bsz, perm0=perm0)
    probs = tuple(it.prob for it in items if isinstance(it, ChannelItem))
    from .ops import fused as _fused
    if nsh:
        from .parallel import dist as PAR

        exchange_key = PAR.exchange_config_key()
    else:
        exchange_key = None
    mesh = qureg.env.mesh if nsh else None
    precision = _fused.matmul_precision_name()
    batch_flag = (2 if mats_batched else 1) if bsz else 0
    # bypass the amps property (which would re-enter drain); the live
    # permutation the windowed plan leaves behind is carried on the
    # register — the next drain starts from it, the next READ
    # rematerializes canonical order (Qureg.amps).  The governor's
    # ladder may have split the program into several dispatch groups
    # (bit-identical — part boundaries already carry an
    # optimization_barrier); each group runs through the
    # RESOURCE_EXHAUSTED net, and sharded groups dispatch under the
    # collective guard so a dead peer surfaces as ShardLossError and
    # the resilience layer can fail over (docs/design.md §19)
    groups = (gov or {}).get("groups") or (program,)
    # §30 per-op wall-time attribution: each dispatched group is timed
    # and charged to its dominant plan-entry route (megawin / winfused /
    # permfast / channel / remap) — plan_route_seconds{route} feeds the
    # reportPerf attribution section and its dispatch-bound detector.
    # Trace mode blocks on the group result so the sample is true wall
    # time; the default mode times dispatch only (no added sync on the
    # hot path — the <5% bench_telemetry budget).
    import time as _time

    attrib = _telemetry.enabled()
    deep = attrib and _telemetry.mode_name() == "trace"
    ai = pi = 0
    for gprog in groups:
        a0, p0 = ai, pi
        for part in gprog:
            ai, pi = _part_advance(part, ai, pi)
        garrays, gprobs = arrays[a0:ai], probs[p0:pi]
        runner = _plan_runner(nloc, gprog, mesh, precision, exchange_key,
                              batch_flag)
        if nsh:
            def dispatch(r=runner, ga=garrays, gp=gprobs):
                return PAR.guarded_dispatch(
                    r, qureg._amps, ga, gp,
                    op="drain", shards=qureg.num_chunks)
        else:
            def dispatch(r=runner, ga=garrays, gp=gprobs):
                return r(qureg._amps, ga, gp)
        t0 = _time.perf_counter() if attrib else 0.0
        qureg._amps = _gov.oom_net(dispatch, qureg)
        if attrib:
            if deep:
                jax.block_until_ready(qureg._amps)
            route = _group_route(gprog)
            _telemetry.observe("plan_route_seconds",
                               _time.perf_counter() - t0, route=route)
            _telemetry.inc("plan_route_dispatch_total", route=route)
    if nsh:
        if final_perm is not None and list(final_perm) != list(range(n)):
            qureg._perm = tuple(final_perm)
        else:
            qureg._perm = None


def _plan_batched_items(items, bsz: int, n: int, nloc: int, nsh: int,
                        perm0, sweep_ok: bool, phase=C._no_phase):
    """Plan a drain whose items carry PER-ELEMENT matrices: each batch
    element is planned independently (the decomposition of a controlled
    gate is value-dependent) and all elements must produce the SAME
    program skeleton — the compiled executor is shared across the batch,
    only the pass arrays differ.  Returns (program, arrays, final_perm)
    with each pass array stacked to a leading (B, ...) batch axis;
    ``phase`` as _split_items."""
    program = None
    final_perm = None
    per_elem = []
    for b in range(bsz):
        eit = _items_for_element(items, b)
        if nsh:
            pb, ab, fp = _split_items_sharded(eit, n, nloc, perm0, sweep_ok,
                                              phase)
        else:
            (pb, ab), fp = _split_items(eit, nloc, sweep_ok, phase), None
        if b == 0:
            program, final_perm = pb, fp
        elif pb != program or fp != final_perm:
            from .validation import QuESTError

            raise QuESTError(
                "batched drain: batch element %d's gate stream plans to a "
                "different program skeleton than element 0 (value-dependent "
                "decomposition, e.g. a controlled gate of different Schmidt "
                "rank) — such submissions cannot share one batched program; "
                "run them in separate ensemble groups" % b)
        per_elem.append(ab)
    arrays = tuple(
        np.stack([np.asarray(per_elem[b][j]) for b in range(bsz)])
        for j in range(len(per_elem[0])))
    return program, arrays, final_perm


def _part_advance(part, ai: int, pi: int):
    """Walk the (pass-array, channel-probability) offsets past one
    program part — shared by the compiled executor and the governor's
    grouped-dispatch split, so both slice the argument streams
    identically."""
    if part[0] == "plan":
        return ai + part[2], pi
    if part[0] == "chansweep":
        return ai, pi + len(part[1])
    if part[0] in ("remap", "perm"):
        return ai, pi
    return ai, pi + 1


def plan_items_quiet(qureg, items):
    """Plan ``items`` exactly as _run would — same program parts, pass
    arrays, and final permutation — WITHOUT touching telemetry or the
    plan cache: the dry-run planning path behind explain_circuit's
    ``memory`` section and the governor predictor.  A cached plan is
    read (identical values), but a miss is NOT inserted — explaining a
    circuit must not flip the cache status the introspection tests pin.
    Returns (program, arrays, final_perm, nloc, nsh)."""
    n = qureg.num_qubits_in_state_vec
    nsh = _shard_bits(qureg)
    nloc = n - nsh
    perm0 = qureg._perm if nsh else None
    if not items:
        return (), (), None, nloc, nsh
    # the same optimizer rewrite _run applies, quietly — a dry run must
    # predict the stream that would actually drain
    items, _ostats = _opt.optimize_items(
        items, n=n, nloc=nloc, nsh=nsh, perm0=perm0, quiet=True)
    if not items:
        return (), (), None, nloc, nsh
    bsz = int(getattr(qureg, "batch_size", 0) or 0)
    mats_batched = bool(bsz) and any(
        not isinstance(it, ChannelItem) and getattr(it.mat, "ndim", 0) == 4
        for it in items)
    from .ops import fused as _fusedmod
    sweep_ok = _fusedmod.channel_sweep_enabled(qureg.dtype)
    key = _plan_key(items, nloc, sweep_ok, perm0, nsh)
    hit = _plan_cache.get(key) if key is not None else None
    if hit is not None:
        program, arrays, final_perm = hit
        return program, arrays, final_perm, nloc, nsh
    with C.quiet_planning():
        if mats_batched:
            program, arrays, final_perm = _plan_batched_items(
                items, bsz, n, nloc, nsh, perm0, sweep_ok)
        elif nsh:
            program, arrays, final_perm = _split_items_sharded(
                items, n, nloc, perm0, sweep_ok)
        else:
            program, arrays = _split_items(items, nloc, sweep_ok)
            final_perm = None
    return program, arrays, final_perm, nloc, nsh


def aot_plan_info(qureg, items):
    """Quiet planning PLUS the dispatch-key derivation _run_dispatch
    applies (mesh / precision / exchange key / batch flag / channel-prob
    slot count) — everything the AOT tier (§31) needs to name or prewarm
    the executor a drain of ``items`` would dispatch, without touching
    telemetry or the plan cache.  Returns None for an empty plan.

    Single-group assumption: the prediction names the ungoverned
    whole-program runner; a governor ladder split dispatches per-group
    executors with their own (sub-program) keys."""
    program, arrays, _fp, nloc, nsh = plan_items_quiet(qureg, items)
    if not program:
        return None
    n = qureg.num_qubits_in_state_vec
    bsz = int(getattr(qureg, "batch_size", 0) or 0)
    mats_batched = False
    if bsz:
        perm0 = qureg._perm if nsh else None
        oitems, _ostats = _opt.optimize_items(
            items, n=n, nloc=nloc, nsh=nsh, perm0=perm0, quiet=True)
        mats_batched = any(
            not isinstance(it, ChannelItem)
            and getattr(it.mat, "ndim", 0) == 4 for it in oitems)
    if nsh:
        from .parallel import dist as PAR

        exchange_key = PAR.exchange_config_key()
        mesh = qureg.env.mesh
    else:
        exchange_key = None
        mesh = None
    from .ops import fused as _fusedmod

    ai = pi = 0
    for part in program:
        ai, pi = _part_advance(part, ai, pi)
    return {
        "program": program, "arrays": arrays, "nloc": nloc, "nsh": nsh,
        "mesh": mesh, "precision": _fusedmod.matmul_precision_name(),
        "exchange_key": exchange_key,
        "batch_flag": (2 if mats_batched else 1) if bsz else 0,
        "batch_size": bsz, "nprobs": pi, "final_perm": _fp,
    }


def aot_probe(qureg, items):
    """Side-effect-free AOT-tier prediction for the drain ``items``
    would dispatch — explainCircuit's ``compile`` section (§31).
    Returns {"enabled", "status", "key"} with status in disabled /
    uncacheable / memory / hit / miss."""
    from . import aotcache as _aotcache

    if not _aotcache.enabled():
        return {"enabled": False, "status": "disabled", "key": None}
    info = aot_plan_info(qureg, items)
    if info is None:
        return {"enabled": True, "status": "uncacheable", "key": None}
    amps = _aotcache.amps_struct(
        qureg.num_amps_total, info["batch_size"], qureg.dtype,
        info["mesh"])
    probs = tuple(0.5 for _ in range(info["nprobs"]))
    sig = _aotcache.arg_sig(amps, info["arrays"], probs)
    return _aotcache.probe(
        info["nloc"], info["program"], info["mesh"], info["precision"],
        info["exchange_key"], info["batch_flag"], sig)


@lru_cache(maxsize=256)
def _plan_runner(nloc: int, program: tuple, mesh, precision: str = None,
                 exchange_key: str = None, batch: int = 0):
    """Jitted whole-program executor over ("plan", skeleton, n_arrays) /
    ("chan", kind, t, b) parts in order.  For a sharded register the
    program (all items shard-local by capture policy) runs inside ONE
    shard_map over the amplitude mesh — the multi-chip analogue of the
    drain.  ``exchange_key`` is dist.exchange_config_key(): the remap
    parts bake the pipelined-exchange chunk count in at trace time, so
    the compiled executor must be keyed on the QT_EXCHANGE_CHUNKS
    override (a stale cache entry would silently keep the old chunk
    schedule).

    ``batch``: 0 = scalar register; 1 = (B, 2, 2^n) register bank, pass
    arrays shared across the batch; 2 = bank + per-element pass arrays
    (leading (B, ...) axis, vmap in_axes 0).  The batched program is the
    SAME ``_apply`` body vmapped over the batch axis — on a mesh the
    vmap sits INSIDE the shard_map kernel (batch-outer/amps-inner:
    collectives move every element's shard slice in one exchange)."""
    # this body runs only on an lru_cache MISS: each execution is a new
    # compiled-executor shape — the drain's retrace count
    _telemetry.inc("fusion_retrace_total")
    from .ops import density as _density

    if mesh is not None:
        from .parallel import dist as PAR

        _ndev = PAR.amp_axis_size(mesh)
        # the chunk policy of the mesh's own devices (a program compiled
        # for a TPU mesh from a CPU process pipelines as on the chip)
        _backend = PAR.mesh_platform(mesh)

    def _apply_part(part, amps, arrays, probs, ai, pi):
        if part[0] == "plan":
            _, skeleton, na = part
            amps = C.execute_plan(
                amps, C.rebuild_plan(skeleton, arrays[ai:ai + na]),
                nloc, precision=precision)
        elif part[0] == "perm":
            # matrix-free permutation window (§28): xor / gatherperm /
            # permute ops are fully static — zero pass arrays
            amps = C.execute_plan(amps, list(part[1]), nloc,
                                  precision=precision)
        elif part[0] == "remap":
            # ONE batched window relocalization (mixed half-shard
            # swaps + per-shard axis permutation + composed shard
            # ppermute) — only emitted inside the mesh path's
            # shard_map body
            from .parallel import dist as PAR
            amps = PAR._remap_in_shard(
                amps, part[1], nloc, _ndev,
                PAR.remap_chunk_plan(nloc, amps.dtype.itemsize,
                                     backend=_backend))
        elif part[0] == "chansweep":
            entries = part[1]
            from .ops import fused as _fusedmod
            amps = _fusedmod.apply_pair_channel_sweep(
                amps.reshape(2, -1), entries,
                probs[pi:pi + len(entries)],
                num_bits=nloc).reshape(amps.shape)
        else:
            _, kind, t, b = part
            amps = _density.apply_pair_channel(
                amps, kind, probs[pi], nn=nloc, t=t, b=b)
        return amps

    def _apply(amps, arrays, probs):
        ai = pi = 0
        for part in program:
            amps = _apply_part(part, amps, arrays, probs, ai, pi)
            ai, pi = _part_advance(part, ai, pi)
            # without this barrier XLA:TPU's memory assignment keeps every
            # part's temporaries live to the end of the program (measured:
            # +1.25 GiB PER CHANNEL at 13q rho -> 21 GiB OOM; flat 1.75 GiB
            # with it)
            amps = jax.lax.optimization_barrier(amps)
        return amps

    if batch:
        def _apply_fn(amps, arrays, probs):
            # vmap part by part: optimization_barrier has no batching rule,
            # and keeping it between (rather than inside) the vmapped parts
            # preserves the same per-part liveness cut for the whole bank
            ai = pi = 0
            for part in program:
                step = partial(_apply_part, part, ai=ai, pi=pi)
                amps = jax.vmap(
                    step, in_axes=(0, 0 if batch == 2 else None, None)
                )(amps, arrays, probs)
                ai, pi = _part_advance(part, ai, pi)
                amps = jax.lax.optimization_barrier(amps)
            return amps
    else:
        _apply_fn = _apply

    @partial(jax.jit, donate_argnums=0)
    def run(amps, arrays, probs):
        if mesh is None:
            return _apply_fn(amps, arrays, probs)
        from jax.sharding import PartitionSpec as P

        from .env import AMP_AXIS, shard_map

        def kernel(local, *arrs):
            return _apply_fn(local, arrs[:len(arrays)], arrs[len(arrays):])

        amp_spec = P(None, None, AMP_AXIS) if batch else P(None, AMP_AXIS)
        return shard_map(
            kernel, mesh=mesh,
            in_specs=(amp_spec,) + (P(),) * (len(arrays) + len(probs)),
            out_specs=amp_spec,
            check_vma=False,  # pallas_call inside shard_map has no vma info
        )(amps, *arrays, *probs)

    # §31 persistent AOT tier: with QT_AOT_CACHE set the runner is
    # wrapped consult-before-compile / persist-on-miss (and gains the
    # .prewarm entry point the serve warm pool drives); unset, this is
    # an identity pass-through
    from . import aotcache as _aotcache

    return _aotcache.wrap_runner(
        run, nloc=nloc, program=program, mesh=mesh, precision=precision,
        exchange_key=exchange_key, batch=batch)


def _shard_bits(qureg) -> int:
    """Number of leading qubits held as mesh coordinates (0 when the
    register is single-device or replicated)."""
    env = qureg.env
    if env.mesh is None:
        return 0
    from .parallel import dist as PAR

    nd = PAR.amp_axis_size(env.mesh)
    if nd <= 1 or qureg.num_amps_total < env.num_devices:
        return 0
    return PAR.num_shard_bits(env.mesh)


def _capturable(qureg, bits) -> bool:
    """Can a dense gate on qubit positions ``bits`` be buffered?  Size-
    capped; on a sharded register the drain runs the whole plan inside
    one shard_map, relocalizing gates that touch mesh-coordinate bits at
    WINDOW granularity through the lazy logical->physical permutation
    (_split_items_sharded) — one batched remap per window instead of two
    half-shard exchanges per gate.  Only gates too wide for the
    shard-local space (or the GSPMD-opt-out mode, which has no remap
    kernel) fall back to eager execution."""
    buf = getattr(qureg, "_fusion", None)
    if buf is None:
        return False
    bits = tuple(bits)
    if len(bits) > FUSION_MAX_GATE_QUBITS:
        return False
    nsh = _shard_bits(qureg)
    if nsh:
        nloc = qureg.num_qubits_in_state_vec - nsh
        if len(set(bits)) > nloc:
            return False
        if max(bits) >= nloc:
            from .parallel import dist as PAR

            if not (PAR.explicit_dist_enabled()
                    and PAR.lazy_remap_enabled()):
                return False
    return True


def capture_unitary(qureg, stacked, targets, controls=(),
                    control_states=()) -> bool:
    """Buffer a dense gate (with the density-matrix conjugate twin,
    QuEST.c:181-183) if fusion is active and the gate qualifies; returns
    False to tell the caller to execute eagerly (after draining, so order
    is preserved)."""
    base_bits = tuple(targets) + tuple(controls)
    ok = _capturable(qureg, base_bits)
    if ok and qureg.is_density_matrix:
        sh = qureg.num_qubits_represented
        ok = _capturable(qureg, tuple(b + sh for b in base_bits))
    if not ok:
        drain(qureg)
        return False
    mat = stacked
    if controls:
        mat = C.controlled_dense(stacked, len(controls), control_states)
    buf = qureg._fusion
    buf.gates.append(C.Gate(tuple(targets) + tuple(controls), mat))
    if qureg.is_density_matrix:
        sh = qureg.num_qubits_represented
        cmat = _cplx.conj(stacked)
        if controls:
            cmat = C.controlled_dense(cmat, len(controls), control_states)
        buf.gates.append(
            C.Gate(tuple(t + sh for t in targets)
                   + tuple(c + sh for c in controls), cmat)
        )
    return True


def capture_raw(qureg, stacked, targets) -> bool:
    """Buffer an arbitrary dense matrix on STATE-VECTOR qubit positions
    ``targets`` with NO density-matrix twin — used for decoherence-channel
    superoperators, which already act on the combined (T, T+n) targets
    (mixDepolarising et al., QuEST_common.c:630-652).  Captured channels
    fold into the same window passes as gates, so a noise-heavy density
    workload (BASELINE config 4) runs as a handful of fused passes instead
    of one dispatch per channel."""
    if not _capturable(qureg, tuple(targets)):
        drain(qureg)
        return False
    qureg._fusion.gates.append(C.Gate(tuple(targets), stacked))
    return True


_X = np.stack([np.array([[0.0, 1.0], [1.0, 0.0]]), np.zeros((2, 2))])


def capture_pair_channel(qureg, kind: str, target: int, prob) -> bool:
    """Buffer a depolarise/damping channel as a ChannelItem — the one-pass
    elementwise pair kernel runs INSIDE the drain program, interleaved in
    call order with the gate segments, so a whole noise layer is one
    dispatch.  Deliberately NOT a superoperator fold (capture_raw): these
    channels' superoperators have operator-Schmidt rank 4 across
    (t, t+n): a rank-4 window pass per channel does four times the
    matmul work of the elementwise kernel."""
    sh = qureg.num_qubits_represented
    bits = (target, target + sh)
    if not _capturable(qureg, bits):
        drain(qureg)
        return False
    qureg._fusion.gates.append(ChannelItem(kind, target, target + sh, prob))
    return True


def capture_not(qureg, targets, controls=(), control_states=()) -> bool:
    """Buffer a (multi-controlled) multi-qubit NOT: uncontrolled targets
    become independent 1q X gates; controlled ones one dense gate."""
    if not controls:
        buf = getattr(qureg, "_fusion", None)
        if buf is None:
            return False
        sh = qureg.num_qubits_represented
        bits = list(targets)
        if qureg.is_density_matrix:
            bits += [t + sh for t in targets]
        if not all(_capturable(qureg, (b,)) for b in bits):
            drain(qureg)
            return False
        for t in targets:
            buf.gates.append(C.Gate((t,), _X))
            if qureg.is_density_matrix:
                buf.gates.append(C.Gate((t + sh,), _X))
        return True
    # controlled: one dense gate, X^(x)nt (the bit-COMPLEMENT permutation
    # i -> i ^ (2^nt - 1)) under the controls.  Size-check BEFORE
    # densifying — 2^nt x 2^nt would be catastrophic for a wide
    # multiQubitNot outside the cap.
    if not _capturable(qureg, tuple(targets) + tuple(controls)):
        drain(qureg)
        return False
    nt = len(targets)
    d = 1 << nt
    xr = np.zeros((d, d))
    for i in range(d):
        xr[i, i ^ (d - 1)] = 1.0
    mat = np.stack([xr, np.zeros((d, d))])
    return capture_unitary(qureg, mat, targets, controls, control_states)


def capture_diag(qureg, diag_stacked, targets, controls=(),
                 control_states=()) -> bool:
    """Buffer a diagonal gate as its dense matrix."""
    if not _capturable(qureg, tuple(targets) + tuple(controls)):
        drain(qureg)
        return False
    diag = diag_stacked
    d = diag.shape[-1]
    if isinstance(diag, np.ndarray):
        mat = np.zeros((2, d, d), dtype=diag.dtype)
        mat[0][np.diag_indices(d)] = diag[0]
        mat[1][np.diag_indices(d)] = diag[1]
    else:
        mat = jnp.zeros((2, d, d), diag.dtype)
        mat = mat.at[0, np.arange(d), np.arange(d)].set(diag[0])
        mat = mat.at[1, np.arange(d), np.arange(d)].set(diag[1])
    return capture_unitary(qureg, mat, targets, controls, control_states)


@contextmanager
def gate_fusion(qureg):
    """Context manager: buffer dense imperative-API gates on ``qureg`` and
    execute them through the fused circuit scheduler on exit (or the
    moment any operation needs the amplitudes).  Nesting-safe: an inner
    context reuses the outer buffer and leaves it active on exit."""
    created = getattr(qureg, "_fusion", None) is None
    start_gate_fusion(qureg)
    try:
        yield qureg
    finally:
        if created:
            stop_gate_fusion(qureg)
