"""Execution introspection: plan explainer, HLO audit, reconciliation.

The telemetry layer (telemetry.py) counts what *happened* — dispatches,
exchange programs, per-shard ICI bytes.  Nothing so far could tell a user
what a circuit *will* cost before it runs, nor prove that the measured
counters still agree with the scheduler's cost model as the planner
evolves.  mpiQulacs (arXiv:2203.16044 §V) and qHiPSTER (arXiv:1601.07195
§IV) both treat predictive communication accounting as the tuning
surface of a distributed simulator; this module closes that loop
(docs/design.md §21):

* **Plan explainer** — :func:`explain_circuit` dry-runs the fusion
  planner (circuit.plan_remap_windows + the channel-segmentation rules
  of fusion._split_items) with NO device execution and returns a
  per-window report: gates fused, remap sigma, predicted per-shard ICI
  bytes (circuit.remap_exchange_bytes), the pipeline chunk split the
  PIPELINE_MIN_BYTES policy resolves, the plan-cache key status /
  expected retrace behavior, and bucket occupancy for a BatchedQureg.
  The report is a JSON-serializable dict with a ``.table()`` text
  rendering; :func:`report_circuit_plan` prints it (the ``report*``
  family, like reportQuregParams / reportPerf).

* **HLO audit** — :func:`audit` compiles a function and histograms the
  ACTUAL collective instructions in the optimized HLO (exact opcodes,
  promoted from tests/test_distributed_hlo.py where the recipe was
  trapped), plus ``Compiled.cost_analysis()`` flops/bytes.
  :class:`CollectiveBudget` asserts per-op budgets — as a context
  manager every :func:`audit` inside is checked automatically, so user
  code, CI, and the tests share one budget surface.

* **Reconciliation** — after each sharded drain, fusion._run calls
  :func:`reconcile_drain`: the measured ``exchanges_total`` /
  ``exchange_bytes_total{op=window_remap}`` deltas are compared against
  an INDEPENDENT re-derivation from the window planner's cost model.
  Agreement is the contract (``model_drift_total == 0``); any deviation
  increments ``model_drift_total{kind}`` and emits one structured JSON
  log line on the ``quest_tpu.introspect`` logger.  reportPerf gains a
  predicted-vs-measured section.  :func:`perturb_prediction` (or the
  ``QT_INTROSPECT_PERTURB`` env var) injects a planner-policy
  perturbation — e.g. a forced chunk-count override — to prove the loop
  detects drift, the same fault-injection philosophy as
  resilience.FaultPlan.
"""

from __future__ import annotations

import contextlib
import functools
import json
import logging
import os
import re
from typing import Iterator, Optional, Sequence

import numpy as np

from . import circuit as C
from . import telemetry as _telemetry

_LOG = logging.getLogger("quest_tpu.introspect")

_PERTURB_ENV = "QT_INTROSPECT_PERTURB"

# ---------------------------------------------------------------------------
# Plan explainer
# ---------------------------------------------------------------------------


class ExplainReport(dict):
    """The explain_circuit result: a plain JSON-serializable dict (every
    value is a Python native) plus a ``table()`` text rendering."""

    def table(self) -> str:
        return format_explain(self)


def _as_items(gates) -> list:
    """Normalize a user gate sequence to drain items: circuit.Gate and
    fusion.ChannelItem pass through; ``(targets, mat)`` pairs become
    Gates (mat in the stacked (2, s, s) SoA form)."""
    from . import fusion as F

    items = []
    for g in gates:
        if isinstance(g, (C.Gate, F.ChannelItem)):
            items.append(g)
        else:
            targets, mat = g
            items.append(C.Gate(tuple(int(t) for t in targets),
                                np.asarray(mat)))
    return items


def _segment_stats(items, nloc=None, perm=None) -> tuple:
    """(plan_windows, gates, channels, perm_windows, mega_windows,
    mega_ops) for one item run under fusion._split_items's segmentation:
    each maximal consecutive gate run splits into permutation runs (§28
    — their own ("perm", ...) parts, which fusion_windows_total does NOT
    count) and dense runs that fold into ONE ("plan", ...) part each;
    channels emit chan/chansweep parts, also uncounted.  When ``nloc``
    is given and the §29 megakernel planner is active, each dense run is
    additionally planned through circuit.plan_circuit — the exact local
    planner the drain dispatches — to count megawin groups and the
    winfused ops they absorb; ``perm`` first rewrites logical targets to
    their physical shard-local bits, mirroring the sharded dispatcher's
    own rewrite."""
    from . import fusion as F
    from .ops import fused as _fused

    count_mega = (nloc is not None and nloc >= C.WINDOW
                  and _fused.megakernel_planning())
    plan_parts = 0
    perm_parts = 0
    gates = 0
    chans = 0
    mega_groups = 0
    mega_ops = 0
    seg: list = []

    def flush():
        nonlocal plan_parts, perm_parts, mega_groups, mega_ops
        if not seg:
            return
        for kind, sub in F._perm_runs(seg):
            if kind == "perm":
                perm_parts += 1
            else:
                plan_parts += 1
                if count_mega:
                    with C.quiet_planning():
                        ops = C.plan_circuit(list(sub), nloc)
                    for op in ops:
                        if op[0] == "megawin":
                            mega_groups += 1
                            mega_ops += len(op[1])
        seg.clear()

    for it in items:
        if isinstance(it, F.ChannelItem):
            chans += 1
            flush()
        else:
            gates += 1
            if perm is not None:
                it = C.Gate(tuple(perm[t] for t in it.targets), it.mat)
            seg.append(it)
    flush()
    return plan_parts, gates, chans, perm_parts, mega_groups, mega_ops


def _sigma_cost(sigma, n: int, nloc: int, nsh: int, itemsize: int,
                backend: Optional[str] = None) -> dict:
    """Exchange classes, per-shard ICI bytes, and the pipeline chunk
    split for ONE batched remap — straight from the scheduling layer's
    own cost model (dist.decompose_sigma / circuit.remap_exchange_bytes
    / the PIPELINE_MIN_BYTES policy via dist.remap_chunk_plan)."""
    from .parallel import dist as PAR

    mixed, _lp, mesh_tau = PAR.decompose_sigma(tuple(sigma), nloc, nsh,
                                               itemsize)
    ch_half, ch_full = PAR.remap_chunk_plan(nloc, itemsize, backend=backend)
    # per-interconnect-tier refinement of the same model (QT_TOPOLOGY;
    # single-host arrangements put everything under "ici")
    tiers = PAR.remap_exchange_tiers(tuple(sigma), nloc, nsh, itemsize)
    return {
        "sigma": [int(p) for p in sigma],
        "mixed_swaps": len(mixed),
        "mesh_permute": mesh_tau is not None,
        "exchanges": PAR.remap_exchange_count(tuple(sigma), nloc, nsh,
                                              itemsize),
        "exchange_bytes": int(C.remap_exchange_bytes(
            tuple(sigma), n, nloc, itemsize)),
        "tier_bytes": {t: int(b) for t, (_c, b) in tiers.items()},
        "tier_exchanges": {t: int(c) for t, (c, _b) in tiers.items()},
        "chunks": {"half_shard": int(ch_half), "full_shard": int(ch_full)},
    }


def _optimizer_section(orig_items, opt_items, ostats, *, n, nloc, nsh,
                       perm0, itemsize, bw) -> dict:
    """The explain report's ``optimizer`` entry: the rewrite's own stats
    plus projected exchange savings — the SAME per-tier cost model the
    window accounting uses, diffed between the original and the
    optimized stream (sharded registers; scalar registers diff the local
    planner's pass count instead)."""
    from . import fusion as F
    from .parallel import dist as PAR

    section = {
        "mode": ostats["mode"],
        "gates_in": int(ostats["gates_in"]),
        "gates_out": int(ostats["gates_out"]),
        "removed": {k: int(v) for k, v in ostats["removed"].items()},
        "reordered": bool(ostats["reordered"]),
        "windows_before": ostats["windows_before"],
        "windows_after": ostats["windows_after"],
        "tier_savings_bytes": None,
        "exchange_savings": None,
    }
    changed = (ostats["reordered"]
               or any(ostats["removed"].values())
               or len(opt_items) != len(orig_items))
    if nsh and orig_items:

        def _cost(seq):
            tiers = {"ici": 0, "dcn": 0}
            count = 0
            if not seq:
                return tiers, count
            segments, fperm = C.plan_remap_windows(
                [F._item_entry(it) for it in seq], n, nloc, perm0)
            sigmas = [s for _ij, s, _p in segments if s is not None]
            if fperm is not None and list(fperm) != list(range(n)):
                sigmas.append(PAR.canonical_sigma(tuple(fperm)))
            for sigma in sigmas:
                count += PAR.remap_exchange_count(tuple(sigma), nloc, nsh,
                                                  itemsize)
                for t, b in C.remap_exchange_bytes_tiers(
                        tuple(sigma), n, nloc, itemsize).items():
                    tiers[t] = tiers.get(t, 0) + b
            return tiers, count

        t0, c0 = _cost(orig_items)
        t1, c1 = (t0, c0) if not changed else _cost(opt_items)
        section["tier_savings_bytes"] = {
            t: int((t0.get(t, 0) - t1.get(t, 0)) * bw) for t in t0}
        section["exchange_savings"] = int((c0 - c1) * bw)
    elif not nsh:
        # scalar registers have no exchange cost; the comparable
        # quantity is the local planner's HBM pass count (bounded:
        # a dry re-plan of very long streams is not worth the host time)
        gates0 = [it for it in orig_items if isinstance(it, C.Gate)]
        if 0 < len(gates0) <= 512 and all(
                isinstance(g.mat, np.ndarray) and g.mat.ndim == 3
                for g in gates0):
            gates1 = [it for it in opt_items if isinstance(it, C.Gate)]
            with C.quiet_planning():
                wb = C.stats(C.plan_circuit(gates0, nloc))["total_passes"]
                wa = C.stats(C.plan_circuit(gates1, nloc))["total_passes"] \
                    if gates1 else 0
            if not changed:
                wa = wb
            section["windows_before"] = int(wb)
            section["windows_after"] = int(wa)
    return section


def explain_circuit(qureg, gates=None) -> ExplainReport:
    """Dry-run the fusion planner over ``gates`` (or the register's
    pending fusion buffer when None) — NO device execution, no drain,
    no telemetry mutation — and return the per-window plan report.

    The predicted window-remap exchange count and per-shard bytes are
    the SAME quantities telemetry records at dispatch time
    (``exchanges_total``/``exchange_bytes_total{op=window_remap}``):
    running the explained stream and diffing the counters must agree
    exactly, and :func:`reconcile_drain` asserts exactly that after
    every sharded drain.  ``final_remap`` is the extra canonical-order
    rematerialization (``op=remap``) the next ``Qureg.amps`` read pays
    when the plan leaves a live permutation behind.

    The circuit optimizer (optimizer.py, docs/design.md §26) rewrites
    the stream before planning, so the whole report prices the
    OPTIMIZED stream — exactly what a drain would execute — and the
    ``optimizer`` section carries the rewrite's accounting: gates
    in/out, removals by kind, remap windows before/after, and the
    projected per-tier exchange savings from the same cost model."""
    from . import fusion as F
    from . import optimizer as _optimizer
    from .ops import fused as _fusedmod
    from .parallel import topology as _topology

    if gates is None:
        buf = getattr(qureg, "_fusion", None)
        items = list(buf.gates) if buf is not None else []
    else:
        items = _as_items(gates)
    n = qureg.num_qubits_in_state_vec
    nsh = F._shard_bits(qureg)
    nloc = n - nsh
    bsz = int(getattr(qureg, "batch_size", 0) or 0)
    bw = max(bsz, 1)
    itemsize = int(np.dtype(qureg.dtype).itemsize)
    sweep_ok = _fusedmod.channel_sweep_enabled(qureg.dtype)
    perm0 = qureg._perm if nsh else None

    # the optimizer rewrite a drain would apply (quiet: no telemetry,
    # no cache-status flips) — everything below prices opt_items; the
    # memory section re-derives the same rewrite through
    # plan_items_quiet, so both views describe one stream
    orig_items = items
    items, ostats = _optimizer.optimize_items(
        items, n=n, nloc=nloc, nsh=nsh, perm0=perm0, quiet=True)
    optimizer_section = _optimizer_section(
        orig_items, items, ostats, n=n, nloc=nloc, nsh=nsh, perm0=perm0,
        itemsize=itemsize, bw=bw)

    register = {
        "qubits": int(qureg.num_qubits_represented),
        "density": bool(qureg.is_density_matrix),
        "state_bits": int(n),
        "shards": int(1 << nsh),
        "shard_bits": int(nsh),
        "nloc": int(nloc),
        "perm0": None if perm0 is None else [int(p) for p in perm0],
        "itemsize": itemsize,
    }
    if bsz:
        from . import batch as _batch

        register["batch"] = _batch.bank_occupancy(qureg)

    windows: list = []
    final_remap = None
    tot_exch = 0
    tot_bytes = 0
    tot_tier = {"ici": 0, "dcn": 0}
    plan_windows = 0
    perm_windows = 0
    mega_windows = 0
    if nsh and items:
        entries = [F._item_entry(it) for it in items]
        segments, final_perm = C.plan_remap_windows(entries, n, nloc, perm0)
        for k, ((i, j), sigma, _perm) in enumerate(segments):
            if C._is_relabel_entry(entries[i]):
                # §28 permutation fold: nothing dispatches — the run is
                # composed into the live perm; any cross-shard component
                # surfaces in final_remap like every deferred hop
                windows.append({"window": k, "start": int(i), "end": int(j),
                                "gates": j - i, "channels": 0,
                                "plan_windows": 0, "perm_windows": 0,
                                "mega_windows": 0, "mega_ops": 0,
                                "kind": "relabel", "sigma": None,
                                "exchanges": 0, "exchange_bytes": 0,
                                "chunks": None})
                continue
            parts, ngates, nchans, pparts, mparts, mops = _segment_stats(
                items[i:j], nloc=nloc, perm=_perm)
            plan_windows += parts
            perm_windows += pparts
            mega_windows += mparts
            entry = {"window": k, "start": int(i), "end": int(j),
                     "gates": ngates, "channels": nchans,
                     "plan_windows": parts, "perm_windows": pparts,
                     "mega_windows": mparts, "mega_ops": mops,
                     "kind": ("mega" if mparts
                              else "perm" if parts == 0 and pparts
                              else "dense"),
                     "sigma": None,
                     "exchanges": 0, "exchange_bytes": 0, "chunks": None}
            if sigma is not None:
                entry.update(_sigma_cost(sigma, n, nloc, nsh, itemsize))
                entry["exchanges"] *= bw
                entry["exchange_bytes"] *= bw
                for t in entry["tier_bytes"]:
                    entry["tier_bytes"][t] *= bw
                    entry["tier_exchanges"][t] *= bw
                    tot_tier[t] += entry["tier_bytes"][t]
                tot_exch += entry["exchanges"]
                tot_bytes += entry["exchange_bytes"]
            windows.append(entry)
        if final_perm is not None and list(final_perm) != list(range(n)):
            from .parallel import dist as PAR

            final_remap = _sigma_cost(
                PAR.canonical_sigma(tuple(final_perm)), n, nloc, nsh,
                itemsize)
            final_remap["exchanges"] *= bw
            final_remap["exchange_bytes"] *= bw
            for t in final_remap["tier_bytes"]:
                final_remap["tier_bytes"][t] *= bw
                final_remap["tier_exchanges"][t] *= bw
            final_remap["final_perm"] = [int(p) for p in final_perm]
    else:
        parts, ngates, nchans, pparts, mparts, mops = _segment_stats(
            items, nloc=nloc)
        plan_windows = parts
        perm_windows = pparts
        mega_windows = mparts
        if items:
            windows.append({"window": 0, "start": 0, "end": len(items),
                            "gates": ngates, "channels": nchans,
                            "plan_windows": parts, "perm_windows": pparts,
                            "mega_windows": mparts, "mega_ops": mops,
                            "kind": ("mega" if mparts
                                     else "perm" if parts == 0 and pparts
                                     else "dense"),
                            "sigma": None,
                            "exchanges": 0, "exchange_bytes": 0,
                            "chunks": None})

    key = F._plan_key(items, nloc, sweep_ok, perm0, nsh) if items else None
    cacheable = key is not None
    hit = cacheable and key in F._plan_cache
    from .parallel import dist as PAR

    plan = {
        "cacheable": cacheable,
        "cache": "hit" if hit else ("miss" if cacheable else "uncacheable"),
        # a plan-cache hit replays a program the compiled-executor
        # lru_cache has already traced (same skeleton + exchange key);
        # a miss may still reuse an executor if the skeleton coincides
        "retrace_expected": (None if not cacheable else not hit),
        "exchange_chunks_key": str(PAR.exchange_config_key() or "auto"),
    }

    # §31 AOT-tier prediction, computed on the SAME live plan key the
    # drain will use (fusion.aot_probe replans quietly and hashes the
    # full semantic identity): "memory" = an in-process executor is
    # live (no disk consult, no counter moves), "hit"/"miss" = what the
    # persistent tier will answer, "disabled"/"uncacheable" otherwise.
    # Pinned drift-0 against the post-run aot_cache_* counters.
    aot = F.aot_probe(qureg, orig_items)
    compile_section = {
        "aot": aot["status"],
        "aot_enabled": aot["enabled"],
        "aot_key": aot["key"],
        "plan_cache": plan["cache"],
    }

    read_exch = final_remap["exchanges"] if final_remap else 0
    read_bytes = final_remap["exchange_bytes"] if final_remap else 0
    # predicted per-device footprint of draining this stream — the
    # governor's analytic model (state x live-copy multiplier + pass
    # arrays, docs/design.md §22) over the EXACT program the drain
    # would dispatch, planned quietly (no telemetry, no cache insert;
    # plan_items_quiet re-applies the same optimizer rewrite, so the
    # ORIGINAL stream goes in and is optimized exactly once)
    from . import governor as _gov

    memory = _gov.explain_memory(qureg, orig_items)
    return ExplainReport(
        register=register,
        items=len(items),
        windows=windows,
        final_remap=final_remap,
        plan=plan,
        compile=compile_section,
        optimizer=optimizer_section,
        memory=memory,
        totals={
            "windows": len(windows),
            "plan_windows": int(plan_windows),
            "perm_windows": int(perm_windows),
            "mega_windows": int(mega_windows),
            "exchanges": int(tot_exch),
            "exchange_bytes": int(tot_bytes),
            "exchanges_with_read": int(tot_exch + read_exch),
            "exchange_bytes_with_read": int(tot_bytes + read_bytes),
            "tier_bytes": {t: int(b) for t, b in tot_tier.items()},
            "weighted_exchange_cost": float(sum(
                _topology.tier_weights()[t] * b
                for t, b in tot_tier.items())),
            "topology": _topology.resolve(1 << nsh).describe()
            if nsh else None,
        },
    )


def format_explain(report: dict) -> str:
    """Fixed-width text table for an :func:`explain_circuit` report —
    the ``report*`` print family's rendering."""
    reg = report["register"]
    head = (f"circuit plan: {reg['qubits']} qubits"
            f"{' (density)' if reg['density'] else ''}, "
            f"{reg['shards']} shard(s)")
    if reg["shard_bits"]:
        head += f" (nloc={reg['nloc']})"
    if reg.get("batch"):
        b = reg["batch"]
        head += (f", batch={b['size']} (bucket={b['bucket']} "
                 f"occupancy={b['occupancy']:.2f})")
    plan = report["plan"]
    head += (f", {report['items']} item(s), plan-cache={plan['cache']}, "
             f"chunks={plan['exchange_chunks_key']}")
    comp = report.get("compile")
    if comp and comp.get("aot") != "disabled":
        head += f", aot={comp['aot']}"
    lines = [head]
    opt = report.get("optimizer")
    if opt:
        rm = opt["removed"]
        oline = (f"optimizer: mode={opt['mode']} "
                 f"gates {opt['gates_in']}->{opt['gates_out']} "
                 f"(cancel={rm['cancel']} merge={rm['merge']} "
                 f"diag={rm['diag_coalesce']} "
                 f"perm={rm.get('perm_coalesce', 0)}"
                 + (" reordered" if opt["reordered"] else "") + ")")
        if opt["windows_before"] is not None:
            oline += f" windows {opt['windows_before']}->{opt['windows_after']}"
        ts = opt.get("tier_savings_bytes")
        if ts is not None:
            oline += (f" saves exch={opt['exchange_savings']} "
                      f"bytes ici={ts['ici']} dcn={ts['dcn']}")
        lines.append(oline)
    cols = f"{'window':>7} {'kind':>8} {'items':>6} {'gates':>6} " \
           f"{'chans':>6} {'exch':>5} {'bytes/shard':>12} {'chunks':>7}" \
           f"  sigma"
    lines.append(cols)

    def row(label, kind, items, gates, chans, entry):
        ch = entry.get("chunks")
        ch_s = f"{ch['half_shard']}/{ch['full_shard']}" if ch else "-"
        sig = entry.get("sigma")
        sig_s = "(" + ",".join(str(p) for p in sig) + ")" if sig else "-"
        lines.append(
            f"{label:>7} {kind:>8} {items:>6} {gates:>6} {chans:>6} "
            f"{entry['exchanges']:>5} {entry['exchange_bytes']:>12} "
            f"{ch_s:>7}  {sig_s}")

    for w in report["windows"]:
        row(str(w["window"]), w.get("kind", "dense"),
            w["end"] - w["start"], w["gates"], w["channels"], w)
    if report["final_remap"]:
        row("read", "-", "-", "-", "-", report["final_remap"])
    t = report["totals"]
    lines.append(
        f"totals: plan_windows={t['plan_windows']}"
        + (f" perm_windows={t['perm_windows']}"
           if t.get("perm_windows") else "")
        + (f" mega_windows={t['mega_windows']}"
           if t.get("mega_windows") else "")
        + f" exchanges={t['exchanges']} bytes={t['exchange_bytes']}"
        + (f" (+{t['exchanges_with_read'] - t['exchanges']} exch / "
           f"+{t['exchange_bytes_with_read'] - t['exchange_bytes']} bytes "
           f"at read)" if report["final_remap"] else ""))
    if t.get("topology"):
        tb = t["tier_bytes"]
        lines.append(
            f"topology: {t['topology']} tier bytes: ici={tb['ici']} "
            f"dcn={tb['dcn']} weighted_cost={t['weighted_exchange_cost']:.0f}")
    mem = report.get("memory")
    if mem:
        line = (f"memory: peak/device={mem['predicted_peak_bytes']} "
                f"(state={mem['state_bytes_per_device']} "
                f"x{mem['live_multiplier']:.2f} + "
                f"arrays={mem['pass_array_bytes']}), "
                f"resident_other={mem['other_resident_bytes']}")
        if mem["budget_bytes"] is not None:
            line += (f", budget={mem['budget_bytes']} "
                     f"policy={mem['policy']} "
                     f"fits={'yes' if mem['fits'] else 'NO'}")
        lines.append(line)
    return "\n".join(lines)


def report_circuit_plan(qureg, gates=None) -> None:
    """Print the plan-explainer table — the introspection member of the
    reference's ``report*`` family (reportQuregParams, reportPerf...)."""
    print(explain_circuit(qureg, gates).table())


# ---------------------------------------------------------------------------
# HLO audit
# ---------------------------------------------------------------------------

# loose word-regex over the whole HLO text: also matches metadata/comment
# mentions, so counts are upper bounds — useful for "is there ANY
# communication" / "none at all" audits
COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|collective-permute|all-gather|all-to-all|"
    r"reduce-scatter)\b")

# exact HLO opcodes (an instruction is "%name = TYPE opcode(args)")
COLLECTIVE_OPS = (
    "all-reduce", "all-reduce-start", "collective-permute",
    "collective-permute-start", "all-gather", "all-gather-start",
    "all-to-all", "reduce-scatter",
)

# one collective-permute instruction's routing table in optimized HLO:
# source_target_pairs={{0,1},{1,0},...}
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:[^{}]|\{[^{}]*\})*)\}")
_PAIR_RE = re.compile(r"\{\s*(\d+)\s*,\s*(\d+)\s*\}")


class CollectiveBudgetError(AssertionError):
    """An audited program exceeded its collective budget."""


class AuditReport:
    """Result of :func:`audit`: ``collectives`` (exact opcode histogram),
    ``matches`` (loose word-regex histogram, an upper bound including
    metadata mentions), ``flops`` / ``bytes_accessed`` / ``cost`` from
    ``Compiled.cost_analysis()``, and the optimized HLO ``text``."""

    __slots__ = ("collectives", "matches", "flops", "bytes_accessed",
                 "cost", "text")

    def __init__(self, collectives, matches, cost, text):
        self.collectives = collectives
        self.matches = matches
        self.cost = cost
        self.flops = cost.get("flops")
        self.bytes_accessed = cost.get("bytes accessed")
        self.text = text

    def count(self, family: str) -> int:
        """Exact occurrences of ``family`` summed with its async
        ``-start`` variant (all-reduce may lower to all-reduce-start +
        -done on some backends)."""
        return (self.collectives.get(family, 0)
                + self.collectives.get(family + "-start", 0))

    @property
    def total(self) -> int:
        return sum(self.collectives.values())

    def tier_counts(self, chips: int) -> dict:
        """Per-interconnect-tier histogram of the compiled program's
        collective-permute instructions under an ``hosts x chips``
        arrangement (parallel/topology.py): an instruction whose routing
        table contains ANY pair crossing a host boundary
        (``src ^ dst >= chips``) counts as "dcn", else "ici" — the
        emulated-topology placement pin hlocheck's per-tier verification
        and tests/test_topology.py assert against real HLO."""
        from .parallel import topology as _topo

        out = {"ici": 0, "dcn": 0}
        for m in _PAIRS_RE.finditer(self.text):
            pairs = [(int(a), int(b))
                     for a, b in _PAIR_RE.findall(m.group(1))]
            split = _topo.split_pair_list(pairs, chips)
            if split["ici"] or split["dcn"]:
                out["dcn" if split["dcn"] else "ici"] += 1
        return out

    def as_dict(self) -> dict:
        return {"collectives": dict(self.collectives),
                "matches": dict(self.matches),
                "flops": self.flops, "bytes_accessed": self.bytes_accessed}

    def __repr__(self) -> str:
        return (f"AuditReport(collectives={self.collectives}, "
                f"flops={self.flops}, bytes_accessed={self.bytes_accessed})")


def _cost_analysis(compiled) -> dict:
    """Normalize Compiled.cost_analysis() across JAX versions (dict, or
    a one-element list of dicts, or unavailable on some backends)."""
    try:
        cost = compiled.cost_analysis()
    # qlint: allow(broad-except): cost_analysis availability and failure types vary per backend/JAX version; the audit degrades to an empty cost dict
    except Exception:  # pragma: no cover - backend-dependent API
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost) if cost else {}


def audit(fn, *args, donate: bool = False) -> AuditReport:
    """Compile ``fn(*args)`` and audit the optimized HLO: the exact
    collective-opcode histogram, the loose word-match histogram, and
    cost_analysis flops/bytes.  Every ambient :class:`CollectiveBudget`
    (entered as a context manager) checks the report before it is
    returned.  Compilation only — the program never executes."""
    import jax

    jfn = jax.jit(fn, donate_argnums=(0,) if donate else ())
    compiled = jfn.lower(*args).compile()
    txt = compiled.as_text()
    collectives: dict = {}
    for op in COLLECTIVE_OPS:
        c = txt.count(f" {op}(")
        if c:
            collectives[op] = c
    matches: dict = {}
    for m in COLLECTIVE_RE.finditer(txt):
        matches[m.group(1)] = matches.get(m.group(1), 0) + 1
    report = AuditReport(collectives, matches, _cost_analysis(compiled), txt)
    for budget in _BUDGET_STACK:
        budget.check(report)
    return report


_BUDGET_STACK: list = []


class CollectiveBudget:
    """Collective-count budget for audited programs.

    ``CollectiveBudget(collective_permute=2)`` caps the exact
    collective-permute count (including the ``-start`` variant) at 2;
    ``exact={"collective-permute": 1}`` pins the whole exact histogram;
    ``total=N`` caps the sum of all collectives; ``allow=(...)`` rejects
    any opcode family outside the set.  ``check(report)`` raises
    :class:`CollectiveBudgetError` on violation.  As a context manager
    the budget becomes ambient: every :func:`audit` inside is checked
    automatically::

        with CollectiveBudget(collective_permute=1):
            introspect.audit(my_sharded_gate, amps, donate=True)
    """

    def __init__(self, exact: Optional[dict] = None,
                 total: Optional[int] = None,
                 allow: Optional[Sequence[str]] = None, **max_ops):
        self.exact = dict(exact) if exact is not None else None
        self.total = total
        self.allow = tuple(allow) if allow is not None else None
        # keyword budgets name op families with underscores
        self.max_ops = {k.replace("_", "-"): int(v)
                        for k, v in max_ops.items()}

    def check(self, report) -> "AuditReport":
        hist = (report.collectives if isinstance(report, AuditReport)
                else dict(report))
        if not isinstance(report, AuditReport):
            report = None

        def fam_count(family):
            return hist.get(family, 0) + hist.get(family + "-start", 0)

        if self.exact is not None and hist != self.exact:
            raise CollectiveBudgetError(
                f"collective budget: expected exactly {self.exact}, "
                f"compiled program has {hist}")
        for family, cap in self.max_ops.items():
            got = fam_count(family)
            if got > cap:
                raise CollectiveBudgetError(
                    f"collective budget: {family} x{got} exceeds the "
                    f"budget of {cap} ({hist})")
        if self.total is not None and sum(hist.values()) > self.total:
            raise CollectiveBudgetError(
                f"collective budget: {sum(hist.values())} collectives "
                f"exceed the total budget of {self.total} ({hist})")
        if self.allow is not None:
            allowed = set(self.allow) | {a + "-start" for a in self.allow}
            extra = set(hist) - allowed
            if extra:
                raise CollectiveBudgetError(
                    f"collective budget: {sorted(extra)} outside the "
                    f"allowed families {sorted(self.allow)} ({hist})")
        return report

    def __enter__(self) -> "CollectiveBudget":
        _BUDGET_STACK.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _BUDGET_STACK.remove(self)


# ---------------------------------------------------------------------------
# Predicted-vs-measured reconciliation
# ---------------------------------------------------------------------------

# active prediction perturbations (perturb_prediction context manager);
# the QT_INTROSPECT_PERTURB env var ("chunks=4" / "scale=2") is folded in
# at reconcile time so operators can arm the drift alarm without code
_PERTURB_STACK: list = []


@contextlib.contextmanager
def perturb_prediction(count: Optional[int] = None,
                       nbytes: Optional[int] = None,
                       chunks: Optional[str] = None,
                       scale: Optional[float] = None) -> Iterator[None]:
    """Inject a planner-policy perturbation into the reconciliation
    prediction — the fault-injection hook proving the predict->measure->
    reconcile loop actually detects drift (resilience.FaultPlan's
    philosophy applied to the cost model).  ``chunks`` forces the
    predicted chunk-config key; ``scale`` multiplies the predicted
    exchange count and bytes; ``count``/``nbytes`` force them
    outright."""
    entry = {"count": count, "nbytes": nbytes, "chunks": chunks,
             "scale": scale}
    _PERTURB_STACK.append(entry)
    try:
        yield
    finally:
        _PERTURB_STACK.remove(entry)


def _env_perturbation() -> Optional[dict]:
    raw = os.environ.get(_PERTURB_ENV, "").strip()
    if not raw:
        return None
    out = {"count": None, "nbytes": None, "chunks": None, "scale": None}
    for part in raw.split(","):
        k, _, v = part.partition("=")
        k = k.strip()
        if k == "chunks":
            out["chunks"] = v.strip()
        elif k == "scale":
            out["scale"] = float(v)
        elif k in ("count", "nbytes"):
            out[k] = int(v)
    return out


def _apply_perturbations(pred: dict) -> dict:
    stack = list(_PERTURB_STACK)
    env = _env_perturbation()
    if env:
        stack.append(env)
    for p in stack:
        if p["scale"] is not None:
            pred["count"] = int(pred["count"] * p["scale"])
            pred["nbytes"] = int(pred["nbytes"] * p["scale"])
        if p["count"] is not None:
            pred["count"] = int(p["count"])
        if p["nbytes"] is not None:
            pred["nbytes"] = int(p["nbytes"])
        if p["chunks"] is not None:
            pred["chunks"] = str(p["chunks"])
    return pred


@functools.lru_cache(maxsize=256)
def _predict_cached(bit_key, n: int, nloc: int, nsh: int, perm_key,
                    itemsize: int, topo_sig):
    # Pure function of the plan inputs, memoized so the per-drain
    # reconciliation stays O(1) on repeated streams — the measured path
    # it is compared against hits the plan cache the same way.
    # ``topo_sig`` (topology.signature) keys the memo on the live
    # QT_TOPOLOGY / planner-mode arrangement: the tier-aware planner
    # emits different sigmas per arrangement, so a stale entry would
    # mispredict across an env flip.
    from .parallel import dist as PAR
    from .parallel import topology as _topo

    count = 0
    nbytes = 0
    tiers = {"ici": 0, "dcn": 0}
    topology = _topo.resolve(1 << nsh)
    segments, _final_perm = C.plan_remap_windows(
        [list(b) for b in bit_key], n, nloc,
        list(perm_key) if perm_key is not None else None)
    for _ij, sigma, _perm in segments:
        if sigma is None:
            continue
        count += PAR.remap_exchange_count(tuple(sigma), nloc, nsh, itemsize)
        nbytes += C.remap_exchange_bytes(tuple(sigma), n, nloc, itemsize)
        for t, (_c, b) in PAR.remap_exchange_tiers(
                tuple(sigma), nloc, nsh, itemsize, topology).items():
            tiers[t] += b
    return count, nbytes, (tiers["ici"], tiers["dcn"])


def predict_window_exchanges(bit_sets: Sequence, n: int, nloc: int,
                             nsh: int, perm0, itemsize: int,
                             batch: int = 0) -> dict:
    """Independent re-derivation of what a sharded drain over
    ``bit_sets`` must exchange (``op=window_remap`` only — the
    canonical-read rematerialization is the separate ``op=remap``):
    re-plan the windows and fold every sigma through the cost model,
    including the per-interconnect-tier byte split under the live
    topology.  This is the prediction reconcile_drain holds the
    measured counters against."""
    from .parallel import dist as PAR
    from .parallel import topology as _topo

    bw = max(int(batch), 1)
    count, nbytes, (ici_b, dcn_b) = _predict_cached(
        tuple(tuple(b) for b in bit_sets), n, nloc, nsh,
        tuple(perm0) if perm0 is not None else None, itemsize,
        _topo.signature(1 << nsh))
    return {"count": count * bw, "nbytes": nbytes * bw,
            "tier_nbytes": {"ici": ici_b * bw, "dcn": dcn_b * bw},
            "chunks": str(PAR.exchange_config_key() or "auto")}


def reconcile_drain(*, bit_sets: Sequence, n: int, nloc: int, nsh: int,
                    perm0, itemsize: int, batch: int,
                    measured_count: float, measured_bytes: float,
                    measured_chunks: str,
                    measured_tier_bytes: Optional[dict] = None
                    ) -> Optional[dict]:
    """Compare a drain's measured window-remap telemetry deltas against
    the independent plan prediction.  Records the prediction into
    ``predicted_exchanges_total`` / ``predicted_exchange_bytes_total``
    (reportPerf's predicted-vs-measured section; bytes carry the
    per-interconnect ``tier`` label so the per-tier series reconcile
    too); any deviation increments ``model_drift_total{kind}`` per
    drifting dimension (count / bytes / chunks / tier_bytes) and emits
    ONE structured JSON log line.  Returns the drift dict (empty when
    the model holds)."""
    if not _telemetry.enabled():
        return None
    pred = predict_window_exchanges(bit_sets, n, nloc, nsh, perm0,
                                    itemsize, batch)
    pred = _apply_perturbations(pred)
    if pred["count"]:
        _telemetry.inc("predicted_exchanges_total", pred["count"],
                       op="window_remap")
    for tier, b in pred["tier_nbytes"].items():
        if b:
            _telemetry.inc("predicted_exchange_bytes_total", b,
                           op="window_remap", tier=tier)
    drift: dict = {}
    if int(measured_count) != int(pred["count"]):
        drift["count"] = {"predicted": int(pred["count"]),
                          "measured": int(measured_count)}
    if int(measured_bytes) != int(pred["nbytes"]):
        drift["bytes"] = {"predicted": int(pred["nbytes"]),
                          "measured": int(measured_bytes)}
    if measured_tier_bytes is not None:
        for tier, b in pred["tier_nbytes"].items():
            if int(measured_tier_bytes.get(tier, 0)) != int(b):
                drift.setdefault("tier_bytes", {})[tier] = {
                    "predicted": int(b),
                    "measured": int(measured_tier_bytes.get(tier, 0))}
    if (pred["count"] or measured_count) and \
            str(measured_chunks) != str(pred["chunks"]):
        drift["chunks"] = {"predicted": str(pred["chunks"]),
                           "measured": str(measured_chunks)}
    if drift:
        for kind in drift:
            _telemetry.inc("model_drift_total", kind=kind)
        _telemetry.flight_event("model_drift",
                                kinds=",".join(sorted(drift)),
                                shards=1 << nsh, items=len(bit_sets))
        _LOG.warning(json.dumps(
            {"event": "model_drift", "kinds": sorted(drift),
             "drift": drift, "shards": 1 << nsh, "items": len(bit_sets)},
            sort_keys=True))
    return drift


def measure_dispatch_floor(calls: int = 64) -> float:
    """Median host cost of dispatching ONE trivial jitted program — the
    live, in-process version of scripts/bench_dispatch.py's per-program
    overhead probe.  Publishes the ``per_program_dispatch_seconds``
    gauge; the §30 per-op attribution section of ``reportPerf`` labels a
    route ``dispatch_bound`` when its mean dispatched-group wall time
    sits within 10% of this floor (the r04->r05 measurement regime,
    flagged live instead of by forensic bisection)."""
    import time

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros(())
    f(x).block_until_ready()  # compile outside the timed loop
    samples = []
    for _ in range(max(8, int(calls))):
        t0 = time.perf_counter()
        f(x).block_until_ready()
        samples.append(time.perf_counter() - t0)
    samples.sort()
    floor = samples[len(samples) // 2]
    _telemetry.set_gauge("per_program_dispatch_seconds", floor)
    return floor


# camelCase mirrors (the reference-style API surface)
explainCircuit = explain_circuit
reportCircuitPlan = report_circuit_plan
