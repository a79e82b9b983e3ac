"""Benchmark suite: one JSON line per BASELINE.json config.

Sizes marked (scaled) are reduced from the BASELINE.json pod-scale targets
to fit the single benchmarking chip (v5e, 16 GB HBM); the workload shape
(gate mix, reduction structure) is preserved.  bench.py remains the
driver-facing headline (config 2).

Usage: python bench_suite.py [--config N] [--all]
       QT_BENCH_CPU=1 for off-TPU smoke runs (tiny sizes).
"""

import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

if os.environ.get("QT_BENCH_CPU") == "1":
    # config 6's 8-shard dryrun needs the virtual mesh; the flag must be
    # set before jax initialises
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

import jax

if os.environ.get("QT_BENCH_CPU") == "1":
    jax.config.update("jax_platforms", "cpu")

import numpy as np

CPU = os.environ.get("QT_BENCH_CPU") == "1"


_LAST_COMPILE_S = [0.0]


def _time_best(fn, reps=3):
    """(best_seconds, last_result, compile_seconds) — result captured so
    callers never rerun the workload just to log it; the warm-up (compile +
    first run) wall is returned AND kept in _LAST_COMPILE_S for _emit
    (compile cost is a first-class metric for a traced-program
    framework).  Configs that time several variants pass the compile_s of
    the variant they report to _emit via _set_compile."""
    t0 = time.perf_counter()
    result = fn()  # warm-up/compile
    _LAST_COMPILE_S[0] = time.perf_counter() - t0
    best = math.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        result = fn()
        best = min(best, time.perf_counter() - t0)
    return best, result, _LAST_COMPILE_S[0]


def _set_compile(compile_s: float) -> None:
    _LAST_COMPILE_S[0] = compile_s


def _emit(config, metric, value, unit, seconds, extra=None):
    rec = {
        "config": config,
        "metric": metric,
        "value": value,
        "unit": unit,
        "seconds": seconds,
        "compile_plus_first_run_s": round(_LAST_COMPILE_S[0], 1),
        "backend": jax.default_backend(),
    }
    rec.update(extra or {})
    print(json.dumps(rec), flush=True)


def config1():
    """12q hadamard + controlledRotateX chain + calcProbOfOutcome, through
    the imperative API (gate-at-a-time dispatch — the reference's model)."""
    import quest_tpu as qt

    n = 12
    env = qt.createQuESTEnv()

    def run():
        q = qt.createQureg(n, env)
        qt.hadamard(q, 0)
        for t in range(1, n):
            qt.controlledRotateX(q, t - 1, t, 0.3)
        return qt.calcProbOfOutcome(q, n - 1, 0)

    def run_fused():
        q = qt.createQureg(n, env)
        with qt.gateFusion(q):
            qt.hadamard(q, 0)
            for t in range(1, n):
                qt.controlledRotateX(q, t - 1, t, 0.3)
        return qt.calcProbOfOutcome(q, n - 1, 0)

    seconds, prob, compile_s = _time_best(run)
    fused_seconds, fused_prob, _ = _time_best(run_fused)
    _set_compile(compile_s)
    gates = n  # 1 H + (n-1) controlled rotations
    _emit(1, "12q API chain gate rate", gates * (1 << n) / seconds,
          "amp_updates_per_sec", seconds,
          {"prob": prob, "gatefusion_seconds": fused_seconds,
           "gatefusion_prob": fused_prob})


def config2():
    """Delegates to bench.py (26q depth-20 random circuit, fused path).
    The CPU smoke run shrinks the register: the full 26q plan through
    interpret-mode Pallas on CPU takes tens of minutes."""
    if CPU:
        os.environ.setdefault("QT_BENCH_QUBITS", "16")
        os.environ.setdefault("QT_BENCH_DEPTH", "4")
    import bench

    bench.main()


def config3():
    """QFT via fused controlled-phase ladders + swaps (cross-shard exercise
    on a mesh; single-chip here). Scaled 30q -> 26q (8 GB f32 SoA)."""
    import jax.numpy as jnp

    from quest_tpu.models import circuits
    from quest_tpu.ops import kernels

    n = 10 if CPU else 26
    jqft = jax.jit(lambda a: circuits.qft_circuit(a, n), donate_argnums=0)

    def run():
        amps = kernels.init_debug_state(1 << n, np.float32)
        amps /= np.sqrt(float(jnp.sum(amps * amps)))
        out = jqft(amps)
        # device-to-host fetch: the timing ends on a value read back
        float(np.asarray(out[0, 0]))
        return out

    seconds, _, _ = _time_best(run)
    gates = n + n * (n - 1) // 2 + n // 2  # H ladder + CPhase ladder + swaps
    _emit(3, f"{n}q QFT gate rate", gates * (1 << n) / seconds,
          "amp_updates_per_sec", seconds, {"gates": gates})


def config4():
    """Density-matrix noise: mixDepolarising + mixTwoQubitKrausMap +
    calcFidelity. Scaled 20q -> 13q rho (2^26 amps, chip-resident)."""
    import quest_tpu as qt

    n = 5 if CPU else 13
    env = qt.createQuESTEnv()
    rng = np.random.default_rng(5)
    # random 2-qubit CPTP map (4 Kraus ops)
    raw = rng.standard_normal((4, 4, 4)) + 1j * rng.standard_normal((4, 4, 4))
    s = np.zeros((4, 4), dtype=complex)
    for k in raw:
        s += k.conj().T @ k
    w = np.linalg.inv(np.linalg.cholesky(s).conj().T)
    ops = [k @ w for k in raw]

    def run(k=1, fused=True):
        rho = qt.createDensityQureg(n, env)
        qt.initPlusState(rho)
        # fused: the whole noise block drains as ONE jitted program —
        # depol channels capture as ChannelItems (the one-pass
        # elementwise pair kernels, in call order) and the 2q Kraus map
        # as a superoperator fold; eager: one dispatch per channel
        if fused:
            with qt.gateFusion(rho):
                for _ in range(k):
                    for q in range(n):
                        qt.mixDepolarising(rho, q, 0.05)
                    qt.mixTwoQubitKrausMap(rho, 0, 1, ops)
        else:
            for _ in range(k):
                for q in range(n):
                    qt.mixDepolarising(rho, q, 0.05)
                qt.mixTwoQubitKrausMap(rho, 0, 1, ops)
        psi = qt.createQureg(n, env)
        qt.initPlusState(psi)
        return qt.calcFidelity(rho, psi)

    # ADVICE r3 (c): emit BOTH eager and fused timings so the faster
    # configuration stays measured and a regression in either is visible
    seconds, fidelity, compile_s = _time_best(run)
    sec2, _, _ = _time_best(lambda: run(2))
    eager_s, _, eager_compile = _time_best(lambda: run(fused=False))
    eager2, _, _ = _time_best(lambda: run(2, fused=False))
    _set_compile(compile_s)
    _emit(4, f"{n}q density noise+fidelity wall-clock", seconds, "seconds",
          seconds, {"fidelity": fidelity,
                    "kdiff_noise_device_s": round(sec2 - seconds, 3),
                    "eager_seconds": eager_s,
                    "eager_compile_s": round(eager_compile, 1),
                    "eager_kdiff_noise_device_s": round(eager2 - eager_s, 3)})


def config5():
    """calcExpecPauliHamil + applyTrotterCircuit on a random PauliHamil.
    Scaled 34q (pod) -> 24q (chip)."""
    import quest_tpu as qt

    n = 8 if CPU else 24
    terms = 16
    env = qt.createQuESTEnv()
    rng = np.random.default_rng(7)
    hamil = qt.createPauliHamil(n, terms)
    codes = rng.integers(0, 4, size=(terms, n))
    coeffs = rng.standard_normal(terms)
    qt.initPauliHamil(hamil, coeffs, codes)

    def run():
        psi = qt.createQureg(n, env)
        qt.initPlusState(psi)
        work = qt.createQureg(n, env)
        e = qt.calcExpecPauliHamil(psi, hamil, work)
        qt.applyTrotterCircuit(psi, hamil, 0.1, 2, 1)
        return e

    seconds, energy, _ = _time_best(run)
    _emit(5, f"{n}q PauliHamil expec+Trotter wall-clock", seconds, "seconds",
          seconds, {"energy": energy})


def config6():
    """Communication-avoiding lazy qubit remap (mpiQulacs-style) on the
    8-shard dryrun: a depth-d stream alternating shard-local and
    sharded-target 2q unitaries, run (a) lazily — relocalizations fold
    into the persistent logical->physical permutation, no swap-back, one
    rematerializing remap at the final read — vs (b) the reference's
    eager per-gate swap-in/swap-out (QuEST_cpu_distributed.c:1447-1545).
    The dispatch-level metric is the number of exchange programs issued
    (half-shard swap_sharded + batched remap_sharded dispatches) plus
    wall clock."""
    import quest_tpu as qt
    from quest_tpu.parallel import dist

    env = qt.createQuESTEnv()
    if env.num_devices < 8:
        _emit(6, "8-shard lazy remap (SKIPPED: needs 8 amp shards)",
              0.0, "seconds", 0.0)
        return
    n = 10 if CPU else 24
    depth = 12
    rng = np.random.default_rng(11)
    g = rng.standard_normal((4, 4)) + 1j * rng.standard_normal((4, 4))
    u, _ = np.linalg.qr(g)

    counts = {"swap": 0, "remap": 0}
    orig_swap, orig_remap = dist.swap_sharded, dist.remap_sharded

    def counting_swap(*a, **k):
        counts["swap"] += 1
        return orig_swap(*a, **k)

    def counting_remap(*a, **k):
        counts["remap"] += 1
        return orig_remap(*a, **k)

    def run():
        q = qt.createQureg(n, env)
        for _ in range(depth):
            qt.multiQubitUnitary(q, [0, 1], u)          # shard-local
            qt.multiQubitUnitary(q, [n - 2, n - 1], u)  # sharded targets
        return qt.calcProbOfOutcome(q, 0, 0)

    dist.swap_sharded, dist.remap_sharded = counting_swap, counting_remap
    try:
        dist.use_lazy_remap(True)
        lazy_s, lazy_p, compile_s = _time_best(run)
        counts["swap"] = counts["remap"] = 0
        run()
        lazy_exchanges = counts["swap"] + counts["remap"]
        dist.use_lazy_remap(False)
        eager_s, eager_p, _ = _time_best(run)
        counts["swap"] = counts["remap"] = 0
        run()
        eager_exchanges = counts["swap"] + counts["remap"]
    finally:
        dist.swap_sharded, dist.remap_sharded = orig_swap, orig_remap
        dist.use_lazy_remap(True)
    _set_compile(compile_s)
    _emit(6, f"{n}q 8-shard lazy-remap wall-clock", lazy_s, "seconds",
          lazy_s,
          {"eager_seconds": eager_s,
           "lazy_exchange_dispatches": lazy_exchanges,
           "eager_exchange_dispatches": eager_exchanges,
           "exchange_reduction": round(
               eager_exchanges / max(lazy_exchanges, 1), 2),
           "prob_delta": abs(lazy_p - eager_p)})


def config7():
    """Pipelined chunked shard exchange A/B (ISSUE 3): the distributed
    hot-path exchanges (sharded-target 1q gate, half-shard swap, batched
    window remap) run monolithic (C=1) vs chunk-pipelined over a chunk
    sweep C in {1, 2, 4, 8} on the 8-shard dryrun, measuring wall clock,
    HLO collective-permute dispatch counts, and the per-exchange ICI
    volume (circuit.remap_exchange_bytes for the remap).  On CPU there is
    no async collective to overlap, so this config measures the OVERHEAD
    side of the pipeline (the fallback-threshold calibration —
    dist.PIPELINE_MIN_BYTES); the overlap win needs ICI (docs/design.md
    §17)."""
    import jax.numpy as jnp

    import quest_tpu as qt
    from quest_tpu import circuit as CIRC
    from quest_tpu.parallel import dist

    env = qt.createQuESTEnv()
    if env.num_devices < 8:
        _emit(7, "8-shard pipelined exchange (SKIPPED: needs 8 amp shards)",
              0.0, "seconds", 0.0)
        return
    n = 20 if CPU else 26
    reps = 8          # exchanges per timed run (amortizes dispatch noise)
    rng = np.random.default_rng(13)
    h = (1 / np.sqrt(2)) * np.array([[1.0, 1], [1, -1]])
    m = jnp.asarray(np.stack([h, np.zeros((2, 2))]))
    sigma = dist.canonical_sigma(
        tuple([n - 1, 1] + list(range(2, n - 1)) + [0]))
    nloc = n - dist.num_shard_bits(env.mesh)
    shard_bytes = 2 * (1 << nloc) * (4 if jnp.zeros(()).dtype == jnp.float32
                                     else 8)

    def fresh():
        a = rng.standard_normal((2, 1 << n))
        a /= np.sqrt((a ** 2).sum())
        return jax.device_put(jnp.asarray(a), env.amp_sharding())

    def run_gate(c):
        a = fresh()
        for _ in range(reps):
            a = dist.apply_matrix_1q_sharded(
                a, m, mesh=env.mesh, num_qubits=n, target=n - 1, chunks=c)
        a.block_until_ready()
        return a

    def run_swap(c):
        a = fresh()
        for _ in range(reps):
            a = dist.swap_sharded(a, mesh=env.mesh, num_qubits=n,
                                  qb_low=0, qb_high=n - 1, chunks=c)
        a.block_until_ready()
        return a

    def run_remap(c):
        a = fresh()
        for _ in range(reps):
            a = dist.remap_sharded(a, mesh=env.mesh, num_qubits=n,
                                   sigma=sigma, chunks=(c, c))
        a.block_until_ready()
        return a

    def permute_count(c):
        jfn = jax.jit(lambda a: dist.apply_matrix_1q_sharded(
            a, m, mesh=env.mesh, num_qubits=n, target=n - 1, chunks=c),
            donate_argnums=0)
        txt = jfn.lower(fresh()).compile().as_text()
        return (txt.count(" collective-permute(")
                + txt.count(" collective-permute-start("))

    sweep = {}
    compile_s = 0.0
    for c in (1, 2, 4, 8):
        gate_s, _, cs = _time_best(lambda c=c: run_gate(c))
        swap_s, _, _ = _time_best(lambda c=c: run_swap(c))
        remap_s, _, _ = _time_best(lambda c=c: run_remap(c))
        if c == 1:
            compile_s = cs
            mono = gate_s
        sweep[f"C{c}"] = {
            "gate_s": round(gate_s, 4), "swap_s": round(swap_s, 4),
            "remap_s": round(remap_s, 4),
            "gate_permute_dispatches": permute_count(c) * reps,
        }
    auto = dist.exchange_chunks(shard_bytes)
    auto_s, _, _ = _time_best(lambda: run_gate(None))
    _set_compile(compile_s)
    _emit(7, f"{n}q 8-shard pipelined-exchange wall-clock (auto C={auto})",
          auto_s, "seconds", auto_s,
          {"monolithic_seconds": mono,
           "auto_over_monolithic": round(auto_s / mono, 3),
           "chunk_sweep": sweep,
           "shard_bytes": shard_bytes,
           "remap_exchange_bytes_per_shard": CIRC.remap_exchange_bytes(
               sigma, n, nloc),
           "pipeline_min_bytes": dist.PIPELINE_MIN_BYTES})


def config8():
    """Telemetry-instrumented fused chain (ISSUE 4): runs with
    QT_TELEMETRY=on and dumps the full metrics snapshot JSON
    (TELEMETRY_snapshot.json, next to this timing line) so a bench run
    leaves behind the exchange/window/dispatch accounting of its own
    workload.  The <5% enabled-mode overhead gate is the separate
    scripts/bench_telemetry.py guard (make verify-telemetry)."""
    import quest_tpu as qt
    from quest_tpu import telemetry

    n = 10 if CPU else 22
    depth = 8
    env = qt.createQuESTEnv()
    sharded = env.num_devices >= 8 and (1 << n) >= 8 * env.num_devices
    rng = np.random.default_rng(23)
    g = rng.standard_normal((4, 4)) + 1j * rng.standard_normal((4, 4))
    u, _ = np.linalg.qr(g)
    prev_mode = telemetry.mode_name()
    telemetry.configure("on")

    def run():
        q = qt.createQureg(n, env)
        with qt.gateFusion(q):
            for _ in range(depth):
                for t in range(n):
                    qt.hadamard(q, t)
                qt.multiQubitUnitary(q, [0, 1], u)
                if sharded:  # exercise the window-remap accounting
                    qt.multiQubitUnitary(q, [n - 2, n - 1], u)
        return qt.calcProbOfOutcome(q, 0, 0)

    try:
        seconds, prob, compile_s = _time_best(run)
        telemetry.reset()
        run()  # the snapshot reflects exactly ONE instrumented run
        snap = telemetry.snapshot()
        path = os.path.abspath("TELEMETRY_snapshot.json")
        with open(path, "w") as f:
            json.dump(snap, f, indent=1)
        _set_compile(compile_s)
        _emit(8, f"{n}q telemetry-instrumented fused chain", seconds,
              "seconds", seconds,
              {"prob": prob, "snapshot_file": path,
               "exchanges_total": telemetry.counter_total(
                   "exchanges_total"),
               "exchange_bytes_total": telemetry.counter_total(
                   "exchange_bytes_total"),
               "fusion_windows_total": telemetry.counter_total(
                   "fusion_windows_total"),
               "dispatch_total": telemetry.counter_total(
                   "dispatch_total")})
    finally:
        telemetry.configure(prev_mode)


def config9():
    """Batched-vs-looped ensemble A/B (round-11): B copies of a depth-4
    layered ansatz as one (B, 2, 2^n) BatchedQureg bank against B
    independent scalar runs, B in {1, 4, 16, 64}.  The per-B timing rows
    (circuits/sec both arms, per-circuit latency, speedup) land in the
    standard BENCH artifact; the >= 4x-at-B=16 acceptance gate is the
    separate scripts/bench_batch.py guard (make verify-batch)."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "scripts"))
    import bench_batch

    n = 10 if CPU else 20
    t0 = time.perf_counter()
    _env, rows = bench_batch.run_ab(n, depth=4, batches=[1, 4, 16, 64],
                                    reps=3)
    _set_compile(0.0)  # warm-up folded into each row's own best-of loop
    at16 = next(r for r in rows if r["batch"] == 16)
    _emit(9, f"{n}q batched-vs-looped ensemble throughput",
          at16["batched_circuits_per_sec"], "circuits_per_sec",
          round(time.perf_counter() - t0, 3),
          {"speedup_at_16": at16["speedup"],
           "per_circuit_ms_at_16": at16["batched_per_circuit_ms"],
           "results": rows})


def config10():
    """Plan-explainer snapshot (ISSUE 8): dry-run the fusion planner over
    the config-6 workload (the 8-shard alternating local/sharded 2q
    stream) with introspect.explain_circuit — no device execution — and
    dump the per-window report (EXPLAIN_snapshot.json, the predictive
    twin of config 8's post-hoc TELEMETRY_snapshot.json).  The stream is
    then actually drained so the timing line carries the reconciliation
    verdict: predicted vs measured window-remap exchanges and
    model_drift_total (0 = the cost model holds)."""
    import quest_tpu as qt
    from quest_tpu import telemetry

    env = qt.createQuESTEnv()
    if env.num_devices < 8:
        _emit(10, "plan-explainer snapshot (SKIPPED: needs 8 amp shards)",
              0.0, "seconds", 0.0)
        return
    n = 10 if CPU else 24
    depth = 12
    rng = np.random.default_rng(11)
    g = rng.standard_normal((4, 4)) + 1j * rng.standard_normal((4, 4))
    u, _ = np.linalg.qr(g)
    prev_mode = telemetry.mode_name()
    telemetry.configure("on")
    try:
        q = qt.createQureg(n, env)
        qt.startGateFusion(q)
        for _ in range(depth):
            qt.multiQubitUnitary(q, [0, 1], u)          # shard-local
            qt.multiQubitUnitary(q, [n - 2, n - 1], u)  # sharded targets
        t0 = time.perf_counter()
        report = qt.explainCircuit(q)   # dry-run: nothing executes
        explain_s = time.perf_counter() - t0
        path = os.path.abspath("EXPLAIN_snapshot.json")
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
        telemetry.reset()
        qt.stopGateFusion(q)            # the real drain
        measured = telemetry.counter_sum("exchanges_total",
                                         op="window_remap")
        measured_bytes = telemetry.counter_sum("exchange_bytes_total",
                                               op="window_remap")
        _set_compile(0.0)  # the explainer never traces
        _emit(10, f"{n}q 8-shard plan-explainer dryrun", explain_s,
              "seconds", explain_s,
              {"snapshot_file": path,
               "windows": report["totals"]["windows"],
               "predicted_exchanges": report["totals"]["exchanges"],
               "predicted_exchange_bytes":
                   report["totals"]["exchange_bytes"],
               "measured_exchanges": measured,
               "measured_exchange_bytes": measured_bytes,
               "model_drift_total": telemetry.counter_total(
                   "model_drift_total")})
    finally:
        telemetry.configure(prev_mode)


def config11():
    """Budget-constrained A/B (ISSUE 9): the config-10 style alternating
    local/sharded 2q stream, run once unconstrained and once under a
    QT_HBM_BUDGET_BYTES pinned just below the unconstrained predicted
    peak — the memory governor walks its degradation ladder (exchange
    -chunk bump / program split / spill) and the run must still complete
    bit-identically.  Dumps the predictor numbers, ladder counters, and
    both timings (GOVERNOR_snapshot.json, the memory twin of config 8's
    TELEMETRY_snapshot.json)."""
    import warnings

    import quest_tpu as qt
    from quest_tpu import governor, telemetry

    env = qt.createQuESTEnv()
    n = 13 if CPU else 24
    depth = 6
    rng = np.random.default_rng(29)
    g = rng.standard_normal((4, 4)) + 1j * rng.standard_normal((4, 4))
    u, _ = np.linalg.qr(g)

    def run():
        q = qt.createQureg(n, env)
        with qt.gateFusion(q):
            for _ in range(depth):
                qt.multiQubitUnitary(q, [0, 1], u)          # shard-local
                qt.multiQubitUnitary(q, [n - 2, n - 1], u)  # sharded
        amps = np.asarray(q.amps)
        qt.destroyQureg(q, env)
        return amps

    prev_mode = telemetry.mode_name()
    telemetry.configure("on")
    os.environ.pop("QT_HBM_BUDGET_BYTES", None)
    governor.reset()
    try:
        run()  # warm the plan + executor caches
        t0 = time.perf_counter()
        want = run()
        free_s = time.perf_counter() - t0

        # the unconstrained predicted peak for this exact stream
        os.environ["QT_HBM_BUDGET_BYTES"] = str(1 << 40)
        governor.reset()
        q = qt.createQureg(n, env)
        with qt.gateFusion(q):
            for _ in range(depth):
                qt.multiQubitUnitary(q, [0, 1], u)
                qt.multiQubitUnitary(q, [n - 2, n - 1], u)
            prediction = governor.explain_memory(q, q._fusion.gates)
        qt.destroyQureg(q, env)

        budget = prediction["predicted_total_bytes"] - 1
        os.environ["QT_HBM_BUDGET_BYTES"] = str(budget)
        governor.reset()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            run()  # warm under the constrained config
            t0 = time.perf_counter()
            got = run()
            governed_s = time.perf_counter() - t0
        identical = bool(np.array_equal(want, got))
        snap = {
            "budget_bytes": budget,
            "prediction": prediction,
            "bit_identical": identical,
            "unconstrained_seconds": round(free_s, 5),
            "governed_seconds": round(governed_s, 5),
            "degradations": telemetry.snapshot().get("counters", {}).get(
                "governor_degradations_total", {}),
            "spills_total": telemetry.counter_total("spills_total"),
            "spill_bytes_total": telemetry.counter_total(
                "spill_bytes_total"),
            "oom_retries_total": telemetry.counter_total(
                "oom_retries_total"),
        }
        path = os.path.abspath("GOVERNOR_snapshot.json")
        with open(path, "w") as f:
            json.dump(snap, f, indent=1)
        _set_compile(0.0)  # warmed above under each config
        _emit(11, f"{n}q budget-constrained governed drain", governed_s,
              "seconds", governed_s,
              {"snapshot_file": path,
               "unconstrained_seconds": round(free_s, 5),
               "governed_over_unconstrained": round(
                   governed_s / free_s, 3) if free_s else None,
               "budget_bytes": budget,
               "predicted_peak_bytes":
                   prediction["predicted_peak_bytes"],
               "bit_identical": identical})
    finally:
        os.environ.pop("QT_HBM_BUDGET_BYTES", None)
        governor.reset()
        telemetry.configure(prev_mode)


def config12():
    """Multi-tenant serving saturation A/B (ISSUE 11): a seeded
    open-loop Poisson arrival trace replayed against the continuous
    batcher (quest_tpu.serve.SimServer, window-granular admission +
    preempt-to-checkpoint) and against batch-at-once per-request
    EnsembleScheduler drains.  The timing line carries the serving
    headline (continuous circuits/sec) plus the A/B speedup, bank
    occupancy, and per-class p50/p99 latency; the >= 2x-throughput /
    <= 2x-interactive-p99 acceptance gates are the separate
    scripts/bench_serve.py guard (make verify-serve)."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "scripts"))
    import bench_serve

    n = 8
    t0 = time.perf_counter()
    # the trace length is NOT scaled down on CPU: the continuous win
    # comes from backlog coalescing into full banks, which a short
    # trace never builds
    rec = bench_serve.run(n=n, reps=1 if CPU else 2)
    _set_compile(0.0)  # warm-up/calibration folded into run()'s phases
    cont = rec["continuous"]
    _emit(12, f"{n}q continuous-batching serving throughput",
          cont["circuits_per_sec"], "circuits_per_sec",
          round(time.perf_counter() - t0, 3),
          {"speedup_vs_batch_at_once": rec["speedup"],
           "baseline_circuits_per_sec":
               rec["baseline"]["circuits_per_sec"],
           "bank_occupancy_mean": cont["bank_occupancy_mean"],
           "interactive_p99_ratio": rec["interactive_p99_ratio"],
           "interactive_e2e": cont.get("interactive", {}).get("e2e"),
           "preemptions": cont["preemptions"],
           "resumes": cont["resumes"],
           "arrival_rate_per_sec": rec["arrival_rate_per_sec"]})


def config13():
    """Pod-topology tier-aware planner A/B (ISSUE 12): the config-6
    style churn workload drained on the emulated slow-DCN 2x4 topology
    under the flat vs the hierarchical remap planner
    (scripts/bench_pod.py).  The timing line carries the measured DCN
    byte reduction (the headline — must be >= 2x, gated separately by
    make verify-pod) plus the modeled reduction, the weighted-cost
    ratio, and the bit-identity/drift checks."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "scripts"))
    import bench_pod

    t0 = time.perf_counter()
    try:
        rec = bench_pod.run(n=10 if CPU else 24, reps=10)
    except RuntimeError as e:
        _emit(13, f"2x4 tier-aware DCN reduction (SKIPPED: {e})",
              0.0, "dcn_reduction_x", 0.0)
        return
    _set_compile(0.0)  # both arms warm inside run()
    _emit(13, f"{rec['n']}q 2x4 tier-aware DCN byte reduction",
          rec["measured_dcn_reduction"], "dcn_reduction_x",
          round(time.perf_counter() - t0, 3),
          {"modeled_dcn_reduction": rec["modeled_dcn_reduction"],
           "weighted_cost_reduction": rec["weighted_cost_reduction"],
           "flat_dcn_bytes": rec["flat"]["measured"].get("dcn", 0),
           "hier_dcn_bytes": rec["hier"]["measured"].get("dcn", 0),
           "bit_identical": rec["bit_identical"],
           "model_drift": rec["flat"]["drift"] + rec["hier"]["drift"],
           "topology": rec["topology"]})


def config14():
    """Circuit-optimizer A/B (ISSUE 13): QT_OPTIMIZER=on vs off on a
    config-2-style random circuit, a QFT-like phase-heavy ladder, and
    the config-6-style remap churn (scripts/bench_optimizer.py).  The
    timing line carries the headline wall-clock speedup plus per-workload
    exchange reductions and the parity/drift checks."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "scripts"))
    import bench_optimizer

    t0 = time.perf_counter()
    try:
        rec = bench_optimizer.run(n=10 if CPU else 24,
                                  depth=24 if CPU else 60)
    except RuntimeError as e:
        _emit(14, f"optimizer A/B (SKIPPED: {e})", 0.0, "speedup_x", 0.0)
        return
    _set_compile(0.0)  # both arms warm inside run()
    w = rec["workloads"]
    _emit(14, f"{rec['n']}q circuit-optimizer wall-clock speedup",
          rec["optimizer_speedup_x"], "speedup_x",
          round(time.perf_counter() - t0, 3),
          {name: {"speedup_x": r["speedup_x"],
                  "exchange_reduction_x": r["exchange_reduction_x"],
                  "gates": f"{r['on']['gates_in']}->{r['on']['gates_out']}",
                  "max_abs_err": r["max_abs_err"],
                  "drift": r["on"]["drift"] + r["off"]["drift"]}
           for name, r in w.items()})


def config15():
    """Serving-layer chaos replay (ISSUE 14): the seeded fault-injection
    harness (scripts/chaos_serve.py) replays three deterministic
    multi-tenant traces — fault-free baseline vs a FaultPlan covering
    bank faults, checkpoint-IO faults, shard AND host loss + mesh heal,
    OOM bisection, and a NaN-poisoned job.  The timing line carries the
    non-poison availability headline (must be 100%, gated separately by
    make verify-chaos) plus failover MTTR, bit-identity, and the
    retry/quarantine/failover/heal counters."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "scripts"))
    import chaos_serve

    t0 = time.perf_counter()
    rec = chaos_serve.run()
    _set_compile(0.0)  # A/B replays warm inside run()
    _emit(15, "serving chaos replay non-poison availability",
          rec["availability_pct"], "chaos_availability_pct",
          round(time.perf_counter() - t0, 3),
          {"ok": rec["ok"],
           "failover_mttr_seconds": rec["failover_mttr_seconds"],
           "failovers": rec["failovers"],
           "heals": rec["heals"],
           "bank_retries": rec["bank_retries"],
           "quarantined": rec["quarantined"],
           "bit_identical": rec["bit_identical"],
           "completed": rec["completed"],
           "seeds": rec["seeds"]})


def config16():
    """Permutation fast paths + sparse state prep (ISSUE 15):
    QT_PERM_FAST=on vs off on a ripple-carry-adder-style CNOT/Toffoli
    chain, a relabel-only SWAP churn, and sparse clustered-state
    preparation (scripts/bench_sparse.py, arXiv:2504.08705).  Two
    timing lines: the permutation wall-clock speedup and the
    sparse-init speedup, each with the parity/drift/zero-collective
    checks in tow."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "scripts"))
    import bench_sparse

    t0 = time.perf_counter()
    try:
        rec = bench_sparse.run(n=16 if CPU else 26,
                               depth=60 if CPU else 100)
    except RuntimeError as e:
        _emit(16, f"perm fast-path A/B (SKIPPED: {e})", 0.0,
              "perm_speedup_x", 0.0)
        return
    _set_compile(0.0)  # both arms warm inside run()
    seconds = round(time.perf_counter() - t0, 3)
    w = rec["workloads"]
    _emit(16, f"{rec['n']}q permutation-lowering wall-clock speedup",
          rec["perm_speedup_x"], "perm_speedup_x", seconds,
          {name: {"speedup_x": w[name]["speedup_x"],
                  "max_abs_err": w[name]["max_abs_err"],
                  "drift": w[name]["on"]["drift"]
                  + w[name]["off"]["drift"]}
           for name in ("relabel", "ripple")}
          | {"relabel_read_collectives":
             sum(w["relabel"]["read_collectives"].values()),
             "relabel_window_exchanges":
             w["relabel"]["on"]["window_remap_exchanges"]})
    _emit(16, f"{rec['n']}q sparse clustered-state init speedup",
          rec["sparse_init_speedup_x"], "sparse_init_speedup_x", seconds,
          {"nonzeros": w["sparse"]["sparse"]["nonzeros"],
           "max_abs_err": w["sparse"]["max_abs_err"]})


def config17():
    """Window megakernel (ISSUE 18 / docs/design.md §29):
    QT_MEGAKERNEL=on vs off on the dense-window drain
    (scripts/bench_megakernel.py).  One timing line —
    ``megakernel_speedup_x``, the chained-plan device marginal of the
    off arm over the on arm — with bit-parity, drift==0-both-arms, and
    megawin-routing checks in tow."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "scripts"))
    import bench_megakernel

    t0 = time.perf_counter()
    # devices=1 under the CPU smoke mesh: sharding 14q across the 8
    # virtual devices leaves nloc below the fused-window size, so the
    # drain-half routing telemetry would be vacuous
    rec = bench_megakernel.run(n=14 if CPU else 22,
                               depth=60 if CPU else 40,
                               devices=1 if CPU else None)
    _set_compile(0.0)  # both arms warm inside run()
    seconds = round(time.perf_counter() - t0, 3)
    _emit(17, f"{rec['n']}q dense-window megakernel A/B speedup",
          rec["megakernel_speedup_x"], "megakernel_speedup_x", seconds,
          {"max_abs_err": rec["max_abs_err"],
           "drift": rec["drain"]["on"]["drift"]
           + rec["drain"]["off"]["drift"],
           "programs_per_iter_off":
           rec["plan"]["off"]["programs_per_iter"],
           "programs_per_iter_on": rec["plan"]["on"]["programs_per_iter"],
           "megawin_groups": rec["plan"]["on"]["megawin_groups"],
           "mega_dispatches": rec["drain"]["on"]["mega_dispatches"],
           "hbm_round_trips_per_window":
           rec["drain"]["on"]["hbm_round_trips_per_window"]})


def config18():
    """Observability front door (ISSUE 19 / docs/design.md §30): one
    chaotic serving run with the live HTTP endpoint up — scrapes
    /metrics and /healthz over the wire, dumps the per-job request
    traces (tracez span trees) and the incident flight records to a
    demo directory, and reports trace completeness.  The timing line
    carries the count of completed jobs whose span trees reconstruct
    complete, plus the flight-dump reasons and artifact paths."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "scripts"))
    import json
    import tempfile
    import urllib.request

    import chaos_serve as cs

    import quest_tpu as qt
    from quest_tpu import resilience as R
    from quest_tpu import serve as S
    from quest_tpu import telemetry as T

    t0 = time.perf_counter()
    demo_dir = tempfile.mkdtemp(prefix="qt_obs_demo_")
    old_dir = os.environ.get("QT_SERVE_FLIGHT_DIR")
    os.environ["QT_SERVE_FLIGHT_DIR"] = demo_dir
    try:
        env = qt.createQuESTEnv()
        plan_spec, poisoned = cs._schedule(11)
        server = S.SimServer(env, window=cs.WINDOW, max_batch=4,
                             retries=4, watchdog=1,
                             quarantine=(100, 3600.0),
                             faults=R.FaultPlan(plan_spec))
        try:
            host, port = server.serve_http()
            handles = []
            for i, (tenant, theta, prio, measure) in enumerate(
                    cs._trace(11)):
                handles.append(server.submit(
                    cs._circ(theta), num_qubits=cs.N, tenant=tenant,
                    priority=prio, measure=measure))
                if i % 3 == 2:
                    for _ in range(2):
                        server.step()
            server.run_until_idle(max_steps=cs.STEP_BOUND)
            base = f"http://{host}:{port}"
            metrics = urllib.request.urlopen(
                base + "/metrics").read().decode()
            healthz = json.loads(urllib.request.urlopen(
                base + "/healthz").read().decode())
            traces = {h.id: server.tracez(h) for h in handles}
            trace_path = os.path.join(demo_dir, "job_traces.json")
            with open(trace_path, "w") as f:
                json.dump(traces, f, sort_keys=True)
            done = sum(1 for h in handles if h.state == "done")
            complete = sum(1 for tz in traces.values()
                           if tz and tz.get("complete"))
            reasons = []
            for path in server.flight_dumps:
                with open(path) as f:
                    reasons.append(json.load(f)["reason"])
            dump_count = len(server.flight_dumps)
        finally:
            server.close()
    finally:
        if old_dir is None:
            os.environ.pop("QT_SERVE_FLIGHT_DIR", None)
        else:
            os.environ["QT_SERVE_FLIGHT_DIR"] = old_dir
    _set_compile(0.0)  # host-side scheduling demo; no fresh kernels
    _emit(18, "observability: complete request traces under chaos",
          float(complete), "traces_complete",
          round(time.perf_counter() - t0, 3),
          {"jobs_done": done,
           "poisoned": sorted(poisoned),
           "metrics_live": metrics == T.prometheus_text(),
           "healthz_status": healthz["status"],
           "flight_dumps": dump_count,
           "flight_dump_reasons": reasons,
           "demo_dir": demo_dir,
           "job_traces": trace_path})


def config19():
    """Cold-start elimination (ISSUE 20 / docs/design.md §31): the
    persistent AOT executable cache measured where it matters — the
    first-request latency of a FRESH PROCESS.  scripts/bench_coldstart
    launches the same sharded workload twice against one QT_AOT_CACHE
    directory (empty, then warm) in subprocesses; the second child must
    deserialize instead of compiling.  Emits the uncached/cached
    first-request ratio — higher is better, and a regression that
    reintroduces the compile collapses it toward 1."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "scripts"))
    import bench_coldstart as bc

    t0 = time.perf_counter()
    rec = bc.run(check=False)
    _set_compile(rec["uncached_first_s"])  # the cost the cache removes
    _emit(19, "cold start: fresh-process first-request speedup",
          rec["value"], "coldstart_speedup_x",
          round(time.perf_counter() - t0, 3),
          {"uncached_first_s": rec["uncached_first_s"],
           "cached_first_s": rec["cached_first_s"],
           "cached_steady_s": rec["cached_steady_s"],
           "cached_hits": rec["cached_aot"]["hits"],
           "cached_puts": rec["cached_aot"]["puts"],
           "bit_identical": rec["bit_identical"]})


CONFIGS = {1: config1, 2: config2, 3: config3, 4: config4, 5: config5,
           6: config6, 7: config7, 8: config8, 9: config9, 10: config10,
           11: config11, 12: config12, 13: config13, 14: config14,
           15: config15, 16: config16, 17: config17, 18: config18,
           19: config19}


def main():
    if "--config" in sys.argv:
        which = [int(sys.argv[sys.argv.index("--config") + 1])]
    else:
        which = sorted(CONFIGS)
    for c in which:
        CONFIGS[c]()


if __name__ == "__main__":
    main()
