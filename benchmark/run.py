#!/usr/bin/env python3
"""Run one cell of the quest_tpu benchmark on the chip this is started on.

    python3 benchmark/run.py --workload rc30.sweep --seed 7 --seconds 51 \
        --trace 0

Everything a cell is made of is found by name from ``BENCHMARK.json`` at
the root of the checkout: the configuration file it names, the traffic
mix ``benchmark/traffic/<traffic>.json``, the circuit family module
``benchmark/families/<family>.py`` the configuration names (the register,
the circuit body and its reference), the mix's ``prepare`` and ``read``
modules under ``benchmark/prepares/`` and ``benchmark/reads/``, the
limits of the correctness comparison ``benchmark/limits/<workload>.json``
and, with ``--trace 1``, one reader ``benchmark/metrics/<metric>.py`` per
per-layer metric.  A new cell needs new files and a ``workloads`` entry,
and no edit here.

A run builds the register on the device, runs the mix's first circuit as
the warm-up (every program the window uses compiles or loads from the
persistent compilation cache at ``<checkout>/.jax_cache`` there), then
drives circuits through the ``qt.*`` API in a closed loop of one caller
for ``--seconds``.  With ``--trace 1`` the
window runs under the JAX profiler and the per-layer metrics are read
from its trace.  After the window the reference of ``benchmark/reference``
recomputes what the mix checks, and the last line of standard output is
the result as one JSON object.  Without a TPU, with fewer chips than the
cell asks for, or on a device kind not in ``peaks.json``, it exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from contextlib import contextmanager  # noqa: E402
from dataclasses import dataclass  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import tracefile  # noqa: E402


class BenchError(Exception):
    """A run that cannot give a result (no chip, missing file)."""


# ---------------------------------------------------------------------------
# the cell, found by name
# ---------------------------------------------------------------------------


def _json(path):
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    family: object
    limits: dict
    end_to_end: list
    per_layer: list


def _applies(metric, workload):
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: str = ROOT, overrides=None,
              limits=None) -> Cell:
    """The cell ``workload`` of ``root``'s BENCHMARK.json; ``overrides``
    replace configuration keys and ``limits`` the limits file (the CPU
    rehearsal runs at a smaller size)."""
    bench_path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(bench_path):
        raise BenchError(f"no BENCHMARK.json in {root}")
    bench = _json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}")
    w = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = dict(_json(os.path.join(root, entry["file"])))
    cfg.update(overrides or {})
    here = os.path.join(root, "benchmark")
    mix = _json(os.path.join(here, "traffic", w["traffic"] + ".json"))
    fam = importlib.import_module("benchmark.families." + cfg["family"])
    limits = limits or _json(os.path.join(here, "limits", workload + ".json"))
    return Cell(workload, int(w["chips"]), cfg, mix, fam.Family(cfg),
                limits,
                [m for m in bench["end_to_end"] if _applies(m, workload)],
                [m for m in bench["per_layer"] if _applies(m, workload)])


# ---------------------------------------------------------------------------
# the traffic: a closed loop of circuits drawn from the seed
# ---------------------------------------------------------------------------


@dataclass
class Circuit:
    index: int
    init: tuple          # the prepare module's spec, or None: state carried
    params: object       # the family's parameters (angles) or None
    read: object         # the read module's spec
    value: object = float("nan")
    t0: float = 0.0
    t1: float = 0.0
    ok: bool = False


def _module(kind: str, name: str):
    return importlib.import_module(f"benchmark.{kind}.{name}")


class Stream:
    """Circuits of one traffic mix, in a closed loop of one caller.  Every
    seed gives the same sizes and the same compiled programs; the seed
    draws the parameters, the prepared states and what is read.  The mix
    names its ``prepare`` and ``read`` modules (``benchmark/prepares/``,
    ``benchmark/reads/``); ``params`` is "fresh" (drawn for every circuit)
    or "fixed" (one draw for all), and with ``carry`` only the first
    circuit is prepared and each later one runs on the state left
    before it."""

    def __init__(self, mix: dict, family, seed: int):
        if mix["params"] not in ("fresh", "fixed"):
            raise BenchError(f"traffic params={mix['params']!r} is neither "
                             f"'fresh' nor 'fixed'")
        self.mix, self.family = mix, family
        n = family.n
        ss = np.random.SeedSequence(int(seed) % (1 << 64))
        r_params, r_init, r_read = (np.random.default_rng(s)
                                    for s in ss.spawn(3))
        self.r_params = r_params
        self.prepare = _module("prepares", mix["prepare"]).Prepare(r_init, n)
        self.read_mod = _module("reads", mix["read"])
        self.read = self.read_mod.Read(r_read, n, **mix.get("read_args", {}))
        self.fixed = (family.draw_params(r_params)
                      if mix["params"] == "fixed" else None)

    def circuit(self, i: int) -> Circuit:
        init = (self.prepare.spec(i) if i == 0 or not self.mix.get("carry")
                else None)
        params = (self.fixed if self.mix["params"] == "fixed"
                  else self.family.draw_params(self.r_params))
        return Circuit(i, init, params, self.read.spec(i))


HOST_S: dict = {}


@contextmanager
def span(name: str):
    """A host span of the benchmark's own: a profiler annotation, and its
    seconds added to HOST_S[name]."""
    import jax

    t = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        try:
            yield
        finally:
            HOST_S[name] = HOST_S.get(name, 0.0) + time.perf_counter() - t


def run_circuit(qt, q, stream: Stream, c: Circuit) -> Circuit:
    """One circuit through the public API; the read syncs the host."""
    c.t0 = time.perf_counter()
    try:
        with span("circuit"):
            if c.init is not None:
                with span("prepare"):
                    stream.prepare.apply(qt, q, c.init)
            with span("drain"):
                stream.family.issue(qt, q, c.params, span)
            with span("read"):
                c.value = stream.read.program(qt, q, c.read)
        c.ok = bool(np.all(np.isfinite(c.value)))
    except Exception as e:  # a circuit that raises has failed
        print(f"circuit {c.index} raised {type(e).__name__}: {e}",
              file=sys.stderr)
        c.ok = False
    c.t1 = time.perf_counter()
    return c


# ---------------------------------------------------------------------------
# compile accounting
# ---------------------------------------------------------------------------


class CompileLog:
    """Backend compiles, trace/lower seconds and persistent-cache hits and
    misses, from jax.monitoring."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        self.trace_lower_s = 0.0
        self.hits = 0
        self.misses = 0

        def on_duration(event, duration, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration
            elif event in ("/jax/core/compile/jaxpr_trace_duration",
                           "/jax/core/compile/jaxpr_to_mlir_module_duration"):
                self.trace_lower_s += duration

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)


def enable_cache(jax) -> str:
    """JAX's persistent compilation cache at the fixed path
    ``<checkout>/.jax_cache``, set before the program wires its own (it
    then keeps the directory configured here)."""
    path = os.path.join(ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peaks_for(kind: str) -> dict:
    table = _json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# the comparison with the reference
# ---------------------------------------------------------------------------


def verify(cell: Cell, stream: Stream, done: list, snap, on_chip: bool):
    """({number: value}, the indices of the circuits compared).

    ``done``: every circuit run (the warm-up first); ``snap``: (index,
    the family's snapshot) of the circuit whose state the mix compares.
    The numbers: ``state_err``, the reference's distance to the snapshot;
    ``read_err``, the largest gap between a number the compared reads
    answered and the reference's."""
    mix, fam = cell.mix, cell.family
    ran = [c for c in done if c.t1 > 0]
    which = mix["check_reads"]
    if which == "last":
        reads = [ran[-1].index]
    elif which == "all":
        reads = [c.index for c in ran]
    else:
        reads = [c.index for c in ran[:int(which)]]
    want = sorted(set(reads) | {snap[0]})
    by_index = {c.index: c for c in ran}
    out = {}
    gaps = []
    # group what is wanted by the preparation it follows
    chains = {}
    for i in want:
        j = i
        while by_index[j].init is None:
            j -= 1
        chains.setdefault(j, []).append(i)
    for j, targets in chains.items():
        last = max(targets)
        chain = [by_index[k].params for k in range(j, last + 1)]
        refs = fam.reference(by_index[j].init, chain, on_chip)
        for k, ref in zip(range(j, last + 1), refs):
            c = by_index[k]
            if k in reads:
                want_v = np.asarray(stream.read.reference(ref, c.read))
                gaps.append(np.abs(np.asarray(c.value) - want_v).ravel()
                            if c.ok else np.array([math.inf]))
            if k == snap[0]:
                out["state_err"] = ref.state_err(snap[1])
            if k == last:
                break
        refs.close()
    out["read_err"] = float(np.max(np.concatenate(gaps)))
    return out, want


def judge(values: dict, limits: dict, attempted: int, failed: int):
    """(correct, checks): every number with a limit, beside its limit.
    Correct when circuits ran, none failed, and each such number is
    within its limit."""
    checks = {k: {"value": values[k], "limit": v} for k, v in limits.items()}
    correct = (failed == 0 and attempted > 0 and all(
        v["value"] <= v["limit"] for v in checks.values()))
    return correct, checks


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def end_to_end(cell: Cell, window: list, w0: float, seconds: float,
               setup_s: float) -> dict:
    ok = [c for c in window if c.ok and c.t1 <= w0 + seconds]
    out = {}
    for m in cell.end_to_end:
        name = m["name"]
        if name == "setup_s":
            v = setup_s
        elif name == "circuits_per_s":
            v = len(ok) / (ok[-1].t1 - w0) if ok else None
        elif name == "circuit_p90_s":
            d = [c.t1 - c.t0 for c in ok]
            v = (statistics.quantiles(d, n=10, method="inclusive")[8]
                 if len(d) >= 2 else None)
        else:
            raise BenchError(f"no computation for end-to-end metric "
                             f"{name!r}")
        if v is not None:
            out[name] = {"value": v, "unit": m["unit"]}
    return out


def per_layer(cell: Cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        mod = importlib.import_module("benchmark.metrics." + m["name"])
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, overrides=None, limits=None,
             keep_trace: str = None):
    """Run one cell; returns (result dict, checks, compiles in the
    window).  With ``require_chip`` False (the CPU rehearsal) nothing is
    checked about the device and the compilation cache is left alone.
    ``keep_trace``: a path to copy the traced window's ``.xplane.pb`` to."""
    HOST_S.clear()
    cell = load_cell(workload, overrides=overrides, limits=limits)
    import jax

    if require_chip:
        cache_dir = enable_cache(jax)
    devices = jax.devices()
    kind = devices[0].device_kind
    if require_chip:
        if devices[0].platform != "tpu":
            raise BenchError(f"needs a TPU; JAX reports "
                             f"{devices[0].platform}")
        if len(devices) < cell.chips:
            raise BenchError(f"cell needs {cell.chips} chips; JAX reports "
                             f"{len(devices)}")
        peaks = peaks_for(kind)
    else:
        cache_dir, peaks = None, None
    log = CompileLog()
    t_jax = time.perf_counter()

    import quest_tpu as qt

    if not os.path.abspath(qt.__file__).startswith(ROOT + os.sep):
        raise BenchError(f"quest_tpu is not the checkout's: {qt.__file__}")
    qt.set_precision({"single": 1, "double": 2}[cell.cfg["precision"]])
    env = qt.createQuESTEnv(num_devices=cell.chips)
    q = cell.family.create(qt, env)
    stream = Stream(cell.mix, cell.family, seed)

    # warm-up: the mix's first circuit
    t_warm = time.perf_counter()
    c = run_circuit(qt, q, stream, stream.circuit(0))
    if not c.ok:
        raise BenchError("the warm-up circuit failed")
    done = [c]
    # the state after the warm-up, where the mix compares that one; its
    # copy is the comparison's and not set-up
    snap, snap_s = None, 0.0
    if cell.mix["check_state"] == "warm":
        t = time.perf_counter()
        snap = (c.index, cell.family.snapshot(q))
        snap_s = time.perf_counter() - t
    plan_s = _span_seconds(("fusion.optimize", "fusion.plan"))
    t_ready = time.perf_counter()
    setup_s = t_ready - T_START - snap_s
    setup = {"setup_s": setup_s, "jax_init_s": t_jax - T_START,
             "import_env_qureg_s": t_warm - t_jax,
             "capture_s": HOST_S.get("capture", 0.0),
             "optimize_plan_s": plan_s,
             "trace_lower_s": log.trace_lower_s,
             "backend_compile_s": log.compile_s,
             "compiles": log.compiles, "cache_hits": log.hits,
             "cache_misses": log.misses,
             "first_circuit_s": done[0].t1 - done[0].t0,
             "warm_snapshot_s": snap_s, "cache_dir": cache_dir}
    print(json.dumps({"setup": setup}), flush=True)

    # the measured window
    tdir = tempfile.mkdtemp(prefix="qt_bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1      # TraceAnnotation spans only
        jax.profiler.start_trace(tdir, profiler_options=opts)
    compiles0 = log.compiles
    window = []
    w0 = time.perf_counter()
    with span("window"):
        i = len(done)
        while time.perf_counter() - w0 < seconds:
            c = run_circuit(qt, q, stream, stream.circuit(i))
            window.append(c)
            i += 1
            if not c.ok:
                break
    w1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    in_window_compiles = log.compiles - compiles0
    done += window
    failed = sum(1 for c in window if not c.ok)
    print(json.dumps({"window": {
        "circuits": len(window), "seconds": w1 - w0,
        "completed_in_window": sum(1 for c in window
                                   if c.ok and c.t1 <= w0 + seconds),
        "compiles_in_window": in_window_compiles,
        "circuit_s": [round(c.t1 - c.t0, 6) for c in window]}}),
        flush=True)

    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in env.mesh.devices.flat)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": cell.chips, "memory_peak_bytes": mem_peak}
    result = {"correct": False, "attempted": len(window), "failed": failed}
    if trace:
        xplane = tracefile.find_xplane(tdir)
        if keep_trace:
            shutil.copyfile(xplane, keep_trace)
        tr = tracefile.load(xplane)
        shutil.rmtree(tdir, ignore_errors=True)
        tw0, tw1 = tr.window()
        busy = tr.busy(tw0, tw1)
        ctx = types.SimpleNamespace(
            trace=tr, w0=tw0, w1=tw1, busy_s=busy, peaks=peaks,
            circuits=sum(1 for c in window if c.ok))
        result["metrics"] = per_layer(cell, ctx)
        device.update(busy_s=busy, window_s=tw1 - tw0)
        result["device"] = device
        result["breakdown"] = {"device_ops": tr.top_ops(tw0, tw1),
                               "idle_gaps": tr.idle_by_span(tw0, tw1)}
    else:
        result["metrics"] = end_to_end(cell, window, w0, seconds, setup_s)
        result["device"] = device

    # correctness, once the window has closed and the peak is read
    t_check = time.perf_counter()
    if cell.mix["check_state"] == "last":
        last = window[-1] if window else done[-1]
        snap = (last.index, cell.family.snapshot(q))
    t_snap = time.perf_counter()
    qt.destroyQureg(q, env)
    del q
    values, compared = verify(cell, stream, done, snap, on_chip=require_chip)
    del snap
    print(json.dumps({"check": {"snapshot_s": t_snap - t_check + snap_s,
                                "reference_s": time.perf_counter() - t_snap,
                                "circuits": compared, "values": values}}),
          flush=True)
    # only the numbers with a limit are compared (see PERF.md)
    result["correct"], checks = judge(values, cell.limits, len(window),
                                      failed)
    result["checks"] = checks
    return result, checks, in_window_compiles


def _span_seconds(names) -> float:
    """Seconds the program's telemetry spans ``names`` took so far."""
    from quest_tpu import telemetry

    hist = telemetry.snapshot().get("histograms", {}).get("span_seconds", {})
    return sum(float(v.get("sum", 0.0)) for label, v in hist.items()
               if any(name in label for name in names))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, checks, _ = run_cell(args.workload, args.seed, args.seconds,
                                     bool(args.trace))
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    for name, v in checks.items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
