"""Unified telemetry layer (quest_tpu/telemetry.py, ISSUE 4).

Covers the acceptance contract:
  * counter/label semantics (canonical label order, accumulation,
    per-series isolation) and histogram bucket bookkeeping;
  * span nesting emits Chrome-trace "X" events with the schema Perfetto
    loads, and ``write_trace`` round-trips them through JSON;
  * ``snapshot()`` / ``prometheus_text()`` agree series-for-series;
  * ``QT_TELEMETRY=off`` yields an empty snapshot, empty exposition,
    and never creates trace files;
  * the pinned 8-shard dryrun circuit's exchange count and byte totals
    match ``circuit.remap_exchange_bytes``'s cost model EXACTLY;
  * the fusion drain, resilience, and measurement instrumentation all
    report into the same registry, and ``run_resumable`` logs one JSON
    line per checkpoint/restore/watchdog event.
"""

import collections
import json
import logging
import re
import threading
import time

import numpy as np
import pytest

import quest_tpu as qt
from quest_tpu import circuit as CIRC
from quest_tpu import fusion
from quest_tpu import resilience as R
from quest_tpu import telemetry as T
from quest_tpu.parallel import dist

H_SOA = np.stack([(1 / np.sqrt(2)) * np.array([[1.0, 1], [1, -1]]),
                  np.zeros((2, 2))])


@pytest.fixture(autouse=True)
def tele():
    """Telemetry on + a clean registry per test; the session mode is
    restored afterwards so other suites see their configured default."""
    prev = T.mode_name()
    T.configure("on")
    T.reset()
    yield T
    T.reset()
    T.configure(prev)


def _sum(series: dict) -> float:
    return sum(series.values())


# ---------------------------------------------------------------------------
# Registry semantics
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_counter_accumulates(self):
        T.inc("widgets_total")
        T.inc("widgets_total", 2)
        assert T.counter_total("widgets_total") == 3

    def test_labels_are_canonical_and_isolated(self):
        """Label ORDER never splits a series; label VALUES always do."""
        T.inc("exchanges_total", 1, op="remap", chunks="4")
        T.inc("exchanges_total", 2, chunks="4", op="remap")
        T.inc("exchanges_total", 5, op="swap", chunks="4")
        snap = T.snapshot()["counters"]["exchanges_total"]
        assert snap["chunks=4,op=remap"] == 3
        assert snap["chunks=4,op=swap"] == 5
        assert T.counter_value("exchanges_total", op="remap", chunks=4) == 3

    def test_non_string_label_values_coerced(self):
        T.inc("c_total", 1, chunks=8)
        assert T.counter_value("c_total", chunks="8") == 1

    def test_gauge_overwrites(self):
        T.set_gauge("g", 1.0, device="d0")
        T.set_gauge("g", 7.5, device="d0")
        assert T.snapshot()["gauges"]["g"]["device=d0"] == 7.5

    def test_histogram_stats_and_buckets(self):
        for v in (0.0005, 0.05, 0.05, 3.0):
            T.observe("lat_seconds", v)
        h = T.snapshot()["histograms"]["lat_seconds"][""]
        assert h["count"] == 4
        assert h["min"] == 0.0005 and h["max"] == 3.0
        assert abs(h["sum"] - 3.1005) < 1e-12
        # cumulative le-buckets are monotone and end at the total count
        cums = list(h["buckets"].values())
        assert cums == sorted(cums) and cums[-1] == 4
        assert h["buckets"]["0.001"] == 1      # 0.0005
        assert h["buckets"]["0.1"] == 3        # + the two 0.05s

    def test_snapshot_folds_legacy_registries(self):
        """env._CACHE_STATS and the degradation registry surface as
        series of the same namespace (satellite: one consolidated view,
        old accessors keep working)."""
        snap = T.snapshot()
        assert "compile_cache_hits_total" in snap["counters"]
        assert "compile_cache_misses_total" in snap["counters"]
        from quest_tpu import env as E

        assert set(E.compile_cache_stats()) == {"hits", "misses", "dir"}

    def test_degradation_becomes_series(self, monkeypatch):
        monkeypatch.setattr(R, "DEGRADATIONS", {}, raising=True)
        with pytest.warns(UserWarning):
            R.record_degradation("unit_test", "synthetic downgrade")
        snap = T.snapshot()
        assert snap["counters"]["degradations_total"]["name=unit_test"] == 1
        assert snap["gauges"]["degradation_active"]["name=unit_test"] == 1.0
        assert R.degradation_report() == {"unit_test": "synthetic downgrade"}


# ---------------------------------------------------------------------------
# Off mode
# ---------------------------------------------------------------------------


class TestOffMode:
    def test_off_yields_empty_everything(self):
        T.inc("pre_total")
        T.configure("off")
        T.inc("post_total")
        assert T.snapshot() == {}
        assert T.prometheus_text() == ""
        assert T.counter_total("post_total") == 0
        # recording resumes (and the pre-off series survives) on re-enable
        T.configure("on")
        assert T.counter_total("pre_total") == 1
        assert T.counter_total("post_total") == 0

    def test_off_no_trace_files(self, tmp_path):
        T.configure("off")
        with T.span("invisible"):
            pass
        out = T.write_trace(str(tmp_path / "t.json"))
        assert out is None
        assert list(tmp_path.iterdir()) == []

    def test_env_var_resolution(self, monkeypatch):
        monkeypatch.setenv("QT_TELEMETRY", "off")
        assert T.configure() == "off"
        monkeypatch.setenv("QT_TELEMETRY", "trace")
        assert T.configure() == "trace"
        monkeypatch.delenv("QT_TELEMETRY")
        assert T.configure() == "on"  # the always-on default

    def test_off_dispatch_is_silent(self, env):
        T.configure("off")
        q = qt.createQureg(3, env)
        qt.hadamard(q, 0)
        qt.measure(q, 0)
        assert T.snapshot() == {}


# ---------------------------------------------------------------------------
# Spans and Chrome trace
# ---------------------------------------------------------------------------


class TestSpans:
    def test_span_records_duration_histogram(self):
        with T.span("unit.work"):
            pass
        h = T.snapshot()["histograms"]["span_seconds"]["name=unit.work"]
        assert h["count"] == 1 and h["sum"] >= 0

    def test_nested_spans_chrome_schema(self, tmp_path):
        T.configure("trace")
        with T.span("outer", phase="drain"):
            with T.span("inner"):
                pass
        path = T.write_trace(str(tmp_path / "trace.json"))
        with open(path) as f:
            doc = json.load(f)
        events = doc["traceEvents"]
        assert [e["name"] for e in events] == ["inner", "outer"]
        for e in events:
            assert e["ph"] == "X" and e["cat"] == "quest_tpu"
            assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        inner, outer = events
        # proper nesting: inner starts after outer and ends before it
        assert inner["tid"] == outer["tid"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
        assert outer["args"] == {"phase": "drain"}

    def test_write_trace_drains_buffer(self, tmp_path):
        T.configure("trace")
        with T.span("once"):
            pass
        assert T.write_trace(str(tmp_path / "a.json")) is not None
        assert T.write_trace(str(tmp_path / "b.json")) is None
        assert not (tmp_path / "b.json").exists()

    def test_phases_tile_the_region_and_extend_a_repeat(self, tmp_path):
        T.configure("trace")
        with T.phases() as phase:
            phase("step.a")
            phase("step.a")     # already open: extends it
            phase("step.b")
            phase("step.a")
        with open(T.write_trace(str(tmp_path / "t.json"))) as f:
            events = json.load(f)["traceEvents"]
        assert [e["name"] for e in events] == ["step.a", "step.b", "step.a"]
        for x, y in zip(events, events[1:]):
            assert y["ts"] >= x["ts"] + x["dur"] - 1e-3
        h = T.snapshot()["histograms"]["span_seconds"]
        assert h["name=step.a"]["count"] == 2
        assert h["name=step.b"]["count"] == 1

    def test_phases_off_records_nothing(self):
        T.configure("off")
        with T.phases() as phase:
            phase("step.a")
        T.configure("on")
        assert "span_seconds" not in T.snapshot()["histograms"]


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------


_PROM_LINE = re.compile(r"^(\w+)(?:\{(.*)\})? ([-+0-9.e]+)$")


def _parse_prom(text: str) -> dict:
    out = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        m = _PROM_LINE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        name, labels, value = m.groups()
        labels = ",".join(
            part.replace('"', "") for part in (labels or "").split(","))
        out[(name, labels)] = float(value)
    return out


class TestPrometheus:
    def test_round_trip_matches_snapshot(self):
        T.inc("exchanges_total", 3, op="remap", chunks="2")
        T.inc("exchanges_total", 1, op="swap", chunks="1")
        T.set_gauge("hbm_bytes", 123.0, device="cpu0")
        T.observe("lat_seconds", 0.02)
        parsed = _parse_prom(T.prometheus_text())
        snap = T.snapshot()
        for name, series in snap["counters"].items():
            for labels, v in series.items():
                assert parsed[(name, labels)] == pytest.approx(v)
        for name, series in snap["gauges"].items():
            for labels, v in series.items():
                assert parsed[(name, labels)] == pytest.approx(v)
        # histogram triplet: _count/_sum/_bucket with cumulative le
        assert parsed[("lat_seconds_count", "")] == 1
        assert parsed[("lat_seconds_sum", "")] == pytest.approx(0.02)
        assert parsed[("lat_seconds_bucket", "le=+Inf")] == 1

    def test_type_lines_present(self):
        T.inc("a_total")
        T.set_gauge("b", 1)
        T.observe("c_seconds", 0.5)
        text = T.prometheus_text()
        assert "# TYPE a_total counter" in text
        assert "# TYPE b gauge" in text
        assert "# TYPE c_seconds histogram" in text


class TestPrometheusStrictConformance:
    """The exposition must parse under a REAL Prometheus text-format
    parser (prometheus_client), not just our own reader — the regression
    this pins: non-finite values rendered as Python's ``inf``/``nan``
    (which Prometheus rejects) instead of ``+Inf``/``-Inf``/``NaN``."""

    @pytest.fixture(autouse=True)
    def _parser(self):
        pytest.importorskip("prometheus_client")

    def _families(self):
        from prometheus_client.parser import text_string_to_metric_families

        return {f.name: f for f in
                text_string_to_metric_families(T.prometheus_text())}

    def test_full_registry_parses(self):
        T.inc("exchanges_total", 3, op="remap", chunks="2")
        T.inc("exchanges_total", 1, op="swap", chunks="1")
        T.set_gauge("hbm_bytes", 123.0, device='weird"dev\\0')
        T.observe("lat_seconds", 0.02)
        T.observe("lat_seconds", 5.0)
        T.observe("fusion_window_gates", 3)
        fams = self._families()
        samples = {(s.name, tuple(sorted(s.labels.items()))): s.value
                   for f in fams.values() for s in f.samples}
        assert samples[("exchanges_total",
                        (("chunks", "2"), ("op", "remap")))] == 3
        assert samples[("hbm_bytes",
                        (("device", 'weird"dev\\0'),))] == 123.0

    def test_nonfinite_values_spelled_per_spec(self):
        T.set_gauge("g_inf", float("inf"), k="a")
        T.set_gauge("g_ninf", float("-inf"), k="a")
        T.set_gauge("g_nan", float("nan"), k="a")
        text = T.prometheus_text()
        assert 'g_inf{k="a"} +Inf' in text
        assert 'g_ninf{k="a"} -Inf' in text
        assert 'g_nan{k="a"} NaN' in text
        fams = self._families()
        import math

        vals = {s.metric_name if hasattr(s, "metric_name") else s.name:
                s.value for f in fams.values() for s in f.samples}
        assert math.isinf(vals["g_inf"]) and vals["g_inf"] > 0
        assert math.isinf(vals["g_ninf"]) and vals["g_ninf"] < 0
        assert math.isnan(vals["g_nan"])

    def test_histogram_semantics_cumulative_and_inclusive(self):
        """Cumulative le buckets with INCLUSIVE upper bounds, the +Inf
        bucket equal to _count, and consistent _sum — checked through
        the real parser's sample view."""
        bounds = T.HIST_BOUNDS["fusion_window_gates"]
        T.observe("fusion_window_gates", 1)    # == first bound: inclusive
        T.observe("fusion_window_gates", 2)    # == second bound: inclusive
        T.observe("fusion_window_gates", 10_000)  # beyond the last bound
        fams = self._families()
        f = fams["fusion_window_gates"]
        buckets = {s.labels["le"]: s.value for s in f.samples
                   if s.name == "fusion_window_gates_bucket"}
        count = next(s.value for s in f.samples
                     if s.name == "fusion_window_gates_count")
        total = next(s.value for s in f.samples
                     if s.name == "fusion_window_gates_sum")
        assert buckets[repr(float(bounds[0]))] == 1  # le=1 contains v==1
        assert buckets[repr(float(2))] == 2          # le=2 contains v==2
        assert buckets["+Inf"] == count == 3
        assert total == pytest.approx(1 + 2 + 10_000)
        # cumulative monotone over ascending bounds
        ordered = [buckets[repr(float(b))] for b in bounds] + \
            [buckets["+Inf"]]
        assert ordered == sorted(ordered)


# ---------------------------------------------------------------------------
# The 8-shard dryrun: exchange accounting vs the cost model
# ---------------------------------------------------------------------------


def _expected_remap_cost(bit_sets, n, nloc, r, itemsize):
    """Re-derive what the drain + final canonical read must exchange,
    straight from the scheduling layer's own cost model."""
    count = 0
    nbytes = 0
    segments, final_perm = CIRC.plan_remap_windows(bit_sets, n, nloc, None)
    sigmas = [s for _ij, s, _p in segments if s is not None]
    if final_perm is not None and list(final_perm) != list(range(n)):
        sigmas.append(dist.canonical_sigma(final_perm))
    for sigma in sigmas:
        mixed, _lp, mesh_tau = dist.decompose_sigma(sigma, nloc, r)
        count += len(mixed) + (1 if mesh_tau is not None else 0)
        nbytes += CIRC.remap_exchange_bytes(sigma, n, nloc, itemsize)
    return count, nbytes


class TestExchangeAccounting:
    @pytest.fixture(autouse=True)
    def _mesh(self, env):
        if env.num_devices < 8:
            pytest.skip("needs the 8-device virtual mesh")
        dist.use_explicit_dist(True)
        dist.use_lazy_remap(True)
        yield

    def test_pinned_dryrun_matches_remap_cost_model(self, env):
        """Acceptance: the pinned 8-shard circuit's telemetry exchange
        count and byte totals equal circuit.remap_exchange_bytes's model
        EXACTLY — one windowed remap inside the drain plus the canonical
        rematerialization on the final read, nothing else."""
        n, r = 6, dist.num_shard_bits(env.mesh)
        nloc = n - r
        rng = np.random.default_rng(3)
        g = rng.standard_normal((4, 4)) + 1j * rng.standard_normal((4, 4))
        u, _ = np.linalg.qr(g)
        q = qt.createQureg(n, env)
        itemsize = np.dtype(q.dtype).itemsize
        bit_sets = [(0, 1), (n - 2, n - 1), (0, 1)]
        exp_count, exp_bytes = _expected_remap_cost(
            bit_sets, n, nloc, r, itemsize)
        assert exp_count > 0 and exp_bytes > 0  # the circuit IS sharded
        T.reset()
        with qt.gateFusion(q):
            for a, b in bit_sets:
                qt.multiQubitUnitary(q, [a, b], u)
        _ = q.amps  # drains + rematerializes canonical order
        snap = T.snapshot()
        got_bytes = _sum(snap["counters"]["exchange_bytes_total"])
        got_count = _sum(snap["counters"]["exchanges_total"])
        assert got_bytes == exp_bytes
        assert got_count == exp_count
        # and both op families are present: the in-drain window remap
        # and the canonical-order rematerialization on read (flat 1x8
        # topology: every hop rides ICI)
        assert "op=window_remap,tier=ici" \
            in snap["counters"]["exchange_bytes_total"]
        assert "op=remap,tier=ici" \
            in snap["counters"]["exchange_bytes_total"]

    def test_tier_split_sums_to_cost_model(self, env, monkeypatch):
        """Satellite (ISSUE 12): under the emulated 2x4 topology the
        tier-labeled byte series sum EXACTLY to the flat cost-model
        totals, and each tier individually matches the tier-aware
        model (circuit.remap_exchange_bytes_tiers)."""
        monkeypatch.setenv("QT_TOPOLOGY", "2x4")
        n, r = 6, dist.num_shard_bits(env.mesh)
        nloc = n - r
        rng = np.random.default_rng(3)
        g = rng.standard_normal((4, 4)) + 1j * rng.standard_normal((4, 4))
        u, _ = np.linalg.qr(g)
        q = qt.createQureg(n, env)
        itemsize = np.dtype(q.dtype).itemsize
        bit_sets = [(0, 1), (n - 2, n - 1), (0, 1)]
        exp_count, exp_bytes = _expected_remap_cost(
            bit_sets, n, nloc, r, itemsize)
        # per-tier expectation straight from the tier-aware cost model,
        # over the same sigmas the drain + final read will dispatch
        exp_tier = {"ici": 0, "dcn": 0}
        segments, final_perm = CIRC.plan_remap_windows(
            bit_sets, n, nloc, None)
        sigmas = [s for _ij, s, _p in segments if s is not None]
        if final_perm is not None and list(final_perm) != list(range(n)):
            sigmas.append(dist.canonical_sigma(final_perm))
        for sigma in sigmas:
            for tier, b in CIRC.remap_exchange_bytes_tiers(
                    sigma, n, nloc, itemsize).items():
                exp_tier[tier] += b
        assert sum(exp_tier.values()) == exp_bytes  # model is a split
        T.reset()
        with qt.gateFusion(q):
            for a, b in bit_sets:
                qt.multiQubitUnitary(q, [a, b], u)
        _ = q.amps  # drains + rematerializes canonical order
        series = T.snapshot()["counters"]["exchange_bytes_total"]
        got_tier = {t: sum(v for k, v in series.items()
                           if f"tier={t}" in k) for t in ("ici", "dcn")}
        assert got_tier == exp_tier
        assert sum(got_tier.values()) == exp_bytes

    def test_eager_1q_exchange_payload(self, env):
        """A sharded-target 1q gate records one full-shard exchange with
        the resolved chunk config."""
        n = 6
        amps = qt.createQureg(n, env).amps
        T.reset()
        out = dist.apply_matrix_1q_sharded(
            amps, H_SOA.reshape(2, 2, 2), mesh=env.mesh, num_qubits=n,
            target=n - 1, chunks=2)
        out.block_until_ready()
        shard_bytes = 2 * (1 << (n - dist.num_shard_bits(env.mesh))) \
            * amps.dtype.itemsize
        assert T.counter_value("exchanges_total",
                               op="matrix_1q", chunks=2, tier="ici") == 1
        assert T.counter_value("exchange_bytes_total",
                               op="matrix_1q", tier="ici") == shard_bytes

    def test_swap_records_half_shard(self, env):
        n = 6
        amps = qt.createQureg(n, env).amps
        T.reset()
        dist.swap_sharded(amps, mesh=env.mesh, num_qubits=n,
                          qb_low=0, qb_high=n - 1).block_until_ready()
        shard_bytes = 2 * (1 << (n - dist.num_shard_bits(env.mesh))) \
            * amps.dtype.itemsize
        assert T.counter_value("exchange_bytes_total",
                               op="swap", tier="ici") == shard_bytes // 2

    def test_no_double_count_inside_user_jit(self, env):
        """A wrapper reached while TRACING a user jit must not record —
        dispatch-time accounting, not trace-time."""
        import jax

        n = 6
        amps = qt.createQureg(n, env).amps
        jfn = jax.jit(lambda a: dist.swap_sharded(
            a, mesh=env.mesh, num_qubits=n, qb_low=0, qb_high=n - 1))
        T.reset()
        jfn(amps).block_until_ready()
        jfn(amps).block_until_ready()
        assert T.counter_total("exchanges_total") == 0


# ---------------------------------------------------------------------------
# Fusion, dispatch, measurement instrumentation
# ---------------------------------------------------------------------------


class TestHotLayerHooks:
    def test_drain_and_plan_cache_counters(self, env):
        n = 5
        rng = np.random.default_rng(11)
        g = rng.standard_normal((2, 2)) + 1j * rng.standard_normal((2, 2))
        u, _ = np.linalg.qr(g)

        def run_once():
            q = qt.createQureg(n, env)
            with qt.gateFusion(q):
                for t in range(n):
                    qt.unitary(q, t, u)
            return qt.calcTotalProb(q)

        run_once()  # not measured: may hit stale session-wide caches
        before = T.snapshot()
        run_once()
        after = T.snapshot()

        def delta(name):
            return (_sum(after["counters"].get(name, {}))
                    - _sum(before["counters"].get(name, {})))

        assert delta("fusion_drains_total") == 1
        assert delta("fusion_plan_cache_hits_total") == 1
        assert delta("fusion_plan_cache_misses_total") == 0
        assert delta("fusion_retrace_total") == 0  # same program shape
        assert delta("fusion_windows_total") >= 1
        assert after["counters"]["dispatch_total"]["family=unitary"] \
            >= before["counters"]["dispatch_total"]["family=unitary"] + n
        h = after["histograms"]["fusion_drain_gates"][""]
        assert h["count"] >= 2 and h["max"] >= n

    def test_measurement_shot_counters(self, env):
        q = qt.createQureg(3, env)
        qt.hadamard(q, 0)
        T.reset()
        qt.measure(q, 0)
        qt.measureSequence(q, [0, 1, 2])
        assert T.counter_total("measurement_shots_total") == 4

    def test_environment_string_has_consolidated_block(self, env):
        qt.hadamard(qt.createQureg(2, env), 0)
        s = qt.getEnvironmentString(env)
        assert "[telemetry: on" in s
        assert "dispatch=" in s
        T.configure("off")
        assert "[telemetry: off]" in qt.getEnvironmentString(env)

    def test_report_perf_prints_counters(self, env, capsys):
        qt.hadamard(qt.createQureg(2, env), 0)
        qt.reportPerf(env)
        out = capsys.readouterr().out
        assert "quest_tpu perf report" in out
        assert "dispatch_total{family=unitary}" in out
        assert "EnvType=quest_tpu" in out


# ---------------------------------------------------------------------------
# Profiling satellites
# ---------------------------------------------------------------------------


class TestProfilingHooks:
    def test_timed_observes_histogram(self):
        from quest_tpu.utils import profiling

        with profiling.timed("unit_block") as t:
            pass
        assert "seconds" in t
        h = T.snapshot()["histograms"]["timed_seconds"]["label=unit_block"]
        assert h["count"] == 1
        assert abs(h["sum"] - t["seconds"]) < 1e-9

    def test_memory_watermark_per_device(self):
        import jax

        from quest_tpu.utils import profiling

        wm = profiling.memory_watermark()
        assert len(wm) == len(jax.local_devices())
        # CPU backend exposes no stats: the graceful fallback is {}
        for stats in wm.values():
            assert isinstance(stats, dict)


# ---------------------------------------------------------------------------
# Resilience instrumentation + structured run logging
# ---------------------------------------------------------------------------


class TestResilienceHooks:
    def test_checkpoint_metrics_and_json_log(self, env, tmp_path, caplog):
        n, every = 4, 2
        gates = [CIRC.Gate((t,), H_SOA) for t in range(n)]
        q = qt.createQureg(n, env)
        T.reset()
        with caplog.at_level(logging.INFO, logger="quest_tpu.resilience"):
            qt.run_resumable(q, gates, str(tmp_path / "ck"), every=every)
        snap = T.snapshot()
        assert _sum(snap["counters"]["checkpoints_total"]) == 2
        assert snap["histograms"]["checkpoint_commit_seconds"][""]["count"] \
            == 2
        verdicts = snap["counters"]["watchdog_verdicts_total"]
        assert verdicts["policy=raise,verdict=ok"] == 2
        # one JSON line per event, each carrying the run context
        events = [json.loads(rec.message) for rec in caplog.records]
        kinds = [e["event"] for e in events]
        assert kinds.count("checkpoint") == 2
        assert kinds.count("watchdog") == 2
        run_ids = {e["run"] for e in events}
        assert len(run_ids) == 1
        for e in events:
            assert "elapsed" in e
            if e["event"] == "checkpoint":
                assert e["generation"].startswith("gen-")
                assert "window" in e and "seconds" in e

    def test_restore_logs_and_counts(self, env, tmp_path, caplog):
        n, every = 4, 2
        gates = [CIRC.Gate((t,), H_SOA) for t in range(n)]
        ck = str(tmp_path / "ck")
        qt.run_resumable(qt.createQureg(n, env), gates, ck, every=every)
        T.reset()
        q2 = qt.createQureg(n, env)
        with caplog.at_level(logging.INFO, logger="quest_tpu.resilience"):
            qt.run_resumable(q2, gates, ck, every=every)
        assert T.counter_total("checkpoint_restores_total") == 1
        events = [json.loads(rec.message) for rec in caplog.records]
        assert events[0]["event"] == "restore"
        assert events[0]["cursor"] == n  # resumed at the finished cursor

    def test_io_retry_counter(self, env, tmp_path):
        q = qt.createQureg(4, env)
        plan = qt.FaultPlan("io@2")
        T.reset()
        qt.run_resumable(q, [CIRC.Gate((0,), H_SOA)],
                         str(tmp_path / "ck"), every=1, faults=plan)
        assert T.counter_total("checkpoint_io_retries_total") == 2
        assert plan.log == ["io", "io"]


class TestServingResilienceSeries:
    """The serving fault-tolerance series names are operator contract
    (dashboards and alerts key on them) — pinned against the exposition
    byte-for-byte, plus the perf_report "serving resilience" block."""

    def _record(self):
        T.inc("serve_bank_retries_total", 2, reason="transient")
        T.inc("serve_bank_retries_total", reason="failover")
        T.inc("serve_bank_retries_total", reason="poison")
        T.inc("serve_jobs_quarantined_total", tenant="acme")
        T.inc("serve_failovers_total")
        T.inc("serve_heals_total")
        T.set_gauge("serve_degraded", 1.0)
        T.set_gauge("serve_failover_mttr_seconds", 0.025)

    def test_pinned_prometheus_names(self):
        self._record()
        text = T.prometheus_text()
        assert 'serve_bank_retries_total{reason="transient"} 2' in text
        assert 'serve_bank_retries_total{reason="failover"} 1' in text
        assert 'serve_bank_retries_total{reason="poison"} 1' in text
        assert 'serve_jobs_quarantined_total{tenant="acme"} 1' in text
        assert "\nserve_failovers_total 1" in text
        assert "\nserve_heals_total 1" in text
        assert "\nserve_degraded 1" in text
        assert "\nserve_failover_mttr_seconds 0.025" in text

    def test_perf_report_serving_resilience_block(self):
        self._record()
        report = T.perf_report()
        assert "serving resilience:" in report
        assert "bank retries: total=4 " \
               "(transient=2 failover=1 poison=1)" in report
        assert "quarantined=1 failovers=1 heals=1 degraded=1" in report
        assert "failover_mttr_seconds=0.025" in report

    def test_block_absent_when_no_faults(self):
        T.inc("serve_jobs_submitted_total", tenant="acme")
        assert "serving resilience:" not in T.perf_report()

    def test_environment_string_serve_fragment(self, env):
        from quest_tpu.env import get_environment_string
        assert "Serve=" not in get_environment_string(env)
        self._record()
        s = get_environment_string(env)
        assert "Serve=retries:4,quarantined:1,failovers:1," \
               "heals:1,degraded:1" in s


# ---------------------------------------------------------------------------
# Bounded Chrome-trace ring (docs/design.md §30)
# ---------------------------------------------------------------------------


class TestBoundedTraceRing:
    """The trace buffer is a bounded ring: overflow drops the OLDEST
    event, counts ``trace_events_dropped_total``, and ``write_trace``
    notes the drops (then resets the accounting for the next capture)."""

    def test_overflow_drops_oldest_and_counts(self, monkeypatch):
        T.configure("trace")
        monkeypatch.setattr(T, "_TRACE_MAX", 4)
        for i in range(7):
            with T.span("ring", seq=i):
                pass
        assert len(T._TRACE_EVENTS) == 4
        assert [e["args"]["seq"] for e in T._TRACE_EVENTS] == \
            ["3", "4", "5", "6"]
        assert T.counter_total("trace_events_dropped_total") == 3

    def test_write_trace_notes_drops_then_resets(self, monkeypatch,
                                                 tmp_path):
        T.configure("trace")
        monkeypatch.setattr(T, "_TRACE_MAX", 2)
        for _ in range(5):
            with T.span("w"):
                pass
        path = T.write_trace(str(tmp_path / "t.json"))
        with open(path) as f:
            doc = json.load(f)
        assert len(doc["traceEvents"]) == 2
        assert doc["otherData"]["trace_events_dropped"] == 3
        # the drain reset the drop accounting: a fresh capture that
        # does not overflow writes no otherData note
        with T.span("w"):
            pass
        with open(T.write_trace(str(tmp_path / "t2.json"))) as f:
            assert "otherData" not in json.load(f)


class TestThreadExactness:
    """The registry lock makes concurrent upserts exact (§30): no lost
    increments or observations under contended writers on the inc /
    inc_key / observe / set_gauge hot paths."""

    def test_concurrent_writers_exact_totals(self):
        workers, per = 8, 400
        fast = T.counter_key("contended_fast_total", lane="x")
        barrier = threading.Barrier(workers)

        def work(k):
            barrier.wait()
            for _ in range(per):
                T.inc("contended_total", worker=k % 2)
                T.inc_key(fast)
                T.observe("contended_seconds", 1e-6)
                T.set_gauge("contended_gauge", float(k))

        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert T.counter_total("contended_total") == workers * per
        assert T.counter_total("contended_fast_total") == workers * per
        hd = T.snapshot()["histograms"]["contended_seconds"][""]
        assert hd["count"] == workers * per
        assert hd["sum"] == pytest.approx(workers * per * 1e-6)


# ---------------------------------------------------------------------------
# Flight recorder (docs/design.md §30)
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_is_bounded_oldest_dropped(self, monkeypatch):
        monkeypatch.setattr(T, "_FLIGHT", collections.deque(maxlen=3))
        for i in range(6):
            T.flight_event("tick", seq=i)
        evs = T.flight_snapshot()
        assert [e["seq"] for e in evs] == [3, 4, 5]
        assert all(e["kind"] == "tick" for e in evs)

    def test_dump_parseable_and_ring_not_drained(self, tmp_path):
        T.flight_event("bank_dissolved", bank=1, reason="transient",
                       jobs=3)
        T.flight_event("admission_rejected", tenant="acme",
                       reason="queue_full", limit=4)
        path = T.dump_flight(str(tmp_path / "f.json"), reason="quarantine",
                             tenant="acme", job=7, error=ValueError("boom"))
        with open(path) as f:
            doc = json.load(f)
        assert doc["reason"] == "quarantine"
        assert doc["context"]["tenant"] == "acme"
        assert doc["context"]["job"] == 7  # primitives pass through
        assert doc["context"]["error"] == "boom"  # non-primitives -> str
        assert [e["kind"] for e in doc["events"]] == \
            ["bank_dissolved", "admission_rejected"]
        assert doc["events"][0]["jobs"] == 3
        assert T.counter_value("flight_dumps_total",
                               reason="quarantine") == 1
        # the ring is NOT drained: a later incident still sees the
        # earlier context in its own dump
        p2 = T.dump_flight(str(tmp_path / "g.json"), reason="failover")
        with open(p2) as f:
            assert len(json.load(f)["events"]) == 2

    def test_reserved_keys_and_stringification(self):
        T.flight_event("k", ts=-1.0, kind="spoof", err=ValueError("x"),
                       n=2)
        ev = T.flight_snapshot()[-1]
        assert ev["kind"] == "k" and ev["ts"] >= 0  # reserved keys win
        assert ev["err"] == "x" and ev["n"] == 2
        json.dumps(ev)  # always JSON-serializable

    def test_off_mode_records_nothing_writes_nothing(self, tmp_path):
        T.configure("off")
        T.flight_event("tick")
        target = tmp_path / "f.json"
        assert T.dump_flight(str(target), reason="x") is None
        assert not target.exists()
        T.configure("on")
        assert T.flight_snapshot() == []


# ---------------------------------------------------------------------------
# Request-scoped tracing (docs/design.md §30)
# ---------------------------------------------------------------------------


class TestRequestTraces:
    def _lifecycle(self, tid):
        """The serve-layer shape: one root "job" span wrapping points
        (admit/complete), a nested span, and an externally-timed span."""
        T.trace_begin(tid, "job", tenant="acme")
        T.trace_point(tid, "serve.admit", queue_depth=1)
        with T.trace_span(tid, "serve.window", bank=0):
            pass
        T.trace_add(tid, "serve.window", t0=time.perf_counter(),
                    dur=1e-3, bank=0, window=1)
        T.trace_point(tid, "serve.complete", outcomes=2)
        T.trace_end(tid, status="done")

    def test_complete_trace_well_nested(self):
        self._lifecycle("s0-j1")
        tz = T.tracez("s0-j1")
        assert tz["complete"] and not tz["open"] and tz["dropped"] == 0
        assert [e["name"] for e in tz["events"]] == \
            ["job", "serve.admit", "serve.window", "serve.window",
             "serve.complete"]
        roots = tz["tree"]
        assert len(roots) == 1 and roots[0]["name"] == "job"
        assert roots[0]["args"] == {"tenant": "acme", "status": "done"}
        assert [c["name"] for c in roots[0]["children"]] == \
            ["serve.admit", "serve.window", "serve.window",
             "serve.complete"]

    def test_index_unknown_id_and_open_spans(self):
        self._lifecycle("a")
        T.trace_begin("b", "job")
        assert T.tracez("nope") is None
        idx = T.tracez()["traces"]
        assert idx["a"]["complete"] and idx["a"]["events"] == 5
        assert idx["b"]["open"] == ["job"] and not idx["b"]["complete"]
        assert T.trace_ids() == ["a", "b"]
        assert T.tracez("b")["open"][0]["name"] == "job"

    def test_id_eviction_oldest_first(self, monkeypatch):
        monkeypatch.setattr(T, "_TRACEZ_IDS", 2)
        for tid in ("t1", "t2", "t3"):
            T.trace_point(tid, "x")
        assert T.trace_ids() == ["t2", "t3"]
        assert T.tracez("t1") is None

    def test_per_id_event_cap_counts_drops(self, monkeypatch):
        monkeypatch.setattr(T, "_TRACEZ_EVENTS", 3)
        for i in range(5):
            T.trace_point("t", "p", seq=i)
        tz = T.tracez("t")
        assert len(tz["events"]) == 3 and tz["dropped"] == 2
        assert [e["args"]["seq"] for e in tz["events"]] == ["2", "3", "4"]

    def test_mirrors_into_flight_ring(self):
        self._lifecycle("s1-j2")
        kinds = {(e["kind"], e.get("name")) for e in T.flight_snapshot()}
        assert ("event", "serve.admit") in kinds
        assert ("span", "job") in kinds

    def test_off_mode_records_nothing(self):
        T.configure("off")
        T.trace_begin("t", "job")
        T.trace_point("t", "x")
        T.trace_end("t")
        T.configure("on")
        assert T.tracez("t") is None


# ---------------------------------------------------------------------------
# Per-op wall-time attribution (docs/design.md §30)
# ---------------------------------------------------------------------------


class TestPerOpAttribution:
    def test_report_flags_dispatch_bound_route(self):
        T.observe("plan_route_seconds", 0.0100, route="winfused")
        T.observe("plan_route_seconds", 0.0102, route="winfused")
        T.observe("plan_route_seconds", 0.5, route="megawin")
        T.set_gauge("per_program_dispatch_seconds", 0.0095)
        rep = T.perf_report()
        assert "per-op attribution" in rep
        lines = {l.split(":")[0].strip(): l for l in rep.splitlines()
                 if "route=" in l}
        assert "dispatch_bound" in lines["route=winfused"]
        assert "dispatch_bound" not in lines["route=megawin"]

    def test_no_floor_gauge_no_verdict(self):
        T.observe("plan_route_seconds", 1e-4, route="winfused")
        rep = T.perf_report()
        assert "per-op attribution" in rep
        assert "dispatch_bound" not in rep

    def test_drain_records_route_series(self, env):
        h = (1 / np.sqrt(2)) * np.array([[1.0, 1], [1, -1]],
                                        dtype=complex)
        q = qt.createQureg(4, env)
        with qt.gateFusion(q):
            for t in range(4):
                qt.unitary(q, t, h)
        qt.calcTotalProb(q)
        routes = T.snapshot()["histograms"].get("plan_route_seconds", {})
        assert routes, "drain recorded no per-route attribution"
        assert T.counter_total("plan_route_dispatch_total") >= 1


def _phase_circuit(q, seed: int) -> None:
    """Three layers of rotations and controlled phases, buffered on
    ``q``'s open fusion block: one dense segment, one plan part."""
    n = q.num_qubits_represented
    angles = np.random.default_rng(seed).uniform(0, 2 * np.pi, (3, n))
    for layer in range(3):
        for t in range(n):
            qt.rotateY(q, t, float(angles[layer, t]))
            qt.rotateZ(q, t, 0.7 * float(angles[layer, t]))
        for t in range(layer % 2, n - 1, 2):
            qt.controlledPhaseShift(q, t, t + 1, 0.3)


_PLANNER_PHASES = ("fusion.analyse", "fusion.schedule", "fusion.materialize",
                   "fusion.group")
_DRAIN_PHASES = ("fusion.key", "fusion.govern", "fusion.dispatch")


@pytest.fixture(scope="module")
def phase_drains(tmp_path_factory):
    """A 15-qubit single-device drain in trace mode, then the same drain
    again (a plan-cache hit): per drain the Chrome events, the
    fusion_passes_total and plan_folds_total it added and the program
    plan_items_quiet gives for its items."""
    prev = T.mode_name()
    T.configure("trace")
    out = []
    try:
        q = qt.createQureg(15, qt.createQuESTEnv(num_devices=1))
        for i in range(2):
            T.reset()
            qt.startGateFusion(q)
            _phase_circuit(q, 2024)
            program = fusion.plan_items_quiet(q, list(q._fusion.gates))[0]
            qt.stopGateFusion(q)
            passes = T.counter_total("fusion_passes_total")
            folds = {p: T.counter_value("plan_folds_total", path=p)
                     for p in ("structured", "dense")}
            path = T.write_trace(str(tmp_path_factory.mktemp("drain")
                                     / f"{i}.json"))
            with open(path) as f:
                events = json.load(f)["traceEvents"]
            out.append({"events": events, "passes": passes,
                        "folds": folds, "program": program})
    finally:
        T.reset()
        T.configure(prev)
    return out


class TestDrainPhaseSpans:
    """The fusion drain's phase spans: the planner's steps inside
    fusion.plan, the drain's own host work inside fusion.drain, and the
    fusion_passes_total counter."""

    @pytest.mark.parametrize("name,parent", (
        [(n, "fusion.plan") for n in _PLANNER_PHASES]
        + [(n, "fusion.drain") for n in _DRAIN_PHASES]))
    def test_drain_records_phase_once_inside_parent(self, phase_drains,
                                                    name, parent):
        events = phase_drains[0]["events"]
        mine = [e for e in events if e["name"] == name]
        outer = [e for e in events if e["name"] == parent]
        assert len(mine) == 1 and len(outer) == 1
        (m,), (p,) = mine, outer
        assert m["tid"] == p["tid"]
        assert p["ts"] <= m["ts"]
        assert m["ts"] + m["dur"] <= p["ts"] + p["dur"] + 1e-3

    def test_planner_phases_tile_fusion_plan(self, phase_drains):
        events = phase_drains[0]["events"]
        plan = next(e for e in events if e["name"] == "fusion.plan")
        steps = sum(e["dur"] for e in events if e["name"] in _PLANNER_PHASES)
        assert steps >= 0.9 * plan["dur"]

    @pytest.mark.parametrize("name", _PLANNER_PHASES)
    def test_plan_cache_hit_records_no_planner_phase(self, phase_drains,
                                                     name):
        names = [e["name"] for e in phase_drains[1]["events"]]
        assert "fusion.drain" in names and "fusion.plan" not in names
        assert set(_DRAIN_PHASES) <= set(names)
        assert name not in names

    @pytest.mark.parametrize("drain", [0, 1], ids=["miss", "hit"])
    def test_passes_counter_counts_skeleton_entries(self, phase_drains,
                                                    drain):
        d = phase_drains[drain]
        want = sum(len(part[1]) for part in d["program"]
                   if part[0] == "plan")
        assert want > 0 and d["passes"] == want

    @pytest.mark.parametrize("drain", [0, 1], ids=["miss", "hit"])
    def test_fold_counter_counts_structured_products(self, phase_drains,
                                                     drain):
        """A planned drain folds its concrete gates on their own bits
        (circuit.fold_gate); a plan-cache hit folds nothing."""
        folds = phase_drains[drain]["folds"]
        assert folds["dense"] == 0
        assert (folds["structured"] > 0) == (drain == 0)

    @pytest.mark.parametrize("dry", ["plan_items_quiet", "explain_circuit",
                                     "explain_memory"])
    def test_dry_run_planning_records_nothing(self, dry, tmp_path):
        from quest_tpu import governor

        T.configure("trace")
        q = qt.createQureg(15, qt.createQuESTEnv(num_devices=1))
        qt.startGateFusion(q)
        _phase_circuit(q, 2025)
        items = list(q._fusion.gates)
        T.reset()
        if dry == "plan_items_quiet":
            fusion.plan_items_quiet(q, items)
        elif dry == "explain_circuit":
            qt.explain_circuit(q)
        else:
            governor.explain_memory(q, items)
        assert "span_seconds" not in T.snapshot()["histograms"]
        assert T.counter_total("fusion_passes_total") == 0
        assert T.counter_total("plan_folds_total") == 0
        assert T.write_trace(str(tmp_path / "t.json")) is None

    def test_sharded_drain_plans_inside_fusion_plan(self, env, tmp_path):
        T.configure("trace")
        q = qt.createQureg(10, env)
        qt.startGateFusion(q)
        _phase_circuit(q, 2026)
        qt.stopGateFusion(q)
        with open(T.write_trace(str(tmp_path / "t.json"))) as f:
            events = json.load(f)["traceEvents"]
        (plan,) = [e for e in events if e["name"] == "fusion.plan"]
        steps = [e for e in events if e["name"] in _PLANNER_PHASES]
        assert {"fusion.analyse", "fusion.schedule"} <= {
            e["name"] for e in steps}
        for e in steps:
            assert plan["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= plan["ts"] + plan["dur"] + 1e-3

    def test_perf_report_prints_passes_per_window(self):
        assert "hbm_round_trips/plan_window" not in T.perf_report()
        T.inc("fusion_windows_total", 4)
        T.inc("fusion_passes_total", 10)
        assert ("fusion passes: total=10 hbm_round_trips/plan_window=2.5"
                in T.perf_report())

    def test_perf_report_prints_plan_folds_by_path(self):
        assert "plan folds" not in T.perf_report()
        T.inc("plan_folds_total", 7, path="structured")
        T.inc("plan_folds_total", 2, path="dense")
        assert ("plan folds: total=9 structured=7 dense=2"
                in T.perf_report())


class TestMemoryWatermarkGauge:
    def test_watermark_published_for_metrics(self, env):
        from quest_tpu.utils import profiling
        profiling.memory_watermark()
        series = T.snapshot()["gauges"].get(
            "device_memory_watermark_bytes", {})
        assert series, "no watermark gauge published"
        assert all(v >= 0 for v in series.values())
        assert "device_memory_watermark_bytes" in T.prometheus_text()
