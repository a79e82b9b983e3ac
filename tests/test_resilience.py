"""Fault-tolerant execution suite (quest_tpu/resilience.py, ISSUE 2).

Covers the acceptance contract:
  * a kill injected mid-save leaves a loadable last-good checkpoint;
  * run_resumable after a simulated preemption produces amplitudes
    BIT-IDENTICAL to an uninterrupted run of the same circuit + seed,
    including on the multi-shard dryrun mesh with a live logical->physical
    permutation at the kill point;
  * the watchdog detects an injected NaN within one window cadence, and
    the rollback policy restores the last-good state;
  * transient IO errors are absorbed by the bounded-backoff retry
    wrapper; post-commit corruption falls back to the previous
    generation;
  * measurement-RNG state round-trips so resumed outcome streams match
    uninterrupted ones (host MT19937 and device-key paths).
"""

import json
import os
import warnings

import numpy as np
import pytest

import quest_tpu as qt
from quest_tpu import circuit as CIRC
from quest_tpu import resilience as R
from quest_tpu import rng as RNG
from quest_tpu.ops import measurement as M

pytestmark = pytest.mark.faults

N = 6  # 64 amps over the 8-device dryrun mesh -> 3 sharded qubits

H_SOA = np.stack([(1 / np.sqrt(2)) * np.array([[1.0, 1], [1, -1]]),
                  np.zeros((2, 2))])
CX_SOA = np.stack([
    np.array([[1.0, 0, 0, 0], [0, 1, 0, 0], [0, 0, 0, 1], [0, 0, 1, 0]]),
    np.zeros((4, 4)),
])


@pytest.fixture(autouse=True)
def _fast_retry(monkeypatch):
    monkeypatch.setenv("QT_RETRY_BASE_SECONDS", "0.001")


def _circuit(n=N, depth=4):
    """Entangling brickwork reaching every qubit — including the sharded
    high qubits, so drains leave a live permutation behind."""
    gates = []
    for _ in range(depth):
        for t in range(n):
            gates.append(CIRC.Gate((t,), H_SOA))
        for t in range(n - 1):
            gates.append(CIRC.Gate((t, t + 1), CX_SOA))
    return gates


def _fresh(env, n=N, seed=7):
    qt.seedQuEST(env, [seed])
    return qt.createQureg(n, env)


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    """Amplitudes of the uninterrupted resumable run (every=8)."""
    env = qt.createQuESTEnv()
    q = _fresh(env)
    qt.run_resumable(q, _circuit(), str(tmp_path_factory.mktemp("ref")),
                     every=8)
    return np.asarray(q.amps)


class TestResumeBitExact:
    def test_uninterrupted_equals_plain_fusion_run(self, env, reference):
        """run_resumable is the same computation as one gateFusion drain
        per window — the checkpoint/watchdog layer must not perturb the
        numerics at all."""
        from quest_tpu import fusion

        q = _fresh(env)
        gates = _circuit()
        for cur in range(0, len(gates), 8):
            fusion.start_gate_fusion(q)
            q._fusion.gates.extend(gates[cur:cur + 8])
            fusion.stop_gate_fusion(q)
        np.testing.assert_array_equal(np.asarray(q.amps), reference)

    def test_kill_then_resume_bit_identical_multishard(self, env, tmp_path,
                                                       reference):
        if env.num_devices < 8:
            pytest.skip("needs the 8-device virtual mesh")
        ckpt = str(tmp_path / "ck")
        q = _fresh(env)
        plan = qt.FaultPlan("kill@3")
        with pytest.raises(qt.SimulatedPreemption):
            qt.run_resumable(q, _circuit(), ckpt, every=8, faults=plan)
        assert plan.log == ["kill@3"]
        # the kill point's last-good checkpoint carries a LIVE permutation
        loaded = R.load_latest(ckpt, env)
        assert loaded is not None
        meta = loaded[1]
        assert meta["cursor"] == 24
        assert meta["perm"] is not None
        assert meta["perm"] != list(range(N))
        # fresh register, fresh seed state: the process died
        q2 = _fresh(env)
        qt.run_resumable(q2, _circuit(), ckpt, every=8)
        np.testing.assert_array_equal(np.asarray(q2.amps), reference)

    def test_resume_bit_identical_with_pipelined_exchange(
            self, env, tmp_path, reference, monkeypatch):
        """ISSUE 3: the pipelined chunked exchange must not perturb the
        resume contract.  Snapshots taken mid-stream store RAW permuted
        amplitudes, whose layout is chunk-INDEPENDENT — the chunk count
        only reschedules the exchange, it never changes what lands where
        — so a run killed and resumed under QT_EXCHANGE_CHUNKS=4 stays
        bit-identical to the unchunked uninterrupted reference."""
        if env.num_devices < 8:
            pytest.skip("needs the 8-device virtual mesh")
        monkeypatch.setenv("QT_EXCHANGE_CHUNKS", "4")
        ckpt = str(tmp_path / "ck")
        q = _fresh(env)
        with pytest.raises(qt.SimulatedPreemption):
            qt.run_resumable(q, _circuit(), ckpt, every=8,
                             faults=qt.FaultPlan("kill@3"))
        q2 = _fresh(env)
        qt.run_resumable(q2, _circuit(), ckpt, every=8)
        np.testing.assert_array_equal(np.asarray(q2.amps), reference)

    def test_checkpoints_at_window_boundaries_only(self, env, tmp_path):
        """One fusion drain per window: a checkpoint can never land
        mid-window (fusion.py drain counter)."""
        q = _fresh(env)
        qt.run_resumable(q, _circuit(), str(tmp_path / "ck"), every=8)
        assert q._drain_count == len(
            CIRC.plan_checkpoint_boundaries(len(_circuit()), 8))

    def test_resume_refuses_different_circuit(self, env, tmp_path):
        ckpt = str(tmp_path / "ck")
        q = _fresh(env)
        with pytest.raises(qt.SimulatedPreemption):
            qt.run_resumable(q, _circuit(), ckpt, every=8,
                             faults=qt.FaultPlan("kill@2"))
        other = _circuit(depth=2)
        with pytest.raises(qt.QuESTError, match="different circuit"):
            qt.run_resumable(_fresh(env), other, ckpt, every=8)
        # a different cadence changes the window plans too
        with pytest.raises(qt.QuESTError, match="different circuit"):
            qt.run_resumable(_fresh(env), _circuit(), ckpt, every=4)


class TestKillMidSave:
    def test_mid_save_kill_leaves_loadable_last_good(self, env, tmp_path,
                                                     reference):
        ckpt = str(tmp_path / "ck")
        q = _fresh(env)
        plan = qt.FaultPlan("killsave@2")
        with pytest.raises(qt.SimulatedPreemption):
            qt.run_resumable(q, _circuit(), ckpt, every=8, faults=plan)
        assert plan.log == ["killsave@2"]
        loaded = R.load_latest(ckpt, env)
        assert loaded is not None
        # window 2's commit never happened: last-good is window 1's
        assert loaded[1]["cursor"] == 16
        q2 = _fresh(env)
        qt.run_resumable(q2, _circuit(), ckpt, every=8)
        np.testing.assert_array_equal(np.asarray(q2.amps), reference)


class TestCorruptCheckpoint:
    def test_corrupt_newest_falls_back_to_predecessor(self, env, tmp_path,
                                                      reference):
        ckpt = str(tmp_path / "ck")
        q = _fresh(env)
        with pytest.raises(qt.SimulatedPreemption):
            qt.run_resumable(q, _circuit(), ckpt, every=8,
                             faults=qt.FaultPlan("corrupt@2,kill@3"))
        q2 = _fresh(env)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            qt.run_resumable(q2, _circuit(), ckpt, every=8)
        assert any("unreadable" in str(x.message) for x in w)
        np.testing.assert_array_equal(np.asarray(q2.amps), reference)

    def test_all_generations_corrupt_raises(self, env, tmp_path):
        ckpt = tmp_path / "ck"
        q = _fresh(env)
        with pytest.raises(qt.SimulatedPreemption):
            qt.run_resumable(q, _circuit(), str(ckpt), every=8,
                             faults=qt.FaultPlan("kill@3"))
        for gen in ckpt.glob("gen-*"):
            R._corrupt_generation(str(gen))
        with pytest.raises(qt.QuESTError, match="no loadable checkpoint"):
            qt.run_resumable(_fresh(env), _circuit(), str(ckpt), every=8)


class TestTransientIO:
    def test_retry_absorbs_transient_errors(self, env, tmp_path, reference):
        q = _fresh(env)
        plan = qt.FaultPlan("io@3")
        qt.run_resumable(q, _circuit(), str(tmp_path / "ck"), every=8,
                         faults=plan)
        assert plan.log.count("io") == 3
        assert plan.io_budget == 0
        np.testing.assert_array_equal(np.asarray(q.amps), reference)

    def test_retry_io_bounded(self):
        calls = []

        def always_fails():
            calls.append(1)
            raise OSError("disk on fire")

        with pytest.raises(qt.QuESTError, match="failed after 3 attempts"):
            R.retry_io(always_fails, attempts=3, base_delay=0.0,
                       what="test-op")
        assert len(calls) == 3

    def test_retry_io_returns_value(self):
        assert R.retry_io(lambda: 42, attempts=2, base_delay=0.0) == 42


class TestWatchdog:
    def test_health_check_clean(self, env):
        q = _fresh(env)
        norm, finite = qt.checkQuregHealth(q)
        assert finite and abs(norm - 1.0) < 1e-12

    def test_nan_detected_within_one_window(self, env, tmp_path):
        q = _fresh(env)
        with pytest.raises(qt.NumericalHealthError) as ei:
            qt.run_resumable(q, _circuit(), str(tmp_path / "ck"), every=8,
                             watchdog="raise", faults=qt.FaultPlan("nan@1"))
        # injected after window 1 ([8, 16)) -> caught by ITS OWN check
        assert ei.value.window == (8, 16)
        assert not ei.value.finite
        assert "window [8, 16)" in str(ei.value)

    def test_rollback_restores_last_good(self, env, tmp_path, reference):
        ckpt = str(tmp_path / "ck")
        q = _fresh(env)
        with pytest.raises(qt.NumericalHealthError) as ei:
            qt.run_resumable(q, _circuit(), ckpt, every=8,
                             watchdog="rollback",
                             faults=qt.FaultPlan("nan@2"))
        assert ei.value.rolled_back_to == 16
        # register now holds the last-good (16-gate) state
        qp = _fresh(env)
        qt.run_resumable(qp, _circuit()[:16], str(tmp_path / "partial"),
                         every=8)
        np.testing.assert_array_equal(np.asarray(q._amps_raw()),
                                      np.asarray(qp._amps_raw()))
        # and re-entering run_resumable resumes to the full bit-exact end
        q2 = _fresh(env)
        qt.run_resumable(q2, _circuit(), ckpt, every=8)
        np.testing.assert_array_equal(np.asarray(q2.amps), reference)

    def test_renormalize_policy(self, env, tmp_path):
        q = _fresh(env)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            qt.run_resumable(q, _circuit(), str(tmp_path / "ck"), every=8,
                             watchdog="renormalize",
                             faults=qt.FaultPlan("scale@1"))
        assert any("renormalized" in str(x.message) for x in w)
        norm, finite = qt.checkQuregHealth(q)
        assert finite and abs(norm - 1.0) < 1e-10

    def test_renormalize_does_not_mask_nonfinite(self, env, tmp_path):
        """NaN is not drift: the renormalize policy must escalate."""
        q = _fresh(env)
        with pytest.raises(qt.NumericalHealthError):
            qt.run_resumable(q, _circuit(), str(tmp_path / "ck"), every=8,
                             watchdog="renormalize",
                             faults=qt.FaultPlan("inf@1"))

    def test_unknown_policy_rejected(self, env, tmp_path):
        with pytest.raises(qt.QuESTError, match="watchdog policy"):
            qt.run_resumable(_fresh(env), _circuit(),
                             str(tmp_path / "ck"), watchdog="panic")


class TestRNGStateRoundTrip:
    def test_host_mt_stream_resumes(self, env, monkeypatch):
        """seed -> measure x k -> snapshot -> restore -> measure matches
        an uninterrupted run (satellite: MT19937 state round-trip)."""
        monkeypatch.setenv("QT_HOST_MEASURE", "1")
        qt.seedQuEST(env, [11])
        q = qt.createQureg(4, env)
        qt.initPlusState(q)
        for _ in range(3):
            qt.measure(q, 0)
        snap = RNG.GLOBAL_RNG.get_state()
        amps = np.asarray(q.amps).copy()

        qa = qt.createQureg(4, env)
        qa.amps = qa.device_put(amps)
        uninterrupted = [qt.measure(qa, t) for t in (1, 2, 3)]

        RNG.GLOBAL_RNG.set_state(snap)
        qb = qt.createQureg(4, env)
        qb.amps = qb.device_put(amps)
        resumed = [qt.measure(qb, t) for t in (1, 2, 3)]
        assert resumed == uninterrupted

    def test_get_state_is_json_serializable(self):
        json.dumps(RNG.GLOBAL_RNG.get_state())

    def test_device_key_stream_resumes(self, env):
        qt.seedQuEST(env, [13])
        q = qt.createQureg(4, env)
        qt.initPlusState(q)
        qt.measure(q, 0)
        snap = M.KEYS.get_state()
        json.dumps(snap)  # checkpoint-metadata representable
        amps = np.asarray(q._amps_raw()).copy()
        uninterrupted = [qt.measure(q, t) for t in (1, 2, 3)]
        M.KEYS.set_state(snap)
        qb = qt.createQureg(4, env)
        qb.amps = qb.device_put(amps)
        resumed = [qt.measure(qb, t) for t in (1, 2, 3)]
        assert resumed == uninterrupted

    def test_resumed_run_continues_measurement_stream(self, env, tmp_path):
        """The generation metadata carries the RNG state: a measurement
        AFTER a resumed circuit matches the uninterrupted run's."""
        ckpt = str(tmp_path / "ck")
        q = _fresh(env)
        qt.run_resumable(q, _circuit(), str(tmp_path / "ref"), every=8)
        want = qt.measureSequence(q, list(range(N)))[0]

        q2 = _fresh(env)
        with pytest.raises(qt.SimulatedPreemption):
            qt.run_resumable(q2, _circuit(), ckpt, every=8,
                             faults=qt.FaultPlan("kill@3"))
        q3 = _fresh(env)
        qt.run_resumable(q3, _circuit(), ckpt, every=8)
        got = qt.measureSequence(q3, list(range(N)))[0]
        assert got == want


class TestFaultPlanParsing:
    def test_parse_and_env(self, monkeypatch):
        plan = qt.FaultPlan("kill@2, nan@5, io@4")
        assert ("kill", 2) in plan.events
        assert ("nan", 5) in plan.events
        assert plan.io_budget == 4
        monkeypatch.setenv("QT_FAULT_PLAN", "killsave@1")
        got = qt.FaultPlan.from_env()
        assert got is not None and ("killsave", 1) in got.events
        monkeypatch.delenv("QT_FAULT_PLAN")
        assert qt.FaultPlan.from_env() is None

    def test_unknown_kind_rejected(self):
        with pytest.raises(qt.QuESTError, match="unknown fault kind"):
            qt.FaultPlan("meteor@3")  # qlint: allow(fault-plan-spec): deliberately unknown kind — the test pins the rejection path

    def test_exchange_fault_kinds_parse(self):
        plan = qt.FaultPlan("stall@2, shard_loss@3")
        assert ("stall", 2) in plan.events
        assert ("shard_loss", 3) in plan.events

    def test_arm_and_take_exchange_faults(self):
        """Window-keyed arming moves stall/shard_loss into the pending
        slots the dispatch hook drains — shard loss first (it preempts
        the window), one fault per dispatch attempt, then clean."""
        plan = qt.FaultPlan("stall@1, shard_loss@1")
        assert plan.take_exchange_fault("drain") is None  # nothing armed
        plan.arm_exchange_window(0)
        assert plan.take_exchange_fault("drain") is None  # wrong window
        plan.arm_exchange_window(1)
        assert plan.take_exchange_fault("drain") == "shard_loss"
        assert plan.take_exchange_fault("drain") == "stall"
        assert plan.take_exchange_fault("drain") is None
        assert plan.log == ["stall@1", "shard_loss@1"]

    def test_oom_kind_parses_and_arms(self):
        plan = qt.FaultPlan("oom@2")
        assert ("oom", 2) in plan.events
        assert not plan.take_oom_fault()  # not armed yet
        plan.arm_exchange_window(2)
        assert plan.take_oom_fault()  # one event -> one synthetic OOM
        assert not plan.take_oom_fault()
        assert plan.log == ["oom@2"]


class TestOomNet:
    """oom@W: the memory governor's RESOURCE_EXHAUSTED net (ISSUE 9).
    One armed event makes a window's drain dispatch fail once — the net
    evicts idle registers, clears the plan caches, and retries; arming
    the SAME window twice burns the single retry and the failure
    propagates."""

    def test_evict_and_retry_fires_exactly_once(self, env, tmp_path,
                                                reference):
        from quest_tpu import telemetry as T

        q = _fresh(env)
        plan = qt.FaultPlan("oom@2")
        before = T.counter_total("oom_retries_total")
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            qt.run_resumable(q, _circuit(), str(tmp_path), every=8,
                             faults=plan)
        assert plan.log.count("oom@2") == 1
        assert T.counter_total("oom_retries_total") == before + 1
        np.testing.assert_array_equal(np.asarray(q.amps), reference)

    def test_exhaustion_reraises(self, env, tmp_path):
        from quest_tpu import telemetry as T

        q = _fresh(env)
        plan = qt.FaultPlan("oom@2,oom@2")
        before = T.counter_total("oom_retries_total")
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
                qt.run_resumable(q, _circuit(), str(tmp_path), every=8,
                                 faults=plan)
        assert T.counter_total("oom_retries_total") == before + 1

    def test_plain_drain_arms_window_zero(self, env):
        """A gateFusion drain outside run_resumable counts as window 0,
        so oom@0 exercises the net without the checkpoint machinery."""
        from quest_tpu import telemetry as T

        u = np.linalg.qr(np.random.default_rng(5).normal(size=(4, 4)))[0]
        qa = _fresh(env)
        qb = _fresh(env)
        plan = qt.FaultPlan("oom@0")
        before = T.counter_total("oom_retries_total")
        R._ACTIVE_FAULTS[0] = plan
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                with qt.gateFusion(qa):
                    qt.multiQubitUnitary(qa, [0, 1], u)
        finally:
            R._ACTIVE_FAULTS[0] = None
        with qt.gateFusion(qb):
            qt.multiQubitUnitary(qb, [0, 1], u)
        assert plan.log == ["oom@0"]
        assert T.counter_total("oom_retries_total") == before + 1
        np.testing.assert_array_equal(np.asarray(qa.amps),
                                      np.asarray(qb.amps))


@pytest.fixture
def _no_fault_hook():
    """Isolate guarded_dispatch tests from any leftover injection hook,
    and clean up the ones they install."""
    from quest_tpu.parallel import dist as PAR

    old = PAR.EXCHANGE_FAULT_HOOK[0]
    PAR.EXCHANGE_FAULT_HOOK[0] = None
    yield PAR
    PAR.EXCHANGE_FAULT_HOOK[0] = old


class TestGuardedDispatch:
    """Unit contract of dist.guarded_dispatch (the collective guard the
    elastic failover path is built on — tests/test_elastic.py drives it
    end to end through run_resumable)."""

    def test_passthrough_and_latency_histogram(self, _no_fault_hook):
        PAR = _no_fault_hook
        from quest_tpu import telemetry as T

        hist_key = ("exchange_latency_seconds",
                    (("op", "unit_test"), ("shards", "8")))
        T._HISTS.pop(hist_key, None)
        out = PAR.guarded_dispatch(lambda a, k=None: (a, k), 5, k=7,
                                   op="unit_test", shards=8)
        assert out == (5, 7)
        assert T._HISTS[hist_key].as_dict()["count"] == 1

    def test_transient_failure_retried(self, _no_fault_hook):
        PAR = _no_fault_hook
        calls = []

        def flaky(x):
            calls.append(x)
            if len(calls) < 3:
                raise RuntimeError("transient")
            return x * 2

        assert PAR.guarded_dispatch(flaky, 21, op="unit_test") == 42
        assert len(calls) == 3

    def test_exhaustion_raises_shard_loss(self, _no_fault_hook,
                                          monkeypatch):
        PAR = _no_fault_hook
        monkeypatch.setenv("QT_EXCHANGE_RETRIES", "2")

        def always_fails(_x):
            raise RuntimeError("dead link")

        with pytest.raises(PAR.ShardLossError, match="after 2 attempts"):
            PAR.guarded_dispatch(always_fails, 1, op="unit_test")

    def test_injected_shard_loss_raises_immediately(self, _no_fault_hook):
        PAR = _no_fault_hook
        PAR.EXCHANGE_FAULT_HOOK[0] = lambda op: "shard_loss"
        with pytest.raises(PAR.ShardLossError, match="injected shard loss"):
            PAR.guarded_dispatch(lambda x: x, 1, op="unit_test")

    def test_injected_stall_absorbed_and_counted(self, _no_fault_hook):
        PAR = _no_fault_hook
        from quest_tpu import telemetry as T

        faults = iter(["stall"])
        PAR.EXCHANGE_FAULT_HOOK[0] = lambda op: next(faults, None)
        before = T.counter_value("exchange_timeouts_total", op="unit_test")
        assert PAR.guarded_dispatch(lambda x: x + 1, 1, op="unit_test") == 2
        after = T.counter_value("exchange_timeouts_total", op="unit_test")
        assert after == before + 1

    def test_deadline_overrun_counted_but_result_kept(self, _no_fault_hook,
                                                      monkeypatch):
        PAR = _no_fault_hook
        from quest_tpu import telemetry as T

        monkeypatch.setenv("QT_EXCHANGE_DEADLINE_S", "1e-9")  # all late
        before = T.counter_value("exchange_timeouts_total", op="unit_test")
        assert PAR.guarded_dispatch(lambda x: x, 9, op="unit_test") == 9
        after = T.counter_value("exchange_timeouts_total", op="unit_test")
        assert after == before + 1


class TestElasticContracts:
    """Fast unit contracts of the elastic restore path (the full
    save/resume + failover cycles live in tests/test_elastic.py, run by
    make verify-elastic)."""

    def test_validated_perm(self):
        assert R._validated_perm(None, 4) is None
        assert R._validated_perm([1, 0, 2, 3], 4) == (1, 0, 2, 3)
        with pytest.raises(ValueError):
            R._validated_perm([0, 0, 1, 2], 4)  # not a permutation
        with pytest.raises(ValueError):
            R._validated_perm([0, 1], 4)  # wrong length

    def test_shrink_env_validates(self, env):
        from quest_tpu import env as ENV

        with pytest.raises(ValueError):
            ENV.shrink_env(env, 3)  # not a power of two
        with pytest.raises(ValueError):
            ENV.shrink_env(env, 16)  # more devices than survive
        e2 = ENV.shrink_env(env, 2)
        assert e2.num_devices == 2
        assert e2.seeds == env.seeds  # RNG streams belong to the run


class TestBoundaries:
    def test_plan_checkpoint_boundaries(self):
        assert CIRC.plan_checkpoint_boundaries(44, 8) == [8, 16, 24, 32,
                                                          40, 44]
        assert CIRC.plan_checkpoint_boundaries(16, 8) == [8, 16]
        assert CIRC.plan_checkpoint_boundaries(16, 8, start=8) == [16]
        assert CIRC.plan_checkpoint_boundaries(16, 8, start=16) == []
        assert CIRC.plan_checkpoint_boundaries(3, 8) == [3]
        with pytest.raises(ValueError):
            CIRC.plan_checkpoint_boundaries(8, 0)

    def test_completed_run_resumes_to_noop(self, env, tmp_path, reference):
        ckpt = str(tmp_path / "ck")
        q = _fresh(env)
        qt.run_resumable(q, _circuit(), ckpt, every=8)
        # re-entering after completion replays nothing and changes nothing
        q2 = _fresh(env)
        qt.run_resumable(q2, _circuit(), ckpt, every=8)
        np.testing.assert_array_equal(np.asarray(q2.amps), reference)
        assert q2._drain_count == 0
