"""Gate-fusion context (quest_tpu/fusion.py): imperative API gates are
buffered and drained through the circuit scheduler with IDENTICAL
semantics to eager dispatch — only the number of HBM passes changes.
(No reference counterpart: QuEST dispatches gate-at-a-time, QuEST.c.)
"""

import numpy as np
import pytest

import quest_tpu as qt
from quest_tpu import fusion

N = 16  # >= 14 so the windowed scheduler engages


@pytest.fixture
def env():
    # fusion captures only on single-device amplitude meshes (sharded
    # registers use the explicit-distributed path); pin one device
    return qt.createQuESTEnv(num_devices=1)


def _layers(q, n, depth=3):
    for d in range(depth):
        for t in range(n):
            qt.hadamard(q, t)
        for t in range(d % 2, n - 1, 2):
            qt.controlledNot(q, t, t + 1)
    qt.controlledPhaseShift(q, 2, n - 1, 0.3)
    qt.multiStateControlledUnitary(
        q, [0, 9], [0, 1], 4, np.array([[0, 1], [1, 0]], complex))
    qt.tGate(q, 5)
    qt.rotateAroundAxis(q, 7, 0.4, qt.Vector(1.0, 1.0, 0.0))


def _rel_err(a, b):
    return np.abs(a - b).max() / np.abs(b).max()


class TestEquivalence:
    def test_statevector(self, env):
        q0 = qt.createQureg(N, env)
        qt.initPlusState(q0)
        _layers(q0, N)
        ref = np.asarray(q0.amps)

        q1 = qt.createQureg(N, env)
        qt.initPlusState(q1)
        with qt.gateFusion(q1):
            _layers(q1, N)
        assert _rel_err(np.asarray(q1.amps), ref) < 1e-5

    def test_density_matrix(self, env):
        def prog(q):
            qt.hadamard(q, 0)
            qt.controlledNot(q, 0, 5)
            qt.pauliY(q, 3)
            qt.phaseShift(q, 6, 0.7)

        q0 = qt.createDensityQureg(7, env)
        qt.initPlusState(q0)
        prog(q0)
        qt.mixDepolarising(q0, 2, 0.05)
        prog(q0)
        ref = np.asarray(q0.amps)

        q1 = qt.createDensityQureg(7, env)
        qt.initPlusState(q1)
        with qt.gateFusion(q1):
            prog(q1)
            qt.mixDepolarising(q1, 2, 0.05)  # implicit drain mid-context
            prog(q1)
        assert _rel_err(np.asarray(q1.amps), ref) < 1e-5


class TestDrainTriggers:
    def test_read_drains(self, env):
        q = qt.createQureg(N, env)
        qt.initZeroState(q)
        with qt.gateFusion(q):
            qt.hadamard(q, 0)
            assert len(q._fusion.gates) == 1
            p = qt.calcProbOfOutcome(q, 0, 0)  # reads amps -> drain
            assert len(q._fusion.gates) == 0
            assert abs(p - 0.5) < 1e-6

    def test_write_drains_in_order(self, env):
        q = qt.createQureg(N, env)
        qt.initZeroState(q)
        with qt.gateFusion(q):
            qt.pauliX(q, 0)
            qt.initZeroState(q)  # overwrites; buffered X must not leak after
            qt.hadamard(q, 1)
        assert abs(qt.calcProbOfOutcome(q, 0, 1)) < 1e-6
        assert abs(qt.calcProbOfOutcome(q, 1, 1) - 0.5) < 1e-6

    def test_large_gate_falls_back_eagerly(self, env):
        q = qt.createQureg(N, env)
        qt.initPlusState(q)
        u = np.eye(1 << 8, dtype=complex)
        with qt.gateFusion(q):
            qt.hadamard(q, 0)
            qt.applyMatrixN(q, list(range(8)), u)  # 8 qubits > cap
            # the big gate drained the buffer before executing eagerly
            assert len(q._fusion.gates) == 0
        assert abs(qt.calcTotalProb(q) - 1.0) < 1e-5

    def test_context_exit_drains(self, env):
        q = qt.createQureg(N, env)
        qt.initZeroState(q)
        with qt.gateFusion(q):
            qt.hadamard(q, 3)
            assert len(q._fusion.gates) == 1
        assert q._fusion is None
        assert abs(qt.calcProbOfOutcome(q, 3, 0) - 0.5) < 1e-6


class TestSideChannels:
    def test_qasm_recorded_in_call_order(self, env):
        q = qt.createQureg(N, env)
        qt.initZeroState(q)
        qt.startRecordingQASM(q)
        with qt.gateFusion(q):
            qt.hadamard(q, 0)
            qt.controlledNot(q, 0, 1)
        qt.stopRecordingQASM(q)
        text = str(q.qasm_log)
        assert text.index("h q[0]") < text.index("cx q[0],q[1]")

    def test_validation_still_eager(self, env):
        q = qt.createQureg(N, env)
        qt.initZeroState(q)
        with qt.gateFusion(q):
            with pytest.raises(qt.QuESTError):
                qt.hadamard(q, N)  # out of range

    def test_measure_drains(self, env):
        qt.seedQuEST(qt.createQuESTEnv(), [7])
        q = qt.createQureg(N, env)
        qt.initZeroState(q)
        with qt.gateFusion(q):
            qt.pauliX(q, 4)
            outcome = qt.measure(q, 4)
        assert outcome == 1


class TestReviewRegressions:
    def test_failed_drain_restores_buffer(self, env, monkeypatch):
        # ADVICE r1: a drain that raises must not lose the buffered gates
        from quest_tpu import fusion as F

        q = qt.createQureg(N, env)
        qt.initZeroState(q)
        qt.startGateFusion(q)
        qt.pauliX(q, 0)
        qt.hadamard(q, 1)
        assert len(q._fusion.gates) == 2

        def boom(qureg, gates):
            raise RuntimeError("injected drain failure")

        monkeypatch.setattr(F, "_run", boom)
        with pytest.raises(RuntimeError, match="injected"):
            F.drain(q)
        assert len(q._fusion.gates) == 2  # restored, not lost
        monkeypatch.undo()
        qt.stopGateFusion(q)
        assert qt.calcProbOfOutcome(q, 0, 1) == pytest.approx(1.0)

    def test_nested_contexts_keep_outer_buffering(self, env):
        q = qt.createQureg(N, env)
        qt.initZeroState(q)
        with qt.gateFusion(q):
            qt.hadamard(q, 0)
            with qt.gateFusion(q):  # inner context reuses the outer buffer
                qt.hadamard(q, 1)
            assert q._fusion is not None  # outer still active
            qt.hadamard(q, 2)
            assert len(q._fusion.gates) == 3
        assert q._fusion is None
        for t in (0, 1, 2):
            assert abs(qt.calcProbOfOutcome(q, t, 0) - 0.5) < 1e-6

    def test_wide_controlled_not_stays_cheap(self, env):
        # 20 targets under one control must NOT densify 2^20 x 2^20
        n = 22
        q = qt.createQureg(n, env)
        qt.initZeroState(q)
        qt.pauliX(q, n - 1)
        with qt.gateFusion(q):
            qt.multiControlledMultiQubitNot(q, [n - 1], list(range(20)))
        for t in range(20):
            assert abs(qt.calcProbOfOutcome(q, t, 1) - 1.0) < 1e-6

    def test_overwrite_discards_buffer_cheaply(self, env):
        q = qt.createQureg(N, env)
        qt.initZeroState(q)
        with qt.gateFusion(q):
            qt.hadamard(q, 0)
            qt.initClassicalState(q, 5)  # overwrite: buffer dropped unexecuted
            assert len(q._fusion.gates) == 0
        assert abs(qt.calcProbOfOutcome(q, 0, 1) - 1.0) < 1e-6
        assert abs(qt.calcProbOfOutcome(q, 2, 1) - 1.0) < 1e-6


class TestSwapCapture:
    def test_swap_gate_buffers(self, env):
        q = qt.createQureg(N, env)
        qt.initZeroState(q)
        qt.pauliX(q, 2)
        with qt.gateFusion(q):
            qt.hadamard(q, 0)
            qt.swapGate(q, 2, 9)          # buffered, not a drain
            assert len(q._fusion.gates) == 2
        assert abs(qt.calcProbOfOutcome(q, 9, 1) - 1.0) < 1e-6
        assert abs(qt.calcProbOfOutcome(q, 2, 1)) < 1e-6

    def test_swap_gate_density(self, env):
        r = qt.createDensityQureg(7, env)
        qt.initClassicalState(r, 1)
        with qt.gateFusion(r):
            qt.swapGate(r, 0, 6)
        assert abs(qt.calcProbOfOutcome(r, 6, 1) - 1.0) < 1e-6


class TestShardedFusion:
    """Fusion on SHARDED registers: local-bit gates buffer and drain as
    one shard_map program over the amplitude mesh; gates touching
    mesh-coordinate bits drain and run the explicit-distributed path."""

    def test_sharded_drain_matches_eager(self):
        env8 = qt.createQuESTEnv()  # 8 virtual devices -> 3 shard bits
        n = 17                      # nloc = 14: full window space local

        def prog(q):
            for t in range(14):
                qt.hadamard(q, t)
            for t in range(0, 13, 2):
                qt.controlledNot(q, t, t + 1)
            qt.pauliX(q, 16)         # mesh-coordinate bit: eager fallback
            qt.rotateZ(q, 5, 0.3)

        q1 = qt.createQureg(n, env8)
        qt.initZeroState(q1)
        with qt.gateFusion(q1):
            qt.hadamard(q1, 0)
            assert len(q1._fusion.gates) == 1
            prog(q1)
        got = np.asarray(q1.amps)
        extra = qt.createQureg(n, env8)
        qt.initZeroState(extra)
        qt.hadamard(extra, 0)
        prog(extra)
        np.testing.assert_allclose(got, np.asarray(extra.amps), atol=1e-6)
        assert abs(qt.calcTotalProb(q1) - 1.0) < 1e-5

    def test_global_bit_gate_buffers_through_lazy_remap(self):
        """A gate on a mesh-coordinate bit now BUFFERS too: the drain
        relocalizes it at window granularity through the lazy
        logical->physical permutation instead of bailing to the eager
        per-gate path (the communication-avoiding scheduler)."""
        env8 = qt.createQuESTEnv()
        q = qt.createQureg(17, env8)
        qt.initZeroState(q)
        with qt.gateFusion(q):
            qt.hadamard(q, 2)
            assert len(q._fusion.gates) == 1
            qt.hadamard(q, 15)   # >= nloc: stays buffered
            assert len(q._fusion.gates) == 2
        assert abs(qt.calcProbOfOutcome(q, 15, 0) - 0.5) < 1e-6
        assert abs(qt.calcProbOfOutcome(q, 2, 0) - 0.5) < 1e-6
        # probabilities read through the live permutation; a full state
        # read rematerializes canonical order
        assert q._perm is not None
        amps = np.asarray(q.amps)
        assert q._perm is None
        expect = np.zeros(1 << 17)
        expect[[0, 4, 1 << 15, (1 << 15) | 4]] = 0.5
        np.testing.assert_allclose(amps[0], expect, atol=1e-6)


class TestChannelCapture:
    """Depolarise/damping captured as ChannelItems: the one-pass
    elementwise kernels run inside the drain program, interleaved in call
    order with gate segments (never the rank-4 superoperator fold)."""

    def test_channels_interleave_with_gates(self, env):
        n = 4
        def prog(r):
            qt.hadamard(r, 0)
            qt.mixDepolarising(r, 1, 0.1)
            qt.controlledNot(r, 0, 2)
            qt.mixDamping(r, 0, 0.2)
            qt.mixDepolarising(r, 3, 0.05)

        fused = qt.createDensityQureg(n, env)
        qt.initPlusState(fused)
        with qt.gateFusion(fused):
            prog(fused)
            # buffered: 2 gate entries x2 twins... entries stay buffered
            assert any(isinstance(g, fusion.ChannelItem)
                       for g in fused._fusion.gates)
        eager = qt.createDensityQureg(n, env)
        qt.initPlusState(eager)
        prog(eager)
        np.testing.assert_allclose(np.asarray(fused.amps),
                                   np.asarray(eager.amps), atol=1e-12)

    def test_channel_oracle(self, env):
        """Fused channel sequence against the dense Kraus oracle."""
        import oracle

        n = 3
        p1, p2 = 0.3, 0.4
        rng = np.random.default_rng(11)
        mat = oracle.random_density(n, rng)
        r = qt.createDensityQureg(n, env)
        oracle.set_qureg_from_array(qt, r, mat)
        with qt.gateFusion(r):
            qt.mixDepolarising(r, 2, p1)
            qt.mixDamping(r, 1, p2)
        X = oracle.full_operator(n, [2], oracle.X)
        Y = oracle.full_operator(n, [2], oracle.Y)
        Z = oracle.full_operator(n, [2], oracle.Z)
        ref = (1 - p1) * mat + (p1 / 3) * (
            X @ mat @ X + Y @ mat @ Y + Z @ mat @ Z)
        k0 = np.array([[1, 0], [0, np.sqrt(1 - p2)]])
        k1 = np.array([[0, np.sqrt(p2)], [0, 0]])
        ref = oracle.apply_kraus_to_density(ref, n, [1], [k0, k1])
        got = oracle.state_from_qureg(r)
        np.testing.assert_allclose(got, ref, atol=1e-10)

    def test_reprob_no_recompile_key(self, env):
        """Same shape, different probabilities -> same cached plan key."""
        n = 3
        keys = []
        for p in (0.1, 0.25):
            r = qt.createDensityQureg(n, env)
            qt.initPlusState(r)
            with qt.gateFusion(r):
                qt.hadamard(r, 0)
                qt.mixDepolarising(r, 1, p)
                items = list(r._fusion.gates)
                keys.append(fusion._plan_key(
                    items, r.num_qubits_in_state_vec, True))
        assert keys[0] == keys[1]

    def test_sharded_register_channel_capture(self):
        """On a sharded density register, shard-local channels capture and
        the drain (one shard_map) matches the eager path."""
        env8 = qt.createQuESTEnv()
        if env8.num_devices < 8:
            pytest.skip("needs 8 virtual devices")
        n = 7                    # 2n=14 on 8 shards -> nloc=11
        def prog(r):
            qt.hadamard(r, 0)
            qt.mixDepolarising(r, 1, 0.2)   # bits (1, 8): local
            qt.mixDamping(r, 0, 0.1)        # bits (0, 7): local
        fused = qt.createDensityQureg(n, env8)
        qt.initPlusState(fused)
        with qt.gateFusion(fused):
            prog(fused)
        eager = qt.createDensityQureg(n, env8)
        qt.initPlusState(eager)
        prog(eager)
        np.testing.assert_allclose(np.asarray(fused.amps),
                                   np.asarray(eager.amps), atol=1e-12)

    def test_sharded_bra_bit_channel_captured_via_remap(self):
        """A channel whose bra bit is a mesh coordinate is now CAPTURED:
        the drain's window remap pulls the bra bit shard-local (the pair
        kernel runs at the permuted positions — both channel kinds are
        (t, b)-symmetric) and the result matches the eager
        explicit-distributed path."""
        env8 = qt.createQuESTEnv()
        if env8.num_devices < 8:
            pytest.skip("needs 8 virtual devices")
        n = 7
        fused = qt.createDensityQureg(n, env8)
        qt.initPlusState(fused)
        with qt.gateFusion(fused):
            qt.hadamard(fused, 0)
            qt.mixDepolarising(fused, 6, 0.2)   # bra bit 13 >= nloc=11
            assert len(fused._fusion.gates) == 3  # H + bra twin + channel
        eager = qt.createDensityQureg(n, env8)
        qt.initPlusState(eager)
        qt.hadamard(eager, 0)
        qt.mixDepolarising(eager, 6, 0.2)
        np.testing.assert_allclose(np.asarray(fused.amps),
                                   np.asarray(eager.amps), atol=1e-12)

    def test_channel_sweep_path(self, env, monkeypatch):
        """With sweeps enabled (interpret opt-in on CPU), a noise layer on
        a >= 15-bit register drains through apply_pair_channel_sweep and
        matches the eager path."""
        monkeypatch.setenv("QT_CHAN_SWEEP_INTERPRET", "1")
        n = 8                              # nn = 16 >= 15
        def prog(r):
            qt.hadamard(r, 0)
            for q in range(n):
                qt.mixDepolarising(r, q, 0.04 + 0.01 * q)
            qt.mixDamping(r, 2, 0.3)
        fused = qt.createDensityQureg(n, env)
        qt.initPlusState(fused)
        with qt.gateFusion(fused):
            prog(fused)
        eager = qt.createDensityQureg(n, env)
        qt.initPlusState(eager)
        prog(eager)
        np.testing.assert_allclose(np.asarray(fused.amps),
                                   np.asarray(eager.amps), atol=1e-5)


def test_sharded_drain_channel_sweep(monkeypatch):
    """ADVICE r3 (a): the chansweep branch INSIDE the sharded drain's
    shard_map actually runs (needs nloc >= 15: a 9q rho over 8 devices
    gives nloc = 15) and matches the eager per-channel path.  f32 +
    QT_CHAN_SWEEP_INTERPRET=1 so channel_sweep_enabled engages on the
    CPU interpret path."""
    env = qt.createQuESTEnv()   # the full 8-device mesh, not the pinned
    if env.num_devices < 8:      # single-device fixture this module uses
        pytest.skip("needs the 8-device virtual mesh")
    monkeypatch.setenv("QT_CHAN_SWEEP_INTERPRET", "1")
    from quest_tpu.ops import fused as F
    calls = {"n": 0}
    real_sweep = F.apply_pair_channel_sweep

    def spy(*a, **k):
        calls["n"] += 1
        return real_sweep(*a, **k)

    monkeypatch.setattr(F, "apply_pair_channel_sweep", spy)
    qt.set_precision(1)
    try:
        nq = 9
        r1 = qt.createDensityQureg(nq, env)
        qt.initPlusState(r1)
        r2 = qt.createDensityQureg(nq, env)
        qt.initPlusState(r2)

        def noise(r):
            for t in range(6):   # bra bit t+9 < nloc=15 so channels capture
                qt.mixDepolarising(r, t, 0.03 + 0.01 * t)
            qt.hadamard(r, 0)
            for t in range(6):
                qt.mixDamping(r, t, 0.02)

        with qt.gateFusion(r1):
            noise(r1)
        noise(r2)
        assert calls["n"] >= 1, "chansweep branch never ran in the drain"
        np.testing.assert_allclose(np.asarray(r1.amps), np.asarray(r2.amps),
                                   atol=5e-6)
        assert abs(qt.calcTotalProb(r1) - 1.0) < 1e-5
    finally:
        qt.set_precision(2)
