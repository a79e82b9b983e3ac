"""Communication-avoiding lazy qubit remapping (the mpiQulacs-style
scheduler, arXiv:2203.16044): the distributed planner keeps the state in a
permuted physical order, schedules ONE batched remap per window of gates
instead of two half-shard exchanges per sharded-target gate, and only
rematerializes canonical order on a state read.

Covers the acceptance contract:
  * HLO-audited collective counts: a circuit with k sharded-target gates
    across w windows emits O(w) remap exchanges, not 2k half-shard
    ppermutes;
  * final amplitudes BIT-IDENTICAL to the eager swap-in/swap-out per-gate
    path (dist.use_lazy_remap(False));
  * every read (calcProbOfOutcome, measurement, checkpoint write, host
    gather) returns canonical-order results while a permutation is live —
    including reads interleaved mid-circuit.
"""

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

import oracle
import quest_tpu as qt
from quest_tpu import circuit as CIRC
from quest_tpu import fusion
from quest_tpu.ops import fused as F
from quest_tpu.parallel import dist

N = 6  # 64 amps over 8 devices -> nloc = 3: qubits 3, 4, 5 are sharded
# the HBM a v5e program may use, as its compiler reports it ("15.75G")
V5E_HBM_BYTES = int(15.75 * (1 << 30))
ATOL = 1e-12

_COLLECTIVE_OPS = (
    "all-reduce", "all-reduce-start", "collective-permute",
    "collective-permute-start", "all-gather", "all-gather-start",
    "all-to-all", "reduce-scatter",
)


def _hlo_collectives(jitted, *args):
    txt = jitted.lower(*args).compile().as_text()
    hist = {}
    for op in _COLLECTIVE_OPS:
        c = txt.count(f" {op}(")
        if c:
            hist[op] = c
    return hist


@pytest.fixture(autouse=True)
def _require_multidevice(env):
    if env.num_devices < 8:
        pytest.skip("needs the 8-device virtual mesh")


@pytest.fixture(autouse=True)
def _lazy_on():
    dist.use_lazy_remap(True)
    yield
    dist.use_lazy_remap(True)


def _rand_psi(env, rng, n=N):
    vec = oracle.random_state(n, rng)
    q = qt.createQureg(n, env)
    oracle.set_qureg_from_array(qt, q, vec)
    return q, vec


H_SOA = np.stack([(1 / np.sqrt(2)) * np.array([[1.0, 1], [1, -1]]),
                  np.zeros((2, 2))])


# ---------------------------------------------------------------------------
# Unit level: the permutation algebra
# ---------------------------------------------------------------------------


class TestRemapAlgebra:
    def test_decompose_sigma_classes(self):
        # n=6, nloc=3, r=3: swap (0<->3), pure local swap (1<->2),
        # pure mesh swap (4<->5)
        sigma = (3, 2, 1, 0, 5, 4)
        mixed, local_perm, mesh_tau = dist.decompose_sigma(sigma, 3, 3)
        assert mixed == ((0, 0),)          # local bit 0 <-> mesh bit 0
        assert local_perm == (0, 2, 1)     # swap local bits 1, 2
        assert mesh_tau == (0, 2, 1)       # swap mesh bits 1, 2

    def test_remap_sharded_is_the_bit_permutation(self, env):
        rng = np.random.default_rng(3)
        q, vec = _rand_psi(env, rng)
        sigma = (3, 2, 1, 0, 5, 4)
        got = dist.remap_sharded(q.amps, mesh=env.mesh, num_qubits=N,
                                 sigma=sigma)
        out = np.asarray(got)[0] + 1j * np.asarray(got)[1]
        idx = np.arange(1 << N)
        dest = np.zeros_like(idx)
        for p in range(N):
            dest |= ((idx >> p) & 1) << sigma[p]
        expect = np.zeros_like(vec)
        expect[dest] = vec[idx]
        np.testing.assert_allclose(out, expect, atol=0)

    def test_plan_window_remap_keeps_residents(self):
        # wanted {0, 4}: 0 already local stays; 4 swaps with the local
        # slot whose resident is needed furthest (qubit 2, never again)
        sigma, perm = dist.plan_window_remap(
            6, 3, tuple(range(6)), [0, 4], next_use={1: 0, 0: 1})
        assert perm[0] == 0 and perm[4] == 2 and perm[2] == 4
        assert sigma[2] == 4 and sigma[4] == 2
        # already-local window: no movement
        sigma, perm = dist.plan_window_remap(6, 3, tuple(range(6)), [0, 1])
        assert sigma is None and perm == tuple(range(6))
        # over-capacity window is rejected, not mangled
        sigma, perm = dist.plan_window_remap(6, 3, tuple(range(6)),
                                             [0, 1, 2, 3])
        assert sigma is None and perm is None

    def test_plan_remap_windows_one_remap_per_window(self):
        # 3 windows of 3 distinct qubits on nloc=3: {3,4,5}, {0,1,2},
        # {3,4,5} — one sigma each, and window 2's sigma undoes nothing
        # (the permutation persists, no swap-back)
        bits = [(3,), (4,), (5,), (0,), (1,), (2,), (3,), (4,), (5,)]
        segments, final_perm = CIRC.plan_remap_windows(bits, 6, 3)
        assert [seg[0] for seg in segments] == [(0, 3), (3, 6), (6, 9)]
        assert all(seg[1] is not None for seg in segments)
        # every window's qubits are local under its perm
        for (i, j), _, perm in segments:
            for k in range(i, j):
                assert all(perm[b] < 3 for b in bits[k])
        assert sorted(final_perm) == list(range(6))


class TestCanonicalShardRemap:
    """Shards of 2^14+ amplitudes are held in the canonical
    (2, B, 128, 128) shape and swap their halves in place, chunk by
    chunk (dist._swap_halves_canonical); the result is the same bit
    permutation the flat path applies."""

    # lane bit, sublane bit, block bit with both halves in a chunk, block
    # bit whose halves are a chunk apart (n = 20 over 8 devices: nloc 17,
    # B = 8 blocks per shard)
    @pytest.mark.parametrize("lb", [3, 9, 14, 16])
    @pytest.mark.parametrize("chunks", [1, 4])
    def test_canonical_swap_is_the_flat_swap(self, env, lb, chunks):
        n = 20
        nloc = n - 3
        sigma = list(range(n))
        sigma[lb], sigma[n - 2] = n - 2, lb
        sigma = tuple(sigma)
        vec = np.random.default_rng(lb).standard_normal((2, 1 << n))
        shards = NamedSharding(env.mesh, P(None, "amps"))
        flat = jax.device_put(vec, shards)
        want = np.asarray(dist.remap_sharded(
            flat, mesh=env.mesh, num_qubits=n, sigma=sigma,
            chunks=(chunks, chunks)))
        canon = jax.device_put(vec.reshape(2, 1 << (n - 14), 128, 128),
                               shards)
        got = dist.remap_sharded(canon, mesh=env.mesh, num_qubits=n,
                                 sigma=sigma, chunks=(chunks, chunks))
        assert got.shape == canon.shape
        assert dist.decompose_sigma(sigma, nloc, 3)[0] == ((lb, 1),)
        np.testing.assert_array_equal(np.asarray(got).reshape(2, -1), want)

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_large_shard_residue_routes_through_a_mesh_bit(
            self, seed, monkeypatch):
        """At 8 GiB f32 shards (32 qubits over four v5e chips) a remap
        whose local residue keeps the block bits in place decomposes into
        ordered half-shard swaps of block bits only, no axis permutation
        (its second copy does not fit the chip), and the swaps replayed
        in order realise sigma; the cost model counts each of them."""
        n, nloc, r = 32, 30, 2
        monkeypatch.setenv("QT_HBM_BUDGET_BYTES", str(V5E_HBM_BYTES))
        rng = np.random.default_rng(seed)
        sigma = tuple(list(range(14))
                      + [int(b) for b in rng.permutation(range(14, n))])
        mixed, local_perm, mesh_tau = dist.decompose_sigma(sigma, nloc, r,
                                                           4)
        assert local_perm is None
        assert mixed and min(lb for lb, _mb in mixed) >= 14
        held = list(range(n))     # held[p]: the bit now at position p
        for lb, mb in mixed:
            held[lb], held[nloc + mb] = held[nloc + mb], held[lb]
        if mesh_tau is not None:
            top = held[nloc:]
            for b, dst in enumerate(mesh_tau):
                held[nloc + dst] = top[b]
        assert [held[sigma[p]] for p in range(n)] == list(range(n))
        assert dist.remap_exchange_count(sigma, nloc, r, 4) == (
            len(mixed) + (mesh_tau is not None))

    @pytest.mark.parametrize("nloc,itemsize,budget,routed", [
        (30, 4, V5E_HBM_BYTES, True),      # 8 GiB shard: no room for a copy
        (29, 4, V5E_HBM_BYTES, False),     # 4 GiB shard and its copy fit
        (29, 8, V5E_HBM_BYTES, True),      # the same shard in f64: 8 GiB
        (30, 4, None, False),              # no budget known (the CPU)
    ])
    def test_residue_route_follows_the_chip_memory(
            self, monkeypatch, nloc, itemsize, budget, routed):
        """The residue is routed exactly where the shard and the axis
        permutation's copy of it exceed the per-chip HBM budget, and the
        cost model counts the path the remap takes."""
        if budget is None:
            monkeypatch.delenv("QT_HBM_BUDGET_BYTES", raising=False)
        else:
            monkeypatch.setenv("QT_HBM_BUDGET_BYTES", str(budget))
        assert dist.route_residue(nloc, itemsize) == (
            routed and budget is not None)
        r = 2
        sigma = list(range(nloc + r))
        sigma[20], sigma[21], sigma[22] = 21, 22, 20    # a 3-cycle
        mixed, local_perm, _tau = dist.decompose_sigma(tuple(sigma), nloc,
                                                       r, itemsize)
        assert (local_perm is None) == routed
        assert len(mixed) == (4 if routed else 0)
        assert dist.remap_exchange_count(tuple(sigma), nloc, r,
                                         itemsize) == len(mixed)

    @pytest.mark.parametrize("chunks", [1, 4])
    def test_routed_residue_is_the_axis_permutation(self, env, monkeypatch,
                                                    chunks):
        """The routed swaps give the state the axis permutation gives: a
        20-qubit register over 8 devices (canonical shards, block bits
        14..16) with an HBM budget below twice its 2 MiB shard."""
        n, nloc = 20, 17
        # a local 3-cycle of block bits and a block bit <-> mesh crossing
        sigma = list(range(n))
        sigma[14], sigma[15], sigma[16] = 16, 14, 15
        sigma[13], sigma[18] = 18, 13
        sigma = tuple(sigma)
        vec = np.random.default_rng(chunks).standard_normal((2, 1 << n))
        shards = NamedSharding(env.mesh, P(None, "amps"))
        canon = vec.reshape(2, 1 << (n - 14), 128, 128)
        # out index bit sigma[p] = in index bit p (axis 1 + k holds bit
        # n - 1 - k of the index)
        axes = [0] * n
        for p in range(n):
            axes[n - 1 - sigma[p]] = 1 + n - 1 - p
        want = vec.reshape((2,) + (2,) * n).transpose(
            [0] + axes).reshape(canon.shape)
        monkeypatch.delenv("QT_HBM_BUDGET_BYTES", raising=False)
        assert dist.decompose_sigma(sigma, nloc, 3)[1] is not None
        monkeypatch.setenv("QT_HBM_BUDGET_BYTES", str(3 << 20))
        mixed, local_perm, _tau = dist.decompose_sigma(sigma, nloc, 3)
        assert local_perm is None and len(mixed) == 1 + 4
        got = dist.remap_sharded(jax.device_put(canon, shards),
                                 mesh=env.mesh, num_qubits=n, sigma=sigma,
                                 chunks=(chunks, chunks))
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_large_shard_windows_swap_block_bits_only(self):
        """A 32-qubit register over four chips (8 GiB shards) plans every
        remap of chip_smoke's circuit as swaps of block bits (>= 14),
        the ones that run in place; a small shard may use any slot."""
        import chip_smoke

        n, nloc = 32, 30
        bits = [op[2:3] if op[0] == "rot" else op[1:3]
                for op in chip_smoke.random_circuit(n)]
        assert dist.remap_window_cap(nloc) == nloc - 14
        assert dist.remap_window_cap(27) == 27
        segments, _perm = CIRC.plan_remap_windows(bits, n, nloc)
        swapped = [lb for _ij, sigma, _p in segments if sigma is not None
                   for lb, _mb in dist.decompose_sigma(sigma, nloc, 2)[0]]
        assert swapped and min(swapped) >= 14


# ---------------------------------------------------------------------------
# HLO audit: O(windows) exchanges, not O(2 * sharded gates)
# ---------------------------------------------------------------------------


class TestWindowExchangeCounts:
    def test_drain_program_emits_one_exchange_per_window(self, env):
        """k = 18 sharded-target gates across w = 3 windows: the compiled
        drain program contains EXACTLY 3 half-shard exchanges per window
        (every window displaces all three local residents) = 9
        collective-permutes total — the per-gate path would cost 2k = 36."""
        n, nloc = N, 3
        items = []
        # window 1: 6 gates on {3, 4, 5}; window 2: 6 on {0, 1, 2} (which
        # window 1 evicted to mesh bits!); window 3: 6 on {3, 4, 5} again
        for block in ([3, 4, 5], [0, 1, 2], [3, 4, 5]):
            for t in block + block:
                items.append(CIRC.Gate((t,), H_SOA))
        k = sum(1 for it in items)          # 18 gates
        program, arrays, final_perm = fusion._split_items_sharded(
            items, n, nloc, None, False)
        remaps = [p for p in program if p[0] == "remap"]
        assert len(remaps) == 3             # ONE remap per window
        runner = fusion._plan_runner(nloc, program, env.mesh,
                                     F.matmul_precision_name())
        amps = qt.createQureg(n, env).amps
        hist = _hlo_collectives(runner, amps, tuple(arrays), ())
        assert set(hist) <= {"collective-permute"}, hist
        # each remap moves every qubit of its window across the boundary:
        # 3 half-shard exchanges per window, 9 total — far below the
        # per-gate path's 2k = 36 (audited: swap-in + swap-out per gate)
        assert hist.get("collective-permute", 0) == 9
        assert hist.get("collective-permute", 0) < 2 * k

    def test_final_materialization_is_one_remap(self, env):
        """Rematerializing canonical order from any live permutation is
        ONE batched remap: <= r mixed half-shard exchanges + <= 1 composed
        shard permutation, never per-gate."""
        perm = (3, 4, 5, 0, 1, 2)           # all six qubits displaced
        sigma = dist.canonical_sigma(perm)
        amps = qt.createQureg(N, env).amps

        def f(a):
            return dist.remap_sharded(a, mesh=env.mesh, num_qubits=N,
                                      sigma=sigma)

        hist = _hlo_collectives(jax.jit(f), amps)
        assert set(hist) <= {"collective-permute"}, hist
        assert hist.get("collective-permute", 0) <= 4  # r mixed + 1 composed

    def test_eager_amortization_one_swap_round_for_k_gates(self, env, monkeypatch):
        """The imperative (unfused) path through the lazy permutation:
        k repeated multi-target gates on the same sharded qubits cost ONE
        round of relocation swaps; with lazy remap disabled they cost 2k
        (the reference's per-gate swap-in/swap-out)."""
        rng = np.random.default_rng(21)
        u = oracle.random_unitary(2, rng)
        calls = []
        orig = dist.swap_sharded

        def counting(*a, **kw):
            calls.append(kw["qb_high"])
            return orig(*a, **kw)

        monkeypatch.setattr(dist, "swap_sharded", counting)
        q, vec = _rand_psi(env, rng)
        for _ in range(5):
            qt.multiQubitUnitary(q, [4, 5], u)
        assert len(calls) == 2              # one swap per sharded target, once
        calls.clear()
        dist.use_lazy_remap(False)
        q2, _ = _rand_psi(env, rng)
        for _ in range(5):
            qt.multiQubitUnitary(q2, [4, 5], u)
        assert len(calls) == 2 * 2 * 5      # 2 targets x (in + out) x 5 gates


# ---------------------------------------------------------------------------
# Bit-identity with the eager per-gate path
# ---------------------------------------------------------------------------


def _alternating_circuit(q, u1, u2):
    """Local and sharded targets interleaved; multi-target sharded gates
    force relocation."""
    qt.hadamard(q, 0)
    qt.multiQubitUnitary(q, [4, 5], u2)
    qt.unitary(q, 3, u1)
    qt.hadamard(q, 1)
    qt.multiQubitUnitary(q, [4, 5], u2)
    qt.controlledUnitary(q, 0, 4, u1)
    qt.multiQubitUnitary(q, [3, 4], u2)
    qt.pauliX(q, 5)
    qt.tGate(q, 4)
    qt.swapGate(q, 0, 5)


def _relocation_circuit(q, u2):
    """Multi-target sharded gates + pure-movement/diagonal gates: every
    gate runs the SAME arithmetic kernel (apply_matrix after relocation)
    under both the lazy and the eager swap-back path, so outputs are
    bitwise comparable.  (1q gates on sharded targets are excluded: the
    eager path combines them in the ppermute-exchange kernel while the
    lazy path applies them locally after a remap — mathematically equal,
    1-ulp different.)"""
    qt.hadamard(q, 0)
    qt.multiQubitUnitary(q, [4, 5], u2)
    qt.multiQubitUnitary(q, [4, 5], u2)
    qt.multiQubitUnitary(q, [3, 4], u2)
    qt.pauliX(q, 5)
    qt.tGate(q, 4)
    qt.swapGate(q, 0, 5)
    qt.multiQubitUnitary(q, [3, 5], u2)


class TestBitIdentity:
    def test_lazy_vs_eager_bitwise(self, env):
        rng = np.random.default_rng(31)
        u2 = oracle.random_unitary(2, rng)

        def run():
            q, _ = _rand_psi(env, np.random.default_rng(32))
            _relocation_circuit(q, u2)
            return np.asarray(q.amps)

        lazy = run()
        dist.use_lazy_remap(False)
        eager = run()
        np.testing.assert_array_equal(lazy, eager)

    def test_fused_drain_vs_eager(self, env):
        """The windowed-remap drain vs the eager per-gate swap-back path:
        remaps and relocation swaps are pure data movement, but the window
        planner may localize a gate to different physical slots than the
        per-gate relocalizer, where apply_matrix can take a different
        (mathematically identical) internal branch — equal to ~1 ulp,
        matching the pre-existing fused-vs-eager contract
        (test_fusion.test_sharded_drain_matches_eager)."""
        rng = np.random.default_rng(33)
        u2 = oracle.random_unitary(2, rng)

        def run(use_fusion):
            q, _ = _rand_psi(env, np.random.default_rng(34))
            if use_fusion:
                with qt.gateFusion(q):
                    _relocation_circuit(q, u2)
            else:
                _relocation_circuit(q, u2)
            return np.asarray(q.amps)

        fused_out = run(True)
        dist.use_lazy_remap(False)
        eager = run(False)
        np.testing.assert_allclose(fused_out, eager, atol=1e-14)

    def test_mixed_circuit_lazy_vs_eager(self, env):
        """Circuits mixing 1q sharded-target gates select different (but
        mathematically identical) kernels per path — equal to ~1 ulp."""
        rng = np.random.default_rng(37)
        u1 = oracle.random_unitary(1, rng)
        u2 = oracle.random_unitary(2, rng)

        def run():
            q, _ = _rand_psi(env, np.random.default_rng(38))
            _alternating_circuit(q, u1, u2)
            return np.asarray(q.amps)

        lazy = run()
        dist.use_lazy_remap(False)
        eager = run()
        np.testing.assert_allclose(lazy, eager, atol=1e-14)

    def test_lazy_vs_oracle(self, env):
        rng = np.random.default_rng(35)
        u1 = oracle.random_unitary(1, rng)
        u2 = oracle.random_unitary(2, rng)
        q, vec = _rand_psi(env, rng)
        _alternating_circuit(q, u1, u2)
        SW = np.array([[1, 0, 0, 0], [0, 0, 1, 0],
                       [0, 1, 0, 0], [0, 0, 0, 1]])
        T = np.diag([1, np.exp(1j * np.pi / 4)])
        e = oracle.apply_to_statevec(vec, N, [0], oracle.H)
        e = oracle.apply_to_statevec(e, N, [4, 5], u2)
        e = oracle.apply_to_statevec(e, N, [3], u1)
        e = oracle.apply_to_statevec(e, N, [1], oracle.H)
        e = oracle.apply_to_statevec(e, N, [4, 5], u2)
        e = oracle.apply_to_statevec(e, N, [4], u1, controls=[0])
        e = oracle.apply_to_statevec(e, N, [3, 4], u2)
        e = oracle.apply_to_statevec(e, N, [5], oracle.X)
        e = oracle.apply_to_statevec(e, N, [4], T)
        e = oracle.apply_to_statevec(e, N, [0, 5], SW)
        np.testing.assert_allclose(oracle.state_from_qureg(q), e, atol=ATOL)

    def test_density_twin_through_lazy_path(self, env):
        n = 4
        rng = np.random.default_rng(36)
        mat = oracle.random_density(n, rng)
        r = qt.createDensityQureg(n, env)
        oracle.set_qureg_from_array(qt, r, mat)
        u = oracle.random_unitary(2, rng)
        qt.multiQubitUnitary(r, [2, 3], u)   # bra bits 6, 7 sharded
        assert r._perm is not None
        U = oracle.full_operator(n, [2, 3], u)
        np.testing.assert_allclose(oracle.state_from_qureg(r),
                                   U @ mat @ U.conj().T, atol=1e-10)
        assert abs(qt.calcTotalProb(r) - 1.0) < 1e-10


# ---------------------------------------------------------------------------
# Reads rematerialize canonical order (interleaved mid-circuit)
# ---------------------------------------------------------------------------


class TestReadsMaterializeCanonical:
    def _permuted_state(self, env, rng):
        u2 = oracle.random_unitary(2, rng)
        q, vec = _rand_psi(env, rng)
        qt.multiQubitUnitary(q, [4, 5], u2)
        assert q._perm is not None          # laziness actually engaged
        return q, oracle.apply_to_statevec(vec, N, [4, 5], u2)

    def test_calc_prob_of_outcome_mid_circuit(self, env):
        rng = np.random.default_rng(41)
        q, expect = self._permuted_state(env, rng)
        p = np.abs(expect) ** 2
        idx = np.arange(1 << N)
        for t in (0, 4):
            want0 = p[(idx >> t) & 1 == 0].sum()
            assert abs(qt.calcProbOfOutcome(q, t, 0) - want0) < 1e-10
        # ... and the circuit continues correctly after the read
        qt.hadamard(q, 5)
        expect = oracle.apply_to_statevec(expect, N, [5], oracle.H)
        np.testing.assert_allclose(oracle.state_from_qureg(q), expect,
                                   atol=ATOL)

    def test_get_amp_and_total_prob(self, env):
        rng = np.random.default_rng(42)
        q, expect = self._permuted_state(env, rng)
        a = qt.getAmp(q, 5)
        assert abs(a - expect[5]) < 1e-12
        assert abs(qt.calcTotalProb(q) - 1.0) < 1e-12

    def test_measurement_with_live_perm(self, env):
        rng = np.random.default_rng(43)
        q, expect = self._permuted_state(env, rng)
        prob = qt.collapseToOutcome(q, 4, 0)
        idx = np.arange(1 << N)
        mask = ((idx >> 4) & 1) == 0
        want = (np.abs(expect) ** 2)[mask].sum()
        assert abs(prob - want) < 1e-10
        coll = expect * mask / np.sqrt(want)
        np.testing.assert_allclose(oracle.state_from_qureg(q), coll,
                                   atol=1e-10)

    def test_checkpoint_write_is_canonical(self, env, tmp_path):
        rng = np.random.default_rng(44)
        q, expect = self._permuted_state(env, rng)
        path = str(tmp_path / "state.csv")
        qt.writeStateToFile(q, path)
        q2 = qt.createQureg(N, env)
        assert qt.readStateFromFile(q2, path)
        np.testing.assert_allclose(oracle.state_from_qureg(q2), expect,
                                   atol=1e-12)

    def test_host_gather_is_canonical(self, env):
        rng = np.random.default_rng(45)
        q, expect = self._permuted_state(env, rng)
        raw = np.asarray(q.amps)            # the host-gather read
        np.testing.assert_allclose(raw[0] + 1j * raw[1], expect,
                                   atol=ATOL)
        assert q._perm is None

    def test_read_inside_fusion_context(self, env):
        rng = np.random.default_rng(46)
        q, vec = _rand_psi(env, rng)
        e = vec
        with qt.gateFusion(q):
            for t in (3, 4, 5, 0):
                qt.hadamard(q, t)
                e = oracle.apply_to_statevec(e, N, [t], oracle.H)
            p0 = qt.calcProbOfOutcome(q, 5, 0)   # drains + materializes
            idx = np.arange(1 << N)
            want = (np.abs(e) ** 2)[((idx >> 5) & 1) == 0].sum()
            assert abs(p0 - want) < 1e-10
            for t in (1, 5):
                qt.hadamard(q, t)
                e = oracle.apply_to_statevec(e, N, [t], oracle.H)
        np.testing.assert_allclose(oracle.state_from_qureg(q), e, atol=ATOL)
