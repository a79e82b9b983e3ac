"""Collective-emission audits: what XLA actually compiles per op family.

SURVEY.md §7.5 calls for benchmarking/verifying the explicit ppermute
layer against GSPMD propagation; VERDICT r1 item 4 asks for "a test that
counts/asserts the collectives in the compiled program per op family".
These tests lower each family against 8-way-sharded avals on the virtual
CPU mesh and assert which communication primitives appear:

- elementwise families (dephasing, DiagonalOp apply, phase functions,
  parity phases) must compile to ZERO collectives — their masks derive
  from the global index, which GSPMD computes per-shard (the reference's
  "no pairing" phase kernels, QuEST_cpu.c:3146-3361, have the same
  property: no MPI exchange);
- reductions must emit all-reduce (the reference's MPI_Allreduce,
  QuEST_cpu_distributed.c:35-117);
- the explicit distributed layer's sharded-target gates must emit
  collective-permute (the reference's pairwise MPI_Sendrecv, :489-517);
- amplitude-pair families on mesh-coordinate bits (depolarising,
  damping, the fused QFT's high ladders + bit reversal) must emit SOME
  collective (permute / all-to-all / all-gather), and the elementwise
  ones must not regress into them.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import quest_tpu as qt
from quest_tpu import circuit as CIRC
from quest_tpu import introspect
from quest_tpu.env import AMP_AXIS
from quest_tpu.introspect import CollectiveBudget
from quest_tpu.ops import density as D
from quest_tpu.ops import kernels as K
from quest_tpu.ops import phasefunc as PF
from quest_tpu.parallel import dist as PAR

# the audit recipe these tests pioneered is now the public runtime API
# (quest_tpu.introspect, ISSUE 8); the module-level names stay because
# test_mesh_sweep imports them
COLLECTIVE_RE = introspect.COLLECTIVE_RE
_COLLECTIVE_OPS = introspect.COLLECTIVE_OPS


def collective_ops(fn, *args, donate=False):
    """Histogram of ACTUAL collective instructions in the optimized HLO
    (exact opcode occurrences, not word matches) — introspect.audit."""
    return introspect.audit(fn, *args, donate=donate).collectives


@pytest.fixture(scope="module")
def env8():
    e = qt.createQuESTEnv()
    if e.num_ranks < 8:
        pytest.skip("needs the 8-device virtual mesh")
    return e


def collectives(fn, *args, env=None, donate=False):
    """Compile fn against sharded args and histogram the loose collective
    word matches in the optimized HLO (introspect.audit's upper-bound
    view — metadata mentions included)."""
    return introspect.audit(fn, *args, donate=donate).matches


def sharded_state(env, n, seed=0):
    rng = np.random.default_rng(seed)
    amps = rng.standard_normal((2, 1 << n))
    amps /= np.sqrt((amps ** 2).sum())
    return jax.device_put(jnp.asarray(amps), env.amp_sharding())


class TestElementwiseFamiliesNoComm:
    """Index-derived elementwise ops must partition with zero collectives."""

    def test_dephasing_density(self, env8):
        nq = 7                       # rho -> 14 sv qubits, 3 sharded
        amps = sharded_state(env8, 2 * nq, 1)

        def f(a):
            return D.mix_dephasing(a, 0.3, num_qubits=nq, target=nq - 1)

        assert collectives(f, amps) == {}

    def test_two_qubit_dephasing_density(self, env8):
        nq = 7
        amps = sharded_state(env8, 2 * nq, 2)

        def f(a):
            return D.mix_two_qubit_dephasing(
                a, 0.3, num_qubits=nq, qubit1=0, qubit2=nq - 1)

        assert collectives(f, amps) == {}

    def test_diagonal_op_apply(self, env8):
        n = 14
        amps = sharded_state(env8, n, 3)
        op = jax.device_put(jnp.ones((1 << n,), amps.dtype),
                            env8.vec_sharding())

        def f(a):
            return K.apply_full_diagonal(a, op, op * 0.5)

        assert collectives(f, amps) == {}

    def test_phase_func(self, env8):
        n = 14
        amps = sharded_state(env8, n, 4)

        def f(a):
            return PF.apply_phase_func(
                a, np.asarray([0.5]), np.asarray([2.0]),
                np.zeros((0, 1), np.int64), np.zeros((0,), np.float64),
                num_qubits=n, qubits=tuple(range(6)), encoding=0)

        assert collectives(f, amps) == {}

    def test_parity_phase(self, env8):
        n = 14
        amps = sharded_state(env8, n, 5)

        def f(a):
            # parity phase across local AND mesh-coordinate bits
            return K.apply_parity_phase(a, 0.7, num_qubits=n,
                                        qubits=(0, n - 1))

        assert collectives(f, amps) == {}


class TestReductionsAllReduce:
    def test_total_prob_explicit(self, env8):
        amps = sharded_state(env8, 14, 6)

        def f(a):
            return PAR.total_prob_sharded(a, mesh=env8.mesh)

        hist = collectives(f, amps)
        assert hist.get("all-reduce", 0) >= 1, hist

    def test_expec_diagonal(self, env8):
        n = 14
        amps = sharded_state(env8, n, 7)
        op = jax.device_put(jnp.ones((1 << n,), amps.dtype),
                            env8.vec_sharding())

        def f(a):
            from quest_tpu.ops import calculations as C
            return C.calc_expec_diagonal_statevec(a, op, op * 0.0)

        hist = collectives(f, amps)
        assert hist.get("all-reduce", 0) >= 1, hist


class TestExplicitDistLayer:
    def test_sharded_target_gate_permutes(self, env8):
        n = 14
        amps = sharded_state(env8, n, 8)
        h = (1 / np.sqrt(2)) * np.array([[1, 1], [1, -1]])
        m = jnp.asarray(np.stack([h, np.zeros((2, 2))]))

        def f(a):
            return PAR.apply_matrix_1q_sharded(
                a, m, mesh=env8.mesh, num_qubits=n, target=n - 1)

        hist = collectives(f, amps)
        assert hist.get("collective-permute", 0) >= 1, hist

    def test_swap_sharded_permutes(self, env8):
        n = 14
        amps = sharded_state(env8, n, 9)

        def f(a):
            return PAR.swap_sharded(a, mesh=env8.mesh, num_qubits=n,
                                    qb_low=0, qb_high=n - 1)

        hist = collectives(f, amps)
        assert hist.get("collective-permute", 0) >= 1, hist


class TestGspmdAB:
    """SURVEY.md §7 layer 5's explicit-vs-GSPMD benchmark, pinned
    structurally: for the representative 1q sharded-target gate the
    explicit layer exchanges 1 hypercube ppermute (one state pass of
    bytes) while GSPMD propagation of the SAME local kernel emits
    4 permutes + 2 all-gathers (~10.5x the exchanged bytes, measured
    7x wall on the virtual mesh) — the quantitative reason the explicit
    layer is the default."""

    def test_gspmd_1q_gate_collectives_exceed_explicit(self, env8):
        n = 14
        amps = sharded_state(env8, n, 50)
        h = (1 / np.sqrt(2)) * np.array([[1, 1], [1, -1]])
        m = jnp.asarray(np.stack([h, np.zeros((2, 2))]))

        def explicit(a):
            return PAR.apply_matrix_1q_sharded(
                a, m, mesh=env8.mesh, num_qubits=n, target=n - 1)

        def gspmd(a):
            out = K.apply_matrix(a, m, num_qubits=n, targets=(n - 1,))
            return jax.lax.with_sharding_constraint(
                out, env8.amp_sharding())

        hist_a = collective_ops(explicit, amps)
        hist_b = collective_ops(gspmd, amps)
        assert hist_a == {"collective-permute": 1}, hist_a
        # GSPMD must communicate MORE than the explicit path (today:
        # 4 permutes + 2 all-gathers); equal-or-fewer would mean XLA
        # caught up and the default deserves re-measurement
        assert sum(hist_b.values()) > 1, hist_b
        # and both compute the same state (fresh arrays: the explicit
        # kernel donates its input)
        out_a = np.asarray(explicit(sharded_state(env8, n, 50)))
        out_b = np.asarray(jax.jit(gspmd)(sharded_state(env8, n, 50)))
        np.testing.assert_allclose(out_a, out_b, atol=1e-12)


class TestPairFamiliesCommunicate:
    def test_explicit_depolarising_one_permute(self, env8):
        """The explicit pair-exchange channel is EXACTLY one
        collective-permute — the redesign of the reference's
        pack-and-exchange distributed decoherence
        (QuEST_cpu_distributed.c:553-852)."""
        nq = 7
        amps = sharded_state(env8, 2 * nq, 10)

        def f(a):
            return PAR.mix_pair_channel_sharded(
                a, 0.3, mesh=env8.mesh, num_qubits=nq, target=nq - 1,
                kind="depol")

        # the ambient budget checks every audit inside the block — the
        # same pin as asserting the histogram, through the public API
        with CollectiveBudget(exact={"collective-permute": 1}):
            introspect.audit(f, amps, donate=True)

    def test_explicit_damping_one_permute(self, env8):
        nq = 7
        amps = sharded_state(env8, 2 * nq, 12)

        def f(a):
            return PAR.mix_pair_channel_sharded(
                a, 0.3, mesh=env8.mesh, num_qubits=nq, target=nq - 1,
                kind="damping")

        with CollectiveBudget(exact={"collective-permute": 1}):
            introspect.audit(f, amps, donate=True)

    def test_gspmd_elementwise_depol_fallback_bounded(self, env8):
        """The GSPMD fallback (elementwise kernel under sharding
        propagation) is measurably WORSE than the explicit path — its
        flipped-copy gather costs all-gathers (measured: 6 all-gathers +
        1 permute here, vs the explicit kernel's single permute pinned
        above) — which is exactly why mixDepolarising/mixDamping route
        the explicit path on sharded registers.  This audit bounds the
        fallback so a regression to something pathological still fails."""
        nq = 7
        amps = sharded_state(env8, 2 * nq, 13)

        def f(a):
            return D.mix_depolarising(a, 0.3, num_qubits=nq, target=nq - 1)

        hist = collective_ops(f, amps, donate=True)
        assert set(hist) <= {"collective-permute", "all-gather"}, hist
        assert sum(hist.values()) <= 8, hist

    def test_diagonal_op_on_rho_gathers_only_the_op(self, env8):
        """applyDiagonalOp on a sharded rho replicates the (small) OP
        vector to every shard — the reference's copyDiagOpIntoMatrixPair-
        State (QuEST_cpu_distributed.c:1548-1587) — and must NOT gather
        the state.  Pinned by opcode (all-gathers only, bounded count)
        AND by gathered size (every all-gather in the HLO is op-sized,
        2^nq elements, never state-sized 2^2nq)."""
        nq = 7
        amps = sharded_state(env8, 2 * nq, 14)
        op = jax.device_put(jnp.ones((1 << nq,), amps.dtype),
                            env8.vec_sharding())

        def f(a, re, im):
            return D.apply_diagonal_op_density(a, re, im, num_qubits=nq)

        report = introspect.audit(f, amps, op, op * 0.5)
        hist = report.collectives
        assert set(hist) == {"all-gather"} and hist["all-gather"] <= 4, hist
        for line in report.text.splitlines():
            if " all-gather(" in line:
                assert f"[{1 << nq}]{{" in line, line  # op-sized, ever

    def test_api_routes_explicit_channel_on_sharded_rho(self, env8):
        """The API-level routing predicate sends sharded-bra channels to
        the explicit kernel (the audit above pins it at 1 permute)."""
        import quest_tpu as qt
        from quest_tpu import api_ops

        rho = qt.createDensityQureg(7, env8)
        assert api_ops._pair_channel_sharded(rho, 0.3, 6, "depol")
        assert abs(qt.calcTotalProb(rho) - 1.0) < 1e-5

    def test_fused_qft_sharded_exact_collectives(self, env8):
        """The explicit shard_map QFT emits EXACTLY r hypercube permutes
        (one per mesh-bit H exchange) + 1 all-to-all (the bit-reversal
        lanes<->mesh block swap)."""
        n = 14
        amps = sharded_state(env8, n, 11)
        r = PAR.num_shard_bits(env8.mesh)

        def f(a):
            return PAR.fused_qft_sharded(a, mesh=env8.mesh, num_qubits=n)

        with CollectiveBudget(exact={"collective-permute": r,
                                     "all-to-all": 1}):
            introspect.audit(f, amps, donate=True)


class TestScanCompositesExactCollectives:
    """The shard_map scan composites (VERDICT r3 item 1) compile to the
    pinned collective pattern: ppermute exchanges for sharded qubits in
    the rotation layers, one psum for the expectation reduce — nothing
    else (no state-sized gathers, no all-to-alls)."""

    def test_trotter_scan_sharded_direct_switch_permutes(self, env8):
        """The direct term body's mesh-flip lax.switch carries one static
        XOR ppermute per nonzero mesh mask: exactly 2^r - 1 collective-
        permutes in the scan body (all inside the switch — at most ONE
        executes per term), and no other collective.  This replaces the
        2*r rotate/unrotate-layer exchanges of the conjugation body
        (VERDICT round-5 item (a)): per-term exchange volume drops from
        2*r full shards to at most one."""
        n = 10
        amps = sharded_state(env8, n, 20)
        ndev = PAR.amp_axis_size(env8.mesh)
        codes = jnp.asarray(np.random.default_rng(0).integers(
            0, 4, size=(5, n)), jnp.int32)
        angles = jnp.asarray(np.linspace(0.1, 0.5, 5))

        def f(a):
            return PAR.trotter_scan_sharded(
                a, codes, angles, mesh=env8.mesh, num_qubits=n,
                rep_qubits=n)

        with CollectiveBudget(exact={"collective-permute": ndev - 1}):
            introspect.audit(f, amps, donate=True)

    def test_trotter_scan_sharded_density_two_switches(self, env8):
        """A density-matrix term rotates ket and bra separately: two
        mesh-flip switches per term, but the branch computations are
        identical (same static XOR permutes) so XLA shares them — the
        module still holds exactly 2^r - 1 collective-permutes."""
        nq = 5
        amps = sharded_state(env8, 2 * nq, 24)
        ndev = PAR.amp_axis_size(env8.mesh)
        codes = jnp.asarray(np.random.default_rng(4).integers(
            0, 4, size=(3, nq)), jnp.int32)
        angles = jnp.asarray(np.linspace(0.1, 0.3, 3))

        def f(a):
            return PAR.trotter_scan_sharded(
                a, codes, angles, mesh=env8.mesh, num_qubits=2 * nq,
                rep_qubits=nq)

        assert collective_ops(f, amps, donate=True) == {
            "collective-permute": ndev - 1}

    def test_expec_scan_sharded_permutes_plus_one_allreduce(self, env8):
        """One mesh-flip switch per term (2^r - 1 branch permutes, at
        most one executed) + ONE final psum (the reference's
        local-reduce + MPI_Allreduce, QuEST_cpu_distributed.c:35-51)."""
        n = 10
        amps = sharded_state(env8, n, 21)
        ndev = PAR.amp_axis_size(env8.mesh)
        codes = jnp.asarray(np.random.default_rng(1).integers(
            0, 4, size=(4, n)), jnp.int32)
        coeffs = jnp.asarray(np.linspace(1.0, 2.0, 4))

        def f(a):
            return PAR.expec_pauli_sum_scan_sharded(
                a, codes, coeffs, mesh=env8.mesh, num_qubits=n)

        report = introspect.audit(f, amps)
        hist = report.collectives
        assert report.count("collective-permute") == ndev - 1, hist
        assert report.count("all-reduce") == 1, hist
        assert set(hist) <= {"collective-permute", "all-reduce",
                             "all-reduce-start"}, hist


class TestQftRunsExactCollectives:
    """dist.fused_qft_runs_sharded compiles to the pinned pattern: one
    ppermute per mesh-bit layer, one ppermute per local<->mesh reversal
    swap, one composed ppermute for all mesh<->mesh reversal pairs —
    never a state gather."""

    def test_top_run_statevec(self, env8):
        """Run [7, 16) on n=16 over 8 devices (nloc=13): 3 mesh layers +
        3 mixed reversal swaps = 6 permutes, nothing else."""
        n = 16
        amps = sharded_state(env8, n, 22)
        r = PAR.num_shard_bits(env8.mesh)
        assert r == 3

        def f(a):
            return PAR.fused_qft_runs_sharded(
                a, mesh=env8.mesh, num_qubits=n, runs=((7, 9, False),))

        assert collective_ops(f, amps, donate=True) == {
            "collective-permute": 6}

    def test_density_full_qft(self, env8):
        """9q density (18 state bits, nloc=15): ket run is fully local
        (zero collectives), bra run costs 3 mesh layers + 3 mixed
        reversal swaps."""
        n = 18
        amps = sharded_state(env8, n, 23)

        def f(a):
            return PAR.fused_qft_runs_sharded(
                a, mesh=env8.mesh, num_qubits=n,
                runs=((0, 9, False), (9, 9, True)))

        assert collective_ops(f, amps, donate=True) == {
            "collective-permute": 6}

    def test_mesh_mesh_reversal_composes_to_one_permute(self, env8):
        """A run living entirely in the top bits ([nloc+? ..]): the
        mesh<->mesh reversal pairs fold into ONE composed shard
        permutation."""
        n = 16  # nloc = 13; run [13, 16) is all mesh bits
        amps = sharded_state(env8, n, 24)

        def f(a):
            return PAR.fused_qft_runs_sharded(
                a, mesh=env8.mesh, num_qubits=n, runs=((13, 3, False),))

        # 3 mesh layers + 1 composed reversal permute (pair 13<->15)
        assert collective_ops(f, amps, donate=True) == {
            "collective-permute": 4}


class TestTwoQubitChannelsExactCollectives:
    """The explicit 2q decoherence + DiagonalOp-on-rho replication
    kernels (VERDICT r3 item 4) compile to the pinned collective
    pattern."""

    def test_two_qubit_depol_both_bra_sharded_two_permutes(self, env8):
        """Both bra bits on mesh coordinates: the orbit sum's recursive
        doubling = exactly 2 collective-permutes (the reference's 3-part
        pack-and-exchange does more, QuEST_cpu_distributed.c:553-852)."""
        nq = 7
        amps = sharded_state(env8, 2 * nq, 30)

        def f(a):
            return PAR.mix_two_qubit_depol_sharded(
                a, 0.3, mesh=env8.mesh, num_qubits=nq, qubit1=nq - 1,
                qubit2=nq - 2)

        assert collective_ops(f, amps, donate=True) == {
            "collective-permute": 2}

    def test_two_qubit_depol_one_bra_sharded(self, env8):
        """One bra bit sharded, one local: 1 permute + 1 local flip."""
        nq = 7
        amps = sharded_state(env8, 2 * nq, 31)

        def f(a):
            return PAR.mix_two_qubit_depol_sharded(
                a, 0.3, mesh=env8.mesh, num_qubits=nq, qubit1=0,
                qubit2=nq - 1)

        assert collective_ops(f, amps, donate=True) == {
            "collective-permute": 1}

    def test_diag_op_on_rho_two_op_sized_gathers(self, env8):
        """Explicit replication: exactly 2 all-gathers (re, im), each
        op-sized (2^nq), never state-sized — the reference's
        copyDiagOpIntoMatrixPairState (QuEST_cpu_distributed.c:1548-1587)."""
        nq = 7
        amps = sharded_state(env8, 2 * nq, 32)
        op = jax.device_put(jnp.ones((1 << nq,), amps.dtype),
                            env8.vec_sharding())

        def f(a, re, im):
            return PAR.apply_diag_op_density_sharded(
                a, re, im, mesh=env8.mesh, num_qubits=nq)

        report = introspect.audit(f, amps, op, op * 0.5, donate=True)
        hist = report.collectives
        assert report.count("all-gather") == 2, hist
        assert "collective-permute" not in hist, hist
        for line in report.text.splitlines():
            if " all-gather(" in line or " all-gather-start(" in line:
                assert f"[{1 << nq}]{{" in line, line

    def test_kraus_relocalization_route(self, env8):
        """A generic 2q Kraus map whose bra bits are sharded routes
        through SWAP-relocalization (2 ppermutes per sharded bit) and
        matches the dense Kraus oracle."""
        import oracle
        import quest_tpu as qt

        nq = 4
        rng = np.random.default_rng(33)
        mat = oracle.random_density(nq, rng)
        r = qt.createDensityQureg(nq, env8)
        oracle.set_qureg_from_array(qt, r, mat)
        ks = oracle.random_kraus_map(2, 3, rng)
        qt.mixTwoQubitKrausMap(r, nq - 1, nq - 2, ks)
        expect = np.zeros_like(mat)
        for k in ks:
            K2 = oracle.full_operator(nq, [nq - 1, nq - 2], k)
            expect = expect + K2 @ mat @ K2.conj().T
        np.testing.assert_allclose(oracle.state_from_qureg(r), expect,
                                   atol=1e-10)


class TestPipelinedExchange:
    """ISSUE 3 pins: the chunked double-buffered exchange
    (dist.exchange_pipelined) lowers to exactly C collective-permutes,
    every one of them CHUNK-sized (shard/C) — the transient exchange
    buffer is at most one chunk in flight plus one being consumed,
    <= shard/C + one chunk, where the monolithic path's recv buffer is a
    full shard — and the pipelined output is numerically identical to
    the monolithic one (bit-identical for pure relabelings and the
    elementwise gate combine; channels may differ by an XLA
    fusion/FMA-contraction ulp)."""

    N = 14

    def _state(self, env, seed):
        return sharded_state(env, self.N, seed)

    def _gate(self, env, chunks):
        h = (1 / np.sqrt(2)) * np.array([[1, 1], [1, -1]])
        m = jnp.asarray(np.stack([h, np.zeros((2, 2))]))

        def f(a):
            return PAR.apply_matrix_1q_sharded(
                a, m, mesh=env.mesh, num_qubits=self.N, target=self.N - 1,
                chunks=chunks)

        return f

    def test_exactly_c_chunk_sized_permutes(self, env8):
        n = self.N
        r = PAR.num_shard_bits(env8.mesh)
        shard_amps = 1 << (n - r)
        for C in (2, 4, 8):
            jfn = jax.jit(self._gate(env8, C), donate_argnums=0)
            txt = jfn.lower(self._state(env8, 60)).compile().as_text()
            cps = [ln for ln in txt.splitlines()
                   if " collective-permute(" in ln
                   or " collective-permute-start(" in ln]
            assert len(cps) == C, (C, txt.count("collective-permute"))
            # every exchange buffer is exactly chunk-sized: (2, shard/C)
            for ln in cps:
                assert f"[2,{shard_amps // C}]" in ln, (C, ln)

    def test_transient_memory_below_monolithic(self, env8):
        """The chunked program is C chunk exchanges against the
        monolithic one exchange (the CPU half of the pin).  Its transient
        memory is compared on the four-chip v5e mesh, where the property
        holds (tests/test_chip_compile.py); XLA's CPU buffer assignment
        keeps a whole staging shard either way."""
        def permutes(C):
            jfn = jax.jit(self._gate(env8, C), donate_argnums=0)
            txt = jfn.lower(self._state(env8, 61)).compile().as_text()
            return sum(1 for ln in txt.splitlines()
                       if " collective-permute(" in ln
                       or " collective-permute-start(" in ln)

        assert permutes(1) == 1
        for C in (4, 8):
            assert permutes(C) == C

    def test_pipelined_bit_identical_gate_swap_remap(self, env8):
        n = self.N
        h = (1 / np.sqrt(2)) * np.array([[1, 1], [1, -1]])
        m = jnp.asarray(np.stack([h, np.zeros((2, 2))]))
        for C in (2, 4):
            a1 = np.asarray(PAR.apply_matrix_1q_sharded(
                self._state(env8, 62), m, mesh=env8.mesh, num_qubits=n,
                target=n - 1, controls=(0, 9, 12), control_states=(1, 0, 1),
                chunks=1))
            a2 = np.asarray(PAR.apply_matrix_1q_sharded(
                self._state(env8, 62), m, mesh=env8.mesh, num_qubits=n,
                target=n - 1, controls=(0, 9, 12), control_states=(1, 0, 1),
                chunks=C))
            np.testing.assert_array_equal(a1, a2)
            s1 = np.asarray(PAR.swap_sharded(
                self._state(env8, 63), mesh=env8.mesh, num_qubits=n,
                qb_low=2, qb_high=n - 1, chunks=1))
            s2 = np.asarray(PAR.swap_sharded(
                self._state(env8, 63), mesh=env8.mesh, num_qubits=n,
                qb_low=2, qb_high=n - 1, chunks=C))
            np.testing.assert_array_equal(s1, s2)
        sigma = PAR.canonical_sigma(
            (3, 1, 2, 0) + tuple(range(4, n - 3)) + (n - 1, n - 2, n - 3))
        r1 = np.asarray(PAR.remap_sharded(
            self._state(env8, 64), mesh=env8.mesh, num_qubits=n,
            sigma=sigma, chunks=(1, 1)))
        r4 = np.asarray(PAR.remap_sharded(
            self._state(env8, 64), mesh=env8.mesh, num_qubits=n,
            sigma=sigma, chunks=(4, 4)))
        np.testing.assert_array_equal(r1, r4)

    def test_pipelined_channels_and_trotter_match(self, env8):
        nq = 7
        rho = sharded_state(env8, 2 * nq, 65)
        for kind in ("depol", "damping"):
            c1 = np.asarray(PAR.mix_pair_channel_sharded(
                sharded_state(env8, 2 * nq, 65), 0.3, mesh=env8.mesh,
                num_qubits=nq, target=nq - 1, kind=kind, chunks=1))
            c4 = np.asarray(PAR.mix_pair_channel_sharded(
                sharded_state(env8, 2 * nq, 65), 0.3, mesh=env8.mesh,
                num_qubits=nq, target=nq - 1, kind=kind, chunks=4))
            np.testing.assert_allclose(c1, c4, atol=1e-14)
        n = 10
        codes = jnp.asarray(np.random.default_rng(2).integers(
            0, 4, size=(5, n)), jnp.int32)
        angles = jnp.asarray(np.linspace(0.1, 0.5, 5))
        t1 = np.asarray(PAR.trotter_scan_sharded(
            sharded_state(env8, n, 66), codes, angles, mesh=env8.mesh,
            num_qubits=n, rep_qubits=n, chunks=1))
        t2 = np.asarray(PAR.trotter_scan_sharded(
            sharded_state(env8, n, 66), codes, angles, mesh=env8.mesh,
            num_qubits=n, rep_qubits=n, chunks=2))
        np.testing.assert_array_equal(t1, t2)

    def test_trotter_chunk_override_is_monolithic_on_direct_body(self, env8):
        """The direct term body's switch exchange is monolithic by
        construction (the local gather mixes rows across any chunk
        boundary): a chunk override neither changes the collective count
        nor the result."""
        n = 10
        ndev = PAR.amp_axis_size(env8.mesh)
        amps = sharded_state(env8, n, 67)
        codes = jnp.asarray(np.random.default_rng(3).integers(
            0, 4, size=(5, n)), jnp.int32)
        angles = jnp.asarray(np.linspace(0.1, 0.5, 5))

        def f(a):
            return PAR.trotter_scan_sharded(
                a, codes, angles, mesh=env8.mesh, num_qubits=n,
                rep_qubits=n, chunks=2)

        assert collective_ops(f, amps, donate=True) == {
            "collective-permute": ndev - 1}

    def test_env_override_routes_wrappers(self, env8, monkeypatch):
        """QT_EXCHANGE_CHUNKS acts at DISPATCH time: the public wrappers
        re-resolve the chunk count per call, so flipping the env var
        mid-process retraces instead of reusing a stale schedule."""
        monkeypatch.setenv("QT_EXCHANGE_CHUNKS", "4")
        jfn = jax.jit(self._gate(env8, None), donate_argnums=0)
        txt = jfn.lower(self._state(env8, 68)).compile().as_text()
        assert txt.count(" collective-permute(") == 4
        monkeypatch.setenv("QT_EXCHANGE_CHUNKS", "1")
        jfn = jax.jit(self._gate(env8, None), donate_argnums=0)
        txt = jfn.lower(self._state(env8, 68)).compile().as_text()
        assert txt.count(" collective-permute(") == 1

    def test_auto_heuristic_small_shard_monolithic(self, env8):
        """The measured fallback rules: monolithic on the CPU backend
        (chunking is a flat 21-41% loss with no asynchrony to recoup —
        config 7), monolithic below PIPELINE_MIN_BYTES on accelerators,
        target-sized chunks above, structural limit always respected,
        non-power-of-two overrides rounded down."""
        assert PAR.exchange_chunks(1 << 40, backend="cpu") == 1
        assert PAR.exchange_chunks(PAR.PIPELINE_MIN_BYTES - 1,
                                   backend="tpu") == 1
        assert PAR.exchange_chunks(PAR.PIPELINE_MIN_BYTES * 64,
                                   backend="tpu") > 1
        assert PAR.exchange_chunks(1 << 40,
                                   backend="tpu") == PAR.MAX_EXCHANGE_CHUNKS
        assert PAR.exchange_chunks(1 << 40, limit=2, backend="tpu") == 2
        # the 14q/8-dev test states sit far below the threshold anyway:
        # the default path everywhere else in this suite is monolithic,
        # keeping every exact-collective pin above valid
        r = PAR.num_shard_bits(env8.mesh)
        assert 2 * (1 << (self.N - r)) * 8 < PAR.PIPELINE_MIN_BYTES


class TestMeasurementCollectives:
    def test_measure_fused_one_allreduce_no_gather(self, env8):
        """The fused measure program on a sharded register: the prob
        reduce lowers to all-reduce(s), the threshold draw is replicated
        (key broadcast = the reference's seed broadcast,
        QuEST_cpu_distributed.c:1384-1395), the conditional collapse is
        elementwise — and the STATE is never gathered."""
        import jax.random as jr

        from quest_tpu.ops import measurement as M

        n = 10
        amps = sharded_state(env8, n, 40)
        key = jr.PRNGKey(0)

        def f(a):
            out, o, p = M.measure_fused(
                a, key, 3, num_qubits=n, target=n - 1, is_density=False)
            return out, o, p

        hist = collective_ops(f, amps, donate=True)
        assert set(hist) <= {"all-reduce", "all-reduce-start"}, hist
        assert 1 <= sum(hist.values()) <= 3, hist
