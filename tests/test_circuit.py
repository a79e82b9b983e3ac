"""Fused-circuit scheduler tests: Pallas cluster kernel (interpret mode on
CPU), the Python planner, the native C++ planner, and end-to-end circuit
equivalence against the gate-at-a-time kernel path (the reference's
execution model, QuEST/src/QuEST.c dispatch)."""

import numpy as np
import jax.numpy as jnp
import pytest

from quest_tpu import circuit as C
from quest_tpu import native
from quest_tpu.ops import cplx, fused, kernels

from oracle import random_unitary

CNOT = np.array(
    [[1, 0, 0, 0], [0, 1, 0, 0], [0, 0, 0, 1], [0, 0, 1, 0]], dtype=complex
)


def _rand_state(rng, n):
    amps = rng.standard_normal((2, 1 << n)).astype(np.float32)
    return amps / np.sqrt((amps ** 2).sum())


def _apply_gatewise(amps0, gates, n):
    ref = jnp.asarray(amps0)
    for g in gates:
        ref = kernels.apply_matrix(
            ref, jnp.asarray(g.mat), num_qubits=n, targets=g.targets
        )
    return np.asarray(ref)


def _layered_circuit(rng, n, depth):
    gates = []
    for d in range(depth):
        for q in range(n):
            gates.append(C.Gate((q,), cplx.soa(random_unitary(1, rng)).astype(np.float32)))
        for q in range(d % 2, n - 1, 2):
            gates.append(C.Gate((q, q + 1), cplx.soa(CNOT).astype(np.float32)))
    return gates


class TestClusterKernel:
    def test_identity(self):
        rng = np.random.default_rng(0)
        amps = _rand_state(rng, 14)
        eye = np.stack([np.eye(128), np.zeros((128, 128))]).astype(np.float32)
        out = fused.apply_cluster_pair(
            jnp.asarray(amps), eye, eye, num_qubits=14
        )
        np.testing.assert_allclose(np.asarray(out), amps, atol=1e-6)

    @pytest.mark.parametrize("n", [14, 15, 17])
    def test_matches_gatewise(self, n):
        rng = np.random.default_rng(n)
        amps = _rand_state(rng, n)
        us = [random_unitary(1, rng) for _ in range(14)]
        ref = jnp.asarray(amps)
        for q in range(14):
            ref = kernels.apply_matrix(
                ref, jnp.asarray(cplx.soa(us[q]), jnp.float32),
                num_qubits=n, targets=(q,),
            )
        a = us[6]
        for u in us[5::-1]:
            a = np.kron(a, u)
        b = us[13]
        for u in us[12:6:-1]:
            b = np.kron(b, u)
        out = fused.apply_cluster_pair(
            jnp.asarray(amps),
            jnp.asarray(cplx.soa(a), jnp.float32),
            jnp.asarray(cplx.soa(b), jnp.float32),
            num_qubits=n,
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-6)

    def test_too_small_raises(self):
        eye = np.stack([np.eye(128), np.zeros((128, 128))]).astype(np.float32)
        with pytest.raises(ValueError):
            fused.apply_cluster_pair(
                jnp.zeros((2, 1 << 10), jnp.float32), eye, eye, num_qubits=10
            )


class TestPermuteQubits:
    @pytest.mark.parametrize("n", [4, 8])
    def test_against_index_oracle(self, n):
        rng = np.random.default_rng(n)
        amps = _rand_state(rng, n)
        perm = tuple(rng.permutation(n).tolist())
        out = np.asarray(
            kernels.permute_qubits(jnp.asarray(amps), num_qubits=n, perm=perm)
        )
        idx = np.arange(1 << n)
        src = np.zeros_like(idx)
        for q in range(n):
            src |= ((idx >> q) & 1) << perm[q]
        np.testing.assert_allclose(out, amps[:, src], atol=0)

    def test_swap_equivalence(self):
        rng = np.random.default_rng(3)
        n = 6
        amps = _rand_state(rng, n)
        perm = list(range(n))
        perm[1], perm[4] = perm[4], perm[1]
        out = kernels.permute_qubits(
            jnp.asarray(amps), num_qubits=n, perm=tuple(perm)
        )
        ref = kernels.swap_qubit_amps(jnp.asarray(amps), num_qubits=n, qb1=1, qb2=4)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=0)


class TestEmbedding:
    def test_embed_1q(self):
        rng = np.random.default_rng(5)
        u = random_unitary(1, rng)
        for b in range(7):
            e = cplx.unsoa(np.asarray(C.embed_in_cluster(cplx.soa(u), (b,))))
            expect = np.kron(
                np.kron(np.eye(1 << (6 - b)), u), np.eye(1 << b)
            )
            np.testing.assert_allclose(e, expect, atol=1e-12)

    def test_embed_2q_nonadjacent(self):
        rng = np.random.default_rng(6)
        u = random_unitary(2, rng)
        e = cplx.unsoa(np.asarray(C.embed_in_cluster(cplx.soa(u), (1, 4))))
        # oracle: E[i,j] = U[x(i), x(j)] when the other bits agree
        idx = np.arange(128)
        x = ((idx >> 1) & 1) | (((idx >> 4) & 1) << 1)
        rest = idx & ~0b10010
        expect = u[x[:, None], x[None, :]] * (rest[:, None] == rest[None, :])
        np.testing.assert_allclose(e, expect, atol=1e-12)

    def test_controlled_dense(self):
        rng = np.random.default_rng(7)
        u = random_unitary(1, rng)
        cu = cplx.unsoa(C.controlled_dense(cplx.soa(u), 1))
        expect = np.eye(4, dtype=complex)
        expect[2:, 2:] = u
        np.testing.assert_allclose(cu, expect, atol=1e-12)


class TestFoldGate:
    """circuit.fold_gate: the planner's gate-into-term product, contracted
    on the gate's own bits for concrete numpy operands."""

    @pytest.mark.parametrize("dtype,atol", [(np.float32, 1e-5),
                                            (np.float64, 1e-12)],
                             ids=["f32", "f64"])
    @pytest.mark.parametrize("bits", [(0,), (3,), (6,), (5, 2), (0, 6),
                                      (1, 4), (6, 3, 1), (0, 5, 2)],
                             ids=str)
    def test_matches_dense_product(self, bits, dtype, atol):
        rng = np.random.default_rng(sum(b << (3 * i)
                                        for i, b in enumerate(bits)))
        mat = cplx.soa(random_unitary(len(bits), rng)).astype(dtype)
        acc = rng.standard_normal((2, 128, 128)).astype(dtype)
        want = C.soa_matmul(C.embed_in_cluster(mat, bits), acc)
        got = C.fold_gate(mat, bits, acc)
        assert got.dtype == want.dtype == dtype
        np.testing.assert_allclose(got, want, rtol=0, atol=atol)

    def test_identity_term_takes_contiguous_embedding(self):
        mat = cplx.soa(random_unitary(2, np.random.default_rng(3)))
        got = C.fold_gate(mat, (5, 2), None)
        assert got.flags.c_contiguous
        np.testing.assert_array_equal(got, C.embed_in_cluster(mat, (5, 2)))

    def test_concrete_plan_never_takes_the_dense_product(self, monkeypatch):
        """A concrete 20-qubit plan folds every gate on its own bits:
        soa_matmul is never called from the fold path, and each product
        counts once as plan_folds_total{path=structured}."""
        import sys

        from quest_tpu import telemetry as T

        callers = []
        products = []
        soa_matmul, fold_gate = C.soa_matmul, C.fold_gate

        def counting_matmul(a, b):
            callers.append(sys._getframe(1).f_code.co_name)
            return soa_matmul(a, b)

        def spying_fold(mat, bits, acc):
            if acc is not None:
                products.append(bits)
            return fold_gate(mat, bits, acc)

        monkeypatch.setattr(C, "soa_matmul", counting_matmul)
        monkeypatch.setattr(C, "fold_gate", spying_fold)
        prev = T.mode_name()
        T.configure("on")
        T.reset()
        try:
            gates = _layered_circuit(np.random.default_rng(20), 20, 3)
            ops = C.plan_circuit(gates, 20)
            structured = T.counter_value("plan_folds_total",
                                         path="structured")
            dense = T.counter_value("plan_folds_total", path="dense")
        finally:
            T.reset()
            T.configure(prev)
        assert any(op[0] == "winfused" for op in ops)
        assert "fold_gate" not in callers
        assert products and structured == len(products)
        assert dense == 0

    def test_paged_cross_fold_stays_structured(self):
        """_FoldAcc (the paged planner's accumulator) keeps a concrete
        lane-x-sublane gate in numpy: its block products and every later
        fold take the structured path and agree with device operands."""
        import jax.numpy as jnp

        from quest_tpu import telemetry as T

        rng = np.random.default_rng(5)
        u1 = cplx.soa(random_unitary(1, rng))
        u2 = cplx.soa(random_unitary(2, rng))

        def fold_all(cast):
            acc = C._FoldAcc()
            acc.fold("A", (2,), cast(u1))
            acc.fold("B", (4,), cast(u1))
            acc.fold_cross((3, 9), cast(u2))
            acc.fold("A", (6,), cast(u1))
            return acc.stacks()

        prev = T.mode_name()
        T.configure("on")
        T.reset()
        try:
            got = fold_all(lambda m: m)
            structured = T.counter_value("plan_folds_total",
                                         path="structured")
            dense = T.counter_value("plan_folds_total", path="dense")
        finally:
            T.reset()
            T.configure(prev)
        assert all(isinstance(s, np.ndarray) for s in got)
        assert dense == 0 and structured > 0
        want = fold_all(jnp.asarray)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, np.asarray(w), atol=1e-12)

    def test_traced_gate_takes_the_dense_product(self):
        import jax

        from quest_tpu import telemetry as T

        def plan(m):
            ops = C.plan_circuit([C.Gate((q,), m) for q in range(3)], 14)
            return [op[2] for op in ops if op[0] == "winfused"]

        mat = cplx.soa(random_unitary(1, np.random.default_rng(4)))
        prev = T.mode_name()
        T.configure("on")
        T.reset()
        try:
            (a,) = jax.jit(plan)(mat.astype(np.float32))
            structured = T.counter_value("plan_folds_total",
                                         path="structured")
            dense = T.counter_value("plan_folds_total", path="dense")
        finally:
            T.reset()
            T.configure(prev)
        assert dense == 2 and structured == 0
        u = cplx.unsoa(mat)
        want = np.kron(np.kron(np.eye(16), u), np.kron(u, u))
        np.testing.assert_allclose(cplx.unsoa(np.asarray(a[0])), want,
                                   atol=1e-5)


class TestScheduler:
    @pytest.mark.parametrize("n,depth", [(14, 2), (15, 3), (16, 2)])
    def test_e2e_matches_gatewise(self, n, depth):
        rng = np.random.default_rng(100 + n)
        gates = _layered_circuit(rng, n, depth)
        amps0 = _rand_state(rng, n)
        ref = _apply_gatewise(amps0, gates, n)
        out = np.asarray(C.apply_circuit(jnp.asarray(amps0), gates, n))
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_pass_reduction(self):
        rng = np.random.default_rng(9)
        gates = _layered_circuit(rng, 16, 4)
        ops = C.plan_circuit_py(gates, 16)
        st = C.stats(ops)
        assert st["total_passes"] < len(gates) // 2

    def test_small_n_fallback(self):
        rng = np.random.default_rng(11)
        gates = [
            C.Gate((q,), cplx.soa(random_unitary(1, rng)).astype(np.float32))
            for q in range(5)
        ]
        ops = C.plan_circuit(gates, 5)
        assert all(o[0] == "apply" for o in ops)
        amps0 = _rand_state(rng, 5)
        out = np.asarray(C.execute_plan(jnp.asarray(amps0), ops, 5))
        ref = _apply_gatewise(amps0, gates, 5)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_high_qubit_2q_gate(self):
        rng = np.random.default_rng(12)
        n = 16
        gates = [
            C.Gate((14, 15), cplx.soa(random_unitary(2, rng)).astype(np.float32)),
            C.Gate((0, 15), cplx.soa(CNOT).astype(np.float32)),
        ]
        amps0 = _rand_state(rng, n)
        ref = _apply_gatewise(amps0, gates, n)
        out = np.asarray(C.apply_circuit(jnp.asarray(amps0), gates, n))
        np.testing.assert_allclose(out, ref, atol=1e-5)


class TestNativeScheduler:
    def test_available(self):
        assert native.native_available(), "native scheduler failed to build"

    @pytest.mark.parametrize("n,depth", [(14, 2), (16, 3), (20, 2)])
    def test_plans_match_python(self, n, depth):
        rng = np.random.default_rng(200 + n)
        gates = _layered_circuit(rng, n, depth)
        ops_py = C.plan_circuit_py(gates, n)
        ops_nat = C.plan_circuit(gates, n, use_native=True, planner="paged")
        assert [o[0] for o in ops_py] == [o[0] for o in ops_nat]
        for a, b in zip(ops_py, ops_nat):
            if a[0] in ("permute", "segswap"):
                assert tuple(a[1:]) == tuple(b[1:])
            elif a[0] == "apply":
                assert tuple(a[1]) == tuple(b[1])
                np.testing.assert_allclose(np.asarray(a[2]), np.asarray(b[2]))
            else:
                np.testing.assert_allclose(
                    np.asarray(a[1]), np.asarray(b[1]), atol=1e-6
                )
                np.testing.assert_allclose(
                    np.asarray(a[2]), np.asarray(b[2]), atol=1e-6
                )

    def test_native_e2e(self):
        rng = np.random.default_rng(13)
        n = 15
        gates = _layered_circuit(rng, n, 2)
        amps0 = _rand_state(rng, n)
        ops = C.plan_circuit(gates, n, use_native=True, planner="paged")
        out = np.asarray(C.execute_plan(jnp.asarray(amps0), ops, n))
        ref = _apply_gatewise(amps0, gates, n)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_empty_circuit(self):
        assert C.plan_circuit([], 16, use_native=True) == []

    def test_out_of_range_target_rejected(self):
        # native planner must reject bad targets (rc=3), falling back to
        # the Python planner's IndexError — never a silently wrong plan
        rng = np.random.default_rng(14)
        bad = [C.Gate((16,), cplx.soa(random_unitary(1, rng)).astype(np.float32))]
        assert native.plan_native([(16,)], 16) is None
        with pytest.raises(IndexError):
            C.plan_circuit(bad, 16, use_native=True)


class TestWindowedScheduler:
    """Offset-window planner (plan_circuit_windowed + apply_window_stack):
    zero-relocation passes whose sublane cluster sits at an arbitrary
    contiguous bit window [k, k+7)."""

    def test_schmidt_rank(self):
        rng = np.random.default_rng(21)
        cnot = cplx.soa(CNOT).astype(np.float32)
        terms = C.schmidt_terms_2q(cnot)
        assert len(terms) == 2
        cz = np.zeros((2, 4, 4), np.float32)
        cz[0] = np.diag([1, 1, 1, -1])
        assert len(C.schmidt_terms_2q(cz)) == 2
        u1 = random_unitary(1, rng)
        u2 = random_unitary(1, rng)
        prod = cplx.soa(np.kron(u2, u1)).astype(np.float32)
        assert len(C.schmidt_terms_2q(prod)) == 1
        dense = cplx.soa(random_unitary(2, rng)).astype(np.float32)
        assert len(C.schmidt_terms_2q(dense)) == 4

    def test_schmidt_small_angle_f64_keeps_rank2(self):
        # ADVICE r1: a fixed 1e-7 truncation silently flattened f64
        # controlled rotations with angle < ~1e-7 to rank 1
        theta = 1e-9
        cp = np.diag([1, 1, 1, np.exp(1j * theta)])
        terms = C.schmidt_terms_2q(cplx.soa(cp).astype(np.float64))
        assert len(terms) == 2
        acc = np.zeros((4, 4), complex)
        for lo, hi in terms:
            acc += np.kron(hi[0] + 1j * hi[1], lo[0] + 1j * lo[1])
        np.testing.assert_allclose(acc, cp, atol=1e-14)

    def test_schmidt_zero_matrix_rank1(self):
        # ADVICE r1: empty decompositions must not reach fold_cross
        zero = np.zeros((2, 4, 4), np.float64)
        terms = C.schmidt_terms_2q(zero)
        assert len(terms) == 1
        gates = [C.Gate((0, 9), zero)]
        ops = C.plan_circuit(gates, 12)
        amps = np.zeros((2, 1 << 12), np.float64)
        amps[0, 0] = 1.0
        out = np.asarray(C.execute_plan(jnp.asarray(amps), ops, 12))
        np.testing.assert_allclose(out, 0.0, atol=1e-15)

    def test_schmidt_reconstruction(self):
        rng = np.random.default_rng(22)
        for u in [CNOT, random_unitary(2, rng)]:
            soa = cplx.soa(u).astype(np.float64)
            acc = np.zeros((4, 4), complex)
            for lo, hi in C.schmidt_terms_2q(soa):
                acc += np.kron(hi[0] + 1j * hi[1], lo[0] + 1j * lo[1])
            np.testing.assert_allclose(acc, u, atol=1e-12)

    @pytest.mark.parametrize("k", [7, 9, 13])
    def test_window_stack_matches_gatewise(self, k):
        n = 20
        rng = np.random.default_rng(23 + k)
        amps = _rand_state(rng, n)
        ua = random_unitary(1, rng)
        ub = random_unitary(1, rng)
        ref = kernels.apply_matrix(
            jnp.asarray(amps), jnp.asarray(cplx.soa(ua).astype(np.float32)),
            num_qubits=n, targets=(3,))
        ref = kernels.apply_matrix(
            ref, jnp.asarray(cplx.soa(ub).astype(np.float32)),
            num_qubits=n, targets=(k + 2,))
        a = C.embed_in_cluster(cplx.soa(ua).astype(np.float32), (3,))
        b = C.embed_in_cluster(cplx.soa(ub).astype(np.float32), (2,))
        out = fused.apply_window_stack(
            jnp.asarray(amps), a[None], b[None], num_qubits=n, k=k)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    @pytest.mark.parametrize("n,depth", [(14, 3), (16, 2), (20, 2)])
    def test_windowed_e2e(self, n, depth):
        rng = np.random.default_rng(300 + n)
        gates = _layered_circuit(rng, n, depth)
        # sprinkle far cross gates + a window-internal dense 2q gate
        gates.append(C.Gate((2, n - 1), cplx.soa(CNOT).astype(np.float32)))
        if n >= 16:
            gates.append(C.Gate(
                (n - 6, n - 3),
                cplx.soa(random_unitary(2, rng)).astype(np.float32)))
        ops = C.plan_circuit_windowed(gates, n)
        assert any(o[0] == "winfused" for o in ops)
        amps0 = _rand_state(rng, n)
        out = np.asarray(C.execute_plan(jnp.asarray(amps0), ops, n))
        ref = _apply_gatewise(amps0, gates, n)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_windowed_beats_paged_pass_count(self):
        rng = np.random.default_rng(31)
        gates = _layered_circuit(rng, 20, 4)
        win = C.stats(C.plan_circuit_windowed(gates, 20))
        paged = C.stats(C.plan_circuit_py(gates, 20))
        assert win["total_passes"] <= paged["total_passes"]
        assert win["segswap"] == 0  # zero-relocation by construction

    def test_rank_cap_respected(self):
        rng = np.random.default_rng(32)
        n = 15
        # many cross CNOTs straddling lane x window in sequence
        gates = []
        for i in range(6):
            gates.append(C.Gate((i % 7, 7 + (i % 7)),
                                cplx.soa(CNOT).astype(np.float32)))
        ops = C.plan_circuit_windowed(gates, n)
        for op in ops:
            if op[0] == "winfused":
                assert op[2].shape[0] <= C.RANK_CAP
        amps0 = _rand_state(rng, n)
        out = np.asarray(C.execute_plan(jnp.asarray(amps0), ops, n))
        ref = _apply_gatewise(amps0, gates, n)
        np.testing.assert_allclose(out, ref, atol=1e-5)


class TestMaskScheduling:
    """Diagonal-mask folding of crossing controlled gates (round 2):
    controlled-form 2q gates rewrite to W-sandwich + diagonal, and crossing
    diagonals fold into the pass's elementwise mask at zero rank cost."""

    def test_controlled_form_cnot(self):
        cf = C.controlled_form_2q(cplx.soa(CNOT).astype(np.float64))
        assert cf is not None
        pre, d4, post, acted = cf
        # reconstruct: U = (post on acted) . diag(d4) . (pre on acted)
        pre_c = pre[0] + 1j * pre[1]
        post_c = post[0] + 1j * post[1]
        d = d4[0] + 1j * d4[1]
        if acted == 1:
            full_pre = np.kron(pre_c, np.eye(2))
            full_post = np.kron(post_c, np.eye(2))
        else:
            full_pre = np.kron(np.eye(2), pre_c)
            full_post = np.kron(np.eye(2), post_c)
        u = full_post @ np.diag(d) @ full_pre
        np.testing.assert_allclose(u, CNOT, atol=1e-12)

    def test_controlled_form_random_controlled_v(self):
        rng = np.random.default_rng(9)
        for ctrl_bit in (0, 1):
            v = random_unitary(1, rng)
            u = np.eye(4, dtype=complex)
            if ctrl_bit == 0:           # control = matrix bit 0
                u[1::2, 1::2] = v
            else:                       # control = matrix bit 1
                u[2:, 2:] = v
            cf = C.controlled_form_2q(cplx.soa(u).astype(np.float64))
            assert cf is not None and cf[3] == 1 - ctrl_bit
        # generic dense 2q gate is NOT controlled-form
        dense = cplx.soa(random_unitary(2, rng)).astype(np.float64)
        assert C.controlled_form_2q(dense) is None
        # a fully diagonal gate is excluded (handled by diag4_2q directly)
        cz = np.diag([1, 1, 1, -1]).astype(complex)
        assert C.controlled_form_2q(cplx.soa(cz)) is None
        assert C.diag4_2q(cplx.soa(cz)) is not None

    def test_ladder_plan_is_all_rank1(self):
        # the headline circuit shape: every crossing CNOT must fold via the
        # mask, leaving every window pass at rank 1
        rng = np.random.default_rng(11)
        n, depth = 16, 4
        gates = _layered_circuit(rng, n, depth)
        ops = C.plan_circuit_windowed(gates, n)
        for op in ops:
            assert op[0] == "winfused"
            assert np.shape(op[2])[0] == 1      # rank 1
        assert any(len(op) > 6 and op[6] is not None for op in ops)

    def test_masked_plan_matches_gatewise(self):
        rng = np.random.default_rng(12)
        n = 15
        gates = _layered_circuit(rng, n, 3)
        # add crossing CPhase (diagonal, masks directly) and a
        # control-on-low CRz
        cphase = np.diag([1, 1, 1, np.exp(0.7j)]).astype(complex)
        gates.append(C.Gate((3, 9), cplx.soa(cphase).astype(np.float32)))
        crz = np.eye(4, dtype=complex)
        crz[1, 1], crz[3, 3] = np.exp(-0.4j), np.exp(0.4j)
        gates.append(C.Gate((2, 14), cplx.soa(crz).astype(np.float32)))
        amps0 = _rand_state(rng, n)
        ops = C.plan_circuit_windowed(gates, n)
        out = np.asarray(C.execute_plan(jnp.asarray(amps0), ops, n))
        ref = _apply_gatewise(amps0, gates, n)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_mask_only_pass(self):
        # a lone crossing CZ: pass with no matmul on either side, just mask
        n = 14
        cz = np.zeros((2, 4, 4), np.float64)
        cz[0] = np.diag([1, 1, 1, -1])
        gates = [C.Gate((0, 13), cz)]
        ops = C.plan_circuit_windowed(gates, n)
        assert len(ops) == 1 and ops[0][6] is not None
        rng = np.random.default_rng(13)
        amps0 = _rand_state(rng, n)
        out = np.asarray(C.execute_plan(jnp.asarray(amps0), ops, n))
        ref = _apply_gatewise(amps0, gates, n)
        np.testing.assert_allclose(out, ref, atol=1e-6)


class TestNativeWindowedScheduler:
    """Parity of the C++ windowed planner (qts_plan_windowed) with the
    Python reference implementation plan_circuit_windowed."""

    @pytest.mark.parametrize("n,depth", [(14, 2), (16, 3), (20, 2)])
    def test_plans_match_python(self, n, depth):
        # generic dense 2q gates only, so no masks appear in these plans
        # (mask-circuit parity is covered by
        # test_plans_match_python_with_masks)
        rng = np.random.default_rng(400 + n)
        gates = []
        for d in range(depth):
            for q in range(n):
                gates.append(C.Gate(
                    (q,), cplx.soa(random_unitary(1, rng)).astype(np.float32)))
            for q in range(d % 2, n - 1, 2):
                gates.append(C.Gate(
                    (q, q + 1),
                    cplx.soa(random_unitary(2, rng)).astype(np.float32)))
        gates.append(C.Gate(
            (2, n - 1), cplx.soa(random_unitary(2, rng)).astype(np.float32)))
        py = C.plan_circuit_windowed(gates, n)
        structural = native.plan_native_windowed(
            [g.targets for g in gates], n, C._gate_xranks(gates))
        assert structural is not None, "native windowed planner unavailable"
        nat = C.materialize_windowed_plan(structural, gates)
        assert [o[0] for o in py] == [o[0] for o in nat]
        for a, b in zip(py, nat):
            if a[0] == "winfused":
                assert a[1] == b[1]          # same window offset k
                np.testing.assert_allclose(
                    np.asarray(a[2]), np.asarray(b[2]), atol=1e-6)
                np.testing.assert_allclose(
                    np.asarray(a[3]), np.asarray(b[3]), atol=1e-6)
                assert a[4:6] == b[4:6]      # same apply_a/apply_b flags
                assert len(a) < 7 or a[6] is None   # no mask on these plans
            else:
                assert tuple(a[1]) == tuple(b[1])

    @pytest.mark.parametrize("n,depth", [(14, 3), (18, 2)])
    def test_plans_match_python_with_masks(self, n, depth):
        # CNOT ladders: the controlled-form rewrite + mask folds must agree
        # between the C++ planner (flags path) and the Python planner
        rng = np.random.default_rng(500 + n)
        gates = _layered_circuit(rng, n, depth)
        py = C.plan_circuit_windowed(gates, n)
        glist = C.rewrite_controlled_gates(gates)
        structural = native.plan_native_windowed(
            [g.targets for g in glist], n,
            C._gate_xranks(glist), C._gate_flags(glist))
        assert structural is not None, "native windowed planner unavailable"
        nat = C.materialize_windowed_plan(structural, glist)
        assert [o[0] for o in py] == [o[0] for o in nat]
        for a, b in zip(py, nat):
            if a[0] != "winfused":
                continue
            assert a[1] == b[1]
            np.testing.assert_allclose(np.asarray(a[2]), np.asarray(b[2]),
                                       atol=1e-6)
            np.testing.assert_allclose(np.asarray(a[3]), np.asarray(b[3]),
                                       atol=1e-6)
            assert a[4:6] == b[4:6]
            ma, mb = a[6], b[6]
            assert (ma is None) == (mb is None)
            if ma is not None:
                np.testing.assert_allclose(ma, mb, atol=1e-12)

    def test_native_windowed_e2e(self):
        rng = np.random.default_rng(41)
        n = 15
        gates = _layered_circuit(rng, n, 2)
        amps0 = _rand_state(rng, n)
        ops = C.plan_circuit(gates, n, use_native=True, planner="windowed")
        assert any(o[0] == "winfused" for o in ops)
        out = np.asarray(C.execute_plan(jnp.asarray(amps0), ops, n))
        ref = _apply_gatewise(amps0, gates, n)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_unknown_planner_rejected(self):
        with pytest.raises(ValueError, match="unknown planner"):
            C.plan_circuit([], 16, planner="window")


class TestPallasQFTLadder:
    """The Pallas ladder kernels (high: pair bit >= 14 with SMEM-table
    phases; low: pair bit in the sublane axis) vs the XLA elementwise
    formulation — interpret mode, since real-TPU selection is gated by
    qft_ladder_supported."""

    @pytest.mark.parametrize("t", [7, 9, 10, 13, 14, 15, 17])
    @pytest.mark.parametrize("conj", [False, True])
    def test_matches_xla_formulation(self, t, conj, monkeypatch):
        n = 18
        rng = np.random.default_rng(600 + t)
        st = rng.standard_normal((2, 1 << n)).astype(np.float32)
        st /= np.sqrt((st ** 2).sum())
        # force the XLA elementwise formulation for the reference
        monkeypatch.setattr(fused, "qft_ladder_supported",
                            lambda *a, **k: False)
        ref = np.asarray(kernels.apply_qft_ladder(
            jnp.asarray(st), num_qubits=n, target=t, conj=conj))
        monkeypatch.undo()
        # the SHIPPED wrapper (builds the tables), interpret mode on CPU
        out = fused.apply_qft_ladder_pallas(
            jnp.asarray(st), num_qubits=n, target=t, conj=conj,
            interpret=True)
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-6)

    def test_two_level_smem_table_split(self, monkeypatch):
        # shrink the split threshold so the high SMEM factor table is
        # non-trivial (nhi > 1) at a small, fast size — exercises the
        # l % SPLIT / l // SPLIT phase reconstruction used for t > 25
        monkeypatch.setattr(fused, "_TL_SPLIT", 4)
        n, t = 18, 17               # L = 8 > SPLIT -> nhi = 2
        rng = np.random.default_rng(7)
        st = rng.standard_normal((2, 1 << n)).astype(np.float32)
        st /= np.sqrt((st ** 2).sum())
        out = fused.apply_qft_ladder_pallas(
            jnp.asarray(st), num_qubits=n, target=t, interpret=True)
        monkeypatch.setattr(fused, "qft_ladder_supported",
                            lambda *a, **k: False)
        ref = np.asarray(kernels.apply_qft_ladder(
            jnp.asarray(st), num_qubits=n, target=t))
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-6)


def test_split_plan_sides_merges_adjacent_duals():
    """VERDICT r3 item 6: two adjacent rank-1 maskless dual-side passes
    rewrite to two B-only passes + ONE merged A pass (the A sides act on
    lanes [0,7), the B sides on windows >= 7 — disjoint, commuting), and
    the rewritten plan is numerically identical."""
    import jax.numpy as jnp

    from quest_tpu import circuit as C
    from quest_tpu.ops import kernels

    n = 16
    rng = np.random.default_rng(9)

    def ru():
        a = rng.standard_normal((128, 128)) + 1j * rng.standard_normal(
            (128, 128))
        q, r = np.linalg.qr(a)
        u = q * (np.diag(r) / np.abs(np.diag(r)))
        return np.stack([u.real, u.imag])

    ops = [("winfused", 7, ru()[None], ru()[None], True, True, None),
           ("winfused", 9, ru()[None], ru()[None], True, True, None)]
    split = C.split_plan_sides(ops)
    kinds = [(op[4], op[5]) for op in split]
    assert kinds == [(False, True), (False, True), (True, False)], kinds
    a = np.array(kernels.init_debug_state(1 << n, np.float64))
    a /= np.sqrt((a ** 2).sum())
    r1 = np.asarray(C.execute_plan(jnp.asarray(a), ops, n))
    r2 = np.asarray(C.execute_plan(jnp.asarray(a), split, n))
    np.testing.assert_allclose(r1, r2, atol=1e-11)


def test_split_plan_sides_leaves_singletons_and_masked():
    """A lone dual pass must NOT split (2 x 1.25 ms > 2.1 ms), and
    mask/rank-tied passes are barriers — exactly why the rewrite never
    engages on the 26q headline plan."""
    from quest_tpu import circuit as C

    rng = np.random.default_rng(10)
    m = rng.standard_normal((2, 128, 128))
    single = [("winfused", 7, m[None], m[None], True, True, None)]
    assert C.split_plan_sides(single) == single
    masked = [("winfused", 7, m[None], m[None], True, True, m),
              ("winfused", 9, m[None], m[None], True, True, None),
              ("winfused", 10, m[None], m[None], True, True, m)]
    assert C.split_plan_sides(masked) == masked


def test_split_plan_sides_multibit_lane_product_blocks_mask():
    """Review regression: an A-side product of X(l).X(m) touches BOTH
    lane bits (the single-flip-diagonal test missed it); a masked pass
    depending on either bit must stay a barrier, so the rewrite leaves
    the plan alone rather than reordering A past a non-commuting mask."""
    import jax.numpy as jnp

    from quest_tpu import circuit as C
    from quest_tpu.ops import kernels

    n = 16
    x = np.array([[0.0, 1.0], [1.0, 0.0]])
    xx = np.kron(np.eye(1 << 5), np.kron(x, x))  # X on lane bits 0, 1
    a_xx = np.stack([xx, np.zeros_like(xx)])
    rng = np.random.default_rng(12)

    def ru():
        a = rng.standard_normal((128, 128)) + 1j * rng.standard_normal(
            (128, 128))
        q, r = np.linalg.qr(a)
        u = q * (np.diag(r) / np.abs(np.diag(r)))
        return np.stack([u.real, u.imag])

    # CZ-style diagonal mask depending on lane bit 0
    lane_phase = np.where((np.arange(128) & 1) == 1, -1.0, 1.0)
    mask = np.stack([np.broadcast_to(lane_phase, (128, 128)).copy(),
                     np.zeros((128, 128))])
    ops = [("winfused", 7, a_xx[None], ru()[None], True, True, None),
           ("winfused", 9, ru()[None], ru()[None], False, True, mask),
           ("winfused", 9, ru()[None], ru()[None], True, True, None)]
    split = C.split_plan_sides(ops)
    a = np.array(kernels.init_debug_state(1 << n, np.float64))
    a /= np.sqrt((a ** 2).sum())
    r1 = np.asarray(C.execute_plan(jnp.asarray(a), ops, n))
    r2 = np.asarray(C.execute_plan(jnp.asarray(a), split, n))
    np.testing.assert_allclose(r1, r2, atol=1e-11)
    # and the masked pass must have stayed a barrier (no merged A pass
    # crossing it): the first op must still be dual-side
    assert split[0][4] and split[0][5]


def test_native_library_rebuilds_when_the_source_hash_changes(
        tmp_path, monkeypatch):
    """A library built from other source is never loaded: the recorded
    content hash, not file times, decides the rebuild."""
    import shutil

    from quest_tpu import native

    src = tmp_path / "scheduler.cc"
    shutil.copy(native._SRC, src)
    lib = tmp_path / "_qts.so"
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_LIB", str(lib))
    monkeypatch.setattr(native, "_LIB_HASH", str(lib) + ".sha256")
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_failed", False)
    assert native.get_lib() is not None
    first = (tmp_path / "_qts.so.sha256").read_text()
    assert first == native._source_hash()
    with open(src, "a") as f:
        f.write("\n// edited\n")
    monkeypatch.setattr(native, "_lib", None)
    assert native.get_lib() is not None
    assert (tmp_path / "_qts.so.sha256").read_text() == native._source_hash()
    assert native._source_hash() != first
