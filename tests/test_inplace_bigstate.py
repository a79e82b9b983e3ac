"""In-place big-state kernels (ops/bigstate.py) and the 30q bit-reversal
path (circuit._bit_reversal_big), plus the planner's k in {8,9} pruning.

The sigma kernel runs in interpret mode at small n; the 30q reversal is
validated at the INDEX level (composing each op's permutation semantics
over random sample indices) since a 2^30 state cannot be materialized in
CI.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from quest_tpu import circuit as C
from quest_tpu.ops import bigstate, kernels


@pytest.mark.parametrize("n,g", [(9, 2), (12, 2), (13, 3), (16, 4)])
def test_sigma_swap_matches_permute(n, g):
    rng = np.random.default_rng(7)
    a = rng.normal(size=(2, 1 << n)).astype(np.float32)
    out = bigstate.apply_sigma_swap(
        jnp.asarray(a), num_qubits=n, group_bits=g, interpret=True)
    perm = bigstate.sigma_perm(n, g)
    ref = kernels.permute_qubits(jnp.asarray(a), num_qubits=n, perm=perm)
    np.testing.assert_array_equal(
        np.asarray(out).reshape(2, -1), np.asarray(ref).reshape(2, -1))


def test_sigma_perm_is_involution():
    for n, g in ((9, 2), (28, 7), (30, 7), (34, 7)):
        p = bigstate.sigma_perm(n, g)
        assert [p[p[q]] for q in range(n)] == list(range(n))


def _winfused_index_map(op, n):
    """Index map f with out[i] = in[f(i)] for a winfused op whose A/B are
    PERMUTATION matrices (the only kind _bit_reversal_big emits)."""
    _, k, a, b, a_used, b_used = op[:6]
    a = np.asarray(a)[0, 0]
    b = np.asarray(b)[0, 0]
    # out[l'] takes in[j] where A[l', j] == 1
    pl_ = np.argmax(a, axis=1)
    pw_ = np.argmax(b, axis=1)
    assert (a[np.arange(128), pl_] == 1).all()
    assert (b[np.arange(128), pw_] == 1).all()

    def f(i):
        l = i & 127
        w = (i >> k) & 127
        rest = i & ~(127 | (127 << k))
        return rest | int(pl_[l]) | (int(pw_[w]) << k)

    return f


def _sigma_index_map(n, g):
    perm = bigstate.sigma_perm(n, g)

    def f(i):
        j = 0
        for q in range(n):
            j |= ((i >> q) & 1) << perm[q]
        return j

    return f


def test_bit_reversal_big_composes_to_full_reversal():
    """_bit_reversal_big's op list, composed at the index level, is the
    full bit reversal — checked on random sample indices at n = 28..31."""
    rng = np.random.default_rng(3)
    for n in (28, 29, 30, 31):
        ops = C._bit_reversal_big(n, np.float32)
        assert ops[-1][0] == "sigma_swap"
        maps = []
        for op in ops:
            if op[0] == "winfused":
                maps.append(_winfused_index_map(op, n))
            elif op[0] == "sigma_swap":
                maps.append(_sigma_index_map(n, op[1]))
            else:  # pragma: no cover
                raise AssertionError(op[0])
        samples = rng.integers(0, 1 << n, size=2000)
        for i in samples:
            j = int(i)
            # ops applied in order op1..opm: total map = f1(f2(...fm(i)))
            for f in reversed(maps):
                j = f(j)
            expect = int(format(int(i), f"0{n}b")[::-1], 2)
            assert j == expect, (n, i, j, expect)


def test_planner_prunes_k8_but_keeps_last_resort():
    """k in {8,9} is pruned from window candidates (layout-hostile view),
    but a gate coverable ONLY by k=8 still folds there instead of falling
    back to a per-gate apply pass."""
    u = np.zeros((2, 4, 4), np.float32)
    u[0] = np.eye(4)[[0, 3, 2, 1]]  # CNOT-like, concrete
    n = 22
    # (8, 14) spans exactly bits 8..14: k=8 is the unique covering window
    gates = [C.Gate((8, 14), u)]
    for use_native in (False, True):
        ops = C.plan_circuit(gates, n, use_native=use_native)
        kinds = [op[0] for op in ops]
        # never a per-gate apply pass, and the unavoidable k=8 window is
        # used as the last resort (the controlled-form rewrite may split
        # the gate across an extra k=7 pass first)
        assert set(kinds) == {"winfused"}, (use_native, kinds)
        assert 8 in {op[1] for op in ops}, (use_native, ops)
    # an ordinary layered circuit avoids k in {8, 9}
    rng = np.random.default_rng(1)
    gates2 = []
    for q in range(n):
        z = rng.normal(size=(2, 2)) + 1j * rng.normal(size=(2, 2))
        qm, r = np.linalg.qr(z)
        uu = qm * (np.diag(r) / np.abs(np.diag(r)))
        gates2.append(C.Gate(
            (q,), np.stack([uu.real, uu.imag]).astype(np.float32)))
    for q in range(0, n - 1, 2):
        gates2.append(C.Gate((q, q + 1), u))
    for use_native in (False, True):
        ops = C.plan_circuit(gates2, n, use_native=use_native)
        ks = {op[1] for op in ops if op[0] == "winfused"}
        assert not (ks & {8, 9}), (use_native, ks)


def test_chained_executor_matches_monolithic():
    """execute_plan_chained (canonical view) == execute_plan (flat)."""
    rng = np.random.default_rng(5)
    n = 15
    gates = []
    for d in range(3):
        for q in range(n):
            z = rng.normal(size=(2, 2)) + 1j * rng.normal(size=(2, 2))
            qm, r = np.linalg.qr(z)
            u = qm * (np.diag(r) / np.abs(np.diag(r)))
            gates.append(C.Gate(
                (q,), np.stack([u.real, u.imag]).astype(np.float32)))
        cx = np.zeros((2, 4, 4), np.float32)
        cx[0] = np.eye(4)[[0, 3, 2, 1]]
        for q in range(d % 2, n - 1, 2):
            gates.append(C.Gate((q, q + 1), cx))
    fresh = lambda: kernels.init_zero_state(1 << n, np.float32)
    ref = np.asarray(C.execute_plan(fresh(), C.plan_circuit(gates, n), n))
    ops = C.plan_to_device(C.plan_circuit(gates, n), jnp.float32)
    out = np.asarray(C.execute_plan_chained(fresh(), ops, n)).reshape(2, -1)
    np.testing.assert_array_equal(out, ref)


# lane, sublane and block targets; the lowest block target sets how many
# canonical rows one grid step covers (r = 1, 2, 8 and the whole state)
@pytest.mark.parametrize("targets", [(3, 9, 15, 17), (0, 14), (16, 2, 8),
                                     (17, 15, 16), (5, 11)])
def test_diagonal_canonical_matches_dense(targets):
    """fused.apply_diagonal_canonical (the in-place pass for a diagonal
    gate no window covers) against a dense diagonal multiply, amplitude
    by amplitude: the factor of index i is diag[bits of i at targets]."""
    from quest_tpu.ops import fused

    n, k = 18, len(targets)
    rng = np.random.default_rng(sum(targets))
    psi = rng.normal(size=1 << n) + 1j * rng.normal(size=1 << n)
    d = np.exp(1j * rng.uniform(0, 2 * np.pi, size=1 << k))
    idx = np.arange(1 << n)
    code = sum(((idx >> t) & 1) << j for j, t in enumerate(targets))
    want = psi * d[code]
    state = jnp.asarray(np.stack([psi.real, psi.imag]).reshape(
        2, 1 << (n - 14), 128, 128))
    out = fused.apply_diagonal_canonical(
        state, jnp.asarray(np.stack([d.real, d.imag])), num_qubits=n,
        targets=targets, interpret=True)
    got = np.asarray(out).reshape(2, -1)
    np.testing.assert_allclose(got[0] + 1j * got[1], want, atol=1e-12)
