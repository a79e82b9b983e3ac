"""The window pass's three-product complex form (ops/fused.py
_window_block_body) against a complex128 dense reference.

Every pass signature the planner issues (dual-side, B-only, A-only,
mask-only; with and without a mask; rank 1, 2 and 4; window offsets in
the 4-d k=7 block and the 5-d k>7 one) at 16 qubits, in f32 and f64, and
a chain of 64 Haar-random dual-side passes whose f32 error must stay
within twice that of the four-product form computed in f32 by numpy on
the same inputs.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from oracle import random_unitary

from quest_tpu.ops import fused

N = 16
LANES = 128


@pytest.fixture(autouse=True, scope="module")
def _one_blas_thread():
    """The 128x128 QRs of random_unitary(7) and the reference's products
    run on one BLAS thread: with test workers side by side, threaded
    OpenBLAS spins on the cores the other workers use (seconds a QR)."""
    try:
        from threadpoolctl import threadpool_limits
    except ImportError:
        yield
        return
    with threadpool_limits(1):
        yield


def _soa(mats):
    """Complex (..., 128, 128) -> SoA with the channel axis third from
    the end: (R, 2, 128, 128) matrices, a (2, 128, 128) mask."""
    return np.stack([np.real(mats), np.imag(mats)], axis=-3)


def _left(m, x):
    """m applied to the window axis (1) of a (hi, window, mid, lane) x."""
    return np.moveaxis(np.tensordot(m, x, axes=([1], [1])), 0, 1)


def _right(x, m):
    """m applied to the lane axis (last) of x, as one 2-d product."""
    return (x.reshape(-1, LANES) @ m.T).reshape(x.shape)


def _apply_ref(psi, a, b, mask, k, apply_a, apply_b):
    """sum_r B_r (x) A_r on window bits [k, k+7) and lane bits [0, 7),
    then the elementwise (window, lane) mask, in complex128."""
    hi, mid = 1 << (N - k - 7), 1 << (k - 7)
    x = psi.reshape(hi, LANES, mid, LANES)       # (hi, window, mid, lane)
    eye = np.eye(LANES)
    out = 0
    for r in range(a.shape[0]):
        ar = a[r] if apply_a else eye
        br = b[r] if apply_b else eye
        out = out + _left(br, _right(x, ar))
    if mask is not None:
        out = out * mask[None, :, None, :]
    return out.reshape(-1)


def _run(psi, a, b, mask, k, apply_a, apply_b, dtype):
    amps = jnp.asarray(np.stack([psi.real, psi.imag]).astype(dtype))
    out = fused.apply_window_stack(
        amps, jnp.asarray(_soa(a).astype(dtype)),
        jnp.asarray(_soa(b).astype(dtype)),
        None if mask is None else jnp.asarray(_soa(mask).astype(dtype)),
        num_qubits=N, k=k, apply_a=apply_a, apply_b=apply_b,
        interpret=True, precision="highest")
    out = np.asarray(out, np.float64)
    return out[0] + 1j * out[1]


def _state(rng):
    psi = rng.standard_normal(1 << N) + 1j * rng.standard_normal(1 << N)
    return psi / np.linalg.norm(psi)


def _four_product_f32(psi, a, b, k):
    """One dual-side rank-1 pass in f32 with four real products per
    side: the form the three-product one replaced."""
    f32 = np.float32
    hi, mid = 1 << (N - k - 7), 1 << (k - 7)
    xr = psi.real.astype(f32).reshape(hi, LANES, mid, LANES)
    xi = psi.imag.astype(f32).reshape(hi, LANES, mid, LANES)
    ar, ai = a.real.astype(f32), a.imag.astype(f32)
    br, bi = b.real.astype(f32), b.imag.astype(f32)
    yr = _right(xr, ar) - _right(xi, ai)
    yi = _right(xr, ai) + _right(xi, ar)
    zr = _left(br, yr) - _left(bi, yi)
    zi = _left(br, yi) + _left(bi, yr)
    return (zr.astype(np.float64) + 1j * zi.astype(np.float64)).reshape(-1)


SIDES = {"dual": (True, True), "B": (False, True), "A": (True, False)}
CASES = (
    [(side, masked, rank, k, "f32")
     for side in SIDES for masked in (False, True)
     for rank in (1, 2, 4) for k in (7, 9)]
    + [(side, True, 2, 9, "f64") for side in SIDES]
    + [("mask_only", True, 1, k, "f32") for k in (7, 9)]
    + [("chain64", False, 1, None, "f32")]
)


@pytest.mark.parametrize("side,masked,rank,k,dt", CASES)
def test_window_pass_three_products(side, masked, rank, k, dt):
    rng = np.random.default_rng([rank, 0 if k is None else k, len(side),
                                 masked])
    psi = _state(rng)
    if side == "chain64":
        # 64 Haar-random dual-side passes alternating the two layouts
        ref, got, four = psi, psi.astype(np.complex64), psi
        for step in range(64):
            kk = (7, 9)[step % 2]
            a = random_unitary(7, rng)[None]
            b = random_unitary(7, rng)[None]
            ref = _apply_ref(ref, a, b, None, kk, True, True)
            got = _run(got, a, b, None, kk, True, True, np.float32)
            four = _four_product_f32(four, a[0], b[0], kk)
        err3 = np.linalg.norm(got - ref)
        err4 = np.linalg.norm(four - ref)
        assert 0 < err3 <= 2 * err4, (err3, err4)
        return
    apply_a, apply_b = SIDES.get(side, (False, False))
    a = np.stack([random_unitary(7, rng) for _ in range(rank)])
    b = np.stack([random_unitary(7, rng) for _ in range(rank)])
    mask = (np.exp(1j * rng.uniform(0, 2 * np.pi, (LANES, LANES)))
            if masked else None)
    ref = _apply_ref(psi, a, b, mask, k, apply_a, apply_b)
    got = _run(psi, a, b, mask, k, apply_a, apply_b,
               np.float32 if dt == "f32" else np.float64)
    tol = 1e-5 if dt == "f32" else 1e-12
    assert np.linalg.norm(got - ref) <= tol * np.linalg.norm(ref)
