"""Reduction kernels: probabilities, inner products, purity, fidelity.

TPU-native re-implementation of the reference's ``calc*`` kernels
(QuEST_cpu.c:3363-3645 OpenMP reductions; QuEST_gpu.cu:1930-2146 two-level
shared-memory tree reductions).  Every reduction is a single fused XLA
reduce over the SoA state (see ops/cplx.py); under a sharded mesh the same
code lowers to per-shard partial sums plus one ``psum`` over ICI (the
analogue of the reference's MPI_Allreduce, QuEST_cpu_distributed.c:35-117).

Complex results return as stacked (2,) arrays; the API layer converts.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import cplx


def _axis(n: int, q: int) -> int:
    return 1 + (n - 1 - q)


@jax.jit
def calc_total_prob_statevec(amps):
    """Sum of |amp|^2 (reference uses Kahan summation, QuEST_cpu_local.c:118;
    a single XLA reduce is at least as accurate at f64, and the f32 TPU path
    accumulates in f32 vector lanes like the reference's OpenMP loop)."""
    return jnp.sum(cplx.abs2(amps))


# ---------------------------------------------------------------------------
# Quad-precision (QuEST_PREC=4) reductions: double-double accumulation
# ---------------------------------------------------------------------------

_QUAD_BLOCK = 256


def neumaier_sum(vals):
    """Neumaier error-free-transform scan over a 1-D vector: the serial
    double-double combine used on block partials (quad_sum) and on small
    signed sequences (per-term expectation contributions)."""

    def body(carry, v):
        s, c = carry
        t = s + v
        c = c + jnp.where(jnp.abs(s) >= jnp.abs(v),
                          (s - t) + v, (v - t) + s)
        return (t, c), None

    z = jnp.zeros((), vals.dtype)
    (s, c), _ = jax.lax.scan(body, (z, z), vals)
    return s + c


def quad_sum2(x, y):
    """Channel-split compensated sum: quad_sum(x) + quad_sum(y).

    THE invariant for every two-channel quad reduction (inner products,
    norms, signed expectation summands): the two product grids enter
    SEPARATE compensated sums — a per-element f64 pre-add of x + y
    would round the smaller channel's contribution away before
    compensation ever sees it (the failure class the prec-4 contract
    exists to prevent)."""
    return quad_sum(x) + quad_sum(y)


def quad_sum(x):
    """Double-double-compensated sum of a vector — the quad-precision
    (QuEST_PREC=4, QuEST_precision.h:55-68) accumulation mode for the
    reductions where extended precision is observable.  Pairwise block
    partials (XLA tree reduce, error eps*log B within a block) are
    combined with a Neumaier error-free-transform scan, so cross-block
    cancellation and magnitude disparity accumulate at double-double
    precision instead of f64."""
    flat = x.reshape(-1)
    nb = max(1, flat.size // _QUAD_BLOCK)
    partials = flat.reshape(nb, -1).sum(axis=1)
    # cap the serial compensated scan at _QUAD_BLOCK steps: a second
    # pairwise level costs only eps*log(B) within each super-block while
    # keeping the scan O(256) instead of O(size/256) (a 26q state would
    # otherwise be a 262k-step scalar chain)
    if nb > _QUAD_BLOCK:
        partials = partials.reshape(_QUAD_BLOCK, -1).sum(axis=1)
    return neumaier_sum(partials)


@jax.jit
def calc_total_prob_statevec_quad(amps):
    return quad_sum2(amps[0] * amps[0], amps[1] * amps[1])


@partial(jax.jit, static_argnames=("num_qubits",))
def calc_total_prob_density_quad(amps, *, num_qubits: int):
    return quad_sum(_diag(amps, num_qubits)[0])


@jax.jit
def calc_inner_product_quad(bra_amps, ket_amps):
    """<bra|ket> with double-double accumulation (signed terms — the
    case where cross-block cancellation actually bites)."""
    br, bi = bra_amps[0], bra_amps[1]
    kr, ki = ket_amps[0], ket_amps[1]
    re = quad_sum2(br * kr, bi * ki)
    im = quad_sum2(br * ki, -(bi * kr))
    return jnp.stack([re, im])


# The remaining observable reductions take a static ``quad`` flag
# selecting the double-double reducer — ONE kernel body per family, so
# the prec-4 path cannot diverge from the plain one.  The reference's
# QuEST_PREC=4 makes EVERY calc* accumulate in long double
# (QuEST_precision.h:55-68; QuEST_cpu.c:861-1071, 3363-3645).


def _diag(amps, num_qubits: int):
    """Diagonal of the column-major flattened rho: (2, dim) stacked."""
    dim = 1 << num_qubits
    mat = amps.reshape(2, dim, dim)  # [channel, col, row]
    return jnp.diagonal(mat, axis1=1, axis2=2)


@partial(jax.jit, static_argnames=("num_qubits",))
def calc_total_prob_density(amps, *, num_qubits: int):
    """Re(trace(rho)) (densmatr_calcTotalProb,
    QuEST_cpu_distributed.c:53-86)."""
    return jnp.sum(_diag(amps, num_qubits)[0])


@partial(jax.jit, static_argnames=("num_qubits", "target", "outcome",
                                   "quad"))
def calc_prob_of_outcome_statevec(amps, *, num_qubits: int, target: int,
                                  outcome: int, quad: bool = False):
    """(statevec_calcProbOfOutcome, QuEST_cpu.c:3418-3508)."""
    from .kernels import bit_indicator_2d, bit_indicator_canonical

    n = num_qubits
    if amps.ndim == 4:
        # the canonical view reduces in its own layout (no re-layout copy)
        ind = bit_indicator_canonical(n, ((target, outcome),), amps.dtype)
        view = amps
    else:
        ind = bit_indicator_2d(n, ((target, outcome),), amps.dtype)
        view = amps.reshape(2, ind.shape[0], ind.shape[1])
    if quad:
        return quad_sum2(view[0] * view[0] * ind, view[1] * view[1] * ind)
    return jnp.sum(cplx.abs2(view) * ind)


@partial(jax.jit, static_argnames=("num_qubits", "target", "outcome",
                                   "quad"))
def calc_prob_of_outcome_density(amps, *, num_qubits: int, target: int,
                                 outcome: int, quad: bool = False):
    """Sum of diagonal rho elements whose target bit equals outcome
    (densmatr_calcProbOfOutcome via findProbabilityOfZero,
    QuEST_cpu.c:3363-3417)."""
    from .kernels import bit_indicator_2d

    n = num_qubits
    diag_re = _diag(amps, num_qubits)[0]
    ind = bit_indicator_2d(n, ((target, outcome),), amps.dtype)
    red = quad_sum if quad else jnp.sum
    return red(diag_re.reshape(ind.shape) * ind)


def _outcome_histogram(vals, n: int, qubits: Tuple[int, ...]):
    """sum vals over amps grouped by the bits of ``qubits`` (outcome index
    bit j <-> qubits[j]): hist = A_hi^T (V A_lo) with {0,1} indicator
    matrices built from iotas — two MXU matmuls, no scatter (the reference
    uses an omp-atomic scatter, QuEST_cpu.c:3510-3574) and no small-minor
    reshape."""
    from ..utils import bits as bits_mod
    from .kernels import _split2

    k = len(qubits)
    hi, lo = _split2(n)
    qlo = [q for q in qubits if q < lo]
    qhi = [q for q in qubits if q >= lo]
    ilo = jax.lax.iota(jnp.int32, 1 << lo)
    ihi = jax.lax.iota(jnp.int32, 1 << hi)

    def onehot(iota, qs, offset):
        """(len(iota), 2^len(qs)) {0,1} indicator of the qs bit pattern."""
        code = jnp.zeros_like(iota)
        for j, q in enumerate(qs):
            code = code + (bits_mod.bits_of(iota, q - offset) << j)
        return (code[:, None] == jnp.arange(1 << len(qs))[None, :]).astype(vals.dtype)

    a_lo = onehot(ilo, qlo, 0)          # (2^lo, 2^kl)
    a_hi = onehot(ihi, qhi, lo)         # (2^hi, 2^kh)
    v = vals.reshape(1 << hi, 1 << lo)
    inner = jnp.matmul(v, a_lo, precision=jax.lax.Precision.HIGHEST)
    hist2 = jnp.matmul(a_hi.T, inner,
                       precision=jax.lax.Precision.HIGHEST)  # (2^kh, 2^kl)
    # hist2[ch, cl]: ch bit j <-> qhi[j], cl bit j <-> qlo[j]; remap to the
    # outcome convention (bit j <-> qubits[j]) with a tiny static gather.
    hist_flat = hist2.reshape(-1)  # index = ch * 2^kl + cl
    res = np.zeros(1 << k, dtype=np.int64)
    for o in range(1 << k):
        ch = 0
        cl = 0
        for j, q in enumerate(qubits):
            bitv = (o >> j) & 1
            if q < lo:
                cl |= bitv << qlo.index(q)
            else:
                ch |= bitv << qhi.index(q)
        res[o] = ch * (1 << len(qlo)) + cl
    return hist_flat[jnp.asarray(res)]


@partial(jax.jit, static_argnames=("num_qubits", "qubits"))
def calc_prob_of_all_outcomes_statevec(amps, *, num_qubits: int, qubits: Tuple[int, ...]):
    """2^k-outcome histogram; outcome index bit j <-> qubits[j]
    (calcProbOfAllOutcomes, QuEST_cpu.c:3510-3574 — the reference builds it
    with an omp-atomic scatter; a reshape+reduce is the vectorized form)."""
    return _outcome_histogram(cplx.abs2(amps), num_qubits, qubits)


@partial(jax.jit, static_argnames=("num_qubits", "qubits"))
def calc_prob_of_all_outcomes_density(amps, *, num_qubits: int, qubits: Tuple[int, ...]):
    return _outcome_histogram(_diag(amps, num_qubits)[0], num_qubits, qubits)


@jax.jit
def calc_inner_product(bra_amps, ket_amps):
    """<bra|ket> -> stacked (2,) (statevec_calcInnerProductLocal,
    QuEST_cpu.c:1071)."""
    return cplx.vdot(bra_amps, ket_amps)


@partial(jax.jit, static_argnames=("quad",))
def calc_density_inner_product(rho1_amps, rho2_amps, *, quad: bool = False):
    """Tr(rho1^dagger rho2) real part (densmatr_calcInnerProductLocal,
    QuEST_cpu.c:958)."""
    if quad:
        return quad_sum2(rho1_amps[0] * rho2_amps[0],
                         rho1_amps[1] * rho2_amps[1])
    return jnp.sum(rho1_amps[0] * rho2_amps[0] + rho1_amps[1] * rho2_amps[1])


@partial(jax.jit, static_argnames=("quad",))
def calc_purity(rho_amps, *, quad: bool = False):
    """Tr(rho^2) = sum |rho_rc|^2 for Hermitian rho (calcPurityLocal,
    QuEST_cpu.c:861)."""
    if quad:
        return quad_sum2(rho_amps[0] * rho_amps[0],
                         rho_amps[1] * rho_amps[1])
    return jnp.sum(cplx.abs2(rho_amps))


@partial(jax.jit, static_argnames=("num_qubits", "quad"))
def calc_fidelity_density(rho_amps, psi_amps, *, num_qubits: int,
                          quad: bool = False):
    """<psi|rho|psi> (densmatr_calcFidelityLocal, QuEST_cpu.c:990).

    Quad switches to the fully elementwise form: w_{rc} =
    Re[conj(psi_r) rho_{rc} psi_c] quad-summed over ALL dim^2 terms, so
    the signed cross terms see double-double accumulation end-to-end
    (the matmul form would round the inner contraction at f64)."""
    dim = 1 << num_qubits
    m = rho_amps.reshape(2, dim, dim)  # [channel, col, row]; m[., c, r] = rho_{r,c}
    p0, p1 = psi_amps[0], psi_amps[1]
    if quad:
        # conj(psi_r) psi_c = A[c,r] + i B[c,r]
        a = p0[:, None] * p0[None, :] + p1[:, None] * p1[None, :]
        b = p1[:, None] * p0[None, :] - p0[:, None] * p1[None, :]
        return quad_sum2(m[0] * a, -(m[1] * b))
    hi = jax.lax.Precision.HIGHEST
    # v_c = sum_r rho_{r,c} conj(psi_r)
    v_re = jnp.matmul(m[0], p0, precision=hi) + jnp.matmul(m[1], p1, precision=hi)
    v_im = jnp.matmul(m[1], p0, precision=hi) - jnp.matmul(m[0], p1, precision=hi)
    # Re( sum_c psi_c v_c )
    return jnp.sum(p0 * v_re - p1 * v_im)


@partial(jax.jit, static_argnames=("quad",))
def calc_hilbert_schmidt_distance(rho1_amps, rho2_amps, *,
                                  quad: bool = False):
    """sqrt(sum |rho1-rho2|^2) (calcHilbertSchmidtDistanceSquaredLocal,
    QuEST_cpu.c:923)."""
    d = rho1_amps - rho2_amps
    if quad:
        return jnp.sqrt(quad_sum2(d[0] * d[0], d[1] * d[1]))
    return jnp.sqrt(jnp.sum(cplx.abs2(d)))


@partial(jax.jit, static_argnames=("quad",))
def calc_expec_diagonal_statevec(amps, op_real, op_imag, *,
                                 quad: bool = False):
    """sum_i |amp_i|^2 d_i -> stacked (2,) (statevec_calcExpecDiagonalOp,
    QuEST_cpu.c:4094-4126)."""
    if quad:
        sq0, sq1 = amps[0] * amps[0], amps[1] * amps[1]
        return jnp.stack(
            [quad_sum2(sq0 * op_real, sq1 * op_real),
             quad_sum2(sq0 * op_imag, sq1 * op_imag)])
    p = cplx.abs2(amps)
    return jnp.stack([jnp.sum(p * op_real), jnp.sum(p * op_imag)])


@partial(jax.jit, static_argnames=("num_qubits", "quad"))
def calc_expec_diagonal_density(amps, op_real, op_imag, *, num_qubits: int,
                                quad: bool = False):
    """sum_r d_r rho_rr -> stacked (2,) — diagonal elements are node-local by
    construction in the reference (densmatr_calcExpecDiagonalOp,
    QuEST_cpu.c:4127-4186)."""
    d = _diag(amps, num_qubits)
    if quad:
        return jnp.stack(
            [quad_sum2(d[0] * op_real, -(d[1] * op_imag)),
             quad_sum2(d[0] * op_imag, d[1] * op_real)])
    re = jnp.sum(d[0] * op_real - d[1] * op_imag)
    im = jnp.sum(d[0] * op_imag + d[1] * op_real)
    return jnp.stack([re, im])
