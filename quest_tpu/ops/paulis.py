"""Pauli-string application, expectation values.

Re-implements the reference's workspace-based Pauli machinery
(QuEST_common.c:505-569: clone + apply X/Y/Z kernels + inner product) the
TPU way: a whole PauliHamil expectation is one jitted program — per term the
Pauli product is applied with permutation/sign fast kernels (X = axis flip,
Z = parity sign, Y = flip then +/-i sign; no dense 2x2 matmuls) and reduced
against the original state, so XLA fuses and pipelines across terms instead
of paying T full clone+dispatch round-trips.

States are SoA ``(2, num_amps)`` real arrays (see ops/cplx.py).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from . import cplx

PAULI_I, PAULI_X, PAULI_Y, PAULI_Z = 0, 1, 2, 3


def apply_pauli_string(amps, n: int, targets: Tuple[int, ...], codes: Tuple[int, ...]):
    """Apply a Pauli product to flat (2, 2^n) SoA amps using only axis flips
    (X), a parity sign mask (Z), and their composition (Y).

    Factorization: flipping all X and Y targets, the residual elementwise
    factor is (-i)^{#Y} * (-1)^{parity(Z and Y bits)} — Y|b> = i(2b'-1)|b'>
    with b' the flipped bit, and i(2b'-1) = -i * (-1)^{b'}.  So one multi-
    flip plus one fused parity multiply, never a high-rank broadcast.
    Matches statevec_applyPauliProd (QuEST_common.c:505-516) semantics.
    """
    from .kernels import _flip_bits_flat, parity_sign_2d

    flips = []
    par = []
    num_y = 0
    for t, c in zip(targets, codes):
        if c == PAULI_X:
            flips.append(t)
        elif c == PAULI_Z:
            par.append(t)
        elif c == PAULI_Y:
            flips.append(t)
            par.append(t)
            num_y += 1
    amps = _flip_bits_flat(amps, n, tuple(flips))
    if not par and num_y % 4 == 0:
        return amps
    # constant (-i)^{#Y}: one of 1, -i, -1, i
    c_re, c_im = [(1.0, 0.0), (0.0, -1.0), (-1.0, 0.0), (0.0, 1.0)][num_y % 4]
    if par:
        s = parity_sign_2d(n, par, amps.dtype)
        view = amps.reshape(2, s.shape[0], s.shape[1])
        return cplx.cmul(view, c_re * s, c_im * s).reshape(2, -1)
    return cplx.cmul(amps, jnp.asarray(c_re, amps.dtype),
                     jnp.asarray(c_im, amps.dtype))


@partial(jax.jit, static_argnames=("num_qubits", "targets", "codes"), donate_argnums=0)
def apply_pauli_prod(amps, *, num_qubits: int, targets: Tuple[int, ...], codes: Tuple[int, ...]):
    return apply_pauli_string(amps, num_qubits, targets, codes)


@partial(jax.jit, static_argnames=("num_qubits", "codes_flat", "num_terms",
                                   "quad"))
def calc_expec_pauli_sum_statevec(amps, coeffs, *, num_qubits: int,
                                  codes_flat: Tuple[int, ...], num_terms: int,
                                  quad: bool = False):
    """Re <psi| sum_t c_t P_t |psi> as ONE fused program (reference loops
    clone+apply+innerProduct per term, QuEST_common.c:534-546).  ``quad``
    (prec 4) accumulates each term's signed inner product — and the
    cross-term combine — in double-double."""
    from . import calculations as _calc

    n = num_qubits
    coeffs = jnp.asarray(coeffs, amps.dtype)
    vals = []
    for t in range(num_terms):
        codes = codes_flat[t * n:(t + 1) * n]
        pv = apply_pauli_string(amps, n, tuple(range(n)), codes)
        # Re <amps|pv>
        if quad:
            r = _calc.quad_sum2(amps[0] * pv[0], amps[1] * pv[1])
        else:
            r = jnp.sum(amps[0] * pv[0] + amps[1] * pv[1])
        vals.append(coeffs[t] * r)
    stacked = jnp.stack(vals)
    return _calc.neumaier_sum(stacked) if quad else jnp.sum(stacked)


@partial(jax.jit, static_argnames=("num_qubits", "codes_flat", "num_terms",
                                   "quad"))
def calc_expec_pauli_sum_density(amps, coeffs, *, num_qubits: int,
                                 codes_flat: Tuple[int, ...], num_terms: int,
                                 quad: bool = False):
    """Re Tr(rho sum_t c_t P_t): apply P to the ket qubits of the flattened
    rho, then take the diagonal trace (reference routes this through
    densmatr_calcTotalProb of a workspace, QuEST_common.c:519-546)."""
    from . import calculations as _calc

    n = num_qubits
    nn = 2 * n
    dim = 1 << n
    coeffs = jnp.asarray(coeffs, amps.dtype)
    red = _calc.quad_sum if quad else jnp.sum
    vals = []
    for t in range(num_terms):
        codes = codes_flat[t * n:(t + 1) * n]
        pv = apply_pauli_string(amps, nn, tuple(range(n)), codes)
        vals.append(coeffs[t] * red(jnp.diagonal(pv[0].reshape(dim, dim))))
    stacked = jnp.stack(vals)
    return _calc.neumaier_sum(stacked) if quad else jnp.sum(stacked)


@partial(jax.jit, static_argnames=("num_qubits", "num_state_qubits", "codes_flat", "num_terms"), donate_argnums=2)
def apply_pauli_sum(amps, coeffs, out_amps, *, num_qubits: int,
                    num_state_qubits: int, codes_flat: Tuple[int, ...],
                    num_terms: int):
    """out = sum_t c_t P_t |in> (statevec_applyPauliSum,
    QuEST_common.c:547-569). NOTE apply*-family: on rho this left-multiplies
    (SURVEY.md §2.3 semantic trap): num_state_qubits = 2*num_qubits and the
    codes act on the ket (low) qubits only."""
    n = num_qubits
    nsv = num_state_qubits
    coeffs = jnp.asarray(coeffs, amps.dtype)
    acc = jnp.zeros_like(amps)
    for t in range(num_terms):
        codes = codes_flat[t * n:(t + 1) * n]
        pv = apply_pauli_string(amps, nsv, tuple(range(n)), codes)
        acc = acc + coeffs[t] * pv
    del out_amps  # donated buffer re-used by XLA for the result
    return acc


# ---------------------------------------------------------------------------
# Scan-based Trotter body (agnostic_applyTrotterCircuit, QuEST_common.c:752-834)
# ---------------------------------------------------------------------------

def _rot_tables(dt):
    """SoA (4, 2, 2, 2) basis-rotation tables indexed by Pauli code:
    I/Z -> identity, X -> Ry(-90) (Z->X), Y -> Rx(+90) (Z->Y); plus the
    dagger and the conjugated (bra-twin) variants."""
    import numpy as np

    from . import gatedefs as G

    eye = np.eye(2, dtype=complex)
    tab = np.stack([eye, G.RY_M90, G.RX_P90, eye])
    tabd = np.conj(np.transpose(tab, (0, 2, 1)))

    def soa(t):
        return jnp.asarray(np.stack([t.real, t.imag], axis=1), dt)

    return soa(tab), soa(tabd), soa(np.conj(tab)), soa(np.conj(tabd))


_PAR_LO_BITS = 31  # uint32 iota stays exact up to 2^31 entries


def _parity_sign_dynamic(zm_lo, zm_hi, n, dt):
    """(2^n,)-shaped (+1/-1) sign of parity(idx & zmask) with a TRACED
    64-bit mask carried as two uint32 halves (bits [0,31) / [31,62)) —
    parity factorises over the split, so the sign is an outer product of
    two <=2^31-entry factors and no index arithmetic ever exceeds 32 bits
    (the reference's isOddParity runs on 64-bit masks,
    QuEST_cpu_internal.h:38).  Everything fuses; nothing materialises
    beyond the output sign."""
    lo = min(n, _PAR_LO_BITS)
    idx_lo = jax.lax.iota(jnp.uint32, 1 << lo)
    s_lo = 1.0 - 2.0 * (
        (jax.lax.population_count(idx_lo & zm_lo) & jnp.uint32(1))
        .astype(dt))
    if n <= _PAR_LO_BITS:
        return s_lo
    idx_hi = jax.lax.iota(jnp.uint32, 1 << (n - lo))
    s_hi = 1.0 - 2.0 * (
        (jax.lax.population_count(idx_hi & zm_hi) & jnp.uint32(1))
        .astype(dt))
    return (s_hi[:, None] * s_lo[None, :]).reshape(-1)


def _parity_phase_mask(amps, theta, zm_lo, zm_hi, n):
    """exp(-i theta/2 (-1)^parity(idx & zmask)) with a TRACED mask —
    the data-driven variant of kernels.apply_parity_phase (reference
    multiRotateZ bit-parity trick, QuEST_cpu.c:3268-3317)."""
    s = _parity_sign_dynamic(zm_lo, zm_hi, n, amps.dtype)
    ang = -0.5 * theta
    return cplx.cmul(amps, jnp.cos(ang), jnp.sin(ang) * s)


def _zmask_halves(codes, qbit_offset, nq):
    """(lo, hi) uint32 halves of sum_q [codes_q != I] << (q + offset)."""
    zm_lo = jnp.uint32(0)
    zm_hi = jnp.uint32(0)
    for q in range(nq):
        bit = (codes[q] != 0).astype(jnp.uint32)
        pos = q + qbit_offset
        if pos < _PAR_LO_BITS:
            zm_lo = zm_lo | (bit << pos)
        else:
            zm_hi = zm_hi | (bit << (pos - _PAR_LO_BITS))
    return zm_lo, zm_hi


def _product_layer(amps, mats, n):
    """Apply the 1q-gate product layer (x)_q mats[q] to all n state-vector
    qubits.  For n >= 14 the layer folds into ceil(n/7) window passes
    (lane side + one 7-qubit window each, circuit.py embedding); below
    that, per-qubit dense kernels."""
    from . import fused, kernels

    if n < fused.CLUSTER_QUBITS:
        for q in range(n):
            amps = kernels.apply_matrix(amps, mats[q], num_qubits=n,
                                        targets=(q,))
        return amps
    from .. import circuit as C

    def side(qs, rel_off):
        acc = None
        for q in qs:
            e = C.embed_in_cluster(mats[q], (q - rel_off,))
            acc = e if acc is None else C.soa_matmul(e, acc)
        return acc

    a = side(range(fused.LANE_QUBITS), 0)
    b7 = side(range(fused.LANE_QUBITS, fused.CLUSTER_QUBITS), fused.LANE_QUBITS)
    amps = fused.apply_window_stack(amps, a[None], b7[None],
                                    num_qubits=n, k=fused.LANE_QUBITS)
    eye = jnp.asarray(C._eye_cluster(), amps.dtype)[None]
    s = fused.CLUSTER_QUBITS
    while s < n:
        e = min(s + fused.LANE_QUBITS, n)
        k = min(s, n - fused.LANE_QUBITS)
        b = side(range(s, e), k)
        amps = fused.apply_window_stack(amps, eye, b[None],
                                        num_qubits=n, k=k, apply_a=False)
        s = e
    return amps


def make_trotter_body(dt, nq: int, is_density: bool, layer, parity_phase):
    """The per-term Trotter scan body (rotate -> parity phase [+ bra
    twin] -> unrotate), parameterized by the layer applier
    ``layer(carry, mats)`` and the parity phase
    ``parity_phase(carry, theta, zlo, zhi)`` so the unsharded scan
    (trotter_scan) and the shard_map scan
    (parallel.dist.trotter_scan_sharded) share ONE body — including the
    non-obvious all-identity-term angle zeroing (such terms contribute
    only a global phase the unfused path skips)."""
    tab, tabd, tabc, tabcd = _rot_tables(dt)

    def mats_for(codes, t, tc):
        m = t[codes]                        # (nq, 2, 2, 2)
        if is_density:
            m = jnp.concatenate([m, tc[codes]], axis=0)
        return m

    def body(carry, inp):
        codes, ang = inp
        ang = ang.astype(dt)
        carry = layer(carry, mats_for(codes, tab, tabc))
        zlo, zhi = _zmask_halves(codes, 0, nq)
        theta = jnp.where((zlo | zhi) == 0, jnp.asarray(0.0, dt), ang)
        carry = parity_phase(carry, theta, zlo, zhi)
        if is_density:
            blo, bhi = _zmask_halves(codes, nq, nq)
            carry = parity_phase(carry, -theta, blo, bhi)
        carry = layer(carry, mats_for(codes, tabd, tabcd))
        return carry, None

    return body


def make_expec_term_value(dt, n: int, layer, signed_norm):
    """The per-term PauliSum expectation body: basis-rotate a copy of the
    state (``layer``), then reduce the parity-signed norm
    (``signed_norm(phi, zlo, zhi)``).  Shared by expec_pauli_sum_scan and
    parallel.dist.expec_pauli_sum_scan_sharded."""
    tab, _, _, _ = _rot_tables(dt)

    def body_of(amps):
        def body(acc, inp):
            codes, coeff = inp
            phi = layer(amps, tab[codes])
            zlo, zhi = _zmask_halves(codes, 0, n)
            v = coeff.astype(dt) * signed_norm(phi, zlo, zhi)
            # per-term value also emitted as scan output so the quad
            # path can Neumaier-combine ACROSS terms instead of trusting
            # the f64 carry accumulation
            return acc + v, v
        return body

    return body_of


# ---------------------------------------------------------------------------
# Direct Pauli rotation: e^{-i th/2 P} psi = cos(th/2) psi
#                                            - i sin(th/2) (P psi)
# with (P psi)[i] = (-i)^{#Y} * (-1)^{parity(i & zm)} * psi[i ^ fm]
# (fm = X|Y bits, zm = Z|Y bits, P^2 = I).  ONE split-axis gather + one
# fused elementwise combine per term, in place of a rotate-layer ->
# parity-phase -> unrotate-layer body (three passes); the (hi, lo)
# row/lane split keeps the permutation's gather DMA-friendly.  The
# reference's
# multiRotatePauli instead conjugates by basis rotations
# (QuEST_common.c:424-462).
# ---------------------------------------------------------------------------

_GATHER_LO_BITS = 12   # lane-axis width of the split gather (4096)
# Direct-rotation cap, DERIVED from the gather split and the int32
# max-index invariant rather than hand-counted: _flip_gather's hi-axis
# index vector is an int32 iota over 2^(n - _GATHER_LO_BITS) rows, so its
# largest value 2^(n - _GATHER_LO_BITS) - 1 must fit int32 — at most 31
# hi bits on top of the lane split.
_DIRECT_MAX_N = _GATHER_LO_BITS + 31
assert (1 << (_DIRECT_MAX_N - _GATHER_LO_BITS)) - 1 <= 2**31 - 1, (
    "_DIRECT_MAX_N violates the int32 row-index invariant")


def _direct_masks(codes, nq: int, offset: int, n: int):
    """(fm_lo, fm_hi, zlo, zhi, ny) for a Pauli-code row acting on qubits
    [offset, offset+nq): the flip mask split at _GATHER_LO_BITS for the
    row/lane gather, the parity mask split at _PAR_LO_BITS for the sign,
    and the Y count for the (-i)^{#Y} factor."""
    lo = min(_GATHER_LO_BITS, n)
    fm_lo = jnp.uint32(0)
    fm_hi = jnp.uint32(0)
    zlo = jnp.uint32(0)
    zhi = jnp.uint32(0)
    ny = jnp.uint32(0)
    for q in range(nq):
        c = codes[q]
        is_x = (c == PAULI_X).astype(jnp.uint32)
        is_y = (c == PAULI_Y).astype(jnp.uint32)
        is_z = (c == PAULI_Z).astype(jnp.uint32)
        pos = q + offset
        fbit = is_x | is_y
        if pos < lo:
            fm_lo = fm_lo | (fbit << pos)
        else:
            fm_hi = fm_hi | (fbit << (pos - lo))
        zbit = is_y | is_z
        if pos < _PAR_LO_BITS:
            zlo = zlo | (zbit << pos)
        else:
            zhi = zhi | (zbit << (pos - _PAR_LO_BITS))
        ny = ny + is_y
    return fm_lo, fm_hi, zlo, zhi, ny


def _flip_gather(amps, fm_lo, fm_hi, n: int):
    """psi[i ^ fm] for the whole (2, 2^n) state with a TRACED flip mask:
    one row-axis take (contiguous 2^lo-element rows) + one lane-axis
    take — the split keeps both index vectors small and the row reads
    contiguous."""
    lo = min(_GATHER_LO_BITS, n)
    hi = n - lo
    idx_lo = jax.lax.iota(jnp.uint32, 1 << lo) ^ fm_lo
    v = amps.reshape(2, 1 << hi, 1 << lo)
    if hi:
        idx_hi = jax.lax.iota(jnp.uint32, 1 << hi) ^ fm_hi
        v = jnp.take(v, idx_hi, axis=1)
    return jnp.take(v, idx_lo, axis=2).reshape(2, -1)


def _iexp_factor(ny, dt):
    """(-i)^{ny} as (re, im) scalars."""
    k = ny % 4
    c_re = jnp.where(k == 0, 1.0, jnp.where(k == 2, -1.0, 0.0)).astype(dt)
    c_im = jnp.where(k == 1, -1.0, jnp.where(k == 3, 1.0, 0.0)).astype(dt)
    return c_re, c_im


def _apply_pauli_traced(amps, codes, nq: int, offset: int, n: int,
                        conj: bool):
    """(P psi) with traced codes: gather + sign + (-i)^{#Y} factor
    (conj negates the factor's imaginary part — conj(P) flips Y's
    sign)."""
    dt = amps.dtype
    fm_lo, fm_hi, zlo, zhi, ny = _direct_masks(codes, nq, offset, n)
    s = _parity_sign_dynamic(zlo, zhi, n, dt)
    c_re, c_im = _iexp_factor(ny, dt)
    if conj:
        c_im = -c_im
    pv = _flip_gather(amps, fm_lo, fm_hi, n)
    pr = s * (c_re * pv[0] - c_im * pv[1])
    pi = s * (c_re * pv[1] + c_im * pv[0])
    return jnp.stack([pr, pi]), (fm_lo | fm_hi | zlo | zhi) == 0


def _direct_rotation(amps, codes, ang, nq: int, offset: int, n: int,
                     conj: bool):
    """e^{-i ang/2 P} psi (or e^{-i ang/2 conj(P)} psi when ``conj``) in
    ONE gather + combine; all-identity terms contribute only a global
    phase the gate stream skips (the same zeroing as make_trotter_body)."""
    dt = amps.dtype
    pv, is_identity = _apply_pauli_traced(amps, codes, nq, offset, n, conj)
    theta = jnp.where(is_identity, jnp.asarray(0.0, dt), ang)
    co = jnp.cos(0.5 * theta)
    si = jnp.sin(0.5 * theta)
    # out = cos*psi - i sin * (P psi)
    return jnp.stack([co * amps[0] + si * pv[1],
                      co * amps[1] - si * pv[0]])


# ---------------------------------------------------------------------------
# Pallas fused direct rotation: the whole term in ONE HBM pass per block
# (bit-identical to the take-take gather).  The XOR permutation
# decomposes as
#   - block-level row XOR: the flip input's BlockSpec index_map reads
#     block (i ^ (fm_row >> 8)) — pure DMA redirection;
#   - in-block row XOR (8 bits) and lane XOR (7 bits): dynamically built
#     0/1 permutation matmuls (256x256 and 128x128) on the MXU — Mosaic
#     has no rev lowering, and at HIGHEST precision a permutation matmul
#     is exact;
# parity signs factor as s_row (x) s_lane: the lane factor is a (1, 128)
# input, the row factor is folded from the row iota in the kernel (a
# (rows, 1) input would tile-pad to 128 lanes — half a state at 30 bits).
# ---------------------------------------------------------------------------

_PL_BR = 256            # rows per block (n >= _PL_MIN_N so R >= _PL_BR)
_PL_MIN_N = 15


def _pl_routable(amps, n: int) -> bool:
    """The fused Pallas term kernels serve f32 states of 15..32 bits on
    the TPU; there a kernel compiles or the run raises
    (tests/test_chip_compile.py compiles them for a described v5e)."""
    from . import fused as _fused

    return (_PL_MIN_N <= n <= 32 and amps.dtype == jnp.float32
            and not _fused._interpret_default())


def _pl_flip_signed(meta, fvals, x_ref, f_ref, slane_ref):
    """Shared kernel-body algebra: load the two blocks, apply the
    in-block row XOR and lane XOR as exact permutation matmuls, and
    return (x, pr, pi) with the parity sign and (-i)^{#Y} factor folded
    in — used by both the rotation and the expectation kernels."""
    from jax import lax

    rb = meta[1]
    fl = meta[2]
    x = x_ref[...]                  # (2, BR, 128)
    f = f_ref[...]
    hi = lax.Precision.HIGHEST
    ri = lax.broadcasted_iota(jnp.int32, (_PL_BR, _PL_BR), 0)
    rj = lax.broadcasted_iota(jnp.int32, (_PL_BR, _PL_BR), 1)
    prow = ((ri ^ rb) == rj).astype(x.dtype)
    f = jnp.concatenate([
        jnp.dot(prow, f[0], preferred_element_type=x.dtype,
                precision=hi)[None],
        jnp.dot(prow, f[1], preferred_element_type=x.dtype,
                precision=hi)[None],
    ])
    li = lax.broadcasted_iota(jnp.int32, (128, 128), 0)
    lj = lax.broadcasted_iota(jnp.int32, (128, 128), 1)
    perm = ((li ^ fl) == lj).astype(x.dtype)
    pv = jnp.dot(f.reshape(2 * _PL_BR, 128), perm,
                 preferred_element_type=x.dtype,
                 precision=hi).reshape(2, _PL_BR, 128)
    import jax.experimental.pallas as pl

    rows = (pl.program_id(0) * _PL_BR
            + lax.broadcasted_iota(jnp.int32, (_PL_BR, 128), 0)) & meta[3]
    for sh in (16, 8, 4, 2, 1):
        rows = rows ^ (rows >> sh)
    s = (1 - 2 * (rows & 1)).astype(x.dtype) * slane_ref[...]
    c_re = fvals[0, 2]
    c_im = fvals[0, 3]
    pr = s * (c_re * pv[0] - c_im * pv[1])
    pi = s * (c_re * pv[1] + c_im * pv[0])
    return x, pr, pi


def _pl_rotation_kernel(meta, fvals, x_ref, f_ref, slane_ref, out_ref):
    x, pr, pi = _pl_flip_signed(meta, fvals, x_ref, f_ref, slane_ref)
    co = fvals[0, 0]
    si = fvals[0, 1]
    out_ref[0, :, :] = co * x[0] + si * pi
    out_ref[1, :, :] = co * x[1] - si * pr


def _pl_expec_kernel(meta, fvals, x_ref, f_ref, slane_ref, out_ref):
    """Per-term expectation contribution Re <x| P |x>: flip (same
    permutation algebra as the rotation kernel) + sign + product-reduce,
    one HBM pass — emitting ONE PARTIAL PER GRID BLOCK.  The (G,)
    partials are tree-reduced OUTSIDE the kernel (_expec_term_pallas):
    chaining every block through a single f32 accumulator cell makes the
    rounding error grow linearly in the block count and loses
    cross-block cancellation exactly where terms with opposing signs
    should cancel (ADVICE r5)."""
    x, pr, pi = _pl_flip_signed(meta, fvals, x_ref, f_ref, slane_ref)
    # the block's partial fills a (1, 1, 128) row: Mosaic blocks end in
    # (8k | full, 128k | full) dims, which a (1, 1) scalar block is not
    out_ref[...] = jnp.broadcast_to(jnp.sum(x[0] * pr + x[1] * pi),
                                    (1, 1, 128))


def _pl_term_inputs(amps, codes, ang, nq: int, offset: int, n: int,
                    conj: bool):
    """(meta, fvals, view, s_lane) shared by the two Pallas term kernels;
    meta = (block row XOR, in-block row XOR, lane XOR, row parity mask)."""
    dt = amps.dtype
    R = 1 << (n - 7)
    fm_lo, fm_hi, zlo, zhi, ny = _direct_masks(codes, nq, offset, n)
    fm = fm_lo.astype(jnp.uint32)
    if n > _GATHER_LO_BITS:
        fm = fm | (fm_hi << _GATHER_LO_BITS)
    fm_lane = (fm & jnp.uint32(127)).astype(jnp.int32)
    fm_row = (fm >> 7).astype(jnp.int32)
    # parity factorises: s(r*128 + l) = s_row(r) * s_lane(l); the row
    # mask (< 2^25 for n <= 32) rides the scalar prefetch
    zm = zlo.astype(jnp.uint32)
    zrow = zm >> 7
    if n > _PAR_LO_BITS:
        zrow = zrow | (zhi.astype(jnp.uint32) << (_PAR_LO_BITS - 7))
    meta = jnp.stack([fm_row >> 8, fm_row & 255, fm_lane,
                      zrow.astype(jnp.int32)])
    s_lane = 1.0 - 2.0 * ((jax.lax.population_count(
        jax.lax.iota(jnp.uint32, 128) & (zm & jnp.uint32(127)))
        & jnp.uint32(1)).astype(dt)).reshape(1, 128)
    theta = jnp.where((fm_lo | fm_hi | zlo | zhi) == 0,
                      jnp.asarray(0.0, dt), ang)
    c_re, c_im = _iexp_factor(ny, dt)
    if conj:
        c_im = -c_im
    fvals = jnp.stack([jnp.cos(0.5 * theta), jnp.sin(0.5 * theta),
                       c_re, c_im]).reshape(1, 4)
    return meta, fvals, amps.reshape(2, R, 128), s_lane


def _pl_grid_spec(R, out_blockspec):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R // _PL_BR,),
        in_specs=[
            pl.BlockSpec((1, 4), lambda i, meta: (0, 0)),
            pl.BlockSpec((2, _PL_BR, 128), lambda i, meta: (0, i, 0)),
            pl.BlockSpec((2, _PL_BR, 128),
                         lambda i, meta: (0, i ^ meta[0], 0)),
            pl.BlockSpec((1, 128), lambda i, meta: (0, 0)),
        ],
        out_specs=out_blockspec,
    )


def _expec_term_pallas(amps, codes, n: int):
    """Re <amps| P |amps> with a traced code row, one fused HBM pass:
    the kernel writes one partial per grid block and the (G,) partials
    tree-reduce here under XLA — O(log G) error depth instead of a
    single-cell sequential accumulation's O(G)."""
    import jax
    import jax.experimental.pallas as pl

    from . import fused as _fused

    meta, fvals, view, s_lane = _pl_term_inputs(
        amps, codes, jnp.zeros((), amps.dtype), n, 0, n, conj=False)
    R = view.shape[1]
    out = pl.pallas_call(
        _pl_expec_kernel,
        grid_spec=_pl_grid_spec(
            R, pl.BlockSpec((1, 1, 128), lambda i, meta: (i, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((R // _PL_BR, 1, 128), view.dtype),
        interpret=_fused._interpret_default(),
    )(meta, fvals, view, view, s_lane)
    return jnp.sum(out[:, 0, 0])


def _direct_rotation_pallas(amps, codes, ang, nq: int, offset: int,
                            n: int, conj: bool):
    """One fused-HBM-pass direct rotation (15 <= n <= 32); bit-identical
    to _direct_rotation by construction (exact permutation matmuls + the
    same sign/factor algebra)."""
    import jax
    import jax.experimental.pallas as pl

    from . import fused as _fused

    meta, fvals, view, s_lane = _pl_term_inputs(
        amps, codes, ang, nq, offset, n, conj)
    R = view.shape[1]
    out = pl.pallas_call(
        _pl_rotation_kernel,
        grid_spec=_pl_grid_spec(
            R, pl.BlockSpec((2, _PL_BR, 128),
                            lambda i, meta: (0, i, 0))),
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        interpret=_fused._interpret_default(),
    )(meta, fvals, view, view, s_lane)
    return out.reshape(amps.shape)


@partial(jax.jit, static_argnames=("num_qubits", "rep_qubits"),
         donate_argnums=0)
def trotter_scan(amps, codes_seq, angles, *, num_qubits: int,
                 rep_qubits: int):
    """The whole Trotter gate stream as ONE lax.scan over a (T, nq)
    Pauli-code table + (T,) angle vector: compile cost is a single term
    body regardless of term count, replacing the unrolled per-term
    multiRotatePauli stream whose first-call compile took minutes at
    config-5 scale (agnostic_applyTrotterCircuit, QuEST_common.c:752-834).

    The term body is the direct Pauli rotation (one split-axis gather +
    elementwise combine; density matrices add the conjugated bra twin at
    -theta) — ~8x the throughput of the rotate/phase/unrotate window
    body at 24q.  Registers beyond _DIRECT_MAX_N state bits (where the
    row-gather iota would overflow int32) keep the rotation-conjugation
    body; the SHARDED scan (parallel.dist.trotter_scan_sharded) carries
    the same direct body with the mesh-bit part of the traced flip mask
    riding a lax.switch over the 2^r static XOR ppermutes
    (dist._mesh_flip_gather); mesh-sweep parity tests pin the forms
    equal."""
    n, nq = num_qubits, rep_qubits
    dt = amps.dtype
    if n > _DIRECT_MAX_N:
        body = make_trotter_body(
            dt, nq, n == 2 * nq,
            layer=lambda carry, mats: _product_layer(carry, mats, n),
            parity_phase=lambda carry, theta, zlo, zhi: _parity_phase_mask(
                carry, theta, zlo, zhi, n),
        )
        amps, _ = jax.lax.scan(body, amps, (codes_seq, angles))
        return amps

    is_density = n == 2 * nq
    # fused Pallas term for block-decomposable sizes (one HBM pass per
    # term, 2.3x the take-take gather; u32 mask recombination caps at 32
    # state bits).  Real-Mosaic only for f32 on TPU: Mosaic has no f64
    # dot lowering (fused._resolve_interpret documents the same
    # constraint), and on CPU the interpreted grid would be far slower
    # than the fused XLA gather — both take the gather form instead
    # (tests/test_direct_rotation.py drives the kernels directly in
    # interpret mode to keep them covered off-TPU).
    rot = (_direct_rotation_pallas if _pl_routable(amps, n)
           else _direct_rotation)

    def body(carry, inp):
        codes, ang = inp
        ang = ang.astype(dt)
        carry = rot(carry, codes, ang, nq, 0, n, conj=False)
        if is_density:
            carry = rot(carry, codes, -ang, nq, nq, n, conj=True)
        return carry, None

    amps, _ = jax.lax.scan(body, amps, (codes_seq, angles))
    return amps


@partial(jax.jit, static_argnames=("num_qubits", "quad"))
def expec_pauli_sum_scan(amps, codes_seq, coeffs, *, num_qubits: int,
                         quad: bool = False):
    """Re <psi| sum_t c_t P_t |psi> as ONE lax.scan over the (T, n)
    Pauli-code table: per term, basis-rotate a COPY of the state so P_t
    becomes a Z-string (the multiRotatePauli trick, QuEST_common.c:424-462
    applied to expectation values), then reduce sum s(idx) |phi|^2 with the
    parity sign fused into the sum.  Compile cost is one term body
    regardless of term count — the unrolled variant took ~100 s to compile
    at 16 terms x 24 qubits.

    ``quad`` (prec 4): the signed per-term norm accumulates in
    double-double (calculations.quad_sum) and the cross-term combine runs
    a Neumaier scan over the emitted term values — the reference's
    QuEST_PREC=4 runs this whole reduction in long double."""
    from . import calculations as _calc

    n = num_qubits
    dt = amps.dtype
    use_pl = not quad and _pl_routable(amps, n)
    if not use_pl:
        # the gather forms index the flat state (the Pallas term kernel
        # views a canonical (2, 2^(n-14), 128, 128) state in place)
        amps = amps.reshape(2, -1)

    if n > _DIRECT_MAX_N:
        def signed_norm(phi, zlo, zhi):
            s = _parity_sign_dynamic(zlo, zhi, n, dt)
            if quad:
                return _calc.quad_sum2(s * phi[0] * phi[0],
                                       s * phi[1] * phi[1])
            return jnp.sum(s * (phi[0] * phi[0] + phi[1] * phi[1]))

        body = make_expec_term_value(
            dt, n,
            layer=lambda a, mats: _product_layer(a, mats, n),
            signed_norm=signed_norm,
        )(amps)
        total, vals = jax.lax.scan(body, jnp.zeros((), dt),
                                   (codes_seq, coeffs))
        return _calc.neumaier_sum(vals) if quad else total

    # direct form: Re <psi| c_t P_t |psi> = c_t * sum_i (psi_r pr +
    # psi_i pi) with (pr, pi) = P psi — fused flip+sign+reduce Pallas
    # kernel (one HBM pass per term) at block-decomposable sizes; the
    # split-axis gather + reduce otherwise.  Quad keeps the gather form:
    # its channel-split double-double accumulation needs the full
    # product vectors, not f32 block partials.
    def body(acc, inp):
        codes, coeff = inp
        if use_pl:
            r = _expec_term_pallas(amps, codes, n)
        else:
            pv, _ = _apply_pauli_traced(amps, codes, n, 0, n, conj=False)
            if quad:
                r = _calc.quad_sum2(amps[0] * pv[0], amps[1] * pv[1])
            else:
                r = jnp.sum(amps[0] * pv[0] + amps[1] * pv[1])
        v = coeff.astype(dt) * r
        return acc + v, v

    total, vals = jax.lax.scan(body, jnp.zeros((), dt),
                               (codes_seq, coeffs))
    return _calc.neumaier_sum(vals) if quad else total


@partial(jax.jit, static_argnames=("num_qubits", "dtype", "sharding"))
def diag_from_z_hamil(zmasks_lo, zmasks_hi, coeffs, *, num_qubits: int,
                      dtype, sharding=None):
    """diag_d = sum_t c_t (-1)^parity(d & zmask_t) entirely ON DEVICE —
    the reference computes this distributed over each node's chunk
    (agnostic_initDiagonalOpFromPauliHamil, QuEST_cpu.c:4188-4227); the
    previous host-numpy version materialised a dense 2^n array per term,
    blowing host memory for exactly the large-n DiagonalOps the type
    exists for.  Scan over the (T,) z-mask table (uint32 lo/hi halves so
    n > 31 stays exact): one compiled body, no host arrays beyond the
    tiny mask/coeff vectors.  ``sharding`` constrains the accumulator so
    the diagonal is built sharded over the mesh rather than on one
    device."""

    def body(acc, inp):
        zlo, zhi, c = inp
        s = _parity_sign_dynamic(zlo, zhi, num_qubits, acc.dtype)
        return acc + c.astype(acc.dtype) * s, None

    acc0 = jnp.zeros((1 << num_qubits,), dtype)
    if sharding is not None:
        acc0 = jax.lax.with_sharding_constraint(acc0, sharding)
    acc, _ = jax.lax.scan(body, acc0, (zmasks_lo, zmasks_hi, coeffs))
    return acc


def z_sum_masks(codes_seq, shape) -> "np.ndarray":
    """(T, axes) uint32 parity masks of I/Z code rows over the index axes
    of a state held in ``shape``: flat (2, 2^n) has one axis holding bits
    [0, n); the canonical (2, 2^(n-14), 128, 128) has rows (bits 14..),
    sublanes (bits 7..13) and lanes (bits 0..6)."""
    import numpy as np

    z = (np.asarray(codes_seq) == PAULI_Z).astype(np.uint64)
    widths = [int(d).bit_length() - 1 for d in shape[1:]]
    out = np.zeros((z.shape[0], len(widths)), np.uint64)
    lo = z.shape[1]
    for ax, w in enumerate(widths):
        lo -= w
        out[:, ax] = (z[:, lo:lo + w] << np.arange(w, dtype=np.uint64)).sum(1)
    return out.astype(np.uint32)


@jax.jit
def expec_z_sum(amps, masks, coeffs):
    """Re <psi| sum_t c_t Z_t |psi> for I/Z-only Pauli strings, on the
    state where it lies: ``amps`` flat or in the canonical device shape,
    on one device or sharded over the mesh (the partitioner gives each
    device its shard's rows and adds the partial sums), with no copy of
    the state.  ``masks``: z_sum_masks of the code rows for this shape.
    Per term one read pass: the parity sign comes from per-axis index
    iotas, rows are summed before the rows' sums."""
    dt = amps.dtype
    shape = amps.shape[1:]

    def body(acc, inp):
        m, c = inp
        par = jnp.uint32(0)
        for ax in range(len(shape)):
            idx = jax.lax.broadcasted_iota(jnp.uint32, shape, ax)
            par = par + jax.lax.population_count(idx & m[ax])
        s = (1 - 2 * (par & jnp.uint32(1)).astype(jnp.int32)).astype(dt)
        w = s * (amps[0] * amps[0] + amps[1] * amps[1])
        rows = jnp.sum(w, axis=tuple(range(1, len(shape)))) \
            if len(shape) > 1 else w
        return acc + c.astype(dt) * jnp.sum(rows), None

    total, _ = jax.lax.scan(body, jnp.zeros((), dt), (masks, coeffs))
    return total
