"""State-vector kernels: the TPU-native re-implementation of the reference's
backend kernel surface (``QuEST/src/QuEST_internal.h:116-272`` ``statevec_*``).

Design (not a port): the reference hand-codes strided amplitude-pair loops
per gate (e.g. compactUnitaryLocal, QuEST_cpu.c:1743-1800; CUDA
thread-per-pair, QuEST_gpu.cu:1037-1092).  Here a state of n qubits is a
real SoA array of shape ``(2, 2**n)`` (channel 0/1 = real/imag — the
reference's own ComplexArray layout, QuEST.h:77, and the TPU-native one:
see ops/cplx.py); a gate on targets T is a reshape / axis-move plus a small
real einsum or a broadcast elementwise multiply, and XLA generates the
strided fused loops.  Qubit q is bit q of the flat amplitude index
(little-endian), i.e. axis ``1 + (n-1-q)`` of the ``(2,) + (2,)*n`` view —
identical index convention to the reference (QuEST.h:393-400).

All functions are pure ``amps -> amps`` (or ``amps -> scalar``) and
jit-compiled with static qubit indices; the state buffer is donated so gate
chains update HBM in place (the reference instead mutates stateVec and pays
a 2x pairStateVec buffer when distributed, QuEST_cpu.c:1279-1315).

Matrices/diagonals enter as *stacked* SoA arrays ``(2, D, D)`` / ``(2, D)``
built host-side (cplx.soa) — dynamic arguments, so a parameterised gate
never recompiles when only its angle changes.

Controlled gates do not scan a control mask per amplitude as the reference
does (QuEST_cpu.c:1802-1895); they statically slice the controlled sub-block
(an axis index per control), apply the target update to the ``2**(n-c)``
surviving amplitudes, and scatter back with a dynamic-update-slice — so
bandwidth scales with the controlled subspace, beating the reference's
full-state scan.
"""

from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import cplx


def _axis(n: int, q: int) -> int:
    """Axis of qubit q in the (2,) + (2,)*n channel-first view."""
    return 1 + (n - 1 - q)


# ---------------------------------------------------------------------------
# Low-rank bit views
#
# XLA-TPU materializes high-rank reshapes with tiled layouts: an all-2s
# rank-(n+1) view of the state pads each of the two minor dims to the
# (8, 128) tile, a 64x HBM blowup (34 GB at n=26), and transposes of such
# shapes take minutes to compile.  Every kernel therefore views the state
# through *coalesced* reshapes only: one small axis per qubit actually
# touched, one large axis per contiguous bit gap — rank O(k), never O(n).
# ---------------------------------------------------------------------------


def _interleaved(n: int, bits):
    """Shape splitting the flat 2^n axis at each bit (channel axis first).

    Returns (shape, axis_of): ``shape`` interleaves gap axes with one
    size-2 axis per bit in ``bits`` (any order; sorted internally);
    ``axis_of[b]`` is the index of bit b's size-2 axis."""
    bits_desc = sorted(bits, reverse=True)
    shape = [2]
    axis_of = {}
    prev = n
    for b in bits_desc:
        shape.append(1 << (prev - 1 - b))
        axis_of[b] = len(shape)
        shape.append(2)
        prev = b
    shape.append(1 << prev)
    return tuple(shape), axis_of


def _interleaved_sel(n: int, bits_states):
    """(shape, sel): interleaved view shape plus the selector tuple fixing
    each bit to its state — the low-rank control-slice used everywhere the
    reference scans a control mask (QuEST_cpu.c:1802-1895)."""
    shape, axis_of = _interleaved(n, [b for b, _ in bits_states])
    sel = [slice(None)] * len(shape)
    for b, s in bits_states:
        sel[axis_of[b]] = int(s)
    return shape, tuple(sel)


def _remap_targets(controls, targets):
    """Qubit labels inside the control-sliced sub-state (controls removed)."""
    return tuple(t - sum(1 for c in controls if c < t) for t in targets)


def _apply_with_controls(amps, n: int, controls, control_states, targets, body):
    """Run ``body(sub, sub_n, sub_targets)`` on the controlled subspace.

    Controls >= 7 are sliced out as contiguous halves (layout-safe: every
    view keeps a >= 2^7 minor axis) and reassembled by concatenation;
    controls < 7 sit inside the 128-lane block, which cannot be sliced
    without a tiny-minor layout, so the op runs on the whole lane block and
    a static 128-lane indicator mask blends updated and original lanes.
    Replaces the reference's per-amplitude control-mask scan
    (QuEST_cpu.c:1802-1895) with slicing: bandwidth scales with the
    controlled sub-block for the sliced controls."""
    if not control_states:
        control_states = (1,) * len(controls)
    if n < _BIG_N:
        cs = sorted(zip(controls, control_states), key=lambda p: -p[0])
        sub_targets = _remap_targets(controls, targets)

        def rec_small(a, nn, i):
            if i == len(cs):
                return body(a, nn, sub_targets)
            c, s = cs[i]
            v = a.reshape(2, 1 << (nn - 1 - c), 2, 1 << c)
            sub = v[:, :, int(s), :].reshape(2, -1)
            sub = rec_small(sub, nn - 1, i + 1)
            v = v.at[:, :, int(s), :].set(
                sub.reshape(v.shape[0], v.shape[1], v.shape[3])
            )
            return v.reshape(2, -1)

        return rec_small(amps, n, 0)

    high = sorted(((c, s) for c, s in zip(controls, control_states)
                   if c >= _LANE_BITS), key=lambda p: -p[0])
    low = [(c, s) for c, s in zip(controls, control_states) if c < _LANE_BITS]
    high_controls = [c for c, _ in high]
    sub_targets = _remap_targets(high_controls, targets)

    lane_mask = None
    if low:
        idx = np.arange(1 << _LANE_BITS)
        m = np.ones(1 << _LANE_BITS, dtype=bool)
        for c, s in low:
            m &= ((idx >> c) & 1) == int(s)
        lane_mask = jnp.asarray(m)

    def leaf(a, nn):
        new = body(a, nn, sub_targets)
        if lane_mask is None:
            return new
        v = a.reshape(2, -1, 1 << _LANE_BITS)
        nv = new.reshape(2, -1, 1 << _LANE_BITS)
        return jnp.where(lane_mask[None, None, :], nv, v).reshape(2, -1)

    def rec(a, nn, i):
        if i == len(high):
            return leaf(a, nn)
        c, s = high[i]
        lo_half, hi_half = _cslices(a, nn, c)
        if int(s) == 1:
            sub = rec(hi_half.reshape(2, -1), nn - 1, i + 1)
            parts = [lo_half, sub.reshape(lo_half.shape)]
        else:
            sub = rec(lo_half.reshape(2, -1), nn - 1, i + 1)
            parts = [sub.reshape(hi_half.shape), hi_half]
        return jnp.concatenate(parts, axis=2).reshape(2, -1)

    return rec(amps, n, 0)


def _split2(n: int):
    """(hi_bits, lo_bits) split of n index bits, each <= 31 so int32 iotas
    cover density-matrix index spaces (2n up to 62 bits)."""
    lo = n // 2
    return n - lo, lo


def parity_sign_2d(n: int, qubits, dtype):
    """(2^hi, 2^lo) array of (-1)^parity(bits in ``qubits``) built from two
    int32 iotas (XLA fuses it into the consuming multiply) — the vectorized
    form of the reference's bit-parity sign trick (QuEST_cpu.c:3268-3275).
    Callers view the state as (2, 2^hi, 2^lo)."""
    from ..utils import bits as bits_mod

    hi, lo = _split2(n)
    qlo = [q for q in qubits if q < lo]
    qhi = [q - lo for q in qubits if q >= lo]
    plo = bits_mod.parity_of(jax.lax.iota(jnp.int32, 1 << lo), qlo)
    phi = bits_mod.parity_of(jax.lax.iota(jnp.int32, 1 << hi), qhi)
    par = phi[:, None] ^ plo[None, :]
    return (1 - 2 * par).astype(dtype)


def parity_sign_flat(n: int, qubits, dtype):
    """(2^n,) sign vector (-1)^parity(bits in ``qubits``) from ONE flat
    iota.  Under GSPMD a flat iota partitions along the sharded amplitude
    axis with zero communication, where the factored 2-d outer-product
    form (parity_sign_2d) made XLA ALL-GATHER the sharded state to align
    the broadcast (observed: 3 all-gathers per dephasing call on the
    8-way mesh — tests/test_distributed_hlo.py pins the fixed behavior).
    int32 iota limits this to n <= 31; callers fall back to the 2-d form
    beyond that (multi-host scale, where the mask axes are mesh-aligned
    anyway)."""
    from ..utils import bits as bits_mod

    assert n <= 31, "flat parity sign needs an int32-safe index space"
    par = bits_mod.parity_of(jax.lax.iota(jnp.int32, 1 << n), list(qubits))
    return (1 - 2 * par).astype(dtype)


# The lane split: bits 0..6 form the 128-wide minor (lane) block that every
# layout-safe kernel keeps as the minor axis.  States with n >= _BIG_N take
# the layout-safe paths; smaller states use the simple einsum/reshape paths
# (tiny arrays — padding and compile time are irrelevant there).
_LANE_BITS = 7
_BIG_N = 14


def bit_2d(n: int, q: int):
    """Per-amplitude value of qubit q's bit, broadcastable over the
    (2^hi, 2^lo) = _split2(n) view of the state — the shared iota-bit
    convention used by parity_sign_2d / bit_indicator_2d /
    _apply_diagonal_flat and the models."""
    from ..utils import bits as bits_mod

    hi, lo = _split2(n)
    if q < lo:
        return bits_mod.bits_of(jax.lax.iota(jnp.int32, 1 << lo), q)[None, :]
    return bits_mod.bits_of(jax.lax.iota(jnp.int32, 1 << hi), q - lo)[:, None]


def bit_indicator_2d(n: int, bit_states, dtype):
    """(2^hi, 2^lo) {0,1} array: 1 where every (bit, state) pair matches —
    iota-built so XLA fuses it into the consuming multiply (layout-safe at
    any bit position, unlike a size-2-axis broadcast)."""
    from ..utils import bits as bits_mod

    hi, lo = _split2(n)
    ilo = jax.lax.iota(jnp.int32, 1 << lo)
    ihi = jax.lax.iota(jnp.int32, 1 << hi)
    mlo = jnp.ones((1 << lo,), bool)
    mhi = jnp.ones((1 << hi,), bool)
    for b, s in bit_states:
        if b < lo:
            mlo = mlo & (bits_mod.bits_of(ilo, b) == int(s))
        else:
            mhi = mhi & (bits_mod.bits_of(ihi, b - lo) == int(s))
    return (mhi[:, None] & mlo[None, :]).astype(dtype)


def bit_canonical(n: int, q: int):
    """Per-amplitude value of qubit q's bit, broadcastable over the
    canonical (2^(n-14), 128, 128) view (block, sublane, lane axes)."""
    from ..utils import bits as bits_mod

    if q < _LANE_BITS:
        axis, size, b = 2, 128, q
    elif q < _BIG_N:
        axis, size, b = 1, 128, q - _LANE_BITS
    else:
        axis, size, b = 0, 1 << (n - _BIG_N), q - _BIG_N
    shape = [1, 1, 1]
    shape[axis] = size
    return bits_mod.bits_of(jax.lax.broadcasted_iota(jnp.int32, shape, axis),
                            b)


def bit_indicator_canonical(n: int, bit_states, dtype):
    """bit_indicator_2d over the canonical (2^(n-14), 128, 128) view."""
    ind = None
    for b, s in bit_states:
        eq = bit_canonical(n, b) == int(s)
        ind = eq if ind is None else (ind & eq)
    return ind.astype(dtype)


def _flip_bits_flat(amps, n: int, targets):
    """X on each target = index-space reversal.  Low targets (< 7) fold into
    one lane-matmul permutation; high targets are a swapped-halves
    concatenation per target — never a small-minor flip."""
    if not targets:
        return amps
    if n < _BIG_N:
        shape, axis_of = _interleaved(n, targets)
        view = amps.reshape(shape)
        return jnp.flip(view, axis=tuple(axis_of[t] for t in targets)).reshape(2, -1)
    low = tuple(t for t in targets if t < _LANE_BITS)
    if low:
        xmat = _embed_lane_from_traced(
            jnp.asarray(_x_product_np(low), amps.dtype), low
        )
        amps = _lane_matmul(amps, xmat)
    for t in targets:
        if t < _LANE_BITS:
            continue
        B = 1 << t
        v = amps.reshape(2, 1 << (n - 1 - t), 2 * B)
        amps = jnp.concatenate([v[:, :, B:], v[:, :, :B]], axis=2).reshape(2, -1)
    return amps


def _x_product_np(low_targets):
    """SoA (2, 2^k, 2^k) matrix of X on each of ``low_targets`` (np)."""
    k = len(low_targets)
    d = 1 << k
    idx = np.arange(d)
    flipped = idx
    for j in range(k):
        flipped = flipped ^ (1 << j)
    m = np.zeros((2, d, d), np.float64)
    m[0, flipped, idx] = 1.0
    return m


def _lane_rep(mat_soa):
    """(2,128,128) SoA -> (256,256) real right-multiplier for lane
    contraction of [re | im] concatenated rows (see ops/fused.py)."""
    ar, ai = mat_soa[0], mat_soa[1]
    top = jnp.concatenate([ar.T, ai.T], axis=1)
    bot = jnp.concatenate([-ai.T, ar.T], axis=1)
    return jnp.concatenate([top, bot], axis=0)


def _lane_matmul(amps, lane_mat_soa):
    """Apply a (2,128,128) SoA matrix to the lane bits (0..6) of the whole
    state: one MXU pass, minor dims (rows, 256) — never padded."""
    r = _lane_rep(lane_mat_soa)
    v = amps.reshape(2, -1, 1 << _LANE_BITS)
    xc = jnp.concatenate([v[0], v[1]], axis=-1)
    out = jax.lax.dot_general(
        xc, r, (((1,), (0,)), ((), ())),
        preferred_element_type=amps.dtype,
        precision=jax.lax.Precision.HIGHEST,
    )
    d = 1 << _LANE_BITS
    return jnp.stack([out[:, :d], out[:, d:]]).reshape(2, -1)


def _cslices(amps, n: int, t: int):
    """Contiguous halves of the state at bit t (t >= _LANE_BITS): two
    (2, A, 2^t) views — minor dim 2^t >= 128, layout-safe."""
    B = 1 << t
    v = amps.reshape(2, 1 << (n - 1 - t), 2 * B)
    return v[:, :, :B], v[:, :, B:]


def _apply_matrix_flat(amps, n: int, targets, msoa):
    """Complex k-qubit matrix (stacked SoA (2, 2^k, 2^k)) on flat (2, 2^n)
    SoA amps; targets[0] = least-significant matrix bit (reference
    convention).

    Layout-safe decomposition (n >= _BIG_N): recursive contiguous halving
    over targets >= 7 (slices and concats keep a >=2^7 minor axis), with the
    residual low-bit (< 7) block applied as one embedded 128x128 lane
    matmul per (i,j) high-block pair.  XLA-TPU materializes any reshape
    whose minor dim is tiny with (8,128)-tile padding — a 64x HBM blowup at
    26 qubits — so the einsum-over-bit-axes form is reserved for small n."""
    if n < _BIG_N:
        return _apply_matrix_small(amps, n, targets, cplx.real_matrix_rep(msoa))
    high = [t for t in targets if t >= _LANE_BITS]
    low = tuple(t for t in targets if t < _LANE_BITS)
    # matrix bit index of each target
    mbit = {t: j for j, t in enumerate(targets)}
    kl = len(low)
    dl = 1 << kl

    def sub_block(ih, jh):
        """SoA (2, 2^kl, 2^kl) sub-block for high-bit rows ih / cols jh."""
        row = 0
        col = 0
        for pos, t in enumerate(high):
            row |= ((ih >> pos) & 1) << mbit[t]
            col |= ((jh >> pos) & 1) << mbit[t]
        rows = [row | _scatter_low(i, low, mbit) for i in range(dl)]
        cols = [col | _scatter_low(j, low, mbit) for j in range(dl)]
        return msoa[:, jnp.asarray(rows)[:, None], jnp.asarray(cols)[None, :]]

    if not high:
        # pure low-bit gate: one lane matmul with the embedded matrix
        emb = _embed_lane_from_traced(msoa, low)
        return _lane_matmul(amps, emb)

    # Iterative slab decomposition: gather the 2^kh slabs by repeated
    # contiguous halving (descending bit order keeps positions valid).
    kh = len(high)
    highs_desc = sorted(high, reverse=True)
    slabs = [(amps, n)]
    for t in highs_desc:
        nxt = []
        for x, nn in slabs:
            a, b = _cslices(x, nn, t)
            nxt.append((a.reshape(2, -1), nn - 1))
            nxt.append((b.reshape(2, -1), nn - 1))
        slabs = nxt
    # slabs index: bit p of slab index = value of highs_desc[p] (MSB-first
    # split order); convert to high-bit tuple order (high[pos] = bit pos)
    def slab_hbits(si):
        h = 0
        for p, t in enumerate(highs_desc):
            bitval = (si >> (kh - 1 - p)) & 1
            h |= bitval << high.index(t)
        return h

    hmap = [slab_hbits(si) for si in range(1 << kh)]
    inv = [0] * (1 << kh)
    for si, hv in enumerate(hmap):
        inv[hv] = si
    outs = []
    for ih in range(1 << kh):
        acc = None
        for jh in range(1 << kh):
            xj = slabs[inv[jh]][0]
            blk = sub_block(ih, jh)
            if kl:
                emb = _embed_lane_from_traced(blk, low)
                term = _lane_matmul(xj, emb)
            else:
                term = cplx.cmul(xj, blk[0, 0, 0], blk[1, 0, 0])
            acc = term if acc is None else acc + term
        outs.append(acc)
    # reassemble in split order (inverse of halving): concat bottom-up
    level = [outs[hmap[si]] for si in range(1 << kh)]
    for t in reversed(highs_desc):
        nxt = []
        for i in range(0, len(level), 2):
            a, b = level[i], level[i + 1]
            nxt.append(jnp.concatenate(
                [a.reshape(2, -1, 1 << t), b.reshape(2, -1, 1 << t)], axis=2
            ).reshape(2, -1))
        level = nxt
    return level[0]


def _scatter_low(i, low, mbit):
    v = 0
    for pos, t in enumerate(low):
        v |= ((i >> pos) & 1) << mbit[t]
    return v


def _embed_lane_from_traced(mat_soa, bits):
    """Embed a traced SoA (2, 2^k, 2^k) matrix onto lane bits ``bits`` of
    the (2,128,128) lane space via precomputed static gather indices."""
    d = 1 << _LANE_BITS
    idx = np.arange(d)
    sub = np.zeros_like(idx)
    for j, b in enumerate(bits):
        sub |= ((idx >> b) & 1) << j
    rest = idx.copy()
    for b in bits:
        rest &= ~(1 << b)
    mask = jnp.asarray((rest[:, None] == rest[None, :]).astype(np.float32),
                       mat_soa.dtype)
    return mat_soa[:, sub[:, None], sub[None, :]] * mask


def _apply_matrix_small(amps, n: int, targets, rmat):
    """Original einsum path for small states (tests / CPU / n < 14)."""
    k = len(targets)
    if k == 1:
        t = targets[0]
        v = amps.reshape(2, 2 ** (n - 1 - t), 2, 2 ** t)
        # HIGHEST: stop TPU from doing the 2-wide contraction in bf16 —
        # it is bandwidth-bound, so full f32 costs nothing and keeps ~1e-7
        # gate error instead of ~1e-3 (observed with the default precision).
        out = jnp.einsum("cdab,dpbq->cpaq", rmat, v,
                         precision=jax.lax.Precision.HIGHEST)
        return out.reshape(2, -1)
    f, g = _targets_to_top_perms(n, targets)
    flat = _permute_impl(amps, n, f)
    xs = flat.reshape(2, 2 ** k, -1)
    out = jnp.einsum("cdij,djr->cir", rmat, xs,
                     precision=jax.lax.Precision.HIGHEST)
    return _permute_impl(out.reshape(2, -1), n, g)


def _targets_to_top_perms(n: int, targets):
    """(forward, inverse) qubit permutations placing ``targets`` at the top
    bit positions (targets[k-1] = MSB), everything else in original order."""
    order_fwd = list(reversed(targets)) + [
        q for q in range(n - 1, -1, -1) if q not in targets
    ]
    f = [0] * n  # f[output position] = input qubit
    for idx, q in enumerate(order_fwd):
        f[n - 1 - idx] = q
    g = [0] * n  # inverse permutation
    for p, q in enumerate(f):
        g[q] = p
    return tuple(f), tuple(g)


@partial(
    jax.jit,
    static_argnames=("num_qubits", "targets", "controls", "control_states"),
    donate_argnums=0,
)
def apply_matrix(
    amps,
    matrix,
    *,
    num_qubits: int,
    targets: Tuple[int, ...],
    controls: Tuple[int, ...] = (),
    control_states: Tuple[int, ...] = (),
):
    """Apply a dense 2^k x 2^k matrix to target qubits, optionally controlled.

    Covers the reference's unitary/compactUnitary/twoQubitUnitary/
    multiQubitUnitary and every multi(State)Controlled* variant
    (QuEST_cpu.c:1743-1985) as one kernel; ``control_states`` generalizes to
    control-on-zero (reference multiStateControlledUnitary, QuEST.h:3877).
    ``matrix`` is stacked SoA (2, 2^k, 2^k).
    """
    n = num_qubits
    in_shape = amps.shape
    matrix = jnp.asarray(matrix, amps.dtype)
    if controls:
        out = _apply_with_controls(
            amps, n, controls, control_states, targets,
            lambda sub, sub_n, sub_t: _apply_matrix_flat(sub, sub_n, sub_t, matrix),
        )
    else:
        out = _apply_matrix_flat(amps, n, targets, matrix)
    return out.reshape(in_shape)


def _apply_diagonal_flat(amps, n: int, targets, diag):
    """Multiply by diag[bits(targets)] — the phase-only kernel family.

    Big states: the factor is a sum of 2^k iota-bit indicators over a
    (2, 2^hi, 2^lo) view (both axes >= 128 — layout-safe, and XLA fuses the
    whole chain into the multiply); small states use an interleaved
    broadcast."""
    k = len(targets)
    if n < _BIG_N:
        shape, axis_of = _interleaved(n, targets)
        view = amps.reshape(shape)
        # diag bit j <-> targets[j]; reorder its axes to the (descending)
        # interleaved bit order, then stretch with singleton gap axes.
        dv = diag.reshape((2,) + (2,) * k)
        order = sorted(targets, reverse=True)
        dv = jnp.transpose(
            dv, (0,) + tuple(1 + (k - 1 - targets.index(t)) for t in order)
        )
        bshape = [1] * len(shape)
        for i, t in enumerate(order):
            bshape[axis_of[t]] = 2
        f_re = dv[0].reshape(bshape[1:])
        f_im = dv[1].reshape(bshape[1:])
        return cplx.cmul(view, f_re, f_im).reshape(2, -1)
    hi, lo = _split2(n)
    bit = partial(bit_2d, n)

    if k <= 6:
        f_re = jnp.zeros((1, 1), amps.dtype)
        f_im = jnp.zeros((1, 1), amps.dtype)
        for v in range(1 << k):
            ind = None
            for j, t in enumerate(targets):
                eq = bit(t) == ((v >> j) & 1)
                ind = eq if ind is None else (ind & eq)
            indf = ind.astype(amps.dtype)
            f_re = f_re + diag[0, v] * indf
            f_im = f_im + diag[1, v] * indf
    else:
        code = jnp.zeros((1, 1), jnp.int32)
        for j, t in enumerate(targets):
            code = code + (bit(t) << j)
        f_re = jnp.take(diag[0], code, axis=0)
        f_im = jnp.take(diag[1], code, axis=0)
    view = amps.reshape(2, 1 << hi, 1 << lo)
    return cplx.cmul(view, f_re, f_im).reshape(2, -1)


@partial(
    jax.jit,
    static_argnames=("num_qubits", "targets", "controls", "control_states"),
    donate_argnums=0,
)
def apply_diagonal(
    amps,
    diag,
    *,
    num_qubits: int,
    targets: Tuple[int, ...],
    controls: Tuple[int, ...] = (),
    control_states: Tuple[int, ...] = (),
):
    """Multiply amplitudes by ``diag[bits(targets)]`` — the phase-only kernel
    family (reference phaseShiftByTerm/multiControlledPhaseShift/phase-flip,
    QuEST_cpu.c:3146-3361) which needs no amplitude pairing.  ``diag`` is
    stacked SoA (2, 2^k), exponentiated host-side — no transcendental runs
    per amplitude."""
    n = num_qubits
    in_shape = amps.shape
    diag = jnp.asarray(diag, amps.dtype)
    if controls:
        out = _apply_with_controls(
            amps, n, controls, control_states, targets,
            lambda sub, sub_n, sub_t: _apply_diagonal_flat(sub, sub_n, sub_t, diag),
        )
    else:
        out = _apply_diagonal_flat(amps, n, targets, diag)
    return out.reshape(in_shape)


@partial(
    jax.jit,
    static_argnames=("num_qubits", "qubits", "controls", "control_states"),
    donate_argnums=0,
)
def apply_parity_phase(
    amps,
    theta,
    *,
    num_qubits: int,
    qubits: Tuple[int, ...],
    controls: Tuple[int, ...] = (),
    control_states: Tuple[int, ...] = (),
):
    """exp(-i theta/2 * Z x Z ... Z) over a qubit subset — reference
    multiRotateZ / multiControlledMultiRotateZ (QuEST_cpu.c:3268-3361)."""
    n = num_qubits
    theta = jnp.asarray(theta, amps.dtype)

    def phased(sub, sub_n, sub_qubits):
        ang = -0.5 * theta
        if sub_n <= 31:
            # flat sign: partitions along the sharded amplitude axis with
            # zero communication (see parity_sign_flat); flatten first so a
            # canonical 4-d view input broadcasts correctly
            sub = sub.reshape(2, -1)
            s = parity_sign_flat(sub_n, sub_qubits, amps.dtype)
            return cplx.cmul(sub, jnp.cos(ang), jnp.sin(ang) * s)
        s = parity_sign_2d(sub_n, sub_qubits, amps.dtype)
        view = sub.reshape(2, s.shape[0], s.shape[1])
        # e^{i ang s} = cos(ang) + i s sin(ang) (cos even, sin odd in s)
        out = cplx.cmul(view, jnp.cos(ang), jnp.sin(ang) * s)
        return out.reshape(2, -1)

    if controls:
        out = _apply_with_controls(
            amps, n, controls, control_states, qubits,
            lambda sub, sub_n, sub_q: phased(sub, sub_n, sub_q),
        )
    else:
        out = phased(amps, n, qubits)
    return out.reshape(amps.shape)


@partial(jax.jit, static_argnames=("num_qubits", "targets", "controls", "control_states"), donate_argnums=0)
def apply_multi_qubit_not(
    amps,
    *,
    num_qubits: int,
    targets: Tuple[int, ...],
    controls: Tuple[int, ...] = (),
    control_states: Tuple[int, ...] = (),
):
    """X on several targets at once (reference multiControlledMultiQubitNot,
    QuEST.h:2914).  Pure index permutation: axis reversal per target —
    no arithmetic at all, where the reference does an amplitude-pair swap
    loop (QuEST_cpu.c:2554-2660)."""
    n = num_qubits
    if controls:
        out = _apply_with_controls(
            amps, n, controls, control_states, targets,
            lambda sub, sub_n, sub_t: _flip_bits_flat(sub, sub_n, sub_t),
        )
    else:
        out = _flip_bits_flat(amps, n, targets)
    return out.reshape(amps.shape)


@partial(jax.jit, static_argnames=("num_qubits", "perm"), donate_argnums=0)
def permute_qubits(amps, *, num_qubits: int, perm: Tuple[int, ...]):
    """Relabel qubits in ONE transpose pass: output qubit q holds what input
    qubit perm[q] held.  Generalizes swap_qubit_amps to arbitrary
    permutations — the single-chip analogue of the reference's distributed
    SWAP-relocalization (QuEST_cpu_distributed.c:1503-1545), used by the
    fused-circuit scheduler (circuit.py) to rotate high qubits into the
    Pallas cluster window at one-HBM-pass cost.

    Contiguous bit runs are coalesced into single axes so the transpose XLA
    sees is low-rank (a rank-(n+1) transpose makes the TPU backend's compile
    time explode past n≈18); permutations that still would not coalesce are
    decomposed into pairwise swaps, each itself a rank-<=6 transpose."""
    return _permute_impl(amps, num_qubits, perm).reshape(amps.shape)


def _permute_impl(amps, n: int, perm: Tuple[int, ...]):
    order = tuple(perm[n - 1 - i] for i in range(n))  # input qubits, MSB->LSB
    runs = _coalesce_runs(order)
    if len(runs) <= _MAX_TRANSPOSE_RANK:
        return _transpose_runs(amps, runs)
    # Fallback: selection-sort into place via pairwise swaps.  cur[q] = input
    # qubit currently at position q; each swap is a cheap coalesced transpose.
    cur = list(range(n))
    for q in range(n):
        if cur[q] != perm[q]:
            j = cur.index(perm[q])
            amps = _swap_impl(amps, n, q, j)
            cur[q], cur[j] = cur[j], cur[q]
    return amps


def _coalesce_runs(order):
    """Merge descending runs of ``order`` (input qubits listed MSB->LSB).
    A descending run hi..lo is a contiguous little-endian bit block, hence a
    single axis of the input layout.  Returns [(hi, len), ...] in output
    order; the runs partition 0..n-1 into disjoint bit intervals."""
    runs = []
    hi = cur = order[0]
    ln = 1
    for q in order[1:]:
        if q == cur - 1:
            cur = q
            ln += 1
        else:
            runs.append((hi, ln))
            hi = cur = q
            ln = 1
    runs.append((hi, ln))
    return runs


# Above this transpose rank, fall back to pairwise swaps (XLA TPU compile
# time grows super-linearly in transpose rank; <=9 axes compiles in ms).
_MAX_TRANSPOSE_RANK = 8


def _transpose_runs(amps, runs):
    """Transpose coalesced bit runs: reshape to one axis per run (input
    order = descending bit position), permute to output order, flatten."""
    in_order = sorted(runs, key=lambda r: -r[0])
    shape = (2,) + tuple(1 << ln for _, ln in in_order)
    axis_of = {r: i + 1 for i, r in enumerate(in_order)}
    axes = (0,) + tuple(axis_of[r] for r in runs)
    return jnp.transpose(amps.reshape(shape), axes).reshape(2, -1)


@partial(jax.jit, static_argnames=("num_qubits", "qb1", "qb2"), donate_argnums=0)
def swap_qubit_amps(amps, *, num_qubits: int, qb1: int, qb2: int):
    """SWAP gate = transpose of two index axes (reference swapQubitAmps,
    QuEST_cpu.c:3882-3964, which the distributed layer also uses for
    relocalization, QuEST_cpu_distributed.c:1447-1545).  Expressed as a
    rank-6 transpose over coalesced bit blocks, independent of n."""
    return _swap_impl(amps, num_qubits, qb1, qb2).reshape(amps.shape)


_SWAP_SOA = np.zeros((2, 4, 4))
_SWAP_SOA[0] = np.eye(4)[[0, 2, 1, 3]]


def _swap_impl(amps, n: int, qb1: int, qb2: int):
    i, j = max(qb1, qb2), min(qb1, qb2)
    if i == j:
        return amps
    if n >= _BIG_N:
        # A low-bit transpose would materialize with a tiny minor dim
        # (tile-padded 64x); the dense-gate decomposition is one fused pass.
        return _apply_matrix_flat(
            amps, n, (j, i), jnp.asarray(_SWAP_SOA, amps.dtype)
        )
    view = amps.reshape(2, 1 << (n - 1 - i), 2, 1 << (i - j - 1), 2, 1 << j)
    return jnp.transpose(view, (0, 1, 4, 3, 2, 5)).reshape(2, -1)


@partial(jax.jit, static_argnames=("num_qubits", "a", "b", "m"), donate_argnums=0)
def swap_bit_segments(amps, *, num_qubits: int, a: int, b: int, m: int):
    """Exchange the m-bit index segments [a, a+m) and [b, b+m) (a >= b+m).

    This is the TPU-native relocalization move used by the circuit
    scheduler: with b >= 7 the transpose keeps the 2^b >= 128 lane block as
    its minor axis and the 2^m segment as second-minor, so XLA's (8,128)
    tiling needs no padding (unlike single-bit swaps).  Plays the role of
    the reference's SWAP-relocalization of high qubits
    (QuEST_cpu_distributed.c:1503-1545), but moves a whole page per pass."""
    n = num_qubits
    assert a >= b + m, (a, b, m)
    view = amps.reshape(
        2, 1 << (n - a - m), 1 << m, 1 << (a - b - m), 1 << m, 1 << b
    )
    return jnp.transpose(view, (0, 1, 4, 3, 2, 5)).reshape(amps.shape)


# Gather field width cap for apply_index_permutation: past this extent the
# static index table (2^width entries) stops being worth materializing and
# the op falls back to the exact 0/1 permutation-matrix pass.
_GATHER_FIELD_MAX_BITS = 16


@partial(jax.jit, static_argnames=("num_qubits", "targets", "pi"), donate_argnums=0)
def apply_index_permutation(
    amps, *, num_qubits: int, targets: Tuple[int, ...], pi: Tuple[int, ...]
):
    """General basis-index permutation on ``targets``: the new amplitude at
    target-field sub-index i is the old amplitude at sub-index ``pi[i]``
    (``new[i] = old[pi[i]]``, matching circuit.classify_permutation_gate's
    row convention).  This is the gather lowering of the permutation gate
    family (circuit.py §28) — CNOT/Toffoli/MCX products execute as ONE
    static gather pass instead of a cluster matmul, and the move is
    bit-exact (amplitudes are relocated, never recombined).

    Layout: the gather runs along a contiguous bit field [lo, hi] covering
    the targets, viewed as (2, pre, 2^field, 2^lo).  At n >= _BIG_N a field
    reaching below the 128-lane block is extended down to bit 0 so the
    gathered axis stays tile-wide (the tiny-minor rule every kernel here
    follows); fields wider than _GATHER_FIELD_MAX_BITS fall back to the
    exact 0/1 permutation matrix through _apply_matrix_flat (single gates
    have <= 7 targets, so the matrix stays <= 128x128)."""
    n = num_qubits
    lo, hi = min(targets), max(targets)
    if n >= _BIG_N and lo < _LANE_BITS:
        lo = 0
        hi = max(hi, _LANE_BITS - 1)
    if hi + 1 - lo > _GATHER_FIELD_MAX_BITS:
        d = 1 << len(targets)
        m = np.zeros((2, d, d), np.float64)
        m[0, np.arange(d), np.asarray(pi, dtype=np.int64)] = 1.0
        return _apply_matrix_flat(
            amps, n, tuple(targets), jnp.asarray(m, amps.dtype)
        ).reshape(amps.shape)
    span = hi + 1 - lo
    d = 1 << span
    idx = np.arange(d)
    sub = np.zeros(d, dtype=np.int64)
    for b, t in enumerate(targets):
        sub |= ((idx >> (t - lo)) & 1) << b
    mapped = np.asarray(pi, dtype=np.int64)[sub]
    lifted = idx.copy()
    for t in targets:
        lifted &= ~(1 << (t - lo))
    for b, t in enumerate(targets):
        lifted |= ((mapped >> b) & 1) << (t - lo)
    view = amps.reshape(2, 1 << (n - hi - 1), d, 1 << lo)
    out = view[:, :, jnp.asarray(lifted), :]
    return out.reshape(amps.shape)


# ---------------------------------------------------------------------------
# State initialisation (reference QuEST_cpu.c:1453-1729)
# ---------------------------------------------------------------------------


def _filled(shape, dtype, kind: str, x):
    """A ``kind`` state of ``shape``: "basis" puts 1 at flat amplitude
    index ``x`` (traced), "plus" fills the real channel with ``x``,
    "blank" is all zero.  4-d shapes are the canonical view
    (qureg.device_amps_shape), addressed (block, sublane, lane)."""
    z = jnp.zeros(shape, dtype)
    if kind == "basis":
        if len(shape) == 4:
            idx = (0, x >> 14, (x >> _LANE_BITS) & 127, x & 127)
        else:
            idx = (0, x)
        return z.at[idx].set(1)
    if kind == "plus":
        return z.at[0].set(jnp.asarray(x, dtype))
    return z


@lru_cache(maxsize=None)
def _fill_fn(shape, dtype, kind: str, sharding):
    return jax.jit(lambda x: _filled(shape, dtype, kind, x),
                   out_shardings=sharding)


def fill_state(shape, dtype, sharding, kind: str, x=0):
    """Build a "basis" / "plus" / "blank" state (see _filled) directly on
    the device in ``shape`` and ``sharding`` — no host array, no flat
    intermediate."""
    return _fill_fn(tuple(int(d) for d in shape), np.dtype(dtype), kind,
                    sharding)(x)


def init_blank_state(num_amps: int, dtype):
    return jnp.zeros((2, num_amps), dtype=dtype)


def init_zero_state(num_amps: int, dtype):
    return jnp.zeros((2, num_amps), dtype=dtype).at[0, 0].set(1.0)


def init_plus_state(num_amps: int, dtype):
    norm = 1.0 / math.sqrt(num_amps)
    return jnp.stack(
        [jnp.full((num_amps,), norm, dtype=dtype), jnp.zeros((num_amps,), dtype=dtype)]
    )


def init_classical_state(num_amps: int, state_index: int, dtype):
    return jnp.zeros((2, num_amps), dtype=dtype).at[0, state_index].set(1.0)


def init_sparse_state(num_amps: int, indices, res, ims, dtype):
    """Scatter k nonzero amplitudes into an otherwise-zero state — the
    dense-side materialization of sparse state preparation (circuit.py
    §28, arXiv:2504.08705): cost scales with k for the scatter plus one
    zeros fill, never with explicit per-amplitude host uploads."""
    idx = jnp.asarray(np.asarray(indices, dtype=np.int64))
    re = jnp.asarray(res, dtype=dtype)
    im = jnp.asarray(ims, dtype=dtype)
    return (jnp.zeros((2, num_amps), dtype=dtype)
            .at[0, idx].set(re).at[1, idx].set(im))


def init_debug_state(num_amps: int, dtype):
    """amp_k = (2k mod 10)/10 + i((2k+1) mod 10)/10 — reference
    initStateDebug (QuEST_cpu.c:1646, QuEST_debug.h)."""
    k = jnp.arange(num_amps, dtype=dtype)
    re = ((2.0 * k) % 10.0) / 10.0
    im = ((2.0 * k + 1.0) % 10.0) / 10.0
    return jnp.stack([re, im])


def init_classical_density(num_qubits: int, state_index: int, dtype):
    """rho = |s><s| as a flattened 2n-qubit vector (column-major,
    ket = low bits; reference densmatr_initClassicalState)."""
    dim = 1 << num_qubits
    idx = state_index + state_index * dim
    return jnp.zeros((2, dim * dim), dtype=dtype).at[0, idx].set(1.0)


def init_plus_density(num_qubits: int, dtype):
    dim = 1 << num_qubits
    return jnp.stack(
        [
            jnp.full((dim * dim,), 1.0 / dim, dtype=dtype),
            jnp.zeros((dim * dim,), dtype=dtype),
        ]
    )


# ---------------------------------------------------------------------------
# Collapse / renormalisation (reference QuEST_cpu.c:3727-3880, 785-860)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("num_qubits", "target", "outcome"), donate_argnums=0)
def collapse_statevec(amps, prob, *, num_qubits: int, target: int, outcome: int):
    """Zero the discarded half, scale kept half by 1/sqrt(prob) — one fused
    broadcast multiply instead of the reference's two-branch loop
    (statevec_collapseToKnownProbOutcomeLocal, QuEST_cpu.c:3727-3815)."""
    n = num_qubits
    scale = (1.0 / jnp.sqrt(jnp.asarray(prob, amps.dtype)))
    ind = bit_indicator_2d(n, ((target, outcome),), amps.dtype)
    view = amps.reshape(2, ind.shape[0], ind.shape[1])
    return (view * (scale * ind)[None]).reshape(amps.shape)


@partial(jax.jit, static_argnames=("num_qubits", "target", "outcome"), donate_argnums=0)
def collapse_density(amps, prob, *, num_qubits: int, target: int, outcome: int):
    """rho: zero every element whose ket- or bra-target bit differs from the
    outcome; renormalise by 1/prob (densmatr_collapseToKnownProbOutcome,
    QuEST_cpu.c:785-860)."""
    n = num_qubits
    nn = 2 * n
    ind = bit_indicator_2d(
        nn, ((target, outcome), (target + n, outcome)), amps.dtype
    )
    view = amps.reshape(2, ind.shape[0], ind.shape[1])
    return (view * (ind / jnp.asarray(prob, amps.dtype))[None]).reshape(2, -1)


@jax.jit
def set_weighted_qureg(amps_out, amps1, amps2, facs):
    """out = f1*q1 + f2*q2 + fOut*out (reference setWeightedQureg,
    QuEST_cpu.c:3965-4006).  ``facs`` is stacked (2, 3): the three complex
    factors (fOut, f1, f2).  Not donated: callers may alias out with
    q1/q2 (donating a buffer that is ALSO passed as another live argument
    is undefined); the API layer routes the common non-aliased case
    through set_weighted_qureg_donated instead."""
    out = cplx.cmul(amps_out, facs[0, 0], facs[1, 0])
    out = out + cplx.cmul(amps1, facs[0, 1], facs[1, 1])
    out = out + cplx.cmul(amps2, facs[0, 2], facs[1, 2])
    return out


@partial(jax.jit, donate_argnums=0)
def set_weighted_qureg_donated(amps_out, amps1, amps2, facs):
    """set_weighted_qureg with ``out`` donated — the in-place form for the
    (typical) call where ``out`` is a distinct register from q1/q2, saving
    one full state of HBM on the three-register combine (donation audit,
    tests/test_donation.py)."""
    out = cplx.cmul(amps_out, facs[0, 0], facs[1, 0])
    out = out + cplx.cmul(amps1, facs[0, 1], facs[1, 1])
    out = out + cplx.cmul(amps2, facs[0, 2], facs[1, 2])
    return out


@partial(jax.jit, donate_argnums=0)
def apply_full_diagonal(amps, op_real, op_imag):
    """Elementwise multiply by a full-Hilbert diagonal operator given as
    separate real/imag vectors (statevec_applyDiagonalOp,
    QuEST_cpu.c:4007-4041)."""
    return cplx.cmul(amps, op_real.astype(amps.dtype), op_imag.astype(amps.dtype))


@partial(jax.jit, static_argnames=("num_qubits", "target", "base", "conj"),
         donate_argnums=0)
def apply_qft_ladder(amps, *, num_qubits: int, target: int, base: int = 0,
                     conj: bool = False):
    """One QFT layer in ONE fused elementwise pass: Hadamard on ``target``
    followed by the whole controlled-phase ladder against the contiguous
    qubits [base, target), i.e. diag(1, e^{i*pi*low/2^(target-base)}) on the
    target with low = the integer held in those qubits.  The reference
    builds the same layer from one H sweep plus a SCALED_PRODUCT phase
    sweep (agnostic_applyQFT, QuEST_common.c:836-898) — two HBM passes and
    no fusion; here the pair combine and the index-derived phase fuse into
    a single XLA program.  ``base`` > 0 serves the density-matrix bra twin
    (qubits shifted by numQubits); ``conj`` negates the ladder phases.

    The phase exp(i*pi*low/2^tr) factorizes over 7-bit chunks of ``low``
    into HOST-precomputed tables of <= 128 entries each (it is an
    exponential of a sum of per-bit contributions), applied as broadcast
    complex multiplies.  vs the previous on-device recursive-doubling
    table: compile time for a full 26q QFT dropped from ~300 s (26
    unrolled concat chains blew up XLA) to seconds, and for tr >= 10 the
    view's two minor axes are (bits 7-13 chunk, bits 0-6 chunk) —
    layout-identical to the canonical window views (see ops/fused.py), so
    consecutive ladder passes exchange state via free bitcasts instead of
    ~4 ms retile copies.
    """
    n, t = num_qubits, target
    from . import fused as _fused

    if _fused.qft_ladder_supported(amps.dtype, n, t, base):
        # one Pallas pass (canonical layout, pair halves co-resident):
        # ~3x the XLA elementwise formulation, which splits into several
        # fusions around the pair-axis slice/stack
        return _fused.apply_qft_ladder_pallas(
            amps, num_qubits=n, target=t, conj=conj)
    tr = t - base
    lo = 1 << base         # untouched low axis (bra-twin case)
    hi = 1 << (n - 1 - t)
    dt = amps.dtype
    sgn = -1.0 if conj else 1.0
    inv = jnp.asarray(1.0 / math.sqrt(2.0), dt)

    if tr < 10 and base == 0:
        # small ladder: one table, simple view.  The canonical minor-axes
        # split (bits 7-13, bits 0-6) needs the second-minor axis to span
        # >= 8 values of bits 7-9, i.e. tr >= 10; below that the view
        # cannot be layout-compatible anyway, so keep it flat.
        widths = [tr]
    else:
        widths = []        # 7-bit chunks from the low end
        p = 0
        while p < tr:
            widths.append(min(7, tr - p))
            p += 7
    tabs = []
    p = 0
    for w in widths:
        j = np.arange(1 << w, dtype=np.float64)
        ang = sgn * np.pi * (j * (1 << p)) / (1 << tr)
        tabs.append((np.cos(ang).astype(dt), np.sin(ang).astype(dt)))
        p += w
    # axis order after [2, hi, 2(pair)]: highest chunk first, lowest chunk
    # last, then the untouched lo axis (if any)
    factor_dims = [1 << w for w in reversed(widths)]
    shape = [2, hi, 2] + factor_dims + ([lo] if base else [])
    v = amps.reshape(shape)
    x0r, x0i = v[0, :, 0], v[1, :, 0]
    x1r, x1i = v[0, :, 1], v[1, :, 1]
    y0r, y0i = (x0r + x1r) * inv, (x0i + x1i) * inv
    y1r, y1i = (x0r - x1r) * inv, (x0i - x1i) * inv
    ntail = len(widths) + (1 if base else 0)   # axes after hi in y*
    for ci, (w, (tc, ts)) in enumerate(zip(widths, tabs)):
        axis_from_end = (1 if base else 0) + ci
        bshape = [1] * (1 + ntail)
        bshape[len(bshape) - 1 - axis_from_end] = 1 << w
        pr = jnp.asarray(tc).reshape(bshape)
        pi_ = jnp.asarray(ts).reshape(bshape)
        y1r, y1i = pr * y1r - pi_ * y1i, pr * y1i + pi_ * y1r
    out = jnp.stack([
        jnp.stack([y0r, y1r], axis=1),
        jnp.stack([y0i, y1i], axis=1),
    ])
    return out.reshape(amps.shape)
