"""Fused cluster-pair Pallas kernel: many gates, ONE pass over HBM.

The reference applies one kernel sweep per gate (QuEST.c dispatch; e.g.
compactUnitaryLocal, QuEST/src/CPU/QuEST_cpu.c:1743-1800), so a depth-d
circuit costs d full passes over the 2^n-amplitude array.  On TPU the state
sweep is HBM-bandwidth-bound, so the win is to apply MANY gates per pass.

Design: the flat amplitude index is split little-endian as

    [ qubits 14..n-1 | qubits 7..13 | qubits 0..6 ]
         grid rows       sublanes       lanes

so a (2, R, 128, 128) VMEM block holds R*16384 amplitudes with qubits 0..6
as the lane dimension and 7..13 as the sublane dimension — both exactly
TPU-tile-aligned for f32.  Any sequence of gates confined to qubits 0..6
multiplies into ONE 128x128 "cluster" matrix A (likewise 7..13 into B), and
the kernel applies A (right-contraction over lanes) and B (left-contraction
over sublanes) to each block while it is VMEM-resident: two MXU matmuls,
one HBM read + one write, regardless of how many gates were folded in.

Complex arithmetic stays SoA (ops/cplx.py).  The cluster kernels
concatenate the two channels along the contracted axis and make each
cluster matrix the 256x256 real representation [[Re,Im],[-Im,Re]] (lanes)
/ [[Re,-Im],[Im,Re]] (sublanes), one real matmul a side; the window pass
takes three 128-wide real products a side instead (_window_block_body).

Gates on qubits >= 14 are handled by the circuit scheduler (circuit.py)
with a one-pass axis permutation (kernels.permute_qubits) that relabels
high qubits into the cluster window — the single-chip analogue of the
reference's distributed SWAP-relocalization
(QuEST/src/CPU/QuEST_cpu_distributed.c:1503-1545).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE_QUBITS = 7          # qubits 0..6  -> lane dim (128)
SUBLANE_QUBITS = 7       # qubits 7..13 -> sublane dim (128)
CLUSTER_QUBITS = LANE_QUBITS + SUBLANE_QUBITS   # 14
CLUSTER_DIM = 128
BLOCK_AMPS = CLUSTER_DIM * CLUSTER_DIM           # 16384


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _resolve_interpret(interpret, amps) -> bool:
    """Pallas only on real TPU AND a Mosaic-supported dtype: f64 dots raise
    NotImplementedError in the Mosaic lowering, so double-precision states
    (set_precision(2), the reference's default qreal) run the same kernel
    bodies in interpret mode — plain XLA ops, which the TPU executes via
    its software-f64 path."""
    if interpret is not None:
        return interpret
    return _interpret_default() or amps.dtype == jnp.float64


# MXU contraction precision for the cluster/window matmuls.  f32 inputs on
# TPU decompose into bf16 MXU passes: HIGHEST = 6 passes (full f32
# accuracy), DEFAULT = 1 pass (bf16, ~1e-3 — too coarse for amplitudes).
# The window pass is MXU-bound at HIGHEST (measured on v5e: rank-1 A+B
# 4.45 ms vs a 1.3 ms HBM floor at 2^26 amps; rank-4 18.6 ms), so each
# complex side product takes three real products, not four (Gauss's form,
# _window_block_body): at 2^30 amps on one v5e a rank-1 dual pass fell
# from 71.0 to 59.6 ms and a B-only pass from 44.1 to 35.4 ms, against a
# 21.0 ms HBM floor (scripts/window_pass_timing.py).  The
# "bf16_3x" mode implements the 3-pass split Mosaic's dot lowering lacks
# (Precision.HIGH raises NotImplementedError): x@m = xh@mh + xh@ml + xl@mh
# with xh/xl (mh/ml) the bf16 hi/lo halves of each f32 operand and f32
# accumulation.  Dropped term xl@ml is O(2^-16) relative — inside the f32
# REAL_EPS = 1e-5 tolerance the reference's single-precision mode already
# grants (QuEST_precision.h:34).
_PRECISIONS = {
    "highest": jax.lax.Precision.HIGHEST,
    "bf16_3x": "bf16_3x",
    "default": jax.lax.Precision.DEFAULT,
}
_CONFIG = {"precision": "highest"}


def set_matmul_precision(name: str) -> None:
    """Set the window-kernel contraction precision ("highest"|"bf16_3x"|
    "default").  Callers that cache compiled plans key on the name via
    matmul_precision_name()."""
    if name not in _PRECISIONS:
        raise ValueError(f"unknown precision {name!r}; use one of {list(_PRECISIONS)}")
    _CONFIG["precision"] = name


def matmul_precision_name() -> str:
    return _CONFIG["precision"]


def _resolve_precision(name):
    return _PRECISIONS[name or _CONFIG["precision"]]


def _kdot(x, m, dims, prec):
    """dot_general at the requested precision; "bf16_3x" is the manual
    3-pass bf16 split (f64 inputs fall back to HIGHEST — the split is an
    f32 decomposition)."""
    if prec == "bf16_3x" and x.dtype == jnp.float32:
        f32 = jnp.float32
        xh = x.astype(jnp.bfloat16)
        xl = (x - xh.astype(f32)).astype(jnp.bfloat16)
        mh = m.astype(jnp.bfloat16)
        ml = (m - mh.astype(f32)).astype(jnp.bfloat16)
        d = partial(jax.lax.dot_general, dimension_numbers=dims,
                    preferred_element_type=f32)
        return d(xh, mh) + d(xh, ml) + d(xl, mh)
    if prec == "bf16_3x":
        prec = jax.lax.Precision.HIGHEST
    return jax.lax.dot_general(
        x, m, dimension_numbers=dims,
        preferred_element_type=x.dtype, precision=prec,
    )


# Largest segment width whose 2^m-block super-block (plus the kernel's
# transpose/concat temporaries) fits in the 16 MB scoped VMEM for the fused
# swap+cluster kernel (8 blocks = 1 MB per buffer; m=4 overflows).
MAX_FUSED_SWAP_M = 3


def lane_real_rep(mat_soa):
    """(2,128,128) SoA cluster matrix -> (256,256) real right-multiplier.

    For x = [xr | xi] concatenated on the lane axis, x @ M computes the
    complex product U x with U acting on the lane index:
    M = [[Ar^T, Ai^T], [-Ai^T, Ar^T]].
    """
    ar, ai = mat_soa[0], mat_soa[1]
    top = jnp.concatenate([ar.T, ai.T], axis=1)
    bot = jnp.concatenate([-ai.T, ar.T], axis=1)
    return jnp.concatenate([top, bot], axis=0)


def sublane_real_rep(mat_soa):
    """(2,128,128) SoA cluster matrix -> (256,256) real left-multiplier.

    For y = [yr ; yi] stacked on the sublane axis, M @ y computes the
    complex product: M = [[Br, -Bi], [Bi, Br]].
    """
    br, bi = mat_soa[0], mat_soa[1]
    top = jnp.concatenate([br, -bi], axis=1)
    bot = jnp.concatenate([bi, br], axis=1)
    return jnp.concatenate([top, bot], axis=0)


def _cluster_kernel_rank(rank, prec=jax.lax.Precision.HIGHEST):
    """Kernel applying sum_r B_r X A_r to each VMEM-resident block: the
    operator on the 14-qubit window is a rank-``rank`` sum of (sublane op)
    x (lane op) Kronecker products.  rank=1 is the plain cluster pair;
    rank=4 absorbs one lane-x-sublane-crossing 2q gate (circuit.py folds
    the |a><b| (x) U_ab decomposition).  All matmuls hit the MXU; one HBM
    read + one write regardless of rank."""

    def kernel(a_ref, ma_ref, mb_ref, o_ref):
        x = a_ref[...]                  # (2, R, 128, 128)  R = block rows
        xr, xi = x[0], x[1]
        xc0 = jnp.concatenate([xr, xi], axis=-1)         # (R, 128, 256)
        acc = None
        for r in range(rank):
            # lane op: right-contract lanes with the 256x256 real rep
            xc = _kdot(xc0, ma_ref[r], (((2,), (0,)), ((), ())), prec)                                            # (R, 128, 256)
            yr, yi = xc[..., :CLUSTER_DIM], xc[..., CLUSTER_DIM:]
            # sublane op: left-contract sublanes
            yc = jnp.concatenate([yr, yi], axis=1)       # (R, 256, 128)
            out = _kdot(mb_ref[r], yc, (((1,), (1,)), ((), ())), prec)                                            # (256, R, 128)
            acc = out if acc is None else acc + out
        acc = jnp.moveaxis(acc, 0, 1)                    # (R, 256, 128)
        o_ref[...] = jnp.stack(
            [acc[:, :CLUSTER_DIM], acc[:, CLUSTER_DIM:]], axis=0
        )

    return kernel


@partial(jax.jit, static_argnames=("num_qubits", "block_rows", "interpret",
                                   "precision"),
         donate_argnums=0)
def _apply_cluster_pair_jit(
    amps,
    mat_a,
    mat_b,
    *,
    num_qubits: int,
    block_rows: int = 8,
    interpret: bool | None = None,
    precision: str | None = None,
):
    """Apply 7-qubit cluster unitaries A (qubits 0-6) and B (qubits 7-13)
    to the whole state in one HBM pass.

    ``amps``: SoA (2, 2^n), n >= 14.  ``mat_a``/``mat_b``: stacked SoA
    (2, 128, 128) — products of all folded gates, built by circuit.py.
    """
    return _apply_cluster_stack_jit(
        amps, mat_a[None], mat_b[None], num_qubits=num_qubits,
        block_rows=block_rows, interpret=interpret, precision=precision,
    )


def _cluster_swap_kernel(rank, m, b_local, prec=jax.lax.Precision.HIGHEST):
    """Kernel fusing a bit-segment swap [h, h+m) <-> [b, b+m) (b in the
    sublane range, h in the grid range) with a rank-``rank`` cluster pass:
    the 2^m source blocks of the swap arrive as one VMEM super-block, the
    sublane/grid bit exchange is a free in-VMEM transpose, and the cluster
    matmuls run on the swapped data — one HBM read + write for what was
    previously a transpose pass plus a cluster pass."""
    M = 1 << m

    def kernel(a_ref, ma_ref, mb_ref, o_ref):
        x = a_ref[...]                   # (2, 1, M, 1, 128, 128)
        x = x.reshape(2, M, CLUSTER_DIM, CLUSTER_DIM)
        rhi = CLUSTER_DIM >> (b_local + m)
        rlo = 1 << b_local
        y = x.reshape(2, M, rhi, M, rlo, CLUSTER_DIM)
        y = jnp.transpose(y, (0, 3, 2, 1, 4, 5))   # grid bits <-> sublane bits
        x = y.reshape(2, M, CLUSTER_DIM, CLUSTER_DIM)
        xr, xi = x[0], x[1]
        xc0 = jnp.concatenate([xr, xi], axis=-1)
        acc = None
        for r in range(rank):
            xc = _kdot(xc0, ma_ref[r], (((2,), (0,)), ((), ())), prec)
            yr, yi = xc[..., :CLUSTER_DIM], xc[..., CLUSTER_DIM:]
            yc = jnp.concatenate([yr, yi], axis=1)
            out = _kdot(mb_ref[r], yc, (((1,), (1,)), ((), ())), prec)
            acc = out if acc is None else acc + out
        acc = jnp.moveaxis(acc, 0, 1)
        out = jnp.stack([acc[:, :CLUSTER_DIM], acc[:, CLUSTER_DIM:]], axis=0)
        o_ref[...] = out.reshape(2, 1, M, 1, CLUSTER_DIM, CLUSTER_DIM)

    return kernel


@partial(jax.jit,
         static_argnames=("num_qubits", "h", "b", "m", "interpret",
                          "precision"),
         donate_argnums=0)
def _apply_swap_cluster_stack_jit(
    amps,
    mats_a,
    mats_b,
    *,
    num_qubits: int,
    h: int,
    b: int,
    m: int,
    interpret: bool | None = None,
    precision: str | None = None,
):
    """Segment swap [h, h+m) <-> [b, b+m) followed by the rank-R window
    operator sum_r B_r (x) A_r, in ONE HBM pass (see _cluster_swap_kernel).
    Requires h >= 14, 7 <= b and b + m <= 14, m <= MAX_FUSED_SWAP_M.
    Result shape = input shape."""
    n = num_qubits
    in_shape = amps.shape
    interpret = _resolve_interpret(interpret, amps)
    rank = mats_a.shape[0]
    M = 1 << m
    nb = 1 << (n - CLUSTER_QUBITS)
    glo = 1 << (h - CLUSTER_QUBITS)
    ghi = nb // (glo * M)
    ma = jax.vmap(lane_real_rep)(jnp.asarray(mats_a, amps.dtype))
    mb = jax.vmap(sublane_real_rep)(jnp.asarray(mats_b, amps.dtype))
    view = amps.reshape(2, ghi, M, glo, CLUSTER_DIM, CLUSTER_DIM)
    out = pl.pallas_call(
        _cluster_swap_kernel(rank, m, b - LANE_QUBITS,
                             _resolve_precision(precision)),
        grid=(ghi, glo),
        in_specs=[
            pl.BlockSpec((2, 1, M, 1, CLUSTER_DIM, CLUSTER_DIM),
                         lambda i, j: (0, i, 0, j, 0, 0)),
            pl.BlockSpec((rank, 2 * CLUSTER_DIM, 2 * CLUSTER_DIM),
                         lambda i, j: (0, 0, 0)),
            pl.BlockSpec((rank, 2 * CLUSTER_DIM, 2 * CLUSTER_DIM),
                         lambda i, j: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((2, 1, M, 1, CLUSTER_DIM, CLUSTER_DIM),
                               lambda i, j: (0, i, 0, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(view, ma, mb)
    return out.reshape(in_shape)


def _gauss_planes(mats):
    """(R, 2, 128, 128) SoA matrices -> (R, 3, 128, 128) planes
    [Re, Im, Re + Im]: the matrix operands of the three-product complex
    form (_window_block_body), the sum formed once per pass here rather
    than once per grid step in the kernel."""
    return jnp.concatenate([mats, mats[:, :1] + mats[:, 1:]], axis=1)


def _window_block_body(x, ma, mb, mask, rank, apply_a, apply_b, prec):
    """Shared window-pass algebra on one VMEM-resident 5-d value
    (2, R, 128, M, 128) — window index on axis 2, lanes last, R/M pure
    batch axes — or, where M = 1, the 4-d (2, R, 128, 128) block itself
    (a unit M axis would pad each (1, 128) row to an (8, 128) tile).
    Used verbatim by both the single-pass kernel
    (_window_kernel) and the megakernel (_mega_window_kernel) so the two
    routes issue IDENTICAL dot_generals in identical order and stay
    bit-exact against each other (tests/test_megakernel.py pins this).

    Each complex side product M (xr + i xi) takes three real products
    (Gauss's form, as BLAS cgemm3m): t1 = Mr xr, t2 = Mi xi,
    t3 = (Mr + Mi)(xr + xi); re = t1 - t2, im = t3 - t1 - t2.  The
    matrix operands arrive as _gauss_planes [Mr, Mi, Mr + Mi].  The rank
    terms' final-side products sum before the one combine; the mask
    multiplies after it.  A side whose flag is off is identity and costs
    no product, so a mask-only pass is elementwise."""
    xr, xi = x[0], x[1]
    if not (apply_a or apply_b):
        rr, ri = xr, xi
    else:
        xs = xr + xi
        # lanes: y[l'] = sum_l A[l',l] x[l]
        da = (((xr.ndim - 1,), (1,)), ((), ()))
        db = (((1,), (1,)), ((), ()))    # window axis, left-contracted
        acc = None
        for r in range(rank):
            yr, yi, ys = xr, xi, xs
            if apply_a:
                t = tuple(_kdot(y, ma[r, p], da, prec)
                          for p, y in enumerate((yr, yi, ys)))
                if apply_b:
                    yr, yi = t[0] - t[1], t[2] - t[0] - t[1]
                    ys = yr + yi
            if apply_b:                  # (128, R, M, 128)
                t = tuple(_kdot(mb[r, p], y, db, prec)
                          for p, y in enumerate((yr, yi, ys)))
            acc = t if acc is None else tuple(s + u for s, u in zip(acc, t))
        rr, ri = acc[0] - acc[1], acc[2] - acc[0] - acc[1]
        if apply_b:
            rr, ri = jnp.moveaxis(rr, 0, 1), jnp.moveaxis(ri, 0, 1)
    if mask is not None:
        shape = (CLUSTER_DIM,) * 2 if rr.ndim == 3 else (CLUSTER_DIM, 1,
                                                         CLUSTER_DIM)
        mr, mi = mask[0].reshape(shape), mask[1].reshape(shape)
        rr, ri = rr * mr - ri * mi, rr * mi + ri * mr
    return jnp.stack([rr, ri], axis=0)               # the shape of x


def _window_kernel(rank, apply_a, apply_b, prec=jax.lax.Precision.HIGHEST,
                   with_mask=False):
    """Kernel applying [mask (.)] sum_r B_r (x) A_r where A_r acts on the
    lane qubits [0,7) and B_r on an ARBITRARY contiguous sublane window
    [k, k+7) — the block spec (not the kernel) encodes k.  Block shape
    (2, R, 128, M, 128): R hi-axis blocks x M mid-axis blocks; both are
    pure batch axes of the two MXU contractions, so no in-kernel
    transposes are needed.  ``apply_a``/``apply_b`` skip the corresponding
    products when that side of the window operator is identity (half the
    FLOPs of a full pass; none when both are off).  ``with_mask`` appends
    one complex elementwise multiply by a (2, 128, 128) (window x lane)
    mask — how diagonal crossing gates (CZ/CPhase, and CNOT via its
    H-sandwich rewrite) are applied at zero rank cost (circuit.fold_mask)."""

    def kernel(a_ref, ma_ref, mb_ref, *rest):
        mask_ref, o_ref = (rest[0], rest[1]) if with_mask else (None, rest[0])
        xflat = a_ref[...]              # (2, R, 128, M*128) or (2, R, 128, M, 128)
        x = xflat
        if xflat.shape[-1] != CLUSTER_DIM:
            x = xflat.reshape(2, xflat.shape[1], CLUSTER_DIM, -1,
                              CLUSTER_DIM)    # (2, R, 128, M, 128)
        res = _window_block_body(
            x, ma_ref, mb_ref,
            mask_ref[...] if with_mask else None,
            rank, apply_a, apply_b, prec)
        o_ref[...] = res.reshape(xflat.shape)

    return kernel


@partial(jax.jit,
         static_argnames=("num_qubits", "k", "apply_a", "apply_b",
                          "interpret", "precision"),
         donate_argnums=0)
def _apply_window_stack_jit(
    amps,
    mats_a,
    mats_b,
    mask=None,
    *,
    num_qubits: int,
    k: int = SUBLANE_QUBITS,
    apply_a: bool = True,
    apply_b: bool = True,
    interpret: bool | None = None,
    precision: str | None = None,
):
    """Apply the rank-R operator sum_r B_r (x) A_r with A on lane qubits
    [0,7) and B on the contiguous window [k, k+7), 7 <= k <= n-7, in ONE
    HBM pass with NO data relocation: the state is viewed as
    (2, hi, 128, mid, 128) so the window bits land on the sublane axis of
    each block (strided-row DMA).  k = 7 reproduces apply_cluster_stack;
    k > 7 replaces a segswap-relocate + cluster + restore sequence — the
    single-chip analogue of choosing which qubits are "local", cf. the
    reference's SWAP-relocalization (QuEST_cpu_distributed.c:1503-1545).

    ``amps`` may be any full-size view of the state (flat (2, 2^n) or the
    canonical (2, nb, 128, 128)); the result is returned in the SAME
    shape.  Chained per-pass callers (circuit.execute_plan_chained) keep
    the canonical view across jit boundaries — a flat (2, 2^n) parameter
    carries a device layout that differs from the kernels' T(8,128) tiled
    view, forcing XLA to insert a FULL-STATE layout copy at the program
    boundary (8 GB at 30q: the round-2 "30q never reaches the chip" OOM).
    """
    n = num_qubits
    in_shape = amps.shape
    if not (LANE_QUBITS <= k <= n - SUBLANE_QUBITS):
        raise ValueError(f"window offset {k} out of range for n={n}")
    interpret = _resolve_interpret(interpret, amps)
    rank = mats_a.shape[0]
    hi = 1 << (n - k - SUBLANE_QUBITS)
    mid = 1 << (k - LANE_QUBITS)
    # batch mid first — a block's contiguous HBM span per sublane row is
    # M*512 bytes (the trailing (mid, lane) axis is memory-contiguous), so
    # small M means descriptor-bound strided DMA (M=1 -> 512 B chunks);
    # then batch hi with what remains.  The block count is the most the
    # 16 MB scoped VMEM holds beside the unrolled rank loop's temporaries,
    # from compiles of the three-product body for a described v5e
    # (tests/test_chip_compile.py, 28 and 30 qubits): rank 1 fits 16
    # blocks but a masked dual-side pass overflows there in the 5-d view;
    # rank 2 overflows at 16; at rank 3-4 a single-side pass fits 8 and a
    # dual-side one overflows at 8.  k = 8, 9 (mid 2, 4) pad each block's
    # (M, 128) rows to 8 in the kernel: there rank 1 fits 8, rank 2 fits 8
    # single-side and 4 dual-side, rank 3-4 fit 4.  Rank-1 passes take 16
    # only as unmasked single-side passes in the 5-d view and 8 elsewhere:
    # the sizes the chip timed (PERF.md §5); 16 elsewhere is untimed.
    dual = apply_a and apply_b
    if 1 < mid < 8:
        nb = 8 if rank == 1 or (rank == 2 and not dual) else 4
    elif rank == 1:
        nb = 16 if mid >= 8 and apply_a != apply_b and mask is None else 8
    else:
        nb = 4 if dual and rank > 2 else 8
    block_amps = nb * BLOCK_AMPS
    if n <= 21:
        # small states (<= 16 MB) can be VMEM-promoted wholesale by XLA
        # inside larger programs; an 8-block pass then overflows the 16 MB
        # scoped VMEM (measured 18.55M at n=20).  4 blocks always fit.
        block_amps = min(block_amps, 4 * BLOCK_AMPS)
    # View choice is LAYOUT-critical: with mid >= 8 the 5-d view
    # (2, hi, 128, mid, 128) under the default T(8,128) tiling of its two
    # minor dims is PHYSICALLY IDENTICAL to the canonical k=7 view
    # (2, nb, 128, 128) — both tile 8 consecutive values of amp bits 7-9
    # by the 128 lanes — so consecutive passes at different offsets
    # exchange state via free bitcasts.  The collapsed 4-d view
    # (2, hi, 128, mid*128) instead puts window bits in the tile's sublane
    # dim, forcing XLA to insert a full-state retile copy (~4 ms at 26q)
    # at EVERY pass boundary (measured: a 26-pass plan spent ~60 ms in
    # such copies).  k in {8, 9} (mid 2, 4) keeps the 4-d view (the 5-d
    # form would pad mid to 8, up to 4x memory), as do rank 3-4 dual-side
    # passes, whose scoped VMEM cannot hold the 5-d layout's 8-block
    # minimum tile.
    five_d = mid >= 8 and block_amps >= 8 * BLOCK_AMPS
    M = min(mid, max(1, block_amps // BLOCK_AMPS))
    if five_d and M % 8:
        M = 8
    while mid % M:
        M //= 2
    R = min(hi, max(1, block_amps // (M * BLOCK_AMPS)))
    while hi % R:
        R //= 2
    ma = _gauss_planes(jnp.asarray(mats_a, amps.dtype))
    mb = _gauss_planes(jnp.asarray(mats_b, amps.dtype))
    mat_spec = (rank, 3, CLUSTER_DIM, CLUSTER_DIM)
    with_mask = mask is not None
    if five_d:
        view = amps.reshape(2, hi, CLUSTER_DIM, mid, CLUSTER_DIM)
        state_spec = pl.BlockSpec((2, R, CLUSTER_DIM, M, CLUSTER_DIM),
                                  lambda i, j: (0, i, 0, j, 0))
    else:
        view = amps.reshape(2, hi, CLUSTER_DIM, mid * CLUSTER_DIM)
        state_spec = pl.BlockSpec((2, R, CLUSTER_DIM, M * CLUSTER_DIM),
                                  lambda i, j: (0, i, 0, j))
    zmap = (lambda i, j: (0,) * len(mat_spec))
    in_specs = [
        state_spec,
        pl.BlockSpec(mat_spec, zmap),
        pl.BlockSpec(mat_spec, zmap),
    ]
    operands = [view, ma, mb]
    if with_mask:
        in_specs.append(pl.BlockSpec((2, CLUSTER_DIM, CLUSTER_DIM),
                                     lambda i, j: (0, 0, 0)))
        operands.append(jnp.asarray(mask, amps.dtype))
    out = pl.pallas_call(
        _window_kernel(rank, apply_a, apply_b,
                       _resolve_precision(precision), with_mask),
        grid=(hi // R, mid // M),
        in_specs=in_specs,
        out_specs=state_spec,
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(*operands)
    return out.reshape(in_shape)


@partial(jax.jit, static_argnames=("num_qubits", "block_rows", "interpret",
                                   "precision"),
         donate_argnums=0)
def _apply_cluster_stack_jit(
    amps,
    mats_a,
    mats_b,
    *,
    num_qubits: int,
    block_rows: int = 8,
    interpret: bool | None = None,
    precision: str | None = None,
):
    """Apply the rank-R window operator sum_r B_r (x) A_r in one HBM pass.

    ``mats_a``/``mats_b``: stacked SoA (R, 2, 128, 128).  R > 1 encodes
    lane-x-sublane-crossing gates folded by the scheduler (circuit.py)
    through the |a><b| block decomposition — the pass costs R matmul pairs
    but still exactly one state read + write.  Result shape = input shape
    (see _apply_window_stack_jit on canonical views)."""
    n = num_qubits
    in_shape = amps.shape
    if n < CLUSTER_QUBITS:
        raise ValueError(f"apply_cluster_stack needs >= {CLUSTER_QUBITS} qubits")
    interpret = _resolve_interpret(interpret, amps)
    rank = mats_a.shape[0]
    nb = 1 << (n - CLUSTER_QUBITS)
    r = min(block_rows, nb)
    while nb % r:
        r //= 2
    ma = jax.vmap(lane_real_rep)(jnp.asarray(mats_a, amps.dtype))
    mb = jax.vmap(sublane_real_rep)(jnp.asarray(mats_b, amps.dtype))
    view = amps.reshape(2, nb, CLUSTER_DIM, CLUSTER_DIM)
    out = pl.pallas_call(
        _cluster_kernel_rank(rank, _resolve_precision(precision)),
        grid=(nb // r,),
        in_specs=[
            pl.BlockSpec((2, r, CLUSTER_DIM, CLUSTER_DIM),
                         lambda i: (0, i, 0, 0)),
            pl.BlockSpec((rank, 2 * CLUSTER_DIM, 2 * CLUSTER_DIM),
                         lambda i: (0, 0, 0)),
            pl.BlockSpec((rank, 2 * CLUSTER_DIM, 2 * CLUSTER_DIM),
                         lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((2, r, CLUSTER_DIM, CLUSTER_DIM),
                               lambda i: (0, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(view, ma, mb)
    return out.reshape(in_shape)


def _resolved(precision):
    """Resolve the module default NOW — before the jit cache key is formed —
    so set_matmul_precision() affects subsequent calls instead of silently
    hitting a kernel compiled under the old setting."""
    return precision or _CONFIG["precision"]


def apply_cluster_pair(amps, mat_a, mat_b, *, precision=None, **kw):
    """See _apply_cluster_pair_jit."""
    return _apply_cluster_pair_jit(amps, mat_a, mat_b,
                                   precision=_resolved(precision), **kw)


def apply_swap_cluster_stack(amps, mats_a, mats_b, *, precision=None, **kw):
    """See _apply_swap_cluster_stack_jit."""
    return _apply_swap_cluster_stack_jit(amps, mats_a, mats_b,
                                         precision=_resolved(precision), **kw)


def apply_window_stack(amps, mats_a, mats_b, mask=None, *, precision=None, **kw):
    """See _apply_window_stack_jit."""
    return _apply_window_stack_jit(amps, mats_a, mats_b, mask,
                                   precision=_resolved(precision), **kw)


def apply_cluster_stack(amps, mats_a, mats_b, *, precision=None, **kw):
    """See _apply_cluster_stack_jit."""
    return _apply_cluster_stack_jit(amps, mats_a, mats_b,
                                    precision=_resolved(precision), **kw)


# ---------------------------------------------------------------------------
# Diagonal gate on the canonical view: the one-pass op for a diagonal gate
# no window pass covers (circuit._fallback_op).  An XLA complex multiply
# cannot write in place (each output channel reads both input channels),
# so at 30 qubits it needs a second state; this kernel rewrites each
# block in VMEM instead.
# ---------------------------------------------------------------------------


def _diag_kernel(x_ref, t_ref, o_ref):
    x = x_ref[...]                       # (2, R, 128, 128)
    fr = t_ref[0, 0]                     # (128, 128) factor for this block
    fi = t_ref[0, 1]
    o_ref[...] = jnp.stack([x[0] * fr - x[1] * fi, x[0] * fi + x[1] * fr])


@partial(jax.jit, static_argnames=("num_qubits", "targets", "interpret"),
         donate_argnums=0)
def apply_diagonal_canonical(amps, diag, *, num_qubits: int,
                             targets: tuple, interpret: bool | None = None):
    """Multiply the state by ``diag[bits(targets)]`` ((2, 2^k) SoA
    diagonal), n >= 14, in ONE in-place pass over the canonical
    (2, 2^(n-14), 128, 128) view.  The in-block factor for each
    combination of the targets at or above bit 14 is a (2, 128, 128)
    table row; a grid step covers rows that share those bits, and its
    index map picks the row."""
    n = num_qubits
    in_shape = amps.shape
    interpret = _resolve_interpret(interpret, amps)
    nb = 1 << (n - CLUSTER_QUBITS)
    diag = jnp.asarray(diag, amps.dtype)
    blk = [(j, t - CLUSTER_QUBITS) for j, t in enumerate(targets)
           if t >= CLUSTER_QUBITS]
    ri = jax.lax.broadcasted_iota(jnp.int32, (CLUSTER_DIM, CLUSTER_DIM), 0)
    li = jax.lax.broadcasted_iota(jnp.int32, (CLUSTER_DIM, CLUSTER_DIM), 1)
    code = jnp.zeros((CLUSTER_DIM, CLUSTER_DIM), jnp.int32)
    for j, t in enumerate(targets):
        if t < LANE_QUBITS:
            code = code | (((li >> t) & 1) << j)
        elif t < CLUSTER_QUBITS:
            code = code | (((ri >> (t - LANE_QUBITS)) & 1) << j)
    rows = []
    for g in range(1 << len(blk)):
        hi = 0
        for i, (j, _b) in enumerate(blk):
            hi |= ((g >> i) & 1) << j
        rows.append(jnp.stack([jnp.take(diag[0], code | hi),
                               jnp.take(diag[1], code | hi)]))
    table = jnp.stack(rows)              # (2^|blk|, 2, 128, 128)
    low_bit = min((b for _j, b in blk), default=n - CLUSTER_QUBITS)
    r = min(8, 1 << low_bit, nb)

    def row_of(i):
        g = 0
        for k, (_j, b) in enumerate(blk):
            g = g | ((((i * r) >> b) & 1) << k)
        return g

    view = amps.reshape(2, nb, CLUSTER_DIM, CLUSTER_DIM)
    out = pl.pallas_call(
        _diag_kernel,
        grid=(nb // r,),
        in_specs=[
            pl.BlockSpec((2, r, CLUSTER_DIM, CLUSTER_DIM),
                         lambda i: (0, i, 0, 0)),
            pl.BlockSpec((1, 2, CLUSTER_DIM, CLUSTER_DIM),
                         lambda i: (row_of(i), 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((2, r, CLUSTER_DIM, CLUSTER_DIM),
                               lambda i: (0, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(view, table)
    return out.reshape(in_shape)


# ---------------------------------------------------------------------------
# Window megakernel (docs/design.md §29): a RUN of window passes in ONE
# pallas_call — one HBM read + one HBM write for the whole run instead of
# one round-trip per pass.  Eligible passes have window offset k <= 7 + g
# where 2^g VMEM-resident canonical rows make every window bit block-local;
# the in-kernel regroup between passes is a PURE reshape (no transpose):
# little-endian bit order means merging the (row_lo, sub_hi) axes IS the
# window index w = row_lo << (14-k) | sub_hi.
# ---------------------------------------------------------------------------


def megakernel_mode() -> str:
    """QT_MEGAKERNEL knob: "on" forms megawin groups on every backend
    (interpret mode off the TPU — the CPU test/bench arm); anything else
    is "off", the default.  For a described v5e the groups
    tests/test_chip_compile.py tries compile and run in place at 30
    qubits (rank 1-2 on 8 rows, rank 4 on 4, groups opening with a k = 7
    member), but no chip measurement has shown a group beating its
    per-pass kernels, so the default plans per-pass window kernels only."""
    import os

    raw = os.environ.get("QT_MEGAKERNEL", "off").strip().lower()
    return "on" if raw in ("on", "1", "true", "yes") else "off"


def megakernel_planning() -> bool:
    """Whether the planner forms megawin groups: only under
    QT_MEGAKERNEL=on.  A planned group always executes fused — on the
    TPU the kernel compiles or the run raises."""
    return megakernel_mode() == "on"


def megawin_row_cap(rank: int, num_qubits: int) -> int:
    """Largest VMEM block-row grouping a sub-pass of this rank tolerates:
    8 rows at rank 1-2 and 4 at rank 3-4 compile for a described v5e at
    30 qubits (tests/test_chip_compile.py); n <= 21 states risk wholesale
    XLA VMEM promotion, cap 4.  A group's G = 2^(kmax-7) must stay <= min
    over its sub-passes."""
    cap = 8 if rank <= 2 else 4
    if num_qubits <= 21:
        cap = min(cap, 4)
    return cap


def _mega_window_kernel(spec, prec=jax.lax.Precision.HIGHEST):
    """Kernel applying a run of window passes to one VMEM-resident block
    of G consecutive canonical rows.  ``spec``: per-pass statics
    (k, rank, apply_a, apply_b, with_mask).  Each pass regroups the block
    (2, G, 128, 128) -> (2, G/2^(k-7), 128, 2^(k-7), 128) by reshape only
    (the merged (row_lo, sub_hi) axis IS the window index — little-endian
    flat order), runs the SAME block body as the per-pass kernel
    (_window_block_body, so numerics are bit-identical), and reshapes
    back for the next pass.  One HBM read + one write for the whole run."""

    def kernel(a_ref, *refs):
        o_ref = refs[-1]
        x = a_ref[...]                       # (2, G, 128, 128)
        g_rows = x.shape[1]
        ri = 0
        for (k, rank, apply_a, apply_b, with_mask) in spec:
            ma_ref, mb_ref = refs[ri], refs[ri + 1]
            ri += 2
            mask = None
            if with_mask:
                mask = refs[ri][...]
                ri += 1
            wg = 1 << (k - LANE_QUBITS)      # window bits on the row axis
            whi = CLUSTER_DIM >> (k - LANE_QUBITS)  # ... on the sublanes
            ghi = g_rows // wg
            x5 = x                           # k = 7: the block as it is
            if wg > 1:
                x5 = x.reshape(2, ghi, wg, whi, wg, CLUSTER_DIM)
                x5 = x5.reshape(2, ghi, CLUSTER_DIM, wg, CLUSTER_DIM)
            res = _window_block_body(x5, ma_ref, mb_ref, mask,
                                     rank, apply_a, apply_b, prec)
            x = res.reshape(2, g_rows, CLUSTER_DIM, CLUSTER_DIM)
        o_ref[...] = x

    return kernel


@partial(jax.jit,
         static_argnames=("num_qubits", "spec", "interpret", "precision"),
         donate_argnums=0)
def _apply_megawin_jit(
    amps,
    *arrays,
    num_qubits: int,
    spec: tuple,
    interpret: bool | None = None,
    precision: str | None = None,
):
    """Apply the window-pass run described by ``spec`` (per-pass statics
    (k, rank, apply_a, apply_b, with_mask); ``arrays`` = the flattened
    (a, b[, mask]) operands in pass order) in ONE pallas_call: grid over
    2^(n-14)/G super-blocks of G = 2^(kmax-7) consecutive canonical rows,
    so every pass's window bits are block-local.  Result shape = input
    shape (canonical-view layout notes as in _apply_window_stack_jit)."""
    n = num_qubits
    in_shape = amps.shape
    interpret = _resolve_interpret(interpret, amps)
    kmax = max(s[0] for s in spec)
    g_rows = 1 << (kmax - LANE_QUBITS)
    if n < CLUSTER_QUBITS:
        raise ValueError(f"megawin needs >= {CLUSTER_QUBITS} qubits")
    nb = 1 << (n - CLUSTER_QUBITS)
    if g_rows > nb or any(not (LANE_QUBITS <= s[0] <= n - SUBLANE_QUBITS)
                          for s in spec):
        raise ValueError(f"megawin window offsets out of range for n={n}")
    state_spec = pl.BlockSpec((2, g_rows, CLUSTER_DIM, CLUSTER_DIM),
                              lambda i: (0, i, 0, 0))
    in_specs = [state_spec]
    operands = []
    ai = 0
    for (_k, rank, _a, _b, with_mask) in spec:
        mat_spec = pl.BlockSpec((rank, 3, CLUSTER_DIM, CLUSTER_DIM),
                                lambda i: (0, 0, 0, 0))
        in_specs += [mat_spec, mat_spec]
        operands += [_gauss_planes(jnp.asarray(arrays[ai + j], amps.dtype))
                     for j in (0, 1)]
        ai += 2
        if with_mask:
            in_specs.append(pl.BlockSpec((2, CLUSTER_DIM, CLUSTER_DIM),
                                         lambda i: (0, 0, 0)))
            operands.append(jnp.asarray(arrays[ai], amps.dtype))
            ai += 1
    view = amps.reshape(2, nb, CLUSTER_DIM, CLUSTER_DIM)
    out = pl.pallas_call(
        _mega_window_kernel(spec, _resolve_precision(precision)),
        grid=(nb // g_rows,),
        in_specs=in_specs,
        out_specs=state_spec,
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(view, *operands)
    return out.reshape(in_shape)


def apply_window_megastack(amps, subops, *, num_qubits, interpret=None,
                           precision=None):
    """Apply a planned run of winfused passes — ``subops`` is a sequence of
    ("winfused", k, a, b, apply_a, apply_b[, mask]) tuples — as ONE
    pallas_call (see _apply_megawin_jit).  This is the megawin plan op's
    fused route; circuit.execute_plan decomposes to per-pass dispatches
    instead when megakernel_executable() says no."""
    spec = []
    arrays = []
    for op in subops:
        mask = op[6] if len(op) > 6 else None
        spec.append((int(op[1]), int(np.shape(op[2])[0]),
                     bool(op[4]), bool(op[5]), mask is not None))
        arrays += [op[2], op[3]]
        if mask is not None:
            arrays.append(mask)
    return _apply_megawin_jit(amps, *arrays, num_qubits=num_qubits,
                              spec=tuple(spec), interpret=interpret,
                              precision=_resolved(precision))


# ---------------------------------------------------------------------------
# QFT ladder pass (Hadamard + whole controlled-phase ladder) as one Pallas
# kernel — the XLA elementwise formulation measured ~9.2 ms per 26q layer
# (it splits into multiple fusions around the pair-axis slice/stack); this
# kernel is one HBM read + write with the phase from two host tables.
# Reference layer semantics: agnostic_applyQFT, QuEST_common.c:836-898.
# ---------------------------------------------------------------------------


_TL_SPLIT = 1 << 11   # SMEM phase-table halves stay <= 2*2048*4 B = 16 KB


def _qft_ladder_kernel(inv, RL):
    def kernel(x_ref, tab_ref, tlo_ref, thi_ref, o_ref):
        # x_ref: (2, 1, 2, RL, 128, 128); tlo/thi: SMEM factor tables over
        # the low/high halves of the L index (each <= 16 KB regardless of
        # target), phase_L(l) = tlo[l % SPLIT] * thi[l // SPLIT]
        tab_re = tab_ref[0]                # (128, 128): bits 7-13 x 0-6
        tab_im = tab_ref[1]
        j = pl.program_id(1)
        for r in range(RL):                # static unroll
            x0r = x_ref[0, 0, 0, r]
            x0i = x_ref[1, 0, 0, r]
            x1r = x_ref[0, 0, 1, r]
            x1i = x_ref[1, 0, 1, r]
            l = j * RL + r
            alo = tlo_ref[0, l % _TL_SPLIT]
            blo = tlo_ref[1, l % _TL_SPLIT]
            ahi = thi_ref[0, l // _TL_SPLIT]
            bhi = thi_ref[1, l // _TL_SPLIT]
            tlr = alo * ahi - blo * bhi
            tli = alo * bhi + blo * ahi
            ph_re = tlr * tab_re - tli * tab_im
            ph_im = tlr * tab_im + tli * tab_re
            dr = (x0r - x1r) * inv
            di = (x0i - x1i) * inv
            o_ref[0, 0, 0, r] = (x0r + x1r) * inv
            o_ref[1, 0, 0, r] = (x0i + x1i) * inv
            o_ref[0, 0, 1, r] = dr * ph_re - di * ph_im
            o_ref[1, 0, 1, r] = dr * ph_im + di * ph_re

    return kernel


def _qft_ladder_jit(amps, tab, tlo, thi, *, num_qubits: int, target: int,
                    interpret: bool | None = None):
    n, t = num_qubits, target
    in_shape = amps.shape
    L = 1 << (t - CLUSTER_QUBITS)          # bits 14..t-1
    H = 1 << (n - 1 - t)                   # bits t+1..n-1
    if interpret is None:
        interpret = _interpret_default()
    RL = min(L, 8)
    view = amps.reshape(2, H, 2, L, CLUSTER_DIM, CLUSTER_DIM)
    inv = 0.7071067811865476
    out = pl.pallas_call(
        _qft_ladder_kernel(inv, RL),
        grid=(H, L // RL),
        in_specs=[
            pl.BlockSpec((2, 1, 2, RL, CLUSTER_DIM, CLUSTER_DIM),
                         lambda i, j: (0, i, 0, j, 0, 0)),
            pl.BlockSpec((2, CLUSTER_DIM, CLUSTER_DIM),
                         lambda i, j: (0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((2, 1, 2, RL, CLUSTER_DIM, CLUSTER_DIM),
                               lambda i, j: (0, i, 0, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(view, tab, tlo, thi)
    return out.reshape(in_shape)


_qft_ladder_pallas_inner = partial(
    jax.jit, static_argnames=("num_qubits", "target", "interpret"),
    donate_argnums=0)(_qft_ladder_jit)


def qft_ladder_supported(amps_dtype, num_qubits: int, target: int,
                         base: int) -> bool:
    """The Pallas ladder needs base 0, the pair bit above the 14-qubit
    block (t >= 14), and a Mosaic-supported dtype on a real TPU."""
    import numpy as _np

    return (base == 0 and target >= LANE_QUBITS
            and num_qubits > target
            and num_qubits >= CLUSTER_QUBITS + 1
            and _np.dtype(amps_dtype) == _np.float32
            and not _interpret_default())


def apply_qft_ladder_pallas(amps, *, num_qubits: int, target: int,
                            conj: bool = False,
                            interpret: bool | None = None):
    """One QFT layer (H on ``target`` + controlled-phase ladder against
    bits [0, target)) in ONE Pallas pass.  The phase e^{i pi low/2^t}
    factorizes into a host (128, 128) table over bits [0, 14) and two
    SMEM factor tables over the [14, t) index (split at 2^11 so each
    stays <= 16 KB for any target)."""
    import numpy as _np

    n, t = num_qubits, target
    sgn = -1.0 if conj else 1.0
    dt = _np.dtype(amps.dtype)
    if t < CLUSTER_QUBITS:
        jlo = _np.arange(1 << t, dtype=_np.float64)
        ang = sgn * _np.pi * jlo / (1 << t)
        tab = _np.stack([_np.cos(ang), _np.sin(ang)]).reshape(
            2, 1 << (t - LANE_QUBITS), CLUSTER_DIM).astype(dt)
        return _qft_ladder_lo_jit(amps, jnp.asarray(tab),
                                  num_qubits=n, target=t,
                                  interpret=interpret)
    j14 = _np.arange(1 << CLUSTER_QUBITS, dtype=_np.float64)
    ang14 = sgn * _np.pi * j14 / (1 << t)
    tab = _np.stack([_np.cos(ang14), _np.sin(ang14)]).reshape(
        2, CLUSTER_DIM, CLUSTER_DIM).astype(dt)
    L = 1 << (t - CLUSTER_QUBITS)
    nlo = min(L, _TL_SPLIT)
    jlo = _np.arange(nlo, dtype=_np.float64)
    alo = sgn * _np.pi * jlo * (1 << CLUSTER_QUBITS) / (1 << t)
    tlo = _np.stack([_np.cos(alo), _np.sin(alo)]).astype(dt)
    nhi = max(1, L // _TL_SPLIT)
    jhi = _np.arange(nhi, dtype=_np.float64)
    ahi = (sgn * _np.pi * jhi * float(_TL_SPLIT)
           * (1 << CLUSTER_QUBITS) / (1 << t))
    thi = _np.stack([_np.cos(ahi), _np.sin(ahi)]).astype(dt)
    return _qft_ladder_pallas_inner(
        amps, jnp.asarray(tab), jnp.asarray(tlo), jnp.asarray(thi),
        num_qubits=n, target=t, interpret=interpret)


def _qft_ladder_lo_kernel(inv, t):
    """Ladder layer for 7 <= t <= 13: the pair bit lives inside the
    128-sublane axis, so the block reshapes its sublane factor and the
    phase table (2, 2^(t-7), 128) aligns with in-block axes directly."""
    s_hi = 1 << (13 - t)
    s_lo = 1 << (t - LANE_QUBITS)

    def kernel(x_ref, tab_ref, o_ref):
        x = x_ref[...]                      # (2, R, 128, 128)
        R = x.shape[1]
        v = x.reshape(2, R, s_hi, 2, s_lo, CLUSTER_DIM)
        x0 = v[:, :, :, 0]                  # (2, R, s_hi, s_lo, 128)
        x1 = v[:, :, :, 1]
        y0 = (x0 + x1) * inv
        d = (x0 - x1) * inv
        tr = tab_ref[0]                     # (s_lo, 128)
        ti = tab_ref[1]
        y1r = d[0] * tr - d[1] * ti
        y1i = d[0] * ti + d[1] * tr
        out_re = jnp.stack([y0[0], y1r], axis=2)   # (R, s_hi, 2, s_lo, 128)
        out_im = jnp.stack([y0[1], y1i], axis=2)
        out = jnp.stack([out_re, out_im])
        o_ref[...] = out.reshape(2, R, CLUSTER_DIM, CLUSTER_DIM)

    return kernel


@partial(jax.jit, static_argnames=("num_qubits", "target", "interpret"),
         donate_argnums=0)
def _qft_ladder_lo_jit(amps, tab, *, num_qubits: int, target: int,
                       interpret: bool | None = None):
    n, t = num_qubits, target
    in_shape = amps.shape
    HI = 1 << (n - CLUSTER_QUBITS)
    if interpret is None:
        interpret = _interpret_default()
    R = min(HI, 8)
    view = amps.reshape(2, HI, CLUSTER_DIM, CLUSTER_DIM)
    out = pl.pallas_call(
        _qft_ladder_lo_kernel(0.7071067811865476, t),
        grid=(HI // R,),
        in_specs=[
            pl.BlockSpec((2, R, CLUSTER_DIM, CLUSTER_DIM),
                         lambda i: (0, i, 0, 0)),
            pl.BlockSpec((2, 1 << (t - LANE_QUBITS), CLUSTER_DIM),
                         lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((2, R, CLUSTER_DIM, CLUSTER_DIM),
                               lambda i: (0, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(view, tab)
    return out.reshape(in_shape)


# ---------------------------------------------------------------------------
# Multi-layer (radix-2^k) QFT ladder passes
# ---------------------------------------------------------------------------
#
# The per-layer ladder above runs ONE butterfly layer per HBM sweep, so a
# full n-qubit QFT costs ~n sweeps even though each sweep does almost no
# arithmetic.  Classic high-radix FFT blocking fixes that: hold 2^k pair
# bits co-resident in VMEM and run k butterfly+phase layers per sweep.
# The reference has no analogue (its QFT is one kernel sweep per H plus
# one per phase ladder, agnostic_applyQFT, QuEST_common.c:836-898); this
# is a TPU-memory-hierarchy design.
#
#   - _qft_multi_hi: layers t in [t_lo, t_hi], all >= 14.  The state view
#     (2, H, 2^k, M, 128, 128) makes bits [t_lo, t_hi] a co-resident block
#     axis; each layer's controlled-phase factorizes into a per-layer
#     (128, 128) VMEM table over bits [0, 14), an SMEM factor over bits
#     [14, t_lo) (the block's mid coordinate), and a compile-time constant
#     over the already-swept block bits below the layer.
#   - _qft_cluster_multi: ALL seven sublane layers (t = 13..7) in one
#     sweep; each layer reshapes the sublane axis exactly like
#     _qft_ladder_lo_kernel and its phase table rows [:2^(t-7)] align with
#     the in-block axes directly.

QFT_RADIX_DEFAULT = 4    # VMEM per high pass: 2 sides * 2^k * 64 KB blocks


def _qft_radix() -> int:
    import os

    try:
        k = int(os.environ.get("QT_QFT_RADIX", str(QFT_RADIX_DEFAULT)))
    except ValueError:
        k = QFT_RADIX_DEFAULT
    return max(1, min(5, k))


def qft_multilayer_enabled(amps_dtype) -> bool:
    """Multi-layer QFT passes: f32 on a real TPU by default; interpret-mode
    execution (CPU tests) opts in via QT_QFT_ML_INTERPRET=1."""
    import os

    if np.dtype(amps_dtype) != np.float32:
        return False
    if os.environ.get("QT_QFT_MULTILAYER", "1") != "1":
        return False
    if not _interpret_default():
        return True
    return os.environ.get("QT_QFT_ML_INTERPRET") == "1"


def _qft_multi_hi_kernel(k: int, sgn: float):
    C = 1 << k
    inv = 0.7071067811865476

    def kernel(x_ref, ctab_ref, mlo_ref, mhi_ref, o_ref):
        j = pl.program_id(1)
        slabs = [[x_ref[0, 0, c, 0], x_ref[1, 0, c, 0]] for c in range(C)]
        for p in range(k - 1, -1, -1):
            ctr = ctab_ref[p, 0]                   # (128, 128) bits [0,14)
            cti = ctab_ref[p, 1]
            ar = mlo_ref[p, 0, j % _TL_SPLIT]      # bits [14, t_lo) factor
            ai = mlo_ref[p, 1, j % _TL_SPLIT]
            br = mhi_ref[p, 0, j // _TL_SPLIT]
            bi = mhi_ref[p, 1, j // _TL_SPLIT]
            mr = ar * br - ai * bi
            mi = ar * bi + ai * br
            for c0 in range(C):
                if (c0 >> p) & 1:
                    continue
                c1 = c0 | (1 << p)
                # block bits below the layer: compile-time phase constant
                clo = c0 & ((1 << p) - 1)
                a = sgn * np.pi * clo / float(1 << p)
                sr = mr * float(np.cos(a)) - mi * float(np.sin(a))
                si = mr * float(np.sin(a)) + mi * float(np.cos(a))
                phr = sr * ctr - si * cti
                phi_ = sr * cti + si * ctr
                x0r, x0i = slabs[c0]
                x1r, x1i = slabs[c1]
                s0r = (x0r + x1r) * inv
                s0i = (x0i + x1i) * inv
                dr = (x0r - x1r) * inv
                di = (x0i - x1i) * inv
                slabs[c0] = [s0r, s0i]
                slabs[c1] = [dr * phr - di * phi_, dr * phi_ + di * phr]
        for c in range(C):
            o_ref[0, 0, c, 0] = slabs[c][0]
            o_ref[1, 0, c, 0] = slabs[c][1]

    return kernel


@partial(jax.jit,
         static_argnames=("num_qubits", "t_hi", "t_lo", "conj", "interpret"),
         donate_argnums=0)
def _qft_multi_hi_jit(amps, ctab, mlo, mhi, *, num_qubits: int, t_hi: int,
                      t_lo: int, conj: bool, interpret: bool | None = None):
    n, k = num_qubits, t_hi - t_lo + 1
    in_shape = amps.shape
    C = 1 << k
    H = 1 << (n - 1 - t_hi)
    M = 1 << (t_lo - CLUSTER_QUBITS)
    if interpret is None:
        interpret = _interpret_default()
    view = amps.reshape(2, H, C, M, CLUSTER_DIM, CLUSTER_DIM)
    sgn = -1.0 if conj else 1.0
    out = pl.pallas_call(
        _qft_multi_hi_kernel(k, sgn),
        grid=(H, M),
        in_specs=[
            pl.BlockSpec((2, 1, C, 1, CLUSTER_DIM, CLUSTER_DIM),
                         lambda i, j: (0, i, 0, j, 0, 0)),
            pl.BlockSpec((k, 2, CLUSTER_DIM, CLUSTER_DIM),
                         lambda i, j: (0, 0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((2, 1, C, 1, CLUSTER_DIM, CLUSTER_DIM),
                               lambda i, j: (0, i, 0, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(view, ctab, mlo, mhi)
    return out.reshape(in_shape)


def apply_qft_multi_hi(amps, *, num_qubits: int, t_hi: int, t_lo: int,
                       conj: bool = False, interpret: bool | None = None):
    """Layers t = t_hi..t_lo (descending, all >= 14) in ONE pass.

    SMEM budget: the stacked mid-factor tables are (k, 2, <=2048) f32 =
    k x 16 KB (64 KB at the default radix 4) — above the single-table
    16 KB bound the per-layer kernel keeps, but within Mosaic's scalar
    memory: validated on the real chip at the largest enabled size
    (full 30q f32 QFT, first chunk t_lo=26 -> M=4096, amp0 matches
    2^-15)."""
    import numpy as _np

    n = num_qubits
    k = t_hi - t_lo + 1
    if not (CLUSTER_QUBITS <= t_lo <= t_hi < n and 1 <= k <= 5):
        raise ValueError("apply_qft_multi_hi: bad layer chunk")
    dt = _np.dtype(amps.dtype)
    sgn = -1.0 if conj else 1.0
    j14 = _np.arange(1 << CLUSTER_QUBITS, dtype=_np.float64)
    ctab = _np.empty((k, 2, CLUSTER_DIM, CLUSTER_DIM), dtype=dt)
    M = 1 << (t_lo - CLUSTER_QUBITS)
    nlo = min(M, _TL_SPLIT)
    nhi = max(1, M // _TL_SPLIT)
    mlo = _np.empty((k, 2, nlo), dtype=dt)
    mhi = _np.empty((k, 2, nhi), dtype=dt)
    jlo = _np.arange(nlo, dtype=_np.float64)
    jhi = _np.arange(nhi, dtype=_np.float64)
    for p in range(k):
        t = t_lo + p
        a14 = sgn * _np.pi * j14 / (1 << t)
        ctab[p, 0] = _np.cos(a14).reshape(CLUSTER_DIM, CLUSTER_DIM)
        ctab[p, 1] = _np.sin(a14).reshape(CLUSTER_DIM, CLUSTER_DIM)
        alo = sgn * _np.pi * jlo * (1 << CLUSTER_QUBITS) / (1 << t)
        mlo[p, 0], mlo[p, 1] = _np.cos(alo), _np.sin(alo)
        ahi = (sgn * _np.pi * jhi * float(_TL_SPLIT)
               * (1 << CLUSTER_QUBITS) / (1 << t))
        mhi[p, 0], mhi[p, 1] = _np.cos(ahi), _np.sin(ahi)
    return _qft_multi_hi_jit(
        amps, jnp.asarray(ctab), jnp.asarray(mlo), jnp.asarray(mhi),
        num_qubits=n, t_hi=t_hi, t_lo=t_lo, conj=conj, interpret=interpret)


def _qft_cluster_multi_kernel():
    inv = 0.7071067811865476

    def kernel(x_ref, tab_ref, o_ref):
        x = x_ref[...]                      # (2, R, 128, 128)
        R = x.shape[1]
        for t in range(13, LANE_QUBITS - 1, -1):
            idx = 13 - t
            s_hi = 1 << (13 - t)
            s_lo = 1 << (t - LANE_QUBITS)
            v = x.reshape(2, R, s_hi, 2, s_lo, CLUSTER_DIM)
            x0 = v[:, :, :, 0]              # (2, R, s_hi, s_lo, 128)
            x1 = v[:, :, :, 1]
            s0 = (x0 + x1) * inv
            d = (x0 - x1) * inv
            tr = tab_ref[idx, 0, :s_lo]     # (s_lo, 128)
            ti = tab_ref[idx, 1, :s_lo]
            y1r = d[0] * tr - d[1] * ti
            y1i = d[0] * ti + d[1] * tr
            out_re = jnp.stack([s0[0], y1r], axis=2)
            out_im = jnp.stack([s0[1], y1i], axis=2)
            x = jnp.stack([out_re, out_im]).reshape(
                2, R, CLUSTER_DIM, CLUSTER_DIM)
        o_ref[...] = x

    return kernel


@partial(jax.jit, static_argnames=("num_qubits", "interpret"),
         donate_argnums=0)
def _qft_cluster_multi_jit(amps, tab, *, num_qubits: int,
                           interpret: bool | None = None):
    n = num_qubits
    in_shape = amps.shape
    HI = 1 << (n - CLUSTER_QUBITS)
    if interpret is None:
        interpret = _interpret_default()
    R = min(HI, 8)
    view = amps.reshape(2, HI, CLUSTER_DIM, CLUSTER_DIM)
    out = pl.pallas_call(
        _qft_cluster_multi_kernel(),
        grid=(HI // R,),
        in_specs=[
            pl.BlockSpec((2, R, CLUSTER_DIM, CLUSTER_DIM),
                         lambda i: (0, i, 0, 0)),
            pl.BlockSpec((SUBLANE_QUBITS, 2, CLUSTER_DIM, CLUSTER_DIM),
                         lambda i: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((2, R, CLUSTER_DIM, CLUSTER_DIM),
                               lambda i: (0, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(view, tab)
    return out.reshape(in_shape)


def apply_qft_cluster_multi(amps, *, num_qubits: int, conj: bool = False,
                            interpret: bool | None = None):
    """ALL seven sublane ladder layers (t = 13..7) in ONE pass."""
    import numpy as _np

    if num_qubits < CLUSTER_QUBITS + 1:
        raise ValueError("apply_qft_cluster_multi needs n >= 15")
    dt = _np.dtype(amps.dtype)
    sgn = -1.0 if conj else 1.0
    sl = _np.arange(CLUSTER_DIM, dtype=_np.float64)[:, None]
    ll = _np.arange(CLUSTER_DIM, dtype=_np.float64)[None, :]
    tab = _np.empty((SUBLANE_QUBITS, 2, CLUSTER_DIM, CLUSTER_DIM), dtype=dt)
    for t in range(13, LANE_QUBITS - 1, -1):
        idx = 13 - t
        ang = sgn * _np.pi * (sl * CLUSTER_DIM + ll) / (1 << t)
        tab[idx, 0] = _np.cos(ang)
        tab[idx, 1] = _np.sin(ang)
    return _qft_cluster_multi_jit(amps, jnp.asarray(tab),
                                  num_qubits=num_qubits, interpret=interpret)


def apply_qft_multilayer_ladders(amps, *, num_qubits: int, t_top: int,
                                 conj: bool = False,
                                 interpret: bool | None = None,
                                 radix: int | None = None):
    """Ladder layers t = t_top .. 7 (descending) via the multilayer
    kernels: radix-2^k chunks for t >= 14, then ONE cluster pass for the
    seven sublane layers.  Shared by the unsharded QFT
    (circuit._fused_qft_multilayer) and the per-shard local layers of the
    sharded QFT (parallel.dist.fused_qft_sharded) so both use identical
    layer grouping.  Requires t_top >= 13 and num_qubits >= 15."""
    if t_top < CLUSTER_QUBITS - 1:
        raise ValueError("apply_qft_multilayer_ladders needs t_top >= 13 "
                         "(the cluster pass applies ALL sublane layers)")
    if radix is None:
        radix = _qft_radix()
    t = t_top
    while t >= CLUSTER_QUBITS:
        t_lo = max(CLUSTER_QUBITS, t - radix + 1)
        amps = apply_qft_multi_hi(amps, num_qubits=num_qubits, t_hi=t,
                                  t_lo=t_lo, conj=conj, interpret=interpret)
        t = t_lo - 1
    return apply_qft_cluster_multi(amps, num_qubits=num_qubits, conj=conj,
                                   interpret=interpret)


# ---------------------------------------------------------------------------
# Fused pair-channel sweep: many commuting channels per HBM pass
# ---------------------------------------------------------------------------
#
# A depolarise/damping channel on a density register pairs each element
# with its double-bit-flip partner (ket bit t, bra bit b) and combines
# them with block weights (ops/density.py _pair_channel).  Run eagerly,
# each channel costs several HBM passes (flip + combine).  Here the same
# co-residency trick as the multilayer QFT applies: hold 2^k bra (grid)
# bits co-resident in VMEM and run every channel whose bra bit falls in
# that chunk per sweep — partner slabs are in-block, the ket-bit flip is
# a sublane reshape (t >= 7) or an EXACT 3-term bf16 matmul against a
# 0/1 lane permutation (t < 7; 8+8+8 mantissa bits cover f32, so the
# split is lossless and each term is a single MXU pass — Mosaic rejects
# lane-axis reshape flips).  The reference's channel kernels are one
# full sweep per channel (QuEST_cpu.c:125-385).

_CHAN_SWEEP_RADIX = 3   # C=8 slabs; C=16 overflows scoped VMEM (16.8M > 16M)


def channel_sweep_enabled(amps_dtype) -> bool:
    """Fused channel sweeps: f32 on a real TPU by default; interpret-mode
    (CPU tests) opts in via QT_CHAN_SWEEP_INTERPRET=1."""
    import os

    if np.dtype(amps_dtype) != np.float32:
        return False
    if os.environ.get("QT_CHAN_SWEEP", "1") != "1":
        return False
    if not _interpret_default():
        return True
    return os.environ.get("QT_CHAN_SWEEP_INTERPRET") == "1"


def _lane_xmat_np(t: int) -> np.ndarray:
    """0/1 lane permutation matrix for X on lane bit t (y = x @ P)."""
    d = CLUSTER_DIM
    m = np.zeros((d, d), np.float32)
    idx = np.arange(d)
    m[idx ^ (1 << t), idx] = 1.0
    return m


def _exact_lane_perm(x, p_bf16):
    """x @ P for a 0/1 permutation P, exact at f32: 3-term bf16 split of x
    (the terms sum to x exactly; P is exact in bf16), f32 accumulation,
    one MXU pass per term."""
    f32 = jnp.float32
    xh = x.astype(jnp.bfloat16)
    r1 = x - xh.astype(f32)
    xm = r1.astype(jnp.bfloat16)
    xl = (r1 - xm.astype(f32)).astype(jnp.bfloat16)
    dims = (((x.ndim - 1,), (0,)), ((), ()))
    d = partial(jax.lax.dot_general, dimension_numbers=dims,
                preferred_element_type=f32)
    return d(xh, p_bf16) + d(xm, p_bf16) + d(xl, p_bf16)


def _flip_ket_block(x, t: int, xmap, xmats_ref):
    """In-block flip of cluster bit t over a whole (..., 128, 128) array:
    sublane reshape for t >= 7, exact lane-permutation matmul for t < 7."""
    lead = x.shape[:-2]
    if t >= LANE_QUBITS:
        s = t - LANE_QUBITS
        s_hi, s_lo = 1 << (SUBLANE_QUBITS - 1 - s), 1 << s
        v = x.reshape(lead + (s_hi, 2, s_lo, CLUSTER_DIM))
        ax = len(lead) + 1
        f = jnp.concatenate(
            [jax.lax.slice_in_dim(v, 1, 2, axis=ax),
             jax.lax.slice_in_dim(v, 0, 1, axis=ax)], axis=ax)
        return f.reshape(lead + (CLUSTER_DIM, CLUSTER_DIM))
    return _exact_lane_perm(x, xmats_ref[xmap[t]])


def _bit_mask_2d(t: int, dt):
    """(128, 128) {0,1} mask of cluster bit t, iota-built in-kernel."""
    if t < LANE_QUBITS:
        i = jax.lax.broadcasted_iota(jnp.int32, (CLUSTER_DIM, CLUSTER_DIM), 1)
        return ((i >> t) & 1).astype(dt)
    i = jax.lax.broadcasted_iota(jnp.int32, (CLUSTER_DIM, CLUSTER_DIM), 0)
    return ((i >> (t - LANE_QUBITS)) & 1).astype(dt)


def _chan_sweep_kernel(chunk, k: int, xmap):
    """One sweep applying ``chunk`` channels in order, whole-block style
    (per-slab fragmentation measured 1000x slower under Mosaic).  chunk
    entries: (t, b, pbit, wi) — for a grid-bra channel, pbit = the bra
    bit's position within the 2^k block axis; for an in-block channel
    (bra < 14) pbit is None and the partner is the double flip (t, b) on
    the same element block.  Weights (nchan, 5) = (w_same0, w_same1,
    w_diff, w2_00, w2_11) live in SMEM; ket/bra cluster-bit masks are
    iota-built; lane X permutations come in as a stacked bf16 VMEM arg."""
    C = 1 << k

    def kernel(x_ref, w_ref, xmats_ref, o_ref):
        dt = x_ref.dtype
        x = x_ref[...].reshape(2, C, CLUSTER_DIM, CLUSTER_DIM)
        for t, b, pbit, wi in chunk:
            kt = _bit_mask_2d(t, dt)
            ws0 = w_ref[wi, 0]
            ws1 = w_ref[wi, 1]
            wd = w_ref[wi, 2]
            w2_00 = w_ref[wi, 3]
            w2_11 = w_ref[wi, 4]
            if pbit is None:
                bt = _bit_mask_2d(b, dt)
                k1b1 = kt * bt
                k0b0 = (1 - kt) * (1 - bt)
                w1 = wd + (ws0 - wd) * k0b0 + (ws1 - wd) * k1b1
                w2 = w2_00 * k0b0 + w2_11 * k1b1
                f = _flip_ket_block(
                    _flip_ket_block(x, t, xmap, xmats_ref),
                    b, xmap, xmats_ref)
                x = x * w1 + f * w2
                continue
            chi, clo = 1 << (k - 1 - pbit), 1 << pbit
            v = x.reshape(2, chi, 2, clo, CLUSTER_DIM, CLUSTER_DIM)
            x0 = v[:, :, 0]                  # (2, chi, clo, 128, 128)
            x1 = v[:, :, 1]
            f1 = _flip_ket_block(x1, t, xmap, xmats_ref)
            f0 = _flip_ket_block(x0, t, xmap, xmats_ref)
            w1_0 = ws0 * (1 - kt) + wd * kt      # bra bit 0
            w1_1 = wd * (1 - kt) + ws1 * kt      # bra bit 1
            y0 = x0 * w1_0 + f1 * (w2_00 * (1 - kt))
            y1 = x1 * w1_1 + f0 * (w2_11 * kt)
            x = jnp.stack([y0, y1], axis=2).reshape(
                2, C, CLUSTER_DIM, CLUSTER_DIM)
        o_ref[...] = x.reshape(o_ref.shape)

    return kernel


def _chan_sweep_pass(amps, wmat, xmats, *, num_bits: int, b0: int, k: int,
                     chunk: tuple, xmap_items: tuple,
                     interpret: bool | None = None):
    """One pallas sweep over the (2, H, 2^k, M, 128, 128) view with grid
    bits [b0, b0+k) co-resident.  Plain traced function: callers (the
    fusion drain, tests) jit around it."""
    nn = num_bits
    in_shape = amps.shape
    C = 1 << k
    H = 1 << (nn - b0 - k)
    M = 1 << (b0 - CLUSTER_QUBITS)
    if interpret is None:
        interpret = _interpret_default()
    xmap = dict(xmap_items)
    view = amps.reshape(2, H, C, M, CLUSTER_DIM, CLUSTER_DIM)
    nx = max(1, xmats.shape[0])
    out = pl.pallas_call(
        _chan_sweep_kernel(chunk, k, xmap),
        grid=(H, M),
        in_specs=[
            pl.BlockSpec((2, 1, C, 1, CLUSTER_DIM, CLUSTER_DIM),
                         lambda i, j: (0, i, 0, j, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((nx, CLUSTER_DIM, CLUSTER_DIM),
                         lambda i, j: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((2, 1, C, 1, CLUSTER_DIM, CLUSTER_DIM),
                               lambda i, j: (0, i, 0, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(view, wmat, xmats)
    return out.reshape(in_shape)


def channel_weights(kind: str, prob, dtype):
    """(5,) traced weight vector (w_same0, w_same1, w_diff, w2_00, w2_11)
    for one pair channel — the same parametrization ops/density.py's
    eager kernels use."""
    p = jnp.asarray(prob, dtype)
    one = jnp.ones((), dtype)
    if kind == "depol":
        return jnp.stack([1 - 2 * p / 3, 1 - 2 * p / 3, 1 - 4 * p / 3,
                          2 * p / 3 * one, 2 * p / 3 * one])
    if kind == "damping":
        return jnp.stack([one, 1 - p, jnp.sqrt(1 - p),
                          p * one, 0 * one])
    raise ValueError(f"unknown pair channel {kind!r}")


def apply_pair_channel_sweep(amps, program: tuple, probs, *, num_bits: int,
                             interpret: bool | None = None):
    """Run an ordered sequence of pair channels in FEW HBM sweeps.

    ``program``: static tuple of (kind, t, b) with every t, and any
    in-block b, below 14 and num_bits >= 15.  Grid-bra channels are
    grouped into chunks of _CHAN_SWEEP_RADIX co-resident bra bits (one
    sweep each, channels kept in call order within a chunk; channels in
    different chunks act on disjoint (t, b) pairs and commute); in-block
    channels ride the first sweep.  ``probs`` are traced — same program
    with new probabilities reuses the compiled sweeps."""
    nn = num_bits
    if nn < CLUSTER_QUBITS + 1:
        raise ValueError("apply_pair_channel_sweep needs num_bits >= 15")
    pair_of = {}
    for kind, t, b in program:
        if t >= CLUSTER_QUBITS or b >= nn:
            raise ValueError("sweep channels need ket bit < 14")
        # HARD PRECONDITION: chunk assignment must be a function of the
        # bra bit alone — channels sharing a bra bit must share the ket
        # bit, else call order across non-commuting chunks could be
        # silently rearranged (relevant if a future kind carries per-call
        # differing bit pairs, e.g. two-qubit channels)
        if pair_of.setdefault(b, t) != t:
            raise ValueError(
                "apply_pair_channel_sweep: channels sharing a bra bit "
                "must share the ket bit (chunking is keyed on the bra "
                "bit; mixed pairs would reorder non-commuting channels)")
    dt = amps.dtype
    wmat = jnp.stack([channel_weights(kind, p, dt)
                      for (kind, _, _), p in zip(program, probs)])
    lane_ts = sorted({t for _, t, b in program if t < LANE_QUBITS}
                     | {b for _, t, b in program
                        if b < LANE_QUBITS})
    xmap_items = tuple((t, i) for i, t in enumerate(lane_ts))
    if lane_ts:
        xmats = jnp.asarray(np.stack([_lane_xmat_np(t) for t in lane_ts]),
                            jnp.bfloat16)
    else:
        xmats = jnp.zeros((1, CLUSTER_DIM, CLUSTER_DIM), jnp.bfloat16)
    K = _CHAN_SWEEP_RADIX
    # chunk grid-bra channels by bra-bit range, preserving call order
    chunks = []          # (b0, [entries])
    inblock = []
    for wi, (kind, t, b) in enumerate(program):
        if b < CLUSTER_QUBITS:
            inblock.append((t, b, None, wi))
            continue
        placed = False
        for ch in chunks:
            if ch[0] <= b < ch[0] + min(K, nn - ch[0]):
                ch[1].append((t, b, b - ch[0], wi))
                placed = True
                break
        if not placed:
            b0 = max(CLUSTER_QUBITS, min(b, nn - K))
            chunks.append((b0, [(t, b, b - b0, wi)]))
    if not chunks:
        chunks.append((CLUSTER_QUBITS, []))
    if inblock:
        chunks[0][1][:0] = inblock
    for b0, entries in chunks:
        k = min(K, nn - b0)
        amps = _chan_sweep_pass(
            amps, wmat, xmats, num_bits=nn, b0=b0, k=k,
            chunk=tuple(entries), xmap_items=xmap_items,
            interpret=interpret)
    return amps
