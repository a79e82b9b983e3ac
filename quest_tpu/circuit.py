"""Circuit scheduler: fold gate streams into fused window passes.

The reference executes circuits gate-at-a-time through its dispatch layer
(QuEST/src/QuEST.c) — every gate is one full sweep of the amplitude array.
This module is the TPU-native replacement for that dispatch loop: a
*scheduler* that plans a whole gate list into a short program of HBM
passes.  The DEFAULT planner (plan_circuit_windowed) emits

    ('winfused', k, As, Bs, apply_a, apply_b[, mask])
                              one zero-relocation HBM pass applying the
                              rank-R operator [mask (.)] sum_r B_r (x) A_r
                              with A on lane qubits [0,7) and B on the
                              contiguous window [k, k+7) — k is chosen per
                              pass, so high qubits are reached by AIMING
                              the window at them (ops/fused.py
                              apply_window_stack).  The optional trailing
                              mask (SoA (2,128,128), absent in 6-tuple
                              producers like fused_qft and the native
                              materializer) holds diagonal crossing gates
                              as one elementwise multiply (fold_mask)
    ('apply',   targets, mat) fallback standard kernel (gates no window
                              covers, e.g. a dense 2q gate on two
                              far-apart high qubits)

2q gates straddling lane x window fold through their operator-Schmidt
terms (schmidt_terms_2q): rank x2 for controlled gates, x4 generically,
capped at RANK_CAP per pass.

The legacy 'paged' planner (plan_circuit_py, QT_PLANNER=paged) instead
pins the window to [7,14) and relocates high qubits into it:

    ('fused',    matA, matB)  cluster pass on qubits [0,14)
    ('swapfused', h, b, m, As, Bs)  segment swap fused into a cluster pass
    ('segswap',  a, b, m)     exchange bit segments [a,a+m) <-> [b,b+m) as
                              ONE tile-aligned transpose — the single-chip
                              analogue of the reference's distributed
                              SWAP-relocalization
                              (QuEST_cpu_distributed.c:1503-1545)

Planning is pure Python over *static* gate structure (targets), so it runs
once at trace time; gate matrices stay traced values, so parameterised
circuits recompile only when their shape changes, never when angles change.

Both planning algorithms are implemented natively in C++
(native/scheduler.cc) for large gate streams; plan_circuit() transparently
uses the native planner when the library is built (see native/__init__.py).
"""

from __future__ import annotations

import contextlib
import itertools
import math
from dataclasses import dataclass
from functools import lru_cache
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import telemetry
from .ops import cplx, fused, kernels

LANE = fused.LANE_QUBITS            # 7
WINDOW = fused.CLUSTER_QUBITS       # 14
DIM = fused.CLUSTER_DIM             # 128
_LOOKAHEAD = 256                    # next-use horizon for eviction choice


@dataclass(frozen=True)
class Gate:
    """One dense gate: ``mat`` is stacked SoA (2, 2^k, 2^k) over ``targets``
    (targets[0] = least-significant matrix bit, reference convention)."""

    targets: Tuple[int, ...]
    mat: object  # array-like; may be a traced jnp value


def controlled_dense(mat_soa, num_controls: int, control_states=()):
    """Embed a k-qubit SoA matrix as a (num_controls+k)-qubit controlled
    matrix (controls = the high matrix bits; control i is matrix bit k+i,
    conditioned on ``control_states[i]``, default 1) so controlled gates can
    enter the dense scheduling path.  Concrete numpy inputs stay numpy so
    the scheduler can still Schmidt-decompose the result."""
    m = np.asarray(mat_soa) if not isinstance(mat_soa, jnp.ndarray) else mat_soa
    d = m.shape[-1]
    nc = int(num_controls)
    full = d << nc
    states = tuple(int(s) for s in control_states) or (1,) * nc
    active = 0
    for i, s in enumerate(states):
        active |= (s & 1) << i
    idx = np.arange(full)
    ci, ti = idx // d, idx % d
    same_c = ci[:, None] == ci[None, :]
    gate_mask = same_c & (ci == active)[:, None]
    eye_mask = same_c & (ci != active)[:, None] & (idx[:, None] == idx[None, :])
    row = np.broadcast_to(ti[:, None], (full, full))
    col = np.broadcast_to(ti[None, :], (full, full))
    if isinstance(m, np.ndarray):
        out = m[:, row, col] * gate_mask.astype(m.dtype)
        out[0] += eye_mask.astype(m.dtype)
        return out
    out = m[:, row, col] * jnp.asarray(gate_mask, m.dtype)
    return out.at[0].add(jnp.asarray(eye_mask, m.dtype))


# ---------------------------------------------------------------------------
# Permutation gate family: classification + gather-shaped lowering
# (docs/design.md §28)
# ---------------------------------------------------------------------------

# Composed gather tables are 2^|union| entries: past this width a run is
# split into several gather passes instead of one giant index table.
PERM_GATHER_MAX_BITS = 10


def perm_fast_enabled() -> bool:
    """Whether the permutation fast paths are planned: not on the TPU,
    and not under QT_PERM_FAST=off/0/false/no, which reroutes the family
    through the dense matmul pipeline (the A/B baseline
    scripts/bench_sparse.py times).  On the TPU permutation gates stay in
    the in-place window passes: the fast paths' gather and flip ops write
    a second state, which a 30-qubit state has no room for on a 16 GiB
    chip, and the TPU compiler took more than 12 GB of host memory for
    them in a 28-qubit drain over four chips."""
    import os

    raw = os.environ.get("QT_PERM_FAST", "").strip().lower()
    return raw not in ("off", "0", "false", "no") and \
        fused._interpret_default()


def _classify_pi(pi):
    """Classify an index permutation ``new[i] = old[pi[i]]`` into its
    cheapest lowering family: ``("xor", c)`` when pi is ``i ^ c``
    (multi-qubit NOT — one static bit flip, no gather), ``("relabel", s)``
    when pi only reroutes index BITS (output matrix bit j reads input
    matrix bit s[j] — pure qubit relabeling, foldable into Qureg._perm),
    else ``("gather", pi)`` (general one-hot row permutation, e.g. the
    Toffoli's conditional flip)."""
    pi = np.asarray(pi, dtype=np.int64)
    d = len(pi)
    k = d.bit_length() - 1
    idx = np.arange(d)
    c = int(pi[0])
    if np.array_equal(pi, idx ^ c):
        return ("xor", c)
    if c == 0:
        s = []
        for j in range(k):
            img = int(pi[1 << j])
            if img and not (img & (img - 1)):
                s.append(img.bit_length() - 1)
        if len(s) == k and len(set(s)) == k:
            lin = np.zeros(d, dtype=np.int64)
            for j in range(k):
                lin |= ((idx >> j) & 1) << s[j]
            if np.array_equal(pi, lin):
                return ("relabel", tuple(s))
    return ("gather", tuple(int(p) for p in pi))


@lru_cache(maxsize=512)
def _classify_perm_cached(shape, dstr, buf):
    m = np.frombuffer(buf, dtype=np.dtype(dstr)).reshape(shape)
    if m[1].any():
        return None
    re = m[0]
    if not np.all((re == 0) | (re == 1)):
        return None
    if not (np.all(re.sum(axis=0) == 1) and np.all(re.sum(axis=1) == 1)):
        return None
    return _classify_pi(re.argmax(axis=1))


def classify_permutation_gate(mat):
    """``None | ("xor", c) | ("relabel", s) | ("gather", pi)`` for a
    concrete stacked SoA gate matrix (X, CNOT, Toffoli/MCX, SWAP,
    multi-qubit NOT and products thereof).  Traced values and
    non-permutation matrices return None.  Cached on the matrix bytes —
    permutation-dominated streams repeat a handful of tiny matrices."""
    if not isinstance(mat, np.ndarray) or mat.ndim != 3:
        return None
    if mat.shape[0] != 2 or mat.shape[1] != mat.shape[2]:
        return None
    return _classify_perm_cached(mat.shape, mat.dtype.str, mat.tobytes())


def compose_permutation_run(gates):
    """Fold a run of permutation-classified gates (stream order) into ONE
    index permutation over the sorted union of their targets: returns
    ``(union, pi)`` with ``new[i] = old[pi[i]]`` in union-bit order, or
    None when any gate fails classification.  Exact integer arithmetic
    throughout, so executing the composed table is bit-identical to the
    dense matrix product."""
    union = sorted({t for g in gates for t in g.targets})
    upos = {q: j for j, q in enumerate(union)}
    d = 1 << len(union)
    idx = np.arange(d)
    total = idx.copy()
    for g in gates:
        cls = classify_permutation_gate(g.mat)
        if cls is None:
            return None
        kind, payload = cls
        pos = [upos[t] for t in g.targets]
        if kind == "xor":
            mask = 0
            for b, p in enumerate(pos):
                if (payload >> b) & 1:
                    mask |= 1 << p
            lifted = idx ^ mask
        else:
            if kind == "relabel":
                kg = len(pos)
                gidx = np.arange(1 << kg)
                pi_g = np.zeros(1 << kg, dtype=np.int64)
                for j in range(kg):
                    pi_g |= ((gidx >> j) & 1) << payload[j]
            else:
                pi_g = np.asarray(payload, dtype=np.int64)
            sub = np.zeros(d, dtype=np.int64)
            for b, p in enumerate(pos):
                sub |= ((idx >> p) & 1) << b
            mapped = pi_g[sub]
            lifted = idx
            for p in pos:
                lifted = lifted & ~(1 << p)
            for b, p in enumerate(pos):
                lifted |= ((mapped >> b) & 1) << p
        total = total[lifted]
    return tuple(union), tuple(int(p) for p in total)


def lower_permutation_run(gates, num_qubits: int):
    """Lower a permutation-classified gate run to matrix-free plan ops:
    greedy-group stream neighbors while the composed gather table stays
    within PERM_GATHER_MAX_BITS, then emit per group the cheapest op its
    composed permutation admits — ``("xor", flips)`` static flip,
    ``("permute", perm)`` full-register bit relabel (one coalesced
    transpose pass, kernels.permute_qubits), or
    ``("gatherperm", union, pi)`` (kernels.apply_index_permutation)."""
    ops: List[tuple] = []
    group: List[Gate] = []
    gbits: set = set()

    def flush():
        if not group:
            return
        union, pi = compose_permutation_run(group)
        kind, payload = _classify_pi(pi)
        if kind == "xor":
            flips = tuple(union[j] for j in range(len(union))
                          if (payload >> j) & 1)
            if flips:
                ops.append(("xor", flips))
        elif kind == "relabel":
            perm = list(range(num_qubits))
            for j, q in enumerate(union):
                perm[q] = union[payload[j]]
            if perm != list(range(num_qubits)):
                ops.append(("permute", tuple(perm)))
        else:
            ops.append(("gatherperm", tuple(union), tuple(payload)))
        group.clear()
        gbits.clear()

    for g in gates:
        b = set(g.targets)
        if group:
            nb = gbits | b
            # cap the composed table AND the kernel's contiguous gather
            # field — grouping distant gates would force the gather
            # lowering onto its dense-matrix fallback
            if (len(nb) > PERM_GATHER_MAX_BITS
                    or max(nb) - min(nb) >= kernels._GATHER_FIELD_MAX_BITS):
                flush()
        group.append(g)
        gbits |= b
    flush()
    return ops


def perm_item_entry(targets, mat):
    """Window-planner entry for one gate: ``("relabel", pairs)`` when the
    gate is a pure bit relabel under QT_PERM_FAST — pairs =
    ``((q, rho(q)), ...)`` meaning qubit q's new content comes from qubit
    rho(q), the fold plan_remap_windows applies to the live permutation
    with ZERO data motion — else the plain sorted bit tuple the dense
    window planner localizes."""
    if perm_fast_enabled():
        cls = classify_permutation_gate(mat)
        if cls is not None and cls[0] == "relabel":
            s = cls[1]
            pairs = tuple(sorted(
                (targets[j], targets[s[j]])
                for j in range(len(targets)) if s[j] != j))
            return ("relabel", pairs) if pairs else ()
    return tuple(sorted(targets))


def _is_relabel_entry(entry) -> bool:
    """True for the tagged ``("relabel", pairs)`` window-planner entry
    (robust to the list-of-list mangling introspect._predict_cached
    applies to its memo key)."""
    return len(entry) == 2 and isinstance(entry[0], str) \
        and entry[0] == "relabel"


# ---------------------------------------------------------------------------
# Cluster embedding: k-qubit matrix -> 128x128 via static index arrays
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _embed_indices(bits: Tuple[int, ...]):
    """Static (row, col, mask) arrays embedding a 2^k matrix on cluster bits
    ``bits`` into the 128x128 cluster space: E[i,j] = U[r[i,j], c[i,j]] *
    mask[i,j] — the insertZeroBit index algebra of the reference
    (QuEST_cpu.c:1901-1985) expressed as precomputed gathers."""
    k = len(bits)
    idx = np.arange(DIM)
    sub = np.zeros(DIM, dtype=np.int64)
    for pos, b in enumerate(bits):
        sub |= ((idx >> b) & 1) << pos
    rest = idx.copy()
    for b in bits:
        rest &= ~(1 << b)
    # qlint: allow(f64-literal): host-side plan-table constant — cast to the register dtype at embed time, never shipped to the device as f64
    mask = (rest[:, None] == rest[None, :]).astype(np.float64)
    row = sub[:, None] * np.ones((1, DIM), dtype=np.int64)
    col = np.ones((DIM, 1), dtype=np.int64) * sub[None, :]
    return row, col, mask


def embed_in_cluster(mat_soa, bits: Tuple[int, ...]):
    """SoA (2, 2^k, 2^k) gate on cluster bits -> SoA (2, 128, 128).

    Concrete numpy inputs stay numpy: plan materialization outside jit
    (fusion drains) must not issue per-gate eager device ops, each a
    dispatch and a host round trip."""
    row, col, mask = _embed_indices(tuple(bits))
    if isinstance(mat_soa, np.ndarray):
        # the advanced index behind a slice comes back non-C-contiguous
        # (strides (8, 2048, 16)), which takes matmul off BLAS
        return np.ascontiguousarray(
            mat_soa[:, row, col] * mask.astype(mat_soa.dtype))
    m = jnp.asarray(mat_soa)
    return m[:, row, col] * jnp.asarray(mask, m.dtype)


def soa_matmul(a, b):
    """Complex matrix product of stacked SoA matrices (numpy in ->
    numpy out, see embed_in_cluster)."""
    if isinstance(a, np.ndarray) and isinstance(b, np.ndarray):
        re = a[0] @ b[0] - a[1] @ b[1]
        im = a[0] @ b[1] + a[1] @ b[0]
        return np.stack([re, im])
    hi = jax.lax.Precision.HIGHEST
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    re = jnp.matmul(a[0], b[0], precision=hi) - jnp.matmul(a[1], b[1], precision=hi)
    im = jnp.matmul(a[0], b[1], precision=hi) + jnp.matmul(a[1], b[0], precision=hi)
    return jnp.stack([re, im])


_FOLDS_STRUCTURED = telemetry.counter_key("plan_folds_total",
                                          path="structured")
_FOLDS_DENSE = telemetry.counter_key("plan_folds_total", path="dense")
# >0 while a dry run (explain_circuit, the governor's predictor,
# fusion.plan_items_quiet) plans: fold_gate and the fusion drain's
# per-window observations then record nothing, since a dry run mutates
# no telemetry
PLAN_QUIET: List[int] = [0]


@contextlib.contextmanager
def quiet_planning():
    """Plan as a dry run: no fold inside counts in telemetry."""
    PLAN_QUIET[0] += 1
    try:
        yield
    finally:
        PLAN_QUIET[0] -= 1


def fold_gate(mat_soa, bits: Tuple[int, ...], acc):
    """``embed_in_cluster(mat_soa, bits) @ acc`` for a SoA (2, 2^k, 2^k)
    gate on cluster bits ``bits`` and a SoA (2, 128, 128) accumulator
    term; ``acc`` None (identity) returns the embedding itself.

    Concrete numpy operands contract the gate over its own k row bits
    (2^k * 128^2 complex multiply-adds, not the dense 128^3 product of
    the zero-padded embedding); traced or device operands keep the dense
    soa_matmul.  Each product counts in ``plan_folds_total{path}``."""
    if acc is None:
        return embed_in_cluster(mat_soa, bits)
    structured = isinstance(mat_soa, np.ndarray) and isinstance(acc,
                                                                np.ndarray)
    if not PLAN_QUIET[0]:
        telemetry.inc_key(_FOLDS_STRUCTURED if structured else _FOLDS_DENSE)
    if not structured:
        return soa_matmul(embed_in_cluster(mat_soa, bits), acc)
    k = len(bits)
    dt = np.result_type(mat_soa, acc)
    cdt = np.result_type(dt, np.complex64)
    u = (mat_soa[0] + 1j * mat_soa[1]).astype(cdt).reshape((2,) * (2 * k))
    a = (acc[0] + 1j * acc[1]).astype(cdt).reshape((2,) * LANE + (DIM,))
    # row bit b of the cluster is axis LANE-1-b of ``a``; matrix bit p
    # (targets[p], p = 0 least significant) is axis k-1-p (out) and
    # 2k-1-p (in) of ``u``
    out = np.tensordot(u, a, axes=([2 * k - 1 - p for p in range(k)],
                                   [LANE - 1 - b for b in bits]))
    out = np.moveaxis(out, list(range(k)),
                      [LANE - 1 - bits[k - 1 - i] for i in range(k)])
    out = out.reshape(DIM, DIM)
    return np.stack([out.real, out.imag]).astype(dt, copy=False)


_EYE128 = None


def _eye_cluster():
    global _EYE128
    if _EYE128 is None:
        _EYE128 = np.stack([np.eye(DIM), np.zeros((DIM, DIM))])
    return _EYE128


# ---------------------------------------------------------------------------
# Operator-Schmidt decomposition of concrete 2q gates (cross folds)
# ---------------------------------------------------------------------------


_SCHMIDT_TOL = 1e-7


_SCHMIDT_CACHE_MAX = 4096
_schmidt_cache: dict = {}


def schmidt_terms_2q(mat_soa) -> Optional[List[tuple]]:
    """Operator-Schmidt decomposition of a CONCRETE SoA (2,4,4) 2q gate:
    U = sum_r hi_r (x) lo_r over (matrix bit 1, matrix bit 0).  Returns
    [(lo_soa, hi_soa), ...] (each SoA (2,2,2)) with len = the operator
    Schmidt rank — 1 for product gates, 2 for CNOT/CZ/controlled-phase,
    4 generically — or None for traced matrices (rank unknowable at plan
    time).  Cuts the cross-fold rank of the dominant controlled gates from
    4 to 2 vs the generic |a><b| decomposition."""
    if isinstance(mat_soa, jax.core.Tracer):
        return None
    try:
        m = np.asarray(mat_soa)
    # qlint: allow(broad-except): non-materializable values raise framework-version-dependent types; any failure means "not concrete" and the Schmidt path is skipped
    except Exception:  # pragma: no cover - any non-materializable value
        return None
    if m.dtype == object or m.shape != (2, 4, 4):
        return None
    key = (m.dtype.str, m.tobytes())
    hit = _schmidt_cache.get(key)
    if hit is not None:
        return hit
    if len(_schmidt_cache) >= _SCHMIDT_CACHE_MAX:  # bound: drop oldest
        _schmidt_cache.pop(next(iter(_schmidt_cache)))
    u = m[0] + 1j * m[1]
    # row index = 2*b1 + b0; regroup to T[(b1,b1'),(b0,b0')]
    t = u.reshape(2, 2, 2, 2).transpose(0, 2, 1, 3).reshape(4, 4)
    uu, s, vh = np.linalg.svd(t)
    # Truncation threshold scales with the dtype's working precision and is
    # relative to the largest singular value: a fixed 1e-7 would silently
    # flatten small-angle f64 controlled rotations to rank 1 (~1e-7 error
    # where eager f64 dispatch gives ~1e-16).  A zero matrix keeps its
    # leading (zero) term so the rank is always >= 1 and fold_cross never
    # sees an empty decomposition.
    eps = _SCHMIDT_TOL if m.dtype == np.float32 else 1e-12
    tol = eps * max(float(s[0]), 1.0)
    keep = [r for r in range(4) if s[r] > tol] or [0]
    terms = []
    for r in keep:
        hi = (np.sqrt(s[r]) * uu[:, r]).reshape(2, 2)
        lo = (np.sqrt(s[r]) * vh[r, :]).reshape(2, 2)
        terms.append(
            (
                np.stack([lo.real, lo.imag]).astype(m.dtype),
                np.stack([hi.real, hi.imag]).astype(m.dtype),
            )
        )
    _schmidt_cache[key] = terms
    return terms


# ---------------------------------------------------------------------------
# Controlled-form decomposition: crossing gates as diagonal masks
# ---------------------------------------------------------------------------


def _concrete44(mat_soa):
    """np (2,4,4) array or None for traced/odd-shaped matrices."""
    if isinstance(mat_soa, jax.core.Tracer):
        return None
    try:
        m = np.asarray(mat_soa)
    # qlint: allow(broad-except): materialization failure of any type means "traced/odd value" — the concrete-matrix fast path just declines
    except Exception:  # pragma: no cover
        return None
    if m.dtype == object or m.shape != (2, 4, 4):
        return None
    return m


def _diag_tol(m) -> float:
    return 1e-6 if m.dtype == np.float32 else 1e-11


def diag4_2q(mat_soa):
    """The (4,) complex diagonal of a CONCRETE diagonal 2q gate (matrix-bit
    order: index = 2*b1 + b0), or None when traced/non-diagonal.  Diagonal
    crossing gates fold into a window pass's elementwise mask at NO rank
    cost (cf. the reference's phase kernels, which likewise touch no
    amplitude pairs: QuEST_cpu.c:3146-3361)."""
    m = _concrete44(mat_soa)
    if m is None:
        return None
    u = m[0] + 1j * m[1]
    d = np.diag(u)
    if np.abs(u - np.diag(d)).max() > _diag_tol(m) * max(np.abs(u).max(), 1.0):
        return None
    return d


_CTRL_CACHE_MAX = 4096
_ctrl_cache: dict = {}


def controlled_form_2q(mat_soa):
    """Decompose a CONCRETE 2q gate that is diagonal in one matrix bit
    ("controlled form": U = |0><0|_c (x) U0 + |1><1|_c (x) U1, covering
    CNOT / controlled-V / control-on-0 variants) into

        U = (post on acted bit) . diag(d4) . (pre on acted bit)

    with pre = W^H, post = U0 @ W for the eigendecomposition
    U0^H U1 = W diag(ev) W^H.  Returns (pre_soa(2,2,2), d4_soa(2,4),
    post_soa(2,2,2), acted_bit) or None (traced / not controlled-form /
    already fully diagonal).  The planner rewrites such gates so a
    lane-x-window crossing costs one elementwise mask instead of a
    rank-2 Kronecker fold (18.6 -> 4.5 ms measured per rank-4 pass)."""
    m = _concrete44(mat_soa)
    if m is None or diag4_2q(mat_soa) is not None:
        return None
    key = (m.dtype.str, m.tobytes())
    hit = _ctrl_cache.get(key, "miss")
    if hit != "miss":
        return hit
    if len(_ctrl_cache) >= _CTRL_CACHE_MAX:
        _ctrl_cache.pop(next(iter(_ctrl_cache)))
    u = m[0] + 1j * m[1]
    tol = _diag_tol(m) * max(np.abs(u).max(), 1.0)
    result = None
    for cb in (0, 1):
        # coupling between the two values of bit cb must vanish
        v4 = u.reshape(2, 2, 2, 2)  # [b1, b0, b1', b0']
        if cb == 0:
            coupling = np.abs(v4[:, 0, :, 1]).max() + np.abs(v4[:, 1, :, 0]).max()
            blocks = [v4[:, v, :, v] for v in (0, 1)]
        else:
            coupling = np.abs(v4[0, :, 1, :]).max() + np.abs(v4[1, :, 0, :]).max()
            blocks = [v4[v, :, v, :] for v in (0, 1)]
        if coupling > tol:
            continue
        u0, u1 = blocks
        v = u0.conj().T @ u1
        # eigendecomposition of the unitary V (normal matrix)
        if np.abs(v - np.diag(np.diag(v))).max() <= tol:
            w = np.eye(2, dtype=complex)
            ev = np.diag(v)
        else:
            ev, w = np.linalg.eig(v)
            w, _ = np.linalg.qr(w)  # orthonormalize (degenerate safety)
            # recompute ev against the orthonormalized columns
            ev = np.diag(w.conj().T @ v @ w)
        pre = w.conj().T
        post = u0 @ w
        acted = 1 - cb
        d4 = np.ones(4, dtype=complex)
        for ba in (0, 1):
            idx = (2 * ba + 1) if cb == 0 else (2 + ba)
            d4[idx] = ev[ba]
        # Verify the decomposition reconstructs the input: the eig + QR
        # orthonormalization can silently mis-decompose a pathological
        # near-degenerate or slightly non-unitary V (diag(W^H V W) drops
        # any off-diagonal residue).  On failure return None so the gate
        # takes the exact rank-2 Schmidt fold instead.
        if acted == 0:
            full_pre = np.kron(np.eye(2), pre)
            full_post = np.kron(np.eye(2), post)
        else:
            full_pre = np.kron(pre, np.eye(2))
            full_post = np.kron(post, np.eye(2))
        recon = full_post @ np.diag(d4) @ full_pre
        if np.abs(recon - u).max() > 16 * tol:
            continue
        dt = m.dtype
        result = (
            np.stack([pre.real, pre.imag]).astype(dt),
            np.stack([d4.real, d4.imag]).astype(dt),
            np.stack([post.real, post.imag]).astype(dt),
            acted,
        )
        break
    _ctrl_cache[key] = result
    return result


def rewrite_controlled_gates(glist: List[Gate]) -> List[Gate]:
    """Rewrite every concrete controlled-form 2q gate g as
    [pre(acted qubit), diagonal 2q gate, post(acted qubit)] so that if the
    gate ends up straddling a lane-x-window boundary, the diagonal part
    folds into the pass mask (rank-free) while pre/post fold as ordinary
    dense 1q gates.  Non-crossing placements lose nothing: all three
    pieces fold into the same side product."""
    out: List[Gate] = []
    for g in glist:
        cf = controlled_form_2q(g.mat) if len(g.targets) == 2 else None
        if cf is None:
            out.append(g)
            continue
        pre, d4, post, acted = cf
        tq = g.targets[acted]
        dd = np.zeros((2, 4, 4), dtype=d4.dtype)
        dd[0][np.diag_indices(4)] = d4[0]
        dd[1][np.diag_indices(4)] = d4[1]
        out.append(Gate((tq,), pre))
        out.append(Gate(g.targets, dd))
        out.append(Gate((tq,), post))
    return out


def is_identity_gate(mat_soa) -> bool:
    """Concrete and EXACTLY the identity, bitwise — the circuit
    optimizer's cancellation gate (optimizer.py): only a pair whose
    product hits exact 1.0/0.0 entries (X·X, CNOT·CNOT, SWAP·SWAP, any
    permutation pair) may be dropped without perturbing the drained
    state; a merely-near-identity product (H·H is ``1+2e-16`` on the
    f64 diagonal) must merge instead.  Accepts (2, s, s) and batched
    (B, 2, s, s) stacks (all elements must be the identity)."""
    if isinstance(mat_soa, jax.core.Tracer):
        return False
    m = np.asarray(mat_soa)
    if m.dtype == object or m.ndim not in (3, 4):
        return False
    eye = np.eye(m.shape[-1], dtype=m.dtype)
    return bool((m[..., 0, :, :] == eye).all()
                and (m[..., 1, :, :] == 0.0).all())


def is_diag_gate(mat_soa) -> bool:
    """Concrete and diagonal (any size) — such gates commute with a pass's
    diagonal mask and may keep folding after it."""
    if isinstance(mat_soa, jax.core.Tracer):
        return False
    try:
        m = np.asarray(mat_soa)
    # qlint: allow(broad-except): materialization failure of any type means "not concrete" — a non-diagonal answer is always safe (pass merely stops folding)
    except Exception:  # pragma: no cover
        return False
    if m.dtype == object or m.ndim != 3:
        return False
    u = m[0] + 1j * m[1]
    off = np.abs(u - np.diag(np.diag(u))).max()
    return bool(off <= _diag_tol(m) * max(np.abs(u).max(), 1.0))


# ---------------------------------------------------------------------------
# Scheduler
# ---------------------------------------------------------------------------


def _stack_sides(As, Bs):
    """Stack per-rank side matrices (None = identity) into (R, 2, 128, 128)
    arrays; stays numpy when every term is concrete (plan materialization
    outside jit must not issue eager device ops)."""
    eye = _eye_cluster()
    if all(x is None or isinstance(x, np.ndarray) for x in As + Bs):
        dts = [x.dtype for x in As + Bs if x is not None]
        # qlint: allow(f64-literal): all-identity fallback dtype for a host-side numpy plan table; the register dtype overrides it whenever any real term exists
        dt = dts[0] if dts else np.float64
        a = np.stack([x if x is not None else eye.astype(dt) for x in As])
        b = np.stack([x if x is not None else eye.astype(dt) for x in Bs])
        return a, b
    a = jnp.stack([jnp.asarray(x) if x is not None else jnp.asarray(eye)
                   for x in As])
    b = jnp.stack([jnp.asarray(x) if x is not None else jnp.asarray(eye)
                   for x in Bs])
    return a, b


_CROSS_RANK = 4  # rank of the |a><b| (x) U_ab decomposition of a 2q gate


class _FoldAcc:
    """Accumulator for the window operator as a rank-R Kronecker sum
    sum_r B_r (x) A_r (A_r on lanes 0-6, B_r on sublanes 7-13): pure
    cluster gates multiply into every term; one lane-x-sublane 2q gate
    raises R from 1 to 4 via its |a><b| block decomposition
    (fused.apply_cluster_stack executes the sum in one HBM pass).  Shared
    by the planner (_Plan) and the native-plan materializer."""

    def __init__(self):
        self.As = [None]  # per-rank traced (2,128,128); None = identity
        self.Bs = [None]
        self.rank = 1
        self.count = 0

    def fold(self, cluster: str, bits: Tuple[int, ...], mat):
        accs = self.As if cluster == "A" else self.Bs
        for r in range(self.rank):
            accs[r] = fold_gate(mat, bits, accs[r])
        self.count += 1

    def fold_cross(self, phys: Tuple[int, ...], mat):
        """Fold a 2q gate with one lane and one sublane target; requires
        rank == 1 (caller flushes first otherwise)."""
        assert self.rank == 1
        if not isinstance(mat, np.ndarray):
            mat = jnp.asarray(mat)
        if phys[0] < LANE:
            la, sb = phys[0], phys[1]
            def block(a, b):
                return mat[:, 2 * a:2 * a + 2, 2 * b:2 * b + 2]
        else:
            sb, la = phys[0], phys[1]
            def block(a, b):
                return mat[:, a::2, b::2]
        A0, B0 = self.As[0], self.Bs[0]
        As, Bs = [], []
        for a in (0, 1):
            for b in (0, 1):
                eb_np = np.zeros((2, 2, 2))
                eb_np[0, a, b] = 1.0
                As.append(fold_gate(block(a, b), (la,), A0))
                Bs.append(fold_gate(eb_np, (sb - LANE,), B0))
        self.As, self.Bs = As, Bs
        self.rank = _CROSS_RANK
        self.count += 1

    def stacks(self):
        return _stack_sides(self.As, self.Bs)

    def reset(self):
        self.As, self.Bs = [None], [None]
        self.rank = 1
        self.count = 0


class _WinAcc:
    """Accumulator for one offset-window pass: the operator on
    {lane qubits [0,7)} x {window qubits [k, k+7)} as a rank-R Kronecker
    sum sum_r B_r (x) A_r.  Like _FoldAcc but bound to a window offset and
    using the operator-Schmidt decomposition for concrete cross gates
    (rank x2 for CNOT/CZ instead of x4), with rank capped by the planner."""

    def __init__(self, k: int):
        self.k = k
        self.As: List[Optional[object]] = [None]
        self.Bs: List[Optional[object]] = [None]
        self.rank = 1
        self.count = 0
        self.a_used = False
        self.b_used = False
        # elementwise post-mask over (window bit, lane bit) from diagonal
        # crossing gates: out = mask (.) (sum_r B_r (x) A_r) x
        self.mask: Optional[np.ndarray] = None  # complex (128, 128)

    def fold_side(self, side: str, bits: Tuple[int, ...], mat):
        accs = self.As if side == "A" else self.Bs
        for r in range(self.rank):
            accs[r] = fold_gate(mat, bits, accs[r])
        if side == "A":
            self.a_used = True
        else:
            self.b_used = True
        self.count += 1

    def fold_cross(self, lane_bit: int, win_bit: int, mat,
                   lane_is_bit0: bool):
        """Fold a 2q gate with one lane target and one window target.
        ``win_bit`` is window-relative (0-6).  Concrete matrices use their
        Schmidt terms; traced matrices the generic 4-term |a><b| split."""
        terms = schmidt_terms_2q(mat)
        if terms is not None:
            pairs = [
                (lo, hi) if lane_is_bit0 else (hi, lo) for lo, hi in terms
            ]
        else:
            mat = jnp.asarray(mat)
            pairs = []
            for a in (0, 1):
                for b in (0, 1):
                    if lane_is_bit0:
                        lane_m = mat[:, 2 * a:2 * a + 2, 2 * b:2 * b + 2]
                    else:
                        lane_m = mat[:, a::2, b::2]
                    win_m = np.zeros((2, 2, 2))
                    win_m[0, a, b] = 1.0
                    pairs.append((lane_m, win_m))
        As, Bs = [], []
        for lane_m, win_m in pairs:
            for r in range(self.rank):
                As.append(fold_gate(lane_m, (lane_bit,), self.As[r]))
                Bs.append(fold_gate(win_m, (win_bit,), self.Bs[r]))
        self.As, self.Bs = As, Bs
        self.rank = len(As)
        self.a_used = True
        self.b_used = True
        self.count += 1

    def fold_mask(self, lane_bit: int, win_bit: int, d4, lane_is_bit0: bool):
        """Fold a DIAGONAL crossing 2q gate as an elementwise post-mask:
        no rank growth, one VPU multiply in the kernel.  ``d4``: complex
        (4,) diagonal in matrix-bit order (index 2*b1 + b0)."""
        lb = (np.arange(DIM) >> lane_bit) & 1
        wb = (np.arange(DIM) >> win_bit) & 1
        if lane_is_bit0:
            idx = 2 * wb[:, None] + lb[None, :]
        else:
            idx = 2 * lb[None, :] + wb[:, None]
        m = np.asarray(d4, dtype=complex)[idx]          # (win/sublane, lane)
        self.mask = m if self.mask is None else self.mask * m
        self.count += 1

    def mask_soa(self):
        """SoA (2, 128, 128) mask array, or None."""
        if self.mask is None:
            return None
        return np.stack([self.mask.real, self.mask.imag])

    def stacks(self):
        return _stack_sides(self.As, self.Bs)


class _Plan:
    """Mutable planning state; emits the op program."""

    def __init__(self, num_qubits: int):
        self.n = num_qubits
        # pos[logical qubit] = current physical position
        self.pos = list(range(num_qubits))
        self.ops: List[tuple] = []
        self.acc = _FoldAcc()
        # relocation segment (page) size bounds: m <= seg_max by available
        # high bits; m >= seg_min = 3 keeps the 2^m segment axis a multiple
        # of the 8-sublane tile (no transpose padding) except when fewer
        # high bits exist at all
        self.seg_max = min(LANE, max(0, num_qubits - WINDOW))
        self.seg_min = min(3, self.seg_max) if self.seg_max > 0 else 0

    def _fold(self, cluster: str, bits: Tuple[int, ...], mat):
        self.acc.fold(cluster, bits, mat)

    def flush(self):
        if self.acc.count == 0:
            return
        a, b = self.acc.stacks()
        self.ops.append(("fused", a, b))
        self.acc.reset()

    def _emit_segswap(self, h: int, b: int, m: int):
        """Exchange bit segments [h, h+m) <-> [b, b+m)."""
        self.flush()
        self.ops.append(("segswap", h, b, m))
        newpos = []
        for p in self.pos:
            if b <= p < b + m:
                newpos.append(h + (p - b))
            elif h <= p < h + m:
                newpos.append(b + (p - h))
            else:
                newpos.append(p)
        self.pos = newpos

    def final_restore(self):
        """Return every qubit label to its home position with a MINIMAL
        greedy block-sort of segment swaps (replaying the whole swap stack
        in reverse would cost one transpose pass per historical swap; the
        net permutation usually collapses to a handful)."""
        self.flush()
        n = self.n
        while True:
            q = next((i for i in range(n) if self.pos[i] != i), None)
            if q is None:
                break
            assert q >= LANE  # lane bits are never relocated
            p = self.pos[q]  # where logical q currently lives (p > q)
            m = 1
            while (
                q + m < p
                and q + m < n
                and p + m < n
                and self.pos[q + m] == p + m
            ):
                m += 1
            self._emit_segswap(p, q, m)


def _cluster_of(phys: Sequence[int]) -> Optional[str]:
    if all(p < LANE for p in phys):
        return "A"
    if all(LANE <= p < WINDOW for p in phys):
        return "B"
    return None


def _is_cross2(phys: Sequence[int]) -> bool:
    """2q gate with one lane (0-6) and one sublane (7-13) target — foldable
    as a rank-4 Kronecker sum (_Plan._fold_cross)."""
    if len(phys) != 2:
        return False
    a, b = phys
    return (a < LANE <= b < WINDOW) or (b < LANE <= a < WINDOW)


def materialize_plan(structural: Sequence[tuple],
                     gates: Sequence[Gate]) -> List[tuple]:
    """Turn a structural plan (gate indices, from the native C++ scheduler)
    into the executable op list by folding the referenced gate matrices.

    Fused ops carry an ordered entry list [(side, gate_idx, bits), ...]
    with side 0 = lane cluster A, 1 = sublane cluster B, 2 = cross
    (bits = the two physical targets); replayed through _FoldAcc so the
    result is numerically identical to the Python planner's."""
    ops: List[tuple] = []
    for op in structural:
        if op[0] == "fused":
            acc = _FoldAcc()
            for side, gi, bits in op[1]:
                if side == 2:
                    acc.fold_cross(tuple(bits), gates[gi].mat)
                else:
                    acc.fold("A" if side == 0 else "B", tuple(bits),
                             gates[gi].mat)
            a, b = acc.stacks()
            ops.append(("fused", a, b))
        elif op[0] == "apply":
            ops.append(("apply", op[2], gates[op[1]].mat))
        else:
            ops.append(op)
    return ops


def _peephole(ops: List[tuple], num_qubits: int) -> List[tuple]:
    """Merge each segment swap with the cluster pass that follows it into
    one fused swap+cluster HBM pass (fused.apply_swap_cluster_stack) when
    the swap's 2^m super-block fits in VMEM."""
    out: List[tuple] = []
    i = 0
    while i < len(ops):
        op = ops[i]
        if (
            op[0] == "segswap"
            and i + 1 < len(ops)
            and ops[i + 1][0] == "fused"
            and op[3] <= fused.MAX_FUSED_SWAP_M
            and op[1] >= WINDOW
            and LANE <= op[2]
            and op[2] + op[3] <= WINDOW
        ):
            out.append(("swapfused", op[1], op[2], op[3],
                        ops[i + 1][1], ops[i + 1][2]))
            i += 2
        else:
            out.append(op)
            i += 1
    return out


def _side_split_enabled() -> bool:
    import os

    return os.environ.get("QT_SIDE_SPLIT", "0") == "1"


def split_plan_sides(ops: Sequence[tuple]) -> List[tuple]:
    """Side-minimisation rewrite (VERDICT r3 item 6): a run of rank-1
    maskless dual-side window passes (B_i (x) A_i applied in order)
    equals (prod B_i) o (prod A_i) because the A side always acts on lane
    qubits [0,7) and the B sides on window qubits >= 7 — disjoint, so
    they commute.  The round-3 profile prices a single-side pass at the
    ~1.25 ms HBM floor but a dual-side pass at ~2.1 ms (the second
    side's bf16 MXU decomposition can't hide under one sweep's
    bandwidth), so rewriting j >= 2 dual passes into j B-only passes +
    ONE merged A pass trades j*0.85 ms of side cost for one 1.25 ms
    sweep — a win from j = 2.

    Barriers (anything whose lane action is tied to the window or
    non-commuting): rank > 1 passes, masked passes, and every
    non-winfused op.  Regions with fewer than 2 deferrable A sides are
    left untouched (splitting a lone dual pass LOSES: 2.5 vs 2.1 ms)."""
    def deferrable(op):
        return (op[0] == "winfused" and np.shape(op[2])[0] == 1
                and (len(op) < 7 or op[6] is None) and op[4]
                and isinstance(op[2], np.ndarray))

    def mask_commutes(op, touched: set) -> bool:
        """A masked B-only pass is transparent to a pending A product
        when the mask's lane dependence misses every lane bit the
        product touches: m[w, l] must be constant over each touched
        bit's flip."""
        if not isinstance(op[6], np.ndarray):
            return False
        m = op[6][0] + 1j * op[6][1]           # (window, lane)
        cols = np.arange(DIM)
        for l in touched:
            if not np.allclose(m, m[:, cols ^ (1 << l)], atol=1e-12):
                return False
        return True

    def lane_bits_of(a) -> set:
        """Lane bits a (2,128,128) concrete A-operator acts on
        non-trivially: bit l is untouched iff A factors as I_l (x) A',
        i.e. BOTH off-blocks over l vanish (every A[i, j] with bit l of
        i and j differing — not just the single-flip diagonal, which
        misses multi-bit operators like X_l X_m) AND the two same-bit
        blocks are equal."""
        u = a[0] + 1j * a[1]
        idx = np.arange(DIM)
        out = set()
        for l in range(LANE):
            r0 = idx[((idx >> l) & 1) == 0]
            r1 = r0 ^ (1 << l)
            off = max(np.abs(u[np.ix_(r0, r1)]).max(),
                      np.abs(u[np.ix_(r1, r0)]).max())
            sym = np.abs(u[np.ix_(r0, r0)] - u[np.ix_(r1, r1)]).max()
            if off > 1e-12 or sym > 1e-12:
                out.add(l)
        return out

    out: List[tuple] = []
    region: List[tuple] = []

    def region_defer_count():
        return sum(1 for op, d in region if d)

    def flush_region():
        if region_defer_count() < 2:
            out.extend(op for op, _ in region)
            region.clear()
            return
        a_prod = None
        for op, d in region:
            if d:
                a_prod = (op[2][0] if a_prod is None
                          else soa_matmul(op[2][0], a_prod))
                if op[5]:  # B side survives as a single-side pass
                    out.append(("winfused", op[1], op[2], op[3],
                                False, True, None))
            else:
                out.append(op)
        out.append(("winfused", LANE, a_prod[None],
                    _eye_cluster().astype(a_prod.dtype)[None],
                    True, False, None))
        region.clear()

    touched: set = set()
    for op in ops:
        if deferrable(op):
            region.append((op, True))
            touched |= lane_bits_of(op[2][0])
            continue
        # transparent: pure-B rank-any maskless passes never touch lanes;
        # masked B-only passes are transparent when the mask's lane
        # dependence misses every touched bit
        if op[0] == "winfused" and not op[4]:
            if len(op) < 7 or op[6] is None or mask_commutes(op, touched):
                region.append((op, False))
                continue
        flush_region()
        touched = set()
        out.append(op)
    flush_region()
    return out


# Matrix operands of one megawin group are ALL VMEM-resident at once
# (per-pass state temporaries are sequential, the matrices are not), so the
# group closes when their total passes this budget — 4 MB leaves the
# 16 MB scoped VMEM room for the G-row state block in+out plus the active
# pass's temporaries at the megawin_row_cap sizing.
MEGA_MAT_BYTES = 4 << 20


def _winfused_mat_bytes(op) -> int:
    """f32 VMEM bytes of one winfused pass's matrix operands as the
    megakernel stages them: each side's planes [Re, Im, Re + Im]
    (fused._gauss_planes)."""
    nbytes = int(np.shape(op[2])[0]) * 2 * 3 * DIM * DIM * 4
    if len(op) > 6 and op[6] is not None:
        nbytes += 2 * DIM * DIM * 4
    return nbytes


def group_megawins(ops: Sequence[tuple], num_qubits: int) -> List[tuple]:
    """Megakernel grouping rewrite (docs/design.md §29): fold each run of
    consecutive winfused passes into ``("megawin", (passes...))`` groups
    that execute as ONE pallas_call — one HBM round-trip for the run.

    A pass joins the open group while the group stays inside the VMEM
    budget: G = 2^(kmax-7) block rows (every member's window bits must be
    block-local) can't exceed any member's row cap
    (fused.megawin_row_cap), the shard's row count, or the matrix-operand
    budget (MEGA_MAT_BYTES).  Wider-window passes (k > 10 at the default
    caps) stay on the per-pass route — already one HBM trip each.
    Groups of one are pointless and left ungrouped."""
    if num_qubits < WINDOW:
        return list(ops)
    nb = 1 << (num_qubits - WINDOW)
    out: List[tuple] = []
    group: List[tuple] = []
    kmax = allowed = mat_bytes = 0

    def close():
        nonlocal group, kmax, allowed, mat_bytes
        if len(group) >= 2:
            out.append(("megawin", tuple(group)))
        else:
            out.extend(group)
        group, kmax, allowed, mat_bytes = [], 0, 0, 0

    for op in ops:
        if op[0] != "winfused":
            close()
            out.append(op)
            continue
        cap = min(fused.megawin_row_cap(int(np.shape(op[2])[0]),
                                        num_qubits), nb)
        nbytes = _winfused_mat_bytes(op)
        if (1 << (op[1] - LANE)) > cap:
            close()
            out.append(op)           # window too wide to ever be grouped
            continue
        if group:
            nk = max(kmax, op[1])
            na = min(allowed, cap)
            if ((1 << (nk - LANE)) <= na
                    and mat_bytes + nbytes <= MEGA_MAT_BYTES):
                group.append(op)
                kmax, allowed, mat_bytes = nk, na, mat_bytes + nbytes
                continue
            close()
        group, kmax, allowed, mat_bytes = [op], op[1], cap, nbytes
    close()
    return out


def _no_phase(name: str) -> None:
    pass


def plan_circuit(gates: Sequence[Gate], num_qubits: int,
                 use_native: Optional[bool] = None,
                 planner: Optional[str] = None,
                 phase: Callable[[str], None] = _no_phase) -> List[tuple]:
    """Plan a gate list.

    ``planner``: 'windowed' (default — offset-window passes, zero
    relocation) or 'paged' (the segswap-relocation scheduler).  Overridable
    via QT_PLANNER.  The native C++ scheduler (native/scheduler.cc) is used
    when built; Python fallback otherwise — identical algorithm/output.

    ``phase``: the fusion drain's step marker (telemetry.phases), called
    with the name of each planning step as it starts: ``fusion.analyse``
    (the controlled-form rewrite and the per-gate ranks and flags the
    native scheduler takes), ``fusion.schedule`` (the structural
    scheduler; the Python windowed fallback, which rewrites, schedules
    and materializes in one, and the paged planner run wholly in it),
    ``fusion.materialize`` (the native plan's side, cross and mask
    folds) and ``fusion.group`` (the side split and megawin grouping).
    The default marks nothing, as for every caller outside a drain."""
    import os

    from . import native

    if planner is None:
        planner = os.environ.get("QT_PLANNER", "windowed")
    if planner not in ("windowed", "paged"):
        raise ValueError(
            f"unknown planner {planner!r}: expected 'windowed' or 'paged'"
        )
    if planner == "windowed":
        if use_native is None:
            use_native = native.native_available()
        ops = None
        if use_native and num_qubits >= WINDOW:
            # the controlled-form rewrite happens here so the C++ planner
            # sees the same (rewritten) gate stream as the Python one
            phase("fusion.analyse")
            glist = rewrite_controlled_gates(list(gates))
            xranks, flags = _gate_xranks(glist), _gate_flags(glist)
            phase("fusion.schedule")
            structural = native.plan_native_windowed(
                [g.targets for g in glist], num_qubits, xranks, flags)
            if structural is not None:
                phase("fusion.materialize")
                ops = materialize_windowed_plan(structural, glist)
        if ops is None:
            phase("fusion.schedule")
            ops = plan_circuit_windowed(gates, num_qubits)
        phase("fusion.group")
        if _side_split_enabled() and num_qubits >= WINDOW:
            ops = split_plan_sides(ops)
        if fused.megakernel_planning() and num_qubits >= WINDOW:
            ops = group_megawins(ops, num_qubits)
        return ops
    phase("fusion.schedule")
    if use_native is None:
        use_native = native.native_available()
    if use_native:
        structural = native.plan_native([g.targets for g in gates], num_qubits)
        if structural is not None:
            return _peephole(materialize_plan(structural, gates), num_qubits)
    return plan_circuit_py(gates, num_qubits)


def _gate_flags(gates: Sequence[Gate]) -> List[int]:
    """Per-gate diagonality flags for the native planner: bit 0 = diagonal
    matrix (commutes with a pass mask), bit 1 = concrete diagonal 2q
    (mask-foldable when crossing lane x window)."""
    out = []
    for g in gates:
        f = 0
        if is_diag_gate(g.mat):
            f |= 1
        if len(g.targets) == 2 and diag4_2q(g.mat) is not None:
            f |= 2
        out.append(f)
    return out


def _gate_xranks(gates: Sequence[Gate]) -> List[int]:
    """Per-gate cross-fold rank for the native planner: Schmidt rank for
    concrete 2q matrices, 4 for traced 2q matrices, 0 otherwise."""
    out = []
    for g in gates:
        if len(g.targets) == 2:
            terms = schmidt_terms_2q(g.mat)
            out.append(len(terms) if terms is not None else _CROSS_RANK)
        else:
            out.append(0)
    return out


def materialize_windowed_plan(structural: Sequence[tuple],
                              gates: Sequence[Gate]) -> List[tuple]:
    """Structural windowed plan (from native/scheduler.cc) -> executable op
    list.  Winfused ops carry (k, [(kind, gate_idx, bits), ...]) with kind
    0 = lane side A, 1 = window side B, 2 = cross (bits = (lane_bit,
    win_bit, lane_is_bit0)); replayed through _WinAcc so the result is
    numerically identical to the Python planner's."""
    ops: List[tuple] = []
    for op in structural:
        if op[0] == "winfused":
            k, entries = op[1], op[2]
            acc = _WinAcc(k)
            for kind, gi, bits in entries:
                if kind == 3:
                    acc.fold_mask(bits[0], bits[1], diag4_2q(gates[gi].mat),
                                  bool(bits[2]))
                elif kind == 2:
                    acc.fold_cross(bits[0], bits[1], gates[gi].mat,
                                   bool(bits[2]))
                else:
                    acc.fold_side("A" if kind == 0 else "B", tuple(bits),
                                  gates[gi].mat)
            a, b = acc.stacks()
            ops.append(("winfused", k, a, b, acc.a_used, acc.b_used,
                        acc.mask_soa()))
        elif op[0] == "apply":
            ops.append(_fallback_op(op[2], gates[op[1]].mat))
        else:
            ops.append(op)
    return ops


def _fallback_op(targets, mat) -> tuple:
    """The one-pass op for a gate no window pass covers: an exactly
    diagonal concrete gate becomes ("diag", targets, (2, 2^k) diagonal),
    an in-place pass over the canonical view
    (fused.apply_diagonal_canonical); anything else is ("apply", ...),
    the general layout-safe kernel, which writes a second state."""
    if not isinstance(mat, jax.core.Tracer):
        m = np.asarray(mat)
        if m.ndim == 3 and m.dtype != object:
            d = np.stack([np.diag(m[0]), np.diag(m[1])])
            if not (np.count_nonzero(m[0] - np.diag(d[0]))
                    or np.count_nonzero(m[1] - np.diag(d[1]))):
                return ("diag", tuple(targets), d)
    return ("apply", tuple(targets), mat)


def plan_circuit_py(gates: Sequence[Gate], num_qubits: int) -> List[tuple]:
    """Dependency-DAG list scheduler.

    Gates sharing no qubit commute, so the per-qubit program-order queues
    define the only real ordering constraints.  The scheduler repeatedly
    (1) folds every *ready* gate that sits inside a cluster, (2) when
    nothing folds, picks the segment swap that makes the most ready gates
    foldable (>= 2, else not worth the extra pass), (3) otherwise pops the
    smallest ready gate through the standard layout-safe kernel.  This
    batches a whole circuit layer per cluster pass instead of flushing at
    the first non-resident gate (the reference has no such scheduler at
    all — it dispatches gate-at-a-time, QuEST/src/QuEST.c)."""
    n = num_qubits
    glist = list(gates)
    if n < WINDOW:
        # Too small for the cluster kernel: program = plain per-gate applies.
        return [("apply", g.targets, g.mat) for g in glist]

    plan = _Plan(n)
    num_gates = len(glist)
    queues: List[List[int]] = [[] for _ in range(n)]
    for gi, g in enumerate(glist):
        for t in g.targets:
            queues[t].append(gi)
    heads = [0] * n

    def is_ready(gi):
        return all(
            heads[t] < len(queues[t]) and queues[t][heads[t]] == gi
            for t in glist[gi].targets
        )

    ready = sorted(gi for gi in range(num_gates) if is_ready(gi))
    done = 0

    def pop(gi):
        nonlocal done, ready
        for t in glist[gi].targets:
            heads[t] += 1
        done += 1
        ready.remove(gi)
        # gates newly at all their heads
        for t in glist[gi].targets:
            if heads[t] < len(queues[t]):
                cand = queues[t][heads[t]]
                if cand not in ready and is_ready(cand):
                    ready.append(cand)
        ready.sort()

    def phys_of(gi):
        return tuple(plan.pos[t] for t in glist[gi].targets)

    def try_fold(gi):
        phys = phys_of(gi)
        cl = _cluster_of(phys)
        if cl is not None:
            bits = tuple(p if cl == "A" else p - LANE for p in phys)
            plan._fold(cl, bits, glist[gi].mat)
            pop(gi)
            return True
        if _is_cross2(phys):
            if plan.acc.rank > 1:
                plan.flush()
            plan.acc.fold_cross(phys, glist[gi].mat)
            pop(gi)
            return True
        return False

    def swapped_pos(p, h, b, m):
        if b <= p < b + m:
            return h + (p - b)
        if h <= p < h + m:
            return b + (p - h)
        return p

    def best_swap():
        """(h, b, m) of the segment swap enabling the most ready folds;
        None if no swap enables >= 2.  Variable width m lets a swap pull a
        high page in while KEEPING a window-resident partner qubit — e.g. a
        gate on (sublane 8, grid 21) folds after a 3-bit swap that evicts
        [9, 12) only."""
        if plan.seg_max <= 0:
            return None
        cand_hm = []
        for gi in ready:
            high = [p for p in phys_of(gi) if p >= WINDOW]
            if not high:
                continue
            span = max(high) - min(high) + 1
            for m in range(max(plan.seg_min, span), plan.seg_max + 1):
                lo_h = max(WINDOW, max(high) - m + 1)
                hi_h = min(n - m, min(high))
                if lo_h <= hi_h and (hi_h, m) not in cand_hm:
                    cand_hm.append((hi_h, m))
        if not cand_hm:
            return None
        cand_hm.sort()
        # next-use distance per physical position (capped horizon), over
        # pending gate-target occurrences in gate-index order (queues are
        # sorted, so gi is pending on qubit t iff gi >= queues[t][heads[t]])
        next_use = {}
        d = 0
        for gi in range(num_gates):
            if d > _LOOKAHEAD:
                break
            for t in glist[gi].targets:
                if d > _LOOKAHEAD:
                    break
                q = queues[t]
                hpos = heads[t]
                if hpos < len(q) and gi >= q[hpos]:
                    p = plan.pos[t]
                    if p not in next_use:
                        next_use[p] = d
                    d += 1
        best = None
        for h, m in cand_hm:
            for b in range(LANE, WINDOW - m + 1):
                count = 0
                for gi in ready:
                    pp = tuple(swapped_pos(p, h, b, m) for p in phys_of(gi))
                    if _cluster_of(pp) is not None or _is_cross2(pp):
                        count += 1
                evict = min(
                    (next_use.get(p, _LOOKAHEAD + 1) for p in range(b, b + m)),
                    default=0,
                )
                key = (count, evict, -m, -h, -b)
                if best is None or key > best[0]:
                    best = (key, h, b, m)
        # a swap pass costs the same as one transpose (~copy speed) while a
        # standalone apply pass is 2-8x that, so relocating for even ONE
        # foldable gate wins
        if best is None or best[0][0] < 1:
            return None
        return best[1], best[2], best[3]

    while done < num_gates:
        progressed = True
        while progressed:
            progressed = False
            for gi in list(ready):
                if try_fold(gi):
                    progressed = True
        if done == num_gates:
            break
        sw = best_swap()
        if sw is not None:
            h, b, m = sw
            plan._emit_segswap(h, b, m)
            continue
        gi = ready[0]
        plan.flush()
        plan.ops.append(("apply", phys_of(gi), glist[gi].mat))
        pop(gi)
    plan.final_restore()
    return _peephole(plan.ops, n)


RANK_CAP = 4  # max Kronecker-sum rank per window pass (FLOPs scale with it)


def plan_circuit_windowed(gates: Sequence[Gate],
                          num_qubits: int) -> List[tuple]:
    """Offset-window DAG list scheduler — zero-relocation planning.

    Each emitted pass applies a rank-R operator on {lane qubits [0,7)} x
    {window qubits [k, k+7)} where the window offset k is chosen PER PASS:
    the window kernel (ops/fused.py apply_window_stack) views the strided
    bit-window directly through its BlockSpec, so high qubits never have to
    be relocated at all — where the paged planner (plan_circuit_py) pays
    segswap/transpose passes to pull high qubits into [7,14), this planner
    just aims the window at them.  The scheduler greedily picks, per pass,
    the offset k whose transitive fold closure over the ready frontier
    covers the most gates; 2q gates straddling lane x window fold through
    their operator-Schmidt terms (schmidt_terms_2q — rank x2 for
    controlled gates) with pass rank capped at RANK_CAP.  Gates no window
    covers (e.g. a dense 2q gate on two far-apart high qubits) fall back to
    one standard layout-safe kernel pass.

    Concrete controlled-form 2q gates are first rewritten as
    pre/diagonal/post (rewrite_controlled_gates); the diagonal part of a
    crossing gate then folds into the pass's elementwise MASK at zero rank
    cost — after a mask is set, only gates commuting with it (disjoint
    bits, or diagonal) may keep folding into the pass."""
    n = num_qubits
    glist = list(gates)
    if n < WINDOW:
        return [("apply", g.targets, g.mat) for g in glist]
    glist = rewrite_controlled_gates(glist)

    num_gates = len(glist)
    queues: List[List[int]] = [[] for _ in range(n)]
    for gi, g in enumerate(glist):
        for t in g.targets:
            queues[t].append(gi)
    heads = [0] * n

    # cross-fold rank per 2q gate: Schmidt rank when concrete, 4 otherwise
    xrank = _gate_xranks(glist)
    # diagonal crossing gates mask-fold (rank-free); diagonal gates of any
    # size commute with an existing mask
    gdiag4 = [diag4_2q(g.mat) if len(g.targets) == 2 else None for g in glist]
    gdiag = [is_diag_gate(g.mat) for g in glist]

    k_lo, k_hi = LANE, n - LANE  # valid window offsets (inclusive)

    def classify(targets: Tuple[int, ...], k: int):
        """How ``targets`` folds for window [k, k+7): ('A', bits),
        ('B', window-relative bits), ('X', lane_bit, win_bit, lane_is_bit0)
        for a 2q lane x window straddle, or None."""
        lane = all(t < LANE for t in targets)
        if lane:
            return ("A", targets)
        win = all(k <= t < k + LANE for t in targets)
        if win:
            return ("B", tuple(t - k for t in targets))
        if len(targets) == 2:
            t0, t1 = targets
            if t0 < LANE and k <= t1 < k + LANE:
                return ("X", t0, t1 - k, True)
            if t1 < LANE and k <= t0 < k + LANE:
                return ("X", t1, t0 - k, False)
        return None

    def is_ready(gi, hd):
        return all(
            hd[t] < len(queues[t]) and queues[t][hd[t]] == gi
            for t in glist[gi].targets
        )

    ready = sorted(gi for gi in range(num_gates) if is_ready(gi, heads))

    def advance(gi, hd, rdy):
        """Pop gate gi from (hd, rdy) in place."""
        for t in glist[gi].targets:
            hd[t] += 1
        rdy.remove(gi)
        for t in glist[gi].targets:
            if hd[t] < len(queues[t]):
                cand = queues[t][hd[t]]
                if cand not in rdy and is_ready(cand, hd):
                    rdy.append(cand)
        rdy.sort()

    def simulate(k):
        """Transitive fold closure for window k over copies of the DAG
        state: (count, final_rank, folds in fold order).  Mirrors the
        mask rules: a diagonal crossing gate folds into the pass mask
        (rank-free); once the mask is set, a gate may only fold if it
        commutes with the mask (disjoint bits or diagonal)."""
        hd = heads[:]
        rdy = list(ready)
        rank, count, folds = 1, 0, []
        mask_bits: set = set()
        progressed = True
        while progressed:
            progressed = False
            for gi in list(rdy):
                c = classify(glist[gi].targets, k)
                if c is None:
                    continue
                blocked = (
                    mask_bits
                    and not gdiag[gi]
                    and (mask_bits & set(glist[gi].targets))
                )
                if c[0] == "X":
                    if gdiag4[gi] is not None:
                        mask_bits |= set(glist[gi].targets)
                    else:
                        if blocked:
                            continue
                        r = xrank[gi]
                        if rank * r > RANK_CAP:
                            continue
                        rank *= r
                elif blocked:
                    continue
                count += 1
                folds.append(gi)
                advance(gi, hd, rdy)
                progressed = True
        return count, rank, folds

    ops: List[tuple] = []
    while ready:
        # candidate offsets: windows that cover some ready gate's high
        # targets, plus the home window k=7
        cands = {k_lo}
        for gi in ready:
            for t in glist[gi].targets:
                if t >= LANE:
                    for k in range(max(k_lo, t - LANE + 1),
                                   min(k_hi, t) + 1):
                        cands.add(k)
        # Windows k in {8, 9} force the collapsed 4-d state view (mid < 8,
        # ops/fused.py): its layout differs from the canonical T(8,128)
        # tiling, so XLA inserts full-state retile copies at the pass
        # boundary — measured 5.9 ms vs 1.3 ms per pass at 26q, and an
        # 8 GB OOM copy at 30q.  Pruned from the primary candidate set;
        # the rare gates ONLY these windows cover (targets spanning
        # exactly bits [8,14] or [9,15]) are caught by the last-resort
        # retry below — do not delete that fallback.
        if k_hi >= 10:
            cands -= {8, 9}
        best = None
        for k in sorted(cands):
            count, rank, folds = simulate(k)
            key = (count, -rank, -k)
            if best is None or key > best[0]:
                best = (key, k, folds)
        if best is None or best[0][0] == 0:
            # last resort: retry the pruned offsets {8, 9} — a gate whose
            # targets span exactly bits [8,14] or [9,15] is coverable by
            # NO other window, and even the slow collapsed-4-d-view pass
            # beats a per-gate full-state apply
            for k in (8, 9):
                if k_lo <= k <= k_hi:
                    count, rank, folds = simulate(k)
                    key = (count, -rank, -k)
                    if count and (best is None or key > best[0]):
                        best = (key, k, folds)
        if best is None or best[0][0] == 0:
            gi = ready[0]
            ops.append(_fallback_op(glist[gi].targets, glist[gi].mat))
            advance(gi, heads, ready)
            continue
        _, k, folds = best
        acc = _WinAcc(k)
        for gi in folds:
            c = classify(glist[gi].targets, k)
            if c[0] == "X":
                if gdiag4[gi] is not None:
                    acc.fold_mask(c[1], c[2], gdiag4[gi], c[3])
                else:
                    acc.fold_cross(c[1], c[2], glist[gi].mat, c[3])
            else:
                acc.fold_side(c[0], c[1], glist[gi].mat)
            advance(gi, heads, ready)
        a, b = acc.stacks()
        ops.append(("winfused", k, a, b, acc.a_used, acc.b_used,
                    acc.mask_soa()))
    return ops


# ---------------------------------------------------------------------------
# Sharded-register relocalization pass: communication at WINDOW granularity
# ---------------------------------------------------------------------------


_REMAP_LOOKAHEAD = 256  # next-use horizon for the eviction choice


def remap_exchange_bytes(sigma: Tuple[int, ...], num_qubits: int, nloc: int,
                         itemsize: int = 8) -> int:
    """ICI bytes ONE shard exchanges executing the batched remap ``sigma``
    — the scheduling-layer cost model for a window relocalization: each
    mixed local<->mesh transposition moves half the shard
    (dist._swap_halves_in_shard), a residual composed mesh permutation
    moves the whole shard, and the per-shard axis permutation moves
    nothing over ICI.  Used by bench_suite config 7's exchange-volume
    accounting and by the pipelined-exchange tests to size the expected
    chunk payloads (each listed payload is what dist.exchange_chunks
    splits)."""
    from .parallel import dist as PAR

    r = num_qubits - nloc
    mixed, _local_perm, mesh_tau = PAR.decompose_sigma(sigma, nloc, r,
                                                       itemsize)
    shard = 2 * (1 << nloc) * itemsize          # SoA: re + im planes
    total = len(mixed) * (shard // 2)
    if mesh_tau is not None:
        total += shard
    return total


def remap_exchange_bytes_tiers(sigma: Tuple[int, ...], num_qubits: int,
                               nloc: int, itemsize: int = 8,
                               topology=None) -> Dict[str, int]:
    """Per-interconnect-tier split of :func:`remap_exchange_bytes` —
    ``{"ici": bytes, "dcn": bytes}`` summing exactly to the flat total
    (dist.remap_exchange_tiers on the byte axis).  Feeds the per-tier
    columns of introspect.explain_circuit, the governor's weighted drain
    cost and scripts/bench_pod.py's modeled A/B gate."""
    from .parallel import dist as PAR

    r = num_qubits - nloc
    tiers = PAR.remap_exchange_tiers(sigma, nloc, r, itemsize, topology)
    return {tier: b for tier, (_c, b) in tiers.items()}


def plan_remap_windows(bit_sets: Sequence[Tuple[int, ...]], num_qubits: int,
                       nloc: int, perm=None):
    """Relocalization pass for a SHARDED register: group a LOGICAL item
    stream (``bit_sets[i]`` = state-vector bits item i touches) into
    windows whose cumulative distinct-qubit set fits the shard-local space,
    and schedule ONE batched remap per window instead of two half-shard
    exchanges per sharded-target gate (the reference's per-gate scheme,
    QuEST_cpu_distributed.c:1447-1545; window-level reordering is the
    mpiQulacs / qHiPSTER communication-avoidance design,
    arXiv:2203.16044 / arXiv:1601.07195).

    Crucially the permutation is NOT undone between windows: it persists
    into ``final_perm`` (carried by Qureg._perm across drains) and
    canonical order only rematerializes on a state read.

    Returns (segments, final_perm) with segments =
    [((start, end), sigma | None, perm_during_window), ...]: apply the
    physical permutation ``sigma`` (dist.remap_sharded /
    dist._remap_in_shard), then run items [start, end) with their bits
    rewritten through ``perm_during_window``.

    Raises ValueError when a single item touches more than ``nloc``
    distinct qubits — no permutation can localize it (callers fall back
    to the per-gate explicit path; the reference instead REJECTS such
    ops, QuEST_validation.c:469-471)."""
    from .parallel import dist as PAR

    n = num_qubits
    perm = tuple(perm) if perm is not None else tuple(range(n))
    segments: List[tuple] = []
    cap = PAR.remap_window_cap(nloc)
    i = 0
    total = len(bit_sets)
    while i < total:
        if _is_relabel_entry(bit_sets[i]):
            # permutation fold: a run of relabel-tagged items composes
            # straight into the live logical->physical permutation — no
            # sigma, no data motion; the composed exchange (if any) is
            # deferred to the next canonical read like every other perm
            j = i
            while j < total and _is_relabel_entry(bit_sets[j]):
                rho = dict(bit_sets[j][1])
                perm = tuple(perm[rho.get(q, q)] for q in range(n))
                j += 1
            segments.append(((i, j), None, perm))
            i = j
            continue
        w: set = set()
        j = i
        while j < total:
            if _is_relabel_entry(bit_sets[j]):
                break
            b = set(bit_sets[j])
            if len(w | b) > (cap if j > i else nloc):
                break
            w |= b
            j += 1
        if j == i:
            raise ValueError(
                f"plan_remap_windows: item {i} touches {len(set(bit_sets[i]))}"
                f" qubits but only {nloc} can be shard-local")
        # next-use distances over the remaining stream: evict the local
        # residents needed furthest in the future (capped horizon, same
        # policy as the paged planner's eviction choice)
        next_use: dict = {}
        d = 0
        for k in range(j, min(total, j + _REMAP_LOOKAHEAD)):
            if _is_relabel_entry(bit_sets[k]):
                continue
            for q in bit_sets[k]:
                if q not in next_use:
                    next_use[q] = d
                d += 1
        sigma, new_perm = PAR.plan_window_remap(
            n, nloc, perm, sorted(w), next_use)
        assert new_perm is not None  # |w| <= nloc makes the remap feasible
        perm = new_perm
        segments.append(((i, j), sigma, perm))
        i = j
    return segments, perm


def execute_plan(amps, ops: Sequence[tuple], num_qubits: int,
                 interpret: Optional[bool] = None,
                 precision: Optional[str] = None):
    n = num_qubits
    # resolve the config at trace time so callers caching compiled plans can
    # key on fused.matmul_precision_name()
    precision = precision or fused.matmul_precision_name()
    for op in ops:
        if op[0] == "fused":
            amps = fused.apply_cluster_stack(
                amps, jnp.asarray(op[1], amps.dtype), jnp.asarray(op[2], amps.dtype),
                num_qubits=n, interpret=interpret, precision=precision,
            )
        elif op[0] == "apply":
            amps = kernels.apply_matrix(
                amps, jnp.asarray(op[2], amps.dtype), num_qubits=n,
                targets=tuple(op[1]),
            )
        elif op[0] == "diag":
            amps = fused.apply_diagonal_canonical(
                amps, jnp.asarray(op[2], amps.dtype), num_qubits=n,
                targets=tuple(op[1]), interpret=interpret,
            )
        elif op[0] == "segswap":
            amps = kernels.swap_bit_segments(
                amps, num_qubits=n, a=op[1], b=op[2], m=op[3]
            )
        elif op[0] == "swapfused":
            amps = fused.apply_swap_cluster_stack(
                amps, jnp.asarray(op[4], amps.dtype),
                jnp.asarray(op[5], amps.dtype),
                num_qubits=n, h=op[1], b=op[2], m=op[3],
                interpret=interpret, precision=precision,
            )
        elif op[0] == "winfused":
            mask = op[6] if len(op) > 6 else None
            amps = fused.apply_window_stack(
                amps, jnp.asarray(op[2], amps.dtype),
                jnp.asarray(op[3], amps.dtype),
                mask=None if mask is None else jnp.asarray(mask, amps.dtype),
                num_qubits=n, k=op[1], apply_a=op[4], apply_b=op[5],
                interpret=interpret, precision=precision,
            )
        elif op[0] == "megawin":
            # §29: one pallas_call for the whole run of window passes
            amps = fused.apply_window_megastack(
                amps, op[1], num_qubits=n, interpret=interpret,
                precision=precision,
            )
        elif op[0] == "permute":
            amps = kernels.permute_qubits(amps, num_qubits=n, perm=op[1])
        elif op[0] == "xor":
            amps = kernels.apply_multi_qubit_not(
                amps, num_qubits=n, targets=tuple(op[1]))
        elif op[0] == "gatherperm":
            amps = kernels.apply_index_permutation(
                amps, num_qubits=n, targets=tuple(op[1]), pi=tuple(op[2]))
        elif op[0] == "sigma_swap":
            from .ops import bigstate
            amps = bigstate.apply_sigma_swap(
                amps, num_qubits=n, group_bits=op[1], interpret=interpret)
        else:  # pragma: no cover
            raise ValueError(f"unknown op {op[0]}")
    return amps


def plan_checkpoint_boundaries(num_gates: int, every: int,
                               start: int = 0) -> List[int]:
    """Gate cursors where a resumable run may checkpoint: every ``every``
    gates plus the stream end.  Boundaries fall BETWEEN fusion drains —
    the resilience driver (resilience.run_resumable) opens one fusion
    window per [boundary, boundary) span, so a checkpoint never lands
    mid-window and an interrupted run re-plans the identical window
    sequence on resume (same spans -> same plan-cache keys -> bit-exact
    replay)."""
    if every < 1:
        raise ValueError("plan_checkpoint_boundaries: every must be >= 1")
    out = list(range(start + every, num_gates, every))
    if num_gates > start:
        out.append(num_gates)
    return out


def apply_circuit(amps, gates: Sequence[Gate], num_qubits: int,
                  interpret: Optional[bool] = None):
    """Plan + execute in one call (both happen at trace time under jit)."""
    return execute_plan(amps, plan_circuit(gates, num_qubits), num_qubits,
                        interpret=interpret)


# ---------------------------------------------------------------------------
# Chained per-pass execution: many small cached programs, canonical layout
# ---------------------------------------------------------------------------


def canonical_view(amps, num_qubits: int):
    """The state in its canonical tiled view (2, 2^(n-14), 128, 128) —
    sublanes = amp bits [7,14), lanes = bits [0,7).  All per-pass kernels
    accept and return this shape, and a jit parameter of this shape gets
    the same T(8,128) device layout the kernel views use, so every jit
    boundary is a free bitcast.  A flat (2, 2^n) parameter instead carries
    a different layout and XLA inserts a FULL-STATE copy at the program
    boundary — 537 MB at 26q, 8 GB at 30q (the round-2 OOM that blocked
    the 30-qubit benchmark)."""
    if num_qubits < WINDOW:
        return amps
    return amps.reshape(2, 1 << (num_qubits - WINDOW), DIM, DIM)


def plan_to_device(ops: Sequence[tuple], dtype) -> List[tuple]:
    """Upload every concrete pass operand once (numpy -> device array) so a
    chained executor does not re-transfer matrices on every call."""
    out: List[tuple] = []
    for op in ops:
        if op[0] in ("winfused",):
            mask = op[6] if len(op) > 6 else None
            out.append(("winfused", op[1], jnp.asarray(op[2], dtype),
                        jnp.asarray(op[3], dtype), op[4], op[5],
                        None if mask is None else jnp.asarray(mask, dtype)))
        elif op[0] == "megawin":
            out.append(("megawin", tuple(plan_to_device(op[1], dtype))))
        elif op[0] == "fused":
            out.append(("fused", jnp.asarray(op[1], dtype),
                        jnp.asarray(op[2], dtype)))
        elif op[0] == "swapfused":
            out.append(("swapfused", op[1], op[2], op[3],
                        jnp.asarray(op[4], dtype), jnp.asarray(op[5], dtype)))
        elif op[0] in ("apply", "diag"):
            out.append((op[0], op[1], jnp.asarray(op[2], dtype)))
        else:
            out.append(op)
    return out


def execute_plan_chained(amps, ops: Sequence[tuple], num_qubits: int,
                         precision: Optional[str] = None):
    """Execute a plan as a CHAIN of per-pass cached jits (eager dispatch)
    instead of one monolithic traced program.

    Why this exists: tracing a whole 28-30q circuit into one XLA program
    costs 7-14 minutes of AOT compile and, at 30q, an OOM (see
    canonical_view).  Each pass here is its own tiny jitted program —
    compiled once per distinct (kernel, k, rank, flags) signature in ~2 s,
    reused across the whole circuit and across sizes with the same
    signature.  Dispatch is async, so the host enqueues passes while the
    device works; measured per-pass device time at 26q matches the HBM
    floor (~1.3 ms), i.e. chaining costs nothing over the monolithic
    program.  The state must be (and stays) in the canonical view.

    This is the executor the 30q+ benchmark sizes use; the reference's
    whole distributed design exists to reach those sizes
    (QuEST/include/QuEST.h:463-479).
    """
    n = num_qubits
    amps = canonical_view(amps, n)
    return execute_plan(amps, ops, n, precision=precision)


def stats(ops: Sequence[tuple]) -> dict:
    """Pass-count accounting for logging/benchmark output."""
    from collections import Counter

    c = Counter(op[0] for op in ops)
    return {"fused": c.get("fused", 0), "swapfused": c.get("swapfused", 0),
            "winfused": c.get("winfused", 0),
            "megawin": c.get("megawin", 0),
            "megawin_ops": sum(len(op[1]) for op in ops
                               if op[0] == "megawin"),
            "apply": c.get("apply", 0), "diag": c.get("diag", 0),
            "segswap": c.get("segswap", 0),
            "permute": c.get("permute", 0),
            "xor": c.get("xor", 0),
            "gatherperm": c.get("gatherperm", 0),
            "sigma_swap": c.get("sigma_swap", 0),
            "total_passes": sum(c.values())}


# ---------------------------------------------------------------------------
# Fused QFT: ladder passes + one scheduled low-qubit pass + one permute
# ---------------------------------------------------------------------------


def _qft_layer_dense(tr: int, conj: bool, dt) -> np.ndarray:
    """Dense matrix of one low QFT layer on tr+1 contiguous qubits (matrix
    bit tr = the layer target): Hadamard on the target followed by the
    controlled-phase ladder diag(1, e^{i*pi*low/2^tr}) against the lower
    bits."""
    d = 1 << tr
    low = np.arange(d)
    sgn = -1.0 if conj else 1.0
    ph = np.exp(sgn * 1j * np.pi * low / d)
    inv = 1.0 / math.sqrt(2.0)
    m = np.zeros((2 * d, 2 * d), complex)
    m[low, low] = inv
    m[low, d + low] = inv
    m[d + low, low] = inv * ph
    m[d + low, d + low] = -inv * ph
    return np.stack([m.real, m.imag]).astype(dt)


def fused_qft(amps, num_qubits: int, start: int, count: int,
              shifts: Sequence[int] = (0,),
              interpret: Optional[bool] = None,
              conj_first: bool = False):
    """QFT on the contiguous qubits [start, start+count) — plus a
    conjugated twin per extra entry of ``shifts`` (the density-matrix bra
    half) — as:

      * one fused elementwise ladder pass per high layer
        (kernels.apply_qft_ladder: Hadamard + whole controlled-phase
        ladder, ONE HBM sweep each),
      * the <= 7-qubit low layers folded by the windowed scheduler
        (typically one pass),
      * the final swap network of ALL halves as ONE bit-reversal axis
        permutation.

    vs the reference's per-layer dispatch (agnostic_applyQFT,
    QuEST_common.c:836-898): ~n+2 passes instead of ~2.5n.  Requires
    start == 0 or start >= 7 (layout-safe ladder views) — callers fall
    back to the layered path otherwise."""
    from .ops import kernels as K

    n = num_qubits
    if not (start == 0 or start >= LANE):
        raise ValueError("fused_qft needs start == 0 or start >= 7")
    dt = np.float64 if amps.dtype == jnp.float64 else np.float32
    if (start == 0 and tuple(shifts) == (0,) and count >= 15
            and fused.qft_multilayer_enabled(amps.dtype)):
        return _fused_qft_multilayer(amps, n, count, interpret,
                                     conj=conj_first)
    dense_gates: List[Gate] = []
    for si, sh in enumerate(shifts):
        conj = si > 0 or conj_first
        base = start + sh
        for qq in range(count - 1, -1, -1):
            if qq >= LANE:
                amps = K.apply_qft_ladder(
                    amps, num_qubits=n, target=base + qq, base=base,
                    conj=conj)
            else:
                dense_gates.append(Gate(
                    tuple(range(base, base + qq + 1)),
                    _qft_layer_dense(qq, conj, dt)))
    if dense_gates:
        amps = execute_plan(amps, plan_circuit(dense_gates, n), n,
                            interpret=interpret)
    runs = [(start + sh, count) for sh in shifts]
    rev_ops = bit_reversal_ops(n, runs, dt)
    if rev_ops is None:
        perm = list(range(n))
        for b, c in runs:
            for i in range(c // 2):
                perm[b + i], perm[b + c - 1 - i] = (
                    perm[b + c - 1 - i], perm[b + i])
        rev_ops = [("permute", tuple(perm))] if perm != list(range(n)) else []
    amps = execute_plan(amps, rev_ops, n, interpret=interpret)
    return amps


def _fused_qft_multilayer(amps, n: int, count: int,
                          interpret: Optional[bool], conj: bool = False):
    """Radix-2^k QFT (full or [0, count) run of a statevector register):

      * layers t >= 14 in chunks of QT_QFT_RADIX (default 4) per HBM
        sweep (fused.apply_qft_multi_hi — pair bits co-resident in VMEM,
        classic high-radix FFT blocking),
      * ALL seven sublane layers (t = 13..7) as ONE sweep
        (fused.apply_qft_cluster_multi),
      * the seven lane layers (t = 6..0) FOLDED with the lane+sublane
        within-group bit reversals into a single dense window pass,
      * then only the high-group reversal passes and the group-order
        permute remain from bit_reversal_ops(skip_low_group=True) — the
        merged lane+sublane reversal pass it would normally emit first is
        the fold above.

    Pass count at 26q: 3 + 1 + 1 + 3 = 8 vs the per-layer path's 24; the
    reference's per-gate dispatch is ~2.5n sweeps (agnostic_applyQFT,
    QuEST_common.c:836-898)."""
    dt = np.float64 if amps.dtype == jnp.float64 else np.float32
    amps = fused.apply_qft_multilayer_ladders(
        amps, num_qubits=n, t_top=count - 1, conj=conj, interpret=interpret)
    dense_gates = [Gate(tuple(range(qq + 1)), _qft_layer_dense(qq, conj, dt))
                   for qq in range(LANE - 1, -1, -1)]
    rev7 = _rev_perm_mat(LANE, dt)
    dense_gates.append(Gate(tuple(range(LANE)), rev7))
    dense_gates.append(Gate(tuple(range(LANE, 2 * LANE)), rev7))
    ops = plan_circuit(dense_gates, n)
    rev_ops = bit_reversal_ops(n, [(0, count)], dt, skip_low_group=True)
    return execute_plan(amps, list(ops) + rev_ops, n, interpret=interpret)


# ---------------------------------------------------------------------------
# Fast bit reversal: group decomposition instead of one all-axes transpose
# ---------------------------------------------------------------------------


def _rev_perm_mat(bits: int, dt, off: int = 0) -> np.ndarray:
    """SoA 128x128 permutation matrix reversing bits [off, off+bits) of a
    7-bit cluster index (other bits untouched)."""
    d = 1 << LANE
    mask = ((1 << bits) - 1) << off
    m = np.zeros((d, d))
    for i in range(d):
        seg = (i & mask) >> off
        rev = int(format(seg, f"0{bits}b")[::-1], 2) if bits else 0
        m[(i & ~mask) | (rev << off), i] = 1.0
    return np.stack([m, np.zeros((d, d))]).astype(dt)


def _bit_reversal_big(n: int, dt, skip_low_group: bool = False) -> List[tuple]:
    """Bit reversal of the FULL state without any out-of-place transpose:
    rev[0,n) = (within-group reversals, in-place window passes) o sigma
    for the palindromic group split (7, 7, n-28, 7, 7), where sigma (swap
    bits [0,7)<->[n-7,n) and [7,14)<->[n-14,n-7)) runs as the in-place
    block-pair DMA kernel (ops/bigstate.py).  At 30q a full-state XLA
    transpose OOMs (8 GB state + 8 GB output > 15.75 GB HBM); this path
    is 5 in-place passes."""
    r = n - 28
    ops: List[tuple] = []
    rev7 = jnp.asarray(_rev_perm_mat(LANE, dt))
    eye = jnp.asarray(_eye_cluster(), rev7.dtype)
    if not skip_low_group:
        ops.append(("winfused", LANE, rev7[None], rev7[None], True, True))
    if r:
        m = jnp.asarray(_rev_perm_mat(r, dt, off=0))
        ops.append(("winfused", WINDOW, eye[None], m[None], False, True))
    for k in (WINDOW + r, n - LANE):
        ops.append(("winfused", k, eye[None], rev7[None], False, True))
    ops.append(("sigma_swap", LANE))
    return ops


def bit_reversal_ops(n: int, runs: Sequence[Tuple[int, int]],
                     dt, skip_low_group: bool = False
                     ) -> Optional[List[tuple]]:
    """Ops reversing the qubit order of each contiguous run
    (start, count), or None when no fast decomposition applies.

    One all-axes-reversed transpose is pathological for XLA — no adjacent
    axes merge (measured 426 ms / 2.5 GB/s at 26 qubits).  Instead each
    run splits into 7-bit groups: rev(run) = (reverse the ORDER of the
    groups) o (reverse WITHIN each group).  The within-group reversals are
    window-pass permutation matrices at the groups' original positions
    (the lane group rides the A side of the first window pass), and the
    group-order reversal of ALL runs is ONE axis permutation whose long
    order-preserving segments XLA transposes at near copy speed.

    Full-state runs at n >= 30 take the in-place palindromic path
    instead (_bit_reversal_big): the XLA transpose needs a second
    full-state buffer, which no longer fits in HBM there.

    ``skip_low_group=True`` omits the merged lane+sublane within-group
    reversal pass (the caller folds those two rev7 matrices into its own
    dense window pass — circuit._fused_qft_multilayer); it requires a
    single run starting at 0 with two full 7-bit low groups."""
    if skip_low_group and not (
            len(runs) == 1 and runs[0][0] == 0 and runs[0][1] >= 14):
        raise ValueError("skip_low_group needs one run = (0, count >= 14)")
    if (len(runs) == 1 and runs[0] == (0, n) and 30 <= n < 35
            and np.dtype(dt) == np.float32
            and not fused._interpret_default()):
        return _bit_reversal_big(n, dt, skip_low_group=skip_low_group)
    ops: List[tuple] = []
    perm = list(range(n))
    eye = None
    for start, count in runs:
        if count <= 1:
            continue
        if not (start == 0 or start >= LANE):
            return None
        groups = []
        o = start
        while o < start + count:
            sz = min(LANE, start + count - o)
            groups.append((o, sz))
            o += sz
        # within-group reversal passes (merge the lane group into the
        # second group's window pass when both exist)
        i0 = 0
        if groups[0][0] == 0:
            if len(groups) > 1 and groups[1][1] > 1:
                if skip_low_group:
                    i0 = 2   # caller folds both low-group reversals
                else:
                    a_mat = jnp.asarray(_rev_perm_mat(groups[0][1], dt))
                    o1, sz1 = groups[1]
                    k1 = min(o1, n - LANE)
                    b_mat = jnp.asarray(_rev_perm_mat(sz1, dt, off=o1 - k1))
                    ops.append(("winfused", k1, a_mat[None],
                                b_mat[None], True, True))
                    i0 = 2
            else:
                a_mat = jnp.asarray(_rev_perm_mat(groups[0][1], dt))
                eye = jnp.asarray(_eye_cluster(), a_mat.dtype) if eye is None else eye
                ops.append(("winfused", LANE, a_mat[None], eye[None],
                            True, False))
                i0 = 1
        for o, sz in groups[i0:]:
            if sz <= 1:
                continue
            k = min(o, n - LANE)
            b_mat = jnp.asarray(_rev_perm_mat(sz, dt, off=o - k))
            eye = jnp.asarray(_eye_cluster(), b_mat.dtype) if eye is None else eye
            ops.append(("winfused", k, eye[None], b_mat[None], False, True))
        # group-order reversal: new offset of group i = start + total size
        # of the groups after it (order-preserving within groups)
        off = start
        for o, sz in reversed(groups):
            for j in range(sz):
                perm[off + j] = o + j
            off += sz
    if perm != list(range(n)):
        ops.append(("permute", tuple(perm)))
    return ops


# ---------------------------------------------------------------------------
# Plan (de)composition: static skeleton + array operands
# ---------------------------------------------------------------------------


def split_plan(ops: Sequence[tuple]):
    """(hashable skeleton, array list): separates an executable plan into
    its static structure and its array operands so callers can jit (and
    cache) an executor keyed on the skeleton while the matrices stay
    traced arguments (fusion drains, sharded executors)."""
    skeleton: List[tuple] = []
    arrays: List[object] = []
    for op in ops:
        if op[0] == "winfused":
            mask = op[6] if len(op) > 6 else None
            skeleton.append(("winfused", op[1], tuple(np.shape(op[2])),
                             op[4], op[5], mask is not None))
            arrays.extend([op[2], op[3]])
            if mask is not None:
                arrays.append(mask)
        elif op[0] == "megawin":
            sub_sk, sub_arrays = split_plan(op[1])
            skeleton.append(("megawin", sub_sk))
            arrays.extend(sub_arrays)
        elif op[0] in ("apply", "diag"):
            skeleton.append((op[0], tuple(op[1]), tuple(np.shape(op[2]))))
            arrays.append(op[2])
        elif op[0] == "fused":
            skeleton.append(("fused", tuple(np.shape(op[1]))))
            arrays.extend([op[1], op[2]])
        elif op[0] == "swapfused":
            skeleton.append(("swapfused", op[1], op[2], op[3],
                             tuple(np.shape(op[4]))))
            arrays.extend([op[4], op[5]])
        else:  # segswap / permute: fully static
            skeleton.append(tuple(op))
    return tuple(skeleton), arrays


def rebuild_plan(skeleton: Sequence[tuple], arrays: Sequence) -> List[tuple]:
    """Inverse of split_plan given the (possibly traced) array operands."""
    return _rebuild_plan_iter(skeleton, iter(arrays))


def _rebuild_plan_iter(skeleton: Sequence[tuple], it) -> List[tuple]:
    ops: List[tuple] = []
    for sk in skeleton:
        if sk[0] == "winfused":
            a, b = next(it), next(it)
            mask = next(it) if len(sk) > 5 and sk[5] else None
            ops.append(("winfused", sk[1], a, b, sk[3], sk[4], mask))
        elif sk[0] == "megawin":
            ops.append(("megawin", tuple(_rebuild_plan_iter(sk[1], it))))
        elif sk[0] in ("apply", "diag"):
            ops.append((sk[0], sk[1], next(it)))
        elif sk[0] == "fused":
            ops.append(("fused", next(it), next(it)))
        elif sk[0] == "swapfused":
            a, b = next(it), next(it)
            ops.append(("swapfused", sk[1], sk[2], sk[3], a, b))
        else:
            ops.append(sk)
    return ops
