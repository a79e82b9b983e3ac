"""Plain references that decide whether a run is correct.

Nothing here imports the simulator under test, and nothing takes a number
the simulator made.  Three references:

* ``dense_state`` — a dense complex128 NumPy state after a gate list
  (small registers; the CPU rehearsal and the tests);
* ``ChunkedState`` — the same gates at full size on the chip, in plain
  ``jax.numpy``: the state is held as 2^h chunks selected by its top h
  qubits, so the reference needs the state plus a chunk or two of memory;
* ``qft_factors`` — the closed form of the QFT of a basis state, which is
  a product state: its amplitudes are the outer product of two vectors,
  built in float64 on the host.

Rotations are exp(-i angle/2 P), QuEST's convention; CNOT flips the target
where the control is 1.  Amplitude index bit q is qubit q.
"""

from __future__ import annotations

import numpy as np

PAULIS = (np.array([[0, 1], [1, 0]], np.complex128),
          np.array([[0, -1j], [1j, 0]], np.complex128),
          np.array([[1, 0], [0, -1]], np.complex128))
GROUP_BITS = 7


def rotation(axis: int, angle: float) -> np.ndarray:
    """2x2 complex128 matrix of rotate{X,Y,Z}(angle)."""
    return (np.cos(angle / 2) * np.eye(2)
            - 1j * np.sin(angle / 2) * PAULIS[axis])


def dense_state(n: int, ops, psi=None) -> np.ndarray:
    """Dense complex128 state after ``ops`` applied to ``psi`` (|0...0>
    when None).  ``ops`` entries: ("rot", axis, q, angle) or
    ("cnot", control, target)."""
    if psi is None:
        psi = np.zeros(1 << n, np.complex128)
        psi[0] = 1.0
    idx = np.arange(1 << n)
    for op in ops:
        if op[0] == "rot":
            _, axis, q, ang = op
            v = psi.reshape(-1, 2, 1 << q)
            psi = np.einsum("ab,ibj->iaj", rotation(axis, ang),
                            v).reshape(-1)
        else:
            _, c, t = op
            src = idx.copy()
            sel = ((idx >> c) & 1) == 1
            src[sel] ^= 1 << t
            psi = psi[src]
    return psi


def z_mask(codes) -> int:
    """Bit mask of the qubits a Pauli code list (0 = I, 3 = Z) puts Z on."""
    mask = 0
    for q, c in enumerate(codes):
        if int(c) == 3:
            mask |= 1 << q
        elif int(c) != 0:
            raise ValueError("only I and Z codes are read")
    return mask


def dense_z_expectation(psi: np.ndarray, mask: int) -> float:
    """<psi| Z on the qubits of ``mask`` |psi>, in float64."""
    idx = np.arange(psi.size, dtype=np.int64)
    par = np.zeros(psi.size, np.int64)
    m = idx & mask
    while np.any(m):
        par ^= m & 1
        m >>= 1
    return float(np.sum(np.abs(psi) ** 2 * (1 - 2 * par)))


def qft_factors(n: int, x: int, low: int):
    """QFT|x> on n qubits = outer(hi, lo) over (top n - low, low) index
    bits: amplitude k = hi[k >> low] * lo[k & (2^low - 1)], where
    amplitude k is 2^(-n/2) exp(2 pi i x k / 2^n).  The phases are built
    from the exact integer (x * k) mod 2^n (uint64 products wrap mod 2^64,
    a multiple of 2^n), then in float64."""
    mod = np.uint64((1 << n) - 1)

    def phases(count, scale):
        k = np.arange(count, dtype=np.uint64) * np.uint64(scale)
        ph = (np.uint64(x) * k) & mod
        return np.exp(1j * ph.astype(np.float64) * (2.0 * np.pi / (1 << n)))

    lo = phases(1 << low, 1) * 2.0 ** (-low / 2)
    hi = phases(1 << (n - low), 1 << low) * 2.0 ** (-(n - low) / 2)
    return hi, lo


def embed_1q(u: np.ndarray, j: int, k: int) -> np.ndarray:
    """The 2^k x 2^k matrix of ``u`` on bit ``j`` of a k-bit group."""
    return np.kron(np.kron(np.eye(1 << (k - 1 - j)), u), np.eye(1 << j))


def cnot_matrix(c: int, t: int, k: int) -> np.ndarray:
    """The 2^k x 2^k permutation of CNOT(control bit c, target bit t)."""
    i = np.arange(1 << k)
    src = np.where((i >> c) & 1 == 1, i ^ (1 << t), i)
    p = np.zeros((1 << k, 1 << k))
    p[i, src] = 1.0
    return p


class ChunkedState:
    """A state of ``n`` qubits on the default JAX device, as 2^``chunk_bits``
    float32 chunks of shape (2, R, S, L) (real and imaginary planes; L holds
    qubits 0..6, S qubits 7..13, R the rest of the chunk's qubits).

    Gates that act on disjoint qubits commute.  So the qubits below the
    chunk bits are split into groups of seven (the L axis, the S axis,
    then seven bits of R at a time), and the gates that stay inside one
    group are multiplied, in complex128 on the host, into one matrix per
    group; that matrix is applied to every chunk with one matrix product
    when a gate that crosses the group comes, or at the end.  The product
    runs at ``precision``: "highest" (float32 accuracy, what the
    configurations state) or "bf16_3x" (three bfloat16 passes, the
    nearest precision below, which the control uses).  A CNOT across
    groups moves amplitudes with ``where`` and ``roll``; a gate on a
    chunk bit combines chunks pairwise.  Every call
    donates its chunk buffers, so memory stays at the state plus a chunk
    or two.  Reads accumulate in float64 on the host."""

    def __init__(self, n: int, chunk_bits: int, precision: str = "highest"):
        import jax
        import jax.numpy as jnp

        if precision not in ("highest", "bf16_3x"):
            raise ValueError(f"unknown precision {precision!r}")
        self.jax, self.jnp = jax, jnp
        self.n, self.h, self.precision = n, chunk_bits, precision
        self.m = m = n - chunk_bits
        lane = min(m, 7)
        sub = min(max(m - 7, 0), 7)
        self.shape = (2, 1 << max(m - 14, 0), 1 << sub, 1 << lane)
        zeros = jax.jit(lambda: jnp.zeros(self.shape, jnp.float32))
        self.chunks = [zeros() for _ in range(1 << chunk_bits)]
        self.chunks[0] = self.chunks[0].at[0, 0, 0, 0].set(1.0)
        self.pending = {}      # group -> complex128 matrix not yet applied
        self._fns = {}

    # -- where a qubit lives inside a chunk ------------------------------
    def _group(self, q: int):
        """(group, its low qubit, its bit count) of a qubit below ``m``."""
        g = q // GROUP_BITS
        lo = g * GROUP_BITS
        return g, lo, min(GROUP_BITS, self.m - lo)

    def _axis(self, q: int):
        """(axis, bit within the axis) of a qubit below ``m``."""
        if q < 7:
            return 3, q
        if q < 14:
            return 2, q - 7
        return 1, q - 14

    def _bit(self, axis: int, k: int):
        jnp = self.jnp
        shape = [1, 1, 1, 1]
        shape[axis] = self.shape[axis]
        idx = self.jax.lax.broadcasted_iota(jnp.int32, tuple(shape), axis)
        return (idx >> k) & 1

    def _partner(self, x, axis: int, k: int):
        """x at the index with bit k of ``axis`` flipped."""
        jnp = self.jnp
        s = 1 << k
        return jnp.where(self._bit(axis, k) == 1, jnp.roll(x, s, axis),
                         jnp.roll(x, -s, axis))

    def _fn(self, key, build):
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = build()
        return fn

    # -- gates ------------------------------------------------------------
    def _accumulate(self, q_lo: int, mat_of_group) -> None:
        g, lo, k = self._group(q_lo)
        cur = self.pending.get(g)
        new = mat_of_group(lo, k)
        self.pending[g] = new if cur is None else new @ cur

    def rot(self, axis: int, q: int, angle: float) -> None:
        u = rotation(axis, angle)
        if q < self.m:
            self._accumulate(q, lambda lo, k: embed_1q(u, q - lo, k))
        else:
            self._chunk_1q(q, u)

    def cnot(self, control: int, target: int) -> None:
        m = self.m
        if control < m and target < m and (
                self._group(control)[0] == self._group(target)[0]):
            self._accumulate(control, lambda lo, k: cnot_matrix(
                control - lo, target - lo, k))
            return
        for q in (control, target):
            if q < m:
                self.flush(self._group(q)[0])
        self._cnot_across(control, target)

    def flush(self, g=None) -> None:
        """Apply the pending matrix of group ``g`` (every group when
        None) to every chunk."""
        for grp in ([g] if g is not None else sorted(self.pending)):
            mat = self.pending.pop(grp, None)
            if mat is not None:
                self._apply_group(grp, mat)

    def _dot(self, eq: str, m_, v):
        """einsum(eq, m_, v) in float32 at the state's precision."""
        jax, jnp = self.jax, self.jnp
        if self.precision == "highest":
            return jnp.einsum(eq, m_, v, precision=jax.lax.Precision.HIGHEST)

        def split(x):
            # hi: x rounded to bfloat16's bits (to nearest, ties to even)
            # by integer arithmetic, which no compiler rewrite can skip (a
            # float32 -> bfloat16 -> float32 round trip may be dropped as
            # excess precision); lo: the rest
            u32 = jnp.uint32
            bits = jax.lax.bitcast_convert_type(x, u32)
            bits = bits + u32(0x7FFF) + ((bits >> u32(16)) & u32(1))
            hi = jax.lax.bitcast_convert_type(bits & u32(0xFFFF0000),
                                              jnp.float32)
            return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)

        (mh, ml), (vh, vl) = split(m_), split(v)

        def one(p, q):
            return jnp.einsum(eq, p, q, preferred_element_type=jnp.float32)
        return one(mh, vh) + one(mh, vl) + one(ml, vh)

    def _apply_group(self, g: int, mat: np.ndarray) -> None:
        jax, jnp = self.jax, self.jnp
        k = int(mat.shape[0]).bit_length() - 1
        mr = jnp.asarray(np.stack([mat.real, mat.imag]), jnp.float32)
        shape = self.shape

        def build():
            def f(x, mr):
                a, b = mr[0], mr[1]
                if g == 0:        # lanes: x[..., l] -> sum_l' K[l, l'] x
                    eq, xs = "ij,...j->...i", x
                elif g == 1:      # sublanes
                    eq, xs = "ij,rjl->ril", x
                else:             # seven bits of R at a time
                    low = 1 << (GROUP_BITS * (g - 2))
                    xs = x.reshape(2, -1, 1 << k, low, shape[2], shape[3])
                    eq = "ij,xjysl->xiysl"

                def mm(m_, v):
                    return self._dot(eq, m_, v)
                re = mm(a, xs[0]) - mm(b, xs[1])
                im = mm(a, xs[1]) + mm(b, xs[0])
                return jnp.stack([re, im]).reshape(shape)
            return jax.jit(f, donate_argnums=0)

        fn = self._fn(("group", g, k), build)
        self.chunks = [fn(c, mr) for c in self.chunks]

    def _chunk_1q(self, q: int, u: np.ndarray) -> None:
        jax, jnp = self.jax, self.jnp
        ur = jnp.asarray(np.stack([u.real, u.imag]), jnp.float32)

        def build_pair():
            def f(x0, x1, ur):
                def mul(a, b):
                    return (a[0] * b[0] - a[1] * b[1],
                            a[0] * b[1] + a[1] * b[0])
                u00, u01 = (ur[0, 0, 0], ur[1, 0, 0]), (ur[0, 0, 1],
                                                        ur[1, 0, 1])
                u10, u11 = (ur[0, 1, 0], ur[1, 1, 0]), (ur[0, 1, 1],
                                                        ur[1, 1, 1])
                a0, a1 = mul(u00, x0), mul(u01, x1)
                b0, b1 = mul(u10, x0), mul(u11, x1)
                return (jnp.stack([a0[0] + a1[0], a0[1] + a1[1]]),
                        jnp.stack([b0[0] + b1[0], b0[1] + b1[1]]))
            return jax.jit(f, donate_argnums=(0, 1))

        fn = self._fn(("u1pair",), build_pair)
        s = 1 << (q - self.m)
        for c in range(len(self.chunks)):
            if c & s == 0:
                self.chunks[c], self.chunks[c | s] = fn(
                    self.chunks[c], self.chunks[c | s], ur)

    def _cnot_across(self, control: int, target: int) -> None:
        jax, jnp = self.jax, self.jnp
        m = self.m
        if target < m:
            tax, tk = self._axis(target)
            if control < m:
                cax, ck = self._axis(control)

                def build():
                    def f(x):
                        return jnp.where(self._bit(cax, ck) == 1,
                                         self._partner(x, tax, tk), x)
                    return jax.jit(f, donate_argnums=0)

                fn = self._fn(("cx", control, target), build)
                self.chunks = [fn(c) for c in self.chunks]
                return

            def build_x():
                return jax.jit(lambda x: self._partner(x, tax, tk),
                               donate_argnums=0)

            fn = self._fn(("x", target), build_x)
            cs = 1 << (control - m)
            self.chunks = [fn(c) if i & cs else c
                           for i, c in enumerate(self.chunks)]
            return
        ts = 1 << (target - m)
        if control >= m:
            cs = 1 << (control - m)
            for c in range(len(self.chunks)):
                if c & cs and not c & ts:
                    self.chunks[c], self.chunks[c | ts] = (
                        self.chunks[c | ts], self.chunks[c])
            return
        cax, ck = self._axis(control)

        def build_swap():
            def f(x0, x1):
                sel = self._bit(cax, ck) == 1
                return jnp.where(sel, x1, x0), jnp.where(sel, x0, x1)
            return jax.jit(f, donate_argnums=(0, 1))

        fn = self._fn(("cxpair", control), build_swap)
        for c in range(len(self.chunks)):
            if not c & ts:
                self.chunks[c], self.chunks[c | ts] = fn(
                    self.chunks[c], self.chunks[c | ts])

    def apply(self, ops) -> None:
        """Apply ``ops`` and every matrix still pending."""
        for op in ops:
            if op[0] == "rot":
                self.rot(op[1], op[2], op[3])
            else:
                self.cnot(op[1], op[2])
        self.flush()

    # -- reads --------------------------------------------------------------
    def z_expectation(self, mask: int) -> float:
        """<Z on ``mask``>: per-row float32 partial sums on the device,
        summed in float64 on the host."""
        jax, jnp = self.jax, self.jnp
        m = self.m
        lo = mask & ((1 << m) - 1)
        ml, ms, mr = lo & 127, (lo >> 7) & 127, lo >> 14

        def build():
            def f(x):
                par = (jax.lax.population_count(self._iota(3) & ml)
                       + jax.lax.population_count(self._iota(2) & ms)
                       + jax.lax.population_count(self._iota(1) & mr)) & 1
                w = (x[0] * x[0] + x[1] * x[1]) * (1 - 2 * par).astype(
                    jnp.float32)
                return jnp.sum(w, axis=(2, 3))
            return jax.jit(f)

        fn = self._fn(("z", lo), build)
        total = 0.0
        for c, x in enumerate(self.chunks):
            sign = -1.0 if bin(c & (mask >> m)).count("1") & 1 else 1.0
            total += sign * float(np.sum(np.asarray(fn(x), np.float64)))
        return total

    def _iota(self, axis: int):
        shape = [1, 1, 1, 1]
        shape[axis] = self.shape[axis]
        return self.jax.lax.broadcasted_iota(self.jnp.int32, tuple(shape),
                                             axis)

    def sq_dist(self, c: int, prog) -> float:
        """sum |prog - chunk c|^2 for ``prog`` a host or device array of
        the same amplitudes in the chunk's shape: float32 differences and
        row sums on the device, the rows summed in float64 on the host."""
        jax, jnp = self.jax, self.jnp

        def build():
            def f(x, p):
                d = x - p
                return jnp.sum(d * d, axis=(0, 2, 3))
            return jax.jit(f)

        fn = self._fn(("sqdist",), build)
        return float(np.sum(np.asarray(fn(self.chunks[c], prog),
                                       np.float64)))

    def host_state(self) -> np.ndarray:
        """The whole state as a (2, 2^n) float32 host array."""
        return np.concatenate([np.asarray(c).reshape(2, -1)
                               for c in self.chunks], axis=1)

    def free(self) -> None:
        for c in self.chunks:
            c.delete()
        self.chunks = []
