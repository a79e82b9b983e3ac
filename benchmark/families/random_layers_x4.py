"""Random-layer circuits (``random_layers``) on a state sharded over the
chips of one host (``configs/rc32x4.json``: 32 qubits over four chips,
8 GiB a chip).  The circuit, its axes and its angles are
``random_layers.Family``'s; what changes is the size of the state, which
fits no single chip:

* ``snapshot``: the program's state copied to the host shard by shard,
  one thread per chip, and kept as one block per shard;
* ``reference``: the same gates at full size in plain ``jax.numpy`` at
  HIGHEST (or ``bf16_3x`` for the control), on ``SpreadState``, whose
  chunks lie on all the chips.  Off the chip, at HIGHEST, the dense
  complex128 state of ``random_layers``.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .. import reference as R
from . import random_layers


class Family(random_layers.Family):
    def __init__(self, cfg: dict):
        super().__init__(cfg)
        self.chips = int(cfg.get("chips", 1))

    def snapshot(self, q) -> "Blocks":
        """A float32 host copy of the program's state in canonical order,
        one block per shard, each copied by a thread of its own."""
        amps = q.device_amps()
        shards = sorted(amps.addressable_shards,
                        key=lambda s: s.index[1].start or 0)
        with ThreadPoolExecutor(len(shards)) as pool:
            blocks = list(pool.map(
                lambda s: np.asarray(s.data).reshape(2, -1), shards))
        return Blocks(blocks)

    def reference(self, init, chain, on_chip: bool,
                  precision: str = "highest"):
        """Replay ``chain`` on |0...0> and yield a reference after each
        circuit: dense off the chip at HIGHEST, else a SpreadState over
        the first ``chips`` devices."""
        if init != ("zero",):
            raise ValueError("random_layers circuits start from |0...0>")
        if not on_chip and precision == "highest":
            yield from super().reference(init, chain, on_chip, precision)
            return
        import jax

        st = SpreadState(self.n, self.chunk_bits,
                         jax.devices()[:self.chips], precision)
        try:
            for angles in chain:
                st.apply(self.ops(angles))
                yield _Spread(st)
        finally:
            st.free()


class Blocks:
    """A (2, 2^n) float32 host state held as consecutive blocks of the
    amplitude axis, one per shard; ``np.asarray`` joins them."""

    def __init__(self, blocks):
        self.blocks = blocks
        self.size = int(blocks[0].shape[1])

    def __array__(self, dtype=None, copy=None):
        out = np.concatenate(self.blocks, axis=1)
        return out if dtype is None else out.astype(dtype)

    def plane(self, p: int, a: int, b: int) -> np.ndarray:
        """Plane ``p`` (0 real, 1 imaginary) of amplitudes [a, b), which
        lie in one block: a contiguous view."""
        blk, off = divmod(a, self.size)
        if off + (b - a) > self.size:
            raise ValueError("range crosses a block")
        return self.blocks[blk][p, off:off + b - a]


class SpreadState(R.ChunkedState):
    """``reference.ChunkedState`` with its 2^h chunks spread over
    ``devices`` (2^d of them, d <= h): the top d chunk bits (the top d
    qubits, as in the program's shards) choose the device, and the low
    h - d, the slot.  The 2^d chunks of one slot are one array of shape
    (2^d, 2, R, S, L) sharded over the devices, so every gate below the
    chunk bits, and every gate on slot bits, runs on all devices at once
    with no data motion.  A gate on a device bit pairs each chunk with
    the chunk of the same slot on the partner device: one ``ppermute``
    moves a copy of every chunk of the slot to its partner, and each
    device computes its own half.  Each device holds its 2^(h-d) chunks
    plus a chunk or two in flight.  The group products, their precision
    and the reads are ChunkedState's."""

    def __init__(self, n: int, chunk_bits: int, devices,
                 precision: str = "highest"):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        if precision not in ("highest", "bf16_3x"):
            raise ValueError(f"unknown precision {precision!r}")
        ndev = len(devices)
        dbits = ndev.bit_length() - 1
        if ndev != 1 << dbits or dbits > chunk_bits:
            raise ValueError(f"{ndev} devices for {chunk_bits} chunk bits")
        self.jax, self.jnp, self.P = jax, jnp, P
        self.n, self.h, self.precision = n, chunk_bits, precision
        self.m = m = n - chunk_bits
        self.dbits, self.sbits = dbits, chunk_bits - dbits
        lane = min(m, 7)
        sub = min(max(m - 7, 0), 7)
        self.shape = (2, 1 << max(m - 14, 0), 1 << sub, 1 << lane)
        self.mesh = Mesh(np.array(devices), ("chip",))
        zeros = jax.jit(lambda: jnp.zeros((ndev,) + self.shape, jnp.float32),
                        out_shardings=NamedSharding(self.mesh, P("chip")))
        # one array per slot; the base class's chunk list
        self.chunks = [zeros() for _ in range(1 << self.sbits)]
        self.chunks[0] = self.chunks[0].at[0, 0, 0, 0, 0].set(1.0)
        self.pending = {}
        self._fns = {}

    # -- per-device programs ----------------------------------------------
    def _local(self, key, inner, arrays: int, extra: int = 0,
               outs: int = 1, donate: bool = True):
        """jit(shard_map) of ``inner(*chunks, *extra, dev)``: each device
        passes its chunk of each of ``arrays`` slot arrays (4-D), the
        ``extra`` operands whole, and its device index; ``inner`` returns
        one array, or a tuple of ``outs``, per device."""
        jax, P = self.jax, self.P

        def build():
            def body(*args):
                xs = [a[0] for a in args[:arrays]]
                out = inner(*xs, *args[arrays:],
                            jax.lax.axis_index("chip"))
                if outs > 1:
                    return tuple(o[None] for o in out)
                return out[None]

            spec = P("chip")
            f = jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(spec,) * arrays + (P(),) * extra,
                out_specs=(spec,) * outs if outs > 1 else spec,
                check_vma=False)
            return jax.jit(f, donate_argnums=(
                tuple(range(arrays)) if donate else ()))

        return self._fn(key, build)

    def _flip(self, x, j: int, only_set: int = None):
        """This device's partner chunk across device bit ``j`` (from the
        devices whose bit ``only_set`` is 1 when given; zeros elsewhere)."""
        ndev = 1 << self.dbits
        pairs = [(d, d ^ (1 << j)) for d in range(ndev)
                 if only_set is None or (d >> only_set) & 1]
        return self.jax.lax.ppermute(x, "chip", pairs)

    @staticmethod
    def _mul(a, x):
        """Complex scalar ``a`` = (re, im) times the chunk ``x``."""
        return (a[0] * x[0] - a[1] * x[1], a[0] * x[1] + a[1] * x[0])

    # -- gates ------------------------------------------------------------
    def _apply_group(self, g: int, mat: np.ndarray) -> None:
        jnp = self.jnp
        k = int(mat.shape[0]).bit_length() - 1
        mr = np.stack([mat.real, mat.imag]).astype(np.float32)
        shape = self.shape

        def inner(x, mr, dev):
            a, b = mr[0], mr[1]
            if g == 0:        # lanes: x[..., l] -> sum_l' K[l, l'] x
                eq, xs = "ij,...j->...i", x
            elif g == 1:      # sublanes
                eq, xs = "ij,rjl->ril", x
            else:             # seven bits of R at a time
                low = 1 << (R.GROUP_BITS * (g - 2))
                xs = x.reshape(2, -1, 1 << k, low, shape[2], shape[3])
                eq = "ij,xjysl->xiysl"

            def mm(m_, v):
                return self._dot(eq, m_, v)
            re = mm(a, xs[0]) - mm(b, xs[1])
            im = mm(a, xs[1]) + mm(b, xs[0])
            return jnp.stack([re, im]).reshape(shape)

        fn = self._local(("group", g, k), inner, 1, extra=1)
        self.chunks = [fn(c, mr) for c in self.chunks]

    def _chunk_1q(self, q: int, u: np.ndarray) -> None:
        jnp = self.jnp
        ur = np.stack([u.real, u.imag]).astype(np.float32)
        b = q - self.m
        if b < self.sbits:
            def pair(x0, x1, ur, dev):
                u00, u01 = (ur[0, 0, 0], ur[1, 0, 0]), (ur[0, 0, 1],
                                                        ur[1, 0, 1])
                u10, u11 = (ur[0, 1, 0], ur[1, 1, 0]), (ur[0, 1, 1],
                                                        ur[1, 1, 1])
                a0, a1 = self._mul(u00, x0), self._mul(u01, x1)
                b0, b1 = self._mul(u10, x0), self._mul(u11, x1)
                return (jnp.stack([a0[0] + a1[0], a0[1] + a1[1]]),
                        jnp.stack([b0[0] + b1[0], b0[1] + b1[1]]))

            fn = self._local(("u1pair",), pair, 2, extra=1, outs=2)
            self._pairs(1 << b, lambda x0, x1: fn(x0, x1, ur))
            return
        j = b - self.sbits

        def across(x, ur, dev):
            # own half: u00 x + u01 partner on bit 0, u11 x + u10 partner
            # on bit 1
            hi = ((dev >> j) & 1) == 1
            a = (jnp.where(hi, ur[0, 1, 1], ur[0, 0, 0]),
                 jnp.where(hi, ur[1, 1, 1], ur[1, 0, 0]))
            c = (jnp.where(hi, ur[0, 1, 0], ur[0, 0, 1]),
                 jnp.where(hi, ur[1, 1, 0], ur[1, 0, 1]))
            y0, y1 = self._mul(a, x), self._mul(c, self._flip(x, j))
            return jnp.stack([y0[0] + y1[0], y0[1] + y1[1]])

        fn = self._local(("u1dev", j), across, 1, extra=1)
        self.chunks = [fn(c, ur) for c in self.chunks]

    def _pairs(self, s: int, fn) -> None:
        """Replace each slot pair (k, k | s) by ``fn`` of it."""
        for k in range(len(self.chunks)):
            if not k & s:
                self.chunks[k], self.chunks[k | s] = fn(
                    self.chunks[k], self.chunks[k | s])

    def _cnot_across(self, control: int, target: int) -> None:
        jnp = self.jnp
        m, sb = self.m, self.sbits
        if target < m:
            tax, tk = self._axis(target)
            if control < m:
                cax, ck = self._axis(control)
                fn = self._local(("cx", control, target), lambda x, dev: (
                    jnp.where(self._bit(cax, ck) == 1,
                              self._partner(x, tax, tk), x)), 1)
                self.chunks = [fn(c) for c in self.chunks]
            elif control - m < sb:
                fn = self._local(("x", target), lambda x, dev: (
                    self._partner(x, tax, tk)), 1)
                cs = 1 << (control - m)
                self.chunks = [fn(c) if k & cs else c
                               for k, c in enumerate(self.chunks)]
            else:
                jc = control - m - sb
                fn = self._local(("xdev", jc, target), lambda x, dev: (
                    jnp.where(((dev >> jc) & 1) == 1,
                              self._partner(x, tax, tk), x)), 1)
                self.chunks = [fn(c) for c in self.chunks]
            return
        tb = target - m
        if control < m:
            cax, ck = self._axis(control)
            if tb < sb:
                def swap(x0, x1, dev):
                    sel = self._bit(cax, ck) == 1
                    return jnp.where(sel, x1, x0), jnp.where(sel, x0, x1)

                fn = self._local(("cxpair", control), swap, 2, outs=2)
                self._pairs(1 << tb, fn)
            else:
                jt = tb - sb
                fn = self._local(("cxdev", control, jt), lambda x, dev: (
                    jnp.where(self._bit(cax, ck) == 1, self._flip(x, jt),
                              x)), 1)
                self.chunks = [fn(c) for c in self.chunks]
            return
        cb = control - m
        if cb < sb and tb < sb:
            cs, ts = 1 << cb, 1 << tb
            for k in range(len(self.chunks)):
                if k & cs and not k & ts:
                    self.chunks[k], self.chunks[k | ts] = (
                        self.chunks[k | ts], self.chunks[k])
        elif cb < sb:
            jt = tb - sb
            fn = self._local(("flip", jt), lambda x, dev: self._flip(x, jt),
                             1)
            self.chunks = [fn(c) if k & (1 << cb) else c
                           for k, c in enumerate(self.chunks)]
        elif tb < sb:
            jc = cb - sb

            def swap_dev(x0, x1, dev):
                sel = ((dev >> jc) & 1) == 1
                return jnp.where(sel, x1, x0), jnp.where(sel, x0, x1)

            fn = self._local(("pairdev", jc), swap_dev, 2, outs=2)
            self._pairs(1 << tb, fn)
        else:
            jc, jt = cb - sb, tb - sb
            fn = self._local(("cxdevdev", jc, jt), lambda x, dev: (
                jnp.where(((dev >> jc) & 1) == 1,
                          self._flip(x, jt, only_set=jc), x)), 1)
            self.chunks = [fn(c) for c in self.chunks]

    # -- reads --------------------------------------------------------------
    def _chunk_index(self, d: int, k: int) -> int:
        return (d << self.sbits) | k

    def z_expectation(self, mask: int) -> float:
        """<Z on ``mask``>: per-row float32 partial sums on each device,
        summed in float64 on the host with each chunk's sign."""
        jax, jnp = self.jax, self.jnp
        m = self.m
        lo = mask & ((1 << m) - 1)
        ml, ms, mr = lo & 127, (lo >> 7) & 127, lo >> 14

        def inner(x, dev):
            par = (jax.lax.population_count(self._iota(3) & ml)
                   + jax.lax.population_count(self._iota(2) & ms)
                   + jax.lax.population_count(self._iota(1) & mr)) & 1
            w = (x[0] * x[0] + x[1] * x[1]) * (1 - 2 * par).astype(
                jnp.float32)
            return jnp.sum(w, axis=(2, 3))

        fn = self._local(("z", lo), inner, 1, donate=False)
        total = 0.0
        for k, x in enumerate(self.chunks):
            rows = np.asarray(fn(x), np.float64)
            for d in range(rows.shape[0]):
                c = self._chunk_index(d, k)
                sign = -1.0 if bin(c & (mask >> m)).count("1") & 1 else 1.0
                total += sign * float(np.sum(rows[d]))
        return total

    def sq_dist(self, prog: Blocks) -> float:
        """sum |prog - reference|^2 over the whole state, for ``prog`` a
        host Blocks copy: each slot's chunks go to their devices, the
        float32 differences and row sums run there, the rows are summed
        in float64 on the host."""
        jax, jnp = self.jax, self.jnp
        size = 1 << self.m
        fn = self._local(("sqdist",), lambda x, p, dev: jnp.sum(
            (x - p) * (x - p), axis=(0, 2, 3)), 2, donate=False)
        total = 0.0
        for k, x in enumerate(self.chunks):
            def block(index, k=k):
                a = self._chunk_index(index[0].start or 0, k) * size
                return np.stack([prog.plane(0, a, a + size),
                                 prog.plane(1, a, a + size)]).reshape(
                                     (1,) + self.shape)

            p = jax.make_array_from_callback(x.shape, x.sharding, block)
            total += float(np.sum(np.asarray(fn(x, p), np.float64)))
            p.delete()
        return total

    def host_state(self) -> Blocks:
        """The whole state as host Blocks, one per device."""
        size = 1 << self.m
        ndev = 1 << self.dbits
        per = size << self.sbits
        blocks = [np.empty((2, per), np.float32) for _ in range(ndev)]
        for k, x in enumerate(self.chunks):
            for s in x.addressable_shards:
                d = s.index[0].start or 0
                blocks[d][:, k * size:(k + 1) * size] = np.asarray(
                    s.data).reshape(2, size)
        return Blocks(blocks)


class _Spread:
    def __init__(self, st):
        self.st = st

    def z_expectation(self, mask):
        return self.st.z_expectation(mask)

    def host_state(self):
        return self.st.host_state()

    def state_err(self, prog) -> float:
        """||prog - reference||_2 for ``prog`` the host Blocks copy of the
        program's state (a plain (2, ...) host array is taken as one
        block)."""
        if not isinstance(prog, Blocks):
            prog = Blocks([np.asarray(prog).reshape(2, -1)])
        if prog.size * len(prog.blocks) != 1 << self.st.n:
            raise ValueError("reference does not cover the state")
        return float(np.sqrt(self.st.sq_dist(prog)))
