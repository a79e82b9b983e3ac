"""The full quantum Fourier transform: QuEST's and qHiPSTER's QFT
benchmark.  The circuit body is one ``qt.applyFullQFT`` call on a basis
state.  The reference is the closed form of QFT|x>: a product state,
outer(hi, lo) of two float64 vectors over the top and the low 14 index
bits, compared with the program's state where it lies on the device, and
each amplitude a read asks for, from the exact integer phase.
"""

from __future__ import annotations

import numpy as np

from .. import reference as R

LOW_BITS = 14     # the (128, 128) minor axes of the register's device shape


class Family:
    def __init__(self, cfg: dict):
        self.n = int(cfg["qubits"])
        if self.n < LOW_BITS:
            raise ValueError(f"the qft family runs {LOW_BITS} qubits or more")

    def create(self, qt, env):
        return qt.createQureg(self.n, env)

    def draw_params(self, rng):
        return None

    def issue(self, qt, q, params, span) -> None:
        qt.applyFullQFT(q)

    def snapshot(self, q):
        """The program's state where it lies, on the device: the
        reference needs little memory."""
        return q.device_amps()

    def reference(self, init, chain, on_chip: bool):
        """QFT|x> after the one circuit a basis-state mix runs per
        initialisation (``init`` is ("basis", x), ``chain`` one entry)."""
        if init[0] != "basis" or len(chain) != 1:
            raise ValueError("the qft family has a reference for one QFT "
                             "of a basis state")
        yield _Qft(self.n, init[1])


class _Qft:
    def __init__(self, n, x):
        self.n, self.x = n, int(x)

    def amplitude(self, k) -> complex:
        """Amplitude k of QFT|x>: 2^(-n/2) exp(2 pi i x k / 2^n), the
        phase from the exact integer (x * k) mod 2^n."""
        ph = (self.x * int(k)) % (1 << self.n)
        return complex(np.exp(2j * np.pi * ph / (1 << self.n))
                       * 2.0 ** (-self.n / 2))

    def state_err(self, prog) -> float:
        """||prog - QFT|x>||_2 for ``prog`` the program's state on the
        device, viewed as (2, 2^(n-14), 128, 128): float32 differences and
        row sums on the device, the rows summed in float64 on the host."""
        import jax
        import jax.numpy as jnp

        hi, lo = R.qft_factors(self.n, self.x, LOW_BITS)
        f32 = np.float32
        hr, hi_ = (jnp.asarray(v.astype(f32))[:, None, None]
                   for v in (hi.real, hi.imag))
        lr, li = (jnp.asarray(v.reshape(128, 128).astype(f32))
                  for v in (lo.real, lo.imag))

        @jax.jit
        def rows(p, hr, hi_, lr, li):
            p = p.reshape(2, -1, 128, 128)
            dr = p[0] - (hr * lr - hi_ * li)
            di = p[1] - (hr * li + hi_ * lr)
            return jnp.sum(dr * dr + di * di, axis=(1, 2))

        total = np.sum(np.asarray(rows(prog, hr, hi_, lr, li), np.float64))
        return float(np.sqrt(total))
