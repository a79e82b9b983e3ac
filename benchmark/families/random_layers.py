"""Random-layer circuits, an assumed pattern (see ``configs/rc30.json``):
random-axis rotation layers as in McClean et al.'s random parameterized
circuits (arXiv:1803.11173), with a CNOT brickwork in place of their CZ
on every neighbouring pair.

Each of ``layers`` layers puts a rotateX/Y/Z on every qubit, a CNOT ladder
on (q, q+1) from offset layer % 2, and one long-range CNOT
(q, n-1-q) with q = layer % (n // 2).  Which rotation sits where is fixed
by the configuration's ``structure_seed``, so every run seed drives the
same compiled programs; the run seed draws only the angles.  At 30 qubits
and 20 layers that is 910 gates.

The circuit body is issued through ``qt.rotate*`` / ``qt.controlledNot``
inside ``qt.gateFusion`` and drained when the block closes.
"""

from __future__ import annotations

import numpy as np

from .. import reference as R


class Family:
    def __init__(self, cfg: dict):
        self.n = int(cfg["qubits"])
        self.layers = int(cfg["layers"])
        # the axis pattern as chip_smoke.random_circuit draws it: axis and
        # angle interleaved from one generator; only the axes are kept
        rng = np.random.default_rng(int(cfg["structure_seed"]))
        axes = np.zeros((self.layers, self.n), np.int64)
        for layer in range(self.layers):
            for q in range(self.n):
                axes[layer, q] = int(rng.integers(3))
                rng.uniform(0.0, 2.0 * np.pi)
        self.axes = axes
        self.chunk_bits = int(cfg["reference_chunk_bits"])

    def create(self, qt, env):
        return qt.createQureg(self.n, env)

    def draw_params(self, rng) -> np.ndarray:
        """Fresh rotation angles, one per (layer, qubit)."""
        return rng.uniform(0.0, 2.0 * np.pi, size=(self.layers, self.n))

    def ops(self, angles) -> list:
        n = self.n
        ops = []
        for layer in range(self.layers):
            for q in range(n):
                ops.append(("rot", int(self.axes[layer, q]), q,
                            float(angles[layer, q])))
            for q in range(layer % 2, n - 1, 2):
                ops.append(("cnot", q, q + 1))
            q = layer % (n // 2)
            ops.append(("cnot", q, n - 1 - q))
        return ops

    def issue(self, qt, q, angles, span) -> None:
        """The circuit body through the public API, captured in one
        gateFusion block and drained when it closes."""
        rot = (qt.rotateX, qt.rotateY, qt.rotateZ)
        ops = self.ops(angles)
        with qt.gateFusion(q):
            with span("capture"):
                for op in ops:
                    if op[0] == "rot":
                        rot[op[1]](q, op[2], op[3])
                    else:
                        qt.controlledNot(q, op[1], op[2])

    # -- the reference --------------------------------------------------------
    def snapshot(self, q) -> np.ndarray:
        """A float32 host copy of the program's state: the reference
        needs the chip's memory."""
        return np.asarray(q.device_amps())

    def reference(self, init, chain, on_chip: bool,
                  precision: str = "highest"):
        """Replay ``chain`` (a list of angle arrays, applied in order to
        |0...0>) and yield, after each, a reference object with
        ``z_expectation(mask)``, ``state_err(prog)`` and ``host_state()``.
        Off the chip, at "highest", the dense complex128 state."""
        if init != ("zero",):
            raise ValueError("random_layers circuits start from |0...0>")
        if not on_chip and precision == "highest":
            psi = None
            for angles in chain:
                psi = R.dense_state(self.n, self.ops(angles), psi)
                yield _Dense(psi)
            return
        st = R.ChunkedState(self.n, self.chunk_bits, precision)
        try:
            for angles in chain:
                st.apply(self.ops(angles))
                yield _Chunked(st)
        finally:
            st.free()


class _Dense:
    def __init__(self, psi):
        self.psi = psi

    def z_expectation(self, mask):
        return R.dense_z_expectation(self.psi, mask)

    def host_state(self):
        return np.stack([self.psi.real, self.psi.imag]).astype(np.float32)

    def state_err(self, prog) -> float:
        """||prog - psi||_2 for ``prog`` the (2, ...) float32 host copy."""
        p = np.asarray(prog, np.float64).reshape(2, -1)
        return float(np.linalg.norm(p[0] + 1j * p[1] - self.psi))


class _Chunked:
    def __init__(self, st):
        self.st = st

    def z_expectation(self, mask):
        return self.st.z_expectation(mask)

    def host_state(self):
        return self.st.host_state()

    def state_err(self, prog) -> float:
        """||prog - reference||_2, chunk by chunk on the device, for
        ``prog`` the (2, ...) float32 host copy of the program's state."""
        st = self.st
        flat = np.asarray(prog).reshape(2, -1)
        size = 1 << st.m
        if flat.shape[1] != size * len(st.chunks):
            raise ValueError("reference does not cover the state")
        total = sum(st.sq_dist(c, flat[:, c * size:(c + 1) * size].reshape(
            st.shape)) for c in range(len(st.chunks)))
        return float(np.sqrt(total))
