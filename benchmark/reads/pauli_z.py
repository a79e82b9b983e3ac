"""<Z...Z> of one Z string drawn from the seed, read after every circuit
(``qt.calcExpecPauliSum`` with one term of weight 1)."""

import numpy as np

from ..reference import z_mask

API = "calcExpecPauliSum"


class Read:
    def __init__(self, rng, n):
        codes = np.where(rng.random(n) < 0.5, 3, 0)
        codes[rng.integers(n)] = 3
        self.codes = tuple(int(c) for c in codes)

    def spec(self, i):
        return self.codes

    def program(self, qt, q, codes):
        return float(qt.calcExpecPauliSum(q, list(codes), [1.0]))

    def reference(self, ref, codes):
        return ref.z_expectation(z_mask(codes))
