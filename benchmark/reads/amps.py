"""``count`` amplitudes at indices drawn from the seed, fresh for every
circuit (``qt.getAmp``, one call each)."""

import numpy as np

API = "getAmp"


class Read:
    def __init__(self, rng, n, count):
        self.rng, self.n, self.count = rng, n, int(count)

    def spec(self, i):
        return tuple(int(k) for k in self.rng.integers(1 << self.n,
                                                        size=self.count))

    def program(self, qt, q, idx):
        return np.array([qt.getAmp(q, k) for k in idx])

    def reference(self, ref, idx):
        return np.array([ref.amplitude(k) for k in idx])
