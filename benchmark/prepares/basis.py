"""A basis state |x> before every circuit, x drawn from the seed
(``qt.initClassicalState``)."""


class Prepare:
    def __init__(self, rng, n):
        self.rng, self.n = rng, n

    def spec(self, i):
        return ("basis", int(self.rng.integers(1 << self.n)))

    def apply(self, qt, q, spec):
        qt.initClassicalState(q, spec[1])
