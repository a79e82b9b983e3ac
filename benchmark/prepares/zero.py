"""|0...0> before every circuit (``qt.initZeroState``)."""


class Prepare:
    def __init__(self, rng, n):
        pass

    def spec(self, i):
        return ("zero",)

    def apply(self, qt, q, spec):
        qt.initZeroState(q)
