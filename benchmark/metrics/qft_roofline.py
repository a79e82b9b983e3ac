"""Roofline share of the QFT kernels (radix ladders, cluster sweep, the
folded low-layer window pass, bit reversal): bytes / (device time x HBM
peak), in %.  Layer: kernels."""

from ._roofline import share


def read(ctx):
    return share(ctx, ("qft_ladder", "qft_cluster", "window_pass",
                       "bit_reversal"))
