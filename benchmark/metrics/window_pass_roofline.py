"""Roofline share of the window and diagonal passes (``ops/fused.py``):
bytes those calls move / (their device time x HBM peak), in %.  Layer:
kernels."""

from ._roofline import share


def read(ctx):
    return share(ctx, ("window_pass", "diag_pass"))
