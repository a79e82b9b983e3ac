"""Device seconds in which the shard exchange (the collectives that move
state between chips, ``_exchange``) was in flight, per circuit completed
in the traced window, averaged over the chips.  None where the trace has
no collective.  Layer: shard exchange."""

from ._exchange import per_device


def read(ctx):
    acc = per_device(ctx.trace, ctx.w0, ctx.w1)
    if not acc or not ctx.circuits:
        return None
    return sum(s for s, _b in acc.values()) / len(acc) / ctx.circuits
