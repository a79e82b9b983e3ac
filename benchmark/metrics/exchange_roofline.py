"""Share of the chips' interconnect peak that the shard exchange reached
while in flight: the bytes its collectives delivered to each chip over
their time in flight (``_exchange``), summed over the chips, against
that time times one chip's ICI peak (1,600 Gbit/s on a v5e, Google Cloud
documentation), in %.  None where the trace has no collective or the
device has no peak here.  Layer: shard exchange."""

from ._exchange import ici_peak, per_device


def read(ctx):
    acc = per_device(ctx.trace, ctx.w0, ctx.w1)
    peak = ici_peak()
    secs = sum(s for s, _b in acc.values())
    nbytes = sum(b for _s, b in acc.values())
    if not peak or secs <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / (secs * peak)
