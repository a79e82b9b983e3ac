"""Share of the traced window, in %, in which no operation ran on the
device: 100 * (1 - busy / window), busy the union of the device's
operation intervals.  Layer: device."""


def read(ctx):
    window = ctx.w1 - ctx.w0
    if window <= 0 or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - ctx.busy_s / window)
