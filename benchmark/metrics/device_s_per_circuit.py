"""Busy device seconds per circuit completed in the traced window: the
same work whatever kernels do it.  Layer: device."""


def read(ctx):
    if not ctx.circuits or not ctx.trace.devices:
        return None
    return ctx.busy_s / ctx.circuits
