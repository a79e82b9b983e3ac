"""Host seconds of the program's circuit optimizer and fusion planner
(its ``fusion.optimize`` and ``fusion.plan`` spans) per circuit completed
in the traced window.  Layer: fusion planner."""


def read(ctx):
    if not ctx.circuits:
        return None
    s = ctx.trace.span_seconds(("fusion.optimize", "fusion.plan"),
                               ctx.w0, ctx.w1)
    return s / ctx.circuits
