"""The fusion drain's own host seconds per circuit completed in the
traced window: the program's ``fusion.drain`` spans less the
``fusion.optimize`` and ``fusion.plan`` spans inside them.  The rest is
the plan-cache key, the memory governor and the dispatch (the
``fusion.key``, ``fusion.govern`` and ``fusion.dispatch`` spans, which
name the idle gaps in ``breakdown``).  Layer: fusion planner."""

from ._spans import overlaps

DRAIN = ("fusion.drain",)
INNER = ("fusion.optimize", "fusion.plan")


def read(ctx):
    if not ctx.circuits or not overlaps(ctx, DRAIN):
        return None
    tr = ctx.trace
    s = (tr.span_seconds(DRAIN, ctx.w0, ctx.w1)
         - tr.span_seconds(INNER, ctx.w0, ctx.w1))
    return s / ctx.circuits
