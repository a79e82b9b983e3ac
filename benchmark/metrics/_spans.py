"""Host seconds of a set of the program's spans in the traced window, per
circuit completed there.  None when no span of the set overlaps the
window: a program without those spans, or a cell that does not run that
code in its window."""


def overlaps(ctx, names) -> bool:
    return any(s.name in names and s.end > ctx.w0 and s.start < ctx.w1
               for s in ctx.trace.spans)


def per_circuit(ctx, names):
    if not ctx.circuits or not overlaps(ctx, names):
        return None
    return ctx.trace.span_seconds(names, ctx.w0, ctx.w1) / ctx.circuits
