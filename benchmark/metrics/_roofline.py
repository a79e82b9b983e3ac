"""Bandwidth roofline share of a set of kernel families: the HBM bytes
their calls move over their device time times the HBM peak, in %.  None
when the trace has none of their calls, or none that touch HBM."""


def share(ctx, families):
    acc = ctx.trace.by_family(ctx.w0, ctx.w1)
    secs = nbytes = 0
    for fam in families:
        if fam in acc:
            secs += acc[fam][0]
            nbytes += acc[fam][1]
    if secs <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / (secs * ctx.peaks["hbm_bytes_per_s"])
