"""State passes the program's fusion drains dispatched per drain: its
``fusion_passes_total`` counter (one per skeleton entry of every plan
part, a megawin group one) over ``fusion_drains_total``, read from the
program's in-process telemetry registry.  The ratio is over every drain
of the run, the warm-up's included: the drains of a cell all plan the
same shape.  None where the program has no such counter.  Layer: fusion
planner."""


def read(ctx):
    from quest_tpu import telemetry

    drains = telemetry.counter_total("fusion_drains_total")
    passes = telemetry.counter_total("fusion_passes_total")
    if not drains or not passes:
        return None
    return passes / drains
