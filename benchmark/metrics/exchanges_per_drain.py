"""Window-remap exchanges the program's fusion drains dispatched per
drain: its ``exchanges_total{op="window_remap"}`` counter (one per
half-shard swap or composed permute of a drain's remap parts, plan-cache
hits included) over ``fusion_drains_total``, from the program's
in-process telemetry registry, over every drain of the run.  It repeats
exactly from run to run and guards the planner's remap count.  None
where the program counted no window remap.  Layer: shard exchange."""


def read(ctx):
    from quest_tpu import telemetry

    drains = telemetry.counter_total("fusion_drains_total")
    remaps = telemetry.counter_sum("exchanges_total", op="window_remap")
    if not drains or not remaps:
        return None
    return remaps / drains
