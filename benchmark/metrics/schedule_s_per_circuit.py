"""Host seconds of the fusion planner's other steps per circuit completed
in the traced window: the program's ``fusion.analyse`` (controlled-form
rewrite, per-gate ranks and flags, permutation-run classification),
``fusion.schedule`` (the structural scheduler) and ``fusion.group``
(side split, megawin grouping, plan split) spans.  Layer: fusion
planner."""

from ._spans import per_circuit


def read(ctx):
    return per_circuit(ctx, ("fusion.analyse", "fusion.schedule",
                             "fusion.group"))
