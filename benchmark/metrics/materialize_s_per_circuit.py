"""Host seconds of the fusion planner's materialization step (the
program's ``fusion.materialize`` span: the side, cross and mask folds of
the native windowed plan into pass matrices) per circuit completed in
the traced window.  Layer: fusion planner."""

from ._spans import per_circuit


def read(ctx):
    return per_circuit(ctx, ("fusion.materialize",))
