"""Host seconds of the program's ``fusion.reconcile`` span (a sharded
drain's window-remap accounting and its re-plan through the cost model,
run on every drain, plan-cache hits included) per circuit completed in
the traced window.  None where the program has no such span.  Layer:
fusion planner."""

from ._spans import per_circuit


def read(ctx):
    return per_circuit(ctx, ("fusion.reconcile",))
