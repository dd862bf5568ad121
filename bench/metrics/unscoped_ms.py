"""Busy device time of the operations that no phase scope covers (loop
control, the stacking of the objective trace, copies of the program's
arguments) per solve, averaged over the chips: what the phase metrics
do not see."""
import phases


def read(ctx):
    return phases.ms_per(ctx, (phases.UNSCOPED,), ctx.solves)
