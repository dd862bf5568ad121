"""Device time of the Lasso s-step inner stage per outer iteration,
averaged over the chips.

The Lasso solvers do not launch the ``sa_inner`` Pallas kernel: their
inner stage is a scan. So the stage is found by its place in the
compiled HLO, the loops inside the group loop, together with any kernel
named ``sa_inner`` should one be put on the path."""
import hlo
import reduce_trace


def read(ctx):
    names = hlo.inner_stage_ops(ctx.hlo, "sa_inner")
    return reduce_trace.per_outer_ms(
        ctx.trace.op_seconds(lambda op: op.name in names), ctx.outer)
