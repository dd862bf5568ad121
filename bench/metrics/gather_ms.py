"""Device time of the ``phase.gather`` operations (the take of the
sampled columns or rows of A, with any relayout the compiler puts in
for it) per outer iteration, averaged over the chips."""
import phases


def read(ctx):
    return phases.ms_per(ctx, ("gather",), ctx.outer)
