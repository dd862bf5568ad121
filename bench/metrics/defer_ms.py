"""Device time of the ``phase.defer`` operations (the deferred residual
or primal update and the objective trace) per outer iteration, averaged
over the chips."""
import phases


def read(ctx):
    return phases.ms_per(ctx, ("defer",), ctx.outer)
