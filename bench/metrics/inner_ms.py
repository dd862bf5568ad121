"""Device time of the ``phase.inner`` operations (the s dependent
coordinate updates, or the one update of a classical iteration) per
outer iteration, averaged over the chips. Unlike ``sa_inner_ms`` it
needs no place in the HLO: the scope names the operations."""
import phases


def read(ctx):
    return phases.ms_per(ctx, ("inner",), ctx.outer)
