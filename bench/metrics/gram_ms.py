"""Device time of the local Gram or cross product per outer iteration,
averaged over the chips: the ``phase.gram`` operations (the product and
the building of its operands: padding, copies, the kernel or the jnp
product) and the rest of ``phase.assemble`` outside the gather."""
import phases


def read(ctx):
    return phases.ms_per(ctx, ("gram", "assemble"), ctx.outer)
