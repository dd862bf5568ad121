"""Device time per outer iteration in which an all-reduce runs on a chip
and no other operation does, averaged over the chips. The all-reduces
are found by their kind in the compiled HLO: jax names them ``psum.N``."""
import hlo
import reduce_trace


def read(ctx):
    names = hlo.all_reduces(ctx.hlo)
    return reduce_trace.per_outer_ms(
        ctx.trace.exposed_seconds(lambda op: op.name in names), ctx.outer)
