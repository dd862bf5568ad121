"""Device time of the ``phase.sample`` operations (the draw of the
group's coordinate blocks and its schedule window) per outer iteration,
averaged over the chips; read by phase from the compiled program's
named scopes (``bench/phases.py``)."""
import phases


def read(ctx):
    return phases.ms_per(ctx, ("sample",), ctx.outer)
