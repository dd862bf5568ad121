"""Device time of the ``phase.setup`` and ``phase.finalize`` operations
(the work before and after a solve's iteration loop, e.g. the SVM's
x = A^T (b alpha)) per solve, averaged over the chips."""
import phases


def read(ctx):
    return phases.ms_per(ctx, ("setup", "finalize"), ctx.solves)
