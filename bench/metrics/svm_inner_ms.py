"""Device time of the ``svm_inner`` kernel (the SVM's s dependent inner
updates) per outer iteration, averaged over the chips. The kernel is
found by its name in the compiled HLO. The loops beside it in the group
loop are not counted: on the sparse path one of them is the SpMM
reference's loop over the ELL blocks."""
import hlo
import reduce_trace


def read(ctx):
    names = hlo.kernels_named(ctx.hlo, "svm_inner")
    return reduce_trace.per_outer_ms(
        ctx.trace.op_seconds(lambda op: op.name in names), ctx.outer)
