"""Share of the fused Gram kernel's roofline: the least time the chip
could take for the product Y^T [Y | v] (the larger of its FLOPs over the
peak and its least bytes over the HBM bandwidth, ``peaks.py``), over the
kernel's device time, per outer iteration, averaged over the chips.

The kernel's trace events are found by its instruction name in the
compiled HLO (the ``gram_t`` custom call)."""
import hlo
import peaks


def read(ctx):
    names = hlo.kernels_named(ctx.hlo, "gram_t")
    secs = ctx.trace.op_seconds(lambda op: op.name in names)
    if not secs:
        return None
    p = peaks.peaks_for(ctx.device_kind)
    s, mu = ctx.cfg.s, ctx.cfg.block_size
    full, rem = divmod(ctx.cfg.iterations, s)
    least = 0.0
    for groups, smu in ((full, s * mu), (1 if rem else 0, rem * mu)):
        if groups:
            t, _ = peaks.roofline_seconds(peaks.gram_flops(ctx.m_loc, smu),
                                          peaks.gram_least_bytes(ctx.m_loc, smu),
                                          p)
            least += groups * t
    kernel_s = sum(secs.values()) / len(secs) / ctx.solves   # per solve
    return 100.0 * least / kernel_s
