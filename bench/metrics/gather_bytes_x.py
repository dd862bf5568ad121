"""Bytes the ``phase.gather`` operations write, over the bytes of the
sampled columns or rows they have to deliver: each event's result bytes
(from its shape in the trace) summed over the window and averaged over
the chips, over solves * iterations * block_size * m_loc * itemsize.
1 is a gather that moves only what it needs."""
import numpy as np

import phases


def read(ctx):
    written = phases.phase_bytes(ctx, ("gather",))
    cfg = ctx.cfg
    needed = ctx.solves * cfg.iterations * cfg.block_size * ctx.m_loc \
        * np.dtype(cfg.dtype).itemsize
    if written is None or needed <= 0:
        return None
    return written / needed
