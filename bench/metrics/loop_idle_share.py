"""Share of the window in which the device is idle inside a solve's
loop: idle gaps that end at an operation of ``phase.sample``,
``assemble`` (``gather``, ``gram``), ``reduce``, ``inner`` or ``defer``,
over the window, averaged over the chips. The part of
``device_idle_share`` between solves ends at a ``phase.setup``
operation and is left out."""
import phases


def read(ctx):
    return phases.loop_idle_share(ctx)
