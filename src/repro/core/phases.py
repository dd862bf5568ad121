"""Named scopes for the phases of a solve.

Every solver marks where each phase of its iteration runs:

    setup -> [per iteration or outer group: sample -> assemble
              (gather, gram) -> reduce -> inner -> defer] -> finalize

``gather`` (the take of the sampled columns or rows) and ``gram`` (the
local Gram/cross product and the building of its operands) nest inside
``assemble``. A scope is ``jax.named_scope("phase.<name>")``: it lands
in the ``op_name`` metadata of every compiled instruction traced inside
it, so a device trace, whose events carry instruction names, can be
split by phase on the device's own clock. It adds no operation; the
compiled program is the same apart from its metadata.
"""
import jax

NAMES = ("setup", "sample", "assemble", "gather", "gram", "reduce",
         "inner", "defer", "finalize")


def scope(name: str):
    """The named scope of phase ``name`` (one of :data:`NAMES`)."""
    return jax.named_scope("phase." + name)
