"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (smoke tests must keep seeing 1 CPU device).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axes: 'data' (DP/FSDP), 'model' (TP/EP/SP), plus 'pod' (hierarchical
    DP) on the multi-pod mesh.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh helper (tests, examples)."""
    return jax.make_mesh(tuple(shape), tuple(axes))
