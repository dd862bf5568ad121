"""Silent f64 -> f32 downcast detection.

The numerical claims of the paper (machine-precision agreement of the
s-step recurrences with the classical iterates, Fig. 5's stability
sweeps) are only meaningful if a float64 experiment actually runs in
float64 end to end. jax makes that easy to break silently: any literal
created without an explicit dtype, any ``jnp.zeros`` default, any
numpy float32 constant inserts a ``convert_element_type`` that narrows
the computation — and nothing warns.

This pass traces each family×variant solve with float64 inputs under
``jax.enable_x64`` and walks the jaxpr (recursively, into
scan/while/cond/pjit bodies) for ``convert_element_type`` equations
whose source dtype is a WIDER float than their destination — each one
is a place where precision is silently discarded, reported with its
jax source location.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import numpy as np

from repro.analysis.common import (Diagnostic, bench_shape, family_variants,
                                   variant_config)
from repro.core.types import ProblemFamily


def _source_line(eqn) -> str:
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info)
        if frame is not None:
            return f"{frame.file_name.rsplit('/', 1)[-1]}:" \
                   f"{frame.start_line}"
    except Exception:
        pass
    return "<unknown>"


def find_float_narrowing(jaxpr) -> List[Tuple[str, str, str]]:
    """All float-narrowing ``convert_element_type`` eqns in a
    (Closed)Jaxpr, recursively: (src_dtype, dst_dtype, source_line)."""
    found: List[Tuple[str, str, str]] = []

    def walk(open_j) -> None:
        from jax._src import core as jcore
        for eqn in open_j.eqns:
            if eqn.primitive.name == "convert_element_type":
                src = np.dtype(eqn.invars[0].aval.dtype)
                dst = np.dtype(eqn.params["new_dtype"])
                if src.kind == "f" and dst.kind == "f" \
                        and src.itemsize > dst.itemsize:
                    found.append((src.name, dst.name, _source_line(eqn)))
            for val in eqn.params.values():
                vals = val if isinstance(val, (list, tuple)) else (val,)
                for v in vals:
                    if isinstance(v, jcore.ClosedJaxpr):
                        walk(v.jaxpr)
                    elif isinstance(v, jcore.Jaxpr):
                        walk(v)

    walk(getattr(jaxpr, "jaxpr", jaxpr))
    return found


def check_dtypes(fam: ProblemFamily,
                 variants: Optional[Tuple[str, ...]] = None,
                 iterations: int = 16) -> Tuple[List[Diagnostic], List[str]]:
    """Trace each variant's sharded solve with float64 inputs (x64
    enabled for the duration of the trace only — the process-global
    flag is untouched) and flag every silent float narrowing."""
    from repro.core import api
    import jax.numpy as jnp
    diags: List[Diagnostic] = []
    checked: List[str] = []
    axis = fam.default_axes if isinstance(fam.default_axes, str) \
        else fam.default_axes[0]
    mesh = jax.make_mesh((1,), (axis,))
    m, n = bench_shape(fam)
    for variant in variants or family_variants(fam):
        where = f"{fam.name}:{variant}"
        checked.append(where)
        cfg = variant_config(fam, variant, iterations=iterations,
                             dtype=jnp.float64)
        with jax.enable_x64(True):
            traced = api.trace_sharded(fam, cfg, mesh, m=m, n=n,
                                       dtype=jnp.float64)
        for src, dst, line in find_float_narrowing(traced.jaxpr):
            diags.append(Diagnostic(
                "dtypes", "error", where,
                f"silent {src} -> {dst} downcast at {line}: a float64 "
                f"solve loses precision through an implicit "
                f"convert_element_type (unhinted literal or np.float32 "
                f"constant) — thread the dtype through instead"))
    return diags, checked
