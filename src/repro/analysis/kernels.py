"""Static Pallas kernel safety pass.

The solver kernels under ``repro.kernels`` encode three hand-maintained
contracts that nothing cross-checked until now:

  * **VMEM guards** — ``dispatch.kernel_vmem_model()`` models each
    package's resident working set; dispatch admits a configuration
    when the model fits the budget. If the model under-counts what the
    kernel actually holds resident (the exact bug class the f64
    dtype-blind guards had before PR 5), near-cap configurations
    dispatch Pallas and die — or silently spill — on hardware this CI
    never sees. This pass derives the TRUE footprint from each
    package's BlockSpecs, operand shapes and scratch allocations (by
    capturing the ``pallas_call`` invocation under ``jax.eval_shape``
    — no TPU, no compilation) and flags any model that claims less
    than the derived footprint (guard drift).
  * **Output index-map injectivity** — two grid steps mapping to the
    same output block is only legal across grid dimensions declared
    "arbitrary" (sequential — the revisit is the accumulation pattern);
    a revisit across "parallel" dimensions is a write race that
    produces nondeterministic output on real grids.
  * **Index-map / gather bounds** — every BlockSpec index map must land
    inside the operand's block grid for every grid point, and the
    blocked-ELL SpMM's gather indices must address inside the
    VMEM-resident dense operand (checked on a concrete representative
    operand, padded slots included).

Capture is by monkeypatching ``pallas_call`` on the shared
``jax.experimental.pallas`` module for the duration of one traced
invocation: the fake records grid/specs/shapes and returns zeros of the
declared out_shape, so the wrapper code around the kernel runs
unmodified and the recorded specs are EXACTLY what the real call would
launch. Every package named in ``repro.kernels.KERNEL_PACKAGES`` must
have a describer here — a new package without one is itself an error
(coverage check), so kernels cannot bypass the safety pass by
omission.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.common import Diagnostic

_SLACK = 1.25   # admissible derived/modeled overshoot: the O(smaller-
                # operand) residents the models deliberately fold into
                # the budget's 2x headroom.


@dataclasses.dataclass(frozen=True)
class SpecView:
    """One operand's residency view: its full (padded) shape/dtype and,
    when blocked, the BlockSpec's block shape and index map (None block
    shape = the whole operand is VMEM-resident)."""

    label: str
    shape: Tuple[int, ...]
    dtype: Any
    block_shape: Optional[Tuple[int, ...]] = None
    index_map: Optional[Callable] = None


@dataclasses.dataclass(frozen=True)
class KernelCapture:
    """Everything one recorded ``pallas_call`` declares: the grid, the
    per-operand views, scratch allocations, dimension semantics, the
    scalar-prefetch operands and the blocked inputs' values (concrete
    arrays when the capture ran on real inputs, else None — the gather
    bounds check reads them)."""

    name: str
    grid: Tuple[int, ...]
    inputs: Tuple[SpecView, ...]
    outputs: Tuple[SpecView, ...]
    scratch: Tuple[Tuple[Tuple[int, ...], Any], ...]
    semantics: Optional[Tuple[str, ...]] = None
    scalar_args: Tuple[Any, ...] = ()
    input_values: Tuple[Any, ...] = ()

    def dim_semantics(self, dim: int) -> str:
        if self.semantics is None or dim >= len(self.semantics):
            return "arbitrary"    # TPU default: sequential grid dims
        return self.semantics[dim]


def _as_tuple(x):
    if x is None:
        return ()
    return tuple(x) if isinstance(x, (list, tuple)) else (x,)


def _spec_views(specs, args, prefix: str) -> Tuple[SpecView, ...]:
    views = []
    specs = _as_tuple(specs) if specs is not None else (None,) * len(args)
    for i, (spec, arg) in enumerate(zip(specs, args)):
        block = getattr(spec, "block_shape", None) if spec is not None \
            else None
        imap = getattr(spec, "index_map", None) if spec is not None \
            else None
        views.append(SpecView(
            label=f"{prefix}{i}", shape=tuple(jnp.shape(arg)),
            dtype=getattr(arg, "dtype", jnp.float32),
            block_shape=tuple(block) if block is not None else None,
            index_map=imap))
    return tuple(views)


def capture_pallas_calls(fn: Callable, *args) -> List[KernelCapture]:
    """Trace ``fn(*args)`` with ``pallas_call`` replaced by a recorder
    that returns zeros of the declared out_shape. Shape-only arguments
    (``jax.ShapeDtypeStruct``) are fine — the trace runs under
    ``jax.eval_shape`` so nothing is materialized or compiled."""
    import jax.experimental.pallas as pl_mod
    records: List[KernelCapture] = []
    real = pl_mod.pallas_call

    def fake(kernel, out_shape=None, *, grid_spec=None, grid=(),
             in_specs=None, out_specs=None, scratch_shapes=(),
             compiler_params=None, **kw):
        nsp = 0
        if grid_spec is not None:
            grid = tuple(grid_spec.grid)
            in_specs = grid_spec.in_specs
            out_specs = grid_spec.out_specs
            scratch_shapes = getattr(grid_spec, "scratch_shapes",
                                     scratch_shapes)
            nsp = getattr(grid_spec, "num_scalar_prefetch", 0)
        grid = tuple(grid)
        sem = None
        if compiler_params is not None:
            sem = getattr(compiler_params, "dimension_semantics", None)
            if sem is None and isinstance(compiler_params, dict):
                sem = compiler_params.get("dimension_semantics")
            sem = tuple(sem) if sem is not None else None
        out_leaves = jax.tree_util.tree_leaves(out_shape)
        scratch = tuple(
            (tuple(s.shape), getattr(s, "dtype", jnp.float32))
            for s in _as_tuple(scratch_shapes))

        def run(*call_args):
            scalars, blocked = call_args[:nsp], call_args[nsp:]

            def concrete(args):
                return tuple(None if isinstance(a, jax.core.Tracer) else a
                             for a in args)

            out_views = _spec_views(
                out_specs, out_leaves, "out") if out_leaves else ()
            records.append(KernelCapture(
                name=getattr(kernel, "__name__", "kernel"), grid=grid,
                inputs=_spec_views(in_specs, blocked, "in"),
                outputs=out_views, scratch=scratch, semantics=sem,
                scalar_args=concrete(scalars),
                input_values=concrete(blocked)))
            return jax.tree_util.tree_map(
                lambda sds: jnp.zeros(sds.shape, sds.dtype), out_shape)

        return run

    pl_mod.pallas_call = fake
    try:
        jax.eval_shape(fn, *args)
    finally:
        pl_mod.pallas_call = real
    return records


def capture_footprint(capture: KernelCapture) -> float:
    """The VMEM bytes a captured call holds resident: full operands for
    spec-less calls, block tiles (double-buffered — the Pallas pipeline
    prefetches the next tile while computing the current one) for
    blocked ones, a single buffer for operands whose block IS the full
    shape (resident, constant index map — nothing to prefetch), plus
    scratch. Scalar-prefetch operands live in SMEM and are excluded."""
    total = 0.0
    for view in capture.inputs + capture.outputs:
        block = view.block_shape or view.shape
        buffers = 2 if (view.block_shape is not None
                        and view.block_shape != view.shape
                        and capture.grid) else 1
        total += buffers * float(np.prod(block, dtype=np.int64)) \
            * jnp.dtype(view.dtype).itemsize
    for shape, dtype in capture.scratch:
        total += float(np.prod(shape, dtype=np.int64)) \
            * jnp.dtype(dtype).itemsize
    return total


def guard_drift_diags(where: str, modeled_bytes: float,
                      derived_bytes: float, cap: float,
                      slack: float = _SLACK) -> List[Diagnostic]:
    """The drift detector: the hand-maintained VMEM model must claim at
    least the BlockSpec-derived footprint (within ``slack`` for the
    small residents the models fold into the budget's headroom). A
    model claiming LESS admits configurations whose true working set
    exceeds the cap — the f64 2x-VMEM dispatch bug class."""
    if modeled_bytes * slack >= derived_bytes:
        return []
    return [Diagnostic(
        "kernels", "error", where,
        f"VMEM guard drift: kernel_vmem_model claims "
        f"{modeled_bytes:.0f} B resident but the BlockSpec-derived "
        f"footprint is {derived_bytes:.0f} B "
        f"({derived_bytes / max(modeled_bytes, 1.0):.2f}x, over the "
        f"{slack:g}x slack) — the dispatch guard would admit "
        f"configurations exceeding the {cap:.0f} B cap")]


def _grid_points(grid: Sequence[int]):
    if not grid:
        return
    idx = [0] * len(grid)
    while True:
        yield tuple(idx)
        for d in reversed(range(len(grid))):
            idx[d] += 1
            if idx[d] < grid[d]:
                break
            idx[d] = 0
        else:
            return


def output_injectivity_diags(where: str, capture: KernelCapture
                             ) -> List[Diagnostic]:
    """Write-race check: an output block visited by two grid points is
    only legal when every grid dimension the points differ in is
    declared "arbitrary" (sequential revisits accumulate in order); a
    revisit across a "parallel" dimension races."""
    diags: List[Diagnostic] = []
    for view in capture.outputs:
        if view.index_map is None or not capture.grid:
            continue
        seen: Dict[Tuple, Tuple] = {}
        flagged = False
        for point in _grid_points(capture.grid):
            block = tuple(view.index_map(*point))
            prev = seen.setdefault(block, point)
            if prev is point or flagged:
                continue
            racing = [d for d in range(len(capture.grid))
                      if prev[d] != point[d]
                      and capture.dim_semantics(d) == "parallel"]
            if racing:
                flagged = True
                diags.append(Diagnostic(
                    "kernels", "error", where,
                    f"write race on {view.label}: grid points {prev} "
                    f"and {point} both map to output block {block} but "
                    f"differ in \"parallel\" grid dimension(s) "
                    f"{racing} — revisits must be confined to "
                    f"\"arbitrary\" (sequential) dimensions, where "
                    f"they are the accumulation pattern"))
        if flagged:
            continue
    return diags


def index_map_bounds_diags(where: str, capture: KernelCapture
                           ) -> List[Diagnostic]:
    """Every BlockSpec index map must land inside the operand's block
    grid — ceil(dim/block) blocks per dimension — for EVERY grid point
    (padded shapes included: the wrappers pad before calling)."""
    diags: List[Diagnostic] = []
    for view in capture.inputs + capture.outputs:
        if view.index_map is None or view.block_shape is None \
                or not capture.grid:
            continue
        nblocks = [-(-dim // blk) for dim, blk
                   in zip(view.shape, view.block_shape)]
        for point in _grid_points(capture.grid):
            block = tuple(view.index_map(*point))
            oob = [d for d, (b, nb) in enumerate(zip(block, nblocks))
                   if not 0 <= b < nb]
            if oob:
                diags.append(Diagnostic(
                    "kernels", "error", where,
                    f"index map out of bounds on {view.label}: grid "
                    f"point {point} maps to block {block} but the "
                    f"operand shape {view.shape} at block "
                    f"{view.block_shape} only has {nblocks} blocks "
                    f"per dimension"))
                break
    return diags


# ---------------------------------------------------------------------------
# Per-package describers: representative invocations + the model kwargs
# the captured configuration corresponds to in kernel_vmem_model().
# ---------------------------------------------------------------------------

def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _describe_gram():
    from repro.kernels.gram.kernel import gram_t_pallas
    bm, bi, bj = 256, 128, 128
    caps = capture_pallas_calls(
        lambda x, y: gram_t_pallas(x, y, block_m=bm, block_i=bi,
                                   block_j=bj),
        _sds((512, 256)), _sds((512, 384)))
    return [("gram", dict(block_m=bm, block_i=bi, block_j=bj,
                          itemsize=4), caps)]


def _describe_spmm():
    from repro.core.types import SparseOperand
    from repro.kernels.spmm.kernel import ell_spmm_pallas
    # a concrete representative blocked-ELL operand (deterministic
    # banded pattern) so the gather indices are REAL padded data, not
    # just shapes (evaluated eagerly, so the wrapper's padding of them
    # stays concrete inside the capture's trace).
    C, Q, R = 32, 128, 8
    dense = np.zeros((R, C), np.float32)
    for i in range(R):
        for j in range(1 + i % 3):
            dense[i, (3 * i + 5 * j) % C] = 1.0 + j
    op = SparseOperand.from_dense(dense, with_bcoo=False)
    vals, idx, blocks = (np.asarray(op.row_vals), np.asarray(op.row_cols),
                         np.asarray(op.row_blocks))
    def small_call():
        with jax.ensure_compile_time_eval():
            return ell_spmm_pallas(jnp.asarray(vals), jnp.asarray(idx),
                                   jnp.asarray(blocks),
                                   jnp.zeros((C, Q), jnp.float32),
                                   ell_block=op.ell_block)

    small = capture_pallas_calls(small_call)
    Rl, Kl, Cl, Ql = 512, 64, 2048, 128
    large = capture_pallas_calls(
        lambda v, i, b, d: ell_spmm_pallas(v, i, b, d, ell_block=8),
        _sds((Rl, Kl)), _sds((Rl, Kl), jnp.int32),
        _sds((Rl,), jnp.int32), _sds((Cl, Ql)))
    return [
        ("spmm", dict(R=R, K=idx.shape[1], C=C, Q=Q, itemsize=4), small),
        ("spmm[large]", dict(R=Rl, K=Kl, C=Cl, Q=Ql, itemsize=4), large),
    ]


def _inner_shapes(s, mu, n_mats):
    smu = s * mu
    return [_sds((smu, smu))] + [_sds((s, mu))] * n_mats \
        + [_sds((s, mu), jnp.int32)]


def _describe_sa_inner():
    from repro.kernels.sa_inner.kernel import sa_inner_pallas
    out = []
    for s, mu in ((64, 8), (181, 8)):   # large + guard-boundary smu
        G, yp, zp, zv, idx = _inner_shapes(s, mu, 3)
        caps = capture_pallas_calls(
            lambda *a: sa_inner_pallas(*a, q=1.5, lam1=0.1),
            G, yp, zp, zv, idx, _sds((s,)), _sds((s,)))
        out.append((f"sa_inner[s={s},mu={mu}]",
                    dict(s=s, mu=mu, itemsize=4), caps))
    return out


def _describe_svm_inner():
    from repro.kernels.svm_inner.kernel import svm_inner_pallas
    out = []
    for s, mu in ((64, 8), (181, 8)):
        G, proj, b_sel, a_vals, idx = _inner_shapes(s, mu, 3)
        caps = capture_pallas_calls(
            lambda *a: svm_inner_pallas(*a, gamma=1e-3, nu=1.0),
            G, proj, b_sel, a_vals, idx)
        out.append((f"svm_inner[s={s},mu={mu}]",
                    dict(s=s, mu=mu, itemsize=4), caps))
    return out


def _describe_flash_attention():
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    caps = capture_pallas_calls(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True),
        _sds((1, 2, 256, 128)), _sds((1, 1, 256, 128)),
        _sds((1, 1, 256, 128)))
    return [("flash_attention", dict(block_q=128, block_k=128,
                                     head_dim=128, itemsize=4), caps)]


_DESCRIBERS: Dict[str, Callable[[], List[Tuple[str, Dict, List]]]] = {
    "gram": _describe_gram,
    "spmm": _describe_spmm,
    "sa_inner": _describe_sa_inner,
    "svm_inner": _describe_svm_inner,
    "flash_attention": _describe_flash_attention,
}


def _gather_bounds_diags(where: str, capture: KernelCapture
                         ) -> List[Diagnostic]:
    """spmm gather bounds: every (padded) ELL index must address a row
    of the VMEM-resident dense operand — checked on the concrete
    representative operand's data (the integer blocked input)."""
    diags: List[Diagnostic] = []
    ints = [a for v, a in zip(capture.inputs, capture.input_values)
            if a is not None and jnp.issubdtype(v.dtype, jnp.integer)]
    # the dense right operand is the unblocked (whole-array) resident.
    dense = [v for v in capture.inputs
             if v.block_shape is None and len(v.shape) == 2]
    if not ints or not dense:
        return diags
    idx = ints[0]
    rows = dense[0].shape[0]
    lo, hi = int(np.min(idx)), int(np.max(idx))
    if lo < 0 or hi >= rows:
        diags.append(Diagnostic(
            "kernels", "error", where,
            f"ELL gather out of bounds: indices span "
            f"[{lo}, {hi}] but the resident dense operand has {rows} "
            f"rows — padded slots must gather row 0 (value 0), never "
            f"past the operand"))
    return diags


def check_kernels() -> Tuple[List[Diagnostic], List[str]]:
    """Run the full safety pass over every kernel package: coverage
    (every ``KERNEL_PACKAGES`` entry has a describer AND a VMEM model
    entry), guard drift, output index-map injectivity, index-map
    bounds, and the spmm scalar-prefetch gather bounds. Returns
    (diagnostics, checked package names); derived footprints ride along
    as info diagnostics."""
    from repro.kernels import KERNEL_PACKAGES
    from repro.kernels.dispatch import kernel_vmem_model
    diags: List[Diagnostic] = []
    checked: List[str] = []
    model = kernel_vmem_model()
    for pkg in KERNEL_PACKAGES:
        if pkg not in _DESCRIBERS:
            diags.append(Diagnostic(
                "kernels", "error", pkg,
                f"kernel package {pkg!r} has no safety-pass describer "
                f"— register one in repro.analysis.kernels so its "
                f"VMEM guard and index maps are verified"))
            continue
        if pkg not in model:
            diags.append(Diagnostic(
                "kernels", "error", pkg,
                f"kernel package {pkg!r} has no kernel_vmem_model "
                f"entry — dispatch cannot guard what the model does "
                f"not describe"))
            continue
        checked.append(pkg)
        entry = model[pkg]
        for label, model_kwargs, captures in _DESCRIBERS[pkg]():
            for cap in captures:
                derived = capture_footprint(cap)
                modeled = entry.resident_bytes(**model_kwargs)
                diags.extend(guard_drift_diags(label, modeled, derived,
                                               entry.cap))
                diags.extend(output_injectivity_diags(label, cap))
                diags.extend(index_map_bounds_diags(label, cap))
                if pkg == "spmm":
                    diags.extend(_gather_bounds_diags(label, cap))
                diags.append(Diagnostic(
                    "kernels", "info", label,
                    f"derived VMEM footprint {derived:.0f} B vs "
                    f"modeled {modeled:.0f} B (cap {entry.cap} B), "
                    f"grid {cap.grid or '()'} — "
                    f"{len(cap.inputs)} in / {len(cap.outputs)} out / "
                    f"{len(cap.scratch)} scratch"))
    stray = sorted(set(_DESCRIBERS) - set(KERNEL_PACKAGES))
    if stray:
        diags.append(Diagnostic(
            "kernels", "error", ",".join(stray),
            f"describer(s) {stray} name no package in "
            f"repro.kernels.KERNEL_PACKAGES — stale registration"))
    return diags, checked
