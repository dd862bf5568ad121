"""The unified solver facade: ``repro.api.solve(problem, cfg, ...)``.

One entry point, two registry axes:

* **family** — which problem class (``FAMILIES`` in ``repro.core.types``,
  populated by ``@register_family`` in each family's own module). The
  family is inferred from the problem's type (plus its ``accepts`` hook,
  which is how linear and kernel SVM share ``SVMProblem``), or forced
  with ``family="..."``.
* **backend** — where it runs (``BACKENDS`` here): ``"local"`` calls the
  family's dispatch directly (optionally inside a caller-managed
  ``shard_map`` via ``axis_name``); ``"sharded"`` wraps the SAME solver
  in the generic distributed driver below, which builds the
  shard_map/pad/unpad plumbing from the family's declared partition
  axis — the paper's Fig. 1 row layout and Sec. V column layout are the
  two values of one field, not two hand-written drivers.

Every legacy entry point (``solve_lasso``, ``solve_svm_sharded``,
``lower_svm_step``, ...) is a thin shim over this module, so the two
paths are the same compiled program — bit-identical results, by
construction and by test (tests/test_api.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro.core.types import (FAMILIES, ProblemFamily, SolveState,
                              SolverConfig, SolverResult, SparseOperand)

# Importing the family modules is what populates FAMILIES: each family
# self-registers from its own module (the ``KERNELS`` pattern). A new
# family only needs to be imported somewhere — these five lines are the
# complete dispatch "table".
import repro.core.lasso       # noqa: F401  (registers "lasso")
import repro.core.svm         # noqa: F401  (registers "svm")
import repro.core.kernel_svm  # noqa: F401  (registers "ksvm")
import repro.core.logreg      # noqa: F401  (registers "logreg")
import repro.core.sfista      # noqa: F401  (registers "sfista")

AxisNames = Union[str, Tuple[str, ...]]

__all__ = [
    "solve", "solve_sharded", "lower_solve", "resolve_family", "families",
    "BACKENDS", "TracedSolve", "trace_sharded",
]


def families() -> Tuple[str, ...]:
    """Registered family names, sorted."""
    return tuple(sorted(FAMILIES))


def resolve_family(problem=None, family: Optional[object] = None
                   ) -> ProblemFamily:
    """Resolve a family from an explicit name or the problem's type."""
    if family is not None:
        if isinstance(family, ProblemFamily):
            return family
        if family not in FAMILIES:
            raise ValueError(
                f"unknown family {family!r}; registered: {sorted(FAMILIES)}")
        return FAMILIES[family]
    matched = [f for f in FAMILIES.values() if f.matches(problem)]
    if not matched:
        raise ValueError(
            f"no registered problem family handles "
            f"{type(problem).__name__}; registered: {sorted(FAMILIES)}")
    if len(matched) > 1:
        raise ValueError(
            f"problem matches several families "
            f"({sorted(f.name for f in matched)}); disambiguate with "
            f"family=...")
    return matched[0]


# ---------------------------------------------------------------------------
# The generic sharded driver: ONE implementation of the pad/shard_map/
# unpad plumbing, parameterized by the family's declared partition axis.
# ---------------------------------------------------------------------------

def _pad_to(x, size: int, axis: int, dtype):
    """``x`` as a ``dtype`` device array, zero-padded along ``axis`` to
    ``size`` (on the device: a dense A is never copied to the host)."""
    x = jnp.asarray(x, dtype)
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _shard_map(fn, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` over ``mesh`` with its axes made Auto.

    ``jax.make_mesh`` builds Explicit axes by default, under which the
    partitioned outputs carry their sharding in their types and the
    driver's unpadding slice (which cuts a sharded dimension) is a
    sharding type error. The solve's layout is fully given by the specs,
    so the driver runs on an Auto view of the same devices. The
    replication contract is checked by ``repro.analysis.replication``:
    ``check_vma`` rejects the kernel-SVM and logistic-regression
    programs (their scan carries start replicated and become
    shard-varying)."""
    auto = Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    return jax.shard_map(fn, mesh=auto, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _axis_size(mesh: Mesh, axes: AxisNames) -> int:
    if isinstance(axes, str):
        return mesh.shape[axes]
    return int(np.prod([mesh.shape[a] for a in axes]))


def _stack_sparse_shards(op: SparseOperand, n_shards: int, part_axis: int,
                         padded: int, dtype) -> SparseOperand:
    """Split a SparseOperand into per-shard operands along the partition
    axis and stack their blocked-ELL leaves with a leading shard axis —
    the form ``shard_map`` partitions with a single leading-axis spec.

    Zero-padding the partitioned axis is exact: padded rows/columns
    store no nonzeros (zero ELL blocks), so they contribute nothing to
    any Gram/cross product and the corresponding state coordinates stay
    0. Per-shard ELL arrays are rebuilt so indices are shard-LOCAL, with
    widths padded to the max across shards (uniform leaves); the BCOO
    form does not cross shard_map (dropped — ``squeeze_shard`` inside
    rebuilds a pure-ELL local operand).
    """
    from repro.core.types import ell_width

    m, n = op.shape
    rows, cols, vals = op.host_coo()
    vals = vals.astype(np.dtype(dtype) if dtype is not None else vals.dtype)
    size = padded // n_shards
    part = rows if part_axis == 0 else cols
    loc_shape = (size, n) if part_axis == 0 else (m, size)
    pieces = []
    for k in range(n_shards):
        sel = (part >= k * size) & (part < (k + 1) * size)
        r = rows[sel] - (k * size if part_axis == 0 else 0)
        c = cols[sel] - (k * size if part_axis == 1 else 0)
        pieces.append((r, c, vals[sel]))
    # uniform leaf widths across shards (so the stack is rectangular):
    # the max per-row/column count over all shards, block-rounded.
    rw = ell_width(max((np.bincount(r, minlength=loc_shape[0]).max()
                        if r.size else 0) for r, _, _ in pieces),
                   op.ell_block)
    cw = ell_width(max((np.bincount(c, minlength=loc_shape[1]).max()
                        if c.size else 0) for _, c, _ in pieces),
                   op.ell_block)
    built = [SparseOperand.from_coo(r, c, v, loc_shape,
                                    ell_block=op.ell_block,
                                    row_width=rw, col_width=cw)
             for r, c, v in pieces]

    def stack(get):
        return jnp.stack([get(o) for o in built])

    return SparseOperand(
        stack(lambda o: o.row_cols), stack(lambda o: o.row_vals),
        stack(lambda o: o.row_blocks), stack(lambda o: o.col_rows),
        stack(lambda o: o.col_vals), stack(lambda o: o.col_blocks),
        None, op.ell_block)


def _specs(fam: ProblemFamily, axes: AxisNames):
    """PartitionSpecs implied by the family's partition axis: the sharded
    vector spec, A's spec, b's spec, and the solution's output spec."""
    part = axes if isinstance(axes, str) else tuple(axes)
    vec = P(part)
    if fam.partition == "row":
        # Fig. 1: data points sharded; b rides with A; solutions and all
        # R^(s mu)-sized reductions replicated.
        return vec, P(part, None), vec, P()
    # Sec. V: features sharded; everything in R^m replicated; the
    # solution lives on the feature axis.
    return vec, P(None, part), P(), vec


def solve_sharded(problem, cfg: SolverConfig, mesh: Mesh,
                  axes: Optional[AxisNames] = None,
                  family: Optional[object] = None,
                  x0=None, state: Optional[SolveState] = None
                  ) -> SolverResult:
    """Distributed solve for ANY registered family.

    Pads the partitioned axis of A to a multiple of the shard count
    (zero padding is exact for every family — padded rows/columns
    contribute 0 to every Gram/cross product and the corresponding
    state coordinates stay 0; a ``SparseOperand`` A is split into
    per-shard operands whose padded rows/columns store no nonzeros at
    all — see ``_stack_sparse_shards``), runs the family's own solver inside
    ``shard_map`` with ``axis_name=axes``, and unpads the outputs. The
    whole solve jits to ONE compiled program whose HLO carries exactly
    ceil(H/s) all-reduces — see ``benchmarks/collective_count.py``.

    ``axes`` may be a single mesh axis or a tuple (e.g. ('pod', 'data'))
    — reductions then span pods hierarchically.

    ``state``: a LOGICAL (unpadded) :class:`SolveState` from a previous
    solve's ``aux["state"]`` — its "partition" leaves (per the family's
    ``state_layout``) are zero-padded and re-sharded onto THIS mesh, so
    a state checkpointed on one mesh resumes on any other (the elastic
    recovery path). The returned ``aux["state"]`` is logical again:
    partition leaves are unpadded before they leave this function.
    """
    fam = resolve_family(problem, family)
    if axes is None:
        axes = fam.default_axes
    n_shards = _axis_size(mesh, axes)
    sparse = isinstance(problem.A, SparseOperand)
    part_axis = 0 if fam.partition == "row" else 1
    orig = problem.A.shape[part_axis]
    padded = -(-orig // n_shards) * n_shards
    if sparse:
        A_arg = _stack_sparse_shards(problem.A, n_shards, part_axis,
                                     padded, cfg.dtype)
    else:
        A_arg = _pad_to(problem.A, padded, part_axis, cfg.dtype)
    b = problem.b
    if fam.partition == "row":
        b = _pad_to(b, padded, 0, cfg.dtype)

    vec, a_spec, b_spec, x_out = _specs(fam, axes)
    aux_specs = tuple(vec if layout == "partition" else P()
                      for _, layout in fam.aux_out)
    layout = fam.state_layout(cfg) if fam.state_layout is not None else ()
    state_specs = tuple(vec if lay == "partition" else P()
                        for _, lay in layout)
    # a sparse operand's leaves all carry a leading stacked-shard axis,
    # so ONE leading-axis spec partitions the whole pytree.
    in_specs = [vec if sparse else a_spec, b_spec]
    args = [A_arg, jnp.asarray(b, cfg.dtype)]
    if x0 is not None:
        if fam.x0_layout == "partition":
            x0 = _pad_to(x0, padded, 0, cfg.dtype)
            in_specs.append(vec)
        else:
            in_specs.append(P())
        args.append(jnp.asarray(x0, cfg.dtype))
    n_x0 = len(args) - 2
    if state is not None:
        if not layout:
            raise ValueError(
                f"family {fam.name!r} declares no state_layout — it "
                f"cannot resume from a SolveState")
        for name, lay in layout:
            leaf = state.carry[name]
            if lay == "partition":
                leaf = _pad_to(leaf, padded, 0, cfg.dtype)
                in_specs.append(vec)
            else:
                in_specs.append(P())
            args.append(jnp.asarray(leaf, cfg.dtype))

    def local_solve(A_loc, b_loc, *rest):
        if sparse:
            A_loc = A_loc.squeeze_shard()
        local = dataclasses.replace(problem, A=A_loc, b=b_loc)
        kw = {}
        if state is not None:
            kw["state"] = SolveState(
                int(state.iteration),
                {name: leaf for (name, _), leaf
                 in zip(layout, rest[n_x0:])})
        res = fam.solve(local, cfg, axis_name=axes,
                        x0=rest[0] if n_x0 else None, **kw)
        outs = (res.x, res.objective) \
            + tuple(res.aux[k] for k, _ in fam.aux_out)
        if layout:
            outs += tuple(res.aux["state"].carry[name]
                          for name, _ in layout)
        return outs

    out_layout = ((("x", "partition" if fam.partition == "col"
                    else "replicated"), ("objective", "replicated"))
                  + tuple(fam.aux_out) + tuple(layout))
    sharded = _shard_map(local_solve, mesh, tuple(in_specs),
                         (x_out, P()) + aux_specs + state_specs)

    def solve_and_unpad(*a):
        # the padded tail of every partitioned output is cut inside the
        # one compiled program.
        return tuple(v[:orig] if lay == "partition" else v
                     for (_, lay), v in zip(out_layout, sharded(*a)))

    out = jax.jit(solve_and_unpad)(*args)
    x, objective = out[0], out[1]
    n_aux = len(fam.aux_out)
    aux = {k: v for (k, _), v in zip(fam.aux_out, out[2:2 + n_aux])}
    if layout:
        start = 0 if state is None else int(state.iteration)
        aux["state"] = SolveState(
            start + cfg.iterations,
            {name: v for (name, _), v in zip(layout, out[2 + n_aux:])})
    return SolverResult(x=x, objective=objective, aux=aux)


def lower_solve(family: object, cfg: SolverConfig, mesh: Mesh,
                m: int, n: int, axes: Optional[AxisNames] = None,
                dtype=jnp.float32,
                problem_kwargs: Optional[Dict[str, Any]] = None):
    """Lower (without executing) a full distributed solve of any
    registered family for shape (m, n) — the dry-run/collective-count
    entry. Returns the ``jax.stages.Lowered`` object.

    ``problem_kwargs`` fills the family's non-(A, b) problem fields;
    defaults to the family's ``bench_problem_kwargs``.
    """
    fam = resolve_family(family=family)
    if axes is None:
        axes = fam.default_axes
    kwargs = dict(fam.bench_problem_kwargs if problem_kwargs is None
                  else problem_kwargs)
    _, a_spec, b_spec, x_out = _specs(fam, axes)

    def local_solve(A_loc, b_loc):
        prob = fam.problem_cls(A=A_loc, b=b_loc, **kwargs)
        res = fam.solve(prob, cfg, axis_name=axes)
        return res.x, res.objective

    fn = _shard_map(local_solve, mesh, (a_spec, b_spec), (x_out, P()))
    return jax.jit(fn).lower(jax.ShapeDtypeStruct((m, n), dtype),
                             jax.ShapeDtypeStruct((m,), dtype))


@dataclasses.dataclass(frozen=True)
class TracedSolve:
    """A sharded solve as a jaxpr plus its DECLARED output contract —
    the static-analysis view of :func:`solve_sharded` (repro.analysis).

    jaxpr:       the ``ClosedJaxpr`` of the full shard_map'd solve.
    out_layout:  ``(name, layout)`` per output, in output order, with
                 layout in {"replicated", "partition"} — exactly what
                 the family registered (solution/objective/aux_out/
                 state_layout), i.e. the contract the replicated-taint
                 pass verifies the dataflow against.
    axes:        the mesh axis name(s) the solve reduces over.
    """

    jaxpr: Any
    out_layout: Tuple[Tuple[str, str], ...]
    axes: AxisNames


def trace_sharded(family: object, cfg: SolverConfig, mesh: Mesh,
                  m: Optional[int] = None, n: Optional[int] = None,
                  axes: Optional[AxisNames] = None,
                  dtype=jnp.float32,
                  problem_kwargs: Optional[Dict[str, Any]] = None,
                  operand: Optional[SparseOperand] = None
                  ) -> TracedSolve:
    """Trace (without lowering or executing) a full sharded solve for
    shape (m, n), with the family's ``aux_out`` vectors AND
    ``state_layout`` carry leaves as outputs — the same output structure
    :func:`solve_sharded` runs, so a static pass over this jaxpr checks
    the program the driver actually executes. ``repro.analysis`` builds
    its collective-budget, replicated-taint and cost-certification
    passes on this entry; a 1-device mesh suffices (divergence is
    symbolic in the jaxpr).

    ``operand``: an optional concrete :class:`SparseOperand` A. The
    trace then follows the SPARSE execution path — the operand is split
    and stacked exactly as :func:`solve_sharded` does it (the blocked-
    ELL leaves cross shard_map with one leading-axis spec), so the
    jaxpr's flop counts reflect the O(nnz) gather/scatter products,
    which is what the cost certifier's nnz-scaling check measures.
    (m, n) then come from ``operand.shape`` and must not be passed."""
    fam = resolve_family(family=family)
    if axes is None:
        axes = fam.default_axes
    if operand is not None:
        if m is not None or n is not None:
            raise ValueError(
                "trace_sharded: pass either operand= (sparse; shape "
                "comes from the operand) or m=/n= (dense), not both")
        m, n = operand.shape
    elif m is None or n is None:
        raise ValueError("trace_sharded: a dense trace needs m= and n=")
    kwargs = dict(fam.bench_problem_kwargs if problem_kwargs is None
                  else problem_kwargs)
    vec, a_spec, b_spec, x_out = _specs(fam, axes)
    layout = fam.state_layout(cfg) if fam.state_layout is not None else ()
    sparse = operand is not None
    if sparse:
        n_shards = _axis_size(mesh, axes)
        part_axis = 0 if fam.partition == "row" else 1
        padded = -(-operand.shape[part_axis] // n_shards) * n_shards
        A_arg = _stack_sparse_shards(operand, n_shards, part_axis,
                                     padded, dtype)
        b_len = padded if fam.partition == "row" else m
    else:
        A_arg = jax.ShapeDtypeStruct((m, n), dtype)
        b_len = m

    def local_solve(A_loc, b_loc):
        if sparse:
            A_loc = A_loc.squeeze_shard()
        prob = fam.problem_cls(A=A_loc, b=b_loc, **kwargs)
        res = fam.solve(prob, cfg, axis_name=axes)
        outs = (res.x, res.objective) \
            + tuple(res.aux[k] for k, _ in fam.aux_out)
        if layout:
            outs += tuple(res.aux["state"].carry[name]
                          for name, _ in layout)
        return outs

    aux_specs = tuple(vec if lay == "partition" else P()
                      for _, lay in fam.aux_out)
    state_specs = tuple(vec if lay == "partition" else P()
                        for _, lay in layout)
    fn = _shard_map(local_solve, mesh, (vec if sparse else a_spec, b_spec),
                    (x_out, P()) + aux_specs + state_specs)
    jaxpr = jax.make_jaxpr(fn)(A_arg,
                               jax.ShapeDtypeStruct((b_len,), dtype))
    out_layout = (
        ("x", "partition" if fam.partition == "col" else "replicated"),
        ("objective", "replicated"),
    ) + tuple(fam.aux_out) + tuple(("state." + name, lay)
                                   for name, lay in layout)
    return TracedSolve(jaxpr=jaxpr, out_layout=out_layout, axes=axes)


# ---------------------------------------------------------------------------
# The facade.
# ---------------------------------------------------------------------------

def _local_backend(fam: ProblemFamily, problem, cfg: SolverConfig, *,
                   axis_name=None, mesh=None, axes=None, x0=None,
                   state=None) -> SolverResult:
    if mesh is not None or axes is not None:
        raise ValueError(
            "mesh=/axes= are only meaningful with backend='sharded' "
            "(the local backend runs single-host, or inside a "
            "caller-managed shard_map via axis_name=)")
    # keyword only when set: families registered WITHOUT resume support
    # (no `state` parameter) keep working for ordinary solves.
    kw = {} if state is None else {"state": state}
    return fam.solve(problem, cfg, axis_name=axis_name, x0=x0, **kw)


def _sharded_backend(fam: ProblemFamily, problem, cfg: SolverConfig, *,
                     axis_name=None, mesh=None, axes=None, x0=None,
                     state=None) -> SolverResult:
    if mesh is None:
        raise ValueError("backend='sharded' requires mesh=...")
    if axis_name is not None:
        raise ValueError(
            "axis_name= is managed by the sharded backend; pass axes= "
            "to choose the mesh axes")
    return solve_sharded(problem, cfg, mesh, axes=axes, family=fam, x0=x0,
                         state=state)


BACKENDS: Dict[str, Callable] = {
    "local": _local_backend,
    "sharded": _sharded_backend,
}


def solve(problem, cfg: Optional[SolverConfig] = None,
          backend: str = "local", *,
          family: Optional[object] = None,
          axis_name=None, mesh: Optional[Mesh] = None,
          axes: Optional[AxisNames] = None, x0=None,
          state: Optional[SolveState] = None,
          tune: Optional[str] = None,
          callbacks: Optional[Sequence[Callable]] = None) -> SolverResult:
    """Solve any registered problem family on any registered backend.

    problem:  a registered problem dataclass (LassoProblem, SVMProblem,
              LogRegProblem, ...); its type picks the family.
    cfg:      SolverConfig (defaults to ``SolverConfig()``); cfg.s and
              cfg.accelerated pick the variant inside the family.
    backend:  "local" (single host / caller-managed shard_map) or
              "sharded" (the generic distributed driver; needs mesh=).
    family:   optional explicit family name, overriding type inference.
    x0:       optional warm start in the family's iterate space (Lasso
              x, SVM/K-SVM dual alpha, logreg w) — threaded through to
              every solver; the objective trace resumes where a previous
              solve's left off.
    state:    optional :class:`SolveState` from a previous solve's
              ``result.aux["state"]`` — resumes the FULL recurrence
              state (all carries + RNG/θ-schedule offset), so the
              continued solve is bit-identical to an uninterrupted one
              on the same mesh. Mutually exclusive with x0. On the
              sharded backend the state is re-padded/re-sharded, so a
              state saved on one mesh restores onto any other (elastic
              recovery; see ``repro.runtime.elastic``).
    tune:     ``"auto"`` replaces cfg's tunables (s, block_size,
              use_pallas, symmetric_gram) with ``repro.tune.autotune``'s
              calibrated-model selection before solving — iterations,
              dtype, seed etc. are preserved, and the calibrated machine
              is cached per host/regime under ``results/tuned/`` so only
              the first solve of a regime pays the pilot measurements.
              The config actually used lands in
              ``result.aux["tuned_config"]``. None/"off" solves cfg
              as given.
    callbacks: optional callables, each invoked as ``cb(result)`` after
              the solve (the solvers are single jitted programs, so
              per-iteration hooks would force a host round-trip; consume
              ``result.objective`` instead).
    """
    fam = resolve_family(problem, family)
    if cfg is None:
        cfg = SolverConfig()
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}; registered: {sorted(BACKENDS)}")
    tuned = False
    if tune not in (None, False, "off"):
        if tune not in ("auto", True):
            raise ValueError(
                f"unknown tune mode {tune!r}; expected 'auto' (or "
                f"None/'off' to solve cfg as given)")
        if backend != "local":
            # autotune calibrates with LOCAL single-host pilot solves
            # and selects at P=1 — silently applying that to a sharded
            # solve would tune for the wrong machine and topology.
            raise ValueError(
                "tune='auto' only supports backend='local' (pilot "
                "solves run unsharded at P=1); for a sharded solve, "
                "call repro.tune.select_config explicitly with a "
                "calibrated/hand-built Machine and P = the shard "
                "count")
        from repro import tune as tune_mod
        cfg = tune_mod.autotune(problem, cfg, family=fam)
        tuned = True
    result = BACKENDS[backend](fam, problem, cfg, axis_name=axis_name,
                               mesh=mesh, axes=axes, x0=x0, state=state)
    if tuned:
        result.aux["tuned_config"] = cfg
    for cb in callbacks or ():
        cb(result)
    return result
