"""The generic SA s-step engine: ONE unrolled driver for every
synchronization-avoiding solver family.

The paper's core construction — sample all s blocks up front, fuse the
group's Gram/cross products into ONE Allreduce, run the s dependent
inner updates redundantly on replicated data, apply the deferred
O(nnz)/dense updates — was hand-cloned four times (Lasso, accelerated
Lasso, linear SVM, kernel SVM, logreg). Every copy duplicated the same
scaffolding around a family-specific recurrence:

  * ``run_grouped`` scheduling: floor(H/s) full groups in one lax.scan
    plus ONE remainder tail group of H mod s iterations;
  * global ``fold_in`` iteration ids (h = start + j), so SA and
    classical solvers draw bit-identical block sequences and a resumed
    solve continues the uninterrupted schedule;
  * :class:`~repro.core.types.SolveState` resume (restore the named
    recurrence leaves + RNG/schedule offset at an outer boundary);
  * θ/momentum schedules, precomputed over the FULL horizon and sliced
    per group with ``dynamic_slice`` — the remainder tail reads the
    same array at its global offset, so the schedule prefix is
    preserved bitwise no matter how H splits into groups;
  * objective stitching into one (H,) trace;
  * VMEM-guarded Pallas↔ref dispatch surfaced as "main+tail" impl
    labels when the tail group dispatches differently;
  * the single-Allreduce-per-outer-iteration contract.

A family now supplies only the algorithm as a :class:`FamilyProgram` —
sampled-block assembly, the fused-Allreduce payload, the inner update
rule, the deferred application and objective recurrence, plus its carry
schema — and :func:`run_program` owns everything else. The callback
seams follow the phase structure every SA method shares:

    setup -> [per outer group: sample -> assemble -> reduce -> inner
              -> defer] -> finalize

``assemble`` builds the LOCAL (pre-reduce) payload, ``reduce`` performs
the group's ONE collective, ``inner`` runs the s dependent updates on
the replicated reduced data, ``defer`` applies the m/n-dimensional
updates and stitches the objective trace. Each seam runs inside the
named scope of its phase (:mod:`repro.core.phases`), so every family's
compiled instructions carry the phase they belong to. See DESIGN.md
"The SA engine" for the contract and a family-authoring guide.
"""
from __future__ import annotations

import dataclasses
from types import SimpleNamespace
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import linalg, phases
from repro.core.sparse_exec import operand_aux
from repro.core.types import (SolveState, SolverResult, resume_carry)
from repro.kernels import spmm
from repro.kernels.gram import gram_t

__all__ = [
    "FamilyProgram", "run_program", "run_grouped", "grouped_impl_label",
    "gram_local", "reduce_gram_proj", "gram_and_proj", "sample_all",
    "deferred_steps",
]


# ---------------------------------------------------------------------------
# Grouped outer-loop scheduling (moved here from repro.core.sa_loop, which
# remains as a compatibility shim).
# ---------------------------------------------------------------------------

def run_grouped(group, carry, H: int, s: int, dtype, start: int = 0):
    """Run ``group(carry, start, s_grp) -> (carry, objs (s_grp,))`` over
    the full schedule; returns (carry, objs (H,)).

    floor(H/s) full s-step groups run inside one lax.scan, then ONE
    remainder tail group of H mod s iterations (the group body is
    shape-parameterized, so the tail is just a second trace at a smaller
    group size). ceil(H/s) Allreduces total, exactly H inner iterations.
    H < s degenerates to a single tail group with zero scan trips.

    ``start`` (a host int) offsets the global iteration ids — a solve
    resumed from a checkpointed :class:`~repro.core.types.SolveState`
    at iteration ``start`` passes it here so the groups keep the
    uninterrupted schedule's ``fold_in`` ids. Checkpoints are taken at
    outer-iteration boundaries, so ``start`` is a multiple of the
    original run's s whenever group alignment matters (DESIGN.md
    "Elastic recovery of SA recurrences")."""
    K, rem = divmod(H, s)
    objs = jnp.zeros((0,), dtype)
    if K:        # full s-step groups
        carry, objs = jax.lax.scan(
            lambda c, k: group(c, start + k * s, s), carry, jnp.arange(K))
        objs = objs.reshape(K * s)
    if rem:      # remainder tail group: the last H mod s iterations
        carry, objs_tail = group(carry, jnp.asarray(start + K * s), rem)
        objs = jnp.concatenate([objs, objs_tail])
    return carry, objs


def grouped_impl_label(impl_fn, H: int, s: int, mu: int,
                       use_pallas: bool, itemsize: int = 4) -> str:
    """The inner-loop implementation(s) the grouped schedule actually
    runs: the tail group dispatches at (H mod s, mu), which can differ
    from the full groups' (s, mu) — e.g. an over-VMEM s falls back to
    "ref" while a small tail still runs "pallas". Mixed runs are
    labeled "main+tail" so benchmarks never mislabel the timings.
    ``itemsize`` is the solve dtype's bytes/element (the VMEM guards are
    dtype-aware)."""
    K, rem = divmod(H, s)
    labels = ([impl_fn(s, mu, use_pallas, itemsize)] if K else []) \
        + ([impl_fn(rem, mu, use_pallas, itemsize)] if rem else [])
    if len(set(labels)) == 1:
        return labels[0]
    return "+".join(labels)


# ---------------------------------------------------------------------------
# Fused Gram/projection payload helpers (moved here from repro.core.sa_lasso;
# shared by the Lasso, SVM and SFISTA programs).
# ---------------------------------------------------------------------------

def reduce_gram_proj(local, smu, vec_cols, axis_name,
                     symmetric: bool = False):
    """ONE fused Allreduce of the LOCAL (smu, smu + k) Gram/projection
    block -> (G, P) replicated, with G (smu, smu) and P (smu, k).

    symmetric (``SolverConfig.symmetric_gram``, paper footnote 3): G is
    symmetric, so communicating only its lower triangle halves the message
    size — ~2x less W at O(s^2 mu^2) local pack/unpack reshuffling. The
    reduced values are identical, only their layout changes.
    """
    if symmetric:
        il, jl = jnp.tril_indices(smu)
        packed = jnp.concatenate(
            [local[:, :smu][il, jl], local[:, smu:].reshape(-1)])
        packed = linalg.preduce(packed, axis_name)
        ntri = il.shape[0]
        G = jnp.zeros((smu, smu), local.dtype).at[il, jl].set(packed[:ntri])
        G = G + jnp.tril(G, -1).T
        P = packed[ntri:].reshape(smu, vec_cols)
        return G, P
    out = linalg.preduce(local, axis_name)
    return out[:, :smu], out[:, smu:]


def gram_local(Y, vecs, use_pallas: bool = False):
    """LOCAL fused Gram/projection block  Y^T @ [Y | vecs]  (the
    pre-Allreduce half of paper Alg. 2 lines 11-12).

    Y: (m_loc, s*mu) sampled columns; vecs: (m_loc, k) residual-like
    vectors. ``use_pallas`` routes the GEMM through the
    ``repro.kernels.gram`` Pallas kernel (f32 MXU accumulation)."""
    with phases.scope("gram"):
        rhs = jnp.concatenate([Y, vecs], axis=1)
        if use_pallas:
            return gram_t(Y, rhs, use_pallas=True).astype(Y.dtype)
        return Y.T @ rhs


def gram_and_proj(Y, vecs, axis_name, symmetric: bool = False,
                  use_pallas: bool = False):
    """ONE fused Allreduce:  Y^T @ [Y | vecs]  — :func:`gram_local`
    followed by :func:`reduce_gram_proj`. Returns (G, P) with G
    (s*mu, s*mu) and P (s*mu, k), replicated."""
    local = gram_local(Y, vecs, use_pallas)
    return reduce_gram_proj(local, Y.shape[1], vecs.shape[1], axis_name,
                            symmetric)


def sample_all(key, sampler, start, s_grp):
    """Sample the s_grp blocks of the outer group starting after global
    iteration id ``start``, matching the non-SA fold_in indices
    (h = start + j, j = 1..s_grp) so SA and non-SA draw bit-identical
    coordinate sequences."""
    hs = start + 1 + jnp.arange(s_grp)
    return jax.vmap(lambda h: sampler(jax.random.fold_in(key, h)))(hs)


def deferred_steps(ctx, handle, buf, s_grp):
    """The deferred m-dimensional step vectors  S_t = A_{B_t} @ buf_t
    (s_grp, m_loc) for the column-sampling layout: a local GEMV per
    step (sparse: O(nnz of the sampled columns) scatter-adds). ``ctx``
    must carry ``sparse``, ``mu`` and ``m_loc`` (see the Lasso/SFISTA
    programs)."""
    if ctx.sparse:
        rows_g, vals_g, _ = handle
        return spmm.scatter_steps(rows_g.reshape(s_grp, ctx.mu, -1),
                                  vals_g.reshape(s_grp, ctx.mu, -1),
                                  buf, ctx.m_loc)
    return jnp.einsum("scm,sc->sm",
                      handle.reshape(s_grp, ctx.mu, ctx.m_loc), buf)


# ---------------------------------------------------------------------------
# The program spec + the ONE generic unrolled driver.
# ---------------------------------------------------------------------------

Ctx = SimpleNamespace   # programs stash whatever their callbacks close over


@dataclasses.dataclass(frozen=True)
class FamilyProgram:
    """A solver family's s-step program: the six callback seams plus the
    declarative fields the engine needs to own scheduling, resume,
    checkpoint schema and impl labels.

    Callback contract (``ctx`` is the namespace ``setup`` returns;
    ``carry`` is the family's recurrence-leaf tuple, ordered as
    ``carry_names``; ``s_grp`` is the group size — ``cfg.s`` for full
    groups, ``H mod s`` for the remainder tail; ``win`` is the sliced
    ``(sched[start : start+s_grp], sched[start+1 : start+s_grp+1])``
    schedule window, or None for schedule-free families):

    setup(problem, cfg, axis_name, x0, carry0) -> (ctx, carry)
        Prepare operands/closures and build the initial carry — from the
        restored ``carry0`` dict (a SolveState resume), from ``x0`` (a
        warm start), or from zero. The engine has already enforced
        state/x0 mutual exclusion via ``resume_carry``.
    sample(ctx, key) -> (mu,) int block
        Draw ONE iteration's coordinate block. The engine vmaps this
        over the group's ``fold_in`` iteration ids.
    assemble(ctx, carry, idxs, s_grp) -> (handle, local)
        Build the LOCAL (pre-reduce) fused payload for the group's
        sampled blocks ``idxs`` (s_grp, mu). ``handle`` is whatever the
        deferred application needs later (the dense sampled columns, a
        sparse gather handle, ...).
    reduce(ctx, local, idxs, s_grp) -> payload
        The group's ONE Allreduce (+ any post-reduce transform applied
        to the replicated copy, e.g. kernelization). Nothing else in the
        program may communicate — this seam IS the
        one-Allreduce-per-outer-iteration contract.
    inner(ctx, carry, handle, payload, idxs, win, s_grp)
        -> (carry, inner_out)
        The s_grp dependent inner updates, redundantly on replicated
        O(s*mu)-sized data (plus any replicated R^n/R^m leaves the
        family maintains densely).
    defer(ctx, carry, handle, inner_out, payload, idxs, win, s_grp)
        -> (carry, objs (s_grp,))
        Apply the deferred O(nnz)/dense updates and stitch the per-inner-
        iteration objective trace (zeros when ``cfg.track_objective`` is
        off).
    finalize(ctx, carry, sched) -> (x, aux_extra dict)
        Map the final carry to the solution vector and the family's
        extra aux entries (residuals, duals, ...).

    Declarative fields:

    carry_names: the SolveState leaf names, in carry order — the
        engine builds ``aux["state"]`` from these, so they must match
        the family's registered ``state_layout`` exactly.
    schedule(ctx, cfg, total) -> (total + 1,) array, optional
        Deterministic acceleration/momentum schedule over the FULL
        (resume-offset + H) horizon. The engine slices each group's
        window out of this one array with ``dynamic_slice`` at the
        group's global offset — which is what keeps the remainder
        tail's schedule prefix bitwise identical to the uninterrupted
        schedule.
    uses_svm_inner: surface the ``repro.kernels.svm_inner`` dispatch as
        ``aux["inner_impl"]`` with main+tail labels.
    spmm_kind / spmm_extra: the sparse-execution layout of the fused
        payload ("col_gram" / "row_gram" / "cross" + appended-vector
        count) — the engine derives ``aux["spmm_impl"]`` (and, for a
        dense "col_gram", ``aux["gather_layout"]``) from it (ONE place,
        so the label cannot drift from the dispatched shapes).
        Requires ``ctx.A`` to be the prepared operand.
    """

    name: str
    setup: Callable
    sample: Callable
    assemble: Callable
    reduce: Callable
    inner: Callable
    defer: Callable
    finalize: Callable
    carry_names: Tuple[str, ...]
    schedule: Optional[Callable] = None
    uses_svm_inner: bool = False
    spmm_kind: Optional[str] = None
    spmm_extra: int = 0


def run_program(prog: FamilyProgram, problem, cfg, axis_name=None,
                x0=None, state=None) -> SolverResult:
    """Run a :class:`FamilyProgram` over the full grouped schedule.

    Owns everything the hand-cloned SA solvers used to duplicate: the
    resume offset, the replicated RNG key and global ``fold_in`` ids,
    schedule precompute + per-group window slicing, ``run_grouped``
    (full groups + remainder tail), SolveState assembly from the carry
    schema, and the Pallas↔ref impl labels."""
    carry0 = resume_carry(state, x0, prog.name)
    h0 = 0 if state is None else int(state.iteration)
    with phases.scope("setup"):
        ctx, carry = prog.setup(problem, cfg, axis_name, x0, carry0)
        key = jax.random.key(cfg.seed)
        s, H = cfg.s, cfg.iterations
        sched = None if prog.schedule is None \
            else prog.schedule(ctx, cfg, h0 + H)   # (h0 + H + 1,)

    def group(carry, start, s_grp):
        with phases.scope("sample"):
            idxs = sample_all(key, lambda k: prog.sample(ctx, k),
                              start, s_grp)        # (s_grp, mu)
            win = None if sched is None else (
                jax.lax.dynamic_slice(sched, (start,), (s_grp,)),
                jax.lax.dynamic_slice(sched, (start + 1,), (s_grp,)))
        # --- Communication: assemble locally, reduce ONCE ---
        with phases.scope("assemble"):
            handle, local = prog.assemble(ctx, carry, idxs, s_grp)
        with phases.scope("reduce"):
            payload = prog.reduce(ctx, local, idxs, s_grp)
        # --- the s_grp dependent inner updates, then deferred apply ---
        with phases.scope("inner"):
            carry, inner_out = prog.inner(ctx, carry, handle, payload,
                                          idxs, win, s_grp)
        with phases.scope("defer"):
            return prog.defer(ctx, carry, handle, inner_out, payload,
                              idxs, win, s_grp)

    carry, objs = run_grouped(group, carry, H, s, cfg.dtype, start=h0)
    with phases.scope("finalize"):
        x, extra = prog.finalize(ctx, carry, sched)
    aux = dict(extra)
    aux["state"] = SolveState(h0 + H, dict(zip(prog.carry_names, carry)))
    itemsize = jnp.dtype(cfg.dtype).itemsize
    if prog.uses_svm_inner:
        from repro.kernels.svm_inner import inner_impl
        aux["inner_impl"] = grouped_impl_label(
            inner_impl, H, s, cfg.block_size, cfg.use_pallas, itemsize)
    if prog.spmm_kind is not None:
        aux.update(operand_aux(ctx.A, cfg, prog.spmm_kind, H=H,
                               extra=prog.spmm_extra))
    return SolverResult(x=x, objective=objs, aux=aux)
