"""Synchronization-Avoiding coordinate-descent solvers for proximal
least-squares — paper Algorithm 2 (SA-accBCD) and the non-accelerated
SA-BCD / SA-CD variants, expressed as :class:`repro.core.engine`
FamilyPrograms.

The transformation (paper Sec. III): unroll the recurrences s iterations,
sample all s*mu coordinates up front, compute ONE (s*mu) x (s*mu) Gram
matrix plus the projections Y^T [ytil, ztil] with a SINGLE Allreduce, then
run the s inner updates redundantly on replicated O(s*mu)-sized data, and
apply the deferred m-dimensional vector updates (paper Eqs. 6-9) as local
GEMVs. Latency drops by s; flops/bandwidth grow by s (paper Table I). The
iterate sequence is identical to Algorithm 1 in exact arithmetic.

Only the algorithm lives here — sampled-block assembly, the fused
payload, the inner recurrence, the deferred application and the
objective stitching. All s-step scheduling (grouping, remainder tails,
fold_in ids, SolveState resume, the θ schedule windows) is owned by
:func:`repro.core.engine.run_program`.

The hot spots map to the two Pallas kernels:
  * ``repro.kernels.gram``     — the fused  Y^T [Y | ytil | ztil]  GEMM
  * ``repro.kernels.sa_inner`` — the s-step inner loop, entirely in VMEM
Both have pure-jnp paths (used on CPU and inside the multi-device dry-run).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import linalg
# Compatibility aliases: these helpers moved into the engine.
from repro.core.engine import (Ctx, FamilyProgram, deferred_steps,
                               gram_and_proj as _gram_and_proj,
                               gram_local,
                               reduce_gram_proj as _reduce_gram_proj,
                               run_program,
                               sample_all as _sample_all)
from repro.core.lasso import _objective, _prep
from repro.core.sparse_exec import col_block_ops, col_take
from repro.core.types import (LassoProblem, SolveState, SolverConfig,
                              SolverResult, SparseOperand, operand_matvec,
                              require_unit_block)


def _lasso_ctx(problem, cfg, axis_name):
    A, b, n, mu, q, sampler, prox = _prep(problem, cfg)
    sparse = isinstance(A, SparseOperand)
    return Ctx(A=A, b=b, n=n, mu=mu, q=q, sampler=sampler, prox=prox,
               sparse=sparse,
               block_gram=col_block_ops(A, cfg)[0] if sparse else None,
               take=None if sparse else col_take(A, cfg, grouped=True),
               m_loc=A.shape[0], problem=problem, cfg=cfg,
               axis_name=axis_name)


def _lasso_sample(ctx, key):
    return ctx.sampler(key)


def _lasso_assemble(ctx, vecs, idxs, s_grp):
    """LOCAL fused Gram/projection payload for the group's sampled
    columns: (handle, Y^T [Y | vecs]). ``handle`` (the dense sampled
    columns, or the sparse gather triple) feeds the deferred GEMVs."""
    flat = idxs.reshape(s_grp * ctx.mu)
    if ctx.sparse:
        return ctx.block_gram(flat, vecs)
    Yt = ctx.take(flat)                               # (s*mu, m_loc) local
    return Yt, gram_local(Yt.T, vecs, ctx.cfg.use_pallas)


def _lasso_reduce(ctx, local, idxs, s_grp, vec_cols):
    return _reduce_gram_proj(local, s_grp * ctx.mu, vec_cols,
                             ctx.axis_name, ctx.cfg.symmetric_gram)


def _stepped_iterates(x, idxs, buf, s_grp, n, dtype):
    """Reconstruct the per-inner-iteration coordinate iterates from the
    final x and the step buffer, for objective stitching: (s_grp, n)."""
    dfull = jnp.zeros((s_grp, n), dtype).at[
        jnp.arange(s_grp)[:, None], idxs].add(buf)
    return (x - jnp.sum(dfull, 0))[None, :] + jnp.cumsum(dfull, axis=0), \
        dfull


# ---------------------------------------------------------------------------
# SA-BCD (non-accelerated): r_j = A_j^T r_sk + sum_{t<j} G[j,t] dx_t
# ---------------------------------------------------------------------------

def _bcd_setup(problem, cfg, axis_name, x0, carry0):
    ctx = _lasso_ctx(problem, cfg, axis_name)
    if carry0 is not None:
        x = jnp.asarray(carry0["x"], cfg.dtype)
        r = jnp.asarray(carry0["residual"], cfg.dtype)
    elif x0 is None:
        x = jnp.zeros((ctx.n,), cfg.dtype)
        r = -ctx.b
    else:
        x = jnp.asarray(x0, cfg.dtype)
        r = operand_matvec(ctx.A, x) - ctx.b
    return ctx, (x, r)


def _bcd_assemble(ctx, carry, idxs, s_grp):
    return _lasso_assemble(ctx, carry[1][:, None], idxs, s_grp)


def _bcd_reduce(ctx, local, idxs, s_grp):
    return _lasso_reduce(ctx, local, idxs, s_grp, 1)


def _bcd_inner(ctx, carry, handle, payload, idxs, win, s):
    x, r = carry
    cfg, mu = ctx.cfg, ctx.mu
    G, P = payload
    G4 = G.reshape(s, mu, s, mu)
    r_proj = P[:, 0].reshape(s, mu)

    def inner(inner_carry, j):
        x, dx_buf = inner_carry
        idx_j = idxs[j]
        Gj = G4[j]                                    # (mu, s, mu)
        cross = jnp.einsum("ptq,tq->tp", Gj, dx_buf)  # (s, mu)
        mask = (jnp.arange(s) < j).astype(cfg.dtype)
        rj = r_proj[j] + jnp.einsum("t,tp->p", mask, cross)
        v = linalg.power_iteration_max_eig(Gj[:, j, :], cfg.power_iters)
        eta = 1.0 / linalg.floor_eig(v)  # floored: zero block -> no-op
        g = x[idx_j] - eta * rj
        dx = ctx.prox(g, eta) - x[idx_j]
        x = x.at[idx_j].add(dx)
        dx_buf = dx_buf.at[j].set(dx)
        return (x, dx_buf), None

    (x, dx_buf), _ = jax.lax.scan(
        inner, (x, jnp.zeros((s, mu), cfg.dtype)), jnp.arange(s))
    return (x, r), dx_buf


def _bcd_defer(ctx, carry, handle, dx_buf, payload, idxs, win, s):
    x, r = carry
    cfg = ctx.cfg
    # Deferred residual update (Eq. 7): local GEMV / sparse scatter-adds
    steps = deferred_steps(ctx, handle, dx_buf, s)
    r_new = r + jnp.sum(steps, axis=0)

    if cfg.track_objective:
        r_steps = r[None, :] + jnp.cumsum(steps, axis=0)
        x_steps, _ = _stepped_iterates(x, idxs, dx_buf, s, ctx.n, cfg.dtype)
        objs = jax.vmap(
            lambda rr, xx: _objective(rr, xx, ctx.problem, ctx.axis_name))(
            r_steps, x_steps)
    else:
        objs = jnp.zeros((s,), cfg.dtype)
    return (x, r_new), objs


def _bcd_finalize(ctx, carry, sched):
    x, r = carry
    return x, {"residual": r}


_BCD_PROGRAM = FamilyProgram(
    name="sa_bcd_lasso", setup=_bcd_setup, sample=_lasso_sample,
    assemble=_bcd_assemble, reduce=_bcd_reduce, inner=_bcd_inner,
    defer=_bcd_defer, finalize=_bcd_finalize,
    carry_names=("x", "residual"), spmm_kind="col_gram", spmm_extra=1)


def sa_bcd_lasso(problem: LassoProblem, cfg: SolverConfig,
                 axis_name: Optional[object] = None,
                 x0=None, state: Optional[SolveState] = None) -> SolverResult:
    return run_program(_BCD_PROGRAM, problem, cfg, axis_name, x0, state)


# ---------------------------------------------------------------------------
# SA-accBCD — paper Algorithm 2.
# ---------------------------------------------------------------------------

def _acc_setup(problem, cfg, axis_name, x0, carry0):
    ctx = _lasso_ctx(problem, cfg, axis_name)
    if carry0 is not None:
        z = jnp.asarray(carry0["z"], cfg.dtype)
        y = jnp.asarray(carry0["y"], cfg.dtype)
        ztil = jnp.asarray(carry0["ztil"], cfg.dtype)
        ytil = jnp.asarray(carry0["ytil"], cfg.dtype)
    else:
        if x0 is None:
            z = jnp.zeros((ctx.n,), cfg.dtype)
            ztil = -ctx.b
        else:
            z = jnp.asarray(x0, cfg.dtype)
            ztil = operand_matvec(ctx.A, z) - ctx.b
        y = jnp.zeros((ctx.n,), cfg.dtype)
        ytil = jnp.zeros_like(ctx.b)
    return ctx, (z, y, ztil, ytil)


def _acc_schedule(ctx, cfg, total):
    theta0 = jnp.asarray(ctx.mu / ctx.n, cfg.dtype)
    return linalg.theta_schedule(theta0, total, ctx.q)    # (total+1,)


def _acc_assemble(ctx, carry, idxs, s_grp):
    z, y, ztil, ytil = carry
    return _lasso_assemble(ctx, jnp.stack([ytil, ztil], axis=1), idxs,
                           s_grp)


def _acc_reduce(ctx, local, idxs, s_grp):
    return _lasso_reduce(ctx, local, idxs, s_grp, 2)


def _acc_coefU(ctx, th_prev):
    """Alg. 2 lines 21-22 coefficient (1 - q θ_{j-1}) / θ_{j-1}^2."""
    return (1.0 - ctx.q * th_prev) / (th_prev * th_prev)


def _acc_inner(ctx, carry, handle, payload, idxs, win, s):
    z, y, ztil, ytil = carry
    cfg, mu, q = ctx.cfg, ctx.mu, ctx.q
    G, P = payload
    G4 = G.reshape(s, mu, s, mu)
    y_proj = P[:, 0].reshape(s, mu)                   # A_j^T ytil_sk
    z_proj = P[:, 1].reshape(s, mu)                   # A_j^T ztil_sk
    th_prev, _ = win
    coefU = _acc_coefU(ctx, th_prev)

    def inner(inner_carry, j):
        z, y, dz_buf = inner_carry
        idx_j = idxs[j]
        thp = th_prev[j]
        Gj = G4[j]                                    # (mu, s, mu)
        cross = jnp.einsum("ptq,tq->tp", Gj, dz_buf)  # (s, mu)
        # Eq. (3): coefficient (theta_{j-1}^2 * coefU_t - 1) on G[j,t] dz_t
        coef_t = thp * thp * coefU - 1.0              # (s,)
        mask = (jnp.arange(s) < j).astype(cfg.dtype)
        rj = thp * thp * y_proj[j] + z_proj[j] \
            - jnp.einsum("t,t,tp->p", mask, coef_t, cross)
        v = linalg.power_iteration_max_eig(Gj[:, j, :],
                                           cfg.power_iters)  # line 14
        eta = 1.0 / linalg.floor_eig(q * thp * v)     # line 15 (floored)
        g = z[idx_j] - eta * rj                       # Eq. (4)
        dz = ctx.prox(g, eta) - z[idx_j]              # Eq. (5)
        z = z.at[idx_j].add(dz)                       # line 19
        y = y.at[idx_j].add(-coefU[j] * dz)           # line 21
        dz_buf = dz_buf.at[j].set(dz)
        return (z, y, dz_buf), None

    (z, y, dz_buf), _ = jax.lax.scan(
        inner, (z, y, jnp.zeros((s, mu), cfg.dtype)), jnp.arange(s))
    return (z, y, ztil, ytil), dz_buf


def _acc_defer(ctx, carry, handle, dz_buf, payload, idxs, win, s):
    z, y, ztil, ytil = carry
    cfg = ctx.cfg
    th_prev, th_cur = win
    coefU = _acc_coefU(ctx, th_prev)
    # Deferred m-dimensional updates (paper Eqs. 7 & 9): local GEMVs
    # (sparse: O(nnz of the sampled columns) scatter-adds).
    steps = deferred_steps(ctx, handle, dz_buf, s)
    ztil_new = ztil + jnp.sum(steps, axis=0)
    ytil_new = ytil - jnp.einsum("t,tm->m", coefU, steps)

    if cfg.track_objective:
        ztil_steps = ztil[None, :] + jnp.cumsum(steps, axis=0)
        ytil_steps = ytil[None, :] - jnp.cumsum(
            coefU[:, None] * steps, axis=0)
        dz_full = jnp.zeros((s, ctx.n), cfg.dtype).at[
            jnp.arange(s)[:, None], idxs].add(dz_buf)
        z_steps = (z - jnp.sum(dz_full, 0))[None, :] \
            + jnp.cumsum(dz_full, axis=0)
        y_steps = (y + jnp.sum(coefU[:, None] * dz_full, 0))[None, :] \
            - jnp.cumsum(coefU[:, None] * dz_full, axis=0)
        th2 = (th_cur * th_cur)[:, None]
        objs = jax.vmap(
            lambda rr, xx: _objective(rr, xx, ctx.problem, ctx.axis_name))(
            th2 * ytil_steps + ztil_steps, th2 * y_steps + z_steps)
    else:
        objs = jnp.zeros((s,), cfg.dtype)
    return (z, y, ztil_new, ytil_new), objs


def _acc_finalize(ctx, carry, sched):
    z, y, ztil, ytil = carry
    thH = sched[-1]
    return thH * thH * y + z, {"residual": thH * thH * ytil + ztil}


_ACC_PROGRAM = FamilyProgram(
    name="sa_acc_bcd_lasso", setup=_acc_setup, sample=_lasso_sample,
    assemble=_acc_assemble, reduce=_acc_reduce, inner=_acc_inner,
    defer=_acc_defer, finalize=_acc_finalize,
    carry_names=("z", "y", "ztil", "ytil"), schedule=_acc_schedule,
    spmm_kind="col_gram", spmm_extra=2)


def sa_acc_bcd_lasso(problem: LassoProblem, cfg: SolverConfig,
                     axis_name: Optional[object] = None,
                     x0=None, state: Optional[SolveState] = None
                     ) -> SolverResult:
    return run_program(_ACC_PROGRAM, problem, cfg, axis_name, x0, state)


def sa_cd_lasso(problem, cfg, axis_name=None, x0=None, state=None):
    require_unit_block(cfg, "sa_cd_lasso")
    return sa_bcd_lasso(problem, cfg, axis_name, x0, state)


def sa_acc_cd_lasso(problem, cfg, axis_name=None, x0=None, state=None):
    require_unit_block(cfg, "sa_acc_cd_lasso")
    return sa_acc_bcd_lasso(problem, cfg, axis_name, x0, state)
