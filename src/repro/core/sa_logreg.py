"""Synchronization-Avoiding logistic regression — the s-step unroll of
``bcd_logreg`` (after Devarakonda & Demmel, arXiv:2011.08281), expressed
as a :class:`repro.core.engine` FamilyProgram.

Every update direction lives in the span of the sampled rows: unrolling
s damped steps gives  w_{sk+s} = (prod_j d_j) w_sk + Y^T u,  with
d_j = 1 - eta_j lam and u the per-step coefficients, each decayed by
the d-factors of LATER steps. The solver samples all s blocks up front,
Allreduces the fused (m, s*mu) cross block A Y^T ONCE, and runs the s
dependent inner updates redundantly on replicated data:

  * the margins f (replicated R^m) update per inner step as
    f <- d f + (A Y^T)[:, B_j] u_j — a local slice of the reduced cross
    block, so later gathers f[B_t] are current (same-index collisions
    need no special casing: there is ONE copy of each margin);
  * the coefficient buffer decays, U <- d U then U[j] += u_j, recording
    exactly the d-products the closed form requires;
  * sq = ||w||^2 updates from gathered margins and the diagonal slice
    of the cross block (DESIGN.md).

Deferred per outer group: ONE local GEMV  w <- rho w + Y^T vec(U)  with
rho = prod_j d_j. Identical iterates to ``bcd_logreg`` in exact
arithmetic; ONE Allreduce per s inner iterations.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import linalg, phases
from repro.core.engine import Ctx, FamilyProgram, run_program
from repro.core.logreg import _init_state, _step_size, _tracked_objective
from repro.core.sparse_exec import cross_block, row_block_ops
from repro.core.types import (LogRegProblem, SolveState, SolverConfig,
                              SolverResult)


def _logreg_setup(problem, cfg, axis_name, x0, carry0):
    A, b, w, f, sq = _init_state(problem, cfg, axis_name, x0, carry0)
    take, _, densify, apply_t = row_block_ops(A, cfg)
    ctx = Ctx(A=A, b=b, m=A.shape[0], mu=cfg.block_size,
              lam=jnp.asarray(problem.lam, cfg.dtype), take=take,
              densify=densify, apply_t=apply_t, cfg=cfg,
              axis_name=axis_name)
    return ctx, (w, f, sq)


def _logreg_assemble(ctx, carry, idxs, s_grp):
    flat = idxs.reshape(s_grp * ctx.mu)
    Y = ctx.take(flat)                                # (s_grp*mu, n_loc)
    with phases.scope("gram"):
        return Y, cross_block(ctx.A, ctx.densify(Y), ctx.cfg.use_pallas)


def _logreg_inner(ctx, carry, Y, cross, idxs, win, s_grp):
    w, f, sq = carry
    cfg, mu, lam, b = ctx.cfg, ctx.mu, ctx.lam, ctx.b
    cross_r = cross.reshape(ctx.m, s_grp, mu)
    b_sel = b[idxs.reshape(s_grp * mu)].reshape(s_grp, mu)

    def inner(inner_carry, j):
        f, sq, rho, U = inner_carry
        idx_j = idxs[j]
        Kj = cross_r[:, j, :]                         # (m, mu) = A Y_j^T
        G = Kj[idx_j]                                 # (mu, mu) = Y_j Y_j^T
        fB = f[idx_j]                                 # current Y_j w
        c = -b_sel[j] * jax.nn.sigmoid(-b_sel[j] * fB)
        eta = _step_size(G, mu, lam, cfg.power_iters)
        d = 1.0 - eta * lam
        u = -(eta / mu) * c                           # (mu,)
        sq = d * d * sq + 2.0 * d * (fB @ u) + u @ (G @ u)
        f = d * f + Kj @ u                            # replicated, local
        rho = d * rho
        U = (d * U).at[j].add(u)                      # decay, then record
        obj = _tracked_objective(f, sq, b, lam) if cfg.track_objective \
            else jnp.asarray(0.0, cfg.dtype)
        return (f, sq, rho, U), obj

    rho0 = jnp.asarray(1.0, cfg.dtype)
    U0 = jnp.zeros((s_grp, mu), cfg.dtype)
    (f, sq, rho, U), objs = jax.lax.scan(
        inner, (f, sq, rho0, U0), jnp.arange(s_grp))
    return (w, f, sq), (rho, U, objs)


def _logreg_defer(ctx, carry, Y, inner_out, cross, idxs, win, s_grp):
    w, f, sq = carry
    rho, U, objs = inner_out
    w = rho * w + ctx.apply_t(Y, U.reshape(s_grp * ctx.mu))  # local GEMV
    return (w, f, sq), objs


_LOGREG_PROGRAM = FamilyProgram(
    name="sa_bcd_logreg", setup=_logreg_setup,
    sample=lambda ctx, key: linalg.sample_block(key, ctx.m, ctx.mu),
    assemble=_logreg_assemble,
    reduce=lambda ctx, local, *_: linalg.preduce(local, ctx.axis_name),
    inner=_logreg_inner, defer=_logreg_defer,
    finalize=lambda ctx, carry, sched: (
        carry[0], {"margins": carry[1], "w_norm_sq": carry[2]}),
    carry_names=("w", "margins", "sq"), spmm_kind="cross")


def sa_bcd_logreg(problem: LogRegProblem, cfg: SolverConfig,
                  axis_name: Optional[object] = None,
                  x0=None, state: Optional[SolveState] = None
                  ) -> SolverResult:
    """s-step unrolled BCD logreg: identical iterates to ``bcd_logreg``
    in exact arithmetic, ONE Allreduce per s inner iterations."""
    return run_program(_LOGREG_PROGRAM, problem, cfg, axis_name, x0, state)
