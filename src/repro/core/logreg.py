"""Block coordinate-descent (mini-batch) logistic regression — the
communication structure of CA-logistic-regression (Devarakonda & Demmel,
arXiv:2011.08281), in the repo's solver conventions.

Problem:  min_w  (1/m) sum_i log(1 + exp(-b_i a_i^T w)) + lam/2 ||w||^2

Layout (identical to the kernel SVM): A is 1D-COLUMN-partitioned
(m, n_loc), w in R^n is partitioned alongside; b in R^m, the margin
vector f = A w in R^m, and all scalars are replicated.

Per iteration: sample a block B of mu data points, Allreduce the fused
(m, mu) cross block  A Y^T  (ONE message — the replicated margins make
the block gradient a pure gather), and take the damped stochastic
block-gradient step

    w <- (1 - eta lam) w - (eta/mu) Y^T c,
    c_i = -b_i sigma(-b_i f[i])        (sigma = logistic function),

with eta = 1 / (lambda_max(Y Y^T)/(4 mu) + lam) from the existing power
iteration (the logistic loss has curvature at most 1/4, so
lambda_max/(4 mu) bounds the block-mean Hessian; exact diagonal entry at
mu = 1). The margins and the replicated squared norm ||w||^2 update
locally from the SAME reduced cross block:

    f  <- (1 - eta lam) f - (eta/mu) (A Y^T) c
    sq <- d^2 sq + 2 d (f_B . u) + u^T (Y Y^T) u,   d = 1 - eta lam,
                                                    u = -(eta/mu) c

(f_B gathered BEFORE the update = Y w), so the exact full objective is
tracked after every inner iteration with zero extra communication —
``Y Y^T`` is the B-rows slice of the cross block already in hand.
Derivation in DESIGN.md ("SA logistic regression").

This module exists to prove the ``repro.api`` registry claim: the family
registers itself below and is reachable from ``repro.api.solve``, the
generic sharded backend, the launcher and the benchmarks with ZERO edits
to any of them.

``cfg.accelerated`` is ignored (no accelerated variant, as for SVM);
``cfg.symmetric_gram`` does not apply (the (m, mu) cross block is not
symmetric) and is ignored, as in the kernel SVM.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import cost_model, linalg, phases
from repro.core.sparse_exec import (cross_block, operand_aux,
                                    prep_operand, row_block_ops)
from repro.core.types import (LogRegProblem, SolveState, SolverConfig,
                              SolverResult, SparseOperand, operand_matvec,
                              register_family, resume_carry)


def logreg_objective(problem: LogRegProblem, w,
                     axis_name: Optional[object] = None):
    """Direct evaluation  (1/m) sum_i log(1+exp(-b_i a_i^T w))
    + lam/2 ||w||^2.  In distributed (column-partitioned) mode w is the
    local shard and the matvec A w needs one Allreduce."""
    A = problem.A if isinstance(problem.A, SparseOperand) \
        else jnp.asarray(problem.A)
    w = jnp.asarray(w, A.dtype)
    b = jnp.asarray(problem.b, A.dtype)
    margins = linalg.preduce(operand_matvec(A, w), axis_name)  # (m,)
    sq = linalg.preduce(jnp.sum(w * w), axis_name)
    loss = jnp.mean(jnp.logaddexp(0.0, -b * margins))
    return loss + 0.5 * problem.lam * sq


def _tracked_objective(f, sq, b, lam):
    """Objective from the maintained margins f = A w and sq = ||w||^2 —
    replicated data only, no communication."""
    return jnp.mean(jnp.logaddexp(0.0, -b * f)) + 0.5 * lam * sq


def _init_state(problem: LogRegProblem, cfg: SolverConfig, axis_name, x0,
                carry0=None):
    """w (local shard), margins f = A w and sq = ||w||^2 (replicated).
    x0 = None starts at zero, where f and sq are zero without any
    communication; a warm start rebuilds them with one setup Allreduce.
    A restored ``carry0`` (SolveState.carry) restores all three leaves
    verbatim — no matvec, no Allreduce."""
    A = prep_operand(problem.A, cfg.dtype)
    b = jnp.asarray(problem.b, cfg.dtype)
    if carry0 is not None:
        return (A, b, jnp.asarray(carry0["w"], cfg.dtype),
                jnp.asarray(carry0["margins"], cfg.dtype),
                jnp.asarray(carry0["sq"], cfg.dtype))
    if x0 is None:
        w = jnp.zeros((A.shape[1],), cfg.dtype)
        f = jnp.zeros((A.shape[0],), cfg.dtype)
        sq = jnp.asarray(0.0, cfg.dtype)
        return A, b, w, f, sq
    w = jnp.asarray(x0, cfg.dtype)
    packed = linalg.preduce(
        jnp.concatenate([operand_matvec(A, w), jnp.sum(w * w)[None]]),
        axis_name)
    return A, b, w, packed[:-1], packed[-1]


def _step_size(G, mu: int, lam, power_iters: int):
    """eta = 1 / (lambda_max(Y Y^T)/(4 mu) + lam); the (1, 1) block IS
    the eigenvalue at mu = 1 (skip the power loop, as in BDCD)."""
    v = G[0, 0] if mu == 1 else linalg.power_iteration_max_eig(G, power_iters)
    return 1.0 / (0.25 * v / mu + lam)


def bcd_logreg(problem: LogRegProblem, cfg: SolverConfig,
               axis_name: Optional[object] = None,
               x0=None, state: Optional[SolveState] = None) -> SolverResult:
    """Classical (synchronous) block CD / mini-batch logistic regression:
    ONE fused Allreduce of the (m, mu) cross block per iteration."""
    with phases.scope("setup"):
        mu = cfg.block_size
        lam = jnp.asarray(problem.lam, cfg.dtype)
        key = jax.random.key(cfg.seed)
        carry0 = resume_carry(state, x0, "bcd_logreg")
        start = 0 if state is None else int(state.iteration)
        A, b, w, f, sq = _init_state(problem, cfg, axis_name, x0, carry0)
        take, _, densify, apply_t = row_block_ops(A, cfg)
        m = A.shape[0]

    def step(carry, h):
        w, f, sq = carry
        with phases.scope("sample"):
            idx = linalg.sample_block(jax.random.fold_in(key, h), m, mu)
        # --- Communication: ONE fused Allreduce of  A Y^T ---
        with phases.scope("assemble"):
            Y = take(idx)                                # (mu, n_loc) local
            with phases.scope("gram"):
                local = cross_block(A, densify(Y), cfg.use_pallas)
        with phases.scope("reduce"):
            cross = linalg.preduce(local, axis_name)     # (m, mu)
        with phases.scope("inner"):
            G = cross[idx]                               # (mu, mu) = Y Y^T
            fB = f[idx]                                  # = Y w (gather)
            c = -b[idx] * jax.nn.sigmoid(-b[idx] * fB)
            eta = _step_size(G, mu, lam, cfg.power_iters)
            d = 1.0 - eta * lam
            u = -(eta / mu) * c                          # (mu,)
        with phases.scope("defer"):
            w = d * w + apply_t(Y, u)                    # local shard
            sq = d * d * sq + 2.0 * d * (fB @ u) + u @ (G @ u)
            f = d * f + cross @ u                        # replicated
            obj = _tracked_objective(f, sq, b, lam) if cfg.track_objective \
                else jnp.asarray(0.0, cfg.dtype)
        return (w, f, sq), obj

    (w, f, sq), objs = jax.lax.scan(
        step, (w, f, sq), jnp.arange(start + 1, start + cfg.iterations + 1))
    with phases.scope("finalize"):
        return SolverResult(
            x=w, objective=objs,
            aux={"margins": f, "w_norm_sq": sq,
                 "state": SolveState(start + cfg.iterations,
                                     {"w": w, "margins": f, "sq": sq}),
                 **operand_aux(A, cfg, "cross")})


def _cli_problem(args):
    from repro.data.sparse import make_svm_dataset
    A, b = make_svm_dataset(args.dataset, args.seed)
    return LogRegProblem(A=A, b=b, lam=args.logreg_l2)


def _cli_describe(args, res, elapsed: float) -> str:
    import numpy as np
    obj = np.asarray(res.objective)
    return (f"logreg {args.dataset} s={args.s} mu={args.mu}: "
            f"obj {obj[0]:.5f} -> {obj[-1]:.5f}, {elapsed:.2f}s")


@register_family(
    "logreg",
    problem_cls=LogRegProblem,
    partition="col",
    default_axes="model",
    x0_layout="partition",           # warm start = w, on the feature axis
    aux_out=(("margins", "replicated"),),
    variants={
        "classical": "repro.core.logreg:bcd_logreg",
        "sa": "repro.core.sa_logreg:sa_bcd_logreg",
    },
    objective=logreg_objective,
    costs=lambda dims, H, mu, s, P, kernel="linear": cost_model.logreg_costs(
        dims, H, mu, s, P),
    make_problem=_cli_problem,
    describe=_cli_describe,
    default_mu=4,
    bench_block_size=2,
    bench_problem_kwargs={"lam": 1e-3},
    # same (m, s*mu) cross-block message shape as the kernel SVM.
    tune_space={"s": (1, 2, 4, 8, 16, 32), "mu": (1, 2, 4, 8)},
    state_layout=lambda cfg: (("w", "partition"), ("margins", "replicated"),
                              ("sq", "replicated")),
)
def solve_logreg(problem: LogRegProblem, cfg: SolverConfig,
                 axis_name: Optional[object] = None,
                 x0=None, state=None) -> SolverResult:
    """Dispatch on cfg.s: classical BCD vs the SA s-step unroll.

    ``cfg.accelerated`` is ignored (no accelerated variant, as for SVM).
    """
    if cfg.s > 1:
        from repro.core.sa_logreg import sa_bcd_logreg
        return sa_bcd_logreg(problem, cfg, axis_name, x0, state)
    return bcd_logreg(problem, cfg, axis_name, x0, state)
