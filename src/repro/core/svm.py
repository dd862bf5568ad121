"""(Block) dual coordinate descent for linear SVM — paper Algorithm 3
(after Hsieh et al., 2008) and its block generalization BDCD (after
Devarakonda et al., arXiv:1612.04003), for both hinge (SVM-L1) and
squared-hinge (SVM-L2).

Partitioning (paper Sec. V): unlike Lasso, SVM requires 1D-COLUMN
partitioning so the row/primal dot-products parallelize. In distributed
mode A holds the local column shard (m, n_loc); x in R^n is partitioned;
alpha in R^m, b in R^m and all scalars are replicated.

Per-iteration communication: ONE fused Allreduce of the (mu, mu+1)
matrix  Y [Y^T | x]  — the block Gram plus projection (paper
"Communication: lines 7 and 8"; for mu = 1 this is the two scalars
[ ||A_i||^2 , A_i x ]).

The dual objective  f_D(alpha) = 1/2 alpha^T Qbar alpha - e^T alpha  is
tracked *exactly* and incrementally per iteration with local
O(mu^2)-sized data only: for a block update alpha_B += theta,
    delta f_D = theta^T g_B + 1/2 (b_B theta)^T G (b_B theta)
where g_B = (Qbar alpha)_B - 1 is the gradient the step already computes
and G = Y Y^T + gamma I the reduced block; for mu = 1 this collapses to
theta * g + 1/2 theta^2 * eta. (Derivation in DESIGN.md; validated
against the direct quadratic form in tests.)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import cost_model, linalg, phases
from repro.core.sparse_exec import operand_aux, prep_operand, row_block_ops
from repro.core.types import (SVMProblem, SolveState, SolverConfig,
                              SolverResult, operand_matvec, operand_rmatvec,
                              register_family, require_unit_block,
                              resume_carry)


def primal_objective(problem: SVMProblem, x, axis_name: Optional[object] = None):
    """P(x) = 1/2 ||x||^2 + lam * sum_i loss(1 - b_i A_i x).

    In distributed (column-partitioned) mode, x is the local shard and the
    matvec A x needs one Allreduce.
    """
    margins = linalg.preduce(operand_matvec(problem.A, x), axis_name)  # (m,)
    xi = jnp.maximum(1.0 - problem.b * margins, 0.0)
    loss = jnp.sum(xi) if problem.loss == "l1" else jnp.sum(xi * xi)
    sq = linalg.preduce(jnp.sum(x * x), axis_name)
    return 0.5 * sq + problem.lam * loss


def dual_objective(problem: SVMProblem, alpha, axis_name: Optional[object] = None):
    """f_D(alpha) = 1/2 alpha^T Qbar alpha - e^T alpha (direct evaluation)."""
    w = operand_rmatvec(problem.A, problem.b * alpha)    # (n_loc,) local
    quad = linalg.preduce(jnp.sum(w * w), axis_name)
    return 0.5 * quad + 0.5 * problem.gamma * jnp.sum(alpha * alpha) \
        - jnp.sum(alpha)


def duality_gap(problem: SVMProblem, x, alpha,
                axis_name: Optional[object] = None):
    """P(x) + f_D(alpha) >= 0, == 0 at the optimum (strong duality)."""
    return primal_objective(problem, x, axis_name) \
        + dual_objective(problem, alpha, axis_name)


def bdcd_svm(problem: SVMProblem, cfg: SolverConfig,
             axis_name: Optional[object] = None,
             alpha0=None, state: Optional[SolveState] = None) -> SolverResult:
    """Block dual coordinate descent (BDCD) for linear SVM.

    Paper Algorithm 3 generalized to block updates of mu = cfg.block_size
    dual coordinates per iteration, following the CA-BDCD derivation of
    Devarakonda et al. (arXiv:1612.04003): sample a block B of mu rows,
    Allreduce the fused (mu, mu+1) matrix  Y [Y^T | x]  (Gram block plus
    projection, ONE message), and take the projected block-gradient step

        alpha_B <- clip(alpha_B - g_B / lambda_max(Q_BB), 0, nu)

    with lambda_max from the existing power-iteration machinery. Because
    b_i in {-1, +1}, diag(b_B) is orthogonal and
    lambda_max(Q_BB) = lambda_max(Y Y^T + gamma I), so the power method
    runs directly on the reduced Gram block. mu = 1 recovers Algorithm 3
    exactly (eta = ||a_i||^2 + gamma, scalar step).

    The dual objective is tracked incrementally (DESIGN.md): for a block
    update alpha_B += theta,
        delta f_D = theta^T g_B + 1/2 (b_B theta)^T G (b_B theta)
    where G = Y Y^T + gamma I is the reduced block the step already holds.
    """
    with phases.scope("setup"):
        A = prep_operand(problem.A, cfg.dtype)
        take, gram, _, apply_t = row_block_ops(A, cfg)
        b = jnp.asarray(problem.b, cfg.dtype)
        m = A.shape[0]
        mu = cfg.block_size
        gamma = jnp.asarray(problem.gamma, cfg.dtype)
        nu = jnp.asarray(problem.nu, cfg.dtype)
        key = jax.random.key(cfg.seed)
        carry0 = resume_carry(state, alpha0, "bdcd_svm")
        start = 0 if state is None else int(state.iteration)

        if carry0 is not None:
            # resume: alpha, the primal shard x AND the running dual come
            # back from the checkpoint — no matvec, no Allreduce, so the
            # resumed sequence is bit-identical to the uninterrupted one.
            alpha = jnp.asarray(carry0["alpha"], cfg.dtype)
            x = jnp.asarray(carry0["x"], cfg.dtype)
            dual0 = jnp.asarray(carry0["dual"], cfg.dtype)
        else:
            alpha = jnp.zeros((m,), cfg.dtype) if alpha0 is None \
                else jnp.asarray(alpha0, cfg.dtype)
            x = operand_rmatvec(A, b * alpha)            # line 2 (local shard)
            # incremental tracking resumes from f_D(alpha0) on warm start
            # (zero at alpha0 = 0 without any communication), so a
            # warm-started solve's objective trace continues the previous
            # solve's. Reuses the x we just built:
            # f_D(alpha) = 1/2 ||A^T(b a)||^2 + gamma/2 ||a||^2 - e^T a.
            dual0 = jnp.asarray(0.0, cfg.dtype) if alpha0 is None else (
                0.5 * linalg.preduce(jnp.sum(x * x), axis_name)
                + 0.5 * gamma * jnp.sum(alpha * alpha) - jnp.sum(alpha))
        eye_mu = jnp.eye(mu, dtype=cfg.dtype)

    def step(carry, h):
        alpha, x, dual = carry
        with phases.scope("sample"):
            idx = linalg.sample_block(jax.random.fold_in(key, h), m, mu)
        # --- Communication: ONE fused Allreduce of  Y [Y^T | x] ---
        with phases.scope("assemble"):
            Y = take(idx)                                # (mu, n_loc) local
            b_B = b[idx]
            local = gram(Y, x[:, None])
        with phases.scope("reduce"):
            red = linalg.preduce(local, axis_name)
            G = red[:, :mu] + gamma * eye_mu             # line 7 (block)
        with phases.scope("inner"):
            a_B = alpha[idx]
            g = b_B * red[:, mu] - 1.0 + gamma * a_B     # line 8 (block)
            # mu = 1: the (1, 1) Gram "block" IS the eigenvalue (paper
            # Alg. 3's eta = ||a_i||^2 + gamma) — skip the power loop.
            v = G[0, 0] if mu == 1 \
                else linalg.power_iteration_max_eig(G, cfg.power_iters)
            gbar = jnp.abs(jnp.clip(a_B - g, 0.0, nu) - a_B)         # line 9
            theta = jnp.where(
                gbar != 0.0,
                jnp.clip(a_B - g / v, 0.0, nu) - a_B,                # line 11
                0.0)
            alpha = alpha.at[idx].add(theta)             # line 13
        with phases.scope("defer"):
            bt = b_B * theta
            x = x + apply_t(Y, bt)                       # line 14 (local)
            dual = dual + jnp.sum(theta * g) + 0.5 * bt @ (G @ bt)
            obj = dual if cfg.track_objective \
                else jnp.asarray(0.0, cfg.dtype)
        return (alpha, x, dual), obj

    (alpha, x, dual), objs = jax.lax.scan(
        step, (alpha, x, dual0),
        jnp.arange(start + 1, start + cfg.iterations + 1))
    with phases.scope("finalize"):
        return SolverResult(
            x=x, objective=objs,
            aux={"alpha": alpha, "dual": dual,
                 "state": SolveState(start + cfg.iterations,
                                     {"alpha": alpha, "x": x,
                                      "dual": dual}),
                 **operand_aux(A, cfg, "row_gram", extra=1)})


def dcd_svm(problem: SVMProblem, cfg: SolverConfig,
            axis_name: Optional[object] = None,
            alpha0=None, state: Optional[SolveState] = None) -> SolverResult:
    """Paper Algorithm 3: the block_size = 1 special case of ``bdcd_svm``."""
    require_unit_block(cfg, "dcd_svm")
    return bdcd_svm(problem, cfg, axis_name, alpha0, state)


def _cli_kernel(args) -> str:
    """--kernel is None when unset; this family defaults to linear."""
    return args.kernel or "linear"


def _cli_problem(args):
    from repro.data.sparse import make_svm_dataset
    from repro.core.types import build_kernel_params
    A, b = make_svm_dataset(args.dataset, args.seed)
    kernel = _cli_kernel(args)
    return SVMProblem(A=A, b=b, lam=1.0, loss=args.svm_loss, kernel=kernel,
                      kernel_params=build_kernel_params(kernel, args))


def _cli_describe(args, res, elapsed: float) -> str:
    import numpy as np
    obj = np.asarray(res.objective)
    return (f"svm-{args.svm_loss}[{_cli_kernel(args)}] {args.dataset} "
            f"s={args.s} mu={args.mu}: "
            f"dual {obj[0]:.5f} -> {obj[-1]:.5f}, {elapsed:.2f}s")


@register_family(
    "svm",
    problem_cls=SVMProblem,
    partition="col",
    default_axes="model",
    x0_layout="replicated",          # warm start = dual alpha in R^m
    aux_out=(("alpha", "replicated"),),
    accepts=lambda p: getattr(p, "kernel", "linear") == "linear",
    variants={
        "classical": "repro.core.svm:bdcd_svm",
        "sa": "repro.core.sa_svm:sa_bdcd_svm",
    },
    objective=dual_objective,
    # this family only accepts kernel="linear" problems; the hook still
    # takes the registry-wide kernel argument and ignores it.
    costs=lambda dims, H, mu, s, P, kernel="linear": cost_model.svm_costs(
        dims, H, s, P, mu=mu),
    make_problem=_cli_problem,
    describe=_cli_describe,
    default_mu=1,
    bench_block_size=1,
    bench_problem_kwargs={"lam": 1.0},
    supports_symmetric_gram=True,
    state_layout=lambda cfg: (("alpha", "replicated"), ("x", "partition"),
                              ("dual", "replicated")),
)
def solve_svm(problem: SVMProblem, cfg: SolverConfig,
              axis_name: Optional[object] = None,
              x0=None, state=None) -> SolverResult:
    """Dispatch on (problem.kernel, cfg.s).

    Linear problems keep the primal-shadowing (SA-)BDCD solvers with
    their O(s^2 mu^2) reduced message; nonlinear kernels route to the
    kernelized (SA-)K-BDCD solvers of ``repro.core.kernel_svm``
    (``kernel="linear"`` there reproduces the same iterates — the
    dispatch is a communication-cost choice, not an algorithmic one).

    x0: optional warm start for the dual vector alpha (replicated (m,)).
    """
    if getattr(problem, "kernel", "linear") != "linear":
        from repro.core.kernel_svm import solve_ksvm
        return solve_ksvm(problem, cfg, axis_name, x0, state)
    if cfg.s > 1:
        from repro.core.sa_svm import sa_bdcd_svm
        return sa_bdcd_svm(problem, cfg, axis_name, x0, state)
    return bdcd_svm(problem, cfg, axis_name, x0, state)
