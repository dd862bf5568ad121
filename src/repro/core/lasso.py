"""Classical (synchronous) coordinate-descent solvers for proximal
least-squares — paper Algorithm 1 and its non-accelerated / single-coordinate
variants (accBCD, BCD, accCD, CD).

All solvers are pure JAX, jit/scan-based, and run either

* single-device: ``axis_name=None``, A is the full (m, n) matrix; or
* distributed:   inside ``shard_map`` with A 1D-row-partitioned and
  ``axis_name`` naming the mesh axis (or tuple of axes) to reduce over.
  Vectors in R^m (residuals) are row-partitioned like A; vectors in R^n
  (solutions) and all scalars are replicated — exactly Figure 1 of the
  paper.

Communication structure (the object of study): each iteration performs ONE
fused Allreduce of the (mu x mu) Gram block and the (mu,) projection — the
paper's "Communication: lines 8 and 9".
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import cost_model, linalg, phases, prox as prox_lib
from repro.core.sparse_exec import col_block_ops, operand_aux, prep_operand
from repro.core.types import (LassoProblem, SolveState, SolverConfig,
                              SolverResult, SparseOperand, operand_matvec,
                              register_family, require_unit_block,
                              resume_carry)


def _validate_groups(groups, n: int, mu: int) -> None:
    """Enforce the documented group-lasso contract (DESIGN.md): groups
    are contiguous, equal-sized blocks of exactly mu coordinates.

    Both violations used to be silent wrong answers: with mu not
    dividing n, ``n_groups = n // mu`` drops the last ``n % mu``
    coordinates from the sampler — they are never updated; a groups
    array that isn't contiguous mu-blocks makes the block prox shrink
    sets of coordinates that aren't the declared groups.
    """
    import numpy as np
    if n % mu != 0:
        raise ValueError(
            f"group lasso requires block_size (the group size) to divide "
            f"n: got n={n}, block_size={mu} — the trailing {n % mu} "
            f"coordinates would never be sampled or updated")
    g = np.asarray(groups)
    if g.shape != (n,):
        raise ValueError(
            f"groups must be an (n,) array of group ids; got shape "
            f"{g.shape} for n={n}")
    # contract: each consecutive mu-sized block carries ONE group id,
    # and no id spans two blocks. The ids themselves may be any
    # distinct labels (the prox is blockwise and the objective
    # partitions by label, so relabeling does not change the solve).
    blocks = g.reshape(n // mu, mu)
    uniform = (blocks == blocks[:, :1]).all()
    labels = blocks[:, 0]
    if not uniform or len(np.unique(labels)) != labels.size:
        raise ValueError(
            "groups must label contiguous, equal-sized blocks of "
            "block_size coordinates (one distinct group id per "
            "mu-sized block); the provided array does not — reorder "
            "the features or adjust cfg.block_size to the group size")


def _prep(problem: LassoProblem, cfg: SolverConfig):
    A = prep_operand(problem.A, cfg.dtype)
    b = jnp.asarray(problem.b, cfg.dtype)
    n = A.shape[1]
    mu = cfg.block_size
    if problem.groups is not None:
        _validate_groups(problem.groups, n, mu)
        n_groups = n // mu
        q = n_groups
        def sampler(key):
            return linalg.sample_group(key, n_groups, mu)
    else:
        q = -(-n // mu)  # ceil(n / mu)
        def sampler(key):
            return linalg.sample_block(key, n, mu)
    prox = prox_lib.make_prox(problem.lam, problem.l2, problem.groups)
    return A, b, n, mu, q, sampler, prox


def _objective(residual, x, problem, axis_name):
    quad = 0.5 * linalg.preduce(jnp.sum(residual * residual), axis_name)
    return quad + prox_lib.reg_value(x, problem.lam, problem.l2, problem.groups)


# ---------------------------------------------------------------------------
# Non-accelerated BCD (mu = 1 -> CD). Richtarik–Takac style proximal step.
# ---------------------------------------------------------------------------

def bcd_lasso(problem: LassoProblem, cfg: SolverConfig,
              axis_name: Optional[object] = None,
              x0=None, state: Optional[SolveState] = None) -> SolverResult:
    """Classical (non-accelerated) randomized block coordinate descent.

    x0: optional warm start (replicated (n,) vector). The residual is
    rebuilt locally from the row shard — no communication.
    state: optional checkpointed :class:`SolveState` (carries x AND the
    residual, plus the global iteration offset) — the resumed solve
    continues the uninterrupted iterate sequence exactly.
    """
    with phases.scope("setup"):
        A, b, n, mu, q, sampler, prox = _prep(problem, cfg)
        block_gram, block_apply = col_block_ops(A, cfg)
        key = jax.random.key(cfg.seed)
        carry0 = resume_carry(state, x0, "bcd_lasso")
        start = 0 if state is None else int(state.iteration)

        if carry0 is not None:
            x0 = jnp.asarray(carry0["x"], cfg.dtype)
            r0 = jnp.asarray(carry0["residual"], cfg.dtype)
        elif x0 is None:
            x0 = jnp.zeros((n,), cfg.dtype)
            r0 = -b  # residual Ax - b at x = 0 (row shard)
        else:
            x0 = jnp.asarray(x0, cfg.dtype)
            r0 = operand_matvec(A, x0) - b

    def step(carry, h):
        x, r = carry
        with phases.scope("sample"):
            idx = sampler(jax.random.fold_in(key, h))
        # --- Communication: one fused Allreduce of [G | A_h^T r] ---
        with phases.scope("assemble"):
            Ah, local = block_gram(idx, r[:, None])       # (mu, mu+1) local
        with phases.scope("reduce"):
            GR = linalg.preduce(local, axis_name)
            G, rh = GR[:, :mu], GR[:, mu]
        with phases.scope("inner"):
            v = linalg.power_iteration_max_eig(G, cfg.power_iters)
            eta = 1.0 / linalg.floor_eig(v)  # floored: zero block -> no-op
            g = x[idx] - eta * rh
            dx = prox(g, eta) - x[idx]
            x = x.at[idx].add(dx)
        with phases.scope("defer"):
            r = r + block_apply(Ah, dx)
            obj = _objective(r, x, problem, axis_name) \
                if cfg.track_objective else 0.0
        return (x, r), obj

    (x, r), objs = jax.lax.scan(
        step, (x0, r0), jnp.arange(start + 1, start + cfg.iterations + 1))
    with phases.scope("finalize"):
        return SolverResult(
            x=x, objective=objs,
            aux={"residual": r,
                 "state": SolveState(start + cfg.iterations,
                                     {"x": x, "residual": r}),
                 **operand_aux(A, cfg, "col_gram", extra=1)})


# ---------------------------------------------------------------------------
# Accelerated BCD — paper Algorithm 1 (APPROX / Fercoq–Richtarik).
# ---------------------------------------------------------------------------

def acc_bcd_lasso(problem: LassoProblem, cfg: SolverConfig,
                  axis_name: Optional[object] = None,
                  x0=None, state: Optional[SolveState] = None) -> SolverResult:
    """Paper Algorithm 1: accelerated block coordinate descent for Lasso.

    State: z, y in R^n (replicated), ztil = Az - b, ytil = Ay in R^m
    (row-partitioned). x_h = theta_h^2 * y_h + z_h is implicit.

    x0: optional warm start — seeds z (y restarts at 0, i.e. the
    acceleration momentum resets, the standard warm-start convention).
    state: optional checkpointed :class:`SolveState` — resumes z, y,
    ztil, ytil and the theta schedule at the recorded global iteration
    (the schedule is a deterministic recurrence, so recomputing it over
    ``start + H`` steps reproduces the uninterrupted prefix bitwise).
    """
    with phases.scope("setup"):
        A, b, n, mu, q, sampler, prox = _prep(problem, cfg)
        block_gram, block_apply = col_block_ops(A, cfg)
        key = jax.random.key(cfg.seed)
        H = cfg.iterations
        carry0 = resume_carry(state, x0, "acc_bcd_lasso")
        start = 0 if state is None else int(state.iteration)

        theta0 = jnp.asarray(mu / n, cfg.dtype)
        thetas = linalg.theta_schedule(theta0, start + H, q)  # (start+H+1,)

        if carry0 is not None:
            z0 = jnp.asarray(carry0["z"], cfg.dtype)
            y0 = jnp.asarray(carry0["y"], cfg.dtype)
            ztil0 = jnp.asarray(carry0["ztil"], cfg.dtype)
            ytil0 = jnp.asarray(carry0["ytil"], cfg.dtype)
        else:
            if x0 is None:
                z0 = jnp.zeros((n,), cfg.dtype)
                ztil0 = -b                                # A z0 - b
            else:
                z0 = jnp.asarray(x0, cfg.dtype)
                ztil0 = operand_matvec(A, z0) - b
            y0 = jnp.zeros((n,), cfg.dtype)
            ytil0 = jnp.zeros_like(b)                     # A y0

    def step(carry, inputs):
        z, y, ztil, ytil = carry
        h, th_prev, th_cur = inputs
        with phases.scope("sample"):
            idx = sampler(jax.random.fold_in(key, h))
        # --- Communication: one fused Allreduce of [G | r_h]  (lines 8-9) ---
        with phases.scope("assemble"):
            w = th_prev * th_prev * ytil + ztil           # (m_loc,)
            Ah, local = block_gram(idx, w[:, None])       # (mu, mu+1) local
        with phases.scope("reduce"):
            GR = linalg.preduce(local, axis_name)
            G, rh = GR[:, :mu], GR[:, mu]
        with phases.scope("inner"):
            v = linalg.power_iteration_max_eig(G, cfg.power_iters)  # line 10
            eta = 1.0 / linalg.floor_eig(q * th_prev * v)  # line 11 (floored)
            g = z[idx] - eta * rh                         # line 12
            dz = prox(g, eta) - z[idx]                    # line 13
            z = z.at[idx].add(dz)                         # line 14
        with phases.scope("defer"):
            Adz = block_apply(Ah, dz)                     # A_h dz (local)
            ztil = ztil + Adz                             # line 15
            coef = (1.0 - q * th_prev) / (th_prev * th_prev)
            y = y.at[idx].add(-coef * dz)                 # line 16
            ytil = ytil - coef * Adz                      # line 17
            if cfg.track_objective:
                res = th_cur * th_cur * ytil + ztil       # A x_h - b
                x_h = th_cur * th_cur * y + z
                obj = _objective(res, x_h, problem, axis_name)
            else:
                obj = jnp.asarray(0.0, cfg.dtype)
        return (z, y, ztil, ytil), obj

    hs = jnp.arange(start + 1, start + H + 1)
    (z, y, ztil, ytil), objs = jax.lax.scan(
        step, (z0, y0, ztil0, ytil0), (hs, thetas[start:-1],
                                       thetas[start + 1:]))
    with phases.scope("finalize"):
        thH = thetas[-1]
        x = thH * thH * y + z                             # line 19
        return SolverResult(
            x=x, objective=objs,
            aux={"residual": thH * thH * ytil + ztil,
                 "state": SolveState(start + H, {"z": z, "y": y,
                                                 "ztil": ztil,
                                                 "ytil": ytil}),
                 **operand_aux(A, cfg, "col_gram", extra=1)})


def cd_lasso(problem: LassoProblem, cfg: SolverConfig,
             axis_name: Optional[object] = None,
             x0=None, state: Optional[SolveState] = None) -> SolverResult:
    """CD = BCD with mu = 1."""
    require_unit_block(cfg, "cd_lasso")
    return bcd_lasso(problem, cfg, axis_name, x0, state)


def acc_cd_lasso(problem: LassoProblem, cfg: SolverConfig,
                 axis_name: Optional[object] = None,
                 x0=None, state: Optional[SolveState] = None) -> SolverResult:
    """accCD = accBCD with mu = 1."""
    require_unit_block(cfg, "acc_cd_lasso")
    return acc_bcd_lasso(problem, cfg, axis_name, x0, state)


def lasso_objective(problem: LassoProblem, x,
                    axis_name: Optional[object] = None):
    """Direct objective evaluation 1/2 ||Ax - b||^2 + g(x) (diagnostic)."""
    A = problem.A if isinstance(problem.A, SparseOperand) \
        else jnp.asarray(problem.A)
    x = jnp.asarray(x, A.dtype)
    residual = operand_matvec(A, x) - jnp.asarray(problem.b, A.dtype)
    return _objective(residual, x, problem, axis_name)


def _cli_problem(args):
    from repro.data.sparse import make_lasso_dataset
    A, b, lam_max = make_lasso_dataset(args.dataset, args.seed)
    return LassoProblem(A=A, b=b, lam=args.lam_frac * lam_max)


def _cli_describe(args, res, elapsed: float) -> str:
    import numpy as np
    obj = np.asarray(res.objective)
    nnz = int(np.sum(np.abs(np.asarray(res.x)) > 1e-8))
    return (f"lasso {args.dataset} s={args.s} mu={args.mu}: "
            f"obj {obj[0]:.4f} -> {obj[-1]:.4f}, nnz(x)={nnz}, "
            f"{elapsed:.2f}s")


@register_family(
    "lasso",
    problem_cls=LassoProblem,
    partition="row",
    default_axes="data",
    x0_layout="replicated",
    aux_out=(("residual", "partition"),),
    variants={
        "classical": "repro.core.lasso:bcd_lasso",
        "accelerated": "repro.core.lasso:acc_bcd_lasso",
        "sa": "repro.core.sa_lasso:sa_bcd_lasso",
        "sa_accelerated": "repro.core.sa_lasso:sa_acc_bcd_lasso",
    },
    objective=lasso_objective,
    costs=lambda dims, H, mu, s, P, kernel="linear": cost_model.lasso_costs(
        dims, H, mu, s, P),
    make_problem=_cli_problem,
    describe=_cli_describe,
    default_mu=8,
    bench_block_size=4,
    bench_problem_kwargs={"lam": 0.1},
    supports_symmetric_gram=True,
    state_layout=lambda cfg: (
        (("z", "replicated"), ("y", "replicated"),
         ("ztil", "partition"), ("ytil", "partition"))
        if cfg.accelerated else
        (("x", "replicated"), ("residual", "partition"))),
)
def solve_lasso(problem: LassoProblem, cfg: SolverConfig,
                axis_name: Optional[object] = None,
                x0=None, state=None) -> SolverResult:
    """Dispatch on (accelerated, s): s == 1 -> this module; s > 1 -> SA."""
    if cfg.s > 1:
        from repro.core import sa_lasso
        fn = (sa_lasso.sa_acc_bcd_lasso if cfg.accelerated
              else sa_lasso.sa_bcd_lasso)
    else:
        fn = acc_bcd_lasso if cfg.accelerated else bcd_lasso
    return fn(problem, cfg, axis_name, x0, state)
