"""Sampled FISTA (SFISTA) and its s-step synchronization-avoiding unroll
CA-SFISTA — after Soori et al., "Avoiding communication in proximal
methods for convex optimization problems" (arXiv:1710.08883), which
builds accelerated sampled proximal least-squares from the same s-step
recurrences as the BCD line.

SFISTA takes a FISTA step restricted to a random block B of mu
coordinates of the momentum iterate y:

    x_h = y_{h-1} + e_B d,   d = prox(y_B - eta A_B^T ry, eta) - y_B
    y_h = x_h + beta_h e_B (x_B^h - x_B^{h-1}),
    beta_h = (t_{h-1} - 1) / t_h      (the classical FISTA t-sequence),

with eta = 1 / lambda_max(A_B^T A_B) from the sampled Gram block and
rx = A x - b, ry = A y - b the two coupled residuals (row-partitioned
like the Lasso solvers). The momentum extrapolation is applied IN THE
SAMPLED SUBSPACE only: coordinates outside B satisfy y_i = x_i after
every iteration. At mu = n this is exactly FISTA (full-vector
extrapolation); for mu < n extrapolating the untouched coordinates —
which received no gradient contraction to balance it — makes the
iteration diverge, while the subspace rule keeps y - x supported on the
last sampled block and the objective decreasing. Per classical
iteration: ONE fused Allreduce of the (mu, mu + 1) block [G | A_B^T ry].

CA-SFISTA is the same s-step transformation as every other family:
sample all s blocks up front, fuse the group's Gram/projection products
into ONE Allreduce of Y^T [Y | ry], and run the s dependent inner
updates on replicated data. Subspace momentum makes the unrolled
residual recurrence a pure accumulation,

    ry_j  = ry_sk + sum_{t <= j} A_{B_t} c_t,    c_t = d_t + beta_t w_t,
    rx_j  = ry_{j-1} + A_{B_j} d_j,
    w_t   = x_B^t - x_B^{t-1}  (gathered from the replicated x, y),

so the gradient projection at step j is  A_B_j^T ry_sk (one payload
column) plus Gram-block contractions with the recorded c_t — every term
a slice of the ONE reduced payload. x and y in R^n are replicated and
updated densely inside the inner loop (no communication), exactly like
the Lasso solvers' z/y. The deferred O(nnz)/dense application then
materializes rx, ry (and the per-step residuals for objective
stitching) from the two coefficient buffers.

Registered as the ``"sfista"`` family: the generic engine
(:mod:`repro.core.engine`) owns grouping, remainder tails, fold_in ids,
the t-schedule windows and SolveState resume; the registry gives it the
sharded driver, elastic checkpointing, the CLI and the autotuner with
zero edits to any of them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import cost_model, linalg, phases, prox as prox_lib
from repro.core.engine import (Ctx, FamilyProgram, deferred_steps,
                               gram_local, reduce_gram_proj, run_program)
from repro.core.sparse_exec import (col_block_ops, col_take, operand_aux,
                                    prep_operand)
from repro.core.types import (SolveState, SolverConfig, SolverResult,
                              SparseOperand, operand_matvec,
                              register_family, resume_carry)


@dataclasses.dataclass(frozen=True)
class SFISTAProblem:
    """Proximal least-squares problem data for the (CA-)SFISTA family.

    Same data as :class:`~repro.core.types.LassoProblem` — A (m, n) dense
    or :class:`~repro.core.types.SparseOperand` (the local ROW shard when
    distributed), b (m,), l1 weight lam, optional l2 -> elastic net — but
    a distinct problem class: the registry dispatches on it, selecting
    the momentum (FISTA) iteration instead of coordinate descent.
    """

    A: Any
    b: Any
    lam: float
    l2: float = 0.0

    @property
    def shape(self):
        return self.A.shape


def _prep(problem: SFISTAProblem, cfg: SolverConfig):
    A = prep_operand(problem.A, cfg.dtype)
    b = jnp.asarray(problem.b, cfg.dtype)
    n = A.shape[1]
    mu = cfg.block_size
    prox = prox_lib.make_prox(problem.lam, problem.l2, None)
    return A, b, n, mu, prox


def _objective(residual, x, problem, axis_name):
    quad = 0.5 * linalg.preduce(jnp.sum(residual * residual), axis_name)
    return quad + prox_lib.reg_value(x, problem.lam, problem.l2, None)


def _init_iterates(A, b, n, cfg, x0, carry0):
    """(x, y, rx, ry): restored verbatim from a checkpoint, rebuilt
    locally from a warm start (momentum restarts: y = x, ry = rx), or the
    zero start where rx = ry = -b with no communication at all."""
    if carry0 is not None:
        return (jnp.asarray(carry0["x"], cfg.dtype),
                jnp.asarray(carry0["y"], cfg.dtype),
                jnp.asarray(carry0["rx"], cfg.dtype),
                jnp.asarray(carry0["ry"], cfg.dtype))
    if x0 is None:
        x = jnp.zeros((n,), cfg.dtype)
        return x, x, -b, -b
    x = jnp.asarray(x0, cfg.dtype)
    rx = operand_matvec(A, x) - b
    return x, x, rx, rx


# ---------------------------------------------------------------------------
# Classical SFISTA: one (mu, mu + 1) fused Allreduce per iteration.
# ---------------------------------------------------------------------------

def sfista(problem: SFISTAProblem, cfg: SolverConfig,
           axis_name: Optional[object] = None,
           x0=None, state: Optional[SolveState] = None) -> SolverResult:
    """Sampled FISTA (block proximal gradient + subspace momentum).

    x0: optional warm start (replicated (n,)); the momentum restarts
    (y = x0) and both residuals rebuild locally — no communication.
    state: optional checkpointed :class:`SolveState` — resumes x, y, rx,
    ry and the t-schedule at the recorded global iteration (the schedule
    is deterministic, so recomputing over ``start + H`` steps reproduces
    the uninterrupted prefix bitwise).
    """
    with phases.scope("setup"):
        A, b, n, mu, prox = _prep(problem, cfg)
        block_gram, block_apply = col_block_ops(A, cfg)
        key = jax.random.key(cfg.seed)
        H = cfg.iterations
        carry0 = resume_carry(state, x0, "sfista")
        start = 0 if state is None else int(state.iteration)
        ts = linalg.fista_t_schedule(start + H, cfg.dtype)  # (start+H+1,)
        x0_, y0, rx0, ry0 = _init_iterates(A, b, n, cfg, x0, carry0)

    def step(carry, inputs):
        x, y, rx, ry = carry
        h, t_prev, t_cur = inputs
        with phases.scope("sample"):
            idx = linalg.sample_block(jax.random.fold_in(key, h), n, mu)
        # --- Communication: one fused Allreduce of [G | A_B^T ry] ---
        with phases.scope("assemble"):
            Ah, local = block_gram(idx, ry[:, None])      # (mu, mu+1) local
        with phases.scope("reduce"):
            GR = linalg.preduce(local, axis_name)
            G, g = GR[:, :mu], GR[:, mu]
        with phases.scope("inner"):
            v = linalg.power_iteration_max_eig(G, cfg.power_iters)
            eta = 1.0 / linalg.floor_eig(v)  # floored: zero block -> no-op
            yB = y[idx]
            d = prox(yB - eta * g, eta) - yB
            x_new = y.at[idx].add(d)                      # prox step on y
        with phases.scope("defer"):
            rx_new = ry + block_apply(Ah, d)              # A x_new - b
            beta = (t_prev - 1.0) / t_cur
            w = yB + d - x[idx]                           # x_B^h - x_B^{h-1}
            y_new = x_new.at[idx].add(beta * w)           # subspace momentum
            ry_new = ry + block_apply(Ah, d + beta * w)
            obj = _objective(rx_new, x_new, problem, axis_name) \
                if cfg.track_objective else jnp.asarray(0.0, cfg.dtype)
        return (x_new, y_new, rx_new, ry_new), obj

    hs = jnp.arange(start + 1, start + H + 1)
    (x, y, rx, ry), objs = jax.lax.scan(
        step, (x0_, y0, rx0, ry0), (hs, ts[start:-1], ts[start + 1:]))
    with phases.scope("finalize"):
        return SolverResult(
            x=x, objective=objs,
            aux={"residual": rx,
                 "state": SolveState(start + H,
                                     {"x": x, "y": y, "rx": rx, "ry": ry}),
                 **operand_aux(A, cfg, "col_gram", extra=1)})


# ---------------------------------------------------------------------------
# CA-SFISTA: the s-step unroll, as an engine FamilyProgram.
# ---------------------------------------------------------------------------

def _ca_setup(problem, cfg, axis_name, x0, carry0):
    A, b, n, mu, prox = _prep(problem, cfg)
    sparse = isinstance(A, SparseOperand)
    ctx = Ctx(A=A, b=b, n=n, mu=mu, prox=prox, sparse=sparse,
              block_gram=col_block_ops(A, cfg)[0] if sparse else None,
              take=None if sparse else col_take(A, cfg, grouped=True),
              m_loc=A.shape[0], problem=problem, cfg=cfg,
              axis_name=axis_name)
    return ctx, _init_iterates(A, b, n, cfg, x0, carry0)


def _ca_sample(ctx, key):
    return linalg.sample_block(key, ctx.n, ctx.mu)


def _ca_schedule(ctx, cfg, total):
    return linalg.fista_t_schedule(total, cfg.dtype)      # (total+1,)


def _ca_assemble(ctx, carry, idxs, s_grp):
    x, y, rx, ry = carry
    flat = idxs.reshape(s_grp * ctx.mu)
    if ctx.sparse:
        return ctx.block_gram(flat, ry[:, None])
    Yt = ctx.take(flat)                               # (s*mu, m_loc) local
    return Yt, gram_local(Yt.T, ry[:, None], ctx.cfg.use_pallas)


def _ca_reduce(ctx, local, idxs, s_grp):
    return reduce_gram_proj(local, s_grp * ctx.mu, 1, ctx.axis_name,
                            ctx.cfg.symmetric_gram)


def _ca_inner(ctx, carry, handle, payload, idxs, win, s):
    x, y, rx, ry = carry
    cfg, mu = ctx.cfg, ctx.mu
    G, P = payload
    G4 = G.reshape(s, mu, s, mu)
    ry_proj = P[:, 0].reshape(s, mu)                  # A_j^T ry_sk
    th_prev, th_cur = win
    betas = (th_prev - 1.0) / th_cur

    def inner(inner_carry, j):
        x, y, c_buf, d_buf = inner_carry
        idx_j = idxs[j]
        Gj = G4[j]                                    # (mu, s, mu)
        # ry_{j-1} = ry_sk + sum_t A_{B_t} c_t, so the gradient is the
        # payload column plus Gram contractions with the recorded c_t
        # (rows t >= j are still zero).
        cross = jnp.einsum("ptq,tq->tp", Gj, c_buf)   # (s, mu)
        g = ry_proj[j] + jnp.einsum("tp->p", cross)
        v = linalg.power_iteration_max_eig(Gj[:, j, :], cfg.power_iters)
        eta = 1.0 / linalg.floor_eig(v)  # floored: zero block -> no-op
        yB = y[idx_j]
        d = ctx.prox(yB - eta * g, eta) - yB
        x_new = y.at[idx_j].add(d)                    # prox step on y
        w = yB + d - x[idx_j]                         # x_B^j - x_B^{j-1}
        beta = betas[j]
        y_new = x_new.at[idx_j].add(beta * w)         # subspace momentum
        c_buf = c_buf.at[j].set(d + beta * w)
        d_buf = d_buf.at[j].set(d)
        out = x_new if cfg.track_objective else None
        return (x_new, y_new, c_buf, d_buf), out

    init = (x, y, jnp.zeros((s, mu), cfg.dtype), jnp.zeros((s, mu),
                                                           cfg.dtype))
    (x, y, c_buf, d_buf), xs = jax.lax.scan(inner, init, jnp.arange(s))
    return (x, y, rx, ry), (c_buf, d_buf, xs)


def _ca_defer(ctx, carry, handle, inner_out, payload, idxs, win, s):
    x, y, rx, ry = carry
    cfg = ctx.cfg
    c_buf, d_buf, xs = inner_out
    # Deferred m-dimensional steps (local GEMVs; sparse: O(nnz of the
    # sampled columns) scatter-adds): A_{B_t} c_t rebuilds the momentum
    # residual ry, A_{B_t} d_t the prox-point residual rx.
    steps_c = deferred_steps(ctx, handle, c_buf, s)   # (s, m_loc)
    steps_d = deferred_steps(ctx, handle, d_buf, s)
    cum = jnp.cumsum(steps_c, axis=0)
    prefix = ry[None, :] + cum - steps_c              # ry_{j-1} per step
    ry_new = ry + cum[-1]
    rx_new = prefix[-1] + steps_d[-1]

    if cfg.track_objective:
        r_steps = prefix + steps_d                    # rx_j per step
        objs = jax.vmap(
            lambda rr, xx: _objective(rr, xx, ctx.problem, ctx.axis_name))(
            r_steps, xs)
    else:
        objs = jnp.zeros((s,), cfg.dtype)
    return (x, y, rx_new, ry_new), objs


def _ca_finalize(ctx, carry, sched):
    x, y, rx, ry = carry
    return x, {"residual": rx}


_CA_PROGRAM = FamilyProgram(
    name="ca_sfista", setup=_ca_setup, sample=_ca_sample,
    assemble=_ca_assemble, reduce=_ca_reduce, inner=_ca_inner,
    defer=_ca_defer, finalize=_ca_finalize,
    carry_names=("x", "y", "rx", "ry"), schedule=_ca_schedule,
    spmm_kind="col_gram", spmm_extra=1)


def ca_sfista(problem: SFISTAProblem, cfg: SolverConfig,
              axis_name: Optional[object] = None,
              x0=None, state: Optional[SolveState] = None) -> SolverResult:
    """s-step unrolled SFISTA: identical iterates to ``sfista`` in exact
    arithmetic, ONE Allreduce per s inner iterations."""
    return run_program(_CA_PROGRAM, problem, cfg, axis_name, x0, state)


# ---------------------------------------------------------------------------
# Registration: the fifth family — zero edits to dispatch, the sharded
# driver, the elastic runtime, or the CLI.
# ---------------------------------------------------------------------------

def sfista_objective(problem: SFISTAProblem, x,
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
    return SFISTAProblem(A=A, b=b, lam=args.lam_frac * lam_max)


def _cli_describe(args, res, elapsed: float) -> str:
    import numpy as np
    obj = np.asarray(res.objective)
    nnz = int(np.sum(np.abs(np.asarray(res.x)) > 1e-8))
    return (f"sfista {args.dataset} s={args.s} mu={args.mu}: "
            f"obj {obj[0]:.4f} -> {obj[-1]:.4f}, nnz(x)={nnz}, "
            f"{elapsed:.2f}s")


@register_family(
    "sfista",
    problem_cls=SFISTAProblem,
    partition="row",
    default_axes="data",
    x0_layout="replicated",
    aux_out=(("residual", "partition"),),
    variants={
        "classical": "repro.core.sfista:sfista",
        "sa": "repro.core.sfista:ca_sfista",
    },
    objective=sfista_objective,
    # same operand layout and fused-payload shapes as Lasso, so
    # Table I's Lasso entries model it.
    costs=lambda dims, H, mu, s, P, kernel="linear": cost_model.lasso_costs(
        dims, H, mu, s, P),
    make_problem=_cli_problem,
    describe=_cli_describe,
    default_mu=8,
    bench_block_size=4,
    bench_problem_kwargs={"lam": 0.1},
    # the fused payload replicates (s mu)^2 + s mu entries — same growth
    # as Lasso, so the same candidate grid applies.
    tune_space={"s": (1, 2, 4, 8, 16, 32), "mu": (1, 2, 4, 8, 16)},
    supports_symmetric_gram=True,
    state_layout=lambda cfg: (("x", "replicated"), ("y", "replicated"),
                              ("rx", "partition"), ("ry", "partition")),
)
def solve_sfista(problem: SFISTAProblem, cfg: SolverConfig,
                 axis_name: Optional[object] = None,
                 x0=None, state=None) -> SolverResult:
    """Dispatch on cfg.s: classical SFISTA vs the CA-SFISTA unroll."""
    if cfg.s > 1:
        return ca_sfista(problem, cfg, axis_name, x0, state)
    return sfista(problem, cfg, axis_name, x0, state)
