"""Kernel SVM subsystem — K-BDCD and its s-step synchronization-avoiding
unroll SA-K-BDCD (after Shao & Devarakonda, arXiv:2406.18001).

The paper's SA trick extends to kernel methods by swapping the linear
Gram block  Y Y^T  for a kernel block  K(Y, Y): the dual problem becomes

    min_a  1/2 a^T (diag(b) K(A, A) diag(b) + gamma I) a - e^T a,
    0 <= a_i <= nu

and the only structural change to (SA-)BDCD is the state vector. With a
nonlinear kernel there is no n-dimensional primal to shadow, so the
solvers maintain the replicated dual-residual vector

    f = K(A, A) (b * alpha)   in R^m

("function evaluations at every data point"). The block gradient is then
a pure gather  g_B = b_B * f[B] - 1 + gamma a_B,  and f's update needs
the m x mu kernel column block  K(A, Y)  the iteration already
communicates.

Data layout (paper Sec. V, unchanged): A is 1D-COLUMN-partitioned
(m, n_loc); alpha, b, f in R^m are replicated. Per-iteration
communication for K-BDCD: ONE fused Allreduce of the local cross
products  [A Y^T | rownorms(A)]  (the norms column rides along only for
kernels that need it, e.g. rbf). The kernel transform itself is applied
AFTER the reduction on the replicated copy, so kernelizing changes no
communication structure. SA-K-BDCD amortizes this as an engine
FamilyProgram (see ``sa_kbdcd_svm``), running the s inner updates
through the same ``repro.kernels.svm_inner`` fused Pallas kernel as the
linear solver (``cfg.use_pallas``; the chosen path lands in
``SolverResult.aux["inner_impl"]``).

``kernel="linear"`` reproduces ``bdcd_svm`` / ``sa_bdcd_svm`` iterates
exactly (f = A x by definition) — tested in tests/test_kernel_svm.py —
at O(m) replicated state instead of the (mu, mu+1) reduced message, so
``solve_svm`` keeps routing linear problems to the cheaper primal-shadow
solvers and sends everything else here.

``cfg.symmetric_gram`` does not apply (the (m, s*mu) cross block is not
symmetric) and is ignored.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import cost_model, linalg, phases
from repro.core.engine import Ctx, FamilyProgram, run_program
from repro.core.sparse_exec import (cross_block, operand_aux,
                                    prep_operand, row_block_ops)
from repro.core.types import (SVMProblem, SolveState, SolverConfig,
                              SolverResult, SparseOperand, register_family,
                              resume_carry)
from repro.kernels import spmm
from repro.kernels.svm_inner import svm_inner_loop


def _local_norms(A, needs_norms: bool):
    """(m, 1) local partial squared row norms, computed ONCE per solve
    and re-fused into every Allreduce; None when the kernel needs
    none. Sparse operands sum their stored row values (O(nnz))."""
    if not needs_norms:
        return None
    if isinstance(A, SparseOperand):
        return jnp.sum(A.row_vals * A.row_vals, axis=1, keepdims=True)
    return jnp.sum(A * A, axis=1, keepdims=True)


def _reduce_cross(local, axis_name, norms_local):
    """ONE fused Allreduce of the LOCAL cross block ``[local | norms]``
    (the norms column rides along only when the kernel needs it)."""
    if norms_local is None:
        return linalg.preduce(local, axis_name), None
    red = linalg.preduce(
        jnp.concatenate([local, norms_local], axis=1), axis_name)
    return red[:, :-1], red[:, -1]


def _full_cross_local(A):
    """LOCAL  A A^T  (m, m) for the warm-start residual rebuild. A
    sparse A never materializes the (n_loc, m) dense transpose: the
    densified right operand is built a column-chunk at a time (chunk
    sized to ~16 MB f32) and each chunk contracts through the ELL
    arrays — peak extra memory O(n_loc * chunk), output (m, m) as the
    kernel matrix requires anyway. Values are identical to the
    unchunked product (each output entry is still one ELL row pass)."""
    if not isinstance(A, SparseOperand):
        return A @ A.T
    m, n_loc = A.shape
    chunk = int(max(1, min(m, (1 << 22) // max(n_loc, 1))))
    pieces = []
    for start in range(0, m, chunk):
        idx = jnp.arange(start, min(start + chunk, m))
        cols, vals, _ = A.gather_rows(idx)
        pieces.append(cross_block(
            A, spmm.scatter_dense(cols, vals, n_loc)))
    return jnp.concatenate(pieces, axis=1)


def _kernelize(problem: SVMProblem, cross, anorms, flat_idx, dtype):
    """Apply the registered kernel transform to the reduced cross block:
    K(A, Y)[i, j] = k(a_i, y_j), with y's norms gathered from a's."""
    spec = problem.kernel_spec
    ynorms = None if anorms is None else anorms[flat_idx]
    return spec.fn(cross, anorms, ynorms,
                   problem.kernel_params).astype(dtype)


def kernel_dual_objective(problem: SVMProblem, alpha,
                          axis_name: Optional[object] = None):
    """f_D(alpha) = 1/2 (b a)^T K (b a) + gamma/2 ||a||^2 - e^T a,
    evaluated directly from the full m x m kernel matrix (diagnostic /
    test oracle — O(m^2) memory)."""
    A = problem.A if isinstance(problem.A, SparseOperand) \
        else jnp.asarray(problem.A)
    b = jnp.asarray(problem.b, A.dtype)
    alpha = jnp.asarray(alpha, A.dtype)
    spec = problem.kernel_spec
    cross, anorms = _reduce_cross(_full_cross_local(A), axis_name,
                                  _local_norms(A, spec.needs_norms))
    Kmat = spec.fn(cross, anorms, anorms, problem.kernel_params)
    ba = b * alpha
    return 0.5 * ba @ (Kmat @ ba) \
        + 0.5 * problem.gamma * jnp.sum(alpha * alpha) - jnp.sum(alpha)


def _init_state(problem: SVMProblem, cfg: SolverConfig, axis_name,
                alpha0, carry0=None):
    """alpha, its primal shadow x = A^T (b alpha) (local shard), the
    replicated dual residual f = K(A, A)(b alpha), and the starting dual
    objective f_D(alpha0) for the incremental trace. alpha0 = None starts
    at zero, where f, x and the dual are zero without any communication.
    A restored ``carry0`` (SolveState.carry) bypasses the expensive full
    K(A, A) rebuild entirely — every leaf comes back verbatim."""
    A = prep_operand(problem.A, cfg.dtype)
    b = jnp.asarray(problem.b, cfg.dtype)
    m = A.shape[0]
    if carry0 is not None:
        return (A, b, jnp.asarray(carry0["alpha"], cfg.dtype),
                jnp.asarray(carry0["x"], cfg.dtype),
                jnp.asarray(carry0["f"], cfg.dtype),
                jnp.asarray(carry0["dual"], cfg.dtype))
    if alpha0 is None:
        alpha = jnp.zeros((m,), cfg.dtype)
        f = jnp.zeros((m,), cfg.dtype)
        x = jnp.zeros((A.shape[1],), cfg.dtype)
        return A, b, alpha, x, f, jnp.asarray(0.0, cfg.dtype)
    alpha = jnp.asarray(alpha0, cfg.dtype)
    spec = problem.kernel_spec
    cross, anorms = _reduce_cross(_full_cross_local(A), axis_name,
                                  _local_norms(A, spec.needs_norms))
    Kmat = spec.fn(cross, anorms, anorms,
                   problem.kernel_params).astype(cfg.dtype)
    ba = b * alpha
    f = Kmat @ ba
    x = A.rmatvec(ba) if isinstance(A, SparseOperand) else A.T @ ba
    # f_D(alpha0), reusing the f we just built: warm-started solves resume
    # the incremental dual trace where the previous solve left it.
    gamma = jnp.asarray(problem.gamma, cfg.dtype)
    dual0 = 0.5 * ba @ f + 0.5 * gamma * jnp.sum(alpha * alpha) \
        - jnp.sum(alpha)
    return A, b, alpha, x, f, dual0


def kbdcd_svm(problem: SVMProblem, cfg: SolverConfig,
              axis_name: Optional[object] = None,
              alpha0=None, state: Optional[SolveState] = None
              ) -> SolverResult:
    """Kernel block dual coordinate descent (K-BDCD).

    Per iteration: sample a block B of mu rows, Allreduce the fused
    [A Y^T | norms] cross block (ONE message), kernelize it to the
    column block K(A, Y), and take the projected block-gradient step

        alpha_B <- clip(alpha_B - g_B / lambda_max(K_BB + gamma I), 0, nu)

    with  g_B = b_B * f[B] - 1 + gamma alpha_B  a pure gather off the
    maintained dual residual f, then  f += K(A, Y)(b_B theta). mu = 1
    skips the power iteration: the (1, 1) block k(a_i, a_i) + gamma IS
    the step size. The dual objective is tracked incrementally exactly
    as in ``bdcd_svm`` with G -> K_BB + gamma I (DESIGN.md).
    """
    with phases.scope("setup"):
        mu = cfg.block_size
        gamma = jnp.asarray(problem.gamma, cfg.dtype)
        nu = jnp.asarray(problem.nu, cfg.dtype)
        key = jax.random.key(cfg.seed)
        carry0 = resume_carry(state, alpha0, "kbdcd_svm")
        start = 0 if state is None else int(state.iteration)
        A, b, alpha, x, f, dual0 = _init_state(problem, cfg, axis_name,
                                               alpha0, carry0)
        take, _, densify, apply_t = row_block_ops(A, cfg)
        norms_local = _local_norms(A, problem.kernel_spec.needs_norms)
        m = A.shape[0]
        eye_mu = jnp.eye(mu, dtype=cfg.dtype)

    def step(carry, h):
        alpha, x, f, dual = carry
        with phases.scope("sample"):
            idx = linalg.sample_block(jax.random.fold_in(key, h), m, mu)
        # --- Communication: ONE fused Allreduce of [A Y^T | norms] ---
        with phases.scope("assemble"):
            Y = take(idx)                                # (mu, n_loc) local
            b_B = b[idx]
            with phases.scope("gram"):
                local = cross_block(A, densify(Y), cfg.use_pallas)
        with phases.scope("reduce"):
            cross, anorms = _reduce_cross(local, axis_name, norms_local)
            Kcol = _kernelize(problem, cross, anorms, idx, cfg.dtype)
            KBB = Kcol[idx] + gamma * eye_mu             # (mu, mu)
        with phases.scope("inner"):
            a_B = alpha[idx]
            g = b_B * f[idx] - 1.0 + gamma * a_B
            # mu = 1: the (1, 1) block IS the eigenvalue — skip the power
            # loop.
            v = KBB[0, 0] if mu == 1 \
                else linalg.power_iteration_max_eig(KBB, cfg.power_iters)
            gbar = jnp.abs(jnp.clip(a_B - g, 0.0, nu) - a_B)
            theta = jnp.where(
                gbar != 0.0,
                jnp.clip(a_B - g / v, 0.0, nu) - a_B,
                0.0)
            alpha = alpha.at[idx].add(theta)
        with phases.scope("defer"):
            bt = b_B * theta
            f = f + Kcol @ bt                            # replicated, local
            x = x + apply_t(Y, bt)                       # primal shadow
            dual = dual + jnp.sum(theta * g) + 0.5 * bt @ (KBB @ bt)
            obj = dual if cfg.track_objective \
                else jnp.asarray(0.0, cfg.dtype)
        return (alpha, x, f, dual), obj

    (alpha, x, f, dual), objs = jax.lax.scan(
        step, (alpha, x, f, dual0),
        jnp.arange(start + 1, start + cfg.iterations + 1))
    with phases.scope("finalize"):
        return SolverResult(
            x=x, objective=objs,
            aux={"alpha": alpha, "dual": dual, "f": f,
                 "state": SolveState(start + cfg.iterations,
                                     {"alpha": alpha, "x": x, "f": f,
                                      "dual": dual}),
                 **operand_aux(A, cfg, "cross")})


def _sak_setup(problem, cfg, axis_name, alpha0, carry0):
    A, b, alpha, x, f, dual0 = _init_state(problem, cfg, axis_name, alpha0,
                                           carry0)
    take, _, densify, apply_t = row_block_ops(A, cfg)
    ctx = Ctx(A=A, b=b, m=A.shape[0], mu=cfg.block_size,
              gamma=jnp.asarray(problem.gamma, cfg.dtype),
              gamma_f=float(problem.gamma), nu_f=float(problem.nu),
              take=take, densify=densify, apply_t=apply_t,
              norms_local=_local_norms(A, problem.kernel_spec.needs_norms),
              problem=problem, cfg=cfg, axis_name=axis_name)
    return ctx, (alpha, x, f, dual0)


def _sak_assemble(ctx, carry, idxs, s_grp):
    flat = idxs.reshape(s_grp * ctx.mu)
    Y = ctx.take(flat)                                # (s_grp*mu, n_loc)
    # LOCAL half of the fused [A Y^T | norms] cross block — the norms
    # column rides along only when the kernel needs it (rbf).
    with phases.scope("gram"):
        local = cross_block(ctx.A, ctx.densify(Y), ctx.cfg.use_pallas)
        if ctx.norms_local is not None:
            local = jnp.concatenate([local, ctx.norms_local], axis=1)
    return Y, local


def _sak_reduce(ctx, local, idxs, s_grp):
    # the group's ONE Allreduce, then kernelize the replicated copy:
    # K(A, Y_group) + the regularized (s*mu, s*mu) block K(Y, Y), whose
    # off-diagonal blocks carry the inner cross terms.
    flat = idxs.reshape(s_grp * ctx.mu)
    red = linalg.preduce(local, ctx.axis_name)
    cross, anorms = (red, None) if ctx.norms_local is None \
        else (red[:, :-1], red[:, -1])
    Kfull = _kernelize(ctx.problem, cross, anorms, flat, ctx.cfg.dtype)
    G = Kfull[flat] \
        + ctx.gamma * jnp.eye(s_grp * ctx.mu, dtype=ctx.cfg.dtype)
    return G, Kfull


def _sak_inner(ctx, carry, Y, payload, idxs, win, s_grp):
    alpha, _, f, _ = carry
    cfg = ctx.cfg
    G, Kfull = payload
    flat = idxs.reshape(s_grp * ctx.mu)
    b_sel = ctx.b[flat].reshape(s_grp, ctx.mu)
    theta, deltas = svm_inner_loop(
        G, f[flat].reshape(s_grp, ctx.mu), b_sel,      # proj = f_sk gather
        alpha[flat].reshape(s_grp, ctx.mu), idxs, gamma=ctx.gamma_f,
        nu=ctx.nu_f, power_iters=cfg.power_iters,
        use_pallas=cfg.use_pallas)
    return carry, (theta.astype(cfg.dtype), deltas.astype(cfg.dtype),
                   b_sel, flat)


def _sak_defer(ctx, carry, Y, inner_out, payload, idxs, win, s_grp):
    alpha, x, f, dual = carry
    _, Kfull = payload
    theta, deltas, b_sel, flat = inner_out
    bt = (b_sel * theta).reshape(s_grp * ctx.mu)
    alpha = alpha.at[flat].add(theta.reshape(s_grp * ctx.mu))
    f = f + Kfull @ bt                                # deferred GEMV
    x = x + ctx.apply_t(Y, bt)                        # primal shadow
    objs = dual + jnp.cumsum(deltas) if ctx.cfg.track_objective \
        else jnp.zeros((s_grp,), ctx.cfg.dtype)
    dual = dual + jnp.sum(deltas)
    return (alpha, x, f, dual), objs


_SAK_PROGRAM = FamilyProgram(
    name="sa_kbdcd_svm", setup=_sak_setup,
    sample=lambda ctx, key: linalg.sample_block(key, ctx.m, ctx.mu),
    assemble=_sak_assemble, reduce=_sak_reduce, inner=_sak_inner,
    defer=_sak_defer,
    finalize=lambda ctx, carry, sched: (
        carry[1], {"alpha": carry[0], "dual": carry[3], "f": carry[2]}),
    carry_names=("alpha", "x", "f", "dual"), uses_svm_inner=True,
    spmm_kind="cross")


def sa_kbdcd_svm(problem: SVMProblem, cfg: SolverConfig,
                 axis_name: Optional[object] = None,
                 alpha0=None, state: Optional[SolveState] = None
                 ) -> SolverResult:
    """s-step unrolled K-BDCD: identical iterates to ``kbdcd_svm`` in
    exact arithmetic, ONE Allreduce of the (m, s*mu [+1]) cross block
    per s inner iterations. The inner projections are the gathered
    f_sk[idx] — no projection communication at all, unlike the linear
    solver. Deferred per group: f += K(A, Y) vec(b theta) + the primal
    shadow GEMV."""
    return run_program(_SAK_PROGRAM, problem, cfg, axis_name, alpha0,
                       state)


def _cli_kernel(args) -> str:
    """--kernel is None when unset; this family defaults to rbf, but an
    EXPLICIT --kernel linear is honored (the kernelized linear path
    reproduces BDCD iterates — a communication-cost choice)."""
    return args.kernel or "rbf"


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
    return (f"ksvm-{args.svm_loss}[{_cli_kernel(args)}] {args.dataset} "
            f"s={args.s} mu={args.mu}: "
            f"dual {obj[0]:.5f} -> {obj[-1]:.5f}, {elapsed:.2f}s")


@register_family(
    "ksvm",
    problem_cls=SVMProblem,
    partition="col",
    default_axes="model",
    x0_layout="replicated",          # warm start = dual alpha in R^m
    aux_out=(("alpha", "replicated"), ("f", "replicated")),
    accepts=lambda p: getattr(p, "kernel", "linear") != "linear",
    variants={
        "classical": "repro.core.kernel_svm:kbdcd_svm",
        "sa": "repro.core.kernel_svm:sa_kbdcd_svm",
    },
    objective=kernel_dual_objective,
    # kernel threads through from the caller's problem.kernel (default =
    # this family's CLI/bench default, rbf) — poly/linear-kernelized
    # problems used to report rbf eval flops from a hardcoded kernel.
    costs=lambda dims, H, mu, s, P, kernel="rbf": cost_model.svm_costs(
        dims, H, s, P, mu=mu, kernel=kernel),
    make_problem=_cli_problem,
    describe=_cli_describe,
    default_mu=1,
    bench_block_size=2,
    bench_problem_kwargs={"lam": 1.0, "kernel": "rbf",
                          "kernel_params": {"gamma": 0.1}},
    # the kernelized message is the (m, s*mu) cross block — replicated
    # memory grows with s*mu, so the candidate grid stays smaller.
    tune_space={"s": (1, 2, 4, 8, 16, 32), "mu": (1, 2, 4, 8)},
    state_layout=lambda cfg: (("alpha", "replicated"), ("x", "partition"),
                              ("f", "replicated"), ("dual", "replicated")),
)
def solve_ksvm(problem: SVMProblem, cfg: SolverConfig,
               axis_name: Optional[object] = None,
               x0=None, state=None) -> SolverResult:
    """Dispatch on cfg.s. x0: optional warm start for the dual alpha
    (replicated (m,)); rebuilding f = K(b alpha) costs one setup
    Allreduce (zero start and ``state=`` resume cost none)."""
    if cfg.s > 1:
        return sa_kbdcd_svm(problem, cfg, axis_name, x0, state)
    return kbdcd_svm(problem, cfg, axis_name, x0, state)
