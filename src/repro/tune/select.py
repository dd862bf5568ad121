"""Guard-aware config selection: sweep the registry-declared ``costs``
hook over the family's ``tune_space`` with a (calibrated) ``Machine``
and return the complete tuned ``SolverConfig``.

Selection is pure model evaluation — no solves — so it reruns cheaply
for any H once a machine is calibrated. Three constraints make the
result an *executable* recommendation rather than a paper number:

* **VMEM guards** (``repro.kernels.dispatch``): ``use_pallas`` is only
  recommended when the fused inner kernel's (s*mu)^2 Gram block — and,
  for sparse operands, every blocked-ELL SpMM the solve would dispatch
  — fits the budget at the solve dtype's itemsize. A recommendation
  that silently falls back to ref would make the tuner's own
  measurements lies.
* **Structural blocks**: group-lasso problems have mu fixed to the
  declared group size; the sweep only varies s.
* **symmetric_gram** is only proposed for families whose SA solvers
  honor it (registry flag), and only when the halved Gram message
  actually wins under the calibrated beta.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import cost_model
from repro.core.cost_model import Machine
from repro.core.types import SolverConfig, SparseOperand
from repro.kernels import dispatch
from repro.tune.calibrate import problem_dims, sampled_axis

__all__ = ["select_config", "candidate_grid", "pallas_guards_ok",
           "predicted_solve_time"]


def candidate_grid(fam, problem, base_cfg: SolverConfig
                   ) -> List[Tuple[int, int]]:
    """(s, mu) candidates: the family's declared tune_space, clamped to
    the sampled axis and to the structural group size when present."""
    space = dict(fam.tune_space)
    axis = sampled_axis(fam, problem)
    if getattr(problem, "groups", None) is not None:
        mus: Iterable[int] = (base_cfg.block_size,)
    else:
        mus = space.get("mu", (1, 2, 4, 8, 16))
    ss = space.get("s", (1, 2, 4, 8, 16, 32, 64))
    out = []
    for mu in mus:
        if mu > axis:
            continue
        for s in ss:
            if (s, mu) not in out:
                out.append((s, mu))
    return out


def _spmm_shapes(problem, fam, s: int, mu: int,
                 accelerated: bool = True):
    """(R, K, C, Q) of every blocked-ELL SpMM a sparse solve at (s, mu)
    dispatches — mirrors ``sparse_exec.operand_aux``'s shape derivations,
    including which ONE product each family actually issues (guarding a
    shape the solve never dispatches would wrongly withhold Pallas:
    the (m, s*mu) cross block alone exceeds the cap for large m, but
    the linear SVM never communicates it)."""
    A = problem.A
    if fam.partition == "row":              # Lasso fused col-Gram
        K, C = A.col_rows.shape[1], A.shape[0]
        # appended-vector count: the accelerated variant appends 2
        # residual-like columns (ytil, ztil), the plain one appends 1.
        return [(s * mu, K, C, s * mu + (2 if accelerated else 1))]
    K, C = A.row_cols.shape[1], A.shape[1]
    if getattr(problem, "kernel", None) == "linear":
        return [(s * mu, K, C, s * mu + 1)]     # linear-SVM row-Gram
    return [(A.shape[0], K, C, s * mu)]         # ksvm/logreg cross


def pallas_guards_ok(problem, fam, s: int, mu: int,
                     dtype=jnp.float32,
                     accelerated: bool = True) -> bool:
    """Would a Pallas dispatch at (s, mu) actually run, or silently fall
    back? Checks the inner-kernel Gram budget and — for sparse
    operands — every SpMM shape the solve would issue (``accelerated``
    picks the lasso variant's appended-column count; the conservative
    default covers both)."""
    itemsize = jnp.dtype(dtype).itemsize
    if not dispatch.vmem_ok(s, mu, itemsize):
        return False
    if isinstance(problem.A, SparseOperand):
        for shape in _spmm_shapes(problem, fam, s, mu, accelerated):
            if not dispatch.spmm_vmem_ok(*shape, itemsize=itemsize):
                return False
    return True


def predicted_solve_time(fam, dims, cfg: SolverConfig, machine: Machine,
                         P: int = 1, kernel: str = "linear") -> float:
    """Model time of a full solve under ``cfg``; symmetric_gram halves
    the Gram words W (paper footnote 3) when the family executes it —
    but pays the O(s^2 mu^2)-per-outer-iteration triangle pack/unpack
    as local element work (~2 passes), so on a machine whose beta is
    tiny relative to gamma (a single host) the packed message loses
    and the tuner keeps symmetric_gram off."""
    costs = fam.costs(dims, cfg.iterations, cfg.block_size, cfg.s, P,
                      kernel=kernel)
    t = cost_model.predicted_time(costs, machine)
    if cfg.symmetric_gram and fam.supports_symmetric_gram and cfg.s > 1:
        t -= 0.5 * machine.beta * costs["W"]
        t += 2.0 * machine.gamma * cfg.iterations * cfg.s \
            * cfg.block_size ** 2
    return t


def select_config(problem, machine: Machine, base_cfg: SolverConfig,
                  family=None, *, P: int = 1,
                  allow_pallas: Optional[bool] = None,
                  grid=None, certified: bool = False) -> SolverConfig:
    """The tuned SolverConfig: argmin of the calibrated model over the
    candidate grid, preserving everything the tuner does not own
    (iterations, dtype, seed, accelerated, track_objective, ...).

    allow_pallas=None auto-detects: Pallas is only proposed on TPU
    backends (on CPU the kernels run in interpret mode — strictly
    slower than the jnp reference paths).

    certified=True first runs the static cost certifier
    (``repro.analysis.check_costs``) on the family and refuses to fit
    the machine model against a cost hook the certifier rejects — a
    hook whose counted flops/bytes/messages disagree with the traced
    solve would make every "tuned" recommendation a fit to fiction.
    """
    from repro.core.api import resolve_family

    fam = resolve_family(problem, family)
    if certified:
        from repro.analysis.costs import check_costs
        diags, _ = check_costs(fam)
        errors = [d for d in diags if d.severity == "error"]
        if errors:
            detail = "; ".join(f"{d.where}: {d.message}" for d in errors)
            raise ValueError(
                f"refusing to tune against an uncertified cost model "
                f"for family {fam.name!r}: the static cost certifier "
                f"reports {len(errors)} error(s) — {detail}")
    dims = problem_dims(problem)
    kernel = getattr(problem, "kernel", "linear")
    if allow_pallas is None:
        allow_pallas = jax.default_backend() == "tpu"
    if grid is not None:
        # an explicit grid still has to be executable: pin mu to the
        # structural group size when present, drop mu beyond the
        # sampled axis (the default candidate_grid does both).
        axis = sampled_axis(fam, problem)
        if getattr(problem, "groups", None) is not None:
            grid = [(s, base_cfg.block_size) for s, _ in grid]
        candidates = []
        for c in grid:
            if c[1] <= axis and c not in candidates:
                candidates.append(c)
        if not candidates:
            raise ValueError(
                f"no executable (s, mu) candidates in the provided "
                f"grid {list(grid)!r} (sampled axis size {axis})")
    else:
        candidates = candidate_grid(fam, problem, base_cfg)

    best_cfg, best_t = None, float("inf")
    for s, mu in candidates:
        for sym in ((False, True) if fam.supports_symmetric_gram
                    and s > 1 else (False,)):
            cfg = dataclasses.replace(
                base_cfg, s=s, block_size=mu, symmetric_gram=sym,
                use_pallas=bool(
                    allow_pallas
                    and pallas_guards_ok(problem, fam, s, mu,
                                         base_cfg.dtype,
                                         base_cfg.accelerated)))
            t = predicted_solve_time(fam, dims, cfg, machine, P=P,
                                     kernel=kernel)
            if t < best_t:
                best_cfg, best_t = cfg, t
    if best_cfg is None:
        raise ValueError(
            f"no executable (s, mu) candidates for family "
            f"{fam.name!r} (sampled axis size "
            f"{sampled_axis(fam, problem)}, "
            f"block_size={base_cfg.block_size})")
    return best_cfg
