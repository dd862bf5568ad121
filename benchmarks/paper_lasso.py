"""Paper Lasso artifacts: Fig. 2 (convergence vs iteration), Table III
(relative objective error), Fig. 3 (convergence vs modeled running time),
Fig. 4 / Table I (costs + strong-scaling speedups)."""
import os
import subprocess
import sys

import numpy as np

from benchmarks.common import emit, timeit
from repro.core import (LassoProblem, SolverConfig, acc_bcd_lasso,
                        bcd_lasso, sa_acc_bcd_lasso, sa_bcd_lasso)
from repro.core.cost_model import (Machine, PAPER_DATASETS, ProblemDims,
                                   best_s, lasso_costs, lasso_speedup,
                                   predicted_time)
from repro.data.sparse import SYNTHETIC_DATASETS, make_lasso_dataset

FIG2_DATASETS = ("news20-like", "covtype-like", "epsilon-like", "leu-like")
H = 384
S_BIG = 64           # paper uses s=1000; s=64 keeps CPU wall-time sane —
#                      the equivalence claim is s-independent (tests cover
#                      more values; f64 parity in test_sa_equivalence).


def _methods(mu):
    return [
        (f"CD(mu=1)", bcd_lasso, sa_bcd_lasso,
         SolverConfig(block_size=1, iterations=H, accelerated=False)),
        (f"accCD(mu=1)", acc_bcd_lasso, sa_acc_bcd_lasso,
         SolverConfig(block_size=1, iterations=H)),
        (f"BCD(mu={mu})", bcd_lasso, sa_bcd_lasso,
         SolverConfig(block_size=mu, iterations=H, accelerated=False)),
        (f"accBCD(mu={mu})", acc_bcd_lasso, sa_acc_bcd_lasso,
         SolverConfig(block_size=mu, iterations=H)),
    ]


def fig2_convergence():
    """Fig. 2: SA (s=S_BIG) vs classical trajectories per method/dataset;
    derived = final objective + max trajectory deviation."""
    import dataclasses
    for ds in FIG2_DATASETS:
        A, b, lam_max = make_lasso_dataset(ds, seed=0)
        prob = LassoProblem(A=A, b=b, lam=0.1 * lam_max)
        for name, base_fn, sa_fn, cfg in _methods(8):
            us, res = timeit(lambda: base_fn(prob, cfg), repeats=1)
            sa_cfg = dataclasses.replace(cfg, s=S_BIG)
            _, res_sa = timeit(lambda: sa_fn(prob, sa_cfg), repeats=1)
            o1 = np.asarray(res.objective)
            o2 = np.asarray(res_sa.objective)
            dev = float(np.max(np.abs(o1 - o2) / np.abs(o1)))
            emit(f"fig2/{ds}/{name}", us / H,
                 f"obj0={o1[0]:.4g};objH={o1[-1]:.4g};"
                 f"sa_traj_dev={dev:.2e};decreased={o1[-1] < o1[0]}")


def table3_relative_error():
    """Table III: |f_nonSA - f_SA| / f_nonSA at H, f32 in-process and f64
    in a subprocess (paper reports ~1e-16 in double precision)."""
    import dataclasses
    for ds in ("leu-like", "covtype-like", "news20-like"):
        A, b, lam_max = make_lasso_dataset(ds, seed=0)
        prob = LassoProblem(A=A, b=b, lam=0.1 * lam_max)
        for name, base_fn, sa_fn, cfg in _methods(8):
            r1 = base_fn(prob, cfg)
            r2 = sa_fn(prob, dataclasses.replace(cfg, s=S_BIG))
            rel = abs(float(r1.objective[-1]) - float(r2.objective[-1])) \
                / abs(float(r1.objective[-1]))
            emit(f"table3/{ds}/{name}", 0.0, f"rel_err_f32={rel:.3e}")
    # f64 parity (machine-epsilon scale, paper Table III)
    code = (
        "import jax; jax.config.update('jax_enable_x64', True)\n"
        "import numpy as np, jax.numpy as jnp, dataclasses\n"
        "from repro.core import LassoProblem, SolverConfig, "
        "acc_bcd_lasso, sa_acc_bcd_lasso\n"
        "from repro.data.sparse import make_lasso_dataset\n"
        "A, b, lm = make_lasso_dataset('leu-like', 0)\n"
        "p = LassoProblem(A=A, b=b, lam=0.1*lm)\n"
        "c = SolverConfig(block_size=8, iterations=128, dtype=jnp.float64)\n"
        "r1 = acc_bcd_lasso(p, c)\n"
        "r2 = sa_acc_bcd_lasso(p, dataclasses.replace(c, s=32))\n"
        "rel = abs(float(r1.objective[-1]) - float(r2.objective[-1])) "
        "/ abs(float(r1.objective[-1]))\n"
        "print(f'{rel:.3e}')\n")
    # f64 runs on the host: the child never claims an accelerator that
    # this process may hold.
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    rel = out.stdout.strip().splitlines()[-1] if out.returncode == 0 \
        else f"ERROR:{out.stderr[-200:]}"
    emit("table3/leu-like/accBCD-f64", 0.0, f"rel_err_f64={rel}")


def fig3_runtime():
    """Fig. 3: convergence vs modeled running time. Wall-clock measures
    the compute side on CPU; network time is modeled per collective
    (alpha-beta) for the Cray XC30 — SA trades s-fold fewer messages for
    s-fold larger ones, so modeled time favors SA exactly as Fig. 3."""
    machine = Machine.cray_xc30()
    P = 1024
    for ds in ("news20-like", "epsilon-like"):
        A, b, lam_max = make_lasso_dataset(ds, seed=0)
        prob = LassoProblem(A=A, b=b, lam=0.1 * lam_max)
        spec = SYNTHETIC_DATASETS[ds]
        dims = ProblemDims(m=spec.m, n=spec.n, f=spec.density)
        for s in (1, 16, S_BIG):
            cfg = SolverConfig(block_size=8, iterations=H, s=s)
            us, res = timeit(lambda: (sa_acc_bcd_lasso if s > 1
                                      else acc_bcd_lasso)(prob, cfg),
                             repeats=1)
            t_model = predicted_time(
                lasso_costs(dims, H, 8, s, P), machine)
            emit(f"fig3/{ds}/accBCD_s{s}", us / H,
                 f"objH={float(res.objective[-1]):.4g};"
                 f"modeled_time_s={t_model:.4f};"
                 f"modeled_speedup_vs_s1="
                 f"{lasso_speedup(dims, H, 8, s, P, machine):.2f}")


def table1_costs():
    """Table I: F/L/W/M for accBCD vs SA-accBCD (symbolic model
    evaluated); derived shows the s-scalings the paper derives."""
    dims = PAPER_DATASETS["news20"]
    for s in (1, 8, 64):
        c = lasso_costs(dims, H=1024, mu=8, s=s, P=1024)
        emit(f"table1/news20/s{s}", 0.0,
             f"F={c['F']:.3e};L={c['L']:.3e};W={c['W']:.3e};"
             f"M={c['M']:.3e}")
    c1 = lasso_costs(dims, 1024, 8, 1, 1024)
    c64 = lasso_costs(dims, 1024, 8, 64, 1024)
    emit("table1/news20/ratios", 0.0,
         f"L_ratio={c1['L'] / c64['L']:.1f}(=s);"
         f"W_ratio={c64['W'] / c1['W']:.1f}(=s)")


def fig4_scaling():
    """Fig. 4: strong scaling + speedup breakdown from the machine model
    at paper dataset dims (compute shrinks with P; latency term grows as
    log P -> SA's advantage grows with P, paper Fig. 4a-d)."""
    machine = Machine.cray_xc30()
    for ds in ("news20", "covtype", "url", "epsilon"):
        dims = PAPER_DATASETS[ds]
        for P in (192, 768, 3072, 12288):
            s_star, sp = best_s(dims, H=10_000, mu=1, P=P,
                                machine=machine)
            emit(f"fig4/{ds}/P{P}", 0.0,
                 f"best_s={s_star};speedup={sp:.2f}")


def main():
    fig2_convergence()
    table3_relative_error()
    fig3_runtime()
    table1_costs()
    fig4_scaling()


if __name__ == "__main__":
    main()
