"""Solver launcher: any registered problem family on synthetic datasets.

    PYTHONPATH=src python -m repro.launch.solve --problem lasso \
        --dataset news20-like --mu 8 --s 16 --iterations 512 --accelerated

``--problem`` enumerates the family registry (``repro.api.FAMILIES``):
lasso, svm, ksvm, logreg, and any family user code registers — each
family supplies its own problem construction (``make_problem``) and
result summary (``describe``), so a new family shows up here with zero
launcher edits.
"""
from __future__ import annotations

import argparse
import time

from repro import api
from repro.api import FAMILIES, KERNELS, SolverConfig
from repro.launch.compile_cache import enable_compile_cache


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--list-families", action="store_true",
                    help="print every registered problem family (variants, "
                         "sharded partition axis, autotuner grid) and exit")
    ap.add_argument("--problem", choices=sorted(FAMILIES), default="lasso")
    ap.add_argument("--dataset", default="news20-like")
    # default mu: per family (lasso 8, svm 1 = paper Alg. 3-4, ...); pass
    # --mu explicitly for the blocked variants.
    ap.add_argument("--mu", type=int, default=None)
    ap.add_argument("--s", type=int, default=16)
    ap.add_argument("--iterations", type=int, default=512)
    ap.add_argument("--accelerated", action="store_true")
    ap.add_argument("--lam-frac", type=float, default=0.1,
                    help="lasso: lambda as a fraction of ||A^T b||_inf")
    ap.add_argument("--svm-loss", choices=("l1", "l2"), default="l1")
    # kernel SVM (SA-K-BDCD): anything but "linear" routes through
    # repro.core.kernel_svm with the registered kernel block. The default
    # is per family (svm: linear; ksvm: rbf) — None means "unset", so an
    # explicit --kernel linear is honored by the ksvm family (the
    # kernelized linear path is a valid communication-cost choice).
    ap.add_argument("--kernel", choices=sorted(KERNELS), default=None)
    # every registered kernel hyperparameter becomes a --kernel-<name>
    # flag (type and default from KernelSpec.cli_params) and is forwarded
    # via types.build_kernel_params — nothing hardcoded, nothing dropped.
    seen = set()
    for spec in KERNELS.values():
        for pname, default in spec.cli_params.items():
            if pname in seen:
                continue
            seen.add(pname)
            ap.add_argument(f"--kernel-{pname}", type=type(default),
                            default=default,
                            help=f"{spec.name} kernel hyperparameter "
                                 f"(default {default})")
    ap.add_argument("--logreg-l2", type=float, default=1e-3,
                    help="logreg l2 regularization weight")
    # SolverConfig knobs previously unreachable from the CLI:
    ap.add_argument("--use-pallas", action="store_true",
                    help="route the fused Gram/inner-loop hot paths "
                         "through the Pallas TPU kernels")
    ap.add_argument("--symmetric-gram", action="store_true",
                    help="Allreduce only the Gram lower triangle "
                         "(paper footnote 3; SA Lasso/SVM)")
    ap.add_argument("--no-track-objective", dest="track_objective",
                    action="store_false",
                    help="skip the per-iteration objective trace")
    ap.add_argument("--power-iters", type=int, default=32,
                    help="power-method iterations for the block step size")
    ap.add_argument("--tune", action="store_true",
                    help="autotune s/mu/use_pallas/symmetric_gram with "
                         "the calibrated cost model (repro.tune) before "
                         "solving; --s/--mu become the incumbent the "
                         "tuner must beat")
    # elastic fault-tolerant execution (repro.runtime.solve_elastic):
    # --checkpoint-every switches from the plain local solve to the
    # sharded elastic driver with periodic outer-boundary checkpoints.
    ap.add_argument("--checkpoint-dir", default=None,
                    help="checkpoint directory for the elastic sharded "
                         "driver (implies --checkpoint-every 1 if that "
                         "flag is unset)")
    ap.add_argument("--checkpoint-every", type=int, default=None,
                    help="checkpoint every N OUTER iterations and run "
                         "through the elastic sharded driver (survives "
                         "injected host failures)")
    ap.add_argument("--inject-failure", action="append", default=[],
                    metavar="STEP:HOST",
                    help="kill HOST at inner iteration STEP (repeatable); "
                         "requires the elastic driver "
                         "(--checkpoint-every/--checkpoint-dir)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def _elastic_kwargs(args):
    """Parse the elastic CLI flags into solve_elastic kwargs, or None
    when the plain local path should run."""
    if (args.checkpoint_dir is None and args.checkpoint_every is None
            and not args.inject_failure):
        return None
    from repro.runtime import ElasticConfig, FailureInjector
    if args.checkpoint_dir is None:
        import tempfile
        args.checkpoint_dir = tempfile.mkdtemp(prefix="repro_elastic_")
    failures = {}
    for spec in args.inject_failure:
        step_s, host_s = spec.split(":")
        failures.setdefault(int(step_s), []).append(int(host_s))
    return {
        "elastic": ElasticConfig(
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every or 1),
        "injector": FailureInjector(failures=failures) if failures else None,
    }


def list_families() -> str:
    """One block per registered family, straight from the registry — a
    family added via ``register_family`` shows up with zero launcher
    edits (the same contract as ``--problem`` itself)."""
    lines = []
    for name in sorted(FAMILIES):
        fam = FAMILIES[name]
        variants = ", ".join(f"{k} -> {v}" if isinstance(v, str) else k
                             for k, v in sorted(fam.variants.items()))
        grid = ", ".join(f"{k}={list(v)}"
                         for k, v in sorted(fam.tune_space.items()))
        lines += [f"{name}  ({fam.problem_cls.__name__}, "
                  f"partition={fam.partition}, default_mu={fam.default_mu})",
                  f"    variants:   {variants}",
                  f"    tune_space: {grid or '(autotuner: family default)'}"]
    return "\n".join(lines)


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.list_families:
        print(list_families())
        return
    enable_compile_cache()
    family = FAMILIES[args.problem]
    if args.mu is None:
        args.mu = family.default_mu

    cfg = SolverConfig(block_size=args.mu,
                       s=args.s, iterations=args.iterations,
                       accelerated=args.accelerated,
                       power_iters=args.power_iters,
                       track_objective=args.track_objective,
                       symmetric_gram=args.symmetric_gram,
                       use_pallas=args.use_pallas,
                       seed=args.seed)
    t0 = time.perf_counter()
    problem = family.make_problem(args)
    if args.tune:
        from repro import tune
        tr = tune.tune(problem, cfg, family=family.name)
        cfg = tr.config
        print(f"tuned[{family.name}]: s={cfg.s} mu={cfg.block_size} "
              f"use_pallas={cfg.use_pallas} "
              f"symmetric_gram={cfg.symmetric_gram} "
              f"(model {tr.predicted_s:.3g}s vs incumbent "
              f"{tr.predicted_default_s:.3g}s"
              f"{', cached machine' if tr.from_cache else ''})")
        args.s, args.mu = cfg.s, cfg.block_size   # describe() reads these
    ekw = _elastic_kwargs(args)
    if ekw is None:
        res = api.solve(problem, cfg, family=family.name)
    else:
        from repro.runtime import solve_elastic
        res = solve_elastic(problem, cfg, family=family.name, **ekw)
        for ev in res.aux["elastic"]["events"]:
            print(f"elastic: {ev}")
    print(family.describe(args, res, time.perf_counter() - t0))


if __name__ == "__main__":
    main()
