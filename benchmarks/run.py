"""Benchmark entry point — one section per paper table/figure plus the
roofline/dry-run report. Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run [--only fig2,table5,...]
                                           [--smoke]

``--smoke`` forwards ``smoke=True`` to every selected section that
accepts it (density, tuned) — the CI-sized budgets.
"""
import argparse
import inspect
import sys
import traceback

from benchmarks.common import header

SECTIONS = {}


def _register():
    from benchmarks import paper_lasso, paper_svm, certify, \
        collective_count, density_sweep, recovery, roofline_bench, \
        tuned_vs_default
    SECTIONS.update({
        "certify": certify.main,
        "density": density_sweep.main,
        "tuned": tuned_vs_default.main,
        "recovery": recovery.main,
        "fig2": paper_lasso.fig2_convergence,
        "table3": paper_lasso.table3_relative_error,
        "fig3": paper_lasso.fig3_runtime,
        "table1": paper_lasso.table1_costs,
        "fig4": paper_lasso.fig4_scaling,
        "fig5": paper_svm.fig5_duality_gap,
        "table5": paper_svm.table5_speedups,
        "blocked_svm": paper_svm.blocked_smu_sweep,
        "blocked_svm_model": paper_svm.blocked_model_speedups,
        "kernel_svm": paper_svm.kernel_smu_sweep,
        "kernel_svm_model": paper_svm.kernel_model_speedups,
        "collectives": collective_count.main,
        "roofline": roofline_bench.main,
    })


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated section names")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized budgets for sections that support it")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    _register()
    names = args.only.split(",") if args.only else list(SECTIONS)
    header()
    failures = 0
    for name in names:
        try:
            fn = SECTIONS[name]
            if args.smoke and \
                    "smoke" in inspect.signature(fn).parameters:
                fn(smoke=True)
            else:
                fn()
        except Exception:
            failures += 1
            print(f"{name},0.00,SECTION_ERROR", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
