"""The static contract analyzer (repro.analysis): registry-wide green
runs, seeded violations producing distinct diagnostics, lint rules, and
the CLI.

The seeded-violation tests build stub ProblemFamily instances whose
``solve`` deliberately breaks ONE contract (a second psum, a missing
psum before a replicated output, a hard-coded f32 cast) and assert the
matching pass — and only that pass — flags it.
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (CHECKS, Diagnostic, check_all,
                            check_collectives, check_dtypes,
                            check_registry, check_replication,
                            collective_budget, find_float_narrowing,
                            lint_source, shard_map_out_taints)
from repro.core.types import FAMILIES, LassoProblem, ProblemFamily, \
    SolverResult

pytestmark = pytest.mark.analysis


# ---------------------------------------------------------------------------
# stub families: each breaks exactly one contract
# ---------------------------------------------------------------------------

def _stub(solve, name="stub"):
    return ProblemFamily(
        name=name, problem_cls=LassoProblem, solve=solve,
        variants={"classical": ""}, partition="row", default_axes="data",
        bench_problem_kwargs={"lam": 0.1})


def _scan_solve(body_grad, length_attr="iterations"):
    def solve(problem, cfg, axis_name=None, x0=None):
        def body(c, _):
            return c - 0.01 * body_grad(problem, c, axis_name), 0.0
        x, obj = jax.lax.scan(body, jnp.zeros(problem.A.shape[1],
                                              problem.A.dtype),
                              None, length=getattr(cfg, length_attr))
        return SolverResult(x=x, objective=jnp.sum(obj))
    return solve


def _good_grad(problem, c, axis_name):
    return jax.lax.psum(problem.A.T @ (problem.A @ c - problem.b),
                        axis_name)


GOOD = _stub(_scan_solve(_good_grad), "stub_good")


def test_stub_good_is_clean():
    for check in (check_collectives, check_replication, check_dtypes):
        diags, checked = check(GOOD)
        assert checked == ["stub_good:classical"]
        assert not [d for d in diags if d.severity == "error"], \
            [d.format() for d in diags]


def test_seeded_second_psum_flags_collectives_only():
    def grad(problem, c, axis_name):
        g = _good_grad(problem, c, axis_name)
        return g + jax.lax.psum(jnp.sum(g), axis_name)   # the 2nd psum
    fam = _stub(_scan_solve(grad), "stub_two_psum")
    errs = [d for d in check_collectives(fam)[0] if d.severity == "error"]
    assert len(errs) == 1 and errs[0].check == "collectives"
    assert "found 2" in errs[0].message
    # the extra psum keeps everything replicated: replication stays green
    assert not check_replication(fam)[0]


def test_seeded_shard_divergent_replicated_output():
    def grad(problem, c, axis_name):
        return problem.A.T @ (problem.A @ c - problem.b)  # never psum'd
    fam = _stub(_scan_solve(grad), "stub_divergent")
    errs = [d for d in check_replication(fam)[0] if d.severity == "error"]
    assert errs and all(d.check == "replication" for d in errs)
    assert any("'x'" in d.message and "data" in d.message for d in errs)


def test_seeded_f64_downcast_flags_dtypes_only():
    def solve(problem, cfg, axis_name=None, x0=None):
        A32 = problem.A.astype(jnp.float32)              # silent narrow
        def body(c, _):
            g = jax.lax.psum(A32.T @ (A32 @ c), axis_name)
            return c - 0.01 * g.astype(problem.A.dtype), 0.0
        x, obj = jax.lax.scan(body, jnp.zeros(problem.A.shape[1],
                                              problem.A.dtype),
                              None, length=cfg.iterations)
        return SolverResult(x=x, objective=jnp.sum(obj))
    fam = _stub(solve, "stub_downcast")
    errs = [d for d in check_dtypes(fam)[0] if d.severity == "error"]
    assert errs and all(d.check == "dtypes" for d in errs)
    assert "float64 -> float32" in errs[0].message
    # the cast is shard-uniform and the psum is intact: the other two
    # passes stay green (distinct diagnostics per seeded violation).
    assert not check_replication(fam)[0]
    assert not [d for d in check_collectives(fam)[0]
                if d.severity == "error"]


# ---------------------------------------------------------------------------
# jaxpr walkers, directly
# ---------------------------------------------------------------------------

def test_collective_budget_splits_loop_vs_amortized():
    def f(x):
        def body(c, _):
            return jax.lax.psum(c, "i"), None
        out, _ = jax.lax.scan(body, x, None, length=4)
        return jax.lax.psum(out, "i")                    # tail/amortized

    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("i",))
    fn = shard_map(f, mesh=mesh, in_specs=(P(),), out_specs=P(),
                   check_vma=False)
    budget = collective_budget(
        jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((8,), jnp.float32)))
    assert budget.per_iteration["all-reduce"] == 1
    assert budget.amortized["all-reduce"] == 1
    assert budget.per_iteration_bytes == 8 * 4
    assert budget.total["all-reduce"] == 2


def test_taint_axis_index_and_while_predicate():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("i",))

    def f(x):
        div = jnp.float32(jax.lax.axis_index("i"))       # shard-varying

        def cond(c):
            return jnp.sum(c) + div < 10.0               # tainted pred

        def body(c):
            return c + 1.0

        looped = jax.lax.while_loop(cond, body, jnp.zeros(()))
        return jax.lax.psum(x, "i"), looped

    fn = shard_map(f, mesh=mesh, in_specs=(P("i"),), out_specs=(P(), P()),
                   check_vma=False)
    outs, _ = shard_map_out_taints(
        jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((8,), jnp.float32)))
    assert outs[0] == frozenset()          # psum'd: replicated
    assert outs[1] == frozenset({"i"})     # trip count may diverge


def test_find_float_narrowing_reports_site():
    with jax.enable_x64(True):
        j = jax.make_jaxpr(lambda x: (x * 2.0).astype(jnp.float32))(
            jax.ShapeDtypeStruct((4,), jnp.float64))
    hits = find_float_narrowing(j)
    assert hits and hits[0][:2] == ("float64", "float32")


# ---------------------------------------------------------------------------
# lint rules
# ---------------------------------------------------------------------------

def _rules(diags):
    return sorted({d.message.split("]")[0].lstrip("[") for d in diags})


def test_lint_raw_collective_outside_allowlist():
    src = "import jax\n\ndef f(x):\n    return jax.lax.psum(x, 'i')\n"
    assert _rules(lint_source(src, "core/sa_new.py")) == ["raw-collective"]
    assert not lint_source(src, "optim/compress.py")
    assert not lint_source(src, "core/linalg.py")


def test_lint_raw_collective_from_import():
    src = "from jax.lax import psum\n"
    assert _rules(lint_source(src, "core/x.py")) == ["raw-collective"]


def test_lint_ambient_rng():
    assert _rules(lint_source("import random\n", "core/x.py")) == \
        ["ambient-rng"]
    assert _rules(lint_source(
        "import numpy as np\nnp.random.seed(0)\n", "data/x.py")) == \
        ["ambient-rng"]   # global state: not allowed even in data/
    gen = "import numpy as np\nr = np.random.default_rng(0)\n"
    assert _rules(lint_source(gen, "core/x.py")) == ["ambient-rng"]
    assert not lint_source(gen, "data/x.py")
    assert not lint_source(gen, "tune/microbench.py")
    assert not lint_source("import jax\nk = jax.random.key(0)\n",
                           "core/x.py")


def test_lint_bare_assert():
    assert _rules(lint_source("def f(x):\n    assert x > 0\n",
                              "core/x.py")) == ["bare-assert"]
    assert not lint_source(
        "def f(x):\n    if x <= 0:\n        raise ValueError('x')\n",
        "core/x.py")


def test_diagnostic_rejects_unknown_severity():
    with pytest.raises(ValueError, match="severity"):
        Diagnostic("lint", "fatal", "x", "y")


# ---------------------------------------------------------------------------
# registry-wide runs + CLI
# ---------------------------------------------------------------------------

def test_registry_contract_covers_all_programs():
    diags, checked = check_registry()
    # every family with engine-backed variants exposes its program(s)
    assert len(checked) >= len(FAMILIES)
    assert not diags, [d.format() for d in diags]


def test_check_all_full_registry_green():
    report = check_all()
    assert report.ok, report.format()
    combos = sum(len(f.variants) for f in FAMILIES.values())
    for check in ("collectives", "replication", "dtypes", "costs"):
        assert sum(c.startswith(f"{check}:") for c in report.checked) \
            == combos
    from repro.kernels import KERNEL_PACKAGES
    assert sum(c.startswith("kernels:") for c in report.checked) \
        == len(KERNEL_PACKAGES)
    assert any(c.startswith("lint:") for c in report.checked)
    assert any(c.startswith("registry:") for c in report.checked)
    # the bytes-per-outer measurements ride along as info diagnostics
    assert sum(d.severity == "info" and d.check == "collectives"
               for d in report.diagnostics) == combos
    # ...as do the per-variant certified cost ratios
    assert sum(d.severity == "info" and d.check == "costs"
               for d in report.diagnostics) == combos


def test_check_all_validates_selection():
    with pytest.raises(ValueError, match="unknown checks"):
        check_all(checks=("nope",))
    with pytest.raises(ValueError, match="unknown family"):
        check_all(checks=("lint",), families=("nope",))
    with pytest.raises(ValueError, match="registered by no selected"):
        check_all(checks=("collectives",), families=("lasso",),
                  variants=("nope",))
    assert set(CHECKS) == {"collectives", "replication", "dtypes",
                           "costs", "kernels", "lint", "registry"}


def test_check_all_variant_filter():
    report = check_all(checks=("collectives",), families=("lasso",),
                       variants=("sa",))
    assert report.checked == ["collectives:lasso:sa"]
    assert report.ok, report.format()


def test_cli_lint_and_registry():
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--checks", "lint",
         "registry"], capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 error(s)" in out.stdout


def test_sa_lint_cli_clean():
    import pathlib
    root = pathlib.Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, str(root / "tools" / "sa_lint.py")],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 finding(s)" in out.stdout


# ---------------------------------------------------------------------------
# cost certifier: seeded hooks, each firing exactly one diagnostic
# ---------------------------------------------------------------------------

import dataclasses
import json

from repro.analysis import (check_costs, check_kernels,
                            guard_drift_diags, index_map_bounds_diags,
                            output_injectivity_diags, solver_cost_count,
                            variant_config)
from repro.analysis.costs import CERT_SHAPES, CostTolerance, cost_ratio_rows
from repro.analysis.kernels import KernelCapture, SpecView

# wide bands isolate the s-scaling drift check: band violations cannot
# fire, so a drifting ratio is the ONLY possible error.
WIDE = CostTolerance(f_band=(1e-3, 1e3), w_band=(1e-3, 1e3))


def _sa_stub(solve, name):
    return ProblemFamily(
        name=name, problem_cls=LassoProblem, solve=solve,
        variants={"sa": ""}, partition="row", default_axes="data",
        bench_problem_kwargs={"lam": 0.1})


def _outer_scan_solve(problem, cfg, axis_name=None, x0=None):
    # an honest s-stepper for counting purposes: ONE psum'd gradient
    # per OUTER iteration, so flops/words/messages all fall as 1/s.
    def body(c, _):
        return c - 0.01 * _good_grad(problem, c, axis_name), 0.0
    x, obj = jax.lax.scan(body, jnp.zeros(problem.A.shape[1],
                                          problem.A.dtype),
                          None, length=cfg.outer_iterations)
    return SolverResult(x=x, objective=jnp.sum(obj))


def _counted(fam, variant):
    m, n = CERT_SHAPES[fam.partition]
    cfg = variant_config(fam, variant, iterations=48, s=1)
    return solver_cost_count(fam, cfg, m=m, n=n)


def test_cost_certifier_green_on_matching_hook():
    fam0 = _sa_stub(_outer_scan_solve, "stub_cost_good")
    base = _counted(fam0, "sa")

    def costs(dims, H, mu, s, P, kernel="linear"):
        outer = -(-H // s)
        return {"F": base.flops * outer / 48.0,
                "W": base.words * outer / 48.0, "L": outer, "M": dims.n}

    diags, checked = check_costs(dataclasses.replace(fam0, costs=costs),
                                 sparse=False, tolerance=CostTolerance())
    assert checked == ["stub_cost_good:sa"]
    assert not [d for d in diags if d.severity == "error"], \
        [d.format() for d in diags]


def test_cost_mismatch_fires_f_band_alone():
    fam0 = _stub(_scan_solve(_good_grad), "stub_cost_off")
    base = _counted(fam0, "classical")

    def costs(dims, H, mu, s, P, kernel="linear"):
        return {"F": base.flops * 20.0, "W": base.words, "L": H,
                "M": dims.n}                  # F off by a constant 20x

    errs = [d for d in check_costs(dataclasses.replace(fam0, costs=costs),
                                   sparse=False,
                                   tolerance=CostTolerance())[0]
            if d.severity == "error"]
    assert len(errs) == 1, [d.format() for d in errs]
    assert errs[0].check == "costs"
    assert "term F" in errs[0].message and "band" in errs[0].message


def test_wrong_s_exponent_fires_scaling_alone():
    fam0 = _sa_stub(_outer_scan_solve, "stub_cost_sexp")
    base = _counted(fam0, "sa")

    def costs(dims, H, mu, s, P, kernel="linear"):
        outer = -(-H // s)
        return {"F": base.flops,              # misses the 1/s factor
                "W": base.words * outer / 48.0, "L": outer, "M": dims.n}

    errs = [d for d in check_costs(dataclasses.replace(fam0, costs=costs),
                                   sparse=False, tolerance=WIDE)[0]
            if d.severity == "error"]
    assert len(errs) == 1, [d.format() for d in errs]
    assert "term F s-scaling" in errs[0].message
    assert "wrong s exponent" in errs[0].message


def test_ignored_s_fires_latency_alone():
    # the solve issues one message per INNER iteration (it ignores s):
    # counted flops/words still match a constant model, so the latency
    # term is the only violated contract.
    fam0 = _sa_stub(_scan_solve(_good_grad), "stub_cost_lat")
    base = _counted(fam0, "sa")

    def costs(dims, H, mu, s, P, kernel="linear"):
        return {"F": base.flops, "W": base.words, "L": H, "M": dims.n}

    errs = [d for d in check_costs(dataclasses.replace(fam0, costs=costs),
                                   sparse=False, tolerance=WIDE)[0]
            if d.severity == "error"]
    assert len(errs) == 1, [d.format() for d in errs]
    assert "term L" in errs[0].message
    assert "ceil(H/s)" in errs[0].message


def test_sparse_certification_counts_nnz_not_mn():
    # the SparseOperand traces of the real SA solvers must cost O(nnz):
    # at 8% density the sparse flop count sits well below both the
    # density x dense bound and the dense count itself.
    for name in ("lasso", "logreg"):
        rows = cost_ratio_rows(FAMILIES[name], variants=("sa",),
                               s_grid=(1, 4))
        assert rows
        for row in rows:
            assert row.sparse_ratio is not None
            assert row.sparse_ratio <= 1.0, \
                (name, row.s, row.sparse_ratio)
            assert row.sparse_flops < 0.25 * row.flops


def test_select_config_refuses_uncertified_costs():
    import numpy as np
    from repro.core.cost_model import Machine
    from repro.core.types import SolverConfig
    from repro.tune.select import select_config

    A = np.arange(64 * 32, dtype=np.float32).reshape(64, 32) % 7 - 3.0
    prob = LassoProblem(A=jnp.asarray(A), b=jnp.ones(64, jnp.float32),
                        lam=0.1)
    cfg = SolverConfig(block_size=4, iterations=16)
    bad = dataclasses.replace(
        FAMILIES["lasso"],
        costs=lambda dims, H, mu, s, P, kernel="linear":
        {"F": 1.0, "W": 1.0, "L": 1.0, "M": 1.0})
    with pytest.raises(ValueError, match="uncertified cost model"):
        select_config(prob, Machine.cray_xc30(), cfg, family=bad,
                      certified=True)
    tuned = select_config(prob, Machine.cray_xc30(), cfg,
                          family=FAMILIES["lasso"], certified=True)
    assert tuned.s >= 1


# ---------------------------------------------------------------------------
# kernel safety pass: seeded captures, each firing exactly one diagnostic
# ---------------------------------------------------------------------------

def test_guard_drift_fires_on_understating_model():
    assert not guard_drift_diags("k", 1000.0, 1100.0, 8.0e6)  # in slack
    errs = guard_drift_diags("k", 1000.0, 2000.0, 8.0e6)
    assert len(errs) == 1 and errs[0].check == "kernels"
    assert "guard drift" in errs[0].message


def test_write_race_fires_alone():
    cap = KernelCapture(
        name="stub", grid=(2, 2), inputs=(),
        outputs=(SpecView("out0", (2, 2), jnp.float32, (1, 1),
                          lambda i, j: (0, 0)),),
        scratch=(), semantics=("parallel", "parallel"))
    errs = output_injectivity_diags("stub", cap)
    assert len(errs) == 1 and "write race" in errs[0].message
    assert not index_map_bounds_diags("stub", cap)
    # the SAME revisit across "arbitrary" (sequential) dimensions is the
    # legal accumulation pattern — and the TPU default when no
    # dimension_semantics are declared.
    assert not output_injectivity_diags(
        "stub", dataclasses.replace(cap, semantics=None))


def test_oob_index_map_fires_alone():
    cap = KernelCapture(
        name="stub", grid=(2, 2), inputs=(),
        outputs=(SpecView("out0", (2, 2), jnp.float32, (1, 1),
                          lambda i, j: (i + 1, j)),),
        scratch=(), semantics=("parallel", "parallel"))
    errs = index_map_bounds_diags("stub", cap)
    assert len(errs) == 1 and "out of bounds" in errs[0].message
    assert not output_injectivity_diags("stub", cap)


def test_kernel_safety_pass_green_over_all_packages():
    from repro.kernels import KERNEL_PACKAGES
    diags, checked = check_kernels()
    assert checked == list(KERNEL_PACKAGES)
    assert not [d for d in diags if d.severity == "error"], \
        [d.format() for d in diags if d.severity == "error"]
    infos = {d.where.split("[")[0] for d in diags if d.severity == "info"}
    assert set(KERNEL_PACKAGES) <= infos


# ---------------------------------------------------------------------------
# replication taint: cond nested inside scan carries
# ---------------------------------------------------------------------------

def _scan_cond_taints(use_tainted_branch):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("i",))

    def f(x):
        div = jnp.float32(jax.lax.axis_index("i"))       # shard-varying

        def body(c, _):
            c2 = jax.lax.cond(jnp.sum(c) < 10.0,
                              (lambda: c + div) if use_tainted_branch
                              else (lambda: c + 1.0),
                              lambda: c)
            return c2, None

        out, _ = jax.lax.scan(body, jnp.zeros(4, jnp.float32), None,
                              length=3)
        return jax.lax.psum(x, "i"), out

    fn = shard_map(f, mesh=mesh, in_specs=(P("i"),),
                   out_specs=(P(), P()), check_vma=False)
    outs, _ = shard_map_out_taints(
        jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((8,), jnp.float32)))
    return outs


def test_taint_cond_branch_inside_scan_carry():
    outs = _scan_cond_taints(use_tainted_branch=True)
    assert outs[0] == frozenset()          # psum'd: replicated
    assert outs[1] == frozenset({"i"})     # tainted branch joins carry


def test_clean_cond_inside_scan_stays_untainted():
    outs = _scan_cond_taints(use_tainted_branch=False)
    assert outs[1] == frozenset()


def test_taint_cond_predicate_inside_scan_carry():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("i",))

    def f(x):
        div = jnp.float32(jax.lax.axis_index("i"))

        def body(c, _):
            # both branches are shard-uniform; the PREDICATE diverges,
            # so which one ran (and hence the carry) is shard-varying.
            c2 = jax.lax.cond(div < 1.0, lambda: c + 1.0, lambda: c)
            return c2, None

        out, _ = jax.lax.scan(body, jnp.zeros(4, jnp.float32), None,
                              length=3)
        return jax.lax.psum(x, "i"), out

    fn = shard_map(f, mesh=mesh, in_specs=(P("i"),),
                   out_specs=(P(), P()), check_vma=False)
    outs, _ = shard_map_out_taints(
        jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((8,), jnp.float32)))
    assert outs[1] == frozenset({"i"})


def test_cli_json_report():
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--checks", "lint",
         "registry", "--json"], capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    data = json.loads(out.stdout)
    assert data["ok"] is True and data["errors"] == 0
    assert any(c.startswith("lint:") for c in data["checked"])
    assert all({"check", "severity", "where", "message"}
               <= set(d) for d in data["diagnostics"])
