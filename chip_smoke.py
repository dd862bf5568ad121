#!/usr/bin/env python3
"""Smoke run of the SA solvers on one TPU chip, through ``repro.api``.

    python3 chip_smoke.py [--seed N]          # one chip: phases (a)-(d)
    python3 chip_smoke.py --chips 4 [--seed N]  # the sharded phase only

Phases, on data drawn from ``--seed`` (never downloaded):

  (a) dense Lasso at LIBSVM epsilon's shape (400,000 x 2,000 f32):
      SA-BCD (s=16, mu=8, H=512, Pallas kernels) against classical BCD;
  (b) blocked L1-SVM at the same shape: SA-BDCD against BDCD;
  (c) sparse L1-SVM at LIBSVM rcv1.binary's shape (20,242 x 47,236,
      about 1.5M nonzeros): SA-BDCD against BDCD;
  (d) kernel parity: each of the four Pallas kernels at one shape
      inside its VMEM guard against its jnp reference.

Each phase prints one JSON line: set-up (lower + compile) and warm solve
seconds, the objective after the first and the last iteration, the
SA-vs-classical deviation with its tolerance, the implementation each
kernel seam ran ("pallas", or "ref" where a VMEM guard sent it to the
jnp reference), and whether the compiled HLO holds a Pallas kernel
(``tpu_custom_call``). A seam labelled "pallas" with no kernel in the
HLO fails the phase. The classical reference runs under
``jax.default_matmul_precision("highest")``; the SA solves run at the
default precision, as users run them.

``--chips 4`` runs only ``solve_sharded`` on a 4-device mesh (Lasso
with A's rows sharded, SVM with A's columns sharded — each family's own
layout) at s=1 and s=16, and the same solves on one device; it prints
their deviation and the all-reduces per outer iteration read from the
compiled HLO.

The last line of standard output is ``{"ok": true, "device": {...}}``.
With no TPU the script exits non-zero before any phase; a failed phase
raises or exits non-zero, and the last line is then not printed.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro import api  # noqa: E402
from repro.api import LassoProblem, SolverConfig, SVMProblem  # noqa: E402
from repro.core.types import FAMILIES, SparseOperand  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.kernels.gram import gram_t  # noqa: E402
from repro.kernels.gram.ref import gram_t_ref  # noqa: E402
from repro.kernels.sa_inner import sa_inner_loop  # noqa: E402
from repro.kernels.sa_inner.ref import sa_inner_ref  # noqa: E402
from repro.kernels.spmm import ell_spmm  # noqa: E402
from repro.kernels.spmm.ref import ell_spmm_ref  # noqa: E402
from repro.kernels.svm_inner import svm_inner_loop  # noqa: E402
from repro.kernels.svm_inner.ref import svm_inner_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

EPSILON = (400_000, 2_000)          # LIBSVM epsilon: dense
RCV1 = (20_242, 47_236, 1_500_000)  # LIBSVM rcv1.binary: (m, n, ~nnz)
H, S, MU = 512, 16, 8

# SA at the default matmul precision (one bf16 pass on the MXU, unit
# roundoff 2^-8) against the classical solver in f32: the trajectories
# agree to a few roundoffs, normalised by the trajectory's scale.
SA_TOL = 1e-2
# The same algorithm sharded over 4 devices against one device, both
# at the default precision: the order of the f32 sums differs, and the
# single bf16 pass can round the two sides' operands apart.
SHARD_TOL = 1e-3
# Kernel against its jnp reference, both at "highest": f32 summation
# order only (the interpret-mode test sweeps' tolerances).
PARITY_RTOL = 1e-4


def _highest():
    return jax.default_matmul_precision("highest")


def _deviation(got, ref):
    """max |got - ref| over the trajectory, over max |ref|."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def timed_solve(problem, cfg, precision=None):
    """Compile and run ``repro.api.solve(problem, cfg)`` as one jitted
    program: (set-up s, warm solve s, x, objective, impl labels, whether
    the compiled HLO holds a Pallas kernel)."""
    labels = {}

    def run(A, b):
        res = api.solve(dataclasses.replace(problem, A=A, b=b), cfg)
        labels.update({k[:-5]: v for k, v in res.aux.items()
                       if k.endswith("_impl")})
        return res.x, res.objective

    ctx = contextlib.nullcontext() if precision is None \
        else jax.default_matmul_precision(precision)
    with ctx:
        t0 = time.perf_counter()
        compiled = jax.jit(run).lower(problem.A, problem.b).compile()
        setup_s = time.perf_counter() - t0
    jax.block_until_ready(compiled(problem.A, problem.b))
    t0 = time.perf_counter()
    x, obj = jax.block_until_ready(compiled(problem.A, problem.b))
    solve_s = time.perf_counter() - t0
    return dict(setup_s=setup_s, solve_s=solve_s, x=x, objective=obj,
                labels=labels,
                kernel_in_hlo="tpu_custom_call" in compiled.as_text())


def sa_vs_classical(name, problem, cfg, shape):
    """One solver phase: the SA solve (cfg) against classical s=1."""
    sa = timed_solve(problem, cfg)
    ref = timed_solve(problem, dataclasses.replace(cfg, s=1,
                                                   use_pallas=False),
                      precision="highest")
    labels = dict(sa["labels"])
    if not isinstance(problem.A, SparseOperand):
        labels["gram"] = "pallas" if cfg.use_pallas else "ref"
    obj = np.asarray(sa["objective"])
    return dict(
        phase=name, shape=list(shape), s=cfg.s, mu=cfg.block_size,
        iterations=cfg.iterations,
        setup_s={"sa": sa["setup_s"], "classical": ref["setup_s"]},
        solve_s={"sa": sa["solve_s"], "classical": ref["solve_s"]},
        objective={"first": float(obj[0]), "last": float(obj[-1])},
        deviation=_deviation(obj, ref["objective"]), tolerance=SA_TOL,
        impl=labels, tpu_custom_call=sa["kernel_in_hlo"],
        finite=bool(np.all(np.isfinite(obj))))


# ---------------------------------------------------------------------------
# Data, drawn on the device from the seed.
# ---------------------------------------------------------------------------

def dense_lasso(m, n, seed):
    """Unit-norm rows, b = A x* + noise with a 32-sparse x*, and
    lam = 0.1 * ||A^T b||_inf."""
    @jax.jit
    def draw(key):
        kA, ki, kx, ke = jax.random.split(key, 4)
        A = jax.random.normal(kA, (m, n), jnp.float32) / jnp.sqrt(
            jnp.float32(n))
        support = jax.random.choice(ki, n, (min(32, n),), replace=False)
        xs = jnp.zeros((n,), jnp.float32).at[support].set(
            jax.random.normal(kx, support.shape))
        b = A @ xs + 0.01 * jax.random.normal(ke, (m,))
        return A, b, 0.1 * jnp.max(jnp.abs(A.T @ b))

    with _highest():
        A, b, lam = draw(jax.random.key(seed))
    return LassoProblem(A=A, b=b, lam=float(lam))


def dense_svm(m, n, seed):
    """Unit-norm rows, labels b = sign(A w + noise), hinge loss."""
    @jax.jit
    def draw(key):
        kA, kw, ke = jax.random.split(key, 3)
        A = jax.random.normal(kA, (m, n), jnp.float32) / jnp.sqrt(
            jnp.float32(n))
        y = A @ jax.random.normal(kw, (n,)) + 0.1 * jax.random.normal(
            ke, (m,))
        return A, jnp.where(y >= 0, 1.0, -1.0).astype(jnp.float32)

    with _highest():
        A, b = draw(jax.random.key(seed))
    return SVMProblem(A=A, b=b, lam=1.0, loss="l1")


def sparse_svm(m, n, nnz, seed):
    """About ``nnz`` uniformly placed nonzeros with unit-norm rows (as
    rcv1's tf-idf rows), drawn on the device as COO and laid out through
    ``SparseOperand.from_coo``; labels b = sign(A w)."""
    kk, kv, kw = jax.random.split(jax.random.key(seed), 3)
    keys = np.unique(np.asarray(jax.random.randint(kk, (nnz,), 0, m * n)))
    rows, cols = keys // n, keys % n
    vals = np.asarray(jax.random.uniform(kv, (keys.size,), jnp.float32,
                                         0.05, 1.0))
    norms = np.sqrt(np.bincount(rows, vals * vals, minlength=m))
    vals = (vals / np.maximum(norms, 1e-12)[rows]).astype(np.float32)
    A = SparseOperand.from_coo(rows, cols, vals, (m, n))
    y = A.matvec(jax.random.normal(kw, (n,)))
    b = jnp.where(y >= 0, 1.0, -1.0).astype(jnp.float32)
    return SVMProblem(A=A, b=b, lam=1.0, loss="l1")


def _cfg(**kw):
    base = dict(block_size=MU, s=S, iterations=H, accelerated=False,
                use_pallas=True)
    base.update(kw)
    return SolverConfig(**base)


# ---------------------------------------------------------------------------
# The phases: plain functions of a size.
# ---------------------------------------------------------------------------

def phase_dense_lasso(m=EPSILON[0], n=EPSILON[1], seed=0, **cfg):
    return sa_vs_classical("a_dense_lasso", dense_lasso(m, n, seed),
                           _cfg(**cfg), (m, n))


def phase_dense_svm(m=EPSILON[0], n=EPSILON[1], seed=0, **cfg):
    return sa_vs_classical("b_dense_svm", dense_svm(m, n, seed + 1),
                           _cfg(**cfg), (m, n))


def phase_sparse_svm(m=RCV1[0], n=RCV1[1], nnz=RCV1[2], seed=0, **cfg):
    problem = sparse_svm(m, n, nnz, seed + 2)
    out = sa_vs_classical("c_sparse_svm", problem, _cfg(**cfg), (m, n))
    out["nnz"] = problem.A.nnz
    if out["impl"].get("spmm") == "ref":
        cap = dispatch.kernel_vmem_model()["spmm"].cap
        out["note"] = (f"the SpMM VMEM guard (cap {cap} B) "
                       f"sent the sparse Gram product to the jnp "
                       f"reference; phase d runs that kernel")
    return out


def phase_kernels(seed=0, gram_m=8_192, spmm_shape=(128, 4_096, 129),
                  spmm_row_nnz=74):
    """Each Pallas kernel against its jnp reference, both traced under
    "highest" (the kernels' in-kernel dots then contract in f32)."""
    keys = iter(jax.random.split(jax.random.key(seed + 3), 16))
    normal = lambda shape: jax.random.normal(next(keys), shape)  # noqa
    smu = S * MU

    R, C, Q = spmm_shape
    flat = np.unique(np.asarray(jax.random.randint(
        next(keys), (R * spmm_row_nnz,), 0, R * C)))
    op = SparseOperand.from_coo(flat // C, flat % C,
                                np.asarray(normal((flat.size,))), (R, C))
    D = normal((C, Q))
    K = op.row_cols.shape[1]

    Y = normal((gram_m, smu))
    V = normal((gram_m, 1))
    with _highest():
        G0 = Y.T @ Y
    idx = jax.random.randint(next(keys), (S, MU), 0, 96)   # collisions
    th = jnp.linspace(0.5, 0.1, S)
    coefU = (1.0 - 16.0 * th) / (th * th)
    yp, zp, zv = normal((S, MU)), normal((S, MU)), 0.1 * normal((S, MU))
    b = jnp.where(normal((S, MU)) >= 0, 1.0, -1.0)
    a_vals = 0.2 * jax.random.uniform(next(keys), (S, MU))
    Gs = G0 / gram_m + 0.5 * jnp.eye(smu)

    cases = {
        "gram": (lambda p: gram_t(Y, jnp.concatenate([Y, V], 1),
                                  use_pallas=p),
                 lambda: gram_t_ref(Y, jnp.concatenate([Y, V], 1)),
                 2e-4 * gram_m ** 0.5),
        "spmm": (lambda p: ell_spmm(op.row_vals, op.row_cols,
                                    op.row_blocks, D, use_pallas=p),
                 lambda: ell_spmm_ref(op.row_vals, op.row_cols, D), 1e-4),
        "sa_inner": (lambda p: sa_inner_loop(G0, yp, zp, zv, idx, th, coefU,
                                             q=16.0, lam1=0.3,
                                             use_pallas=p),
                     lambda: sa_inner_ref(G0, yp, zp, zv, idx, th, coefU,
                                          16.0, 0.3), 1e-5),
        "svm_inner": (lambda p: svm_inner_loop(Gs, yp, b, a_vals, idx,
                                               gamma=0.3, nu=1.0,
                                               use_pallas=p),
                      lambda: svm_inner_ref(Gs, yp, b, a_vals, idx, 0.3,
                                            1.0), 1e-4),
    }
    guards = {"gram": True,
              "spmm": dispatch.spmm_vmem_ok(R, K, C, Q),
              "sa_inner": dispatch.vmem_ok(S, MU),
              "svm_inner": dispatch.vmem_ok(S, MU)}
    kernels = {}
    for name, (kernel, ref, atol) in cases.items():
        with _highest():
            compiled = jax.jit(lambda: kernel(True)).lower().compile()
            want = jax.tree.leaves(jax.jit(ref)())
        got = jax.tree.leaves(compiled())
        err = max(float(jnp.max(jnp.abs(g - w) - PARITY_RTOL * jnp.abs(w)))
                  for g, w in zip(got, want))
        kernels[name] = dict(
            excess_over_rtol=err, atol=atol, inside_guard=guards[name],
            tpu_custom_call="tpu_custom_call" in compiled.as_text())
    return dict(phase="d_kernel_parity", rtol=PARITY_RTOL,
                shapes={"gram": [gram_m, smu, smu + 1],
                        "spmm": [R, K, C, Q], "inner": [S, MU]},
                kernels=kernels)


def phase_failures(result, on_chip: bool):
    """Why a phase result fails, as a list of messages (empty: passed).
    On the chip, a seam labelled "pallas" must show a kernel in the
    compiled HLO."""
    errs = []
    if "kernels" in result:
        for name, k in result["kernels"].items():
            if not k["inside_guard"]:
                errs.append(f"{name}: parity shape is outside its guard")
            if k["excess_over_rtol"] > k["atol"]:
                errs.append(f"{name}: kernel and reference disagree by "
                            f"{k['excess_over_rtol']} beyond rtol "
                            f"(atol {k['atol']})")
            if on_chip and not k["tpu_custom_call"]:
                errs.append(f"{name}: no tpu_custom_call in the HLO")
        return errs
    if not result["finite"]:
        errs.append("non-finite objective")
    if not result["deviation"] <= result["tolerance"]:
        errs.append(f"SA-vs-classical deviation {result['deviation']} "
                    f"exceeds {result['tolerance']}")
    pallas = [k for k, v in result["impl"].items() if "pallas" in v]
    if on_chip and pallas and not result["tpu_custom_call"]:
        errs.append(f"{pallas} labelled pallas but no tpu_custom_call "
                    f"in the compiled HLO")
    return errs


# ---------------------------------------------------------------------------
# Four chips: the sharded path and the one-device solves it must match.
# ---------------------------------------------------------------------------

def allreduces_per_outer(hlo: str) -> int:
    """all-reduce ops inside the computations a while loop runs (its
    body and everything that body calls): what one trip of the outer
    group loop issues."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    bodies = {b for lines in comps.values() for ln in lines
              for b in re.findall(r"body=%?([\w.\-]+)", ln)}
    seen, stack = set(), list(bodies)
    while stack:
        c = stack.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for ln in comps[c]:
            for ref in re.findall(r"(?:body|calls|to_apply|"
                                  r"branch_computations)=(\{[^}]*\}|\S+)",
                                  ln):
                stack += re.findall(r"%?([\w.\-]+)", ref)
    return sum(len(re.findall(r" all-reduce(?:-start)?\(", ln))
               for c in seen for ln in comps[c])


def phase_sharded(m=EPSILON[0], n=EPSILON[1], seed=0, n_devices=4,
                  iterations=H):
    out = []
    for fam_name, make in (("lasso", dense_lasso), ("svm", dense_svm)):
        fam = FAMILIES[fam_name]
        problem = make(m, n, seed)
        mesh = jax.make_mesh((n_devices,), (fam.default_axes,))
        for s in (1, S):
            cfg = _cfg(s=s, iterations=iterations, track_objective=False)
            one = timed_solve(problem, cfg)
            t0 = time.perf_counter()
            res = api.solve(problem, cfg, backend="sharded", mesh=mesh)
            x = np.asarray(jax.block_until_ready(res.x))
            sharded_s = time.perf_counter() - t0
            hlo = api.lower_solve(fam_name, cfg, mesh, m, n).compile() \
                .as_text()
            x1 = np.asarray(one["x"], np.float64)
            dev = float(np.linalg.norm(x - x1) / max(np.linalg.norm(x1),
                                                     1e-30))
            out.append(dict(
                phase=f"sharded_{fam_name}", partition=fam.partition,
                devices=n_devices, shape=[m, n], s=s, mu=MU,
                iterations=iterations, deviation=dev, tolerance=SHARD_TOL,
                finite=bool(np.all(np.isfinite(x))),
                outer_iterations=cfg.outer_iterations,
                allreduces_per_outer=allreduces_per_outer(hlo),
                sharded_first_call_s=sharded_s,
                one_device_setup_s=one["setup_s"],
                one_device_solve_s=one["solve_s"]))
    return out


def sharded_failures(result):
    errs = []
    if not result["finite"]:
        errs.append("non-finite solution")
    if not result["deviation"] <= result["tolerance"]:
        errs.append(f"sharded-vs-one-device deviation "
                    f"{result['deviation']} exceeds {result['tolerance']}")
    if result["allreduces_per_outer"] != 1:
        errs.append(f"{result['allreduces_per_outer']} all-reduces per "
                    f"outer iteration, expected 1")
    return errs


# ---------------------------------------------------------------------------

def _emit(result, errs):
    result = dict(result, ok=not errs, errors=errs)
    print(json.dumps(result), flush=True)
    return not errs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              f"nothing run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2

    print(json.dumps({"compile_cache": enable_compile_cache()}), flush=True)

    ok = True
    if args.chips == 4:
        for r in phase_sharded(seed=args.seed, n_devices=4):
            ok &= _emit(r, sharded_failures(r))
    else:
        for phase in (phase_dense_lasso, phase_dense_svm, phase_sparse_svm,
                      phase_kernels):
            r = phase(seed=args.seed)
            ok &= _emit(r, phase_failures(r, on_chip=True))
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
