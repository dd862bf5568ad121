"""The dense column gather of the column-sampling solvers: a
feature-major copy of A, taken once per solve, whose rows are the
sampled columns (``repro.core.sparse_exec.col_take``), and the static
rule that decides when a solve takes it (``gather_layout``)."""
import importlib
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sfista import SFISTAProblem
from repro.core.types import LassoProblem, SolverConfig, SparseOperand

# by module path: repro.core re-exports functions under some of these names
lasso, sa_lasso, sfista, sparse_exec = (
    importlib.import_module("repro.core." + m)
    for m in ("lasso", "sa_lasso", "sfista", "sparse_exec"))

M, N = 96, 40
SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# solver: (function, problem class, SolverConfig fields)
SOLVERS = {
    "bcd_lasso": (lasso.bcd_lasso, LassoProblem, dict(s=1, block_size=4)),
    "acc_bcd_lasso": (lasso.acc_bcd_lasso, LassoProblem,
                      dict(s=1, block_size=4)),
    "sa_bcd_lasso": (sa_lasso.sa_bcd_lasso, LassoProblem,
                     dict(s=4, block_size=4)),
    "sa_acc_bcd_lasso": (sa_lasso.sa_acc_bcd_lasso, LassoProblem,
                         dict(s=4, block_size=4)),
    "sa_cd_lasso": (sa_lasso.sa_cd_lasso, LassoProblem,
                    dict(s=4, block_size=1)),
    "sa_acc_cd_lasso": (sa_lasso.sa_acc_cd_lasso, LassoProblem,
                        dict(s=4, block_size=1)),
    "sfista": (sfista.sfista, SFISTAProblem, dict(s=1, block_size=4)),
    "ca_sfista": (sfista.ca_sfista, SFISTAProblem, dict(s=4, block_size=4)),
}


def _data(seed=0):
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((M, N)).astype(np.float32)
    xt = np.zeros(N, np.float32)
    xt[:5] = 1.0
    b = (A @ xt + 0.1 * rng.standard_normal(M)).astype(np.float32)
    return A, b, 0.1 * float(np.abs(A.T @ b).max())


def _solve(name, layout, monkeypatch, iterations=24, **kw):
    """One solve with the gather forced to ``layout``: a device with a
    byte of memory holds no copy of A; one that reports none holds it."""
    fn, problem_cls, fields = SOLVERS[name]
    monkeypatch.setattr(sparse_exec, "device_bytes_limit",
                        lambda: 1 if layout == "row_major" else None)
    A, b, lam = _data()
    res = fn(problem_cls(A=A, b=b, lam=lam),
             SolverConfig(iterations=iterations, **fields), **kw)
    assert res.aux["gather_layout"] == layout
    return res


def _close(a, b):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=1e-6,
                               atol=1e-6 * np.abs(b).max())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k", [1, 8, 24])
def test_feature_major_take_is_the_columns_bitwise(dtype, k):
    A = jnp.asarray(np.random.default_rng(k).standard_normal((M, N)),
                    dtype)
    idx = jnp.asarray(np.random.default_rng(k + 1).integers(0, N, k),
                      jnp.int32)
    cfg = SolverConfig(iterations=8, s=1)
    assert sparse_exec.gather_layout(A, 8) == "feature_major"
    got = jax.jit(lambda A, idx: sparse_exec.col_take(A, cfg)(idx))(A, idx)
    assert got.dtype == A.dtype and got.shape == (k, M)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(A[:, idx].T, np.float32))


@pytest.mark.parametrize("name", SOLVERS)
def test_feature_major_solve_matches_row_major(name, monkeypatch):
    fm = _solve(name, "feature_major", monkeypatch)
    rm = _solve(name, "row_major", monkeypatch)
    _close(fm.x, rm.x)
    _close(fm.objective, rm.objective)


@pytest.mark.parametrize("name", SOLVERS)
def test_feature_major_resume_continues_the_sequence(name, monkeypatch):
    full = _solve(name, "feature_major", monkeypatch, iterations=24)
    first = _solve(name, "feature_major", monkeypatch, iterations=16)
    rest = _solve(name, "feature_major", monkeypatch, iterations=8,
                  state=first.aux["state"])
    _close(rest.x, full.x)
    _close(rest.objective, np.asarray(full.objective)[16:])


@pytest.mark.parametrize("name", SOLVERS)
def test_feature_major_warm_start_matches_row_major(name, monkeypatch):
    x0 = np.asarray(_solve(name, "row_major", monkeypatch).x)
    fm = _solve(name, "feature_major", monkeypatch, iterations=8, x0=x0)
    rm = _solve(name, "row_major", monkeypatch, iterations=8, x0=x0)
    _close(fm.x, rm.x)
    _close(fm.objective, rm.objective)


@pytest.mark.parametrize("name,iterations,layout", [
    ("bcd_lasso", 1, "row_major"),          # one gather
    ("bcd_lasso", 2, "feature_major"),
    ("sa_bcd_lasso", 4, "row_major"),       # one group of s = 4
    ("sa_bcd_lasso", 5, "feature_major"),   # a group and a tail
    ("ca_sfista", 3, "row_major"),          # only the tail group
])
def test_one_gather_keeps_row_major(name, iterations, layout):
    fn, problem_cls, fields = SOLVERS[name]
    A, b, lam = _data()
    res = fn(problem_cls(A=A, b=b, lam=lam),
             SolverConfig(iterations=iterations, **fields))
    assert res.aux["gather_layout"] == layout


@pytest.mark.parametrize("limit,layout", [
    (2 * M * N * 4 - 1, "row_major"),       # a second A does not fit
    (2 * M * N * 4, "feature_major"),       # it just fits
    (None, "feature_major"),                # the backend reports none
])
def test_memory_limit_decides(limit, layout, monkeypatch):
    monkeypatch.setattr(sparse_exec, "device_bytes_limit", lambda: limit)
    A, b, lam = _data()
    res = sa_lasso.sa_bcd_lasso(LassoProblem(A=A, b=b, lam=lam),
                                SolverConfig(iterations=16, s=4))
    assert res.aux["gather_layout"] == layout


@pytest.mark.parametrize("name", ["bcd_lasso", "sa_bcd_lasso",
                                  "ca_sfista"])
def test_sparse_operand_has_no_gather_layout(name):
    fn, problem_cls, fields = SOLVERS[name]
    A, b, lam = _data()
    A[np.abs(A) < 1.0] = 0.0
    res = fn(problem_cls(A=SparseOperand.from_dense(A), b=b, lam=lam),
             SolverConfig(iterations=16, **fields))
    assert "gather_layout" not in res.aux
    assert "spmm_impl" in res.aux


def test_sharded_feature_major_matches_one_device():
    """Four forced host devices, A's rows sharded: each shard takes its
    own feature-major copy, the label comes out of the sharded solve,
    and the sharded solve matches one device."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro import api
from repro.api import LassoProblem, SolverConfig
rng = np.random.default_rng(5)
A = rng.standard_normal((203, 40)).astype(np.float32)
xt = np.zeros(40, np.float32); xt[:5] = 1.0
b = (A @ xt + 0.1 * rng.standard_normal(203)).astype(np.float32)
prob = LassoProblem(A=A, b=b, lam=0.1 * float(np.abs(A.T @ b).max()))
mesh = jax.make_mesh((4,), ("data",))
for s in (1, 4):
    cfg = SolverConfig(block_size=4, iterations=32, s=s)
    one = api.solve(prob, cfg)
    four = api.solve(prob, cfg, backend="sharded", mesh=mesh)
    assert one.aux["gather_layout"] == "feature_major"
    assert four.aux["gather_layout"] == "feature_major", four.aux
    o1, o4 = np.asarray(one.objective), np.asarray(four.objective)
    assert np.max(np.abs(o1 - o4) / np.abs(o1)) < 1e-4, s
print("SHARDED_OK")
"""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_OK" in out.stdout
