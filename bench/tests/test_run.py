"""A whole run of each cell at a tiny size on the CPU (Pallas kernels in
interpret mode), past the harness's look for a chip: a sound run comes
out correct, and a run with the timed path broken underneath comes out
not correct, once for each fault the cell can have."""
import dataclasses
import importlib
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu

import run
from repro.core.types import SolverResult

# by module path: repro.core re-exports functions under some of these names
engine, lasso, sa_lasso, sa_svm = (
    importlib.import_module("repro.core." + m)
    for m in ("engine", "lasso", "sa_lasso", "sa_svm"))

ROOT = os.path.dirname(run.BENCH)
SIZES = {"epsilon-lasso": dict(m=1024, n=64),
         "rcv1-svm": dict(m=256, n=512, nnz=4000)}
# The svm_inner kernel is slow in interpret mode: fewer outer iterations.
SOLVER = {"rcv1-svm.sa16": {"iterations": 32}}
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    _CELLS = json.load(_f)["workloads"]
ONE_CHIP = [w["name"] for w in _CELLS if w["chips"] == 1]
FOUR_CHIP = [w["name"] for w in _CELLS if w["chips"] == 4]


def drive(capsys, workload, seed=2_147_483_701):
    config = run.load_cell(workload).config
    name = os.path.basename(config.__file__)[:-3]
    with pltpu.force_tpu_interpret_mode():
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "0.01"], require_tpu=False,
                      sizes=SIZES[name], solver=SOLVER.get(workload))
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    result = json.loads(lines[-1])
    assert list(result)[-1] == "checks"
    return result


def _frozen(res):
    """The state as it was before the first step: x = 0 and the
    objective where it starts."""
    return SolverResult(x=jnp.zeros_like(res.x),
                        objective=jnp.full_like(res.objective,
                                                res.objective[0]),
                        aux=res.aux)


def _altered(res):
    """One coordinate of the answer moved where it is produced."""
    x = res.x.at[0].add(0.05 * jnp.linalg.norm(res.x) + 1e-3)
    return SolverResult(x=x, objective=res.objective, aux=res.aux)


def _wrap(module, name, change, monkeypatch):
    orig = getattr(module, name)
    monkeypatch.setattr(module, name,
                        lambda *a, **k: change(orig(*a, **k)))


def fault_unchanged(workload, monkeypatch):
    if workload.endswith(".s1"):
        _wrap(lasso, "bcd_lasso", _frozen, monkeypatch)
    else:
        monkeypatch.setattr(
            engine, "run_grouped",
            lambda group, carry, H, s, dtype, start=0:
            (carry, jnp.zeros((H,), dtype)))


def fault_half_batch(workload, monkeypatch):
    """Half of the rows a reduction sums over left out, the rest
    doubled (the mean over the rest)."""
    if workload.startswith("rcv1"):
        prog = sa_svm._BDCD_PROGRAM

        def assemble(ctx, carry, idxs, s_grp):
            handle, _ = prog.assemble(ctx, carry, idxs, s_grp)
            cols, vals, nnb = handle
            half = vals.at[:, vals.shape[1] // 2:].set(0.0)
            return handle, 2.0 * ctx.gram((cols, half, nnb),
                                          carry[1][:, None])
        monkeypatch.setattr(sa_svm, "_BDCD_PROGRAM",
                            dataclasses.replace(prog, assemble=assemble))
    elif workload.endswith(".s1"):
        orig = lasso.col_block_ops

        def ops(A, cfg):
            block_gram, block_apply = orig(A, cfg)

            def half_gram(idx, vecs):
                Ah, _ = block_gram(idx, vecs)
                h = Ah.shape[0] // 2
                return Ah, 2.0 * Ah[:h].T @ jnp.concatenate(
                    [Ah[:h], vecs[:h]], axis=1)
            return half_gram, block_apply
        monkeypatch.setattr(lasso, "col_block_ops", ops)
    else:
        orig = sa_lasso.gram_local

        def half(Y, vecs, use_pallas=False):
            h = Y.shape[0] // 2
            return 2.0 * orig(Y[:h], vecs[:h], use_pallas)
        monkeypatch.setattr(sa_lasso, "gram_local", half)


def fault_altered(workload, monkeypatch):
    if workload.endswith(".s1"):
        _wrap(lasso, "bcd_lasso", _altered, monkeypatch)
    elif workload.startswith("rcv1"):
        _wrap(sa_svm, "run_program", _altered, monkeypatch)
    else:
        _wrap(sa_lasso, "run_program", _altered, monkeypatch)


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_sound_run_is_correct(workload, capsys):
    result = drive(capsys, workload)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) >= {"setup_s", "solve_s"}


@pytest.mark.parametrize("fault", [fault_unchanged, fault_half_batch,
                                   fault_altered])
@pytest.mark.parametrize("workload", ONE_CHIP)
def test_broken_run_is_not_correct(workload, fault, capsys, monkeypatch):
    fault(workload, monkeypatch)
    result = drive(capsys, workload)
    assert not result["correct"], result["checks"]


_FOUR = """
import json, sys
sys.path.insert(0, {bench!r})
import run
from jax.experimental.pallas import tpu as pltpu
from repro.core import linalg
run.CACHE_DIR = {cache!r}
if {fault!r} == "exchange":
    linalg.preduce = lambda x, axis_name: x
with pltpu.force_tpu_interpret_mode():
    run.main(["--workload", {workload!r}, "--seed", "2147483702",
              "--seconds", "0.01"], require_tpu=False, sizes=dict(m=1024, n=64))
"""


@pytest.mark.parametrize("fault,correct", [("none", True),
                                           ("exchange", False)])
@pytest.mark.parametrize("workload", FOUR_CHIP)
def test_four_devices(workload, fault, correct):
    """The four-chip cell on four forced CPU devices; leaving out the
    exchange between chips must come out not correct."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", _FOUR.format(bench=run.BENCH, fault=fault,
                                            cache=run.CACHE_DIR,
                                            workload=workload)],
        env=env, capture_output=True, text=True, timeout=600)
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["device"]["count"] == 4
    assert result["correct"] is correct, result["checks"]


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "epsilon-lasso.sa16",
         "--seed", "1", "--seconds", "1"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_and_prints_nothing():
    out = _cli(ROOT)
    assert out.returncode != 0 and out.stdout == ""
    assert "TPU" in out.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _cli(tmp_path)
    assert out.returncode != 0 and out.stdout == ""
