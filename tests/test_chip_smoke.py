"""``chip_smoke.py``'s phases at a tiny size on the CPU, with the Pallas
kernels in TPU interpret mode: the control flow, the comparisons and
the tolerances, without a chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest
from jax.experimental.pallas import tpu as pltpu

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = dict(iterations=32)


@pytest.mark.parametrize("phase,kw", [
    (chip_smoke.phase_dense_lasso, dict(m=256, n=64)),
    (chip_smoke.phase_dense_svm, dict(m=256, n=32)),
    (chip_smoke.phase_sparse_svm, dict(m=128, n=96, nnz=900)),
])
def test_solver_phase_within_tolerance(phase, kw):
    with pltpu.force_tpu_interpret_mode():
        r = phase(seed=0, **kw, **TINY)
    assert r["finite"] and r["objective"]["last"] != 0.0
    assert r["deviation"] <= r["tolerance"] == chip_smoke.SA_TOL
    assert "pallas" in r["impl"].values()
    assert chip_smoke.phase_failures(r, on_chip=False) == []
    # interpret mode leaves no kernel in the HLO: on the chip, a seam
    # labelled "pallas" without one fails the phase.
    assert not r["tpu_custom_call"]
    assert any("tpu_custom_call" in e
               for e in chip_smoke.phase_failures(r, on_chip=True))


def test_sparse_phase_reports_a_guarded_seam():
    """A seam the VMEM guard sent to the jnp reference is labelled
    "ref" and named in the phase's note."""
    with pltpu.force_tpu_interpret_mode():
        r = chip_smoke.phase_sparse_svm(m=64, n=40_000, nnz=400, seed=1,
                                        **TINY)
    assert r["impl"]["spmm"] == "ref" and r["impl"]["inner"] == "pallas"
    assert "phase d runs that kernel" in r["note"]
    assert chip_smoke.phase_failures(r, on_chip=False) == []


def test_kernel_parity_phase():
    with pltpu.force_tpu_interpret_mode():
        r = chip_smoke.phase_kernels(seed=0, gram_m=256,
                                     spmm_shape=(16, 64, 9), spmm_row_nnz=6)
    assert set(r["kernels"]) == {"gram", "spmm", "sa_inner", "svm_inner"}
    assert chip_smoke.phase_failures(r, on_chip=False) == []


def test_deviation_over_tolerance_fails():
    r = dict(finite=True, deviation=2 * chip_smoke.SA_TOL,
             tolerance=chip_smoke.SA_TOL, impl={"inner": "ref"},
             tpu_custom_call=False)
    assert chip_smoke.phase_failures(r, on_chip=True)


def test_allreduces_per_outer_reads_loop_bodies():
    hlo = """HloModule m

%sum (a: f32[], b: f32[]) -> f32[] {
  ROOT %add = f32[] add(f32[] %a, f32[] %b)
}

%inner (p: f32[8]) -> f32[8] {
  ROOT %ar = f32[8] all-reduce(f32[8] %p), to_apply=%sum
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %c = f32[8] call(f32[8] %x), to_apply=%inner
  ROOT %t2 = (s32[], f32[8]) tuple(s32[] %i, f32[8] %c)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %w = (s32[], f32[8]) while((s32[], f32[8]) %t), condition=%cond, body=%body
  ROOT %ar0 = f32[8] all-reduce(f32[8] %x), to_apply=%sum
}
"""
    assert chip_smoke.allreduces_per_outer(hlo) == 1


def test_no_tpu_means_no_result(tmp_path):
    """On the CPU the script exits non-zero before any phase and prints
    nothing on standard output; alone in a directory it fails too."""
    for cwd, script in ((ROOT, os.path.join(ROOT, "chip_smoke.py")),
                        (tmp_path, shutil.copy(
                            os.path.join(ROOT, "chip_smoke.py"),
                            tmp_path))):
        out = subprocess.run([sys.executable, script], cwd=cwd,
                             capture_output=True, text=True, timeout=300,
                             env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert out.returncode != 0
        assert out.stdout == ""


def test_sharded_phase_on_forced_host_devices():
    """The ``--chips 4`` phase on 4 forced CPU devices (a child process:
    the device count is fixed before jax imports): the sharded solves
    match the one-device ones and issue one all-reduce per outer
    iteration."""
    code = """
import json
import chip_smoke
from jax.experimental.pallas import tpu as pltpu
with pltpu.force_tpu_interpret_mode():
    for r in chip_smoke.phase_sharded(m=256, n=64, n_devices=4,
                                      iterations=32):
        print(json.dumps([r["phase"], r["s"], r["allreduces_per_outer"],
                          chip_smoke.sharded_failures(r)]))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rows = [json.loads(line) for line in out.stdout.splitlines()]
    assert [(p, s) for p, s, _, _ in rows] == [
        ("sharded_lasso", 1), ("sharded_lasso", 16),
        ("sharded_svm", 1), ("sharded_svm", 16)]
    assert all(n == 1 and not errs for _, _, n, errs in rows)
