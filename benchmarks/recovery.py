"""Elastic recovery cost vs checkpoint interval. Writes
``results/perf/recovery.json`` plus the usual CSV rows.

    PYTHONPATH=src python -m benchmarks.recovery [--smoke]

For each checkpoint interval the same Lasso solve runs twice through
``repro.runtime.solve_elastic`` on a 4-device mesh: once undisturbed
(baseline) and once with one host killed mid-run. The recovery cost is

  * ``restore_seconds``        — checkpoint read + state rebuild alone;
  * ``overhead_seconds``       — disturbed minus baseline wall-clock:
                                 restore + smaller-mesh recompile + the
                                 rolled-back iterations replayed on 3
                                 hosts;
  * ``rolled_back_iterations`` — failure step minus resumed iteration:
                                 the work the failure destroyed. Grows
                                 with the interval — sparse checkpoints
                                 are cheap until a host dies.

The measurement runs in a child process on 4 forced host CPU devices
(the flag must be set before jax imports; ``JAX_PLATFORMS=cpu`` keeps
the child off any accelerator). The parent never imports jax, so it
holds no chip the child could need.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

from benchmarks.common import emit

ROOT = os.path.join(os.path.dirname(__file__), "..")
OUT_PATH = os.path.join(ROOT, "results", "perf", "recovery.json")
_SUBPROC_FLAG = "_REPRO_RECOVERY_SUBPROC"


def _measure(smoke: bool) -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.api import LassoProblem, SolverConfig
    from repro.runtime import ElasticConfig, FailureInjector, solve_elastic

    n_dev = len(jax.devices())
    if n_dev < 2:
        raise RuntimeError(
            "recovery benchmark needs >= 2 devices; set XLA_FLAGS="
            "--xla_force_host_platform_device_count=4 before jax imports")

    rng = np.random.default_rng(3)
    m, n = (40, 64) if smoke else (120, 256)
    H = 24 if smoke else 96
    A = rng.standard_normal((m, n)).astype(np.float32)
    b = rng.standard_normal(m).astype(np.float32)
    lam = 0.1 * float(np.abs(A.T @ b).max())
    prob = LassoProblem(A=jnp.asarray(A), b=jnp.asarray(b), lam=lam)
    cfg = SolverConfig(block_size=4, s=2, iterations=H,
                       track_objective=False)
    intervals = (1, 4) if smoke else (1, 2, 4, 8)
    # one step BEFORE a boundary of the coarsest interval (step = -1 mod
    # max_seg_len), so the rolled-back work actually scales with the
    # interval: 1, 3, 7, 15 iterations here. H//2+1 would sit right
    # after a boundary common to EVERY interval and report 1 across the
    # board.
    max_seg = max(intervals) * cfg.s
    fail_step = (H // 2 // max_seg + 1) * max_seg - 1

    def run(ck_every, failures):
        with tempfile.TemporaryDirectory() as d:
            inj = FailureInjector(failures=dict(failures)) if failures \
                else None
            t0 = time.perf_counter()
            res = solve_elastic(
                prob, cfg,
                elastic=ElasticConfig(checkpoint_dir=d,
                                      checkpoint_every=ck_every,
                                      keep=4),
                injector=inj)
            jax.block_until_ready(res.x)
            return time.perf_counter() - t0, res.aux["elastic"]

    entries = []
    for ck in intervals:
        # warm the segment compiles for BOTH mesh sizes (4-host and the
        # post-failure 3-host) so the timed delta is restore + replay,
        # not jit compilation.
        run(ck, None)
        run(ck, {1: [1]})
        base_s, _ = run(ck, None)
        dist_s, report = run(ck, {fail_step: [1]})
        rec = report["recoveries"][0]
        rolled_back = fail_step - rec["resumed_iteration"]
        entry = {
            "checkpoint_every": ck,
            "failure_step": fail_step,
            "baseline_seconds": base_s,
            "disturbed_seconds": dist_s,
            "overhead_seconds": dist_s - base_s,
            "restore_seconds": rec["restore_seconds"],
            "resumed_iteration": rec["resumed_iteration"],
            "rolled_back_iterations": rolled_back,
            "n_hosts_final": rec["n_hosts"],
        }
        entries.append(entry)
        emit(f"recovery/ck{ck}", (dist_s - base_s) * 1e6,
             f"restore={rec['restore_seconds']:.4f}s "
             f"rolled_back={rolled_back}it "
             f"hosts={n_dev}->{rec['n_hosts']}")

    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as fh:
        json.dump({"devices": n_dev, "iterations": H, "s": cfg.s,
                   "failure_step": fail_step, "smoke": smoke,
                   "sweep": entries}, fh, indent=1)
    print(f"# wrote {os.path.relpath(OUT_PATH, ROOT)}", flush=True)


def main(smoke: bool = False) -> None:
    if os.environ.get(_SUBPROC_FLAG) == "1":
        _measure(smoke)
        return
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env[_SUBPROC_FLAG] = "1"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(ROOT, "src"),
                    env.get("PYTHONPATH", "")) if p)
    cmd = [sys.executable, "-m", "benchmarks.recovery"]
    if smoke:
        cmd.append("--smoke")
    out = subprocess.run(cmd, env=env, cwd=os.path.abspath(ROOT),
                         capture_output=True, text=True)
    sys.stdout.write(out.stdout)
    if out.returncode != 0:
        sys.stderr.write(out.stderr[-3000:])
        raise RuntimeError("recovery subprocess failed")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    main(smoke=args.smoke)
