"""Measure the alpha-beta-gamma-kappa ``Machine`` parameters on the
current host.

The cost model (``repro.core.cost_model``) assigns a configuration the
time ``T = gamma F + beta W + alpha L + kappa I``. Its built-in machines
(``Machine.cray_xc30``, ``Machine.tpu_v5e_pod``) are paper-derived
constants; this module produces a ``Machine`` for the host we actually
run on, so ``best_s``-style sweeps stop answering for someone else's
hardware:

* **gamma** (s/flop) — timed square GEMMs at a couple of sizes; the
  flop rate of the dense Gram products that dominate F.
* **beta** (s/word, 8 B words) — timed Allreduce of a large vector:
  ``psum`` over a real mesh axis when more than one device is present,
  otherwise a memory-bound elementwise pass (the single-device proxy
  for moving one word through the reduction).
* **alpha** (s/message) — the time of the SAME reduction on a tiny
  (1-element) vector: pure launch/collective latency, the term SA
  trades against.
* **kappa** (s/inner-iteration) — the slope of a tiny pilot Lasso solve
  in H at negligible flop volume: per-iteration serial overhead that
  unrolling does NOT remove.

These are *priors*: ``repro.tune.calibrate`` refines all four by
fitting predicted to measured times over a pilot (s, mu) grid, which
absorbs constant factors the analytical counts drop.
"""
from __future__ import annotations

import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost_model import Machine

__all__ = ["measure_machine", "measure_gamma", "measure_alpha_beta",
           "measure_kappa", "time_best"]


def time_best(fn: Callable, repeats: int = 5) -> float:
    """Best-of-``repeats`` wall-clock seconds of ``fn()`` (after one
    warmup call, so compile time never lands in the measurement).
    Best-of suppresses scheduler noise, which one-shot timings on a
    shared CPU host drown in."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def measure_gamma(sizes=(256, 512), repeats: int = 5,
                  dtype=jnp.float32) -> float:
    """s/flop from timed n x n GEMMs (2 n^3 flops each); the larger
    size usually wins (amortized dispatch) — take the best rate."""
    best = float("inf")
    for n in sizes:
        a = jnp.ones((n, n), dtype)
        f = jax.jit(lambda x: x @ x)
        t = time_best(lambda: f(a), repeats)
        best = min(best, t / (2.0 * n ** 3))
    return best


def _reduce_fn(n: int):
    """A jitted reduction of an (n,) vector: a real psum over a 1D mesh
    when several devices are present, an elementwise memory pass (the
    single-device bandwidth proxy) otherwise."""
    devs = jax.devices()
    if len(devs) > 1:
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(devs), ("d",))
        fn = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, "d"),
                                   mesh=mesh, in_specs=P(), out_specs=P(),
                                   check_vma=False))
        return fn
    return jax.jit(lambda x: x * 1.0 + 1.0)


def measure_alpha_beta(big: int = 1 << 22, repeats: int = 5):
    """(alpha, beta): latency from a 1-element reduction, inverse
    bandwidth per 8 B word from the marginal cost of a ``big``-element
    one (latency subtracted)."""
    f = _reduce_fn(1)
    alpha = time_best(lambda: f(jnp.ones((1,), jnp.float32)), repeats)
    g = _reduce_fn(big)
    x = jnp.ones((big,), jnp.float32)
    t_big = time_best(lambda: g(x), repeats)
    words = big * 4 / 8.0                     # f32 elements -> 8 B words
    beta = max(t_big - alpha, 1e-12) / words
    return alpha, beta


def measure_kappa(h_small: int = 16, h_big: int = 96,
                  repeats: int = 3) -> float:
    """s/inner-iteration from the slope in H of a tiny (32 x 64, mu=1)
    Lasso solve — at that size the per-iteration flops are sub-us, so
    the slope IS the serial bookkeeping overhead kappa models."""
    from repro.core.lasso import bcd_lasso
    from repro.core.types import LassoProblem, SolverConfig

    rng = np.random.default_rng(0)
    A = rng.standard_normal((32, 64)).astype(np.float32)
    b = rng.standard_normal(32).astype(np.float32)

    def solve_time(H: int) -> float:
        cfg = SolverConfig(block_size=1, iterations=H, accelerated=False,
                           track_objective=False)
        fn = jax.jit(lambda a, bb: bcd_lasso(
            LassoProblem(A=a, b=bb, lam=0.1), cfg).x)
        return time_best(lambda: fn(A, b), repeats)

    slope = (solve_time(h_big) - solve_time(h_small)) / (h_big - h_small)
    return max(slope, 1e-9)


def measure_machine(name: Optional[str] = None, repeats: int = 5
                    ) -> Machine:
    """Measure all four parameters on this host (a few seconds)."""
    alpha, beta = measure_alpha_beta(repeats=repeats)
    gamma = measure_gamma(repeats=repeats)
    kappa = measure_kappa(repeats=max(repeats - 2, 1))
    if name is None:
        import socket
        name = f"{socket.gethostname()}-{jax.default_backend()}"
    return Machine(name=name, alpha=alpha, beta=beta, gamma=gamma,
                   kappa=kappa)
