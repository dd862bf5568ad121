"""Pallas TPU kernel: the SVM s-step inner loop, entirely in VMEM.

Same TPU rethinking as ``repro.kernels.sa_inner``: the paper's
"redundantly execute the s inner iterations on every processor"
(Sec. III) becomes ONE kernel launch holding all replicated
O((s*mu)^2) state — the regularized block matrix G (linear Gram or
kernel block), the projections, labels, gathered duals and the growing
theta history — in VMEM, with zero intermediate HBM round-trips. Per
step: the t<j cross-term product against G, the clipped dual update
with the diagonal block's eigenvalue as step size, and the step's
dual-objective increment (a second product against G).

Layout: the lane-dense (1, P) rows of ``repro.kernels.common`` — step j
computes on every lane and commits block j's (``blk == j``); duals at
repeated rows are kept current by a running collision update; the s
diagonal-block eigenvalues come in precomputed (they depend on G only).

VMEM budget: the dominant resident is G at P^2 * 4 bytes; ops.py
rejects configurations whose (s*mu)^2 * 4 B exceeds the 8 MiB cap (half
of v5e's 16 MiB default scoped VMEM).

Single grid point — the loop is inherently sequential; these flops are
the SA trade's latency-free replicated work.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (block_max_eigs, lane_rows, nt_matvec,
                                  padded_width, running_collisions)


def _make_kernel(s: int, mu: int, gamma: float, nu: float):
    finite_nu = nu == nu and nu != float("inf")

    def _clip(x):
        lo = jnp.maximum(x, 0.0)
        return jnp.minimum(lo, nu) if finite_nu else lo

    def kernel(idx_s_ref, G_ref, blk_ref, idx_ref, proj_ref, b_ref,
               avals_ref, eig_ref, theta_ref, dual_ref):
        blk = blk_ref[...]
        idx = idx_ref[...]
        proj, b = proj_ref[...], b_ref[...]
        eig = eig_ref[...]

        def body(j, carry):
            th, dual, beta = carry
            sel = blk == j
            cross = nt_matvec(jnp.where(blk < j, b * th, 0.0), G_ref[...])
            g = b * (proj + cross) - 1.0 + gamma * beta
            gbar = jnp.abs(_clip(beta - g) - beta)
            theta = jnp.where(gbar != 0.0, _clip(beta - g / eig) - beta,
                              0.0)
            th = jnp.where(sel, theta, th)
            # dual increment theta^T g + 1/2 (b theta)^T G_jj (b theta)
            bt = jnp.where(sel, b * theta, 0.0)
            delta = jnp.sum(jnp.where(sel, theta * g, 0.0), axis=1,
                            keepdims=True) \
                + 0.5 * jnp.sum(nt_matvec(bt, G_ref[...]) * bt, axis=1,
                                keepdims=True)
            dual = jnp.where(sel, delta, dual)
            beta = running_collisions(beta, idx, idx_s_ref, th, j, mu)
            return th, dual, beta

        zero = jnp.zeros_like(proj)
        th, dual, _ = jax.lax.fori_loop(
            0, s, body, (zero, zero, avals_ref[...]))
        theta_ref[...] = th
        dual_ref[...] = dual

    return kernel


def svm_inner_pallas(G, proj, b_sel, a_vals, idx, *, gamma: float,
                     nu: float, power_iters: int = 32,
                     interpret: bool = False):
    """Run the s-step SVM inner loop in one kernel launch. All inputs are
    the replicated post-Allreduce quantities; see ref.py for shapes."""
    s, mu = proj.shape
    smu = s * mu
    P = padded_width(smu)
    G = G.astype(jnp.float32)
    Gp = jnp.pad(G, ((0, P - smu), (0, P - smu)))
    blk, idx_row, pr, b, av, eig = lane_rows(
        P, mu, idx, [proj, b_sel, a_vals,
                     jnp.repeat(block_max_eigs(G, s, mu, power_iters),
                                mu)])
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    theta, duals = pl.pallas_call(
        _make_kernel(s, mu, float(gamma), float(nu)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [vmem] * 7,
        out_specs=(vmem, vmem),
        out_shape=(jax.ShapeDtypeStruct((1, P), jnp.float32),
                   jax.ShapeDtypeStruct((1, P), jnp.float32)),
        interpret=interpret,
        name="svm_inner",
    )(idx.reshape(smu).astype(jnp.int32), Gp, blk, idx_row, pr, b, av,
      eig)
    return theta[0, :smu].reshape(s, mu), duals[0, :smu:mu]
