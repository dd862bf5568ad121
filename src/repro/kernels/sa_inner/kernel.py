"""Pallas TPU kernel: the SA accelerated inner loop, entirely in VMEM.

TPU-native rethinking of the paper's "redundantly execute the s inner
iterations on every processor" (Sec. III): on MPI every rank runs scalar
code between HBM-resident vectors; on TPU we place the replicated
O((s*mu)^2) state — the Gram matrix, projections, theta schedule and the
growing dz history — in VMEM once and run all s dependent steps inside a
single kernel launch with zero intermediate HBM round-trips.

Layout (what Mosaic accepts): every per-coordinate quantity is ONE
lane-dense (1, P) row, P = s*mu rounded up to a multiple of 128, where
lane l holds coordinate l of the flattened (s, mu) group. Step j works
on all lanes at once and commits only block j's lanes (``blk == j``),
so no step slices lanes or sublanes at a dynamic offset. The t<j cross
term is one (1, P) x (P, P)^T MXU product against the resident G; the
collision-corrected z is a running row that each step's dz updates at
every lane sharing one of its coordinates (indices read as scalars from
SMEM). The step sizes' diagonal-block eigenvalues depend on G only, so
the wrapper computes all s of them before the launch and hands them in
lane-replicated.

VMEM budget: the dominant resident is G at P^2 * 4 bytes; ops.py
rejects configurations whose (s*mu)^2 * 4 B exceeds the 8 MiB cap (half
of v5e's 16 MiB default scoped VMEM), which still admits e.g. s=128,
mu=8 or s=1024, mu=1 — the paper's largest settings.

Single grid point (the loop is inherently sequential — that is the SA
trade: these flops are latency-free replicated work).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (block_max_eigs, lane_rows, nt_matvec,
                                  padded_width, running_collisions)

_F32_TINY = float(jnp.finfo(jnp.float32).tiny)


def _make_kernel(s: int, mu: int, q: float, lam1: float, lam2: float):

    def kernel(th_ref, idx_s_ref, G_ref, blk_ref, idx_ref, coefU_ref,
               yproj_ref, zproj_ref, zvals_ref, eig_ref, dz_ref, eta_ref):
        blk = blk_ref[...]
        idx = idx_ref[...]
        coefU = coefU_ref[...]
        yproj, zproj = yproj_ref[...], zproj_ref[...]
        eig = eig_ref[...]

        def body(j, carry):
            dz, eta_out, zcur = carry
            thp = th_ref[j]
            sel = blk == j
            # Eq. (3): coefficient (theta_{j-1}^2 * coefU_t - 1) on
            # G[j, t] dz_t over the earlier blocks t < j.
            v = jnp.where(blk < j, (thp * thp * coefU - 1.0) * dz, 0.0)
            rj = thp * thp * yproj + zproj - nt_matvec(v, G_ref[...])
            # same floor as linalg.floor_eig at the kernel's f32 compute
            # dtype: an all-zero block otherwise yields eta = inf and
            # inf * 0 = NaN against its zero projection.
            eta = 1.0 / jnp.maximum(q * thp * eig, _F32_TINY)
            g = zcur - eta * rj
            shrunk = jnp.sign(g) * jnp.maximum(jnp.abs(g) - lam1 * eta, 0.0)
            dz_j = shrunk / (1.0 + 2.0 * eta * lam2) - zcur
            dz = jnp.where(sel, dz_j, dz)
            eta_out = jnp.where(sel, eta, eta_out)
            zcur = running_collisions(zcur, idx, idx_s_ref, dz, j, mu)
            return dz, eta_out, zcur

        zero = jnp.zeros_like(yproj)
        dz, eta_out, _ = jax.lax.fori_loop(
            0, s, body, (zero, zero, zvals_ref[...]))
        dz_ref[...] = dz
        eta_ref[...] = eta_out

    return kernel


def sa_inner_pallas(G, y_proj, z_proj, z_vals, idx, th_prev, coefU,
                    *, q: float, lam1: float, lam2: float = 0.0,
                    power_iters: int = 32, interpret: bool = False):
    """Run the s-step inner loop in one kernel. All inputs are the
    replicated post-Allreduce quantities; see ref.py for shapes."""
    s, mu = y_proj.shape
    smu = s * mu
    P = padded_width(smu)
    G = G.astype(jnp.float32)
    Gp = jnp.pad(G, ((0, P - smu), (0, P - smu)))
    blk, idx_row, coefU_row, yp, zp, zv, eig = lane_rows(
        P, mu, idx, [jnp.repeat(coefU.reshape(s), mu), y_proj, z_proj,
                     z_vals, jnp.repeat(block_max_eigs(G, s, mu,
                                                       power_iters), mu)])
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    dz, etas = pl.pallas_call(
        _make_kernel(s, mu, float(q), float(lam1), float(lam2)),
        in_specs=[smem, smem] + [vmem] * 8,
        out_specs=(vmem, vmem),
        out_shape=(jax.ShapeDtypeStruct((1, P), jnp.float32),
                   jax.ShapeDtypeStruct((1, P), jnp.float32)),
        interpret=interpret,
        name="sa_inner",
    )(th_prev.reshape(s).astype(jnp.float32),
      idx.reshape(smu).astype(jnp.int32), Gp, blk, idx_row, coefU_row,
      yp, zp, zv, eig)
    return dz[0, :smu].reshape(s, mu), etas[0, :smu:mu]
