"""Helpers shared by the fused inner-loop Pallas kernels (``sa_inner``,
``svm_inner``): the lane-dense (1, P) layout they run in, the in-kernel
products and collision updates, and the diagonal-block eigenvalues the
wrappers compute before the launch."""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128


def padded_width(n: int) -> int:
    """n rounded up to whole 128-lane vregs."""
    return -(-n // LANES) * LANES


def lane_rows(P: int, mu: int, idx, vecs):
    """The kernels' operands as (1, P) lane rows: the block id of every
    lane (``lane // mu`` — at least s on the padded lanes, so no step
    ever selects them), the sampled indices (-1 on padded lanes, so they
    never collide) and each of ``vecs`` flattened to f32, zero-padded."""
    smu = idx.size
    blk = (jnp.arange(P, dtype=jnp.int32) // mu).reshape(1, P)
    idx_row = jnp.pad(idx.reshape(1, smu).astype(jnp.int32),
                      ((0, 0), (0, P - smu)), constant_values=-1)
    rows = [jnp.pad(v.reshape(1, smu).astype(jnp.float32),
                    ((0, 0), (0, P - smu))) for v in vecs]
    return [blk, idx_row] + rows


def nt_matvec(v, G):
    """(1, P) row v against the rows of G: out[p] = sum_k G[p, k] v[k]
    — the row-block orientation the jnp references contract."""
    return jax.lax.dot_general(v, G, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def running_collisions(cur, idx_row, idx_smem, delta, j, mu: int):
    """Add block j's steps ``delta`` to every lane of ``cur`` whose
    sampled index equals one of block j's: the running form of the
    references' eq-matrix gather over the earlier blocks t < j (a lane
    of a later block that repeats a coordinate sees that coordinate's
    updated value). Block j's indices are scalars read from SMEM, its
    steps masked cross-lane sums — no dynamic lane indexing."""
    lane = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 1)
    for kk in range(mu):
        k = j * mu + kk
        d_k = jnp.sum(jnp.where(lane == k, delta, 0.0), axis=1,
                      keepdims=True)
        cur = cur + jnp.where(idx_row == idx_smem[k], d_k, 0.0)
    return cur


def power_iter_max_eig(Gjj, iters: int):
    """Largest eigenvalue of a (mu, mu) PSD block via fixed-count power
    iteration, row-vector form."""
    mu = Gjj.shape[0]
    v = jnp.full((1, mu), 1.0 / jnp.sqrt(jnp.float32(mu)), jnp.float32)

    def body(_, v):
        w = jnp.dot(v, Gjj, preferred_element_type=jnp.float32)
        nrm = jnp.sqrt(jnp.sum(w * w))
        return w / jnp.maximum(nrm, 1e-30)

    v = jax.lax.fori_loop(0, iters, body, v)
    return jnp.sum(jnp.dot(v, Gjj, preferred_element_type=jnp.float32) * v) \
        / jnp.maximum(jnp.sum(v * v), 1e-30)


def block_max_eigs(G, s: int, mu: int, iters: int):
    """(s,) largest eigenvalue of each (mu, mu) diagonal block of the
    (s*mu, s*mu) G. They depend on G only, not on the inner loop's
    state, so all s run as one batched call before the kernel. mu = 1:
    the (1, 1) block IS the eigenvalue."""
    if mu == 1:
        return jnp.diagonal(G)
    G4 = G.reshape(s, mu, s, mu)
    diag = jnp.arange(s)
    return jax.vmap(lambda B: power_iter_max_eig(B, iters))(
        G4[diag, :, diag, :])
