"""Plain block dual coordinate descent for the linear SVM (Hsieh et al.,
2008, in blocks), hinge (L1) or squared hinge (L2) loss.

The reference the SVM cells are judged by: one block of rows at a time,
in plain ``jax.numpy`` at the dtype it is given (float32 matmuls at
"highest"), importing nothing of the program under test. A is held as
its rows' nonzeros, padded to the longest row; a block's rows are made
dense before they are used. The method's definition is shared with the program: the block at
iteration h is the top ``block_size`` of ``uniform(fold_in(key(seed),
h), (m,))``; the step is the projected Newton-like step with
1 / lambda_max of the block's Gram matrix (``power_iters`` power steps
from the normalised ones vector); the dual objective is tracked by
its exact increments.

    min_alpha  1/2 alpha^T Q alpha - e^T alpha,  0 <= alpha <= nu,
    Q = diag(b) (A A^T + gamma I) diag(b),  x = A^T (b * alpha)
"""
import jax
import jax.numpy as jnp
import numpy as np


def _power_max_eig(G, iters):
    mu = G.shape[0]
    if mu == 1:
        return G[0, 0]
    v = jnp.ones((mu,), G.dtype) / jnp.sqrt(jnp.asarray(mu, G.dtype))

    def body(v, _):
        w = G @ v
        return w / jnp.maximum(jnp.linalg.norm(w), 1e-30), None

    v, _ = jax.lax.scan(body, v, None, length=iters)
    return v @ (G @ v)


def solve(data, solver, seed, dtype=jnp.float32, loss="l1"):
    """(x, dual objective after each iteration) of
    ``solver["iterations"]`` block steps from alpha = 0. ``data`` holds
    the COO triplets rows, cols, vals, the labels b, the shape and lam."""
    mu = int(solver["block_size"])
    H = int(solver["iterations"])
    iters = int(solver.get("power_iters", 32))
    lam = float(data["lam"])
    gamma, nu = (0.0, lam) if loss == "l1" else (0.5 / lam, float("inf"))
    m, n = data["shape"]
    precision = "highest" if jnp.dtype(dtype) == jnp.float32 else None

    # rows' nonzeros, padded with zeros (column 0) to the longest row, or
    # to the configuration's fixed width, so that every seed's reference
    # is the same program
    rows, cols, vals = (np.asarray(data[k]) for k in ("rows", "cols", "vals"))
    order = np.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    counts = np.bincount(rows, minlength=m)
    pos = np.arange(rows.size) - (np.cumsum(counts) - counts)[rows]
    width = max(int(counts.max()), (data.get("widths") or (0,))[0] or 0, 1)
    row_cols = np.zeros((m, width), np.int32)
    row_vals = np.zeros(row_cols.shape, np.float32)
    row_cols[rows, pos], row_vals[rows, pos] = cols, vals

    @jax.jit
    def run(row_cols, row_vals, b):
        row_vals = row_vals.astype(dtype)
        b = b.astype(dtype)
        key = jax.random.key(seed)
        eye = jnp.eye(mu, dtype=dtype)
        block = jnp.arange(mu)[:, None]

        def step(carry, h):
            alpha, x, dual = carry
            idx = jax.lax.top_k(jax.random.uniform(
                jax.random.fold_in(key, h), (m,)), mu)[1]
            Y = jnp.zeros((mu, n), dtype).at[block, row_cols[idx]].add(
                row_vals[idx])                         # (mu, n) dense
            G = Y @ Y.T + gamma * eye
            b_B, a_B = b[idx], alpha[idx]
            g = b_B * (Y @ x) - 1.0 + gamma * a_B
            v = _power_max_eig(G, iters)
            gbar = jnp.abs(jnp.clip(a_B - g, 0.0, nu) - a_B)
            theta = jnp.where(gbar != 0.0,
                              jnp.clip(a_B - g / v, 0.0, nu) - a_B, 0.0)
            alpha = alpha.at[idx].add(theta)
            bt = b_B * theta
            x = x + bt @ Y
            dual = dual + jnp.sum(theta * g) + 0.5 * bt @ (G @ bt)
            return (alpha, x, dual), dual

        carry = (jnp.zeros((m,), dtype), jnp.zeros((n,), dtype),
                 jnp.zeros((), dtype))
        (_, x, _), objs = jax.lax.scan(step, carry, jnp.arange(1, H + 1))
        return x, objs

    with jax.default_matmul_precision(precision):
        return run(jnp.asarray(row_cols), jnp.asarray(row_vals),
                   jnp.asarray(data["b"]))
